"""Kernel-level decode microbenchmark: fused vs gather KV datapaths.

The serving decode tick is bandwidth-bound: its cost is the bytes of KV
history dragged through HBM per emitted token. This bench sweeps every
{variant} x {kv_dtype} x {layout} cell of the decode registry and, for
each, times the **gather** datapath (materialize a contiguous, dequantized
fp32 copy of the history, then attend — the ``gather_xla``/``xla_q``
backends) against the **fused** datapath (the Pallas flash-decode kernels
of DESIGN.md §9: in-kernel block-table indexing, in-register dequant — the
``pallas``/``pallas_q`` backends), and reports two byte metrics:

  * ``analytic_bytes_per_ctx_token`` — the datapath's *designed* HBM
    traffic per token of context per decode step, from the operand layouts
    (see ``analytic_bytes_per_ctx_token`` below). This is the
    hardware-relevant number and the CI regression gate: the fused paged
    path must stay at/below the gather path, and int8-paged fused must be
    <= 40% of int8-paged gather (ISSUE-4 acceptance; at D=Dv=64 the model
    gives ~12% fused vs gather at int8-paged, ~33% at fp32-paged).
  * ``xla_cost_bytes_per_step`` — XLA's own cost-model "bytes accessed"
    for the compiled step, when available. On CPU the Pallas kernels run
    in *interpret mode*, so this measured number (and the tokens/s column)
    describes the CPU software proxy, not the TPU target — interpret-mode
    emulation makes the fused path slower in wall-clock here even though
    it moves strictly fewer bytes; the analytic column is the metric that
    transfers.

Emits ``BENCH_decode.json`` next to the repo root (schema:
benchmarks/README.md) — the kernel-level perf trajectory tracked across
PRs alongside the engine-level BENCH_serve.json.

  PYTHONPATH=src python benchmarks/decode_microbench.py            # 4k ctx
  PYTHONPATH=src python benchmarks/decode_microbench.py --smoke    # CI mode
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.core.attention  # noqa: F401 — registers built-in backends
import repro.kernels.kvquant  # noqa: F401 — registers the _q backends
from repro.kernels.paged import slot_rows
from repro.kernels.registry import (
    AttentionSpec,
    dispatch_decode,
    dispatch_paged_decode,
    resolved_backends,
)
# the analytic cost model lives in repro.kernels.costs since DESIGN.md §12;
# re-exported here so existing callers keep their import path
from repro.kernels.costs import analytic_bytes_per_ctx_token  # noqa: F401
from repro.numerics.quant import QuantKV, quantize_kv

INT8_PAGED_MAX_RATIO = 0.40  # ISSUE-4 acceptance bar (fused/gather, analytic)


def _xla_cost_bytes(fn, *args):
    try:
        ca = jax.jit(fn).lower(*args).compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        return float(ca["bytes accessed"])
    except Exception:
        return None


def _time_step(fn, args, *, reps):
    out = fn(*args)  # warmup / compile
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / reps


def bench_cell(rng, *, layout, kv_dtype, variant, path, B, H, Hkv, D, ctx,
               page_size, reps):
    group = H // Hkv
    assert group * Hkv == H
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    lens = jnp.asarray([ctx - (i * 13) % (ctx // 2) for i in range(B)],
                       jnp.int32)
    quant = kv_dtype != "fp32"

    if layout == "contiguous":
        kc = jnp.asarray(rng.standard_normal((B, Hkv, ctx, D)), jnp.float32)
        vc = jnp.asarray(rng.standard_normal((B, Hkv, ctx, D)), jnp.float32)
        if quant:
            kq, vq = quantize_kv(kc, kv_dtype), quantize_kv(vc, kv_dtype)
            kc = QuantKV(kq.codes, kq.scale)
            vc = QuantKV(vq.codes, vq.scale)
        spec = AttentionSpec(
            variant=variant, kv_dtype=kv_dtype,
            decode_impl="xla" if path == "gather" else "pallas")

        def fn(q, kc, vc, lens):
            return dispatch_decode(spec, q, kc, vc, lens)

        args = (q, kc, vc, lens)
        impl = spec.resolved_decode_impl()
    else:
        max_blocks = -(-ctx // page_size)
        nblk = B * max_blocks + 2
        pool_tokens = nblk * page_size
        kp = jnp.asarray(rng.standard_normal((pool_tokens, Hkv, D)),
                         jnp.float32)
        vp = jnp.asarray(rng.standard_normal((pool_tokens, Hkv, D)),
                         jnp.float32)
        if quant:
            kq, vq = quantize_kv(kp, kv_dtype), quantize_kv(vp, kv_dtype)
            kp = QuantKV(kq.codes, kq.scale)
            vp = QuantKV(vq.codes, vq.scale)
        perm = rng.permutation(nblk)  # shuffled physical layout
        bt = jnp.asarray(
            np.stack([perm[i * max_blocks:(i + 1) * max_blocks]
                      for i in range(B)]).astype(np.int32))
        rows = slot_rows(bt, page_size)
        spec = AttentionSpec(
            variant=variant, kv_dtype=kv_dtype,
            paged_impl="gather_xla" if path == "gather" else "pallas")

        if path == "gather":
            def fn(q, kp, vp, rows, lens):
                return dispatch_paged_decode(spec, q, kp, vp, rows, lens)
            args = (q, kp, vp, rows, lens)
        else:
            def fn(q, kp, vp, rows, lens, bt):
                return dispatch_paged_decode(
                    spec, q, kp, vp, rows, lens, block_tables=bt,
                    page_size=page_size)
            args = (q, kp, vp, rows, lens, bt)
        impl = spec.resolved_paged_impl()

    # the analytic-bytes gate below is formula-based, so it can only defend
    # the datapath if the cell really dispatched the backend the formula
    # models — pin the resolved name (a registry regression that re-points
    # "fused" at gather math must fail here, not pass silently)
    expected = {"gather": "xla", "fused": "pallas"}[path]
    if quant:
        expected += "_q"
    if layout == "paged" and path == "gather":
        expected = "gather_" + expected
    assert impl == expected, (
        f"{layout}/{kv_dtype}/{path} resolved to backend {impl!r}, "
        f"expected {expected!r}")
    if path == "fused":
        kind = "paged decode" if layout == "paged" else "decode"
        row = next(r for r in resolved_backends(spec, paged=layout == "paged")
                   if r["kind"] == kind)
        assert not row["fallback"], (
            f"{layout}/{kv_dtype}/fused: {impl!r} is registered as a "
            f"fallback onto {row['resolved']!r} — the fused datapath this "
            f"bench claims to measure no longer exists")

    sec = _time_step(jax.jit(fn), args, reps=reps)
    return {
        "layout": layout,
        "kv_dtype": kv_dtype,
        "variant": variant,
        "path": path,
        "impl": impl,
        "context": ctx,
        "ms_per_step": sec * 1e3,
        "decode_tok_per_s": B / sec,
        "analytic_bytes_per_ctx_token": analytic_bytes_per_ctx_token(
            layout, kv_dtype, path, Hkv=Hkv, D=D, Dv=D,
            page_size=page_size),
        "xla_cost_bytes_per_step": _xla_cost_bytes(fn, *args),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--ctx", type=int, default=4096,
                    help="context length (tokens of resident KV history)")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--kv-heads", type=int, default=2)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--page-size", type=int, default=64,
                    help="KV block size for the paged cells (64 keeps the "
                         "CPU interpret-mode grid tractable at 4k ctx; the "
                         "analytic bytes are page-size independent up to "
                         "the amortized table read)")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fast configuration for CI")
    ap.add_argument("--out", default=str(
        pathlib.Path(__file__).resolve().parent.parent / "BENCH_decode.json"))
    args = ap.parse_args(argv)
    if args.smoke:
        args.ctx, args.reps, args.page_size = 256, 2, 32

    rng = np.random.default_rng(0)
    results = {
        "bench": "decode_microbench",
        "backend": jax.default_backend(),
        "pallas_interpret": jax.default_backend() == "cpu",
        "context": args.ctx,
        "batch": args.batch,
        "heads": args.heads,
        "kv_heads": args.kv_heads,
        "head_dim": args.head_dim,
        "page_size": args.page_size,
        "runs": [],
    }
    print(f"# decode_microbench ctx={args.ctx} B={args.batch} "
          f"H={args.heads}/{args.kv_heads} D={args.head_dim} "
          f"page={args.page_size} backend={jax.default_backend()}"
          + (" (pallas interpret mode: tokens/s is a CPU software proxy; "
             "analytic bytes are the TPU-relevant metric)"
             if jax.default_backend() == "cpu" else ""))
    for layout in ("contiguous", "paged"):
        for kv_dtype in ("fp32", "int8", "fp8"):
            for variant in ("exact", "expmul"):
                for path in ("gather", "fused"):
                    r = bench_cell(
                        rng, layout=layout, kv_dtype=kv_dtype,
                        variant=variant, path=path, B=args.batch,
                        H=args.heads, Hkv=args.kv_heads, D=args.head_dim,
                        ctx=args.ctx, page_size=args.page_size,
                        reps=args.reps)
                    results["runs"].append(r)
                    mb = (r["xla_cost_bytes_per_step"] or 0) / 1e6
                    print(f"  {layout:10s}/{kv_dtype:5s}/{variant:7s}/"
                          f"{path:6s} [{r['impl']:14s}]: "
                          f"{r['ms_per_step']:8.2f} ms/step "
                          f"({r['decode_tok_per_s']:7.1f} tok/s), "
                          f"{r['analytic_bytes_per_ctx_token']:7.1f} "
                          f"B/ctx-tok analytic, {mb:8.2f} MB/step xla-cost")

    def pick(layout, kv_dtype, variant, path):
        return next(r for r in results["runs"] if
                    (r["layout"], r["kv_dtype"], r["variant"], r["path"])
                    == (layout, kv_dtype, variant, path))

    # headline + CI regression gate: fused paged analytic bytes must never
    # regress above the gather path, and int8-paged must hold the 40% bar
    ratios = {}
    for kv_dtype in ("fp32", "int8", "fp8"):
        fused = pick("paged", kv_dtype, "exact", "fused")
        gather = pick("paged", kv_dtype, "exact", "gather")
        ratio = (fused["analytic_bytes_per_ctx_token"]
                 / gather["analytic_bytes_per_ctx_token"])
        ratios[kv_dtype] = ratio
        print(f"  paged/{kv_dtype}: fused analytic bytes/ctx-token = "
              f"{ratio:.1%} of gather")
        assert ratio <= 1.0, (
            f"fused paged {kv_dtype} analytic bytes/token regressed above "
            f"the gather path ({ratio:.2f} > 1)")
    results["paged_fused_vs_gather_analytic_ratio"] = ratios
    assert ratios["int8"] <= INT8_PAGED_MAX_RATIO, (
        f"int8-paged fused datapath reads {ratios['int8']:.1%} of the "
        f"gather path's analytic bytes/token — above the "
        f"{INT8_PAGED_MAX_RATIO:.0%} acceptance bar (ISSUE-4)")
    print(f"  int8-paged fused/gather = {ratios['int8']:.1%} "
          f"(bar: <= {INT8_PAGED_MAX_RATIO:.0%})")

    pathlib.Path(args.out).write_text(json.dumps(results, indent=2) + "\n")
    print(f"wrote {args.out}")
    return results


if __name__ == "__main__":
    main()
