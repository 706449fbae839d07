"""Kernel-level prefill microbenchmark: fused vs gather KV datapaths.

The chunked-prefill tick is the FLOP-dominant half of a serving request,
but its *memory* cost is still the KV history dragged through HBM per
chunk: the gather datapaths (``masked_xla`` / ``gather_xla`` and their
``_q`` twins) first materialize a contiguous, dequantized fp32 copy of
the [cache ++ chunk] history before attending, while the fused Pallas
prefill kernels (DESIGN.md §10: two-segment KV walks, in-kernel
block-table indexing, in-register dequant — the ``pallas``/``pallas_q``
backends) read the serving state directly. This bench sweeps every
{variant} x {kv_dtype} x {layout} cell of the prefill registry and
reports two byte metrics:

  * ``analytic_bytes_per_chunk_token`` — the datapath's *designed* HBM
    traffic per chunk token per prefill step, from the operand layouts
    (see ``analytic_bytes_per_chunk_token`` below). This is the
    hardware-relevant number and the CI regression gate: the fused paged
    path must stay at/below the gather path, and int8-paged fused must be
    <= 50% of int8-paged gather (ISSUE-5 acceptance; at D=Dv=64 the model
    gives ~12% fused vs gather at int8-paged, ~33% at fp32-paged).
  * ``xla_cost_bytes_per_step`` — XLA's own cost-model "bytes accessed"
    for the compiled step, when available. On CPU the Pallas kernels run
    in *interpret mode*, so the measured ms/chunk (and chunk-tokens/s)
    column describes the CPU software proxy, not the TPU target —
    interpret-mode emulation makes the fused path slower in wall-clock
    here even though it moves strictly fewer bytes; the analytic column
    is the metric that transfers.

Emits ``BENCH_prefill.json`` next to the repo root (schema:
benchmarks/README.md) — the prefill twin of BENCH_decode.json.

  PYTHONPATH=src python benchmarks/prefill_microbench.py            # 4k ctx
  PYTHONPATH=src python benchmarks/prefill_microbench.py --smoke    # CI mode
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
    dispatch_paged_prefill,
    dispatch_prefill,
    resolved_backends,
)
# the analytic cost model lives in repro.kernels.costs since DESIGN.md §12;
# re-exported here so existing callers keep their import path
from repro.kernels.costs import analytic_bytes_per_chunk_token  # noqa: F401
from repro.numerics.quant import QuantKV, quantize_kv

INT8_PAGED_MAX_RATIO = 0.50  # ISSUE-5 acceptance bar (fused/gather, analytic)


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
               chunk, page_size, reps):
    q = jnp.asarray(rng.standard_normal((B, H, chunk, D)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((B, Hkv, chunk, D)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((B, Hkv, chunk, D)), jnp.float32)
    lens = jnp.asarray([ctx - (i * 13) % (ctx // 2) for i in range(B)],
                       jnp.int32)
    nv = jnp.full((B,), chunk, jnp.int32)
    quant = kv_dtype != "fp32"
    if quant:
        knq, vnq = quantize_kv(kn, kv_dtype), quantize_kv(vn, kv_dtype)
        kn_op = QuantKV(knq.codes, knq.scale)
        vn_op = QuantKV(vnq.codes, vnq.scale)
    else:
        kn_op, vn_op = kn, vn

    if layout == "contiguous":
        kc = jnp.asarray(rng.standard_normal((B, Hkv, ctx, D)), jnp.float32)
        vc = jnp.asarray(rng.standard_normal((B, Hkv, ctx, D)), jnp.float32)
        if quant:
            kq, vq = quantize_kv(kc, kv_dtype), quantize_kv(vc, kv_dtype)
            kc = QuantKV(kq.codes, kq.scale)
            vc = QuantKV(vq.codes, vq.scale)
        spec = AttentionSpec(
            variant=variant, kv_dtype=kv_dtype,
            prefill_impl="masked_xla" if path == "gather" else "pallas")

        def fn(q, kc, vc, kn, vn, lens, nv):
            return dispatch_prefill(spec, q, kc, vc, kn, vn, lengths=lens,
                                    n_valid=nv)

        args = (q, kc, vc, kn_op, vn_op, lens, nv)
        impl = spec.resolved_prefill_impl()
    else:
        max_blocks = -(-(ctx + chunk) // page_size)
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
        positions = lens[:, None] + jnp.arange(chunk)[None, :]
        cvalid = jnp.ones((B, chunk), bool)
        spec = AttentionSpec(
            variant=variant, kv_dtype=kv_dtype,
            paged_impl="gather_xla" if path == "gather" else "pallas")

        if path == "gather":
            def fn(q, kn, vn, kp, vp, rows, positions, cvalid, lens):
                return dispatch_paged_prefill(
                    spec, q, kn, vn, kp, vp, rows, q_positions=positions,
                    chunk_valid=cvalid, lengths=lens)
            args = (q, kn_op, vn_op, kp, vp, rows, positions, cvalid, lens)
        else:
            def fn(q, kn, vn, kp, vp, rows, positions, cvalid, lens, bt):
                return dispatch_paged_prefill(
                    spec, q, kn, vn, kp, vp, rows, q_positions=positions,
                    chunk_valid=cvalid, lengths=lens, block_tables=bt,
                    page_size=page_size)
            args = (q, kn_op, vn_op, kp, vp, rows, positions, cvalid, lens,
                    bt)
        impl = spec.resolved_paged_impl()

    # the analytic-bytes gate below is formula-based, so it can only defend
    # the datapath if the cell really dispatched the backend the formula
    # models — pin the resolved name and require it fallback-free
    expected = {"gather": "masked_xla" if layout == "contiguous"
                else "gather_xla", "fused": "pallas"}[path]
    if quant:
        expected += "_q"
    assert impl == expected, (
        f"{layout}/{kv_dtype}/{path} resolved to backend {impl!r}, "
        f"expected {expected!r}")
    if path == "fused":
        kind = "paged prefill" if layout == "paged" else "prefill"
        row = next(r for r in resolved_backends(spec, paged=layout == "paged")
                   if r["kind"] == kind)
        assert not row["fallback"], (
            f"{layout}/{kv_dtype}/fused: {impl!r} is registered as a "
            f"fallback onto {row['resolved']!r} — the fused prefill "
            f"datapath this bench claims to measure no longer exists")

    sec = _time_step(jax.jit(fn), args, reps=reps)
    return {
        "layout": layout,
        "kv_dtype": kv_dtype,
        "variant": variant,
        "path": path,
        "impl": impl,
        "context": ctx,
        "chunk": chunk,
        "ms_per_chunk": sec * 1e3,
        "chunk_tok_per_s": B * chunk / sec,
        "analytic_bytes_per_chunk_token": analytic_bytes_per_chunk_token(
            layout, kv_dtype, path, Hkv=Hkv, D=D, Dv=D, ctx=ctx,
            chunk=chunk, page_size=page_size),
        "xla_cost_bytes_per_step": _xla_cost_bytes(fn, *args),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--ctx", type=int, default=4096,
                    help="resident KV history length the chunk attends over")
    ap.add_argument("--chunk", type=int, default=128,
                    help="prefill chunk size (fresh tokens per step)")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--kv-heads", type=int, default=2)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--page-size", type=int, default=64,
                    help="KV block size for the paged cells (64 keeps the "
                         "CPU interpret-mode grid tractable at 4k ctx; the "
                         "analytic bytes are page-size independent up to "
                         "the amortized table read)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fast configuration for CI")
    ap.add_argument("--out", default=str(
        pathlib.Path(__file__).resolve().parent.parent
        / "BENCH_prefill.json"))
    args = ap.parse_args(argv)
    if args.smoke:
        args.ctx, args.chunk, args.reps, args.page_size = 256, 32, 2, 32

    rng = np.random.default_rng(0)
    results = {
        "bench": "prefill_microbench",
        "backend": jax.default_backend(),
        "pallas_interpret": jax.default_backend() == "cpu",
        "context": args.ctx,
        "chunk": args.chunk,
        "batch": args.batch,
        "heads": args.heads,
        "kv_heads": args.kv_heads,
        "head_dim": args.head_dim,
        "page_size": args.page_size,
        "runs": [],
    }
    print(f"# prefill_microbench ctx={args.ctx} chunk={args.chunk} "
          f"B={args.batch} H={args.heads}/{args.kv_heads} "
          f"D={args.head_dim} page={args.page_size} "
          f"backend={jax.default_backend()}"
          + (" (pallas interpret mode: chunk-tok/s is a CPU software "
             "proxy; analytic bytes are the TPU-relevant metric)"
             if jax.default_backend() == "cpu" else ""))
    for layout in ("contiguous", "paged"):
        for kv_dtype in ("fp32", "int8", "fp8"):
            for variant in ("exact", "expmul"):
                for path in ("gather", "fused"):
                    r = bench_cell(
                        rng, layout=layout, kv_dtype=kv_dtype,
                        variant=variant, path=path, B=args.batch,
                        H=args.heads, Hkv=args.kv_heads, D=args.head_dim,
                        ctx=args.ctx, chunk=args.chunk,
                        page_size=args.page_size, reps=args.reps)
                    results["runs"].append(r)
                    mb = (r["xla_cost_bytes_per_step"] or 0) / 1e6
                    print(f"  {layout:10s}/{kv_dtype:5s}/{variant:7s}/"
                          f"{path:6s} [{r['impl']:14s}]: "
                          f"{r['ms_per_chunk']:8.2f} ms/chunk "
                          f"({r['chunk_tok_per_s']:7.1f} tok/s), "
                          f"{r['analytic_bytes_per_chunk_token']:9.1f} "
                          f"B/chunk-tok analytic, {mb:8.2f} MB/step xla-cost")

    def pick(layout, kv_dtype, variant, path):
        return next(r for r in results["runs"] if
                    (r["layout"], r["kv_dtype"], r["variant"], r["path"])
                    == (layout, kv_dtype, variant, path))

    # headline + CI regression gate: fused paged analytic bytes must never
    # regress above the gather path, and int8-paged must hold the 50% bar
    ratios = {}
    for kv_dtype in ("fp32", "int8", "fp8"):
        fused = pick("paged", kv_dtype, "exact", "fused")
        gather = pick("paged", kv_dtype, "exact", "gather")
        ratio = (fused["analytic_bytes_per_chunk_token"]
                 / gather["analytic_bytes_per_chunk_token"])
        ratios[kv_dtype] = ratio
        print(f"  paged/{kv_dtype}: fused analytic bytes/chunk-token = "
              f"{ratio:.1%} of gather")
        assert ratio <= 1.0, (
            f"fused paged {kv_dtype} analytic bytes/chunk-token regressed "
            f"above the gather path ({ratio:.2f} > 1)")
    results["paged_fused_vs_gather_analytic_ratio"] = ratios
    assert ratios["int8"] <= INT8_PAGED_MAX_RATIO, (
        f"int8-paged fused prefill reads {ratios['int8']:.1%} of the "
        f"gather path's analytic bytes/chunk-token — above the "
        f"{INT8_PAGED_MAX_RATIO:.0%} acceptance bar (ISSUE-5)")
    print(f"  int8-paged fused/gather = {ratios['int8']:.1%} "
          f"(bar: <= {INT8_PAGED_MAX_RATIO:.0%})")

    pathlib.Path(args.out).write_text(json.dumps(results, indent=2) + "\n")
    print(f"wrote {args.out}")
    return results


if __name__ == "__main__":
    main()
