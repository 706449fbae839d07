"""Serving-path throughput: chunked prefill vs decode, exact vs ExpMul,
contiguous vs paged KV cache, fp32 vs quantized (int8/fp8) KV storage.

Drives real requests through ``ServeEngine`` (CPU software proxy — the TPU
target's win is VPU op count) at *mixed prompt lengths* and measures:

  * prefill tokens/sec — prompt tokens absorbed by the chunked-prefill graph
  * decode tokens/sec  — sampled tokens from the single-token graph
  * first-token engine steps vs the legacy teacher-forced path, plus
    TTFT/TPOT p50/p99 in engine steps from the engine's own histograms
    (``metrics_snapshot()`` — DESIGN.md §12; every counter/byte column
    below comes from the same snapshot, the bench only adds wall-clock
    rates) and the per-kind analytic attention byte/FLOP ledger
  * KV memory utilization — reserved vs peak-resident vs peak-active tokens
    (the paged pool allocates blocks on demand, so its resident KV tracks
    actual lengths instead of slots x max_len; DESIGN.md §7) and the same
    in real bytes (codes + scale pools) per ``kv_dtype`` — the
    ``kv_bytes_per_active_token`` column is the cross-dtype headline
  * preemptions / evictions / recompute tokens when the pool is tight
  * temp-0 stream fidelity of quantized KV: ``exact_match_vs_fp32`` is the
    token-level exact-match rate against the fp32 run of the same
    variant/layout, asserted against per-(variant, dtype) floors
    (``STREAM_MATCH_MIN``; exact/int8 carries the >= 0.99 acceptance bar,
    the fp8/expmul floors only catch codec breakage — DESIGN.md §8)
  * the shared-prefix scenario (DESIGN.md §11): requests sharing a 1k-token
    system prompt served cold vs warm prefix cache, asserting warm TTFT
    steps and per-request prefill KV HBM bytes <= 25% of cold with
    bit-identical temp-0 streams (``prefix_cache_scenarios`` rows:
    ``ttft_steps_warm``, ``prefix_hit_tokens``, ``prefill_flops_skipped``)
  * the fault matrix (DESIGN.md §13): every chaos injection point driven
    against a fault-free baseline, asserting stream isolation and
    leak-free pool accounting (``fault_scenarios[]`` rows), plus the
    crash-consistency scenario — mid-flight snapshot/restore continues
    temp-0 streams bit-identically and the restored cached tier yields
    warm-after-restore TTFT <= 25% of cold (``snapshot_restore``; the
    engine snapshot itself is left at ``--snapshot-out`` for CI upload)

Token streams are asserted identical between the contiguous and paged runs
of each (variant, kv_dtype), so the numbers always describe equivalent
output; a paged run with an explicit pool budget reserves ~3-4x the tokens
at int8/fp8 for the same unquantized-cache bytes (``pool_blocks`` sizing;
the engines here serve float32, so the multiplier is ~3.2x).

Emits ``BENCH_serve.json`` next to the repo root so the perf trajectory of
the serving path is tracked across PRs (schema: benchmarks/README.md).

  PYTHONPATH=src python benchmarks/serve_throughput.py [--arch qwen2-0.5b]
  PYTHONPATH=src python benchmarks/serve_throughput.py --smoke   # CI mode
  PYTHONPATH=src python benchmarks/serve_throughput.py --kv-dtypes fp32,int8
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.models.api import init_model
from repro.serve.engine import ServeEngine, stream_match_rate
from repro.serve.paged import blocks_for


# temp-0 stream fidelity floors vs the fp32 cache, per (variant, dtype).
# exact/int8 is the acceptance bar: amax/254 max error stays below the
# proxy model's argmax margins, so streams must match essentially always.
# fp8's 3-bit mantissa (rel err <= 2^-4, numerics/quant.py) flips
# near-tied argmaxes of the *random-init* proxy, and one flip cascades
# through the rest of an open-loop greedy stream. Under the ExpMul variant
# softmax weights are themselves powers of two, so a KV perturbation that
# crosses an L_hat rounding threshold jumps a weight by a factor of 2 —
# ties flip by construction and only the exact variant carries the 99%
# bar. The lower floors catch codec breakage (a broken codec scores ~0),
# not near-tie flips.
STREAM_MATCH_MIN = {
    ("exact", "int8"): 0.99,
    ("exact", "fp8"): 0.20,
    ("expmul", "int8"): 0.50,
    ("expmul", "fp8"): 0.20,
}


def mixed_prompts(rng, vocab, slots, prompt_len):
    """One long prompt plus a spread of shorter ones (mixed-length traffic:
    the case where contiguous slot provisioning wastes the most KV)."""
    lens = [max(4, prompt_len >> i) for i in range(slots)]
    return [list(rng.integers(1, vocab, size=n)) for n in lens]


def _ttft_steps(reqs):
    """Per-request time-to-first-token in engine steps (admission ->
    first sampled token, inclusive)."""
    return [r.first_token_step - r.admit_step + 1 for r in reqs]


def bench_prefix_scenario(params, cfg0, kv_dtype, *, n_requests, prefix_len,
                          tail_len, max_new, chunk, slots, page_size,
                          attention_impl=None):
    """The shared-prefix serving scenario (ISSUE-6, DESIGN.md §11):
    ``n_requests`` requests sharing a ``prefix_len``-token system prompt
    with short unique tails, served cold (prefix cache off) vs warm (cache
    on, one seed request populates the index first).

    Asserted here — and CI-gated via the --smoke sweep — at a 1k shared
    prefix:

      * warm temp-0 streams are bit-identical to cold,
      * mean warm TTFT steps <= 25% of cold,
      * mean per-request prefill KV HBM bytes written warm <= 25% of cold
        (the seed request is excluded from the warm means: it IS the cold
        start that fills the cache).
    """
    cfg = cfg0.replace(attention_variant="expmul")
    rng = np.random.default_rng(7)
    prefix = list(rng.integers(1, cfg.vocab_size, size=prefix_len))
    prompts = [prefix + list(rng.integers(1, cfg.vocab_size, size=tail_len))
               for _ in range(n_requests)]
    max_len = prefix_len + tail_len + max_new + 1
    kw = {"slots": slots, "max_len": max_len, "chunk_size": chunk,
          "kv_layout": "paged", "kv_dtype": kv_dtype,
          "page_size": page_size, "attention_impl": attention_impl}

    def serve(prefix_cache, seed_first):
        # compile warmup on a throwaway engine (short prompts suffice: the
        # graphs are shape-static in everything but the block-table fill)
        warm = ServeEngine(params, cfg0.replace(attention_variant="expmul"),
                           **kw, prefix_cache=prefix_cache)
        warm.submit(prompts[0][:2 * chunk], 2)
        warm.run()
        eng = ServeEngine(params, cfg, **kw, prefix_cache=prefix_cache)
        if seed_first:
            # the cache-cold seed request: pays full prefill, fills the index
            seed = eng.submit(prompts[0], max_new, rid=-1)
            eng.run()
        reqs = [eng.submit(p, max_new, rid=i) for i, p in enumerate(prompts)]
        t0 = time.time()
        eng.run()
        dt = time.time() - t0
        return eng, reqs, dt

    cold_eng, cold_reqs, t_cold = serve(prefix_cache=False, seed_first=False)
    warm_eng, warm_reqs, t_warm = serve(prefix_cache=True, seed_first=True)

    assert [r.out for r in cold_reqs] == [r.out for r in warm_reqs], (
        f"shared-prefix warm streams diverged from cold "
        f"({kv_dtype}/{attention_impl})")

    ttft_cold = float(np.mean(_ttft_steps(cold_reqs)))
    ttft_warm = float(np.mean(_ttft_steps(warm_reqs)))
    kvb_cold = float(np.mean([r.prefill_kv_bytes for r in cold_reqs]))
    kvb_warm = float(np.mean([r.prefill_kv_bytes for r in warm_reqs]))
    assert ttft_warm <= 0.25 * ttft_cold, (
        f"warm TTFT {ttft_warm:.1f} steps > 25% of cold {ttft_cold:.1f} "
        f"at a {prefix_len}-token shared prefix ({kv_dtype})")
    assert kvb_warm <= 0.25 * kvb_cold, (
        f"warm per-request prefill KV bytes {kvb_warm:.0f} > 25% of cold "
        f"{kvb_cold:.0f} ({kv_dtype})")

    st = warm_eng.memory_stats()
    sc = {
        "scenario": "shared_prefix",
        "variant": "expmul",
        "attention_impl": warm_eng.attention_impl,
        "kv_dtype": kv_dtype,
        "n_requests": n_requests,
        "prefix_len": prefix_len,
        "tail_len": tail_len,
        "ttft_steps_cold": ttft_cold,
        "ttft_steps_warm": ttft_warm,
        "ttft_warm_over_cold": ttft_warm / ttft_cold,
        "prefill_kv_bytes_cold": kvb_cold,
        "prefill_kv_bytes_warm": kvb_warm,
        "prefill_kv_bytes_warm_over_cold": kvb_warm / kvb_cold,
        "decode_tok_per_s_cold": cold_eng.tokens_generated / max(t_cold, 1e-9),
        "decode_tok_per_s_warm": warm_eng.tokens_generated / max(t_warm, 1e-9),
        "streams_bit_identical": True,
        "prefix_hit_tokens": st["prefix_hit_tokens"],
        "prefill_flops_skipped": st["prefill_flops_skipped"],
        "cache_hits": st["cache_hits"],
        "cache_lookups": st["cache_lookups"],
        "hit_blocks": st["hit_blocks"],
        "cow_copies": st["cow_copies"],
        "cached_evictions": st["cached_evictions"],
        "kv_cached_blocks": st["kv_cached_blocks"],
        "kv_cached_bytes": st["kv_cached_bytes"],
        "kv_token_bytes": st["kv_token_bytes"],
    }
    # snapshot percentile columns (§12). The existing mean-based <=25%
    # floors above stay the CI gate; note the warm engine's histograms
    # include the cache-cold seed request, so its p99 is the seed's TTFT —
    # honest tail reporting, not a bug.
    sc.update(_percentile_cols(cold_eng.metrics_snapshot(), "_cold"))
    sc.update(_percentile_cols(warm_eng.metrics_snapshot(), "_warm"))
    return sc


def bench_fault_scenarios(params, cfg0, *, n_requests, prompt_len, max_new,
                          chunk, slots, page_size, pool_blocks):
    """The chaos matrix (ISSUE-9, DESIGN.md §13) as BENCH_serve.json
    ``fault_scenarios[]`` rows, with the acceptance asserts in-script so
    the CI smoke sweep gates them on every push:

      * delay-only injectors (pool_alloc / admission / preempt) leave
        every temp-0 stream bit-identical to the fault-free baseline;
      * corruption injectors (logits / kv_corrupt) quarantine exactly
        their victim (``finish_reason="failed"``) while co-resident
        streams stay bit-identical;
      * after every run the pool accounting is leak-free
        (used + cached + free == pool_blocks, refcounts rebuilt from
        tables, zero dangling radix keys) and the drained engine pins
        nothing.
    """
    from repro.serve.faults import ChaosInjector, install_fault_injector

    cfg = cfg0.replace(attention_variant="expmul")
    rng = np.random.default_rng(11)
    prompts = [list(rng.integers(1, cfg.vocab_size, size=prompt_len))
               for _ in range(n_requests)]
    kw = {"slots": slots, "max_len": prompt_len + max_new + 1,
          "chunk_size": chunk, "kv_layout": "paged",
          "page_size": page_size, "pool_blocks": pool_blocks}

    def serve(injector):
        install_fault_injector(injector)
        try:
            eng = ServeEngine(params, cfg, **kw)
            reqs = [eng.submit(p, max_new, rid=i)
                    for i, p in enumerate(prompts)]
            eng.run(max_steps=5000)
        finally:
            install_fault_injector(None)
        eng.pool.check_consistency()
        assert eng.pool.used_blocks == 0, "drained engine still pins blocks"
        return eng, reqs

    _, base = serve(None)
    expect = {r.rid: list(r.out) for r in base}
    victim_rid = n_requests // 2
    rows = []
    plans = [(p, ChaosInjector(at={p: [1, 3, 5]}))
             for p in ("pool_alloc", "admission", "preempt")]
    plans += [(p, ChaosInjector(at={p: [4]}, rids={p: {victim_rid}}))
              for p in ("logits", "kv_corrupt")]
    for point, inj in plans:
        eng, reqs = serve(inj)
        delay_only = point in ("pool_alloc", "admission", "preempt")
        assert inj.fired(point) >= 1, f"{point} injector never fired"
        for r in reqs:
            if delay_only or r.rid != victim_rid:
                assert r.finish_reason == "length", (
                    f"{point} chaos spilled into request {r.rid}: "
                    f"{r.finish_reason}")
                assert list(r.out) == expect[r.rid], (
                    f"{point} chaos changed request {r.rid}'s temp-0 "
                    f"stream")
        snap = eng.metrics_snapshot()
        if delay_only:
            assert snap["quarantined"] == 0
        else:
            victim = next(r for r in reqs if r.rid == victim_rid)
            assert victim.finish_reason == "failed", (
                f"{point} victim finished {victim.finish_reason!r}, "
                f"expected quarantine")
            assert snap["quarantined"] == 1
        rows.append({
            "scenario": point,
            "injected": inj.fired(point),
            "opportunities": inj.opportunities(point),
            "quarantined": snap["quarantined"],
            "finish_reasons": {k: v for k, v
                               in snap["finish_reasons"].items() if v},
            "surviving_streams_bit_identical": True,
            "pool_consistent": True,
            "preemptions": int(eng.preemptions),
        })
    return rows


def bench_snapshot_restore(params, cfg0, *, n_requests, prefix_len,
                           tail_len, max_new, chunk, slots, page_size,
                           snapshot_path):
    """Crash-consistent snapshot/restore (ISSUE-9, DESIGN.md §13) as the
    BENCH_serve.json ``snapshot_restore`` section. In-script asserts —
    CI-gated via --smoke:

      * mid-flight temp-0 streams continue bit-identically in the
        restored engine (the snapshotting engine keeps running as the
        never-stopped oracle);
      * the cached prefix tier survives the restart: serving the shared-
        prefix workload against the *restored* cache yields mean warm
        TTFT <= 25% of cold, with streams bit-identical to cold.

    The snapshot file itself is left at ``snapshot_path`` (CI artifact).
    """
    from repro.serve.snapshot import restore_engine

    cfg = cfg0.replace(attention_variant="expmul")
    rng = np.random.default_rng(13)
    prefix = list(rng.integers(1, cfg.vocab_size, size=prefix_len))
    prompts = [prefix + list(rng.integers(1, cfg.vocab_size, size=tail_len))
               for _ in range(n_requests)]
    max_len = prefix_len + tail_len + max_new + 1
    kw = {"slots": slots, "max_len": max_len, "chunk_size": chunk,
          "kv_layout": "paged", "page_size": page_size}

    # leg 1 — mid-flight continuation: snapshot after a few ticks, keep
    # the original running as the oracle, restore and compare
    eng = ServeEngine(params, cfg, **kw, prefix_cache=True)
    reqs = [eng.submit(p, max_new, rid=i) for i, p in enumerate(prompts)]
    for _ in range(4):
        eng.tick()
    mid_path = snapshot_path + ".midflight"
    eng.save_snapshot(mid_path)
    eng.run()
    oracle = {r.rid: list(r.out) for r in reqs}
    restored = restore_engine(mid_path, params, cfg)
    carried = ([r for r in restored.requests if r is not None]
               + list(restored.queue))
    restored.run()
    for r in carried:
        assert list(r.out) == oracle[r.rid], (
            f"request {r.rid} diverged across the snapshot boundary")
    restored.pool.check_consistency()
    os.remove(mid_path)

    # leg 2 — restart survival of the cached tier: cold engine (no cache)
    # vs requests served against a cache restored from disk
    cold = ServeEngine(params, cfg, **kw, prefix_cache=False)
    cold_reqs = [cold.submit(p, max_new, rid=i)
                 for i, p in enumerate(prompts)]
    cold.run()
    seed_eng = ServeEngine(params, cfg, **kw, prefix_cache=True)
    seed_eng.submit(prompts[0], max_new, rid=-1)  # fills the radix index
    seed_eng.run()
    meta = seed_eng.save_snapshot(snapshot_path)
    warm_eng = restore_engine(snapshot_path, params, cfg)
    assert warm_eng.pool.cached_block_count > 0, (
        "restored engine carries no cached prefix tier")
    warm_reqs = [warm_eng.submit(p, max_new) for p in prompts]
    warm_eng.run()
    warm_eng.pool.check_consistency()
    assert [r.out for r in cold_reqs] == [r.out for r in warm_reqs], (
        "warm-after-restore streams diverged from cold")
    ttft_cold = float(np.mean(_ttft_steps(cold_reqs)))
    ttft_warm = float(np.mean(_ttft_steps(warm_reqs)))
    assert ttft_warm <= 0.25 * ttft_cold, (
        f"warm-after-restore TTFT {ttft_warm:.1f} steps > 25% of cold "
        f"{ttft_cold:.1f}: the cached tier did not survive the restart")
    return {
        "n_requests": n_requests,
        "prefix_len": prefix_len,
        "midflight_streams_bit_identical": True,
        "warm_streams_bit_identical": True,
        "ttft_steps_cold": ttft_cold,
        "ttft_steps_warm_restored": ttft_warm,
        "ttft_warm_restored_over_cold": ttft_warm / ttft_cold,
        "cached_blocks_restored": int(warm_eng.pool.cached_block_count),
        "prefix_hit_tokens_after_restore": int(
            warm_eng.prefix_hit_tokens),
        "snapshot_bytes": os.path.getsize(snapshot_path),
        "snapshot_state_leaves": int(meta["n_leaves"]),
        "snapshot_path": snapshot_path,
    }


def _percentile_cols(snap, suffix=""):
    """TTFT/TPOT percentile columns out of an engine metrics snapshot
    (engine steps — DESIGN.md §12), asserted present and finite so a
    broken histogram can never silently ship NaN columns."""
    cols = {}
    for base in ("ttft_steps_p50", "ttft_steps_p99",
                 "tpot_steps_p50", "tpot_steps_p99"):
        v = float(snap[base])
        assert np.isfinite(v), (base, snap["histograms"].get(
            "serve_" + base.rsplit("_", 1)[0]))
        cols[base + suffix] = v
    return cols


def bench_run(params, cfg0, variant, kv_layout, kv_dtype, *, slots,
              prompt_len, max_new, chunk, max_len, page_size, pool_frac,
              attention_impl=None, trace=False):
    cfg = cfg0.replace(attention_variant=variant)
    rng = np.random.default_rng(0)
    prompts = mixed_prompts(rng, cfg.vocab_size, slots, prompt_len)

    kw = {"slots": slots, "max_len": max_len, "chunk_size": chunk,
          "kv_layout": kv_layout, "kv_dtype": kv_dtype,
          "attention_impl": attention_impl}
    if kv_layout == "paged":
        full = slots * blocks_for(max_len, page_size)
        kw.update(page_size=page_size,
                  pool_blocks=max(2, int(full * pool_frac)))

    # warmup: compile both graphs on a throwaway engine
    warm = ServeEngine(params, cfg, **kw)
    for p in prompts:
        warm.submit(p, 2)
    warm.run()

    eng = ServeEngine(params, cfg, **kw, trace=trace)
    reqs = [eng.submit(p, max_new, rid=i) for i, p in enumerate(prompts)]

    t0 = time.time()
    while any(not r.done and r.pos < len(r.prefill_toks) for r in reqs):
        eng.tick()
    t_prefill = time.time() - t0
    prefill_tokens = eng.prompt_tokens + eng.recompute_tokens

    t0 = time.time()
    eng.run()
    t_decode = time.time() - t0

    assert all(r.done for r in reqs)
    # the engine's snapshot is the single source for every counter/byte
    # column (DESIGN.md §12); the bench only contributes wall-clock rates
    snap = eng.metrics_snapshot()
    c = snap["counters"]
    r = {
        "variant": variant,
        "attention_impl": eng.attention_impl,
        "prompt_lens": [len(p) for p in prompts],
        "prefill_tokens": int(prefill_tokens),
        "prefill_steps": int(c["serve_prefill_steps_total"]),
        "decode_steps": int(c["serve_decode_steps_total"]),
        "prefill_tok_per_s": prefill_tokens / max(t_prefill, 1e-9),
        "decode_tok_per_s": (c["serve_tokens_generated_total"]
                             / max(t_decode, 1e-9)),
        "first_token_steps": max(r.first_token_step for r in reqs),
        "legacy_first_token_steps": max(len(p) for p in prompts),
    }
    r.update(_percentile_cols(snap))
    r.update(snap["memory"])
    return r, [q.out for q in reqs], eng


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--max-len", type=int, default=384)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--pool-frac", type=float, default=0.5,
                    help="paged pool budget as a fraction of the fully "
                         "provisioned slots*max_len unquantized bytes "
                         "(small enough to show the memory win, large "
                         "enough to avoid thrashing at fp32)")
    ap.add_argument("--kv-dtypes", default="fp32,int8,fp8",
                    help="comma list of KV storage dtypes to sweep")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fast configuration for CI")
    ap.add_argument("--metrics-json", default=None,
                    help="write the traced run's full metrics_snapshot() "
                         "here (CI artifact)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace/Perfetto JSON of one traced "
                         "run here (load in ui.perfetto.dev)")
    ap.add_argument("--out", default=str(
        pathlib.Path(__file__).resolve().parent.parent / "BENCH_serve.json"))
    ap.add_argument("--snapshot-out", default=str(
        pathlib.Path(__file__).resolve().parent.parent
        / "BENCH_serve_snapshot.npz"),
        help="where the snapshot/restore scenario leaves its engine "
             "snapshot (uploaded as a CI artifact)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.slots, args.prompt_len, args.max_new = 2, 32, 8
        args.chunk, args.max_len, args.page_size = 16, 64, 8

    kv_dtypes = [d.strip() for d in args.kv_dtypes.split(",") if d.strip()]
    assert kv_dtypes and kv_dtypes[0] == "fp32", \
        "the sweep needs fp32 first (quantized runs compare against it)"

    cfg = get_config(args.arch, smoke=True, dtype="float32",
                     param_dtype="float32")
    params = init_model(jax.random.PRNGKey(0), cfg)

    results = {
        "bench": "serve_throughput",
        "arch": args.arch,
        "backend": jax.default_backend(),
        "slots": args.slots,
        "prompt_len": args.prompt_len,
        "max_new": args.max_new,
        "chunk": args.chunk,
        "page_size": args.page_size,
        "pool_frac": args.pool_frac,
        "kv_dtypes": kv_dtypes,
        "runs": [],
    }
    print(f"# serve_throughput {args.arch} slots={args.slots} "
          f"prompt<={args.prompt_len} chunk={args.chunk} "
          f"page={args.page_size} kv_dtypes={','.join(kv_dtypes)}")
    all_streams = {}  # (variant, kv_dtype, kv_layout) -> token streams
    for variant in ("exact", "expmul"):
        fp32_streams = {}
        for kv_dtype in kv_dtypes:
            streams = {}
            for kv_layout in ("contiguous", "paged"):
                r, outs, _ = bench_run(
                    params, cfg, variant, kv_layout, kv_dtype,
                    slots=args.slots, prompt_len=args.prompt_len,
                    max_new=args.max_new, chunk=args.chunk,
                    max_len=args.max_len, page_size=args.page_size,
                    pool_frac=args.pool_frac)
                streams[kv_layout] = outs
                all_streams[(variant, kv_dtype, kv_layout)] = outs
                if kv_dtype == "fp32":
                    fp32_streams[kv_layout] = outs
                    r["exact_match_vs_fp32"] = 1.0
                else:
                    rate = stream_match_rate(fp32_streams[kv_layout], outs)
                    r["exact_match_vs_fp32"] = rate
                    floor = STREAM_MATCH_MIN[(variant, kv_dtype)]
                    assert rate >= floor, (
                        f"{variant}/{kv_dtype}/{kv_layout} temp-0 streams "
                        f"drifted from fp32: exact-match {rate:.2%} < "
                        f"{floor:.0%}")
                results["runs"].append(r)
                print(f"  {variant:7s}/{kv_dtype:5s}/{kv_layout:10s}: "
                      f"prefill {r['prefill_tok_per_s']:9.1f} tok/s "
                      f"({r['prefill_steps']} st), decode "
                      f"{r['decode_tok_per_s']:7.1f} tok/s, first tok "
                      f"{r['first_token_steps']} (legacy "
                      f"{r['legacy_first_token_steps']}), KV "
                      f"{r['kv_peak_used_tokens']}/{r['kv_reserved_tokens']} "
                      f"tok @ {r['kv_token_bytes']} B/tok "
                      f"({r['kv_bytes_per_active_token']:.0f} B/active), "
                      f"TTFT p50/p99 {r['ttft_steps_p50']:.0f}/"
                      f"{r['ttft_steps_p99']:.0f} st, "
                      f"match {r['exact_match_vs_fp32']:.2%}, "
                      f"preempt {r['preemptions']}")
            assert streams["contiguous"] == streams["paged"], \
                f"paged streams diverged from contiguous ({variant}/{kv_dtype})"

    # fused-vs-gather pair (DESIGN.md §9/§10): rerun the exact paged cell
    # with the Pallas fused serving kernels — both ticks: flash-decode AND
    # chunked flash-prefill (in-kernel block tables + in-register dequant) —
    # and assert its temp-0 streams are identical to the gather backend's;
    # the attention_impl column distinguishes the rows in BENCH_serve.json.
    fused_dtype = "int8" if "int8" in kv_dtypes else "fp32"
    r, outs, _ = bench_run(
        params, cfg, "exact", "paged", fused_dtype,
        slots=args.slots, prompt_len=args.prompt_len, max_new=args.max_new,
        chunk=args.chunk, max_len=args.max_len, page_size=args.page_size,
        pool_frac=args.pool_frac, attention_impl="pallas")
    assert outs == all_streams[("exact", fused_dtype, "paged")], (
        f"fused (pallas) exact/{fused_dtype}/paged temp-0 streams diverged "
        f"from the gather backend")
    r["exact_match_vs_fp32"] = stream_match_rate(
        all_streams[("exact", "fp32", "paged")], outs)
    results["runs"].append(r)
    print(f"  exact  /{fused_dtype:5s}/paged[pallas]: prefill "
          f"{r['prefill_tok_per_s']:9.1f} tok/s, decode "
          f"{r['decode_tok_per_s']:7.1f} tok/s, streams == gather backend "
          f"(fused prefill+decode; CPU runs the kernels in interpret mode)")

    # shared-prefix scenario (ISSUE-6, DESIGN.md §11): n requests sharing a
    # 1k-token system prompt, cold vs warm prefix cache. The warm<=25%-cold
    # TTFT and prefill-KV-bytes asserts live inside bench_prefix_scenario,
    # so the CI smoke sweep gates them on every push; the fused (pallas)
    # leg reruns the scenario through the flash kernels to pin the spliced
    # block tables end-to-end.
    sc_kw = dict(
        n_requests=8 if args.smoke else 64,
        prefix_len=1024, tail_len=16, max_new=args.max_new,
        chunk=args.chunk, slots=args.slots, page_size=args.page_size)
    results["prefix_cache_scenarios"] = []
    scenario_impls = [(d, None) for d in kv_dtypes if d in ("fp32", "int8")]
    scenario_impls.append((fused_dtype, "pallas"))
    for kv_dtype, impl in scenario_impls:
        sc = bench_prefix_scenario(params, cfg, kv_dtype,
                                   attention_impl=impl, **sc_kw)
        results["prefix_cache_scenarios"].append(sc)
        print(f"  shared-prefix/{kv_dtype:5s}"
              f"{'[pallas]' if impl else '        '}: "
              f"TTFT {sc['ttft_steps_warm']:.1f} warm vs "
              f"{sc['ttft_steps_cold']:.1f} cold steps "
              f"({sc['ttft_warm_over_cold']:.1%}), prefill KV "
              f"{sc['prefill_kv_bytes_warm']:.0f} vs "
              f"{sc['prefill_kv_bytes_cold']:.0f} B/req "
              f"({sc['prefill_kv_bytes_warm_over_cold']:.1%}), "
              f"{sc['prefix_hit_tokens']} tok skipped "
              f"({sc['prefill_flops_skipped']:.3g} FLOPs), streams == cold")

    # fault scenarios (ISSUE-9, DESIGN.md §13): the chaos matrix with its
    # isolation + leak-free-accounting asserts in-script, CI-gated via
    # --smoke like the prefix-cache scenario above
    results["fault_scenarios"] = bench_fault_scenarios(
        params, cfg, n_requests=4 if args.smoke else 16,
        prompt_len=args.prompt_len, max_new=args.max_new, chunk=args.chunk,
        slots=args.slots, page_size=args.page_size, pool_blocks=None)
    for row in results["fault_scenarios"]:
        print(f"  fault/{row['scenario']:10s}: {row['injected']} injected "
              f"over {row['opportunities']} opportunities, "
              f"quarantined {row['quarantined']}, reasons "
              f"{row['finish_reasons']}, surviving streams == baseline, "
              f"pool consistent")

    # snapshot/restore (ISSUE-9): mid-flight continuation bit-identity and
    # the warm-after-restore TTFT <= 25% cold gate; the snapshot file is
    # kept as a CI artifact
    results["snapshot_restore"] = bench_snapshot_restore(
        params, cfg, n_requests=4 if args.smoke else 16,
        prefix_len=1024, tail_len=16, max_new=args.max_new,
        chunk=args.chunk, slots=args.slots, page_size=args.page_size,
        snapshot_path=args.snapshot_out)
    sr = results["snapshot_restore"]
    print(f"  snapshot-restore: TTFT {sr['ttft_steps_warm_restored']:.1f} "
          f"warm-after-restore vs {sr['ttft_steps_cold']:.1f} cold steps "
          f"({sr['ttft_warm_restored_over_cold']:.1%}), "
          f"{sr['cached_blocks_restored']} cached blocks survived, "
          f"mid-flight + warm streams bit-identical "
          f"({sr['snapshot_bytes']} B snapshot at {sr['snapshot_path']})")

    def pick(variant, kv_dtype, kv_layout):
        # the fused (pallas) rerun shares this triple with its gather row:
        # the summary comparisons are about KV layout/dtype, so they pin
        # the default-impl row explicitly rather than relying on list order
        return next(r for r in results["runs"]
                    if (r["variant"], r["kv_dtype"], r["kv_layout"],
                        r["attention_impl"])
                    == (variant, kv_dtype, kv_layout, cfg.attention_impl))

    # headline 1: paged resident KV per active token vs contiguous (fp32)
    cont = pick("exact", "fp32", "contiguous")
    paged = pick("exact", "fp32", "paged")
    results["kv_memory_reduction_vs_contiguous"] = (
        1.0 - paged["kv_tokens_per_active_token"]
        / cont["kv_tokens_per_active_token"])
    print(f"  paged KV per active token: "
          f"{results['kv_memory_reduction_vs_contiguous']:.1%} below "
          f"contiguous at mixed prompt lengths")
    # headline 2: quantized capacity multiple at the same pool byte budget
    for kv_dtype in kv_dtypes:
        if kv_dtype == "fp32":
            continue
        q = pick("exact", kv_dtype, "paged")
        mult = q["kv_reserved_tokens"] / paged["kv_reserved_tokens"]
        results[f"kv_capacity_multiplier_{kv_dtype}"] = mult
        print(f"  {kv_dtype} paged capacity: {mult:.2f}x the co-resident "
              f"tokens of fp32 at the same pool budget "
              f"({q['kv_token_bytes']} vs {paged['kv_token_bytes']} B/token)")

    # observability artifacts (DESIGN.md §12): rerun the paged fp32 cell
    # with span tracing on, export the snapshot + Chrome trace, and verify
    # both in-script so CI fails loudly on a malformed trace
    if args.metrics_json or args.trace_out:
        _, _, eng_t = bench_run(
            params, cfg, "exact", "paged", "fp32",
            slots=args.slots, prompt_len=args.prompt_len,
            max_new=args.max_new, chunk=args.chunk, max_len=args.max_len,
            page_size=args.page_size, pool_frac=args.pool_frac, trace=True)
        snap = eng_t.metrics_snapshot()
        assert np.isfinite(snap["ttft_steps_p99"]), snap["histograms"]
        if args.metrics_json:
            pathlib.Path(args.metrics_json).write_text(
                json.dumps(snap, indent=2) + "\n")
            print(f"wrote {args.metrics_json}")
        if args.trace_out:
            eng_t.metrics.write_chrome_trace(args.trace_out)
            tr = json.loads(pathlib.Path(args.trace_out).read_text())
            evs = tr["traceEvents"]
            assert evs, "traced run produced no events"
            assert all(e["ph"] in ("X", "B", "E", "i", "M") for e in evs)
            n_b = sum(1 for e in evs if e["ph"] == "B")
            n_e = sum(1 for e in evs if e["ph"] == "E")
            assert n_b == n_e, f"unmatched B/E events ({n_b} vs {n_e})"
            assert all(e["dur"] >= 0 for e in evs if e["ph"] == "X")
            print(f"wrote {args.trace_out} ({len(evs)} events, "
                  f"{n_b} request lifecycles)")

    pathlib.Path(args.out).write_text(json.dumps(results, indent=2) + "\n")
    print(f"wrote {args.out}")
    return results


if __name__ == "__main__":
    main()
