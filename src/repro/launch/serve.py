"""Serving launcher: chunked prefill + continuous decode batching.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
      --requests 8 --max-new 32 --chunk 32 [--variant expmul] \
      [--kv-layout paged --page-size 16 --pool-blocks 0] [--kv-dtype int8] \
      [--attention-impl pallas] [--no-prefix-cache]

``--attention-impl pallas`` selects the Pallas kernel family end-to-end —
including the fused paged (+ quantized) flash-decode with in-kernel
block-table indexing (DESIGN.md §9; interpret mode on CPU).

Observability (DESIGN.md §12): ``--metrics-json PATH`` dumps the full
``metrics_snapshot()`` after the run; ``--trace-out PATH`` turns on span
tracing and writes a Chrome-trace/Perfetto JSON of the request-lifecycle
timeline (load in ui.perfetto.dev); ``--log-metrics-every N`` prints a
one-line progress summary every N engine steps while serving.

Fault tolerance (DESIGN.md §13): ``--deadline-steps N`` / ``--deadline-s S``
set per-request budgets (expired requests finish with
``finish_reason="deadline"``); ``--chaos "point=rate,..."`` installs the
deterministic chaos injector for the run (points: pool_alloc, admission,
preempt, logits, kv_corrupt; each capped at 4 fires); ``--snapshot-path P``
writes a crash-consistent engine snapshot after the run (pool + radix
index + metrics), and ``--restore-path P`` starts the engine from one —
re-serving a warm prompt after a restore splices its cached prefix, the
restart-survival demo.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.models.api import init_model
from repro.serve.engine import ServeEngine, validate_kv_dtype


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=32,
                    help="prefill chunk size (1 = legacy teacher-forcing)")
    ap.add_argument("--prompt-len", type=int, default=0,
                    help="fixed prompt length (0 = random 4..11)")
    ap.add_argument("--variant", default="expmul", choices=["exact", "expmul"])
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--kv-layout", default="contiguous",
                    choices=["contiguous", "paged"])
    ap.add_argument("--page-size", type=int, default=0,
                    help="tokens per KV block (0 = cfg.page_size)")
    ap.add_argument("--pool-blocks", type=int, default=0,
                    help="paged pool size as an unquantized-equivalent "
                         "byte budget (0 = fully provisioned; quantized "
                         "dtypes fit proportionally more blocks)")
    ap.add_argument("--kv-dtype", default="fp32",
                    choices=["fp32", "int8", "fp8"],
                    help="KV-cache storage dtype (int8/fp8: quantize-on-"
                         "write + fused dequant; attention-only decoder "
                         "archs)")
    ap.add_argument("--attention-impl", default=None,
                    choices=["ref", "flash_jnp", "pallas"],
                    help="attention backend family (None = cfg default; "
                         "'pallas' enables the fused paged/quantized "
                         "flash-decode kernel, DESIGN.md §9)")
    ap.add_argument("--prefix-cache", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="automatic shared-prefix KV caching (DESIGN.md "
                         "§11). Default: on for paged attention-only "
                         "configs, off otherwise; --prefix-cache with "
                         "--kv-layout contiguous is a hard error, not a "
                         "silent no-op")
    ap.add_argument("--metrics-json", default=None,
                    help="write ServeEngine.metrics_snapshot() as JSON "
                         "here after the run (DESIGN.md §12)")
    ap.add_argument("--trace-out", default=None,
                    help="enable span tracing and write the Chrome-trace/"
                         "Perfetto JSON timeline here")
    ap.add_argument("--log-metrics-every", type=int, default=0,
                    help="print a metrics line every N engine steps "
                         "(0 = off)")
    ap.add_argument("--deadline-steps", type=int, default=0,
                    help="per-request engine-step budget from first "
                         "admission (0 = none); expired requests finish "
                         "with finish_reason='deadline'")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="per-request wall-clock budget from submit in "
                         "seconds (0 = none)")
    ap.add_argument("--chaos", default=None,
                    help="deterministic fault injection for this run: "
                         "'point=rate,...' over {pool_alloc, admission, "
                         "preempt, logits, kv_corrupt}; each point is "
                         "capped at 4 fires (DESIGN.md §13)")
    ap.add_argument("--chaos-seed", type=int, default=0)
    ap.add_argument("--snapshot-path", default=None,
                    help="write a crash-consistent engine snapshot here "
                         "after the run (restore with --restore-path)")
    ap.add_argument("--restore-path", default=None,
                    help="start from a snapshot instead of a fresh engine "
                         "(same --arch/--smoke checkpoint; engine-shape "
                         "flags come from the snapshot)")
    args = ap.parse_args(argv)
    if args.prefix_cache and args.kv_layout != "paged":
        ap.error("--prefix-cache requires --kv-layout paged: the contiguous "
                 "layout has no shared physical blocks to dedupe")
    use_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke, dtype="float32",
                     param_dtype="float32", attention_variant=args.variant)
    try:
        validate_kv_dtype(cfg, args.kv_dtype)
    except ValueError as e:
        ap.error(str(e))  # clear rejection (e.g. quantized + recurrent kinds)
    params = init_model(jax.random.PRNGKey(0), cfg)
    if args.restore_path:
        from repro.serve.snapshot import restore_engine
        eng = restore_engine(args.restore_path, params, cfg,
                             trace=bool(args.trace_out))
        carried = sum(r is not None for r in eng.requests) + len(eng.queue)
        print(f"restored engine from {args.restore_path} "
              f"(step {eng.ticks}, {carried} in-flight requests carried)")
    else:
        eng = ServeEngine(params, cfg, slots=args.slots,
                          max_len=args.max_len,
                          chunk_size=args.chunk,
                          temperature=args.temperature,
                          kv_layout=args.kv_layout,
                          page_size=args.page_size or None,
                          pool_blocks=args.pool_blocks or None,
                          kv_dtype=args.kv_dtype,
                          attention_impl=args.attention_impl,
                          prefix_cache=args.prefix_cache,
                          deadline_steps=args.deadline_steps or None,
                          deadline_s=args.deadline_s or None,
                          trace=bool(args.trace_out))
    if args.chaos:
        from repro.serve.faults import (
            ChaosInjector,
            install_fault_injector,
        )
        injector = ChaosInjector.from_spec(args.chaos, seed=args.chaos_seed)
        install_fault_injector(injector)
    else:
        injector = None
    rng = np.random.default_rng(0)
    reqs = [
        eng.submit(
            list(rng.integers(
                1, cfg.vocab_size,
                size=args.prompt_len or rng.integers(4, 12))),
            args.max_new)  # auto rids: never collide with restored ones
        for i in range(args.requests)
    ]
    t0 = time.time()
    if args.log_metrics_every > 0:
        # manual tick loop so progress can be reported mid-run
        every = args.log_metrics_every
        while eng.tick() or eng.queue:
            if eng.ticks % every == 0:
                snap = eng.metrics_snapshot()
                print(f"  [step {eng.ticks}] generated="
                      f"{eng.tokens_generated} queue={len(eng.queue)} "
                      f"preempt={eng.preemptions} "
                      f"ttft_p50={snap['ttft_steps_p50']:.0f} "
                      f"tpot_p50={snap['tpot_steps_p50']:.0f} steps")
    else:
        eng.run()
    dt = time.time() - t0
    # layout/dtype come from the engine: on --restore-path they are the
    # snapshot's, not this invocation's flags
    print(f"variant={args.variant} impl={eng.attention_impl} "
          f"kv={eng.kv_layout}/{eng.kv_dtype} "
          f"requests={len(reqs)} chunk={args.chunk} "
          f"steps={eng.ticks} (prefill {eng.prefill_steps} / decode "
          f"{eng.decode_steps}) generated={eng.tokens_generated} tokens "
          f"({eng.tokens_generated / dt:.1f} tok/s)")
    st = eng.memory_stats()
    if eng.paged:
        print(f"  KV: {st['kv_peak_used_tokens']}/{st['kv_reserved_tokens']} "
              f"peak/reserved tokens "
              f"({st['kv_peak_used_bytes']}/{st['kv_reserved_bytes']} bytes "
              f"at {st['kv_token_bytes']} B/token), "
              f"{st['preemptions']} preemptions")
        if st["prefix_cache"]:
            print(f"  prefix cache: {st['cache_hits']}/{st['cache_lookups']} "
                  f"hits, {st['prefix_hit_tokens']} prompt tokens skipped "
                  f"({st['prefill_flops_skipped']:.3g} FLOPs), "
                  f"{st['cow_copies']} COW copies, "
                  f"{st['kv_cached_blocks']} blocks cached")
    elif eng.kv_dtype != "fp32":
        print(f"  KV: {st['kv_token_bytes']} B/token "
              f"({st['kv_reserved_bytes']} bytes reserved)")
    snap = eng.metrics_snapshot()
    print(f"  TTFT p50/p99 {snap['ttft_steps_p50']:.0f}/"
          f"{snap['ttft_steps_p99']:.0f} steps, TPOT p50/p99 "
          f"{snap['tpot_steps_p50']:.0f}/{snap['tpot_steps_p99']:.0f} steps")
    reasons = {k: v for k, v in snap["finish_reasons"].items() if v}
    if set(reasons) != {"length"} or injector is not None:
        print(f"  finish reasons: {reasons} "
              f"(quarantined: {snap['quarantined']})")
    if injector is not None:
        from repro.serve.faults import install_fault_injector
        install_fault_injector(None)
        fires = {p: injector.fired(p) for p in injector.POINTS
                 if injector.fired(p)}
        print(f"  chaos: injected {fires} over "
              f"{ {p: injector.opportunities(p) for p in fires} } "
              f"opportunities")
        if eng.paged:
            eng.pool.check_consistency()
            print("  pool accounting consistent after chaos "
                  "(used+cached+free == pool_blocks, no dangling keys)")
    if args.snapshot_path:
        meta = eng.save_snapshot(args.snapshot_path)
        print(f"  wrote snapshot {args.snapshot_path} "
              f"({meta['n_leaves']} state leaves, "
              f"{len(meta['requests']) + len(meta['queue'])} in-flight "
              f"requests, cached prefix tier included)")
    if args.metrics_json:
        with open(args.metrics_json, "w") as f:
            json.dump(snap, f, indent=2)
            f.write("\n")
        print(f"  wrote {args.metrics_json}")
    if args.trace_out:
        eng.metrics.write_chrome_trace(args.trace_out)
        print(f"  wrote {args.trace_out} "
              f"({len(eng.metrics.events)} trace events)")
    for r in reqs[:3]:
        print(f"  req {r.rid}: prompt[:4]={r.prompt[:4]} -> out[:8]={r.out[:8]}")
    return reqs


if __name__ == "__main__":
    main()
