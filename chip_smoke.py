"""Smoke run of the main paths on a TPU, through the entry points a user calls.

    python chip_smoke.py               # serving, one chip
    python chip_smoke.py --four-chips  # parallel training, a four-chip host

Serving builds qwen2-0.5b at its published widths and its own bfloat16
dtype from seeded random weights, and serves 8 prompts of 512 tokens with
32 new tokens each through ``ServeEngine``, in three phases:

  1. the default backend (``flash_jnp``) over a contiguous cache;
  2. ``attention_impl="pallas"``, paged, int8 KV, ExpMul;
  3. ``attention_impl="pallas"``, paged, unquantized KV, exact softmax.

Every phase must finish every request, and for one prompt its last-position
prefill logits must agree with ``forward()`` under ``attention_impl="ref"``
at float32 (see ``LOGIT_TOL``). The pallas phases must also dispatch only
the fused kernels (no tableless gather fallback) and resolve every backend
without an interpret-mode note.

``--four-chips`` runs only the parallel-training path: a few
``repro.launch.train`` steps at qwen2-0.5b widths on the host's (2, 2)
(data, model) mesh, then the same steps and seed on a one-device mesh over
the first device. Their losses must agree (see ``LOSS_TOL``).

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``,
printed only when every phase and check passed. The script exits non-zero,
and prints no such line, when JAX finds no TPU, when it is run outside a
checkout of this repository, or when any phase or check fails. The wall
times it prints are smoke figures, not benchmark measurements.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels.registry import AttentionSpec, resolved_backends  # noqa: E402
from repro.launch import train  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models.api import forward, init_model  # noqa: E402
from repro.serve.engine import ServeEngine  # noqa: E402
from repro.serve.metrics import install_dispatch_counters  # noqa: E402

ARCH = "qwen2-0.5b"
SEED = 0
N_PROMPTS, PROMPT_LEN, NEW_TOKENS = 8, 512, 32
CHUNK = 128

# Last-position logits, engine against the float32 reference, as
# max|engine - ref| / max|ref|, per attention variant. Replacing the
# prompt's history while keeping its last token moves the reference by
# ~1.4 of its range at these widths (a CPU run of reference_logits), so a
# lost, stale or foreign page, a wrong mask or a dropped head fails either
# limit.
#   exact:  the engine computes in bfloat16 (relative rounding 2^-9)
#           through 24 layers, and int8 KV adds |err| <= amax/254 per
#           element; a CPU rehearsal at these widths and depth reads
#           1.5e-2. 5e-2 leaves room for the TPU's own rounding.
#   expmul: the power-of-two softmax weights carry up to 0.49 relative
#           error each by design (numerics/log2exp.py), so a rounding that
#           crosses a quantization step halves or doubles a weight, and
#           the blocked kernels' per-tile rescale differs from the
#           reference's one pass by construction (tests/cells.py). The CPU
#           rehearsal reads 5e-2 for the one-pass XLA path and 0.26 for
#           16-token page tiles; 0.5 still fails the ~1.4 of a broken
#           history.
LOGIT_TOL = {"exact": 5e-2, "expmul": 0.5}

# Four-chip losses against one device, absolute. The sharded reductions
# add in another order, and Adam turns a rounding-level difference in a
# near-zero gradient into a learning-rate-sized step, so the curves drift
# apart step by step: on a v5e, 2.6e-5 at the first step and 4.1e-4 by
# the fourth, with the state replicated. 1e-2 is half a percent of the
# ~1.9 the loss falls over these steps; a shard dropped from the forward
# pass or the gradient moves the curve by far more.
LOSS_TOL = 1e-2

PHASES = (
    ("flash_jnp contiguous bf16-KV expmul",
     dict(kv_layout="contiguous"), "expmul"),
    ("pallas paged int8-KV expmul",
     dict(attention_impl="pallas", kv_layout="paged", kv_dtype="int8"),
     "expmul"),
    ("pallas paged unquantized-KV exact",
     dict(attention_impl="pallas", kv_layout="paged", kv_dtype="fp32"),
     "exact"),
)


class SmokeFailure(Exception):
    """A phase or a check did not pass."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def device_report(n_chips):
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX found {len(devs)} {devs[0].platform} device(s)")
    check(len(devs) >= n_chips,
          f"this path needs {n_chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


def dispatch_counts(eng):
    """(kind, impl) -> count of the engine's attention dispatches."""
    out = {}
    for name, labels, value in eng.metrics.dump_values()["counters"]:
        if name == "attention_dispatch_total" and value:
            lab = dict(labels)
            out[(lab["kind"], lab["impl"])] = value
    return out


def reference_logits(params, cfg, prompt):
    """Last-position logits of the plain float32 forward: ``ref`` attention
    (with the cache's quantization codec faked on K/V), float32 weights and
    activations, full-precision matmuls."""
    rcfg = cfg.replace(attention_impl="ref", dtype="float32",
                       param_dtype="float32")
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p, t: forward(p, {"tokens": t}, rcfg))(
            p32, jnp.asarray([prompt], jnp.int32))
    return np.asarray(logits[0, -1], np.float32)


def serve_phase(name, params, cfg, prompts, new_tokens, engine_kw, variant,
                clock):
    pcfg = cfg.replace(attention_variant=variant)
    max_len = len(prompts[0]) + new_tokens
    compile0 = clock.seconds
    t0 = time.perf_counter()
    eng = ServeEngine(params, pcfg, slots=len(prompts), max_len=max_len,
                      chunk_size=CHUNK, **engine_kw)
    # keep the probe request's logits from the prefill step that consumed
    # its last prompt token (the step that sampled its first token)
    probe_logits = {}
    prefill_step = eng._prefill

    def prefill_and_keep(*args):
        logits, state = prefill_step(*args)
        for s, req in enumerate(eng.requests):
            if req is not None and req.rid == probe.rid:
                probe_logits[eng.ticks + 1] = logits[s]
        return logits, state

    eng._prefill = prefill_and_keep
    reqs = [eng.submit(list(p), new_tokens) for p in prompts]
    probe = reqs[0]
    eng.run()
    wall = time.perf_counter() - t0
    compile_s = clock.seconds - compile0

    for r in reqs:
        check(r.done and r.finish_reason == "length"
              and len(r.out) == new_tokens,
              f"{name}: request {r.rid} ended {r.finish_reason!r} with "
              f"{len(r.out)}/{new_tokens} tokens")
    counts = dispatch_counts(eng)
    install_dispatch_counters(None)  # the reference below is not the engine
    if eng.attention_impl == "pallas":
        fused = "pallas" if eng.kv_dtype == "fp32" else "pallas_q"
        check(set(counts) == {("paged_prefill", fused),
                              ("paged_decode", fused)},
              f"{name}: expected only the fused paged {fused!r} prefill and "
              f"decode kernels, dispatched {counts}")
        rows = resolved_backends(AttentionSpec.from_config(eng.cfg),
                                 paged=True)
        bad = [r for r in rows if r["note"] or r["fallback"]]
        check(not bad, f"{name}: backend resolution not clean: {bad}")

    got = np.asarray(probe_logits[probe.first_token_step], np.float32)
    ref = reference_logits(params, eng.cfg, probe.prompt)
    err = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
    tol = LOGIT_TOL[variant]
    check(np.all(np.isfinite(got)), f"{name}: non-finite engine logits")
    check(err <= tol,
          f"{name}: prefill logits differ from the float32 reference by "
          f"{err:.4g} of their range (limit {tol})")
    generated = sum(len(r.out) for r in reqs)
    print(f"phase {name}: {len(reqs)} requests served, {generated} tokens "
          f"generated, compile {compile_s:.1f} s, wall {wall:.1f} s "
          f"(smoke figure, compile included), steps {eng.ticks}, "
          f"dispatches {sorted(counts.items())}, "
          f"logit err {err:.3e} (limit {tol})", flush=True)


def serve_smoke():
    device = device_report(1)
    print(f"device: {device['kind']} x{device['count']} "
          f"({device['platform']})", flush=True)
    clock = CompileClock()
    cfg = get_config(ARCH)
    params = init_model(jax.random.PRNGKey(SEED), cfg)
    rng = np.random.default_rng(SEED)
    prompts = rng.integers(1, cfg.vocab_size, size=(N_PROMPTS, PROMPT_LEN))
    t0 = time.perf_counter()
    for name, kw, variant in PHASES:
        serve_phase(name, params, cfg, prompts, NEW_TOKENS, kw, variant,
                    clock)
    print(f"all {len(PHASES)} phases passed in "
          f"{time.perf_counter() - t0:.1f} s wall (smoke figure)", flush=True)
    return device


def peak_bytes(devs):
    return [d.memory_stats().get("peak_bytes_in_use", 0) for d in devs]


def four_chip_smoke(steps=4, batch=8, seq=128):
    device = device_report(4)
    devs = jax.devices()
    print(f"device: {device['kind']} x{device['count']} "
          f"({device['platform']})", flush=True)
    cfg = get_config(ARCH)
    argv = ["--steps", str(steps), "--batch", str(batch), "--seq", str(seq),
            "--log-every", "1"]
    runs = {}
    for label, mesh_devs in (("2x2 mesh", devs[:4]), ("one device", devs[:1])):
        t0 = time.perf_counter()
        losses = train.main(argv, cfg_override=cfg,
                            mesh=train.make_mesh_for_host(mesh_devs))
        runs[label] = losses
        gib = [b / 2**30 for b in peak_bytes(mesh_devs)]
        print(f"train on {label}: losses {losses}, wall "
              f"{time.perf_counter() - t0:.1f} s (smoke figure, compile "
              f"included), peak memory per device since start "
              f"{[f'{g:.2f} GiB' for g in gib]}", flush=True)
    a, b = (np.asarray(runs[k]) for k in ("2x2 mesh", "one device"))
    check(np.all(np.isfinite(a)) and np.all(np.isfinite(b)),
          "non-finite training loss")
    diff = float(np.max(np.abs(a - b)))
    check(diff <= LOSS_TOL,
          f"2x2-mesh losses differ from one device by {diff:.3g} "
          f"(limit {LOSS_TOL})")
    print(f"losses agree: max |2x2 - one device| = {diff:.3e} "
          f"(limit {LOSS_TOL})", flush=True)
    return device


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the parallel-training path on a "
                         "four-chip host")
    args = ap.parse_args(argv)
    use_compile_cache()
    try:
        device = four_chip_smoke() if args.four_chips else serve_smoke()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
