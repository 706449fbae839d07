"""Serving benchmark: one cell, one seed, one process on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's configuration at its published widths from seeded
weights, serves the cell's traffic through ``ServeEngine.submit`` /
``ServeEngine.tick`` for ``--seconds`` after a set-up that compiles and
warms every program the traffic uses, checks what the timed path served
against the plain float32 reference, and prints one JSON line last on
standard output:

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, ["breakdown": {...},] "compared": {...}}

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window's last seconds. It
exits non-zero, with no result line, when JAX finds no TPU or fewer chips
than the cell needs.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import costs, model, spec, trace_reduce, traffic  # noqa: E402

TRACE_SECONDS = 8.0       # the traced part of a --trace 1 window: its end
CHECK_REQUESTS = 8        # finished requests the reference checks, at most
TRACE_DIR = ROOT / ".bench_traces"


class NoChip(Exception):
    """JAX found no TPU, or fewer chips than the cell needs."""


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {len(devs)} {devs[0].platform} "
                     f"device(s)")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def use_compile_cache():
    """JAX's persistent compilation cache where the program keeps it
    (``JAX_COMPILATION_CACHE_DIR``, else a fixed directory in the
    checkout), for every program, so that only a checkout's first run of
    a cell compiles."""
    import jax

    from repro.launch.compile_cache import use_compile_cache as program_rule

    program_rule()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


class CompileCounter:
    """Compilations JAX reports through its monitoring events."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------
def build_engine(cell: spec.Cell, seed: int):
    """The ``ServeEngine`` for ``cell`` with weights from ``seed``."""
    from repro.serve.engine import ServeEngine

    conf, arch = cell.config, cell.arch
    cfg = arch.program_config(conf)
    params = arch.to_program(arch.canonical_weights(seed, conf), conf)
    model.check_program_layout(params, cfg)
    return ServeEngine(params, cfg, seed=0, **conf["engine"])


def busy(eng) -> bool:
    return bool(eng.queue) or any(r is not None for r in eng.requests)


def context(req) -> int:
    """Tokens of ``req`` already in its cache (0 while queued)."""
    n = len(req.prefill_toks)
    return req.pos if req.pos < n else n + len(req.out) - 1


class Track:
    """What the benchmark sees of one request from outside the engine."""

    __slots__ = ("req", "due", "submitted", "first", "last", "seen", "ctx",
                 "split", "gaps")

    def __init__(self, req, due, submitted):
        self.req, self.due, self.submitted = req, due, submitted
        self.first = self.last = None
        self.seen, self.ctx, self.split, self.gaps = 0, 0, [], []


class Driver:
    """Drives the engine tick by tick and records, per tick, its kind, its
    host span, the slots it served and, per request, when tokens came.
    With ``annotate`` each tick and submit is a ``bench.*`` profiler span.
    A tick's kind is known only once it has run, so every tick's span is
    ``bench.tick``; ``pair_traced_ticks`` gives it the record's kind.
    """

    def __init__(self, eng, annotate: bool):
        import jax

        self.eng = eng
        self.annotate = annotate
        self._ann = jax.profiler.TraceAnnotation
        self.tracks: list[Track] = []
        self.live: list[Track] = []
        self.ticks: list[dict] = []

    def _span(self, name):
        return self._ann(name) if self.annotate else contextlib.nullcontext()

    def submit(self, r: traffic.Req, due: float | None = None):
        with self._span("bench.submit"):
            t = time.perf_counter()
            req = self.eng.submit(r.prompt.tolist(), r.max_new)
        tr = Track(req, t if due is None else due, t)
        self.tracks.append(tr)
        self.live.append(tr)
        return tr

    def tick(self) -> dict | None:
        eng = self.eng
        n_prefill, n_ticks = eng.prefill_steps, eng.ticks
        gen0 = eng.tokens_generated
        t0 = time.perf_counter()
        with self._span("bench.tick"):
            eng.tick()
        t1 = time.perf_counter()
        if eng.ticks == n_ticks:
            return None
        kind = "prefill" if eng.prefill_steps > n_prefill else "decode"
        with self._span("bench.record"):
            return self._record(kind, t0, t1, eng.tokens_generated - gen0)

    def _record(self, kind, t0, t1, tokens):
        rows, still = [], []
        for tr in self.live:
            req = tr.req
            ctx = context(req)
            n = ctx - tr.ctx
            out = len(req.out)
            sampled = out > tr.seen
            if n or sampled:
                rows.append((tr.ctx, n, sampled))
            if sampled:
                if tr.first is None:
                    tr.first = t1
                elif out >= 2:
                    tr.split.append(kind == "prefill")
                    tr.gaps.append((t1 - tr.last) * 1e3)
                tr.last = t1
                tr.seen = out
            tr.ctx = ctx
            if not req.done:
                still.append(tr)
        self.live = still
        rec = {"kind": kind, "t0": t0, "t1": t1, "rows": rows,
               "tokens": tokens}
        self.ticks.append(rec)
        return rec

    def sleep(self, seconds):
        with self._span("bench.sleep"):
            time.sleep(max(0.0, seconds))


# ---------------------------------------------------------------------------
# traffic loops
# ---------------------------------------------------------------------------
def warm_up(drv: Driver, vocab: int):
    """Compile and run every program the cell's traffic uses: a prefill
    tick with every slot busy and decode ticks, at the cell's shapes."""
    eng = drv.eng
    rng = np.random.default_rng(0)
    for _ in range(eng.slots):
        eng.submit(rng.integers(1, vocab, 16).tolist(), 3)
    while busy(eng):
        eng.tick()


def fill_first_wave(drv: Driver, wave):
    """Admit the first wave and tick until each of its requests has its
    first token: the steady-state occupancy the traffic starts from."""
    tracks = [drv.submit(r) for r in wave]
    while any(t.first is None and not t.req.done for t in tracks):
        drv.tick()


def open_loop(drv: Driver, reqs, seconds: float, tracer):
    """Submit each request when due, tick while there is work, sleep only
    when the engine is idle. After the window, tick until every request
    due in it has its first token."""
    eng = drv.eng
    t0 = time.perf_counter()
    t_end = t0 + seconds
    due = [t0 + r.due_s for r in reqs]
    tracks, i = [], 0
    while True:
        now = time.perf_counter()
        tracer.maybe_start(now, t_end)
        if now >= t_end:
            break
        while i < len(reqs) and due[i] <= now:
            tracks.append(drv.submit(reqs[i], due[i]))
            i += 1
        if busy(eng):
            drv.tick()
        else:
            drv.sleep(min(due[i] if i < len(reqs) else t_end, t_end) - now)
    t_close = time.perf_counter()
    tracer.close_window()
    while i < len(reqs):
        tracks.append(drv.submit(reqs[i], due[i]))
        i += 1
    while any(t.first is None and not t.req.done for t in tracks):
        drv.tick()
    return t0, t_close, tracks


def offline_loop(drv: Driver, backlog, depth: int, seconds: float, tracer):
    """Keep ``depth`` requests queued and tick for ``seconds``."""
    eng = drv.eng
    t0 = time.perf_counter()
    t_end = t0 + seconds
    j = 0
    while True:
        now = time.perf_counter()
        tracer.maybe_start(now, t_end)
        if now >= t_end:
            break
        while len(eng.queue) < depth:
            if j == len(backlog):
                raise RuntimeError("the offline backlog ran out; raise its "
                                   "count")
            drv.submit(backlog[j])
            j += 1
        drv.tick()
    t_close = time.perf_counter()
    tracer.close_window()
    return t0, t_close


class Tracer:
    """Profiles the last ``TRACE_SECONDS`` of the window (``--trace 1``).
    The profiler stops only after the loop has finished its last tick."""

    def __init__(self, enabled: bool, cell_name: str, seed: int):
        self.enabled = enabled
        self.dir = TRACE_DIR / f"{cell_name}.{seed}"
        self.on = False
        self.host_start = None
        self._ann = None

    def maybe_start(self, now, t_end):
        if not self.enabled or self.on or now < t_end - TRACE_SECONDS:
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.on = True
        self.host_start = time.perf_counter()
        self._ann = jax.profiler.TraceAnnotation("bench.traced")
        self._ann.__enter__()

    def close_window(self):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None

    def stop(self) -> dict | None:
        if not self.on:
            return None
        import jax

        jax.profiler.stop_trace()
        self.on = False
        files = sorted(self.dir.glob("**/*.xplane.pb"))
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        ev = trace_reduce.extract(str(files[-1]))
        shutil.rmtree(self.dir, ignore_errors=True)
        marks = trace_reduce.spans(ev["host"], "bench.traced")
        if not marks:
            raise RuntimeError("the traced window's span is not in the trace")
        ev["window"] = [marks[0][1], marks[0][2]]
        return ev


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------
def pick_requests(tracks, seed: int) -> list:
    """The longest finished request and up to ``CHECK_REQUESTS - 1``
    others drawn from the seed."""
    done = [t for t in tracks if t.req.finish_reason == "length"]
    if not done:
        return []
    longest = max(done, key=lambda t: len(t.req.out))
    rest = [t for t in done if t is not longest]
    order = np.random.default_rng([2, int(seed)]).permutation(len(rest))
    return [longest] + [rest[k] for k in order[:CHECK_REQUESTS - 1]]


def served_gaps(cell: spec.Cell, seed: int, picked, control: bool):
    """Per served token of the ``picked`` requests, the reference's gap
    (and the float8 control's, with ``control``), as two numpy arrays.
    The reference is the file the configuration names."""
    import jax.numpy as jnp

    conf = cell.config
    sz = cell.arch.sizes(conf)
    S = conf["engine"]["max_len"]
    rows = cell.traffic["output_tokens"]["max"]
    tile = conf["engine"]["page_size"]
    w = cell.arch.canonical_weights(seed, conf)
    gaps, cgaps = [], []
    for t in picked:
        prompt, out = t.req.prompt, t.req.out
        P, n = len(prompt), len(out)
        toks = np.zeros(S, np.int32)
        seq = (prompt + out)[:S]
        toks[:len(seq)] = seq
        split = np.zeros(S, bool)
        split[P:P + len(t.split)] = t.split
        served = np.zeros(rows, np.int32)
        served[:n] = out
        g, c = cell.reference.served_gaps(
            w, jnp.asarray(toks), jnp.asarray(split), jnp.int32(P - 1),
            jnp.asarray(served), jnp.int32(n), sz=tuple(sorted(sz.items())),
            variant=conf["attention_variant"], tile=tile, rows=rows,
            control=control)
        gaps.append(np.asarray(g)[:n])
        cgaps.append(np.asarray(c)[:n])
    empty = np.zeros(0, np.float32)
    return (np.concatenate(gaps) if gaps else empty,
            np.concatenate(cgaps) if control and cgaps else None)


def gap_readings(gaps) -> dict:
    """The numbers a cell's limits may name: the widest gap and the mean
    gap over the checked served tokens (logit units); with no token
    checked both are infinite, so no limit holds."""
    if not len(gaps):
        return {"max_logit_gap": float("inf"),
                "mean_logit_gap": float("inf")}
    return {"max_logit_gap": float(np.max(gaps)),
            "mean_logit_gap": float(np.mean(gaps))}


def judge(limits: dict, readings: dict, foreign: int, failed: int):
    """(compared, correct): each number compared beside its limit, and
    whether every one keeps to it."""
    compared = {name: {"value": readings[name], "limit": limit}
                for name, limit in limits.items()}
    compared["foreign_dispatches"] = {"value": foreign, "limit": 0}
    compared["failed_requests"] = {"value": failed, "limit": 0}
    return compared, all(c["value"] <= c["limit"] for c in compared.values())


def dispatch_counts(eng) -> dict:
    out = {}
    for name, labels, value in eng.metrics.dump_values()["counters"]:
        if name == "attention_dispatch_total" and value:
            lab = dict(labels)
            out[f"{lab['kind']}:{lab['impl']}"] = value
    return out


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------
MISS_MS = 1e9  # a request that failed or never came, in a latency tail


def percentile(values, q) -> float:
    """Linear-interpolated percentile; a tail that reaches a miss reads
    ``MISS_MS`` (JSON has no infinity)."""
    if not values:
        return MISS_MS
    v = float(np.percentile(np.asarray(values, float), q))
    return v if np.isfinite(v) else MISS_MS


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             control: bool = False, require_tpu: bool = True,
             fault=None) -> dict:
    """One run of ``cell``; returns the result object (and more under
    ``"extra"``). ``fault``, for tests, is called with the engine after
    warm-up and may break it. With ``control`` the float8 reference takes
    the program's place in the comparison: ``compared`` and ``correct``
    are the control's, the program's own are under ``"extra"``."""
    import jax

    device = device_info(cell.chips, require_tpu)
    compiles = CompileCounter()
    peak = costs.peaks(device["kind"]) if require_tpu else None
    conf, mix = cell.config, cell.traffic
    sz = cell.arch.sizes(conf)
    eng = build_engine(cell, seed)
    drv = Driver(eng, annotate=trace)
    warm_up(drv, sz["vocab"])
    if fault is not None:
        fault(eng)
    slots = eng.slots
    tracer = Tracer(trace, cell.name, seed)
    if mix["loop"] == "open":
        rate = cell.cell["rate_per_s"]
        reqs = traffic.open_requests(mix, rate, seconds, seed, sz["vocab"])
        wave, _ = traffic.offline_requests(mix, cell.cell["in_flight"], seed,
                                           sz["vocab"], 0)
        fill_first_wave(drv, wave)
        n_warm = len(drv.ticks)
        c0 = compiles.count
        t_setup = time.perf_counter()
        t0, t_close, tracks = open_loop(drv, reqs, seconds, tracer)
    elif mix["loop"] == "offline":
        wave, backlog = traffic.offline_requests(
            mix, slots, seed, sz["vocab"], mix["backlog_count"])
        fill_first_wave(drv, wave)
        n_warm = len(drv.ticks)
        c0 = compiles.count
        t_setup = time.perf_counter()
        t0, t_close = offline_loop(drv, backlog, mix["queue_depth"], seconds,
                                   tracer)
        tracks = [t for t in drv.tracks if t.req.admit_time is not None
                  and t.req.admit_time < t_close]
    else:
        raise ValueError(f"unknown loop kind {mix['loop']!r}")
    in_window_compiles = compiles.count - c0
    ev = tracer.stop()
    ticks = [t for t in drv.ticks[n_warm:] if t["t0"] >= t0]
    window_ticks = [t for t in ticks if t["t1"] <= t_close + 1e-9]
    mem = jax.devices()[0].memory_stats() or {}
    memory_peak = int(mem.get("peak_bytes_in_use", 0))
    dispatched = dispatch_counts(eng)
    failed = sum(1 for t in tracks if t.req.finish_reason not in
                 (None, "length"))

    # end-to-end metrics
    e2e = {}
    if mix["loop"] == "open":
        ttft = [(t.first - t.due) * 1e3 if t.first is not None
                else float("inf") for t in tracks]
        itl = [g for t in tracks for g in t.gaps]
        e2e["ttft_p95_ms"] = percentile(ttft, 95)
        late = [(t.submitted - t.due) * 1e3 for t in tracks]
        log(f"open loop: {len(tracks)} requests due in {seconds} s at "
            f"{cell.cell['rate_per_s']}/s; ttft p50 {percentile(ttft, 50):.1f}"
            f" ms p95 {e2e['ttft_p95_ms']:.1f} ms; inter-token gap p50 "
            f"{percentile(itl, 50):.2f} ms p95 {percentile(itl, 95):.2f} ms "
            f"({len(itl)} gaps); submitted late by p50 "
            f"{percentile(late, 50):.1f} ms, max {max(late):.1f} ms (a tick "
            f"in progress delays submission; the wait counts in ttft)")
    else:
        span = window_ticks[-1]["t1"] - t0 if window_ticks else seconds
        toks = sum(t["tokens"] for t in window_ticks)
        e2e["output_tokens_per_s"] = toks / span
        log(f"offline: {toks} tokens in {span:.3f} s, "
            f"{len(window_ticks)} ticks")
    e2e["setup_s"] = t_setup - T_START
    n_kind = {k: sum(t["kind"] == k for t in window_ticks)
              for k in ("prefill", "decode")}
    n_ticks = max(1, len(window_ticks))
    slot_fill = sum(len(t["rows"]) for t in window_ticks) / n_ticks / slots
    token_fill = sum(L + n for t in window_ticks for L, n, _ in t["rows"]) / (
        n_ticks * slots * conf["engine"]["max_len"])
    log(f"window: {len(window_ticks)} ticks {n_kind}, compilations in the "
        f"window {in_window_compiles}, preemptions {eng.preemptions}, "
        f"pool in use (mean over ticks) {100 * slot_fill:.1f}% of slots, "
        f"{100 * token_fill:.1f}% of token rows, memory peak {memory_peak} "
        f"B, dispatched {dispatched}")

    traced = pair_traced_ticks(window_ticks, ev, tracer.host_start)
    run = RunData(cell=cell, sz=sz, peak=peak, slots=slots,
                  ticks=window_ticks, tracks=tracks, trace=ev,
                  traced_ticks=traced)
    per_layer = {}
    if trace and peak is not None:
        for m in cell.per_layer:
            v = cell.layer_reader(m["name"])(run)
            if v is not None:
                per_layer[m["name"]] = v

    # free the program's state before the reference runs
    picked = pick_requests(drv.tracks, seed)
    del eng, drv.eng
    drv.live = []
    gc.collect()
    gaps, cgaps = served_gaps(cell, seed, picked, control)
    n_checked = len(gaps)
    readings = gap_readings(gaps)
    foreign = sum(v for k, v in dispatched.items()
                  if k not in ("paged_prefill:pallas", "paged_decode:pallas"))
    limits = cell.cell["limits"]
    compared, program_correct = judge(limits, readings, foreign, failed)
    correct = program_correct
    log(f"checked {n_checked} served tokens of {len(picked)} requests: "
        + ", ".join(f"{k} {v}" for k, v in readings.items()))
    if control:
        compared, correct = judge(limits, gap_readings(cgaps), foreign,
                                  failed)
    metrics_src = per_layer if trace else e2e
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    result = {
        "correct": bool(correct),
        "attempted": len(tracks),
        "failed": failed,
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in metrics_src.items() if k in units},
        "device": dict(device, memory_peak_bytes=memory_peak),
    }
    if trace and ev is not None:
        lo, hi = ev["window"]
        result["device"]["busy_s"] = trace_reduce.busy_ns(ev["ops"], lo,
                                                          hi) / 1e9
        result["device"]["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(ev["ops"], lo, hi),
            "idle_gaps": trace_reduce.idle_gaps(
                ev["ops"], tick_kinds_named(ev["host"], traced), lo, hi)}
    result["compared"] = compared
    result["extra"] = {
        "readings": readings, "per_layer": per_layer, "e2e": e2e,
        "program_correct": program_correct,
        "control": None if cgaps is None else gap_readings(cgaps)}
    return result


def pair_traced_ticks(ticks, ev, host_start):
    """[(tick record, (start_ns, end_ns) of its ``bench.tick`` span in the
    trace)] for the ticks of the traced window, matched by order; [] when
    the counts do not agree (then no trace-based metric is read)."""
    if ev is None:
        return []
    lo, hi = ev["window"]
    spans = [s for s in trace_reduce.spans(ev["host"], "bench.tick")
             if s[1] >= lo and s[2] <= hi]
    mine = [t for t in ticks if t["t0"] >= host_start]
    if len(spans) != len(mine):
        log(f"trace: {len(spans)} tick spans for {len(mine)} ticks; "
            f"trace-based metrics left out")
        return []
    return [(t, (s[1], s[2])) for t, s in zip(mine, spans)]


def tick_kinds_named(host, traced):
    """The host spans with each paired tick's span named by its kind
    (``bench.tick.prefill`` / ``bench.tick.decode``), for the idle gaps."""
    kind = {s: t["kind"] for t, s in traced}
    return [[f"bench.tick.{kind[(s, s + d)]}" if (s, s + d) in kind else n,
             s, d] for n, s, d in host]


class RunData:
    """What a per-layer metric's reader gets: the window's ticks and
    requests as the benchmark saw them, the cell (``cell.arch`` counts
    its work) and its shapes, the chip's peaks, and the reduced trace
    (``None`` without ``--trace 1``)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    cell = spec.load_cell(args.workload)
    use_compile_cache()
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(f"bench: {e}")
        return 3
    for name, c in result["compared"].items():
        log(f"compared {name}: {json.dumps(c)}")
    result.pop("extra")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
