"""Observability layer (DESIGN.md §12): metrics registry, request
lifecycle tracing, per-spec dispatch counters, and the single-ownership
contract between ``memory_stats()`` and the registry."""
import contextlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.registry import AttentionSpec, dispatch_decode
from repro.models.api import init_model
from repro.serve.engine import ServeEngine
from repro.serve.metrics import (
    Histogram,
    MetricsRegistry,
    install_dispatch_counters,
)

ARCH = "qwen2-0.5b"


@pytest.fixture(scope="module")
def setup():
    cfg = get_config(ARCH, smoke=True, dtype="float32",
                     param_dtype="float32")
    params = init_model(jax.random.PRNGKey(0), cfg)
    return params, cfg


@pytest.fixture(scope="module")
def traced_run(setup):
    """One traced paged serve run shared by the lifecycle/trace tests."""
    params, cfg = setup
    eng = ServeEngine(params, cfg, slots=2, max_len=64, chunk_size=8,
                      kv_layout="paged", page_size=4, trace=True)
    rng = np.random.default_rng(3)
    reqs = [eng.submit(list(rng.integers(1, 200, size=n)), 5, rid=i)
            for i, n in enumerate((11, 4, 19))]
    eng.run()
    assert all(r.done for r in reqs)
    return eng, reqs


# -- histograms ---------------------------------------------------------------

def test_histogram_quantiles_match_numpy():
    """On integer samples and unit bucket edges the histogram quantile is
    exactly numpy's inverted-CDF percentile (the TTFT/TPOT case)."""
    rng = np.random.default_rng(0)
    data = rng.integers(1, 100, size=257)
    h = Histogram(buckets=tuple(range(1, 129)))
    for v in data:
        h.record(int(v))
    for q in (0.50, 0.90, 0.99):
        want = np.percentile(data, 100 * q, method="inverted_cdf")
        assert h.quantile(q) == float(want), (q, h.quantile(q), want)
    assert h.count == len(data)
    assert h.total == data.sum()
    assert np.isclose(h.mean, data.mean())


def test_histogram_overflow_and_empty():
    h = Histogram(buckets=(1, 2, 4))
    assert np.isnan(h.quantile(0.5))        # empty -> NaN, never a crash
    h.record(3)
    h.record(100)                           # above the last edge
    assert h.overflow == 1 and h.count == 2
    assert h.quantile(0.5) == 4.0           # first covering edge
    assert h.quantile(0.99) == 4.0          # overflow reports the ceiling


def test_registry_prometheus_exposition():
    m = MetricsRegistry()
    m.counter("requests_total", kind="a").inc(3)
    m.gauge("depth").set(7)
    m.histogram("lat", buckets=(1, 2)).record(1)
    text = m.prometheus_text()
    assert '# TYPE requests_total counter' in text
    assert 'requests_total{kind="a"} 3' in text
    assert "depth 7" in text
    assert 'lat_bucket{le="1"} 1' in text
    assert 'lat_bucket{le="+Inf"} 1' in text
    assert "lat_count 1" in text


# -- request lifecycle --------------------------------------------------------

def test_lifecycle_event_ordering(traced_run):
    """Per request: admit (B) < first_token <= finish (E), in both
    timestamps and engine steps; every engine step is one well-formed
    ``serve.tick`` span holding its dispatch."""
    eng, reqs = traced_run
    evs = eng.metrics.events
    for r in reqs:
        per = [e for e in evs if e.get("tid") == r.rid and e["pid"] == 2]
        phases = [e["ph"] for e in per]
        assert phases[0] == "B" and phases[-1] == "E", phases
        first_tok = next(e for e in per if e["name"] == "first_token")
        b, e = per[0], per[-1]
        assert b["ts"] <= first_tok["ts"] <= e["ts"]
        assert b["args"]["step"] < first_tok["args"]["step"] <= \
            e["args"]["step"]
        assert first_tok["args"]["step"] == r.first_token_step
        assert b["args"]["step"] == r.admit_step
    spans = [e for e in evs if e["ph"] == "X"]
    assert all(e["dur"] >= 0 for e in spans)
    ticks = [e for e in spans if e["name"] == "serve.tick"]
    dispatches = [e for e in spans
                  if e["name"].startswith("serve.dispatch.")]
    assert len(dispatches) == eng.ticks
    assert {e["name"] for e in dispatches} == {"serve.dispatch.prefill",
                                               "serve.dispatch.decode"}
    for d in dispatches:
        holders = [t for t in ticks if t["ts"] <= d["ts"]
                   and d["ts"] + d["dur"] <= t["ts"] + t["dur"]]
        assert len(holders) == 1
    assert [t["args"]["step"] for t in ticks
            if any(t["ts"] <= d["ts"] <= t["ts"] + t["dur"]
                   for d in dispatches)] == list(range(1, eng.ticks + 1))


def test_ttft_tpot_histograms_match_request_fields(traced_run):
    """The engine's TTFT histogram carries exactly the bench convention
    (first_token_step - admit_step + 1) for every finished request, and
    TPOT holds one sample per non-first token."""
    eng, reqs = traced_run
    snap = eng.metrics_snapshot()
    ttfts = [r.first_token_step - r.admit_step + 1 for r in reqs]
    h = snap["histograms"]["serve_ttft_steps"]
    assert h["count"] == len(reqs)
    assert h["sum"] == sum(ttfts)
    for q, key in ((50, "ttft_steps_p50"), (99, "ttft_steps_p99")):
        want = float(np.percentile(ttfts, q, method="inverted_cdf"))
        assert snap[key] == want, (key, snap[key], want)
    tpot = snap["histograms"]["serve_tpot_steps"]
    assert tpot["count"] == eng.tokens_generated - len(reqs)
    assert np.isfinite(snap["tpot_steps_p50"])


def test_chrome_trace_valid_json_matched_events(traced_run, tmp_path):
    eng, reqs = traced_run
    path = tmp_path / "trace.json"
    eng.metrics.write_chrome_trace(path)
    tr = json.loads(path.read_text())
    evs = tr["traceEvents"]
    assert evs and all(e["ph"] in ("X", "B", "E", "i", "M") for e in evs)
    n_b = sum(1 for e in evs if e["ph"] == "B")
    n_e = sum(1 for e in evs if e["ph"] == "E")
    assert n_b == n_e == len(reqs)          # every lifecycle closed
    assert all(e["dur"] >= 0 for e in evs if e["ph"] == "X")
    # track-name metadata labels the engine and per-request rows
    names = {(e["pid"], e["tid"]): e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert names[(1, 0)] == "engine steps"
    assert all((2, r.rid) in names for r in reqs)


def test_disabled_mode_records_no_spans(setup):
    """With tracing off (the default) no events are recorded, yet the
    snapshot stays fully formed — counters, histograms, percentiles."""
    params, cfg = setup
    eng = ServeEngine(params, cfg, slots=2, max_len=64, chunk_size=8)
    eng.submit([1, 2, 3, 4, 5, 6, 7], 4)
    eng.run()
    assert eng.metrics.events == []
    snap = eng.metrics_snapshot()
    assert snap["trace_events"] == 0
    assert snap["counters"]["serve_tokens_generated_total"] == 4
    assert np.isfinite(snap["ttft_steps_p50"])
    assert json.loads(json.dumps(snap))  # JSON-able end to end


# -- profiler spans (serve.<phase>) -----------------------------------------

class RecordingAnnotations:
    """An ``annotate`` factory that logs each span's entry as (depth,
    name, args), in the order the engine enters them."""

    def __init__(self):
        self.log = []
        self.stack = []

    @contextlib.contextmanager
    def __call__(self, name, **args):
        self.log.append((len(self.stack), name, args))
        self.stack.append(name)
        try:
            yield
        finally:
            self.stack.pop()


def _tick_children(log):
    """The phases directly under each ``serve.tick``, one list per tick."""
    out = []
    for depth, name, _ in log:
        if name == "serve.tick":
            out.append([])
        elif depth == 1 and out:
            out[-1].append(name)
    return out


def _paged_engine(setup, metrics):
    params, cfg = setup
    return ServeEngine(params, cfg, slots=2, max_len=64, chunk_size=8,
                       kv_layout="paged", page_size=4, metrics=metrics)


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_each_tick_is_one_span_tree(setup, kind):
    """A paged tick of either kind enters exactly one ``serve.tick`` whose
    phases run in order: one dispatch of the tick's kind, then one wait
    for its sampled tokens."""
    rec = RecordingAnnotations()
    eng = _paged_engine(setup, MetricsRegistry(annotate=rec))
    assert eng.metrics.annotate is rec      # the engine keeps a given one
    eng.submit(list(range(1, 12)), 3)       # 11 tokens: two prefill chunks
    while True:
        n_kind = (eng.prefill_steps if kind == "prefill"
                  else eng.decode_steps)
        rec.log.clear()
        eng.tick()
        if (eng.prefill_steps if kind == "prefill"
                else eng.decode_steps) > n_kind:
            break
    assert rec.log[0][:2] == (0, "serve.tick")
    assert rec.log[0][2]["step"] == eng.ticks
    assert _tick_children(rec.log) == [[
        "serve.admit", "serve.reserve", "serve.inputs",
        f"serve.dispatch.{kind}", "serve.sample", "serve.wait",
        "serve.guard", "serve.bookkeep"]]
    assert all(depth <= 1 for depth, _, _ in rec.log)


def test_engine_spans_default_to_profiler_annotations(setup):
    eng = _paged_engine(setup, None)
    assert eng.metrics.annotate is jax.profiler.TraceAnnotation
    eng.submit([1, 2, 3], 2)
    eng.run()                                # runs inside TraceAnnotations
    assert eng.metrics.events == []


class _CountingNumpy:
    """``numpy`` for the engine module, logging the span each host pull of
    a device array (``np.asarray(jax.Array)``) happens in."""

    def __init__(self, rec):
        self.rec = rec
        self.pulls = []

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, x, *a, **k):
        if isinstance(x, jax.Array):
            self.pulls.append(self.rec.stack[-1] if self.rec.stack else None)
        return np.asarray(x, *a, **k)


@pytest.mark.parametrize("trace", [False, True])
def test_spans_add_no_device_to_host_pull(setup, monkeypatch, trace):
    """The spans' own work pulls nothing from the device: a stepped tick
    pulls twice, the sampled tokens in ``serve.wait`` and the guard's
    finiteness vector in ``serve.guard``, traced or not."""
    import repro.serve.engine as engine_mod

    rec = RecordingAnnotations()
    eng = _paged_engine(setup, MetricsRegistry(annotate=rec, trace=trace))
    shim = _CountingNumpy(rec)
    monkeypatch.setattr(engine_mod, "np", shim)
    eng.submit(list(range(1, 12)), 4)
    while eng.tick():
        pass
    assert eng.ticks == 5                    # two prefill ticks, 3 decode
    assert shim.pulls == ["serve.wait", "serve.guard"] * eng.ticks


# -- dispatch counters (kernels/registry.py hook) -----------------------------

def test_eager_dispatch_counters_per_spec():
    """Eager dispatch calls count 1:1 per (kind, resolved impl): fused
    pallas and gather specs land in separate counters."""
    m = MetricsRegistry()
    install_dispatch_counters(m)
    try:
        rng = np.random.default_rng(0)
        B, H, Hkv, D, S = 1, 2, 1, 8, 16
        q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
        lengths = jnp.asarray([4], jnp.int32)
        gather = AttentionSpec(impl="flash_jnp")   # decode -> "xla"
        fused = AttentionSpec(impl="pallas")       # decode -> "pallas"
        for _ in range(3):
            dispatch_decode(gather, q, k, v, lengths)
        dispatch_decode(fused, q, k, v, lengths)
        common = dict(kind="decode", variant="exact", kv_dtype="fp32",
                      layout="contiguous")
        assert m.counter_value("attention_dispatch_total", impl="xla",
                               **common) == 3
        assert m.counter_value("attention_dispatch_total", impl="pallas",
                               **common) == 1
    finally:
        install_dispatch_counters(None)


def test_tableless_paged_dispatch_counts_as_gather():
    """A fused paged backend dispatched without block tables runs its
    gather-then-kernel form, and the counters say so."""
    from repro.kernels.paged import slot_rows
    from repro.kernels.registry import dispatch_paged_decode

    m = MetricsRegistry()
    install_dispatch_counters(m)
    try:
        rng = np.random.default_rng(0)
        B, H, Hkv, D, ps, nblk = 1, 2, 1, 8, 4, 4
        q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
        pool = jnp.asarray(rng.standard_normal((nblk * ps, Hkv, D)),
                           jnp.float32)
        bt = jnp.asarray([[2, 0, 3, 1]], jnp.int32)
        lengths = jnp.asarray([13], jnp.int32)
        spec = AttentionSpec(impl="pallas")
        rows = slot_rows(bt, ps)
        dispatch_paged_decode(spec, q, pool, pool, rows, lengths)
        dispatch_paged_decode(spec, q, pool, pool, rows, lengths,
                              block_tables=bt, page_size=ps)
        common = dict(kind="paged_decode", variant="exact", kv_dtype="fp32",
                      layout="paged")
        assert m.counter_value("attention_dispatch_total",
                               impl="gather_pallas", **common) == 1
        assert m.counter_value("attention_dispatch_total", impl="pallas",
                               **common) == 1
    finally:
        install_dispatch_counters(None)


# -- single-ownership contract ------------------------------------------------

def test_memory_stats_equals_registry_after_preemptions(setup):
    """After a preemption-heavy tight-pool run, the legacy surfaces
    (memory_stats, pool.stats, engine attributes) must equal the registry
    counters exactly — there is only one set of books."""
    params, cfg = setup
    rng = np.random.default_rng(1)
    prompts = [list(rng.integers(1, 200, size=n)) for n in (9, 21, 6, 13)]
    eng = ServeEngine(params, cfg, slots=3, max_len=64, chunk_size=8,
                      kv_layout="paged", page_size=4, pool_blocks=12)
    reqs = [eng.submit(p, 6, rid=i) for i, p in enumerate(prompts)]
    eng.run()
    assert all(r.done for r in reqs)
    assert eng.preemptions > 0              # the point of the tight pool

    st = eng.memory_stats()
    c = eng.metrics.snapshot()["counters"]
    assert st["preemptions"] == c["serve_preemptions_total"]
    assert st["recompute_tokens"] == c["serve_recompute_tokens_total"]
    assert st["evictions"] == c["pool_evictions_total"]
    assert st["alloc_failures"] == c["pool_alloc_failures_total"]
    ps = eng.pool.stats
    assert ps.evictions == c["pool_evictions_total"]
    assert ps.allocs == c["pool_allocs_total"]
    assert ps.frees == c["pool_frees_total"]
    assert ps.cow_copies == c.get("pool_cow_copies_total", 0)
    assert ps.cache_hits == c["pool_cache_hits_total"]
    assert ps.hit_blocks == c["pool_hit_blocks_total"]
    assert eng.ticks == c["serve_steps_total"]
    assert eng.tokens_generated == c["serve_tokens_generated_total"]
    assert eng.prefix_hit_tokens == c["serve_prefix_hit_tokens_total"]
    # engine and pool share one registry: residency gauges agree live
    g = eng.metrics.snapshot()["gauges"]
    assert st["kv_used_blocks"] == g["pool_used_blocks"]
    assert st["kv_cached_blocks"] == g["pool_cached_blocks"]
    assert st["kv_free_blocks"] == g["pool_free_blocks"]
    assert st["kv_peak_used_tokens"] == g["serve_peak_kv_used_tokens"]
