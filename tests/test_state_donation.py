"""The donation contract (DESIGN.md §6): every jitted call that takes the
engine's state and returns it donates it, so XLA updates the KV pool in
place and the device holds it once.

Each scenario runs twice on the same requests: once as the engine runs,
and once with every state-returning step jitted without donation (the
program as it was before the steps donated). Streams must agree, the
donated run must report no copied state, and the tree each call was
given must be deleted by it."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.api import init_decode_state, init_model, init_paged_state
from repro.models.blocks import block_init_cache, block_init_paged_cache
from repro.serve.engine import ServeEngine
from repro.serve.faults import ChaosInjector, install_fault_injector
from repro.serve.snapshot import restore_engine

# the engine's jitted calls that take the state and return it
STATE_STEPS = ("_prefill", "_decode", "_cow_copy", "_poison", "_scrub")

PAGED = dict(kv_layout="paged", page_size=4, pool_blocks=32)
LAYOUTS = {"paged": PAGED, "contiguous": {}}


@pytest.fixture(scope="module")
def model():
    cfg = get_config("qwen2-0.5b", smoke=True, dtype="float32",
                     param_dtype="float32")
    return init_model(jax.random.PRNGKey(0), cfg), cfg


def _prompts(n=3, seed=0, length=12):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(1, 200, size=length)))
            for _ in range(n)]


def _instrument(eng, donate):
    """Record each state-returning call of ``eng`` with the tree it was
    given; ``donate=False`` runs each step jitted without donation. Returns
    ``(eng, calls, counts)``, ``counts`` the donation counter as it stood
    (a restored engine carries the saved engine's)."""
    calls = []
    counts = _donations(eng)
    for name in STATE_STEPS:
        fn = getattr(eng, name, None)
        if fn is None:
            continue
        if not donate:
            fn = jax.jit(fn.__wrapped__)
        is_step = name in ("_prefill", "_decode")

        def call(*args, _fn=fn, _name=name, _at=1 if is_step else 0):
            calls.append((_name, args[_at]))
            return _fn(*args)

        setattr(eng, name, call)
    return eng, calls, counts


def _donations(eng):
    return eng.metrics_snapshot()["state_donations"]


def _counted(eng, counts):
    """The donation counter's growth since ``counts``."""
    return {k: v - counts[k] for k, v in _donations(eng).items()}


def _streams(reqs):
    return {r.rid: (r.finish_reason, list(r.out)) for r in reqs}


def _plain(params, cfg, layout, donate, tmp_path):
    eng = ServeEngine(params, cfg, slots=2, max_len=64, chunk_size=8,
                      **LAYOUTS[layout])
    run = _instrument(eng, donate)
    reqs = [eng.submit(p, 8) for p in _prompts()]
    eng.run()
    return [run], _streams(reqs)


def _cow(params, cfg, layout, donate, tmp_path):
    # a shared prefix that ends mid-page on the chunk grid: the second
    # request splices the straddling page while the first still holds it,
    # and its first write there forces a copy-on-write
    rng = np.random.default_rng(1)
    shared = rng.integers(1, 200, 24).tolist()
    eng = ServeEngine(params, cfg, slots=2, max_len=96, chunk_size=5,
                      kv_layout="paged", page_size=8, prefix_cache=True)
    run = _instrument(eng, donate)
    reqs = []
    for _ in range(2):
        reqs.append(eng.submit(shared + rng.integers(1, 200, 6).tolist(), 5))
        eng.run()
    names = [name for name, _ in run[1]]
    assert 0 < names.index("_cow_copy"), "COW should follow a donated tick"
    return [run], _streams(reqs)


def _kv_corrupt(params, cfg, layout, donate, tmp_path):
    # poison request 2's last page at its fourth chance; the quarantine
    # scrubs the pages it owned before they rejoin the free list
    install_fault_injector(ChaosInjector(at={"kv_corrupt": [4]},
                                         rids={"kv_corrupt": {2}}))
    try:
        eng = ServeEngine(params, cfg, slots=2, max_len=64, chunk_size=8,
                          kv_layout="paged", page_size=4, pool_blocks=24)
        run = _instrument(eng, donate)
        reqs = [eng.submit(p, 8) for p in _prompts(n=4, seed=1)]
        eng.run(max_steps=500)
    finally:
        install_fault_injector(None)
    assert [r.finish_reason for r in reqs].count("failed") == 1
    names = [name for name, _ in run[1]]
    assert "_scrub" in names and 0 < names.index("_poison")
    return [run], _streams(reqs)


def _snapshot(params, cfg, layout, donate, tmp_path):
    eng = ServeEngine(params, cfg, slots=2, max_len=64, chunk_size=8,
                      **LAYOUTS[layout])
    run = _instrument(eng, donate)
    reqs = [eng.submit(p, 8) for p in _prompts()]
    for _ in range(3):
        eng.tick()
    assert any(r.out for r in reqs), "snapshot point should be mid-flight"
    path = str(tmp_path / f"{layout}-{donate}.npz")
    eng.save_snapshot(path)
    eng.tick()  # the saved engine's next tick still donates its state
    restored = restore_engine(path, params, cfg)
    more = _instrument(restored, donate)
    carried = ([r for r in restored.requests if r is not None]
               + list(restored.queue))
    restored.run()
    finished = [r for r in reqs if r.rid not in {c.rid for c in carried}]
    return [run, more], _streams(finished + carried)


SCENARIOS = {
    "plain-paged": (_plain, "paged"),
    "plain-contiguous": (_plain, "contiguous"),
    "cow": (_cow, "paged"),
    "kv_corrupt": (_kv_corrupt, "paged"),
    "snapshot-paged": (_snapshot, "paged"),
    "snapshot-contiguous": (_snapshot, "contiguous"),
}


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_donated_state_matches_undonated_streams(model, scenario, tmp_path):
    """Each scenario donates every state-returning call, silently, and
    serves the streams the undonated steps serve."""
    params, cfg = model
    run, layout = SCENARIOS[scenario]
    with warnings.catch_warnings():
        # "Some donated buffers were not usable" would mean a leaf that
        # XLA could not alias to an output, copied after all
        warnings.simplefilter("error")
        engines, got = run(params, cfg, layout, True, tmp_path)
    for eng, calls, counts in engines:
        assert calls
        for name, passed in calls:
            assert all(leaf.is_deleted()
                       for leaf in jax.tree.leaves(passed)), (
                f"{name} left the state it was given alive")
        assert _counted(eng, counts) == {"donated": len(calls), "copied": 0}
    reference, want = run(params, cfg, layout, False, tmp_path)
    assert got == want
    for eng, calls, counts in reference:
        # the counter tells the reference's copies from donations
        assert _counted(eng, counts) == {"donated": 0, "copied": len(calls)}


@pytest.mark.parametrize("layout", LAYOUTS)
def test_each_tick_deletes_the_state_it_was_given(model, layout):
    """After a prefill tick and a decode tick, no leaf of the state the
    engine held before either is left on the device."""
    params, cfg = model
    eng = ServeEngine(params, cfg, slots=2, max_len=64, chunk_size=8,
                      **LAYOUTS[layout])
    eng.submit(_prompts(n=1, length=6)[0], 4)  # one chunk, then decode
    for kind in ("prefill", "decode"):
        before = eng.state
        steps = eng.prefill_steps, eng.decode_steps
        eng.tick()
        after = eng.prefill_steps, eng.decode_steps
        assert after[kind == "decode"] == steps[kind == "decode"] + 1
        assert all(leaf.is_deleted() for leaf in jax.tree.leaves(before))
        assert not any(leaf.is_deleted()
                       for leaf in jax.tree.leaves(eng.state))
    assert _donations(eng) == {"donated": 2, "copied": 0}


def test_a_live_view_of_the_state_is_counted_as_copied(model):
    """A NumPy view of a state buffer (on a CPU) keeps XLA from donating
    it: the step copies the pool, and the counter says so."""
    params, cfg = model
    eng = ServeEngine(params, cfg, slots=2, max_len=64, chunk_size=8, **PAGED)
    eng.submit(_prompts(n=1)[0], 4)
    eng.tick()
    view = np.asarray(jax.tree.leaves(eng.state)[0])
    eng.tick()
    del view
    eng.tick()
    assert _donations(eng) == {"donated": 2, "copied": 1}


@pytest.mark.parametrize("arch,kw", [
    ("qwen2-0.5b", {}),
    ("qwen2-0.5b", {"kv_dtype": "int8"}),
    ("minicpm3-4b", {}),
    ("recurrentgemma-2b", {}),
    ("xlstm-1.3b", {}),
])
def test_stacked_state_init_matches_per_layer_cache(arch, kw):
    """The stacked caches hold, for every layer, exactly the one-layer
    cache the block kind initialises, in its dtype."""
    cfg = get_config(arch, smoke=True).replace(**kw)
    dt = jnp.dtype(cfg.dtype)
    states = ((init_decode_state(cfg, 2, 16),
               lambda k: block_init_cache(cfg, k, 2, 16, dt)),
              (init_paged_state(cfg, 2, 8, 4),
               lambda k: block_init_paged_cache(cfg, k, 32, 2, dt)))
    for state, one in states:
        for kind, stacked in zip(cfg.block_pattern, state["caches"]):
            for got, want in zip(jax.tree.leaves(stacked),
                                 jax.tree.leaves(one(kind))):
                assert got.dtype == want.dtype
                assert got.shape[1:] == want.shape
                np.testing.assert_array_equal(
                    np.asarray(got), np.broadcast_to(np.asarray(want),
                                                     got.shape))
