"""The comparison that decides ``correct``, at a size a test run holds.

A tiny configuration of the same decoder runs the whole of a benchmark
run on the CPU (the harness's look for a chip skipped, Pallas kernels
interpreted): traffic, warm-up, the timed loop, the reference over the
served tokens. A sound run is correct. The float8 control, put in the
program's place in the same comparison, is not; nor is a run with any
fault a served cell can have:

  * a step that returns its state unchanged (no KV written);
  * half of the batch left out (its rows' logits zeroed);
  * a token altered where it is produced (sampling off by one).

The exchange between chips is not a fault these one-chip cells can have.
The number compared is the mean gap of the served tokens' reference
logits below the reference's best (PERF.md says why not the widest gap
under ExpMul). The tiny limit lies between this size's own readings on
seeds 11-16 (sound at most 0.0015, the control at least 0.0102, the
faults 0.2 and more); the cells' own limits come from chip runs at their
sizes (PERF.md).

The same root holds a second architecture brought as files alone (its
``bench/archs/<model_type>.py``, its reference, its configuration and
cell): the harness builds, checks and counts with them, with no edit.
"""
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import costs, run, spec  # noqa: E402

TINY_LIMIT = 0.004
SECONDS = 2.0


# A second architecture brought as files alone: the qwen2 architecture and
# reference under another model_type, each call marked.
WRAPPED_ARCH = """
from bench.archs import qwen2 as base

CALLED = set()


def _marked(name):
    def call(*args, **kw):
        CALLED.add(name)
        return getattr(base, name)(*args, **kw)
    return call


for _name in ("sizes", "program_config", "canonical_weights", "to_program",
              "step_flops", "attention_kernel_cost"):
    globals()[_name] = _marked(_name)
"""
WRAPPED_REFERENCE = """
from bench import reference as base, reference_core

CALLED = set()


def hidden(*args):
    CALLED.add("hidden")
    return base.hidden(*args)


def logits(*args):
    CALLED.add("logits")
    return base.logits(*args)


served_gaps = reference_core.served_gaps(hidden, logits)
"""


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("tinybench")
    for sub in ("configs", "traffic", "cells", "archs"):
        (root / "bench" / sub).mkdir(parents=True)
    shutil.copy(ROOT / "bench/archs/qwen2.py", root / "bench/archs")
    shutil.copy(ROOT / "bench/reference.py", root / "bench")
    shutil.copytree(ROOT / "bench/layer_metrics", root / "bench/layer_metrics")
    (root / "bench/archs/wrapped.py").write_text(WRAPPED_ARCH)
    (root / "bench/wrapped_reference.py").write_text(WRAPPED_REFERENCE)
    conf = json.loads((ROOT / "bench/configs/qwen2-0.5b.json").read_text())
    conf.update(name="tiny", hidden_size=64, num_attention_heads=4,
                num_key_value_heads=2, intermediate_size=128, vocab_size=256,
                num_hidden_layers=2)
    conf["engine"].update(slots=4, max_len=256, chunk_size=32)
    (root / "bench/configs/tiny.json").write_text(json.dumps(conf))
    conf.update(name="wrapped", model_type="wrapped",
                reference="bench/wrapped_reference.py")
    (root / "bench/configs/wrapped.json").write_text(json.dumps(conf))
    mix = {"loop": "open", "size_seed": 1,
           "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                             "min": 8, "max": 100},
           "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                             "min": 4, "max": 16}}
    (root / "bench/traffic/tinychat.json").write_text(json.dumps(mix))
    for name in ("tiny.chat", "wrapped.chat"):
        (root / f"bench/cells/{name}.json").write_text(json.dumps(
            {"rate_per_s": 3.0, "in_flight": 2,
             "limits": {"mean_logit_gap": TINY_LIMIT}}))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": f"{c}.chat", "config": c,
                       "traffic": "tinychat", "chips": 1, "why": "test"}
                      for c in ("tiny", "wrapped")],
        "end_to_end": [{"name": "ttft_p95_ms", "unit": "ms"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": []}))
    return root


@pytest.fixture(scope="module")
def tiny(tiny_root):
    return spec.load_cell("tiny.chat", tiny_root)


def _run(cell, seed=11, fault=None, control=False):
    return run.run_cell(cell, seed, SECONDS, False, control=control,
                        require_tpu=False, fault=fault)


def _wrap_steps(eng, post):
    for name in ("_prefill", "_decode"):
        step = getattr(eng, name)
        setattr(eng, name, lambda *a, step=step: post(a, *step(*a)))


def keep_state(eng):
    _wrap_steps(eng, lambda args, logits, state: (logits, args[1]))


def drop_half(eng):
    _wrap_steps(eng, lambda args, logits, state:
                (logits.at[::2].set(0.0), state))


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_sound_run_is_correct_and_control_is_not(tiny, seed):
    res = _run(tiny, seed, control=True)
    gap = res["extra"]["readings"]["mean_logit_gap"]
    assert res["extra"]["program_correct"], res["extra"]["readings"]
    assert 0 <= gap <= TINY_LIMIT
    # with control=True the control's gaps are what is compared
    assert res["correct"] is False
    assert res["compared"]["mean_logit_gap"]["value"] == \
        res["extra"]["control"]["mean_logit_gap"] > TINY_LIMIT
    assert res["compared"]["foreign_dispatches"]["value"] == 0
    assert res["attempted"] == round(3.0 * SECONDS)
    assert set(res["metrics"]) == {"ttft_p95_ms", "setup_s"}


def test_sound_run_compares_the_program(tiny):
    res = _run(tiny, 14)
    assert res["correct"], res["compared"]
    assert res["compared"]["mean_logit_gap"]["value"] == \
        res["extra"]["readings"]["mean_logit_gap"] <= TINY_LIMIT


def _finished(lengths):
    from types import SimpleNamespace
    return [SimpleNamespace(req=SimpleNamespace(out=[0] * n,
                                                finish_reason="length"))
            for n in lengths]


@pytest.mark.parametrize("n", [1, 5, 8, 30])
def test_pick_takes_the_longest_and_others_drawn_from_the_seed(n):
    tracks = _finished([5, 9, 30, 2, 7, 4] * 5)[:n]
    picked = run.pick_requests(tracks, 2**33 + 5)
    assert len(picked) == min(n, run.CHECK_REQUESTS)
    assert picked[0] is max(tracks, key=lambda t: len(t.req.out))
    assert len({id(t) for t in picked}) == len(picked)
    assert picked == run.pick_requests(tracks, 2**33 + 5)


@pytest.mark.parametrize("fault", [keep_state, drop_half])
def test_broken_step_is_not_correct(tiny, fault):
    res = _run(tiny, fault=fault)
    assert not res["correct"]
    assert res["compared"]["mean_logit_gap"]["value"] > TINY_LIMIT


def test_altered_token_is_not_correct(tiny, monkeypatch):
    import repro.serve.engine as engine

    sample = engine.sample_tokens

    def off_by_one(keys, logits, **kw):
        tok = sample(keys, logits, **kw)
        return tok.at[0].set((tok[0] + 1) % logits.shape[-1])

    monkeypatch.setattr(engine, "sample_tokens", off_by_one)
    res = _run(tiny)
    assert not res["correct"]
    assert res["compared"]["mean_logit_gap"]["value"] > TINY_LIMIT


def _counted(cell, reader):
    """A per-layer reader that counts work, on a small recorded trace."""
    trace = json.loads((ROOT / "bench/testdata/trace_small.json")
                       .read_text())
    ticks = [{"kind": "prefill", "t0": 1.0, "t1": 1.3, "tokens": 1,
              "rows": [(0, 32, False), (60, 1, True)]},
             {"kind": "decode", "t0": 2.0, "t1": 2.2, "tokens": 2,
              "rows": [(32, 1, True), (61, 1, True)]}]
    data = run.RunData(
        cell=cell, sz=cell.arch.sizes(cell.config),
        peak=costs.peaks("TPU v5 lite"), slots=4, ticks=ticks, tracks=[],
        trace=trace, traced_ticks=run.pair_traced_ticks(ticks, trace, 0.5))
    return cell.layer_reader(reader)(data)


def test_a_second_architecture_comes_in_as_files(tiny_root, tiny):
    cell = spec.load_cell("wrapped.chat", tiny_root)
    assert cell.arch.__file__ == str(tiny_root / "bench/archs/wrapped.py")
    res = _run(cell)
    assert res["correct"], res["compared"]
    # built and checked with the configuration's own files
    assert {"sizes", "program_config", "canonical_weights",
            "to_program"} <= cell.arch.CALLED
    assert cell.reference.CALLED == {"hidden", "logits"}
    # counted with them, by the same readers and formulas
    for reader in ("prefill_mfu.open", "paged_prefill_roofline.open"):
        assert _counted(cell, reader) == _counted(tiny, reader) > 0
    assert {"step_flops", "attention_kernel_cost"} <= cell.arch.CALLED


@pytest.mark.parametrize("key,value,missing", [
    ("model_type", "absent", "bench/archs/absent.py"),
    ("reference", "bench/absent_reference.py", "bench/absent_reference.py")])
def test_a_missing_architecture_file_is_named(tiny_root, tmp_path, key,
                                              value, missing):
    root = tmp_path / "root"
    shutil.copytree(tiny_root, root)
    path = root / "bench/configs/wrapped.json"
    conf = json.loads(path.read_text())
    conf[key] = value
    path.write_text(json.dumps(conf))
    with pytest.raises(FileNotFoundError, match=str(root / missing)):
        spec.load_cell("wrapped.chat", root)
