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
"""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run, spec  # noqa: E402

TINY_LIMIT = 0.004
SECONDS = 2.0


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = tmp_path_factory.mktemp("tinybench")
    for sub in ("configs", "traffic", "cells"):
        (root / "bench" / sub).mkdir(parents=True)
    conf = json.loads((ROOT / "bench/configs/qwen2-0.5b.json").read_text())
    conf.update(name="tiny", hidden_size=64, num_attention_heads=4,
                num_key_value_heads=2, intermediate_size=128, vocab_size=256,
                num_hidden_layers=2)
    conf["engine"].update(slots=4, max_len=256, chunk_size=32)
    (root / "bench/configs/tiny.json").write_text(json.dumps(conf))
    mix = {"loop": "open", "size_seed": 1,
           "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                             "min": 8, "max": 100},
           "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                             "min": 4, "max": 16}}
    (root / "bench/traffic/tinychat.json").write_text(json.dumps(mix))
    (root / "bench/cells/tiny.chat.json").write_text(json.dumps(
        {"rate_per_s": 3.0, "in_flight": 2,
         "limits": {"mean_logit_gap": TINY_LIMIT}}))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": "tiny.chat", "config": "tiny",
                       "traffic": "tinychat", "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "ttft_p95_ms", "unit": "ms"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": []}))
    return spec.load_cell("tiny.chat", root)


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
