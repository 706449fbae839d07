"""The traffic generator: the same seed gives the same requests, every
seed the same sizes in another order, and lengths stay in their ranges."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import traffic  # noqa: E402

MIXES = {name: json.loads((ROOT / "bench" / "traffic" / f"{name}.json")
                          .read_text()) for name in ("chat", "long_gen")}
BIG_SEED = 2**31 + 12345


def _sig(reqs):
    return [(r.prompt.tolist(), r.max_new, r.due_s) for r in reqs]


def test_open_requests_repeat_per_seed_and_permute_across_seeds():
    mix = MIXES["chat"]
    a = traffic.open_requests(mix, 0.7, 40.0, BIG_SEED, 151936)
    b = traffic.open_requests(mix, 0.7, 40.0, BIG_SEED, 151936)
    c = traffic.open_requests(mix, 0.7, 40.0, 7, 151936)
    assert _sig(a) == _sig(b)
    assert _sig(a) != _sig(c)
    assert len(a) == len(c) == round(0.7 * 40)
    for key in (lambda r: len(r.prompt), lambda r: r.max_new):
        assert sorted(map(key, a)) == sorted(map(key, c))
    for reqs in (a, c):
        due = [r.due_s for r in reqs]
        assert due == sorted(due) and 0 < due[0] and due[-1] < 40.0


@pytest.mark.parametrize("name", ["chat", "long_gen"])
def test_lengths_stay_in_their_ranges(name):
    mix = MIXES[name]
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    if mix["loop"] == "open":
        reqs = traffic.open_requests(mix, 5.0, 100.0, 3, 1000)
    else:
        _, reqs = traffic.offline_requests(mix, 16, 3, 1000, 500)
    lens = np.array([len(r.prompt) for r in reqs])
    outs = np.array([r.max_new for r in reqs])
    assert lens.min() >= p["min"] and lens.max() <= p["max"]
    assert outs.min() >= o["min"] and outs.max() <= o["max"]
    assert all(1 <= t < 1000 for r in reqs[:20] for t in r.prompt)
    # the distribution is the mix's: the sample median near the stated one
    assert abs(np.median(lens) / p["median"] - 1) < 0.15


def test_offline_first_wave_is_part_way_through_its_requests():
    mix = MIXES["long_gen"]
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    wave, backlog = traffic.offline_requests(mix, 16, BIG_SEED, 1000, 64)
    again, _ = traffic.offline_requests(mix, 16, BIG_SEED, 1000, 64)
    other, _ = traffic.offline_requests(mix, 16, 5, 1000, 64)
    assert _sig(wave) == _sig(again) and len(backlog) == 64
    assert sorted(r.max_new for r in wave) == sorted(r.max_new for r in other)
    for r in wave:
        # prompt + already generated + still to come fits one request's span
        assert 1 <= r.max_new <= o["max"]
        assert len(r.prompt) + r.max_new <= p["max"] + o["max"]
        assert len(r.prompt) >= p["min"]
    assert any(len(r.prompt) > p["max"] for r in wave)  # some have an age

