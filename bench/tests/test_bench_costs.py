"""bench/costs.py and the qwen2 architecture's counts
(bench/archs/qwen2.py) against counts made by hand, and against the
counts the harness made before they moved into the architecture's file
(pinned: every per-layer metric that reads them reads the same)."""
import hashlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import costs, spec  # noqa: E402


def _conf(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


ARCH = spec.arch_module(_conf("qwen2-0.5b"))


def _sizes(name):
    return ARCH.sizes(_conf(name))


def test_nonembed_params_by_hand():
    # qwen2-0.5b, per layer: q and o 896*896 each, k and v 896*128 each,
    # biases (14 + 2 + 2) * 64, MLP 3 * 896 * 4864, two norms of 896;
    # 24 layers and the final norm
    per_layer = 2 * 896 * 896 + 2 * 896 * 128 + 18 * 64 + 3 * 896 * 4864 \
        + 2 * 896
    assert ARCH.nonembed_params(_sizes("qwen2-0.5b")) == 24 * per_layer + 896
    per_layer = 4 * 1024 * 1024 + 48 * 64 + 3 * 1024 * 2816 + 2 * 1024
    assert ARCH.nonembed_params(_sizes("qwen1.5-0.5b")) == \
        24 * per_layer + 1024


def test_cache_bytes_at_two_bytes_per_bf16_element():
    sz = _sizes("qwen2-0.5b")   # 2 KV heads x 64
    assert ARCH.kv_token_bytes(sz, "bfloat16") == 2 * 2 * 64 * 2
    assert ARCH.kv_token_bytes(sz, "bfloat16") * 24 == 12288
    assert ARCH.kv_token_bytes(_sizes("qwen1.5-0.5b"), "bfloat16") * 24 \
        == 98304
    with pytest.raises(ValueError):    # a quantized cache is not counted
        ARCH.kv_token_bytes(sz, "int8")
    conf = json.loads((ROOT / "bench/configs/qwen2-0.5b.json").read_text())
    assert costs.cache_dtype(conf) == "bfloat16"


def test_causal_pairs_exact():
    assert costs.attn_pairs(0, 1) == 1
    assert costs.attn_pairs(5, 1) == 6          # a decode row reads L + 1
    assert costs.attn_pairs(0, 4) == 1 + 2 + 3 + 4
    assert costs.attn_pairs(128, 128) == 128 * 128 + 128 * 129 // 2


def test_step_and_kernel_costs_by_hand():
    sz = _sizes("qwen2-0.5b")
    n_ne = ARCH.nonembed_params(sz)
    head = 2 * 896 * 151936
    attn = 4 * 14 * 64 * 24
    rows = [(100, 1, True), (0, 128, False), (128, 20, True)]
    want = (2 * n_ne * (1 + 128 + 20)
            + attn * (101 + 128 * 129 // 2 + (20 * 128 + 20 * 21 // 2))
            + 2 * head)
    assert ARCH.step_flops(sz, rows) == want
    flops, nbytes = ARCH.attention_kernel_cost(sz, [(100, 1, True)],
                                                "bfloat16")
    assert flops == 4 * 14 * 64 * 101
    # Q read and O written (14 heads x 64 x 2 B each), 101 tokens of K and V
    assert nbytes == 2 * 14 * 64 * 2 + 101 * 2 * 2 * 64 * 2
    peak = costs.peaks("TPU v5 lite")
    t = costs.roofline_seconds(flops, nbytes, peak)
    assert t == nbytes / 819e9     # a decode row is bound by bandwidth


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        costs.peaks("cpu")


# Counts the harness made at the commit before the move, for both
# configurations, over these rows: (cached, new, sampled).
PINNED_ROWS = [(0, 128, False), (100, 1, True), (128, 20, True),
               (3000, 1, True), (512, 128, True), (2047, 1, False)]
PINNED = {
    "qwen2-0.5b": {
        "sizes": {"layers": 24, "d": 896, "heads": 14, "kv_heads": 2,
                  "head_dim": 64, "ffn": 4864, "vocab": 151936,
                  "rope_theta": 1000000.0, "eps": 1e-06},
        "step_flops": 208534911232,
        "attention_kernel_cost": (322445312, 4105728)},
    "qwen1.5-0.5b": {
        "sizes": {"layers": 24, "d": 1024, "heads": 16, "kv_heads": 16,
                  "head_dim": 64, "ffn": 2816, "vocab": 151936,
                  "rope_theta": 1000000.0, "eps": 1e-06},
        "step_flops": 182179002368,
        "attention_kernel_cost": (368508928, 25989120)},
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_counts_are_the_ones_made_before_the_move(name):
    conf = _conf(name)
    arch = spec.arch_module(conf)
    sz = arch.sizes(conf)
    assert sz == PINNED[name]["sizes"]
    assert arch.step_flops(sz, PINNED_ROWS) == PINNED[name]["step_flops"]
    assert arch.attention_kernel_cost(sz, PINNED_ROWS, costs.cache_dtype(
        conf)) == PINNED[name]["attention_kernel_cost"]


# sha256 of the canonical weights (name, dtype, bytes per array in name
# order) at the tiny size bench/tests/test_bench_correct.py runs, made
# before the move: the key-split order, so every weight, is unchanged.
PINNED_WEIGHTS = {
    11: "96b7c422b30e816c8c4adc370712ee86f5c099c27c5dbe50e6338cfddb2fe02e",
    2**32 + 7:
        "7b4f77b9783813293de3dc550a4a3e67a5abc091f8006c9ed00289dac488997b",
}


@pytest.mark.parametrize("seed", sorted(PINNED_WEIGHTS))
def test_weights_are_the_ones_drawn_before_the_move(seed):
    conf = _conf("qwen2-0.5b")
    conf.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
                intermediate_size=128, vocab_size=256, num_hidden_layers=2)
    w = ARCH.canonical_weights(seed, conf)
    h = hashlib.sha256()
    for k in sorted(w):
        a = np.ascontiguousarray(np.asarray(w[k]))
        h.update(k.encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == PINNED_WEIGHTS[seed]
