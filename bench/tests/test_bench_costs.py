"""bench/costs.py against counts made by hand."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import costs, model  # noqa: E402


def _sizes(name):
    return model.sizes(json.loads(
        (ROOT / "bench" / "configs" / f"{name}.json").read_text()))


def test_nonembed_params_by_hand():
    # qwen2-0.5b, per layer: q and o 896*896 each, k and v 896*128 each,
    # biases (14 + 2 + 2) * 64, MLP 3 * 896 * 4864, two norms of 896;
    # 24 layers and the final norm
    per_layer = 2 * 896 * 896 + 2 * 896 * 128 + 18 * 64 + 3 * 896 * 4864 \
        + 2 * 896
    assert costs.nonembed_params(_sizes("qwen2-0.5b")) == 24 * per_layer + 896
    per_layer = 4 * 1024 * 1024 + 48 * 64 + 3 * 1024 * 2816 + 2 * 1024
    assert costs.nonembed_params(_sizes("qwen1.5-0.5b")) == \
        24 * per_layer + 1024


def test_cache_bytes_at_two_bytes_per_bf16_element():
    sz = _sizes("qwen2-0.5b")   # 2 KV heads x 64
    assert costs.kv_token_bytes(sz, "bfloat16") == 2 * 2 * 64 * 2
    assert costs.kv_token_bytes(sz, "bfloat16") * 24 == 12288
    assert costs.kv_token_bytes(_sizes("qwen1.5-0.5b"), "bfloat16") * 24 \
        == 98304
    with pytest.raises(ValueError):    # a quantized cache is not counted
        costs.kv_token_bytes(sz, "int8")
    conf = json.loads((ROOT / "bench/configs/qwen2-0.5b.json").read_text())
    assert costs.cache_dtype(conf) == "bfloat16"


def test_causal_pairs_exact():
    assert costs.attn_pairs(0, 1) == 1
    assert costs.attn_pairs(5, 1) == 6          # a decode row reads L + 1
    assert costs.attn_pairs(0, 4) == 1 + 2 + 3 + 4
    assert costs.attn_pairs(128, 128) == 128 * 128 + 128 * 129 // 2


def test_step_and_kernel_costs_by_hand():
    sz = _sizes("qwen2-0.5b")
    n_ne = costs.nonembed_params(sz)
    head = 2 * 896 * 151936
    attn = 4 * 14 * 64 * 24
    rows = [(100, 1, True), (0, 128, False), (128, 20, True)]
    want = (2 * n_ne * (1 + 128 + 20)
            + attn * (101 + 128 * 129 // 2 + (20 * 128 + 20 * 21 // 2))
            + 2 * head)
    assert costs.step_flops(sz, rows) == want
    flops, nbytes = costs.attention_kernel_cost(sz, [(100, 1, True)],
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
