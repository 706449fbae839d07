"""Operations and bytes that a serving step and its attention kernels
need, worked out from shapes alone, independent of what implements them.

A step is described by its rows: one ``(L, n, sampled)`` per sequence that
took part, with ``L`` tokens already in its cache, ``n`` new tokens fed in,
and ``sampled`` whether the step's logits for it were used. Padding, idle
rows and logits that were thrown away are not work.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """Per-chip peaks for ``device_kind``; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add them with their source")
    return table[device_kind]


def nonembed_params(sz: dict) -> int:
    """Parameters outside the embedding (tied, so also the LM head)."""
    d, H, Hkv, D, F = (sz[k] for k in ("d", "heads", "kv_heads", "head_dim",
                                        "ffn"))
    attn = d * H * D + 2 * d * Hkv * D + H * D * d + (H + 2 * Hkv) * D
    return sz["layers"] * (attn + 3 * d * F + 2 * d) + d


def attn_pairs(L: int, n: int) -> int:
    """Causal (query, key) pairs of ``n`` new tokens after ``L`` cached."""
    return n * L + n * (n + 1) // 2


def step_flops(sz: dict, rows) -> int:
    """Useful model operations of one step: 2 per parameter per new token,
    the attention's QK and PV products, and the LM head per sampled row."""
    per_tok = 2 * nonembed_params(sz)
    attn = 4 * sz["heads"] * sz["head_dim"] * sz["layers"]
    head = 2 * sz["d"] * sz["vocab"]
    return sum(per_tok * n + attn * attn_pairs(L, n) + (head if s else 0)
               for L, n, s in rows)


def kv_token_bytes(sz: dict, kv_dtype: str) -> int:
    """Bytes of one token's K and V for one layer. Only the bfloat16 cache
    the cells serve is counted; a quantized cache comes with its count."""
    if kv_dtype != "bfloat16":
        raise ValueError(f"no byte count for a {kv_dtype!r} cache")
    return 2 * sz["kv_heads"] * sz["head_dim"] * 2


def attention_kernel_cost(sz: dict, rows, kv_dtype: str,
                          act_bytes: int = 2) -> tuple[int, int]:
    """(operations, bytes) of one layer's attention kernel call over
    ``rows``: Q read and O written once, every cached and new K/V token
    read once at the cache's element size, causal pairs counted exactly."""
    H, D = sz["heads"], sz["head_dim"]
    flops = sum(4 * H * D * attn_pairs(L, n) for L, n, _ in rows)
    kvb = kv_token_bytes(sz, kv_dtype)
    nbytes = sum(2 * n * H * D * act_bytes + (L + n) * kvb
                 for L, n, _ in rows)
    return flops, nbytes


def roofline_seconds(flops: int, nbytes: int, peak: dict) -> float:
    """The least time the chip could take: compute or memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def cache_dtype(conf: dict) -> str:
    """The KV cache's element type for a configuration file: the engine's
    ``kv_dtype="fp32"`` means unquantized, stored in the model's dtype."""
    kv = conf["engine"]["kv_dtype"]
    return conf["torch_dtype"] if kv == "fp32" else kv
