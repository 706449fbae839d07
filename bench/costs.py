"""What the counts of operations and bytes share across architectures:
the chip's peaks, causal pairs, the roofline and the cache's type.

A step is described by its rows: one ``(L, n, sampled)`` per sequence that
took part, with ``L`` tokens already in its cache, ``n`` new tokens fed in,
and ``sampled`` whether the step's logits for it were used. Padding, idle
rows and logits that were thrown away are not work. An architecture's own
counts (``step_flops``, ``attention_kernel_cost``) are in
``bench/archs/<model_type>.py``, worked out from shapes alone,
independent of what implements them.
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


def attn_pairs(L: int, n: int) -> int:
    """Causal (query, key) pairs of ``n`` new tokens after ``L`` cached."""
    return n * L + n * (n + 1) // 2


def roofline_seconds(flops: int, nbytes: int, peak: dict) -> float:
    """The least time the chip could take: compute or memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def cache_dtype(conf: dict) -> str:
    """The KV cache's element type for a configuration file: the engine's
    ``kv_dtype="fp32"`` means unquantized, stored in the model's dtype."""
    kv = conf["engine"]["kv_dtype"]
    return conf["torch_dtype"] if kv == "fp32" else kv
