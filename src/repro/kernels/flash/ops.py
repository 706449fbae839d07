"""Public jit'd wrappers for the FlashAttention-2 Pallas kernels: the
full-sequence forward and the three fused chunked-prefill entry points
(contiguous / quantized / paged — DESIGN.md §10).

Handles: 4-D (B, H, S, D) layout, GQA/MQA head folding, padding of the
sequence axes to block multiples (pad regions are masked in-kernel), and
CPU-interpret fallback for this container.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash.flash import flash_fwd_pallas
from repro.kernels.flash.prefill import (
    paged_prefill_fwd_pallas,
    prefill_fwd_pallas,
)
from repro.kernels.paged import gather_rows


def flash_attention_fwd(
    q: jax.Array,   # (B, H, Sq, D)
    k: jax.Array,   # (B, Hkv, Sk, D)
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    window: int | None = None,
    variant: str = "exact",
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    B, H, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    assert H % Hkv == 0, (H, Hkv)
    assert v.shape[-1] == D, "pallas kernel requires Dq == Dv (MLA uses flash_jnp)"
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    scale = float(1.0 / np.sqrt(D)) if scale is None else float(scale)
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    pq = (-Sq) % bq
    pk = (-Sk) % bk
    q3 = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0))).reshape(B * H, Sq + pq, D)
    k3 = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0))).reshape(B * Hkv, Sk + pk, D)
    v3 = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0))).reshape(B * Hkv, Sk + pk, D)
    o3 = flash_fwd_pallas(
        q3, k3, v3,
        causal=causal,
        scale=scale,
        window=window,
        variant=variant,
        block_q=bq,
        block_k=bk,
        num_q_heads=H,
        num_kv_heads=Hkv,
        kv_len=Sk,
        interpret=interpret,
    )
    return o3.reshape(B, H, Sq + pq, D)[:, :, :Sq, :]


# ---------------------------------------------------------------------------
# Fused chunked prefill (DESIGN.md §10)
# ---------------------------------------------------------------------------
def _interpret_default(interpret):
    return jax.default_backend() == "cpu" if interpret is None else interpret


def _pad_seq(x, target, axis=2):
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - x.shape[axis])
    return jnp.pad(x, pad)


def _fold(x, target):
    """(B, Hkv, S, ·) -> (B*Hkv, S_pad, ·) padded along the sequence axis."""
    B, Hkv = x.shape[:2]
    return _pad_seq(x, target).reshape((B * Hkv, target) + x.shape[3:])


def _meta(lengths, n_valid):
    """(B, 2) int32 [cache length, chunk n_valid]: the scalar-prefetch
    operand every prefill kernel masks from."""
    return jnp.stack([lengths.astype(jnp.int32),
                      n_valid.astype(jnp.int32)], axis=1)


def _prefill_blocks(S, C, block_q, block_k):
    """One block_k serves both KV segments; pad each to a multiple of it.

    Returns (bq, Cq, bk, Sp, Ck): the q/kv block sizes and the padded
    query, cache, and chunk sequence targets (an empty cache pads to one
    all-masked zero block so the cache segment always exists).
    """
    bq = min(block_q, C)
    bk = min(block_k, max(S, C, 1))
    Cq = C + (-C) % bq
    Sp = max(S, 1) + (-max(S, 1)) % bk
    Ck = C + (-C) % bk
    return bq, Cq, bk, Sp, Ck


def prefill_attention_pallas(
    q: jax.Array,        # (B, H, C, D) chunk queries
    k_cache: jax.Array,  # (B, Hkv, S, D) resident cache (values)
    v_cache: jax.Array,  # (B, Hkv, S, Dv)
    k_chunk: jax.Array,  # (B, Hkv, C, D) this chunk's fresh KV
    v_chunk: jax.Array,  # (B, Hkv, C, Dv)
    lengths: jax.Array,  # (B,) tokens already resident in the cache
    n_valid: jax.Array,  # (B,) valid tokens in this chunk
    *,
    scale: float | None = None,
    variant: str = "exact",
    window: int | None = None,
    rolling: bool = False,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused chunked prefill: the chunk attends over [cache ++ chunk]
    without the concatenation ever being materialized — the kernel walks
    the cache segment and the chunk segment of its KV grid axis as separate
    operands, masking positionally from ``lengths``/``n_valid`` in-kernel
    (``rolling`` selects the windowed rolling-buffer slot convention).
    Dv may differ from D (MLA expanded latents)."""
    B, H, C, D = q.shape
    _, Hkv, S, _ = k_cache.shape
    Dv = v_cache.shape[-1]
    interpret = _interpret_default(interpret)
    scale = float(1.0 / np.sqrt(D)) if scale is None else float(scale)
    bq, Cq, bk, Sp, Ck = _prefill_blocks(S, C, block_q, block_k)
    q3 = _pad_seq(q, Cq).reshape(B * H, Cq, D)
    o3 = prefill_fwd_pallas(
        _meta(lengths, n_valid), q3,
        _fold(k_cache, Sp), _fold(v_cache, Sp),
        _fold(k_chunk, Ck), _fold(v_chunk, Ck),
        scale=scale, variant=variant, window=window, rolling=rolling,
        span=S, block_q=bq, block_k=bk, num_q_heads=H, num_kv_heads=Hkv,
        interpret=interpret,
    )
    return o3.reshape(B, H, Cq, Dv)[:, :, :C, :]


def quant_prefill_attention_pallas(
    q: jax.Array,         # (B, H, C, D)
    kc_codes: jax.Array,  # (B, Hkv, S, D) int8/fp8 cache codes
    vc_codes: jax.Array,  # (B, Hkv, S, Dv)
    kc_scale: jax.Array,  # (B, Hkv, S) f32 per-row cache scales
    vc_scale: jax.Array,
    kn_codes: jax.Array,  # (B, Hkv, C, D) chunk codes (quantized on write)
    vn_codes: jax.Array,
    kn_scale: jax.Array,  # (B, Hkv, C) f32
    vn_scale: jax.Array,
    lengths: jax.Array,
    n_valid: jax.Array,
    *,
    scale: float | None = None,
    variant: str = "exact",
    window: int | None = None,
    rolling: bool = False,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Quantized fused prefill: codes + scale rows enter the kernel as-is
    and dequantize in-register inside the score/value matmuls — the fp32
    [cache ++ chunk] never exists in HBM (DESIGN.md §10)."""
    B, H, C, D = q.shape
    _, Hkv, S, _ = kc_codes.shape
    Dv = vc_codes.shape[-1]
    interpret = _interpret_default(interpret)
    scale = float(1.0 / np.sqrt(D)) if scale is None else float(scale)
    bq, Cq, bk, Sp, Ck = _prefill_blocks(S, C, block_q, block_k)
    q3 = _pad_seq(q, Cq).reshape(B * H, Cq, D)

    def fscale(s, target):  # padded scale rows dequantize to exact zeros
        return _fold(s, target)[:, None, :].astype(jnp.float32)

    o3 = prefill_fwd_pallas(
        _meta(lengths, n_valid), q3,
        _fold(kc_codes, Sp), _fold(vc_codes, Sp),
        _fold(kn_codes, Ck), _fold(vn_codes, Ck),
        fscale(kc_scale, Sp), fscale(vc_scale, Sp),
        fscale(kn_scale, Ck), fscale(vn_scale, Ck),
        scale=scale, variant=variant, window=window, rolling=rolling,
        span=S, block_q=bq, block_k=bk, num_q_heads=H, num_kv_heads=Hkv,
        interpret=interpret,
    )
    return o3.reshape(B, H, Cq, Dv)[:, :, :C, :]


def _paged_chunk(x, page_size):
    """(B, Hkv, C, ·) chunk -> token-major (B, C_pad, Hkv, ·), the pool's
    own layout, padded to whole pages."""
    C = x.shape[2]
    return jnp.moveaxis(_pad_seq(x, C + (-C) % page_size), 1, 2)


def fused_paged_prefill_attention_pallas(
    q: jax.Array,         # (B, H, C, D)
    k_chunk: jax.Array,   # (B, Hkv, C, D) this chunk's fresh KV
    v_chunk: jax.Array,   # (B, Hkv, C, Dv)
    k_pool: jax.Array,    # (pool_tokens, Hkv, D) flat physical pool
    v_pool: jax.Array,    # (pool_tokens, Hkv, Dv)
    block_tables: jax.Array,  # (B, max_blocks) int32, sentinel = pool_blocks
    lengths: jax.Array,   # (B,) tokens already resident
    n_valid: jax.Array,   # (B,) valid tokens in this chunk
    *,
    page_size: int,
    scale: float | None = None,
    variant: str = "exact",
    window: int | None = None,
    block_q: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused paged prefill: the kernel's index maps resolve physical blocks
    from the block table per grid step (scalar prefetch), so the chunk
    attends to the paged history straight out of the pool — no gathered
    copy (DESIGN.md §10). History tiles are whole pages; windows mask
    in-kernel and whole pages below the window floor are skipped."""
    B, H, C, D = q.shape
    pool_tokens, Hkv, _ = k_pool.shape
    Dv = v_pool.shape[-1]
    interpret = _interpret_default(interpret)
    scale = float(1.0 / np.sqrt(D)) if scale is None else float(scale)
    assert pool_tokens % page_size == 0, (pool_tokens, page_size)
    nblk = pool_tokens // page_size
    bq = min(block_q, C)
    o4 = paged_prefill_fwd_pallas(
        block_tables.astype(jnp.int32), _meta(lengths, n_valid),
        _pad_seq(q, C + (-C) % bq),
        k_pool.reshape(nblk, page_size, Hkv, D),
        v_pool.reshape(nblk, page_size, Hkv, Dv),
        _paged_chunk(k_chunk, page_size),
        _paged_chunk(v_chunk, page_size),
        scale=scale, variant=variant, window=window, page_size=page_size,
        block_q=bq, interpret=interpret,
    )
    return o4[:, :, :C, :]


def quant_fused_paged_prefill_attention_pallas(
    q: jax.Array,             # (B, H, C, D)
    kn_codes: jax.Array,      # (B, Hkv, C, D) chunk codes
    vn_codes: jax.Array,
    kn_scale: jax.Array,      # (B, Hkv, C) f32
    vn_scale: jax.Array,
    k_code_pool: jax.Array,   # (pool_tokens, Hkv, D) int8/fp8 codes
    v_code_pool: jax.Array,
    k_scale_pool: jax.Array,  # (pool_tokens, Hkv) float32
    v_scale_pool: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    n_valid: jax.Array,
    *,
    page_size: int,
    scale: float | None = None,
    variant: str = "exact",
    window: int | None = None,
    block_q: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """The fully fused prefill serving kernel: paged *and* quantized. Reads
    only code pools, scale pools, block tables and the (already quantized)
    chunk; block-table indexing happens in the index maps and dequant
    happens in-register — the prefill tick's history traffic is the
    quantized pool bytes, nothing more (benchmarks/prefill_microbench.py
    tracks the bytes/chunk-token gap)."""
    B, H, C, D = q.shape
    pool_tokens, Hkv, _ = k_code_pool.shape
    Dv = v_code_pool.shape[-1]
    interpret = _interpret_default(interpret)
    scale = float(1.0 / np.sqrt(D)) if scale is None else float(scale)
    assert pool_tokens % page_size == 0, (pool_tokens, page_size)
    nblk = pool_tokens // page_size
    bq = min(block_q, C)
    o4 = paged_prefill_fwd_pallas(
        block_tables.astype(jnp.int32), _meta(lengths, n_valid),
        _pad_seq(q, C + (-C) % bq),
        k_code_pool.reshape(nblk, page_size, Hkv, D),
        v_code_pool.reshape(nblk, page_size, Hkv, Dv),
        _paged_chunk(kn_codes, page_size),
        _paged_chunk(vn_codes, page_size),
        k_scale_pool.reshape(nblk, page_size, Hkv).astype(jnp.float32),
        v_scale_pool.reshape(nblk, page_size, Hkv).astype(jnp.float32),
        _paged_chunk(kn_scale, page_size).astype(jnp.float32),
        _paged_chunk(vn_scale, page_size).astype(jnp.float32),
        scale=scale, variant=variant, window=window, page_size=page_size,
        block_q=bq, interpret=interpret,
    )
    return o4[:, :, :C, :]


def paged_prefill_attention_pallas(
    q: jax.Array,        # (B, H, C, D)
    k_chunk: jax.Array,  # (B, Hkv, C, D)
    v_chunk: jax.Array,
    k_pool: jax.Array,   # (pool_tokens, Hkv, D)
    v_pool: jax.Array,
    rows: jax.Array,     # (B, L) physical rows in logical position order
    lengths: jax.Array,
    n_valid: jax.Array,
    *,
    scale: float | None = None,
    variant: str = "exact",
    window: int | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Gather-then-kernel paged prefill (the ``gather_pallas`` family).

    The paged history is materialized into logical position order (an XLA
    gather; sentinel rows read zero and sit at/after ``lengths``, so the
    kernel masks them) and handed to the contiguous prefill kernel with
    absolute (non-rolling) positions. Kept as the baseline the fused
    kernel is benchmarked against — the fused ``pallas`` paged backend
    above skips the copy entirely."""
    k_cache = jnp.moveaxis(gather_rows(k_pool, rows), 1, 2)  # (B, Hkv, L, D)
    v_cache = jnp.moveaxis(gather_rows(v_pool, rows), 1, 2)
    return prefill_attention_pallas(
        q, k_cache, v_cache, k_chunk, v_chunk, lengths, n_valid,
        scale=scale, variant=variant, window=window, rolling=False,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
