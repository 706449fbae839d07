"""Public wrappers for the flash-decode Pallas kernels: contiguous caches
(full-precision or quantized codes+scales) and the paged (block-table)
layout, in both its fused form (in-kernel block-table indexing, no gathered
copy — DESIGN.md §9) and the legacy gather-then-kernel form."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.decode.decode import (
    decode_fwd_pallas,
    paged_decode_fwd_pallas,
)
from repro.kernels.paged import gather_rows


def _interpret_default(interpret):
    return jax.default_backend() == "cpu" if interpret is None else interpret


def _block_k_for(S, block_k):
    bk = min(block_k, S)
    pk = (-S) % bk
    return bk, pk


def decode_attention_pallas(
    q: jax.Array,        # (B, H, D)
    k_cache: jax.Array,  # (B, Hkv, S, D)
    v_cache: jax.Array,  # (B, Hkv, S, Dv)
    lengths: jax.Array,  # (B,) int32
    *,
    scale: float | None = None,
    variant: str = "exact",
    block_k: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    B, H, D = q.shape
    _, Hkv, S, _ = k_cache.shape
    Dv = v_cache.shape[-1]
    group = H // Hkv
    interpret = _interpret_default(interpret)
    scale = float(1.0 / np.sqrt(D)) if scale is None else float(scale)
    bk, pk = _block_k_for(S, block_k)
    # (B, H, D) -> (B*Hkv, group, D); heads h in [kvh*group, (kvh+1)*group)
    q3 = q.reshape(B, Hkv, group, D).reshape(B * Hkv, group, D)
    k3 = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pk), (0, 0))).reshape(B * Hkv, S + pk, D)
    v3 = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pk), (0, 0))).reshape(B * Hkv, S + pk, Dv)
    o3 = decode_fwd_pallas(
        lengths, q3, k3, v3,
        scale=scale,
        variant=variant,
        block_k=bk,
        num_kv_heads=Hkv,
        interpret=interpret,
    )
    return o3.reshape(B, Hkv, group, Dv).reshape(B, H, Dv)


def quant_decode_attention_pallas(
    q: jax.Array,        # (B, H, D)
    k_codes: jax.Array,  # (B, Hkv, S, D) int8 / float8_e4m3fn codes
    v_codes: jax.Array,  # (B, Hkv, S, Dv)
    k_scale: jax.Array,  # (B, Hkv, S) float32 per-row scales
    v_scale: jax.Array,
    lengths: jax.Array,  # (B,) int32
    *,
    scale: float | None = None,
    variant: str = "exact",
    block_k: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Flash-decode over a quantized contiguous cache: the kernel loads only
    codes + scale rows and dequantizes in-register, fused into the score and
    value matmuls (``numerics/quant.py`` codec; DESIGN.md §9). The fp32 K/V
    never exists in HBM."""
    B, H, D = q.shape
    _, Hkv, S, _ = k_codes.shape
    Dv = v_codes.shape[-1]
    group = H // Hkv
    interpret = _interpret_default(interpret)
    scale = float(1.0 / np.sqrt(D)) if scale is None else float(scale)
    bk, pk = _block_k_for(S, block_k)
    q3 = q.reshape(B, Hkv, group, D).reshape(B * Hkv, group, D)

    def flat(codes, Dl):
        return jnp.pad(codes, ((0, 0), (0, 0), (0, pk), (0, 0))).reshape(
            B * Hkv, S + pk, Dl)

    def flat_scale(s):  # padded scale rows dequantize to exact zeros
        return jnp.pad(s, ((0, 0), (0, 0), (0, pk))).reshape(
            B * Hkv, 1, S + pk).astype(jnp.float32)

    o3 = decode_fwd_pallas(
        lengths, q3, flat(k_codes, D), flat(v_codes, Dv),
        flat_scale(k_scale), flat_scale(v_scale),
        scale=scale,
        variant=variant,
        block_k=bk,
        num_kv_heads=Hkv,
        interpret=interpret,
    )
    return o3.reshape(B, Hkv, group, Dv).reshape(B, H, Dv)


# ---------------------------------------------------------------------------
# Paged layout — fused (in-kernel block-table indexing)
# ---------------------------------------------------------------------------
def _paged_operands(q, pool_tokens, page_size, Hkv):
    B, H, D = q.shape
    assert pool_tokens % page_size == 0, (pool_tokens, page_size)
    return q.reshape(B, Hkv, H // Hkv, D), pool_tokens // page_size


def fused_paged_decode_attention_pallas(
    q: jax.Array,         # (B, H, D)
    k_pool: jax.Array,    # (pool_tokens, Hkv, D) flat physical pool
    v_pool: jax.Array | None,  # (pool_tokens, Hkv, Dv)
    block_tables: jax.Array,  # (B, max_blocks) int32, sentinel = pool_blocks
    lengths: jax.Array,   # (B,) valid entries incl. the current token
    *,
    page_size: int,
    scale: float | None = None,
    variant: str = "exact",
    window: int | None = None,
    v_width: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused paged flash-decode: the kernel's index maps resolve physical
    blocks from the block table per grid step, so the paged history is read
    straight out of the pool — no materialized ``gather_rows`` copy
    (DESIGN.md §9). Windows are masked in-kernel by absolute position.
    ``v_pool=None``: the shared latent pool (pool_tokens, D) whose rows
    hold V in their first ``v_width`` columns (DESIGN.md §14)."""
    B, H, D = q.shape
    latent = v_pool is None
    pool_tokens = k_pool.shape[0]
    Hkv = 1 if latent else k_pool.shape[1]
    interpret = _interpret_default(interpret)
    scale = float(1.0 / np.sqrt(D)) if scale is None else float(scale)
    q4, nblk = _paged_operands(q, pool_tokens, page_size, Hkv)
    o4 = paged_decode_fwd_pallas(
        block_tables.astype(jnp.int32), lengths.astype(jnp.int32), q4,
        k_pool.reshape(nblk, page_size, *k_pool.shape[1:]),
        None if latent else v_pool.reshape(nblk, page_size, Hkv, -1),
        scale=scale,
        variant=variant,
        page_size=page_size,
        window=window,
        v_width=v_width if latent else None,
        interpret=interpret,
    )
    return o4.reshape(B, H, -1)


def quant_fused_paged_decode_attention_pallas(
    q: jax.Array,          # (B, H, D)
    k_code_pool: jax.Array,   # (pool_tokens, Hkv, D) int8/fp8 codes
    v_code_pool: jax.Array,   # (pool_tokens, Hkv, Dv)
    k_scale_pool: jax.Array,  # (pool_tokens, Hkv) float32
    v_scale_pool: jax.Array,
    block_tables: jax.Array,  # (B, max_blocks) int32
    lengths: jax.Array,
    *,
    page_size: int,
    scale: float | None = None,
    variant: str = "exact",
    window: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """The fully fused serving kernel: paged *and* quantized. Reads only
    codes + scale pools + block tables; block-table indexing happens in the
    index maps and dequant happens in-register inside the matmuls — the
    decode tick's HBM traffic is the quantized pool bytes, nothing more
    (the ISSUE-4 headline; measured by benchmarks/decode_microbench.py)."""
    B, H, D = q.shape
    pool_tokens, Hkv, _ = k_code_pool.shape
    Dv = v_code_pool.shape[-1]
    interpret = _interpret_default(interpret)
    scale = float(1.0 / np.sqrt(D)) if scale is None else float(scale)
    q4, nblk = _paged_operands(q, pool_tokens, page_size, Hkv)
    o4 = paged_decode_fwd_pallas(
        block_tables.astype(jnp.int32), lengths.astype(jnp.int32), q4,
        k_code_pool.reshape(nblk, page_size, Hkv, D),
        v_code_pool.reshape(nblk, page_size, Hkv, Dv),
        k_scale_pool.reshape(nblk, page_size, Hkv).astype(jnp.float32),
        v_scale_pool.reshape(nblk, page_size, Hkv).astype(jnp.float32),
        scale=scale,
        variant=variant,
        page_size=page_size,
        window=window,
        interpret=interpret,
    )
    return o4.reshape(B, H, Dv)


# ---------------------------------------------------------------------------
# Paged layout — legacy gather-then-kernel form (the "gather_pallas" family)
# ---------------------------------------------------------------------------
def paged_decode_attention_pallas(
    q: jax.Array,       # (B, H, D)
    k_pool: jax.Array,  # (pool_tokens, Hkv, D) flat physical pool
    v_pool: jax.Array,
    rows: jax.Array,    # (B, L) physical rows in logical position order
    lengths: jax.Array,  # (B,) valid entries incl. the current token
    *,
    scale: float | None = None,
    variant: str = "exact",
    block_k: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Gather-then-kernel paged decode (DESIGN.md §7).

    The paged history is first materialized into logical position order (an
    XLA gather; sentinel rows read zero and sit beyond ``lengths``, so the
    kernel's length masking hides them) and handed to the contiguous
    kernel. Kept as the ``gather_pallas`` registry family and as the
    baseline the fused kernel is benchmarked against — the fused
    ``pallas`` paged backend above skips the copy entirely.
    """
    k_cache = jnp.moveaxis(gather_rows(k_pool, rows), 1, 2)  # (B, Hkv, L, D)
    v_cache = jnp.moveaxis(gather_rows(v_pool, rows), 1, 2)
    return decode_attention_pallas(
        q, k_cache, v_cache, lengths, scale=scale, variant=variant,
        block_k=block_k, interpret=interpret,
    )
