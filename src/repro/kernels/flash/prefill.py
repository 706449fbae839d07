"""Pallas TPU kernels: fused chunked prefill — a chunk of C fresh queries
against [KV cache ++ chunk] without ever materializing the concatenation
(DESIGN.md §10).

This is the prefill twin of the flash-decode kernels (``kernels/decode``):
the same shared online-softmax tile step (``flash/tile.py``), extended to a
Tq × Tk grid over a *two-segment* KV axis. Grid = (B * H, q_blocks,
cache_blocks + chunk_blocks); each program owns one query head's block_q
chunk rows. KV grid steps 0..nkc-1 walk the resident cache (per-slot
buffers here; the physical pool via block tables in the paged kernel),
steps nkc.. walk the chunk's own fresh KV. Both segments are separate
operands whose index maps *clamp* outside their own segment — a clamped
map repeats the previous block index, so the pipeline never refetches it —
and ``pl.when`` picks exactly one segment body per step. No gathered,
concatenated, or dequantized copy of the history ever exists in HBM.

Masking is computed in-kernel from two per-sequence scalars (cache length
and chunk validity count, a scalar-prefetch SMEM operand) instead of
materialized position/validity tensors:

* cache segment, ``rolling=False`` (fresh contiguous caches, gathered
  paged history, MLA expanded latents): slot j holds position j, valid iff
  j < length. Chunk rows sit at positions >= length, so causality against
  the cache is automatic; local windows mask ``row_pos - j < window`` and
  whole tiles below the window floor are skipped.
* cache segment, ``rolling=True`` (windowed rolling buffers): slot j holds
  position ``last - ((last - j) % span)``, ``last = length - 1`` — the
  newest position congruent to j modulo the span. Exactness argument in
  DESIGN.md §10: this assigns every slot the position the layer last wrote
  there, so the masked valid set equals the window's logical tail even
  while the chunk being processed will overwrite slots its own earlier
  queries still need.
* chunk segment: column j is position length + j, valid iff j < n_valid;
  causality within the chunk is ``row >= col``.

Quantized caches enter as int8/fp8 codes + per-row f32 scales and
dequantize in-register inside the score/value matmuls exactly as decode
does (DESIGN.md §9) — the ExpMul variant's pow2 softmax weights multiply
still-quantized value tiles. Contiguous scale rows enter as
``(BHkv, 1, S)`` so a tile's scales lie along lanes.

The paged kernel takes the block table as a scalar-prefetch operand
(``PrefetchScalarGridSpec``); index maps resolve ``block_table[b, page]``
before each tile DMA, sentinel entries (= pool_blocks) are clamped into
range and only ever cover positions >= length, which the mask hides. Pages
entirely below a local window's floor are skipped outright. Its grid is
(B, q_blocks, pages + chunk_pages): each DMA brings one page with all its
KV heads (trailing ``(Hkv, D)`` dims whole, as TPU tiling requires; the
chunk is passed token-major like the pool) and the kernel loops over the
query heads statically.

On CPU the kernels run in Pallas interpret mode (the wrappers in
``ops.py`` flip the flag automatically) — same math, no TPU lowering.
Interpret mode does not check TPU tiling; ``tests/test_tpu_compile.py``
compiles every kernel for a described v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash.tile import (
    LANES as _LANES,
    finalize_tiles,
    init_tiles,
    online_softmax_tile,
)


def _cache_tile_mask(length, span, c0, r0, iota_r, iota_c, *, window,
                     rolling):
    """Valid-column mask + absolute positions for one cache-segment tile.

    Returns (mask, None); rows/cols are (block_q, block_k) iotas local to
    the tile; positions and validity follow the module docstring.
    """
    rows_pos = length + r0 + iota_r          # absolute chunk-query positions
    cols = c0 + iota_c                       # cache slot indices
    if rolling:
        last = length - 1
        pos = last - ((last - cols) % span)
        mask = (pos >= 0) & (cols < span)
    else:
        pos = cols
        mask = cols < length
    if window is not None:
        mask = mask & ((rows_pos - pos) < window)
    return mask


def _chunk_tile_mask(n_valid, j0, r0, iota_r, iota_c, *, window):
    rows = r0 + iota_r                       # chunk-relative row index
    cols = j0 + iota_c
    mask = (cols < n_valid) & (rows >= cols)
    if window is not None:
        mask = mask & ((rows - cols) < window)
    return mask


# ---------------------------------------------------------------------------
# Contiguous caches (fp32/bf16 values, or quantized codes + scale rows)
# ---------------------------------------------------------------------------
def _prefill_kernel(meta_ref, q_ref, kc_ref, vc_ref, kn_ref, vn_ref, *refs,
                    scale, variant, window, rolling, span, block_q, block_k,
                    nkc, nkn, quant, num_q_heads):
    if quant:
        (ksc_ref, vsc_ref, ksn_ref, vsn_ref,
         o_ref, m_scr, l_scr, acc_scr) = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    b = pl.program_id(0) // num_q_heads
    length = meta_ref[b, 0]
    n_valid = meta_ref[b, 1]

    @pl.when(ki == 0)
    def _init():
        init_tiles(m_scr, l_scr, acc_scr)

    r0 = qi * block_q
    iota_r = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    iota_c = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    # -- cache segment: kv steps 0..nkc-1 -----------------------------------
    c0 = ki * block_k
    run_c = (ki < nkc) & (c0 < jnp.minimum(length, span))
    if window is not None and not rolling:
        # whole tiles below the window floor of the lowest chunk row skip
        run_c = run_c & (c0 + block_k > length + r0 - window)

    @pl.when(run_c)
    def _cache():
        mask = _cache_tile_mask(length, span, c0, r0, iota_r, iota_c,
                                window=window, rolling=rolling)
        online_softmax_tile(
            q_ref[0].astype(jnp.float32),
            kc_ref[0].astype(jnp.float32), vc_ref[0].astype(jnp.float32),
            ksc_ref[0] if quant else None,
            vsc_ref[0] if quant else None,
            mask, m_scr, l_scr, acc_scr, scale=scale, variant=variant)

    # -- chunk segment: kv steps nkc..nkc+nkn-1 -----------------------------
    j0 = (ki - nkc) * block_k
    run_n = (ki >= nkc) & (j0 < n_valid) & (j0 < r0 + block_q)
    if window is not None:
        run_n = run_n & (j0 + block_k > r0 - window)

    @pl.when(run_n)
    def _chunk():
        mask = _chunk_tile_mask(n_valid, j0, r0, iota_r, iota_c,
                                window=window)
        online_softmax_tile(
            q_ref[0].astype(jnp.float32),
            kn_ref[0].astype(jnp.float32), vn_ref[0].astype(jnp.float32),
            ksn_ref[0] if quant else None,
            vsn_ref[0] if quant else None,
            mask, m_scr, l_scr, acc_scr, scale=scale, variant=variant)

    @pl.when(ki == nkc + nkn - 1)
    def _fin():
        finalize_tiles(o_ref.at[0], l_scr, acc_scr)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "variant", "window", "rolling", "span",
                     "block_q", "block_k", "num_q_heads", "num_kv_heads",
                     "interpret"),
)
def prefill_fwd_pallas(
    meta,        # (B, 2) int32 [cache length, chunk n_valid] (scalar pref.)
    q3,          # (B*H, C_padq, D)
    kc3,         # (B*Hkv, S_pad, D)   cache values or codes
    vc3,         # (B*Hkv, S_pad, Dv)
    kn3,         # (B*Hkv, C_padk, D)  chunk values or codes
    vn3,         # (B*Hkv, C_padk, Dv)
    ksc3=None,   # (B*Hkv, 1, S_pad) f32 cache K scales (quantized caches)
    vsc3=None,   # (B*Hkv, 1, S_pad) f32 cache V scales
    ksn3=None,   # (B*Hkv, 1, C_padk) f32 chunk K scales
    vsn3=None,   # (B*Hkv, 1, C_padk) f32 chunk V scales
    *,
    scale,
    variant,
    window,
    rolling,
    span,        # real (unpadded) cache slot count S
    block_q,
    block_k,
    num_q_heads,
    num_kv_heads,
    interpret,
):
    BH, Cq, D = q3.shape
    Sp = kc3.shape[1]
    Ck = kn3.shape[1]
    Dv = vc3.shape[2]
    nq = Cq // block_q
    nkc = Sp // block_k
    nkn = Ck // block_k
    group = num_q_heads // num_kv_heads
    quant = ksc3 is not None
    kernel = functools.partial(
        _prefill_kernel, scale=scale, variant=variant, window=window,
        rolling=rolling, span=span, block_q=block_q, block_k=block_k,
        nkc=nkc, nkn=nkn, quant=quant, num_q_heads=num_q_heads,
    )

    def kvh(bh):
        return (bh // num_q_heads) * num_kv_heads + (
            bh % num_q_heads) // group

    # clamped segment maps: outside its own segment each operand repeats its
    # previous block index, so the pipeline skips the refetch entirely
    def cache_map(bh, qi, ki, meta):
        return (kvh(bh), jnp.minimum(ki, nkc - 1), 0)

    def chunk_map(bh, qi, ki, meta):
        return (kvh(bh), jnp.clip(ki - nkc, 0, nkn - 1), 0)

    def cache_scale_map(bh, qi, ki, meta):
        return (kvh(bh), 0, jnp.minimum(ki, nkc - 1))

    def chunk_scale_map(bh, qi, ki, meta):
        return (kvh(bh), 0, jnp.clip(ki - nkc, 0, nkn - 1))

    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda bh, qi, ki, meta: (bh, qi, 0)),
        pl.BlockSpec((1, block_k, D), cache_map),
        pl.BlockSpec((1, block_k, Dv), cache_map),
        pl.BlockSpec((1, block_k, D), chunk_map),
        pl.BlockSpec((1, block_k, Dv), chunk_map),
    ]
    args = [q3, kc3, vc3, kn3, vn3]
    if quant:
        in_specs += [
            pl.BlockSpec((1, 1, block_k), cache_scale_map),
            pl.BlockSpec((1, 1, block_k), cache_scale_map),
            pl.BlockSpec((1, 1, block_k), chunk_scale_map),
            pl.BlockSpec((1, 1, block_k), chunk_scale_map),
        ]
        args += [ksc3, vsc3, ksn3, vsn3]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, nq, nkc + nkn),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, Dv),
                               lambda bh, qi, ki, meta: (bh, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BH, Cq, Dv), q3.dtype),
        interpret=interpret,
    )(meta, *args)


# ---------------------------------------------------------------------------
# Paged caches: in-kernel block-table indexing (scalar-prefetch index maps)
# ---------------------------------------------------------------------------
def _paged_prefill_kernel(bt_ref, meta_ref, q_ref, kc_ref, vc_ref, kn_ref,
                          vn_ref, *refs, scale, variant, window, page_size,
                          block_q, nkc, nkn, num_kv_heads, quant):
    if quant:
        (ksc_ref, vsc_ref, ksn_ref, vsn_ref,
         o_ref, m_scr, l_scr, acc_scr) = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
        ksc_ref = vsc_ref = ksn_ref = vsn_ref = None
    del bt_ref  # consumed by the index maps; the body never reads it
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    length = meta_ref[b, 0]
    n_valid = meta_ref[b, 1]
    num_q_heads = q_ref.shape[1]
    group = num_q_heads // num_kv_heads

    @pl.when(ki == 0)
    def _init():
        init_tiles(m_scr, l_scr, acc_scr)

    r0 = qi * block_q
    iota_r = jax.lax.broadcasted_iota(jnp.int32, (block_q, page_size), 0)
    iota_c = jax.lax.broadcasted_iota(jnp.int32, (block_q, page_size), 1)

    def heads(k_ref, v_ref, ks_ref, vs_ref, mask):
        """One page (all KV heads) through the tile step of every q head."""
        if quant:  # (page, Hkv) scale blocks -> one lane row per KV head
            k_scales = ks_ref[0].T
            v_scales = vs_ref[0].T
        for g in range(num_kv_heads):
            k = k_ref[0, :, g, :].astype(jnp.float32)
            v = v_ref[0, :, g, :].astype(jnp.float32)
            for h in range(g * group, (g + 1) * group):
                online_softmax_tile(
                    q_ref[0, h].astype(jnp.float32), k, v,
                    k_scales[g:g + 1] if quant else None,
                    v_scales[g:g + 1] if quant else None,
                    mask, m_scr.at[h], l_scr.at[h], acc_scr.at[h],
                    scale=scale, variant=variant)

    # -- paged history: kv steps 0..nkc-1, absolute positions ---------------
    c0 = ki * page_size
    run_c = (ki < nkc) & (c0 < length)
    if window is not None:
        # pages entirely below the window floor of the lowest row skip
        run_c = run_c & (c0 + page_size > length + r0 - window)

    @pl.when(run_c)
    def _cache():
        heads(kc_ref, vc_ref, ksc_ref, vsc_ref,
              _cache_tile_mask(length, nkc * page_size, c0, r0, iota_r,
                               iota_c, window=window, rolling=False))

    # -- chunk segment ------------------------------------------------------
    j0 = (ki - nkc) * page_size
    run_n = (ki >= nkc) & (j0 < n_valid) & (j0 < r0 + block_q)
    if window is not None:
        run_n = run_n & (j0 + page_size > r0 - window)

    @pl.when(run_n)
    def _chunk():
        heads(kn_ref, vn_ref, ksn_ref, vsn_ref,
              _chunk_tile_mask(n_valid, j0, r0, iota_r, iota_c,
                               window=window))

    @pl.when(ki == nkc + nkn - 1)
    def _fin():
        for h in range(num_q_heads):
            finalize_tiles(o_ref.at[0, h], l_scr.at[h], acc_scr.at[h])


@functools.partial(
    jax.jit,
    static_argnames=("scale", "variant", "window", "page_size", "block_q",
                     "interpret"),
)
def paged_prefill_fwd_pallas(
    bt,          # (B, max_blocks) int32 block tables (scalar prefetch)
    meta,        # (B, 2) int32: [:, 0] length, [:, 1] n_valid (scalar pref.)
    q4,          # (B, H, C_padq, D)
    k4,          # (pool_blocks, page_size, Hkv, D)   pool values or codes
    v4,          # (pool_blocks, page_size, Hkv, Dv)
    kn4,         # (B, C_padk, Hkv, D)  chunk values or codes, token-major
    vn4,         # (B, C_padk, Hkv, Dv)
    ks3=None,    # (pool_blocks, page_size, Hkv) f32 K scale pool (quantized)
    vs3=None,    # (pool_blocks, page_size, Hkv) f32 V scale pool
    ksn3=None,   # (B, C_padk, Hkv) f32 chunk K scales
    vsn3=None,   # (B, C_padk, Hkv) f32 chunk V scales
    *,
    scale,
    variant,
    window,
    page_size,
    block_q,
    interpret,
):
    B, H, Cq, D = q4.shape
    nblk, _, Hkv, _ = k4.shape
    Dv = v4.shape[-1]
    Ck = kn4.shape[1]
    _, MB = bt.shape
    nq = Cq // block_q
    nkn = Ck // page_size
    quant = ks3 is not None
    kernel = functools.partial(
        _paged_prefill_kernel, scale=scale, variant=variant, window=window,
        page_size=page_size, block_q=block_q, nkc=MB, nkn=nkn,
        num_kv_heads=Hkv, quant=quant,
    )

    # the block table is resolved here, per grid step, before the tile DMA:
    # sentinel entries (= pool_blocks, unallocated) are clamped into range —
    # they only ever cover positions >= length, which the kernel masks.
    # Outside the cache segment the page index clamps to the last table
    # entry (repeated block => no refetch).
    def pool_map(b, qi, ki, bt, meta):
        page = jnp.minimum(bt[b, jnp.minimum(ki, MB - 1)], nblk - 1)
        return (page, 0, 0, 0)

    def chunk_map(b, qi, ki, bt, meta):
        return (b, jnp.clip(ki - MB, 0, nkn - 1), 0, 0)

    def pool_scale_map(b, qi, ki, bt, meta):
        return pool_map(b, qi, ki, bt, meta)[:3]

    def chunk_scale_map(b, qi, ki, bt, meta):
        return chunk_map(b, qi, ki, bt, meta)[:3]

    in_specs = [
        pl.BlockSpec((1, H, block_q, D),
                     lambda b, qi, ki, bt, meta: (b, 0, qi, 0)),
        pl.BlockSpec((1, page_size, Hkv, D), pool_map),
        pl.BlockSpec((1, page_size, Hkv, Dv), pool_map),
        pl.BlockSpec((1, page_size, Hkv, D), chunk_map),
        pl.BlockSpec((1, page_size, Hkv, Dv), chunk_map),
    ]
    args = [bt, meta, q4, k4, v4, kn4, vn4]
    if quant:
        in_specs += [
            pl.BlockSpec((1, page_size, Hkv), pool_scale_map),
            pl.BlockSpec((1, page_size, Hkv), pool_scale_map),
            pl.BlockSpec((1, page_size, Hkv), chunk_scale_map),
            pl.BlockSpec((1, page_size, Hkv), chunk_scale_map),
        ]
        args += [ks3, vs3, ksn3, vsn3]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nq, MB + nkn),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, block_q, Dv),
                               lambda b, qi, ki, bt, meta: (b, 0, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, block_q, _LANES), jnp.float32),
            pltpu.VMEM((H, block_q, _LANES), jnp.float32),
            pltpu.VMEM((H, block_q, Dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Cq, Dv), q4.dtype),
        interpret=interpret,
    )(*args)
