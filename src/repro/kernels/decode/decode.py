"""Pallas TPU kernels: flash-decode (one query token against a long KV
cache) for contiguous, quantized, and paged (block-table) cache layouts.

The row axis of every tile is the ``group`` query heads that share one KV
head (GQA) — MQA (kv=1) degenerates to all H heads in one tile, which is
exactly the layout that keeps the MXU busy for single-token decode. The
contiguous grid is (B * Hkv, kv_blocks), one program per KV head; the paged
grid is (B, pages), one program per sequence looping over its KV heads.

Three kernels share one online-softmax tile step (``_online_softmax_step``):

* **contiguous** — per-slot ``(B, Hkv, S, ·)`` caches; per-sequence lengths
  arrive as a scalar-prefetch (SMEM) operand.
* **quantized contiguous** — the cache-side operands are int8/fp8 *codes*
  plus per-row float32 scales (``numerics/quant.py`` codec). Dequant is
  fused in-register: the score matmul runs on raw codes and takes one
  column rescale (``(q @ codes^T) * k_scale``), the value matmul folds the
  scale into the probability tile (``(p * v_scale) @ codes``) — the
  full-precision K/V never exists outside VMEM registers. Scale rows enter
  as ``(BHkv, 1, S)`` so a tile's scales lie along lanes.
* **paged** — the KV history lives in a flat physical token pool viewed as
  ``(pool_blocks, page_size, Hkv, ·)``; per-sequence block tables are a
  scalar-prefetch operand and the *index maps* resolve each grid step's
  physical block (``block_table[b, kv_block]``) before the DMA is issued —
  the standard TPU PagedAttention formulation. One DMA brings a page with
  all its KV heads (a block whose trailing ``(Hkv, D)`` dims are whole, as
  TPU tiling requires). No gathered copy of the history is ever
  materialized in HBM. Sentinel entries (= pool_blocks,
  unallocated) are clamped into range by the index map; they only cover
  positions at/after ``length`` so the length mask hides them. Local
  windows mask positions below ``length - window`` in-kernel (paged caches
  keep absolute positions; DESIGN.md §7), and whole pages outside
  [length - window, length) are skipped.

The ExpMul variant applies the paper's operator to the decode path, where
the softmax/rescale work is the dominant VPU cost (there is no large matmul
to hide it behind) — the most favourable case for the technique on TPU. Its
pow2 softmax weights multiply the still-quantized value tiles, so the fused
operator composes with KV quantization exactly as in the paper.

On CPU the kernels run in Pallas interpret mode (the wrappers in ``ops.py``
flip the flag automatically) — same math, no TPU lowering (DESIGN.md §9).
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
    finalize_tiles as _finalize,
    init_tiles,
    online_softmax_tile as _online_softmax_step,
)


# ---------------------------------------------------------------------------
# Contiguous caches (fp32/bf16 values, or quantized codes + scale rows)
# ---------------------------------------------------------------------------
def _decode_kernel(len_ref, q_ref, k_ref, v_ref, *refs, scale, variant,
                   block_k, nk, quant, num_kv_heads):
    if quant:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
        ks_ref = vs_ref = None
    ki = pl.program_id(1)
    length = len_ref[pl.program_id(0) // num_kv_heads]

    @pl.when(ki == 0)
    def _init():
        init_tiles(m_scr, l_scr, acc_scr)

    c0 = ki * block_k

    @pl.when(c0 < length)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        cols = c0 + jax.lax.broadcasted_iota(
            jnp.int32, (q.shape[0], block_k), 1)
        _online_softmax_step(
            q, k_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32),
            ks_ref[0] if quant else None,
            vs_ref[0] if quant else None,
            cols < length, m_scr, l_scr, acc_scr,
            scale=scale, variant=variant)

    @pl.when(ki == nk - 1)
    def _fin():
        _finalize(o_ref.at[0], l_scr, acc_scr)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "variant", "block_k", "num_kv_heads",
                     "interpret"),
)
def decode_fwd_pallas(
    lengths,    # (B,) int32 valid entries (scalar prefetch)
    q3,         # (B*Hkv, group, D)
    k3,         # (B*Hkv, Sk_padded, D)   values or codes
    v3,         # (B*Hkv, Sk_padded, Dv)  values or codes
    ks3=None,   # (B*Hkv, 1, Sk_padded) f32 per-row K scales (quantized)
    vs3=None,   # (B*Hkv, 1, Sk_padded) f32 per-row V scales
    *,
    scale,
    variant,
    block_k,
    num_kv_heads,
    interpret,
):
    BHkv, group, D = q3.shape
    Sk = k3.shape[1]
    Dv = v3.shape[2]
    nk = Sk // block_k
    quant = ks3 is not None
    kernel = functools.partial(
        _decode_kernel, scale=scale, variant=variant, block_k=block_k, nk=nk,
        quant=quant, num_kv_heads=num_kv_heads,
    )
    in_specs = [
        pl.BlockSpec((1, group, D), lambda bh, ki, ln: (bh, 0, 0)),
        pl.BlockSpec((1, block_k, D), lambda bh, ki, ln: (bh, ki, 0)),
        pl.BlockSpec((1, block_k, Dv), lambda bh, ki, ln: (bh, ki, 0)),
    ]
    args = [q3, k3, v3]
    if quant:
        in_specs += [
            pl.BlockSpec((1, 1, block_k), lambda bh, ki, ln: (bh, 0, ki)),
            pl.BlockSpec((1, 1, block_k), lambda bh, ki, ln: (bh, 0, ki)),
        ]
        args += [ks3, vs3]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BHkv, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, group, Dv), lambda bh, ki, ln: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, _LANES), jnp.float32),
            pltpu.VMEM((group, _LANES), jnp.float32),
            pltpu.VMEM((group, Dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BHkv, group, Dv), q3.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), *args)


# ---------------------------------------------------------------------------
# Paged caches: in-kernel block-table indexing (scalar-prefetch index maps)
# ---------------------------------------------------------------------------
def _paged_decode_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, *refs, scale,
                         variant, page_size, nk, quant, window, num_kv_heads):
    if quant:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    del bt_ref  # consumed by the index maps; the body never reads it
    ki = pl.program_id(1)
    length = len_ref[pl.program_id(0)]

    @pl.when(ki == 0)
    def _init():
        init_tiles(m_scr, l_scr, acc_scr)

    c0 = ki * page_size
    run = c0 < length
    if window is not None:
        # pages entirely below the window floor contribute nothing
        run = jnp.logical_and(run, c0 + page_size > length - window)

    @pl.when(run)
    def _body():
        group = q_ref.shape[2]
        cols = c0 + jax.lax.broadcasted_iota(jnp.int32, (group, page_size), 1)
        mask = cols < length
        if window is not None:
            mask = jnp.logical_and(mask, cols >= length - window)
        if quant:  # (page, Hkv) scale blocks -> one lane row per KV head
            k_scales = ks_ref[0].T
            v_scales = vs_ref[0].T
        for h in range(num_kv_heads):
            _online_softmax_step(
                q_ref[0, h].astype(jnp.float32),
                k_ref[0, :, h, :].astype(jnp.float32),
                v_ref[0, :, h, :].astype(jnp.float32),
                k_scales[h:h + 1] if quant else None,
                v_scales[h:h + 1] if quant else None,
                mask, m_scr.at[h], l_scr.at[h], acc_scr.at[h],
                scale=scale, variant=variant)

    @pl.when(ki == nk - 1)
    def _fin():
        for h in range(num_kv_heads):
            _finalize(o_ref.at[0, h], l_scr.at[h], acc_scr.at[h])


@functools.partial(
    jax.jit,
    static_argnames=("scale", "variant", "page_size", "window", "interpret"),
)
def paged_decode_fwd_pallas(
    bt,         # (B, max_blocks) int32 block tables (scalar prefetch)
    len1,       # (B,) int32 valid entries incl. the current token
    q4,         # (B, Hkv, group, D)
    k4,         # (pool_blocks, page_size, Hkv, D)   values or codes
    v4,         # (pool_blocks, page_size, Hkv, Dv)  values or codes
    ks3=None,   # (pool_blocks, page_size, Hkv) f32 K scale pool (quantized)
    vs3=None,   # (pool_blocks, page_size, Hkv) f32 V scale pool
    *,
    scale,
    variant,
    page_size,
    window,
    interpret,
):
    B, Hkv, group, D = q4.shape
    nblk = k4.shape[0]
    Dv = v4.shape[-1]
    _, MB = bt.shape
    quant = ks3 is not None
    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, variant=variant,
        page_size=page_size, nk=MB, quant=quant, window=window,
        num_kv_heads=Hkv,
    )

    # The block table is resolved here, per grid step, before the tile DMA:
    # sentinel entries (= pool_blocks, unallocated) are clamped into range —
    # they only ever cover positions >= length, which the kernel masks.
    def page_map(b, ki, bt_ref, ln):
        return (jnp.minimum(bt_ref[b, ki], nblk - 1), 0, 0, 0)

    def scale_map(b, ki, bt_ref, ln):
        return page_map(b, ki, bt_ref, ln)[:3]

    in_specs = [
        pl.BlockSpec((1, Hkv, group, D), lambda b, ki, bt, ln: (b, 0, 0, 0)),
        pl.BlockSpec((1, page_size, Hkv, D), page_map),
        pl.BlockSpec((1, page_size, Hkv, Dv), page_map),
    ]
    args = [bt, len1.astype(jnp.int32), q4, k4, v4]
    if quant:
        in_specs += [
            pl.BlockSpec((1, page_size, Hkv), scale_map),
            pl.BlockSpec((1, page_size, Hkv), scale_map),
        ]
        args += [ks3, vs3]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, MB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hkv, group, Dv),
                               lambda b, ki, bt, ln: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, group, _LANES), jnp.float32),
            pltpu.VMEM((Hkv, group, _LANES), jnp.float32),
            pltpu.VMEM((Hkv, group, Dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, Dv), q4.dtype),
        interpret=interpret,
    )(*args)
