"""Pallas TPU kernels: fused chunked prefill — a chunk of C fresh queries
against [KV cache ++ chunk] without ever materializing the concatenation
(DESIGN.md §10).

This is the prefill twin of the flash-decode kernels (``kernels/decode``):
the same shared online-softmax step (``flash/tile.py``), extended to a
Tq × Tk grid over a *two-segment* KV axis: the resident history first,
then the chunk's own fresh KV. Both segments are separate operands whose
index maps *clamp* outside their own segment — a clamped map repeats the
previous block index, so the pipeline never refetches it — and ``pl.when``
picks exactly one segment body per step. No gathered, concatenated, or
dequantized copy of the history ever exists in HBM.

* contiguous kernel: grid (B * H, q_blocks, cache_blocks + chunk_blocks),
  one query head's block_q chunk rows a program, one block_k tile a step.
* paged kernel: a work list for a grid, one step per 128-token block
  that has work — each slot's history blocks below its length, then its
  chunk blocks below n_valid, an empty slot one step — all query heads of
  a slot a program. A block is ``block_pages`` pages, each its own
  operand whose index map reads the block table. A step stages its block
  head-major in f32 scratch beside its mask; one loop over the query heads
  serves both segments. Inside a block ExpMul keeps its page-sized key
  tiles (``online_softmax_block``): the benchmark's reference walks page
  tiles, and ExpMul's blocked result depends on the tiling.

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
before each page DMA, sentinel entries (= pool_blocks) are clamped into
range and only ever cover positions >= length, which the mask hides. Blocks
entirely below a local window's floor are skipped. Each DMA brings one page
with all its KV heads (trailing ``(Hkv, D)`` dims whole, as TPU tiling
requires; the chunk is passed token-major like the pool).

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
    finalize_block,
    finalize_tiles,
    init_tiles,
    online_softmax_block,
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
# Paged caches: a work list of 128-token blocks, each slot's only up to its
# length, each page of a block brought by its own operand
# ---------------------------------------------------------------------------
# the shared-latent kernel's scoped VMEM: at 16 heads, 128 query rows and
# 576 columns a block's queries, state and output take 18.8 MB on a v5e,
# past the 16 MB default (tests/test_tpu_compile.py)
LATENT_VMEM_BYTES = 32 * 1024 * 1024


def block_pages(page_size):
    """Pages per block of the paged prefill kernel: 128 tokens' worth, so a
    block fills the lanes; one page where the page does not divide 128."""
    return _LANES // page_size if _LANES % page_size == 0 else 1


def _paged_prefill_kernel(meta_ref, bt_ref, w_slot, w_qblk, w_kv, w_edge,
                          w_hist, w_chunk, q_ref, *refs, scale, variant, window,
                          page_size, pages, nkc, nkn, max_blocks, block_q,
                          num_kv_heads, quant, v_width):
    if v_width is None:
        k_pages, v_pages = refs[:pages], refs[pages:2 * pages]
        kn_ref, vn_ref = refs[2 * pages:2 * pages + 2]
        refs = refs[2 * pages + 2:]
    else:  # one shared latent head: V is K's first v_width columns
        k_pages, kn_ref = refs[:pages], refs[pages]
        refs = refs[pages + 1:]
    if quant:
        ks_pages, vs_pages = refs[:pages], refs[pages:2 * pages]
        refs = refs[2 * pages:]
        ksn_ref, vsn_ref, o_ref, m_scr, l_scr, acc_scr, *blk = refs
        q_t, k_blk, v_blk, mask_blk, ks_blk, vs_blk = blk
    else:
        o_ref, m_scr, l_scr, acc_scr, q_t, k_blk, v_blk, mask_blk = refs
    del bt_ref, w_hist, w_chunk  # consumed by the index maps
    t = pl.program_id(0)
    b = w_slot[t]
    ki = w_kv[t]
    length = meta_ref[b, 0]
    n_valid = meta_ref[b, 1]
    num_q_heads = q_ref.shape[1]
    group = num_q_heads // num_kv_heads
    width = pages * page_size          # one block, tokens

    empty = ki == nkc + nkn            # a row block with nothing to read

    @pl.when((w_edge[t] % 2 == 1) & ~empty)   # a row block's first step
    def _init():
        init_tiles(m_scr, l_scr, acc_scr)
        for h in range(num_q_heads):
            q_t[h] = q_ref[0, h].astype(jnp.float32).T

    r0 = w_qblk[t] * block_q
    # the block step is transposed: keys along sublanes, query rows along
    # lanes, so ExpMul's per-page maxima are reductions over sublanes
    iota_r = jax.lax.broadcasted_iota(jnp.int32, (width, block_q), 1)
    iota_c = jax.lax.broadcasted_iota(jnp.int32, (width, block_q), 0)

    def concat(tiles):
        return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, 0)

    def stage(k_tiles, v_tiles, scale_rows, mask):
        """Lay one block out head-major in f32 scratch beside its mask, so
        a single head loop below serves both segments. ``k_tiles(g)`` gives
        KV head g's pieces of the block, in key order."""
        for g in range(num_kv_heads):
            k_blk[g] = concat([x.astype(jnp.float32) for x in k_tiles(g)])
            if v_width is None:
                v_blk[g] = concat([x.astype(jnp.float32)
                                   for x in v_tiles(g)]).T
            else:
                v_blk[g] = k_blk[g][:, :v_width].T
            if quant:  # (block, Hkv) scale rows -> a (block, 1) column
                for sc, sc_blk in zip(scale_rows, (ks_blk, vs_blk)):
                    sc_blk[g] = sc[:, g:g + 1]
        mask_blk[...] = mask.astype(jnp.int32)

    # -- paged history: kv steps 0..nkc-1, one block of pages each ---------
    c0 = ki * width
    run_c = (ki < nkc) & (c0 < length)

    def head_rows(ref, g):
        return ref[0] if v_width is not None else ref[0, :, g, :]

    @pl.when(run_c)
    def _cache():
        stage(lambda g: [head_rows(r, g) for r in k_pages],
              lambda g: [r[0, :, g, :] for r in v_pages],
              (concat([r[0] for r in ks_pages]),
               concat([r[0] for r in vs_pages])) if quant else None,
              _cache_tile_mask(length, max_blocks * page_size, c0, r0,
                               iota_r, iota_c, window=window, rolling=False))

    # -- chunk segment: kv steps nkc.., one block of chunk tokens each ------
    j0 = (ki - nkc) * width
    run_n = (ki >= nkc) & (j0 < n_valid) & (j0 < r0 + block_q)

    @pl.when(run_n)
    def _chunk():
        stage(lambda g: [head_rows(kn_ref, g)],
              lambda g: [vn_ref[0, :, g, :]],
              (ksn_ref[0], vsn_ref[0]) if quant else None,
              _chunk_tile_mask(n_valid, j0, r0, iota_r, iota_c,
                               window=window))

    @pl.when(run_c | run_n)
    def _attend():
        mask = mask_blk[...] != 0

        def head(h, carry):
            g = h // group
            online_softmax_block(
                q_t[h], k_blk[g], v_blk[g],
                ks_blk[g] if quant else None, vs_blk[g] if quant else None,
                mask, m_scr.at[h], l_scr.at[h], acc_scr.at[h],
                scale=scale, variant=variant, tile=page_size)
            return carry

        jax.lax.fori_loop(0, num_q_heads, head, 0)

    @pl.when((w_edge[t] >= 2) & ~empty)       # a row block's last step
    def _fin():
        for h in range(num_q_heads):
            finalize_block(o_ref.at[0, h], l_scr.at[h], acc_scr.at[h])

    @pl.when(empty)                    # fully masked rows read 0
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)


def _work_list(meta, *, max_blocks, page_size, pages, nkc, nkn, nq,
               block_q, window):
    """The kernel's grid steps, one per block that has work (DESIGN.md
    §10). Each (slot, q block) row block walks its history blocks below
    ``length`` (from the window floor of its lowest row), then its chunk
    blocks below ``n_valid``; a row block with neither takes one empty
    step, so every output block is written.

    Returns the number of steps and, per step: slot, q block, kv step
    (< nkc: history block; nkc + j: chunk block j; nkc + nkn: none), an
    edge code (1 first step of a row block, 2 last, 3 both), and the latest
    step at or before it that read history (resp. the chunk), whose blocks
    the index maps repeat, so a step fetches only what it reads. Running
    sums and maxima are taken over the row blocks only; a step finds its
    row block by one comparison against every row block's end and reads
    the row block's values by one gather."""
    B = meta.shape[0]
    width = pages * page_size
    length, n_valid = meta[:, 0], meta[:, 1]
    r0 = jnp.arange(nq, dtype=jnp.int32) * block_q
    hist = jnp.minimum(length, max_blocks * page_size)
    hi_c = jnp.broadcast_to(-(-hist // width)[:, None], (B, nq))
    hi_n = -(-jnp.minimum(n_valid[:, None], r0 + block_q) // width)
    lo_c = lo_n = jnp.zeros((B, nq), jnp.int32)
    if window is not None:  # blocks entirely below the window floor skip
        lo_c = jnp.clip((length[:, None] + r0 - window + 1) // width, 0, hi_c)
        lo_n = jnp.clip((r0 - window + 1) // width, 0, hi_n)
    n_c, n_n, lo_c, lo_n = (x.ravel() for x in (hi_c - lo_c, hi_n - lo_n,
                                                lo_c, lo_n))
    n = jnp.maximum(n_c + n_n, 1)
    ends = jnp.cumsum(n)
    starts = ends - n
    # each row block's last history (resp. chunk) step, or that of the
    # nearest row block before it that has one
    last_c = jax.lax.cummax(jnp.where(n_c > 0, starts + n_c - 1, 0))
    last_n = jax.lax.cummax(jnp.where(n_n > 0, ends - 1, 0))
    prev_n = jnp.concatenate([jnp.zeros((1,), jnp.int32), last_n[:-1]])
    step = jnp.arange(B * nq * (nkc + nkn), dtype=jnp.int32)  # most steps
    row = jnp.minimum(jnp.sum(ends[None, :] <= step[:, None], axis=1),
                      B * nq - 1)
    start, n_c, n_n, lo_c, lo_n, n, last_c, prev_n = jnp.stack(
        [starts, n_c, n_n, lo_c, lo_n, n, last_c, prev_n], axis=1)[row].T
    local = step - start
    hist_step = local < n_c
    kv = jnp.where(hist_step, lo_c + local, nkc + lo_n + local - n_c)
    kv = jnp.where(n_c + n_n == 0, nkc + nkn, kv)
    chunk_step = ~hist_step & (n_n > 0)
    edge = (local == 0) + 2 * (local == n - 1)
    return (ends[-1], row // nq, row % nq, kv, edge.astype(jnp.int32),
            jnp.where(hist_step, step, last_c),
            jnp.where(chunk_step, step, prev_n))


@functools.partial(
    jax.jit,
    static_argnames=("scale", "variant", "window", "page_size", "block_q",
                     "v_width", "interpret"),
)
def paged_prefill_fwd_pallas(
    bt,          # (B, max_blocks) int32 block tables
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
    v_width=None,
):
    """``v4=vn4=None`` with ``v_width``: one shared latent head (MLA
    absorbed, DESIGN.md §14): k4 (pool_blocks, page_size, D), kn4 (B,
    C_padk, D), V their first ``v_width`` columns, read from K's pages."""
    B, H, Cq, D = q4.shape
    latent = v4 is None
    nblk = k4.shape[0]
    Hkv = 1 if latent else k4.shape[2]
    Dv = v_width if latent else v4.shape[-1]
    _, MB = bt.shape
    nq = Cq // block_q
    quant = ks3 is not None
    assert not (latent and quant), "a quantized pool keeps its own V"
    pages = block_pages(page_size)
    width = pages * page_size
    nkc = -(-MB // pages)
    chunk = [kn4] + ([] if latent else [vn4]) + (
        [ksn3, vsn3] if quant else [])
    pad = -kn4.shape[1] % width            # whole blocks of chunk tokens
    if pad:
        chunk = [jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
                 for x in chunk]
    nkn = chunk[0].shape[1] // width
    steps, *work = _work_list(meta, max_blocks=MB, page_size=page_size,
                              pages=pages, nkc=nkc, nkn=nkn, nq=nq,
                              block_q=block_q, window=window)
    kernel = functools.partial(
        _paged_prefill_kernel, scale=scale, variant=variant, window=window,
        page_size=page_size, pages=pages, nkc=nkc, nkn=nkn, max_blocks=MB,
        block_q=block_q, num_kv_heads=Hkv, quant=quant,
        v_width=v_width if latent else None,
    )

    # The index maps resolve the block table per step, from the latest step
    # that read history: pages past the slot's last live page repeat it, so
    # no table entry past ``length`` is read; sentinels (= pool_blocks)
    # clamp into range and only ever cover positions >= length.
    def page_spec(i, *block):
        def index(t, meta, bt, slot, qblk, kv, edge, hist, chunk):
            b, blk = slot[hist[t]], kv[hist[t]]
            live = pl.cdiv(jnp.minimum(meta[b, 0], MB * page_size),
                           page_size)
            page = jnp.clip(blk * pages + i, 0, jnp.maximum(live - 1, 0))
            return (jnp.minimum(bt[b, page], nblk - 1),) + (0,) * (
                1 + len(block))
        return pl.BlockSpec((1, page_size, *block), index)

    def chunk_spec(*block):
        def index(t, meta, bt, slot, qblk, kv, edge, hist, chunk):
            return (slot[chunk[t]], jnp.clip(kv[chunk[t]] - nkc, 0, nkn - 1)
                    ) + (0,) * len(block)
        return pl.BlockSpec((1, width, *block), index)

    def rows(t, meta, bt, slot, qblk, kv, edge, hist, chunk):
        return (slot[t], 0, qblk[t], 0)

    if latent:
        in_specs = [pl.BlockSpec((1, H, block_q, D), rows),
                    *[page_spec(i, D) for i in range(pages)], chunk_spec(D)]
        args = [q4, *[k4] * pages, chunk[0]]
    else:
        in_specs = [
            pl.BlockSpec((1, H, block_q, D), rows),
            *[page_spec(i, Hkv, D) for i in range(pages)],
            *[page_spec(i, Hkv, Dv) for i in range(pages)],
            chunk_spec(Hkv, D), chunk_spec(Hkv, Dv),
        ]
        args = [q4, *[k4] * pages, *[v4] * pages, *chunk[:2]]
    scratch = [                                      # transposed state
        pltpu.VMEM((H, 1, block_q), jnp.float32),
        pltpu.VMEM((H, 1, block_q), jnp.float32),
        pltpu.VMEM((H, Dv, block_q), jnp.float32),
        pltpu.VMEM((H, D, block_q), jnp.float32),    # q, per row block
        pltpu.VMEM((Hkv, width, D), jnp.float32),    # the staged block
        pltpu.VMEM((Hkv, Dv, width), jnp.float32),
        pltpu.VMEM((width, block_q), jnp.int32),     # and its mask
    ]
    if quant:
        in_specs += [*[page_spec(i, Hkv) for i in range(pages)],
                     *[page_spec(i, Hkv) for i in range(pages)],
                     chunk_spec(Hkv), chunk_spec(Hkv)]
        args += [*[ks3] * pages, *[vs3] * pages, *chunk[2:]]
        scratch += [pltpu.VMEM((Hkv, width, 1), jnp.float32)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(steps,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, block_q, Dv), rows),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Cq, Dv), q4.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=LATENT_VMEM_BYTES) if latent else None,
        interpret=interpret,
    )(meta, bt, *work, *args)
