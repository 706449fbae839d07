"""Core attention API — the paper's technique as a composable JAX module.

Implementations register with the backend registry
(``repro.kernels.registry``) under three calling conventions — full
sequence, chunked prefill, and single-token decode — and layers dispatch
through an ``AttentionSpec`` built from the model config (DESIGN.md §3).
The keyword entry points ``attention``/``decode_attention`` below are thin
wrappers kept for scripts and benchmarks.

Full-sequence implementations:

  impl="ref"        full-softmax reference (small shapes, ground truth)
  impl="flash_jnp"  scan-blocked FlashAttention-2 in pure jnp/lax. This is
                    the XLA path used for 512-device dry-runs and training:
                    O(S·block) memory, autodiff-able, shard_map/pjit friendly.
  impl="pallas"     the Pallas TPU kernel (exact or ExpMul variant), wrapped
                    in a custom_vjp whose backward recomputes via flash_jnp
                    (FlashAttention-style recomputation; the paper's ASIC is
                    forward/inference-only, see DESIGN.md §2).

``variant`` selects the arithmetic: "exact" (baseline hardware: separate exp
and FP multiplies) or "expmul" (the paper's fused operator). For training
through the quantizer set ``use_ste=True`` (straight-through gradients).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash.ops import (
    flash_attention_fwd,
    fused_paged_prefill_attention_pallas,
    paged_prefill_attention_pallas,
    prefill_attention_pallas,
)
from repro.kernels.decode.ops import (
    decode_attention_pallas,
    fused_paged_decode_attention_pallas,
    paged_decode_attention_pallas,
)
from repro.kernels.paged import gather_rows
from repro.kernels.registry import (
    AttentionSpec,
    dispatch_attention,
    dispatch_decode,
    register_attention,
    register_decode,
    register_paged_decode,
    register_paged_prefill,
    register_prefill,
)
from repro.numerics.log2exp import (
    apply_pow2_scale,
    log2exp_lhat,
    pow2_neg,
    qexp_ste,
)

MASK_VALUE = -1e30


def _qexp(x, use_ste):
    """Quantized e^x as an exact power of two (paper's Log2Exp)."""
    if use_ste:
        return qexp_ste(x)
    return pow2_neg(log2exp_lhat(x), jnp.float32)


# ---------------------------------------------------------------------------
# Reference (full softmax)
# ---------------------------------------------------------------------------
def attention_ref(q, k, v, *, causal=True, scale=None, window=None,
                  variant="exact", use_ste=False):
    B, H, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    group = H // Hkv
    scale = float(1.0 / np.sqrt(D)) if scale is None else scale
    qf = q.astype(jnp.float32).reshape(B, Hkv, group, Sq, D)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qf, k.astype(jnp.float32)) * scale
    rows = jnp.arange(Sq)[:, None]
    cols = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= rows >= cols
    if window is not None:
        mask &= (rows - cols) < window
    s = jnp.where(mask, s, MASK_VALUE)
    if variant == "expmul":
        m = jnp.max(s, axis=-1, keepdims=True)
        p = _qexp(s - m, use_ste)
        p = jnp.where(mask, p, 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        p = p / jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0, not NaN
    else:
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(mask, p, 0.0)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return o.reshape(B, H, Sq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Scan-blocked FlashAttention-2 (XLA path)
# ---------------------------------------------------------------------------
def flash_jnp(
    q, k, v, *,
    causal=True,
    scale=None,
    window=None,
    variant="exact",
    use_ste=False,
    block_k=512,
    remat=True,
    causal_q_chunks=4,
):
    """FlashAttention-2 as a lax.scan over KV blocks.

    Memory per step is O(B·H·Sq_chunk·block_k) for the score tile; with
    ``remat=True`` the scan body is rematerialized in the backward pass, so
    residuals do not accumulate across steps.

    ``causal_q_chunks``: causal block skipping. The query axis is split into
    C chunks (a static python loop), and chunk i only scans KV blocks up to
    its own diagonal — cutting causal compute from S^2 to ~S^2·(C+1)/(2C)
    (C=4 -> 62.5%). §Perf iteration 1.
    """
    B, H, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    Dv = v.shape[-1]  # MLA: value head dim can differ from qk dim
    group = H // Hkv
    scale = float(1.0 / np.sqrt(D)) if scale is None else scale
    bk = min(block_k, Sk)
    if Sk % bk:  # choose the largest divisor <= block_k
        bk = next(b for b in range(bk, 0, -1) if Sk % b == 0)
    nk = Sk // bk

    from repro.sharding.constraints import constrain, model_axis_size

    # causal q-chunking applies when q and k cover the same positions
    n_chunks = 1
    if causal and window is None and causal_q_chunks > 1 and Sq == Sk:
        for c in range(min(causal_q_chunks, nk), 0, -1):
            if Sq % c == 0 and (Sq // c) % bk == 0:
                n_chunks = c
                break
    Sq_c = Sq // n_chunks

    # TP dim for attention activations: kv-heads if they divide the model
    # axis, else the head group, else the (chunked) query sequence.
    msize = model_axis_size()
    tp = [None, None, None]  # (Hkv, group, Sq_c)
    for i, dim in enumerate((Hkv, group, Sq_c)):
        if msize and dim % msize == 0:
            tp[i] = "model"
            break
    dims5 = ("batch", tp[0], tp[1], tp[2], None)

    kb_full = jnp.moveaxis(k.reshape(B, Hkv, nk, bk, D), 2, 0)
    vb_full = jnp.moveaxis(v.reshape(B, Hkv, nk, bk, Dv), 2, 0)

    def run_chunk(q_chunk, row0, nk_c):
        # q/k stay in the input dtype; the score einsum accumulates in f32
        # (preferred_element_type) — no materialized f32 copies of q or k
        # (§Perf llava iteration: the f32 casts were ~1/3 of s-tile traffic)
        qf = q_chunk.reshape(B, Hkv, group, Sq_c, D)
        qf = constrain(qf, *dims5)
        rows = row0 + jnp.arange(Sq_c)[:, None]

        def body(masked, carry, kt, vt, ci):
            # keep the online-softmax state sharded: replicated carry inits
            # otherwise win GSPMD's while-loop fixpoint and de-shard batch
            m, l, acc = carry
            m = constrain(m, *dims5)
            l = constrain(l, *dims5)
            acc = constrain(acc, *dims5)
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qf, kt,
                           preferred_element_type=jnp.float32) * scale
            if masked:
                cols = ci * bk + jnp.arange(bk)[None, :]
                mask = jnp.ones((Sq_c, bk), bool)
                if causal:
                    mask &= rows >= cols
                if window is not None:
                    mask &= (rows - cols) < window
                s = jnp.where(mask, s, MASK_VALUE)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            if variant == "expmul":
                alpha = _qexp(m - m_new, use_ste)
                p = _qexp(s - m_new, use_ste)
            else:
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new)
            if masked:
                p = jnp.where(mask, p, 0.0)
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * alpha + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p.astype(v.dtype), vt,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l_new, acc_new), None

        def make_body(masked):
            fn = lambda carry, xs: body(masked, carry, *xs)
            return jax.checkpoint(fn) if remat else fn

        init = (
            jnp.full((B, Hkv, group, Sq_c, 1), MASK_VALUE, jnp.float32),
            jnp.zeros((B, Hkv, group, Sq_c, 1), jnp.float32),
            jnp.zeros((B, Hkv, group, Sq_c, Dv), jnp.float32),
        )
        # interior blocks (entirely below the diagonal band) skip the mask
        # build + two select materializations per tile (§Perf llava iter.)
        if causal and window is None:
            n_interior = max(0, row0 // bk)
        elif not causal and window is None:
            n_interior = nk_c          # no masking at all (cross-attention)
        else:
            n_interior = 0
        n_interior = min(n_interior, nk_c)
        carry = init
        if n_interior:
            carry, _ = jax.lax.scan(
                make_body(False), carry,
                (kb_full[:n_interior], vb_full[:n_interior],
                 jnp.arange(n_interior)),
            )
        if nk_c > n_interior:
            carry, _ = jax.lax.scan(
                make_body(True), carry,
                (kb_full[n_interior:nk_c], vb_full[n_interior:nk_c],
                 jnp.arange(n_interior, nk_c)),
            )
        m, l, acc = carry
        l = jnp.where(l == 0.0, 1.0, l)
        return (acc / l).reshape(B, H, Sq_c, Dv)

    if n_chunks == 1:
        return run_chunk(q, 0, nk).astype(q.dtype)
    outs = []
    for ci in range(n_chunks):
        q_chunk = q[:, :, ci * Sq_c:(ci + 1) * Sq_c, :]
        nk_c = ((ci + 1) * Sq_c) // bk  # only blocks at/below the diagonal
        outs.append(run_chunk(q_chunk, ci * Sq_c, nk_c))
    return jnp.concatenate(outs, axis=2).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas path with recompute backward
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _pallas_attn_vjp(causal, scale, window, variant, block_q, block_k):
    @jax.custom_vjp
    def f(q, k, v):
        return flash_attention_fwd(
            q, k, v, causal=causal, scale=scale, window=window,
            variant=variant, block_q=block_q, block_k=block_k,
        )

    def fwd(q, k, v):
        return f(q, k, v), (q, k, v)

    def bwd(res, g):
        q, k, v = res
        # FlashAttention-style recomputation; expmul uses STE gradients.
        def ref_fn(q, k, v):
            return flash_jnp(
                q, k, v, causal=causal, scale=scale, window=window,
                variant=variant, use_ste=(variant == "expmul"),
                block_k=block_k,
            )
        _, pullback = jax.vjp(ref_fn, q, k, v)
        return pullback(g)

    f.defvjp(fwd, bwd)
    return f


# ---------------------------------------------------------------------------
# Chunked-prefill attention (serving path)
# ---------------------------------------------------------------------------
def prefill_attention(q, k, v, *, q_positions, kv_positions, kv_valid,
                      scale=None, window=None, variant="exact", use_ste=False):
    """Masked attention for a prompt chunk against gathered KV.

    q: (B, H, C, D) — C chunk queries per sequence; k/v: (B, Hkv, T, ·) —
    typically the concatenation [cache ++ chunk]. Causality is positional:
    query i attends KV j iff ``kv_valid[b, j]`` and ``kv_positions[b, j] <=
    q_positions[b, i]`` (and inside ``window`` when set), which makes the
    same code path exact for fresh caches, rolling (windowed) caches, and
    partially-filled chunks (DESIGN.md §6).
    """
    B, H, C, D = q.shape
    _, Hkv, T, _ = k.shape
    group = H // Hkv
    scale = float(1.0 / np.sqrt(D)) if scale is None else scale
    qf = q.astype(jnp.float32).reshape(B, Hkv, group, C, D)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qf, k.astype(jnp.float32)) * scale
    delta = q_positions[:, :, None] - kv_positions[:, None, :]  # (B, C, T)
    mask = kv_valid[:, None, :] & (delta >= 0)
    if window is not None:
        mask &= delta < window
    mask = mask[:, None, None]  # broadcast over (Hkv, group)
    s = jnp.where(mask, s, MASK_VALUE)
    m = jnp.max(s, axis=-1, keepdims=True)
    if variant == "expmul":
        p = _qexp(s - m, use_ste)
    else:
        p = jnp.exp(s - m)
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p / jnp.where(l == 0.0, 1.0, l),
                   v.astype(jnp.float32))
    Dv = v.shape[-1]
    return o.reshape(B, H, C, Dv).astype(q.dtype)


def prefill_positions(lengths, n_valid, span, C, *, rolling):
    """Positional tensors implied by the prefill convention (DESIGN.md §10).

    lengths/n_valid: (B,); span: cache slot count S; C: chunk length.
    Returns (q_positions (B, C), kv_positions (B, S+C), kv_valid (B, S+C))
    for the concatenated [cache ++ chunk] ordering. ``rolling`` selects the
    windowed rolling-buffer slot convention: slot j holds the newest
    written position congruent to j modulo the span — exact because
    softmax over a valid set is order-invariant (DESIGN.md §6/§10).
    """
    B = lengths.shape[0]
    idx = jnp.arange(C)[None, :]
    q_positions = lengths[:, None] + idx
    chunk_valid = idx < n_valid[:, None]
    slot = jnp.arange(span)[None, :]
    if rolling:
        last = lengths[:, None] - 1
        cache_pos = last - ((last - slot) % span)
    else:
        cache_pos = jnp.broadcast_to(slot, (B, span))
    cache_valid = (cache_pos >= 0) & (cache_pos < lengths[:, None])
    kv_positions = jnp.concatenate([cache_pos, q_positions], axis=1)
    kv_valid = jnp.concatenate([cache_valid, chunk_valid], axis=1)
    return q_positions, kv_positions, kv_valid


# ---------------------------------------------------------------------------
# Registry-backed dispatch (DESIGN.md §3)
# ---------------------------------------------------------------------------
@register_attention("ref")
def _attention_ref_impl(q, k, v, *, spec, causal, scale):
    return attention_ref(q, k, v, causal=causal, scale=scale,
                         window=spec.window, variant=spec.variant,
                         use_ste=spec.use_ste)


@register_attention("flash_jnp")
def _flash_jnp_impl(q, k, v, *, spec, causal, scale):
    return flash_jnp(q, k, v, causal=causal, scale=scale, window=spec.window,
                     variant=spec.variant, use_ste=spec.use_ste,
                     block_k=spec.block_k, remat=spec.remat,
                     causal_q_chunks=spec.q_chunks)


@register_attention("pallas")
def _pallas_impl(q, k, v, *, spec, causal, scale):
    if scale is None:
        scale = float(1.0 / np.sqrt(q.shape[-1]))
    fn = _pallas_attn_vjp(causal, scale, spec.window, spec.variant,
                          min(spec.block_q, q.shape[2]),
                          min(spec.block_k, k.shape[2]))
    return fn(q, k, v)


@register_prefill("masked_xla")
def _prefill_masked_xla(q, k_cache, v_cache, k_chunk, v_chunk, *, spec,
                        scale, lengths, n_valid, rolling):
    """Concat [cache ++ chunk], rebuild the implied positional tensors, and
    run the one-pass masked softmax — the XLA prefill baseline every fused
    kernel is pinned against."""
    q_positions, kv_positions, kv_valid = prefill_positions(
        lengths, n_valid, k_cache.shape[2], q.shape[2], rolling=rolling)
    return prefill_attention(
        q, jnp.concatenate([k_cache, k_chunk], axis=2),
        jnp.concatenate([v_cache, v_chunk], axis=2),
        q_positions=q_positions, kv_positions=kv_positions,
        kv_valid=kv_valid, scale=scale, window=spec.window,
        variant=spec.variant, use_ste=spec.use_ste)


@register_prefill("pallas")
def _prefill_pallas(q, k_cache, v_cache, k_chunk, v_chunk, *, spec, scale,
                    lengths, n_valid, rolling):
    """Fused chunked prefill (DESIGN.md §10): the kernel walks the cache
    and the chunk as separate KV grid segments, masking positionally
    in-kernel — no materialized concatenation. Dv != Dq capable, so MLA
    prefill dispatches here too."""
    return prefill_attention_pallas(
        q, k_cache, v_cache, k_chunk, v_chunk, lengths, n_valid,
        scale=scale, variant=spec.variant, window=spec.window,
        rolling=rolling, block_q=spec.block_q, block_k=spec.block_k)


def _masked_decode_xla(q, k_cache, v_cache, mask, *, variant, scale):
    """Shared single-token decode core: q (B,H,D), caches (B,Hkv,S,·),
    mask (B, S) bool over cache rows."""
    B, H, D = q.shape
    _, Hkv, S, _ = k_cache.shape
    group = H // Hkv
    scale = float(1.0 / np.sqrt(D)) if scale is None else scale
    qf = q.astype(jnp.float32).reshape(B, Hkv, group, D)
    s = jnp.einsum("bhgd,bhkd->bhgk", qf, k_cache.astype(jnp.float32)) * scale
    mask = mask[:, None, None, :]
    s = jnp.where(mask, s, MASK_VALUE)
    m = jnp.max(s, axis=-1, keepdims=True)
    if variant == "expmul":
        p = pow2_neg(log2exp_lhat(s - m), jnp.float32)
    else:
        p = jnp.exp(s - m)
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhgk,bhkd->bhgd", p / jnp.where(l == 0, 1, l),
                   v_cache.astype(jnp.float32))
    Dv = v_cache.shape[-1]  # MLA: value head dim can differ from qk dim
    return o.reshape(B, H, Dv).astype(q.dtype)


@register_decode("xla")
def _decode_xla(q, k_cache, v_cache, lengths, *, spec, scale):
    S = k_cache.shape[2]
    mask = jnp.arange(S)[None, :] < lengths[:, None]
    return _masked_decode_xla(q, k_cache, v_cache, mask,
                              variant=spec.variant, scale=scale)


@register_decode("pallas")
def _decode_pallas(q, k_cache, v_cache, lengths, *, spec, scale):
    return decode_attention_pallas(
        q, k_cache, v_cache, lengths, scale=scale, variant=spec.variant,
        block_k=spec.decode_block_k,
    )


# ---------------------------------------------------------------------------
# Paged (block-table) attention: gather-then-compute built-ins (DESIGN.md §7)
# ---------------------------------------------------------------------------
def _gather_kv(pool, rows):
    """(pool_tokens, Hkv, ·) pool + (B, L) rows -> (B, Hkv, L, ·)."""
    return jnp.moveaxis(gather_rows(pool, rows), 1, 2)


@register_paged_prefill("gather_xla")
def _paged_prefill_gather_xla(q, k_chunk, v_chunk, k_pool, v_pool, rows, *,
                              spec, scale, q_positions, chunk_valid, lengths,
                              block_tables=None, page_size=0):
    """Gather the paged history, concat the fresh chunk, and run the exact
    positional-masking prefill math as the contiguous ``masked_xla`` path.

    The gathered rows are in logical position order, so kv position j is
    simply j — the same masking rule as a fresh contiguous cache, for every
    variant (exact/expmul) and for local windows."""
    B, L = rows.shape
    k_all = jnp.concatenate([_gather_kv(k_pool, rows), k_chunk], axis=2)
    v_all = jnp.concatenate([_gather_kv(v_pool, rows), v_chunk], axis=2)
    hist_pos = jnp.broadcast_to(jnp.arange(L)[None, :], (B, L))
    kv_positions = jnp.concatenate([hist_pos, q_positions], axis=1)
    kv_valid = jnp.concatenate(
        [hist_pos < lengths[:, None], chunk_valid], axis=1)
    return prefill_attention(
        q, k_all, v_all, q_positions=q_positions, kv_positions=kv_positions,
        kv_valid=kv_valid, scale=scale, window=spec.window,
        variant=spec.variant, use_ste=spec.use_ste)


@register_paged_prefill("gather_pallas")
def _paged_prefill_gather_pallas(q, k_chunk, v_chunk, k_pool, v_pool, rows,
                                 *, spec, scale, q_positions, chunk_valid,
                                 lengths, block_tables=None, page_size=0):
    """Gather-then-kernel paged prefill: materialize the history in logical
    order (XLA gather), then the contiguous Pallas prefill kernel with
    absolute positions. The baseline the fused kernel is benchmarked
    against, and the identical-tile expmul parity oracle when ``block_k``
    equals the page size (DESIGN.md §10)."""
    n_valid = jnp.sum(chunk_valid.astype(jnp.int32), axis=1)
    return paged_prefill_attention_pallas(
        q, k_chunk, v_chunk, k_pool, v_pool, rows, lengths, n_valid,
        scale=scale, variant=spec.variant, window=spec.window,
        block_q=spec.block_q,
        block_k=page_size if page_size else spec.block_k)


@register_paged_prefill("pallas")
def _paged_prefill_pallas(q, k_chunk, v_chunk, k_pool, v_pool, rows, *,
                          spec, scale, q_positions, chunk_valid, lengths,
                          block_tables=None, page_size=0, v_width=None):
    """Fused paged prefill (DESIGN.md §10): block-table indexing happens
    inside the kernel's index maps, so the chunk attends to the history
    straight out of the pool — no materialized gather copy. Windows mask
    in-kernel with whole-page skipping. Callers that dispatch without the
    table operands (``rows`` only) get the gather-then-kernel form."""
    if block_tables is None:
        return _paged_prefill_gather_pallas(
            q, k_chunk, v_chunk, k_pool, v_pool, rows, spec=spec,
            scale=scale, q_positions=q_positions, chunk_valid=chunk_valid,
            lengths=lengths)
    n_valid = jnp.sum(chunk_valid.astype(jnp.int32), axis=1)
    return fused_paged_prefill_attention_pallas(
        q, k_chunk, v_chunk, k_pool, v_pool, block_tables, lengths, n_valid,
        page_size=page_size, scale=scale, variant=spec.variant,
        window=spec.window, block_q=spec.block_q, v_width=v_width)


@register_paged_decode("pallas")
def _paged_decode_pallas(q, k_pool, v_pool, rows, lengths, *, spec, scale,
                         block_tables=None, page_size=0, v_width=None):
    """Fused paged flash-decode (DESIGN.md §9): block-table indexing happens
    inside the kernel's index maps, so the history is read straight out of
    the pool — no materialized gather copy. Windows mask in-kernel. Callers
    that dispatch without the table operands (``rows`` only) get the
    gather-then-kernel form."""
    if block_tables is None:
        return _paged_decode_gather_pallas(q, k_pool, v_pool, rows, lengths,
                                           spec=spec, scale=scale)
    return fused_paged_decode_attention_pallas(
        q, k_pool, v_pool, block_tables, lengths, page_size=page_size,
        scale=scale, variant=spec.variant, window=spec.window,
        v_width=v_width)


@register_paged_decode("gather_pallas")
def _paged_decode_gather_pallas(q, k_pool, v_pool, rows, lengths, *, spec,
                                scale, block_tables=None, page_size=0):
    if spec.window is not None:
        # the contiguous flash-decode kernel masks only by length; windows
        # need the positional path (the fused "pallas" backend masks them
        # in-kernel)
        return _paged_decode_gather_xla(q, k_pool, v_pool, rows, lengths,
                                        spec=spec, scale=scale)
    return paged_decode_attention_pallas(
        q, k_pool, v_pool, rows, lengths, scale=scale, variant=spec.variant,
        block_k=spec.decode_block_k)


@register_paged_decode("gather_xla")
def _paged_decode_gather_xla(q, k_pool, v_pool, rows, lengths, *, spec,
                             scale, block_tables=None, page_size=0):
    """Gather the paged history (current token included) and decode.

    Unlike the contiguous rolling-buffer decode, windowed layers here keep
    absolute positions, so the window is enforced by masking rows below
    ``lengths - window`` instead of by buffer wrap-around — the same valid
    set, hence the same softmax (order-invariant, DESIGN.md §7)."""
    L = rows.shape[1]
    pos = jnp.arange(L)[None, :]
    mask = pos < lengths[:, None]
    if spec.window is not None:
        mask &= pos >= lengths[:, None] - spec.window
    return _masked_decode_xla(q, _gather_kv(k_pool, rows),
                              _gather_kv(v_pool, rows), mask,
                              variant=spec.variant, scale=scale)


# ---------------------------------------------------------------------------
# Back-compat keyword entry points (thin wrappers over the registry)
# ---------------------------------------------------------------------------
def attention(
    q, k, v, *,
    causal=True,
    scale=None,
    window=None,
    impl="flash_jnp",
    variant="exact",
    use_ste=False,
    block_q=128,
    block_k=512,
    remat=True,
    q_chunks=4,
):
    """Multi-head attention with the paper's ExpMul technique as a variant.

    q: (B, H, Sq, D); k, v: (B, Hkv, Sk, D) with H % Hkv == 0.
    """
    spec = AttentionSpec(impl=impl, variant=variant, use_ste=use_ste,
                         window=window, block_q=block_q, block_k=block_k,
                         remat=remat, q_chunks=q_chunks)
    return dispatch_attention(spec, q, k, v, causal=causal, scale=scale)


def decode_attention(
    q, k_cache, v_cache, lengths, *,
    scale=None,
    impl="xla",
    variant="exact",
    block_k=256,
):
    """Single-token decode attention against a KV cache.

    q: (B, H, D); caches: (B, Hkv, S, D); lengths: (B,) valid entries.
    """
    spec = AttentionSpec(decode_impl=impl, variant=variant,
                         decode_block_k=block_k)
    return dispatch_decode(spec, q, k_cache, v_cache, lengths, scale=scale)
