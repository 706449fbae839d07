"""The shared online-softmax tile step every Pallas attention kernel runs.

One KV tile of the FlashAttention-2 recurrence, in exact or ExpMul
arithmetic, with optional in-register dequantization of quantized K/V
codes — the single piece of math behind the full-sequence forward kernel
(``flash.py``), the three prefill entry points (``prefill.py``, DESIGN.md
§10) and the three decode entry points (``kernels/decode/decode.py``,
DESIGN.md §9). Keeping it in one place is what makes the fused-vs-gather
parity argument compositional: two kernels that feed this step the same
tile sequence and masks compute the same thing.

The row axis of every tile is whatever the caller tiles queries by (a
block of chunk rows for prefill, the GQA head group for decode); the
column axis is one KV tile. State (m, l, acc) lives in VMEM scratch across
the KV grid steps and is finalized by ``finalize_tiles``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.numerics.log2exp import apply_pow2_scale, log2exp_lhat, pow2_neg

MASK_VALUE = -1e30
LANES = 128


def online_softmax_tile(q, k, v, k_scale, v_scale, mask,
                        m_scr, l_scr, acc_scr, *, scale, variant):
    """One KV tile of the online-softmax recurrence (shared by all kernels).

    q: (rows, D) f32; k: (bk, D) f32 values — or raw codes when ``k_scale``
    is given; v: (bk, Dv) values or codes; k_scale/v_scale: (1, bk) f32
    per-row scales laid along lanes (one per KV column), or None; mask:
    (rows, bk) bool of valid columns.

    Quantized fusion: scores take one column rescale after the q·codes
    matmul, and the value matmul folds the scale into the probability tile
    — for the ExpMul variant the pow2 weights therefore multiply the
    still-quantized value codes. The denominator uses the dequantized
    scores (k_scale is already inside ``s``), never v_scale.
    """
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if k_scale is not None:
        s = s * k_scale
    s = jnp.where(mask, s, MASK_VALUE)
    m_prev = m_scr[...][:, :1]
    l_prev = l_scr[...][:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    if variant == "exact":
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = p if v_scale is None else p * v_scale
        acc = acc_scr[...] * alpha + jax.lax.dot_general(
            pv, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
    elif variant == "expmul":
        # paper Alg. 3/4: integer shift-add Log2Exp; the probability tile is
        # an exact power of two assembled from bits; the state rescale is an
        # exponent-field integer subtraction. No exp, no FP multiply.
        lr = log2exp_lhat(m_prev - m_new)
        p = jnp.where(mask, pow2_neg(log2exp_lhat(s - m_new), jnp.float32), 0.0)
        l_new = apply_pow2_scale(l_prev, lr) + jnp.sum(p, axis=1, keepdims=True)
        pv = p if v_scale is None else p * v_scale
        acc = apply_pow2_scale(
            acc_scr[...], jnp.broadcast_to(lr, acc_scr.shape)
        ) + jax.lax.dot_general(
            pv, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
    else:
        raise ValueError(variant)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
    acc_scr[...] = acc


def init_tiles(m_scr, l_scr, acc_scr):
    """Empty online-softmax state, before a row block's first KV tile."""
    m_scr[...] = jnp.full_like(m_scr, MASK_VALUE)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def finalize_tiles(o_ref, l_scr, acc_scr):
    """acc / l into ``o_ref`` (a (rows, Dv) view of the output block);
    fully-masked rows yield 0, never NaN."""
    l = l_scr[...][:, :1]
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)
