"""What every plain reference shares, whatever its architecture: the
float8 control's rounding, ExpMul's softmax on the kernels' tiles, and
the gap of each served token below the reference's best.

A reference (the file a configuration's ``"reference"`` key names) writes
its own layers as ``hidden(w, tokens, split, sz, variant, tile, quant)``,
giving final-norm hidden states (S, d) of one sequence, and its
``logits(w, h, quant)``, (rows, d) -> (rows, V), in float32 ``jax.numpy``.
It rounds each matmul input with ``fq`` (or multiplies with ``mm``),
attends with ``attention``, and exposes ``served_gaps =
reference_core.served_gaps(hidden, logits)``, which the harness calls. It
imports nothing of the program and reads only the benchmark's own
weights (the architecture's ``canonical_weights``).

Attention variants:

  exact   softmax over the whole causal row.
  expmul  the paper's ExpMul online softmax (arXiv:2505.14314, Alg. 3):
          e^x is replaced by 2^-L with L = round(-x * 1.4375) computed in
          16-bit fixed point with 10 fraction bits, clipped to [-15, 0];
          the running state is rescaled by the same power of two. This
          blocked form depends on how keys are grouped into tiles, so the
          reference walks the same tiles as a paged server with
          ``tile``-token pages: aligned tiles of the history, and, for a
          position that was fed in as a lone token of a chunked step
          (``split``), that token as a tile of its own after the history.

``quant="fp8"`` is the control: every matmul input, weights included, is
rounded to float8 e4m3 with one scale per row of the contraction, the
precision one step below the bfloat16 the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
MASK = -1e30
FP8_MAX = 448.0


def fq(x, axis, quant):
    """Fake-quantize ``x`` to ``quant`` with a scale per slice along
    ``axis`` (the contraction axis); identity for float32."""
    if quant is None:
        return x
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x, w, quant):
    """x (..., k) @ w (k, n) in float32, inputs fake-quantized."""
    return jnp.matmul(fq(x, -1, quant), fq(w, 0, quant), precision=HI)


def log2exp(x):
    """Integer L >= 0 with e^x ~= 2^-L (x <= 0), as the paper's shifter
    computes it: 10-fraction-bit fixed point, x*log2(e) ~= x + x>>1 - x>>4
    with flooring shifts, then round half up."""
    xf = jnp.round(jnp.clip(x, -15.0, 0.0) * 1024.0).astype(jnp.int32)
    acc = xf + (xf >> 1) - (xf >> 4)
    return (-acc + 512) >> 10


def pow2(L):
    """2^-L, exactly (an exponent, not a transcendental)."""
    return jnp.ldexp(jnp.ones(L.shape, jnp.float32), -L)


def attn_exact(q, k, v, scale):
    S = q.shape[1]
    s = jnp.einsum("hqd,hkd->hqk", q, k, precision=HI) * scale
    causal = np.tril(np.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, -1), v, precision=HI)


def attn_expmul(q, k, v, scale, split, tile):
    H, S, _ = q.shape
    rows = jnp.arange(S)
    own_tile = split[:, None]                    # (S, 1)

    def step(state, s, mask, vt):
        m, l, acc = state
        s = jnp.where(mask, s, MASK)
        m_new = jnp.maximum(m, jnp.max(s, -1, keepdims=True))
        r = pow2(log2exp(m - m_new))
        p = jnp.where(mask, pow2(log2exp(s - m_new)), 0.0)
        l = l * r + jnp.sum(p, -1, keepdims=True)
        acc = acc * r + jnp.einsum("hqk,hkd->hqd", p, vt, precision=HI)
        return m_new, l, acc

    def body(state, t):
        kt = jax.lax.dynamic_slice_in_dim(k, t * tile, tile, axis=1)
        vt = jax.lax.dynamic_slice_in_dim(v, t * tile, tile, axis=1)
        s = jnp.einsum("hqd,hkd->hqk", q, kt, precision=HI) * scale
        cols = t * tile + jnp.arange(tile)
        causal = cols[None, :] <= rows[:, None]
        own = (cols[None, :] == rows[:, None]) & own_tile
        state = step(state, s, causal & ~own, vt)
        return step(state, s, own, vt), None

    init = (jnp.full((H, S, 1), MASK, jnp.float32),
            jnp.zeros((H, S, 1), jnp.float32),
            jnp.zeros((H, S, v.shape[-1]), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(body, init, jnp.arange(S // tile))
    return acc / jnp.where(l == 0, 1.0, l)


def attention(q, k, v, scale, variant, split, tile):
    """Causal attention of q (H, S, Dk) over k (H, S, Dk), v (H, S, Dv)
    under ``variant`` (module docstring)."""
    if variant == "exact":
        return attn_exact(q, k, v, scale)
    if variant == "expmul":
        return attn_expmul(q, k, v, scale, split, tile)
    raise ValueError(f"unknown attention variant {variant!r}")


def served_gaps(hidden, logits):
    """The jitted ``served_gaps`` of a reference with these ``hidden`` and
    ``logits`` (module docstring)."""

    @functools.partial(jax.jit, static_argnames=("sz", "variant", "tile",
                                                 "rows", "control"))
    def served_gaps(w, tokens, split, first_row, served, n_served, *, sz,
                    variant, tile, rows, control):
        """Per served token, how far its reference logit lies below the
        reference's best: ``max(ref) - ref[served]`` (0 where it is the
        best).

        tokens (S,) is the prompt followed by the served tokens, padded;
        the token served at row i was sampled from position ``first_row +
        i``; ``rows`` bounds ``n_served``. With ``control`` the second
        output is the same gap for the token the fp8 control puts first at
        each position (else zeros). Rows past ``n_served`` read 0.
        """
        sz = dict(sz)
        h = hidden(w, tokens, split, sz, variant, tile, None)
        idx = first_row + jnp.arange(rows)
        valid = jnp.arange(rows) < n_served
        ref = logits(w, h[idx], None)
        best = jnp.max(ref, -1)
        gap = best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
        if control:
            hc = hidden(w, tokens, split, sz, variant, tile, "fp8")
            pick = jnp.argmax(logits(w, hc[idx], "fp8"), -1)
            cgap = best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
        else:
            cgap = jnp.zeros_like(gap)
        return jnp.where(valid, gap, 0.0), jnp.where(valid, cgap, 0.0)

    return served_gaps
