"""The plain float32 reference of a dense Qwen2-style decoder (architecture
``qwen2``), and its low-precision control.

It follows the published architecture (pre-norm RMSNorm, rotary
embeddings on split halves, grouped-query attention with QKV bias, SwiGLU,
tied embeddings) in ``jax.numpy``, float32 weights and activations and
full-precision matmuls. It imports nothing of the program and reads only
the benchmark's own weights (``bench/archs/qwen2.py``:
``canonical_weights``). Attention, the float8 control and the gaps the
harness compares are ``bench/reference_core.py``'s.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference_core as core
from bench.reference_core import HI, fq, mm


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x: (H, S, D); rotate split halves by position."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * inv
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hidden(w, tokens, split, sz, variant, tile, quant):
    """Final-norm hidden states (S, d) of one sequence ``tokens`` (S,)."""
    H, Hkv, D = sz["heads"], sz["kv_heads"], sz["head_dim"]
    S = tokens.shape[0]
    pos = jnp.arange(S)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    x = f32(w["embed"])[tokens]

    def layer(x, lw):
        lw = jax.tree.map(f32, lw)
        h = _rms(x, lw["attn_norm"], sz["eps"])
        q = (mm(h, lw["wq"], quant) + lw["bq"]).reshape(S, H, D)
        k = (mm(h, lw["wk"], quant) + lw["bk"]).reshape(S, Hkv, D)
        v = (mm(h, lw["wv"], quant) + lw["bv"]).reshape(S, Hkv, D)
        q = _rope(q.transpose(1, 0, 2), pos, sz["rope_theta"])
        k = _rope(k.transpose(1, 0, 2), pos, sz["rope_theta"])
        v = v.transpose(1, 0, 2)
        q, k, v = (fq(a, -1, quant) for a in (q, k, v))
        k = jnp.repeat(k, H // Hkv, axis=0)
        v = jnp.repeat(v, H // Hkv, axis=0)
        o = core.attention(q, k, v, D ** -0.5, variant, split, tile)
        x = x + mm(o.transpose(1, 0, 2).reshape(S, H * D), lw["wo"], quant)
        h = _rms(x, lw["mlp_norm"], sz["eps"])
        g = jax.nn.silu(mm(h, lw["w_gate"], quant)) * mm(h, lw["w_up"], quant)
        return x + mm(g, lw["w_down"], quant), None

    names = ("attn_norm", "wq", "bq", "wk", "bk", "wv", "bv", "wo",
             "mlp_norm", "w_gate", "w_up", "w_down")
    x, _ = jax.lax.scan(layer, x, {n: w[n] for n in names})
    return _rms(x, f32(w["final_norm"]), sz["eps"])


def logits(w, h, quant):
    """(rows, d) -> (rows, V) with the tied embedding."""
    e = w["embed"].astype(jnp.float32)
    return jnp.matmul(fq(h, -1, quant), fq(e, -1, quant).T, precision=HI)


served_gaps = core.served_gaps(hidden, logits)
