"""The plain float32 reference of DeepSeek-V3's decoder as Moonlight-16B-A3B
publishes it (architecture ``deepseek_v3``), at one chip's expert share, and
its low-precision control.

It follows the published architecture in ``jax.numpy``, float32 weights
and activations and full-precision matmuls: pre-norm RMSNorm; latent
attention computed the published, un-absorbed way (per-head keys
``[kv_lat · W_UK ‖ k_rope]`` and values ``kv_lat · W_UV`` expanded for every
position, Dk = nope + rope, Dv = v, scale 1/sqrt(nope + rope)); leading
dense SwiGLU layers; then MoE layers whose sigmoid ``noaux_tc`` router picks
the top-k experts on score plus correction bias and weights them by their
unbiased scores, normalised over the k and times ``routed_scaling_factor``,
beside the shared experts; an untied output head.

The expert share: the router scores all ``deployment.n_routed_experts``
experts and normalises over all k chosen, but only the held experts
[0, n_routed_experts) add their part, as on the program's chip; what the
experts held elsewhere would add is left out, in both alike. Departures
from the published description, besides that share: the weights are
random (``bench/archs/deepseek_v3.py``); rotary embeddings rotate split
halves, where the published code interleaves, a fixed permutation of the
random weights' query and key columns. The latent's own RMSNorm uses eps
1e-6, as the published code's ``kv_a_layernorm`` does; the block norms use
``rms_norm_eps``.

It imports nothing of the program and reads only the benchmark's own
weights. Attention, the float8 control and the gaps the harness compares
are ``bench/reference_core.py``'s; the weights are upcast one layer at a
time inside the layer scan, so no float32 copy of the whole model exists.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference_core as core
from bench.reference_core import fq, mm

LATENT_EPS = 1e-6


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


def _swiglu(h, gate, up, down, quant):
    return mm(jax.nn.silu(mm(h, gate, quant)) * mm(h, up, quant), down, quant)


def _attention(x, lw, pos, sz, variant, split, tile, quant):
    """The un-absorbed latent attention of one layer, (S, d) -> (S, d)."""
    H, R, P, N, V = (sz[k] for k in ("heads", "rank", "rope", "nope", "v"))
    S, d = x.shape
    h = _rms(x, lw["attn_norm"], sz["eps"])
    q = mm(h, lw["wq"].reshape(d, H * (N + P)), quant).reshape(S, H, N + P)
    q = q.transpose(1, 0, 2)
    q = jnp.concatenate([q[..., :N], _rope(q[..., N:], pos,
                                           sz["rope_theta"])], -1)
    kv = mm(h, lw["wkv_a"], quant)                       # (S, R + P)
    lat = _rms(kv[:, :R], lw["kv_norm"], LATENT_EPS)
    k_rope = _rope(kv[None, :, R:], pos, sz["rope_theta"])   # (1, S, P)
    kvb = mm(lat, lw["wkv_b"].reshape(R, H * (N + V)), quant)
    kvb = kvb.reshape(S, H, N + V).transpose(1, 0, 2)
    k = jnp.concatenate([kvb[..., :N], jnp.broadcast_to(k_rope, (H, S, P))],
                        -1)
    q, k, v = (fq(a, -1, quant) for a in (q, k, kvb[..., N:]))
    o = core.attention(q, k, v, (N + P) ** -0.5, variant, split, tile)
    return x + mm(o.transpose(1, 0, 2).reshape(S, H * V),
                  lw["wo"].reshape(H * V, d), quant)


def _experts(h, lw, sz, quant):
    """The held experts' part of the routed output and the shared experts,
    (S, d) -> (S, d)."""
    scores = jax.nn.sigmoid(mm(h, lw["router"], quant))   # (S, E)
    _, ids = jax.lax.top_k(scores + lw["e_bias"], sz["top_k"])
    w = jnp.take_along_axis(scores, ids, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * sz["scaling"]
    # each held expert's combine weight per token; other experts add nothing
    held = jnp.arange(sz["held"])
    w_held = jnp.sum(jnp.where(ids[:, :, None] == held, w[:, :, None], 0.0),
                     1)                                    # (S, held)
    hq = fq(h, -1, quant)
    g = jnp.einsum("sd,edf->esf", hq, fq(lw["w_gate"], 1, quant),
                   precision=core.HI)
    u = jnp.einsum("sd,edf->esf", hq, fq(lw["w_up"], 1, quant),
                   precision=core.HI)
    y = jnp.einsum("esf,efd->esd", fq(jax.nn.silu(g) * u, -1, quant),
                   fq(lw["w_down"], 1, quant), precision=core.HI)
    routed = jnp.einsum("esd,se->sd", y, w_held, precision=core.HI)
    return routed + _swiglu(h, lw["shared_gate"], lw["shared_up"],
                            lw["shared_down"], quant)


def hidden(w, tokens, split, sz, variant, tile, quant):
    """Final-norm hidden states (S, d) of one sequence ``tokens`` (S,)."""
    pos = jnp.arange(tokens.shape[0])
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    x = f32(w["embed"])[tokens]

    def dense(x, lw):
        lw = jax.tree.map(f32, lw)
        x = _attention(x, lw, pos, sz, variant, split, tile, quant)
        h = _rms(x, lw["mlp_norm"], sz["eps"])
        return x + _swiglu(h, lw["w_gate"], lw["w_up"], lw["w_down"],
                           quant), None

    def moe(x, lw):
        lw = jax.tree.map(f32, lw)
        x = _attention(x, lw, pos, sz, variant, split, tile, quant)
        return x + _experts(_rms(x, lw["mlp_norm"], sz["eps"]), lw, sz,
                            quant), None

    x, _ = jax.lax.scan(dense, x, w["dense"])
    x, _ = jax.lax.scan(moe, x, w["moe"])
    return _rms(x, f32(w["final_norm"]), sz["eps"])


def logits(w, h, quant):
    """(rows, d) -> (rows, V) through the untied output head."""
    return mm(h, w["lm_head"].astype(jnp.float32), quant)


served_gaps = core.served_gaps(hidden, logits)
