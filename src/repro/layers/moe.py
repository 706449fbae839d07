"""Mixture-of-Experts FFN with top-k routing.

Three dispatch implementations:

* ``dropless`` — the serving path (DESIGN.md §14): every routed row is
  computed. The rows routed to the experts this layer holds are sorted by
  expert and go through one grouped matmul per projection
  (``kernels/experts.py``); no capacity, so a token's output does not
  depend on which other tokens share its batch.
* ``scatter``  — real token routing: top-k -> per-expert capacity positions
  via cumulative counts -> scatter into an (E, C, D) buffer -> batched expert
  GEMMs -> weighted combine. Tokens over capacity are dropped (standard
  capacity-factor semantics). Used by training, tests and examples.
* ``balanced`` — deterministic round-robin assignment with router-derived
  combine weights. Identical FLOP/byte/collective profile to perfectly
  balanced routing with zero scatter overhead; used by the trillion-class
  dry-runs where the scatter gather/scatter HLOs dominate compile time.
  (Recorded in DESIGN.md; routing quality is irrelevant to the dry-run.)

Routers (``MoEConfig.router``): ``softmax`` over the top-k logits, or
DeepSeek-V3's ``sigmoid`` (noaux_tc): sigmoid scores, the top-k chosen on
score plus a per-expert correction bias (``e_bias``), weighted by the
chosen experts' unbiased scores, normalised over the k and scaled by
``routed_scaling_factor``. Either stays exact and in float32 (not ExpMul):
it is O(E) per token, negligible next to attention, and routing decisions
are quality-critical.

An expert share (``MoEConfig.held_experts``): the layer holds the routed
experts [start, stop) of all ``num_experts``, routes over all of them and
adds only its own experts' part of the result, as one chip of an
expert-parallel group does before the exchange. Shared experts
(``num_shared_experts``) are a dense SwiGLU every token passes through.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.experts import grouped_ffn, row_tile
from repro.kernels.registry import dispatch_experts
from repro.layers.common import activation_fn, dense_init
from repro.layers.mlp import mlp_apply, mlp_init


def moe_init(key, cfg, dtype):
    m = cfg.moe
    d = cfg.d_model
    E = m.num_held
    ks = jax.random.split(key, 5)
    gated = cfg.activation in ("swiglu", "geglu")
    p = {
        "router": dense_init(ks[0], (d, m.num_experts), dtype),
        "w_up": dense_init(ks[1], (E, d, m.d_ff), dtype),
        "w_down": dense_init(ks[2], (E, m.d_ff, d), dtype),
    }
    if gated:
        p["w_gate"] = dense_init(ks[3], (E, d, m.d_ff), dtype)
    if m.router == "sigmoid":
        p["e_bias"] = jnp.zeros((m.num_experts,), jnp.float32)
    if m.num_shared_experts:
        p["shared"] = mlp_init(jax.random.fold_in(key, 5), d,
                               m.num_shared_experts * m.d_ff, cfg.activation,
                               dtype)
    if m.dense_residual:
        p["dense"] = mlp_init(ks[4], d, m.dense_d_ff, cfg.activation, dtype)
    return p


def _expert_ffn(params, xe, activation):
    """xe: (E, C, D) -> (E, C, D), batched expert GEMMs."""
    act = activation_fn(activation)
    up = jnp.einsum("ecd,edf->ecf", xe, params["w_up"])
    if "w_gate" in params:
        up = act(jnp.einsum("ecd,edf->ecf", xe, params["w_gate"])) * up
    else:
        up = act(up)
    return jnp.einsum("ecf,efd->ecd", up, params["w_down"])


def _expert_ffn_ep(params, xe, cfg):
    """Expert-parallel FFN under shard_map.

    xe: (C, E, D) dispatch tensor, C over the DP axes, E over 'model'.
    Expert weights arrive FSDP-sharded on d_model and are ALL-GATHERED
    explicitly inside the region; jax.AD of all_gather is reduce-scatter,
    so weight gradients come back sharded by construction (no GSPMD
    guessing). Iteration log: EXPERIMENTS.md §Perf (kimi).
    """
    from repro.sharding.constraints import model_axis_size
    from jax.sharding import PartitionSpec as P

    if model_axis_size() == 0:  # no mesh (unit tests): plain path
        return jnp.swapaxes(
            _expert_ffn(params, jnp.swapaxes(xe, 0, 1), cfg.activation), 0, 1
        )

    mesh = jax.sharding.get_abstract_mesh()
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    act = activation_fn(cfg.activation)
    gated = "w_gate" in params

    def local_fn(wu, wg, wd, xe_l):
        # wu/wg: (E_l, D/dp, F); wd: (E_l, F, D/dp); xe_l: (C_l, E_l, D)
        wu = jax.lax.all_gather(wu, dp, axis=1, tiled=True)
        wd = jax.lax.all_gather(wd, dp, axis=2, tiled=True)
        up = jnp.einsum("ced,edf->cef", xe_l, wu)
        if gated:
            wg = jax.lax.all_gather(wg, dp, axis=1, tiled=True)
            up = act(jnp.einsum("ced,edf->cef", xe_l, wg)) * up
        else:
            up = act(up)
        return jnp.einsum("cef,efd->ced", up, wd)

    wg_arg = params["w_gate"] if gated else params["w_up"]
    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P("model", dp, None), P("model", dp, None),
                  P("model", None, dp), P(dp, "model", None)),
        out_specs=P(dp, "model", None),
    )(params["w_up"], wg_arg, params["w_down"], xe)


def route(params, x2, m):
    """(T, D) -> combine weights (T, k) float32 and expert ids (T, k) over
    all ``m.num_experts`` (module docstring)."""
    from repro.sharding.constraints import constrain

    logits = (x2.astype(jnp.float32) @ params["router"].astype(jnp.float32))
    logits = constrain(logits, "batch", None)
    if m.router == "softmax":
        top_w, top_ids = jax.lax.top_k(logits, m.top_k)      # (T, k)
        return jax.nn.softmax(top_w, axis=-1), top_ids       # exact softmax
    if m.router != "sigmoid":
        raise ValueError(f"unknown router {m.router!r}")
    scores = jax.nn.sigmoid(logits)
    _, top_ids = jax.lax.top_k(scores + params["e_bias"], m.top_k)
    top_w = jnp.take_along_axis(scores, top_ids, axis=1)
    top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    return top_w * m.routed_scaling_factor, top_ids


def _dropless(params, x2, top_w, top_ids, m, activation):
    """The held experts' part of the routed output, (T, D) float32: the
    rows routed to held experts sorted by expert, one grouped matmul per
    projection, weighted and added back to their tokens."""
    T, _ = x2.shape
    k = m.top_k
    start, _ = m.held_range
    E = m.num_held
    local = top_ids.reshape(-1) - start
    held = (local >= 0) & (local < E)
    order = jnp.argsort(jnp.where(held, local, E), stable=True)
    # every held row sorts before every other: at most T * min(k, E) rows
    rows = T * min(k, E)
    rows += -rows % row_tile(rows)
    order = jnp.pad(order, (0, max(0, rows - T * k)))[:rows]
    group_sizes = jnp.bincount(jnp.where(held, local, E), length=E + 1)[:E]
    ys = grouped_ffn(x2[order // k], params, group_sizes.astype(jnp.int32),
                     activation_fn(activation))
    valid = jnp.arange(rows) < jnp.sum(group_sizes)
    w = top_w.reshape(-1)[order]
    contrib = jnp.where(valid[:, None], ys * w[:, None], 0.0)
    return jnp.zeros((T, x2.shape[1]), jnp.float32).at[order // k].add(
        contrib)


def moe_apply(params, x, cfg, *, impl="scatter"):
    """x: (B, S, D) -> (B, S, D)."""
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    x2 = x.reshape(T, D)
    top_w, top_ids = route(params, x2, m)
    dispatch_experts(impl)
    if impl != "dropless" and m.num_held != m.num_experts:
        raise NotImplementedError(
            f"moe impl {impl!r} computes all experts; a layer that holds "
            f"experts {m.held_range} of {m.num_experts} serves dropless")

    if impl == "dropless":
        y = _dropless(params, x2, top_w, top_ids, m, cfg.activation)
    elif impl == "balanced":
        # deterministic balanced dispatch: token-copies map to experts in
        # contiguous slabs (copy i -> expert i // C), combined with router
        # weights. Cost-model exact, routing-content free (dry-run only).
        #
        # Sharding (the §Perf kimi iteration — see EXPERIMENTS.md): the
        # dispatch buffer is pinned to (E='model', C=DP, D=full) so the
        # data->expert exchange lowers to the EP all-to-all instead of a
        # full-buffer all-gather (measured 917GB/layer-class before), and
        # the expert GEMMs contract a FULL d_model against FSDP-gathered
        # weights (kills the (E_loc, C, F) partial-sum all-reduces). The
        # big tensors stay in the model dtype; only the (T, k) combine
        # weights are f32.
        from repro.sharding.constraints import constrain

        k = m.top_k
        E = m.num_experts
        C = -(-T * k // E)
        C = -(-C // 512) * 512  # divisible by dp*model on every target mesh
        pad = E * C - T * k
        xr = jnp.repeat(x2, k, axis=0)                       # (T*k, D)
        xr = jnp.pad(xr, ((0, pad), (0, 0)))
        # Round-robin dispatch layout (C, E, D): copy i -> slot i//E of
        # expert i%E. C stays DP-sharded through the (local) reshape and E
        # reshards to 'model'. Both dims of the dispatch tensor enter
        # shard_map sharded (no replicated-input cotangents); weight FSDP
        # gathers live inside the region so their AD is reduce-scatter by
        # construction. Iteration log in EXPERIMENTS.md §Perf (an explicit
        # all_to_all variant measured WORSE under GSPMD boundary resharding
        # and was reverted — iter5).
        xe = xr.reshape(C, E, D)
        xe = constrain(xe, "batch", "model", None)
        ye = _expert_ffn_ep(params, xe, cfg)
        ye = constrain(ye, "batch", "model", None)
        yr = constrain(ye.reshape(C * E, D), "batch", None)[: T * k]
        yr = constrain(yr, "batch", None)
        # combine stays in the model dtype so backward cotangents of the
        # (T*k, D) dispatch tensors stay bf16 (f32 cotangents doubled every
        # EP wire — §Perf kimi iteration 3); k<=8 partial sums in bf16 cost
        # <0.1% relative error, far under the ExpMul quantization itself.
        y = jnp.einsum(
            "tkd,tk->td",
            yr.reshape(T, k, D),
            top_w.astype(x2.dtype),
        )
    elif impl == "scatter":
        E = m.num_experts
        k = m.top_k
        C = max(1, int(T * k * m.capacity_factor / E))
        buf = jnp.zeros((E, C, D), x2.dtype)
        flat_ids = top_ids.reshape(-1)                       # (T*k,)
        # position of each routed copy within its expert, in (t, j) order
        onehot = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)  # (T*k, E)
        pos = jnp.cumsum(onehot, axis=0) - onehot
        pos = jnp.sum(pos * onehot, axis=-1)                 # (T*k,)
        keep = pos < C
        tok = jnp.repeat(jnp.arange(T), k)
        buf = buf.at[flat_ids, jnp.where(keep, pos, C - 1)].add(
            jnp.where(keep[:, None], x2[tok], 0), mode="drop"
        )
        ye = _expert_ffn(params, buf, cfg.activation)        # (E, C, D)
        gathered = ye[flat_ids, jnp.where(keep, pos, 0)]     # (T*k, D)
        gathered = jnp.where(keep[:, None], gathered, 0)
        y = jnp.einsum(
            "tkd,tk->td",
            gathered.reshape(T, k, D).astype(jnp.float32),
            top_w,
        )
    else:
        raise ValueError(impl)
    return _add_dense_paths(params, x2, y, cfg).reshape(B, S, D).astype(
        x.dtype)


def _add_dense_paths(params, x2, y, cfg):
    """y plus what every token computes alike: the shared experts and
    arctic's dense residual."""
    for name in ("shared", "dense"):
        if name in params:
            y = y + mlp_apply(params[name], x2, cfg.activation).astype(
                y.dtype)
    return y


def moe_ref(params, x, cfg):
    """Dense oracle: every held expert on every token, masked combine
    (small cfgs)."""
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    x2 = x.reshape(T, D)
    top_w, top_ids = route(params, x2, m)
    start, _ = m.held_range
    xe = jnp.broadcast_to(x2, (m.num_held, T, D))
    ye = _expert_ffn(params, xe, cfg.activation)             # (E, T, D)
    w_full = jnp.zeros((T, m.num_experts), jnp.float32).at[
        jnp.arange(T)[:, None], top_ids
    ].add(top_w)[:, start:start + m.num_held]
    y = jnp.einsum("etd,te->td", ye.astype(jnp.float32), w_full)
    return _add_dense_paths(params, x2, y, cfg).reshape(B, S, D).astype(
        x.dtype)
