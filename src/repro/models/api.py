"""Model assembly: init / forward / loss / decode for every assigned family.

Layers are grouped into repeating *units* (cfg.block_pattern) and stacked on
a leading axis so the whole depth runs under one ``lax.scan`` — this keeps
the HLO (and 512-device SPMD compile time) independent of depth, and remat
applies per-unit. Heterogeneous patterns (recurrentgemma's r,r,a;
xLSTM's m...s) scan over multi-block units in true layer order.

``cfg.first_dense_layers`` leading attention blocks with a dense FFN of
width ``cfg.d_ff`` (DeepSeek's first_k_dense_replace) sit before the unit
stack, stacked on a leading axis of their own (``params["lead"]``), and
run first. Their caches are the first entries of the same stacked
cache as the units', so one state covers all ``num_layers`` layers and
pool init, copy-on-write and poisoning need no case of their own.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.layers.attention_layer import (
    cross_attn_apply,
    cross_attn_decode,
    cross_attn_init,
    cross_attn_kv,
)
from repro.layers.common import dense_init, make_norm
from repro.layers.embedding import embed_apply, embed_init, logits_apply
from repro.layers.mlp import mlp_apply, mlp_init
from repro.kernels.paged import slot_rows, token_rows
from repro.models.blocks import (
    block_apply,
    block_decode_step,
    block_init,
    block_init_cache,
    block_init_paged_cache,
    block_paged_decode_step,
    block_paged_prefill,
    block_prefill,
)


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def _unit(cfg: ModelConfig):
    return cfg.block_pattern


def _n_units(cfg: ModelConfig, total=None):
    total = cfg.num_layers - cfg.first_dense_layers if total is None else total
    assert total % len(_unit(cfg)) == 0, (total, _unit(cfg))
    return total // len(_unit(cfg))


def _n_cache_units(cfg: ModelConfig):
    """Entries of each stacked cache: the leading dense layers', then the
    units'."""
    return cfg.first_dense_layers + _n_units(cfg)


def _lead_cfg(cfg: ModelConfig):
    if cfg.first_dense_layers and _unit(cfg) != ("attn",):
        raise NotImplementedError("leading dense layers before a "
                                  "non-attention block pattern")
    return cfg.replace(moe=None)


def _stack_init(key, n, fn):
    """vmap an init fn over n keys -> leading layer axis."""
    return jax.vmap(fn)(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_model(key, cfg: ModelConfig):
    pd = _pdtype(cfg)
    norm_init, _ = make_norm(cfg.norm, cfg.norm_eps)
    keys = jax.random.split(key, 8)
    params = {
        "embed": embed_init(keys[0], cfg, pd),
        "final_norm": norm_init(cfg.d_model, pd),
    }
    if cfg.frontend:
        params["frontend_proj"] = dense_init(
            keys[1], (cfg.frontend_dim, cfg.d_model), pd
        )
    if cfg.encoder_layers:  # encoder-decoder
        params["enc_units"] = tuple(
            _stack_init(
                jax.random.fold_in(keys[2], i),
                _n_units(cfg, cfg.encoder_layers),
                lambda k, kind=kind: block_init(k, cfg, kind, pd),
            )
            for i, kind in enumerate(_unit(cfg))
        )
        params["enc_final_norm"] = norm_init(cfg.d_model, pd)

        def dec_block_init(k):
            k1, k2, k3 = jax.random.split(k, 3)
            p = block_init(k1, cfg, "attn", pd)
            p["norm_cross"] = norm_init(cfg.d_model, pd)
            p["cross"] = cross_attn_init(k2, cfg, pd)
            return p

        params["dec_units"] = (
            _stack_init(keys[3], cfg.decoder_layers, dec_block_init),
        )
    else:
        params["units"] = tuple(
            _stack_init(
                jax.random.fold_in(keys[2], i),
                _n_units(cfg),
                lambda k, kind=kind: block_init(k, cfg, kind, pd),
            )
            for i, kind in enumerate(_unit(cfg))
        )
        if cfg.first_dense_layers:
            params["lead"] = (_stack_init(
                keys[4], cfg.first_dense_layers,
                lambda k: block_init(k, _lead_cfg(cfg), "attn", pd)),)
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _run_stack(units_params, x, cfg, unit_kinds, *, positions, causal,
               moe_impl):
    from repro.sharding.constraints import constrain, model_axis_size

    # Block-boundary activation sharding. When attention heads cannot use
    # the 'model' axis (H % msize != 0) the stack runs fully sequence-
    # parallel: every per-token op (norms, projections, FFN) works on S
    # shards and only attention's K/V broadcast crosses ranks — this
    # replaced 112GB/layer of activation gathers on llava (§Perf).
    msize = model_axis_size()
    S = x.shape[1]
    seq_par = (
        msize > 0
        and cfg.num_heads % msize != 0
        and S % msize == 0
        and cfg.moe is None
    )
    bdry = ("batch", "model" if seq_par else None, None)

    def unit_body(x, xs):
        x = constrain(x, *bdry)
        for pos, kind in enumerate(unit_kinds):
            x = block_apply(xs[pos], x, cfg, kind, positions=positions,
                            causal=causal, moe_impl=moe_impl)
        x = constrain(x, *bdry)
        return x, None

    body = jax.checkpoint(unit_body) if cfg.remat else unit_body
    x, _ = jax.lax.scan(body, x, units_params)
    return x


def _dec_block_apply(p, x, cfg, *, positions, enc_out, moe_impl):
    """Decoder block: self-attn -> cross-attn -> ffn (each pre-normed)."""
    from repro.layers.attention_layer import attn_apply
    from repro.layers.mla import mla_apply
    from repro.layers.moe import moe_apply

    _, norm = make_norm(cfg.norm, cfg.norm_eps)
    fn = mla_apply if cfg.mla else attn_apply
    x = x + fn(p["mix"], norm(p["norm_mix"], x), cfg,
               positions=positions, causal=True)
    x = x + cross_attn_apply(p["cross"], norm(p["norm_cross"], x), enc_out, cfg)
    h = norm(p["norm_ffn"], x)
    if cfg.moe is not None:
        h = moe_apply(p["ffn"], h, cfg, impl=moe_impl)
    else:
        h = mlp_apply(p["ffn"], h, cfg.activation)
    return x + h


def _run_decoder_stack(units_params, x, cfg, *, positions, enc_out, moe_impl):
    def unit_body(x, p_l):
        return _dec_block_apply(p_l, x, cfg, positions=positions,
                                enc_out=enc_out, moe_impl=moe_impl), None

    body = jax.checkpoint(unit_body) if cfg.remat else unit_body
    x, _ = jax.lax.scan(body, x, units_params[0])
    return x


def forward(params, batch, cfg: ModelConfig, *, moe_impl="scatter"):
    """batch: tokens (B, S_text) [+ frontend_embeds (B,T,F)] [+ enc_*].

    Returns logits (B, S_total, V).
    """
    _, norm = make_norm(cfg.norm, cfg.norm_eps)
    tokens = batch["tokens"]
    x = embed_apply(params["embed"], tokens, cfg).astype(_dtype(cfg))
    if cfg.frontend and "frontend_embeds" in batch and not cfg.encoder_layers:
        fe = batch["frontend_embeds"].astype(_dtype(cfg)) @ params["frontend_proj"]
        x = jnp.concatenate([fe, x], axis=1)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    if cfg.encoder_layers:
        enc_in = batch["frontend_embeds"].astype(_dtype(cfg)) @ params["frontend_proj"]
        Be, Se, _ = enc_in.shape
        enc_pos = jnp.broadcast_to(jnp.arange(Se), (Be, Se))
        enc_out = _run_stack(
            params["enc_units"], enc_in, cfg, _unit(cfg),
            positions=enc_pos, causal=False, moe_impl=moe_impl,
        )
        enc_out = norm(params["enc_final_norm"], enc_out)
        x = _run_decoder_stack(
            params["dec_units"], x, cfg,
            positions=positions, enc_out=enc_out, moe_impl=moe_impl,
        )
    else:
        if "lead" in params:
            x = _run_stack(params["lead"], x, cfg, ("attn",),
                           positions=positions, causal=True,
                           moe_impl=moe_impl)
        x = _run_stack(
            params["units"], x, cfg, _unit(cfg),
            positions=positions, causal=True, moe_impl=moe_impl,
        )
    x = norm(params["final_norm"], x)
    return logits_apply(params["embed"], x, cfg)


def loss_fn(params, batch, cfg: ModelConfig, *, moe_impl="scatter"):
    """Next-token cross entropy over the text positions."""
    logits = forward(params, batch, cfg, moe_impl=moe_impl)
    tokens = batch["tokens"]
    n_front = logits.shape[1] - tokens.shape[1]
    logits = logits[:, n_front:]
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    mask = batch.get("loss_mask")
    mask = jnp.ones_like(targets, jnp.float32) if mask is None else mask[:, 1:]
    # fused-stable CE: only (B, S) f32 intermediates, never a f32 logit cube
    m = jnp.max(logits, axis=-1, keepdims=True)
    lse = m[..., 0].astype(jnp.float32) + jnp.log(
        jnp.sum(jnp.exp((logits - m).astype(jnp.float32)), axis=-1)
    )
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = lse - tgt.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------
def init_decode_state(cfg: ModelConfig, batch, max_len, *, enc_len=None):
    """Contiguous per-slot decode state. With a quantized ``cfg.kv_dtype``
    the attention-kind caches hold codes + parallel float32 scale buffers
    (DESIGN.md §8); recurrent kinds keep full-precision state (bypassed,
    as for paging) and encoder-decoder serving stays unquantized."""
    if cfg.encoder_layers and cfg.kv_dtype != "fp32":
        raise NotImplementedError(
            "quantized KV serving targets decoder-only configs; encoder "
            "cross-attention K/V are recomputed activations, not a cache")
    dt = _dtype(cfg)

    def stacked_cache(kind, n):
        one = block_init_cache(cfg, kind, batch, max_len, dt)
        return jax.tree.map(lambda l: jnp.zeros((n,) + l.shape, l.dtype) + l, one)

    if cfg.encoder_layers:
        hd = cfg.resolved_head_dim()
        n = cfg.decoder_layers
        state = {
            "caches": (stacked_cache("attn", n),),
            "cross_kv": (
                jnp.zeros((n, batch, cfg.num_kv_heads, enc_len, hd), dt),
                jnp.zeros((n, batch, cfg.num_kv_heads, enc_len, hd), dt),
            ),
            "enc_len": jnp.zeros((batch,), jnp.int32),
        }
        return state
    nu = _n_cache_units(cfg)
    return {
        "caches": tuple(stacked_cache(kind, nu) for kind in _unit(cfg)),
    }


def init_paged_state(cfg: ModelConfig, slots, pool_blocks, page_size):
    """Decode state with paged attention caches (DESIGN.md §7).

    Attention-kind caches become flat physical pools of
    ``pool_blocks * page_size`` token rows shared by all sequences (no slot
    axis — block tables map logical positions to rows); recurrent kinds keep
    their per-slot O(1) state exactly as in ``init_decode_state``. With a
    quantized ``cfg.kv_dtype`` each pool stores codes plus a parallel
    per-token scale pool addressed by the same block tables (DESIGN.md §8).
    """
    if cfg.encoder_layers:
        raise NotImplementedError("paged serving targets decoder-only "
                                  "configs; encoder-decoder serving uses "
                                  "the contiguous layout")
    dt = _dtype(cfg)
    pool_tokens = pool_blocks * page_size
    nu = _n_cache_units(cfg)

    def stacked_cache(kind):
        one = block_init_paged_cache(cfg, kind, pool_tokens, slots, dt)
        return jax.tree.map(lambda l: jnp.zeros((nu,) + l.shape, l.dtype) + l, one)

    return {
        "caches": tuple(stacked_cache(kind) for kind in _unit(cfg)),
    }


def copy_paged_block(state, cfg: ModelConfig, src, dst, *, page_size):
    """Copy one physical KV page ``src`` -> ``dst`` in every attention-kind
    pool (copy-on-write for the shared-prefix cache, DESIGN.md §11).

    All attention pools — K/V, quantized codes + scale pools, MLA latents —
    share one block-table address space, so a single (src, dst) pair moves
    the page consistently across every leaf with a ``pool_tokens`` leading
    row axis (axis 1 under the stacked unit axis). Recurrent-kind caches
    are per-slot state, not paged, and are untouched.
    """
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)

    def copy_leaf(buf):
        page = jax.lax.dynamic_slice_in_dim(buf, src * page_size, page_size,
                                            axis=1)
        return jax.lax.dynamic_update_slice_in_dim(buf, page,
                                                   dst * page_size, axis=1)

    caches = list(state["caches"])
    for pos, kind in enumerate(_unit(cfg)):
        if kind != "attn":
            continue
        caches[pos] = jax.tree.map(copy_leaf, caches[pos])
    new_state = dict(state)
    new_state["caches"] = tuple(caches)
    return new_state


def poison_paged_block(state, cfg: ModelConfig, block, *, page_size,
                       value=None):
    """Overwrite one physical KV page with non-finite garbage — the
    ``kv_corrupt`` chaos injector's device half (DESIGN.md §13).

    Float leaves (fp32 K/V pools, quantized scale pools, MLA latents) get
    NaN; integer code pools get their most-negative code (the NaN scales
    alone already make every dequantized row non-finite). Attention over a
    poisoned page produces non-finite logits for exactly the sequences
    whose block tables reference it, which is what the engine's NaN
    quarantine sentinel detects and isolates. Recurrent-kind caches are
    per-slot state, not paged, and are untouched.

    ``value`` overrides the fill for every leaf kind — ``value=0`` is the
    quarantine *scrub*: a poisoned page going back to the free list must
    be zeroed first, because a future owner that has only part-written
    the page still attends over all of it, and a masked NaN row survives
    the softmax (weight 0 times NaN is NaN in p@V).
    """
    block = jnp.asarray(block, jnp.int32)

    def poison_leaf(buf):
        shape = (buf.shape[0], page_size) + buf.shape[2:]
        if value is not None:
            bad = jnp.full(shape, value, buf.dtype)
        elif jnp.issubdtype(buf.dtype, jnp.floating):
            bad = jnp.full(shape, jnp.nan, buf.dtype)
        else:
            bad = jnp.full(shape, jnp.iinfo(buf.dtype).min, buf.dtype)
        return jax.lax.dynamic_update_slice_in_dim(
            buf, bad, block * page_size, axis=1)

    caches = list(state["caches"])
    for pos, kind in enumerate(_unit(cfg)):
        if kind != "attn":
            continue
        caches[pos] = jax.tree.map(poison_leaf, caches[pos])
    new_state = dict(state)
    new_state["caches"] = tuple(caches)
    return new_state


def encode_for_decode(params, state, frontend_embeds, enc_lengths, cfg):
    """Run the encoder once and stash per-layer cross K/V (enc-dec serving)."""
    _, norm = make_norm(cfg.norm, cfg.norm_eps)
    enc_in = frontend_embeds.astype(_dtype(cfg)) @ params["frontend_proj"]
    B, Se, _ = enc_in.shape
    enc_pos = jnp.broadcast_to(jnp.arange(Se), (B, Se))
    enc_out = _run_stack(params["enc_units"], enc_in, cfg, _unit(cfg),
                         positions=enc_pos, causal=False, moe_impl="scatter")
    enc_out = norm(params["enc_final_norm"], enc_out)

    def per_layer_kv(p_l):
        return cross_attn_kv(p_l["cross"], enc_out)

    ks, vs = jax.vmap(per_layer_kv)(params["dec_units"][0])
    state = dict(state)
    state["cross_kv"] = (ks, vs)
    state["enc_len"] = enc_lengths
    return state


def _scan_unit_caches(params, caches, x, cfg, step_fn):
    """Run the layer stack with the KV caches riding the scan carry: the
    leading dense layers (``params["lead"]``), then the units.

    Caches are updated with dynamic-update-slice at the layer's index: with
    donated state buffers this is a true in-place update (the previous
    xs->ys restacking materialized the whole stacked cache twice per token —
    §Perf gemma decode). The leading layers' caches are written back after
    the scan: written before it, the stacked caches would take a third copy
    on the device between the two. ``step_fn(p_block, cache_block, x, kind)
    -> (new_cache, x)`` supplies the per-block computation; prefill, decode,
    and their paged variants all share this scan.
    """
    def read(bufs, idx):
        return jax.tree.map(
            lambda buf: jax.lax.dynamic_index_in_dim(buf, idx, 0,
                                                     keepdims=False), bufs)

    def write(bufs, idx, new):
        return jax.tree.map(
            lambda buf, n: jax.lax.dynamic_update_index_in_dim(
                buf, n.astype(buf.dtype), idx, 0), bufs, new)

    lead = []
    for i in range(cfg.first_dense_layers):
        p_l = jax.tree.map(lambda a: a[i], params["lead"][0])
        c_new, x = step_fn(p_l, read(caches[0], i), x, "attn")
        lead.append(c_new)

    def unit_body(carry, xs):
        x, caches = carry
        p_l, idx = xs
        new_caches = []
        for pos, kind in enumerate(_unit(cfg)):
            c_new, x = step_fn(p_l[pos], read(caches[pos], idx), x, kind)
            new_caches.append(write(caches[pos], idx, c_new))
        return (x, tuple(new_caches)), None

    (x, new_caches), _ = jax.lax.scan(
        unit_body, (x, caches),
        (params["units"], cfg.first_dense_layers + jnp.arange(_n_units(cfg))),
    )
    first = new_caches[0]
    for i, c_new in enumerate(lead):
        first = write(first, i, c_new)
    return x, (first,) + tuple(new_caches[1:])


def prefill(params, state, tokens, lengths, n_valid, cfg: ModelConfig):
    """Chunked prefill: run the flash path over a whole prompt chunk.

    tokens: (B, C) teacher-forced chunk; lengths: (B,) tokens already in the
    KV caches; n_valid: (B,) valid tokens per row (0 = idle slot, a no-op).
    Every layer writes all valid chunk positions of its cache in one pass
    and the logits of the last *valid* token per row are returned — so a
    prompt of length L costs ceil(L / C) steps instead of L decode ticks,
    and the final step's logits directly seed sampling (DESIGN.md §6).

    Returns (logits (B, V), new_state).
    """
    if cfg.encoder_layers:
        raise NotImplementedError("chunked prefill targets decoder-only "
                                  "configs; encoder-decoder serving uses "
                                  "encode_for_decode + decode_step")
    _, norm = make_norm(cfg.norm, cfg.norm_eps)
    B, C = tokens.shape
    x = embed_apply(params["embed"], tokens, cfg).astype(_dtype(cfg))

    x, new_caches = _scan_unit_caches(
        params, state["caches"], x, cfg,
        lambda p, c, x, kind: block_prefill(p, c, x, cfg, kind, lengths,
                                            n_valid),
    )
    x = norm(params["final_norm"], x)
    last = jnp.clip(n_valid - 1, 0, C - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    logits = logits_apply(params["embed"], x_last, cfg)
    return logits, {"caches": new_caches}


def prefill_paged(params, state, tokens, lengths, n_valid, block_tables,
                  cfg: ModelConfig, *, page_size):
    """Chunked prefill against paged caches (DESIGN.md §7).

    Same contract as ``prefill`` plus ``block_tables (B, max_blocks)``:
    per-sequence physical block ids (sentinel = pool_blocks for unallocated
    entries). All layers share one block table per sequence — every layer
    stores the same logical positions — so the physical row indices are
    computed once here and broadcast through the unit scan.
    """
    if cfg.encoder_layers:
        raise NotImplementedError("paged prefill targets decoder-only "
                                  "configs")
    _, norm = make_norm(cfg.norm, cfg.norm_eps)
    B, C = tokens.shape
    x = embed_apply(params["embed"], tokens, cfg).astype(_dtype(cfg))
    rows = slot_rows(block_tables, page_size)
    positions = lengths[:, None] + jnp.arange(C)[None, :]
    chunk_rows = token_rows(block_tables, positions, page_size)

    x, new_caches = _scan_unit_caches(
        params, state["caches"], x, cfg,
        lambda p, c, x, kind: block_paged_prefill(p, c, x, cfg, kind,
                                                  lengths, n_valid, rows,
                                                  chunk_rows, block_tables,
                                                  page_size),
    )
    x = norm(params["final_norm"], x)
    last = jnp.clip(n_valid - 1, 0, C - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    logits = logits_apply(params["embed"], x_last, cfg)
    return logits, {"caches": new_caches}


def decode_step_paged(params, state, tokens1, lengths, block_tables,
                      cfg: ModelConfig, *, page_size):
    """One serving step against paged caches: tokens1 (B,) -> logits, state.

    Mirrors ``decode_step``'s carry-and-update scan; the only difference is
    that attention-kind blocks scatter/gather through the block table.
    """
    if cfg.encoder_layers:
        raise NotImplementedError("paged decode targets decoder-only configs")
    _, norm = make_norm(cfg.norm, cfg.norm_eps)
    x = embed_apply(params["embed"], tokens1[:, None], cfg)[:, 0].astype(_dtype(cfg))
    rows = slot_rows(block_tables, page_size)
    write_row = token_rows(block_tables, lengths, page_size)

    x, new_caches = _scan_unit_caches(
        params, state["caches"], x, cfg,
        lambda p, c, x, kind: block_paged_decode_step(p, c, x, cfg, kind,
                                                      lengths, rows,
                                                      write_row, block_tables,
                                                      page_size),
    )
    x = norm(params["final_norm"], x)
    logits = logits_apply(params["embed"], x, cfg)
    return logits, {"caches": new_caches}


def decode_step(params, state, tokens1, lengths, cfg: ModelConfig):
    """One serving step: tokens1 (B,) -> logits (B, V), updated state."""
    _, norm = make_norm(cfg.norm, cfg.norm_eps)
    x = embed_apply(params["embed"], tokens1[:, None], cfg)[:, 0].astype(_dtype(cfg))

    if cfg.encoder_layers:
        from repro.layers.attention_layer import attn_decode_step

        def unit_body(x, xs):
            p_l, c_l, kv_l = xs
            h = norm(p_l["norm_mix"], x)
            c_new, h = attn_decode_step(p_l["mix"], c_l, h, cfg, lengths)
            x = x + h
            h = norm(p_l["norm_cross"], x)
            x = x + cross_attn_decode(p_l["cross"], h, kv_l, state["enc_len"], cfg)
            h = norm(p_l["norm_ffn"], x)
            x = x + mlp_apply(p_l["ffn"], h, cfg.activation)
            return x, c_new

        x, c_new = jax.lax.scan(
            unit_body, x,
            (params["dec_units"][0], state["caches"][0], state["cross_kv"]),
        )
        new_state = dict(state)
        new_state["caches"] = (c_new,)
    else:
        x, new_caches = _scan_unit_caches(
            params, state["caches"], x, cfg,
            lambda p, c, x, kind: block_decode_step(p, c, x, cfg, kind,
                                                    lengths),
        )
        new_state = {"caches": new_caches}

    x = norm(params["final_norm"], x)
    logits = logits_apply(params["embed"], x, cfg)
    return logits, new_state
