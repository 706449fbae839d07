"""Transformer-family residual blocks: init / apply / decode dispatch over
block kinds (attn | rglru | mlstm | slstm), each as norm -> mix -> residual,
norm -> ffn -> residual (ffn optional: xLSTM blocks carry their own
projections; MoE replaces the dense ffn)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.layers.attention_layer import (
    attn_apply,
    attn_decode_step,
    attn_init,
    attn_init_cache,
    attn_init_paged_cache,
    attn_paged_decode_step,
    attn_paged_prefill_step,
    attn_prefill_step,
)
from repro.layers.common import make_norm
from repro.layers.mla import (
    mla_apply,
    mla_decode_step,
    mla_init,
    mla_init_cache,
    mla_init_paged_cache,
    mla_paged_decode_step,
    mla_paged_prefill_step,
    mla_prefill_step,
)
from repro.layers.mlp import mlp_apply, mlp_init
from repro.layers.moe import moe_apply, moe_init
from repro.layers.rglru import (
    rglru_apply,
    rglru_decode_step,
    rglru_init,
    rglru_init_cache,
)
from repro.layers.xlstm import (
    mlstm_apply,
    mlstm_decode_step,
    mlstm_init,
    mlstm_init_cache,
    slstm_apply,
    slstm_decode_step,
    slstm_init,
    slstm_init_cache,
)


def _has_ffn(cfg, kind):
    return kind in ("attn", "rglru") and (cfg.d_ff > 0 or cfg.moe is not None)


def block_init(key, cfg, kind, dtype):
    norm_init, _ = make_norm(cfg.norm, cfg.norm_eps)
    ks = jax.random.split(key, 4)
    p = {"norm_mix": norm_init(cfg.d_model, dtype)}
    if kind == "attn":
        p["mix"] = mla_init(ks[0], cfg, dtype) if cfg.mla else attn_init(ks[0], cfg, dtype)
    elif kind == "rglru":
        p["mix"] = rglru_init(ks[0], cfg, dtype)
    elif kind == "mlstm":
        p["mix"] = mlstm_init(ks[0], cfg, dtype)
    elif kind == "slstm":
        p["mix"] = slstm_init(ks[0], cfg, dtype)
    else:
        raise ValueError(kind)
    if _has_ffn(cfg, kind):
        p["norm_ffn"] = norm_init(cfg.d_model, dtype)
        if cfg.moe is not None:
            p["ffn"] = moe_init(ks[1], cfg, dtype)
        else:
            p["ffn"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.activation, dtype)
    return p


def block_apply(params, x, cfg, kind, *, positions=None, causal=True,
                moe_impl="scatter"):
    _, norm = make_norm(cfg.norm, cfg.norm_eps)
    h = norm(params["norm_mix"], x)
    window = cfg.window if kind == "attn" and cfg.window else None
    if kind == "attn":
        fn = mla_apply if cfg.mla else attn_apply
        h = fn(params["mix"], h, cfg, positions=positions, causal=causal,
               window=window)
    elif kind == "rglru":
        h = rglru_apply(params["mix"], h, cfg)
    elif kind == "mlstm":
        h = mlstm_apply(params["mix"], h, cfg)
    elif kind == "slstm":
        h = slstm_apply(params["mix"], h, cfg)
    x = x + h
    if "ffn" in params:
        x = x + _ffn(params["ffn"], norm(params["norm_ffn"], x), cfg,
                     moe_impl)
    return x


def _ffn(params, h, cfg, moe_impl):
    """The block's FFN: experts where it holds a router (MoE layers), the
    dense MLP otherwise (dense models, and the leading dense layers of an
    MoE stack). Serving paths pass ``moe_impl="dropless"``."""
    if "router" in params:
        return moe_apply(params, h, cfg, impl=moe_impl)
    return mlp_apply(params, h, cfg.activation)


def block_init_cache(cfg, kind, batch, max_len, dtype):
    if kind == "attn":
        if cfg.mla:
            return mla_init_cache(cfg, batch, max_len, dtype)
        # local-attention layers only need a window-sized cache
        span = min(max_len, cfg.window) if cfg.window else max_len
        return attn_init_cache(cfg, batch, span if cfg.window else max_len, dtype)
    if kind == "rglru":
        return rglru_init_cache(cfg, batch, dtype)
    if kind == "mlstm":
        return mlstm_init_cache(cfg, batch, dtype)
    if kind == "slstm":
        return slstm_init_cache(cfg, batch, dtype)
    raise ValueError(kind)


def block_init_paged_cache(cfg, kind, pool_tokens, slots, dtype):
    """Paged cache for one block kind (DESIGN.md §7).

    Attention kinds share the flat physical token pool (no batch axis —
    sequences address it through block tables); recurrent kinds keep their
    O(1) per-slot state and bypass paging entirely — and likewise bypass
    KV quantization (``cfg.kv_dtype``): only attention-kind pools carry
    code + scale buffers (DESIGN.md §8).
    """
    if kind == "attn":
        if cfg.mla:
            return mla_init_paged_cache(cfg, pool_tokens, dtype)
        return attn_init_paged_cache(cfg, pool_tokens, dtype)
    return block_init_cache(cfg, kind, slots, 0, dtype)


def block_paged_prefill(params, cache, x, cfg, kind, lengths, n_valid, rows,
                        chunk_rows, block_tables=None, page_size=0):
    """Chunked prefill through one residual block, paged KV variant.

    rows: (B, L) physical rows of the resident history; chunk_rows: (B, C)
    physical rows for this chunk — both derived from the slot's block table
    (identical for every layer). ``block_tables``/``page_size`` ride along
    for fused backends that index the pool in-kernel (DESIGN.md §9).
    Recurrent kinds ignore them and run the same gated single-token scan as
    the contiguous path.
    """
    _, norm = make_norm(cfg.norm, cfg.norm_eps)
    if kind != "attn":
        return block_prefill(params, cache, x, cfg, kind, lengths, n_valid)
    h = norm(params["norm_mix"], x)
    if cfg.mla:
        cache, h = mla_paged_prefill_step(params["mix"], cache, h, cfg,
                                          lengths, n_valid, rows, chunk_rows,
                                          block_tables, page_size)
    else:
        window = cfg.window if cfg.window else None
        cache, h = attn_paged_prefill_step(params["mix"], cache, h, cfg,
                                           lengths, n_valid, rows, chunk_rows,
                                           window=window,
                                           block_tables=block_tables,
                                           page_size=page_size)
    x = x + h
    if "ffn" in params:
        x = x + _ffn(params["ffn"], norm(params["norm_ffn"], x), cfg,
                     "dropless")
    return cache, x


def block_paged_decode_step(params, cache, x1, cfg, kind, lengths, rows,
                            write_row, block_tables=None, page_size=0):
    """Single-token decode through one residual block, paged KV variant."""
    if kind != "attn":
        return block_decode_step(params, cache, x1, cfg, kind, lengths)
    _, norm = make_norm(cfg.norm, cfg.norm_eps)
    h = norm(params["norm_mix"], x1)
    if cfg.mla:
        cache, h = mla_paged_decode_step(params["mix"], cache, h, cfg,
                                         lengths, rows, write_row,
                                         block_tables, page_size)
    else:
        window = cfg.window if cfg.window else None
        cache, h = attn_paged_decode_step(params["mix"], cache, h, cfg,
                                          lengths, rows, write_row,
                                          window=window,
                                          block_tables=block_tables,
                                          page_size=page_size)
    x1 = x1 + h
    if "ffn" in params:
        h = norm(params["norm_ffn"], x1)
        x1 = x1 + _ffn(params["ffn"], h[:, None, :], cfg, "dropless")[:, 0]
    return cache, x1


def block_prefill(params, cache, x, cfg, kind, lengths, n_valid):
    """Chunked prefill through one residual block.

    x: (B, C, D) chunk; lengths: (B,) tokens already resident in the cache;
    n_valid: (B,) valid chunk tokens per row (0 = idle slot: state must not
    change and output rows are ignored). Attention kinds fill all C cache
    positions in one pass; recurrent kinds (no cache indexing, strictly
    sequential state) scan the single-token step with per-token validity
    gating — exact, just not parallel over the chunk.
    """
    _, norm = make_norm(cfg.norm, cfg.norm_eps)
    if kind == "attn":
        h = norm(params["norm_mix"], x)
        if cfg.mla:
            cache, h = mla_prefill_step(params["mix"], cache, h, cfg,
                                        lengths, n_valid)
        else:
            window = cfg.window if cfg.window else None
            cache, h = attn_prefill_step(params["mix"], cache, h, cfg,
                                         lengths, n_valid, window=window)
        x = x + h
        if "ffn" in params:
            x = x + _ffn(params["ffn"], norm(params["norm_ffn"], x), cfg,
                         "dropless")
        return cache, x

    def tok_body(carry, i):
        cache = carry
        x1 = jax.lax.dynamic_index_in_dim(x, i, 1, keepdims=False)
        c_new, y1 = block_decode_step(params, cache, x1, cfg, kind,
                                      lengths + i)
        valid = i < n_valid  # (B,)
        c_new = jax.tree.map(
            lambda n, o: jnp.where(
                valid.reshape((-1,) + (1,) * (n.ndim - 1)), n, o),
            c_new, cache,
        )
        return c_new, y1

    cache, ys = jax.lax.scan(tok_body, cache, jnp.arange(x.shape[1]))
    return cache, jnp.moveaxis(ys, 0, 1)


def block_decode_step(params, cache, x1, cfg, kind, lengths):
    _, norm = make_norm(cfg.norm, cfg.norm_eps)
    h = norm(params["norm_mix"], x1)
    if kind == "attn":
        if cfg.mla:
            cache, h = mla_decode_step(params["mix"], cache, h, cfg, lengths)
        elif cfg.window:
            # rolling buffer: slot wraps modulo the window span
            span = cache["k"].shape[2]
            cache, h = attn_decode_step(
                params["mix"], cache, h, cfg, lengths,
                write_pos=lengths % span,
                attn_len=jnp.minimum(lengths + 1, span),
            )
        else:
            cache, h = attn_decode_step(params["mix"], cache, h, cfg, lengths)
    elif kind == "rglru":
        cache, h = rglru_decode_step(params["mix"], cache, h, cfg)
    elif kind == "mlstm":
        cache, h = mlstm_decode_step(params["mix"], cache, h, cfg)
    elif kind == "slstm":
        cache, h = slstm_decode_step(params["mix"], cache, h, cfg)
    x1 = x1 + h
    if "ffn" in params:
        h = norm(params["norm_ffn"], x1)
        x1 = x1 + _ffn(params["ffn"], h[:, None, :], cfg, "dropless")[:, 0]
    return cache, x1
