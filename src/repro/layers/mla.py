"""Multi-head Latent Attention (MiniCPM3 / DeepSeek-V2/V3 family).

Queries and keys/values are produced through low-rank latents; the decode
cache stores only the KV latent + shared RoPE key (kv_lora_rank +
qk_rope_dim per token instead of 2*H*hd). The attention core itself still
routes through the backend registry so the ExpMul technique applies
unchanged (DESIGN.md §4). Queries go through a latent of their own
(``q_lora_rank``) or, with ``q_lora_rank=None``, one plain projection.

The unquantized **paged** path attends in absorbed form (DESIGN.md §14):
the pool holds one row ``kv_lat ‖ k_rope`` a token, a single KV head that
all query heads share; each head's query is absorbed into the latent,
``q_nope · W_UK ‖ q_rope``, so scores are read against the latent rows
directly, and the values are the latent itself, the first kv_lora_rank
columns of the same rows, up-projected by W_UV after attention. No
per-head K/V of the history is ever built; the fused paged kernels read
each page once for both products. The contiguous paths, ``forward`` and
the quantized latent pool expand the latents instead.

With a quantized ``cfg.kv_dtype`` it is the **latent pool** that is
quantized (DESIGN.md §8): codes + one float32 scale per token for each of
``kv_lat`` and ``k_rope``, dequantized fused right before the up-projection
``_expand_latents``. The expanded K/V the attention core sees are therefore
always full precision — MLA specs pin ``kv_dtype="fp32"`` at dispatch so
the registry's fake-quant axis never double-quantizes them — and latent
compression composes with quantization exactly as it composes with paging.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import repro.core.attention  # noqa: F401 — registers the built-in backends
from repro.kernels.kvquant import gather_dequant_rows, quant_scatter_rows
from repro.kernels.paged import scatter_rows
from repro.kernels.registry import (
    AttentionSpec,
    dispatch_attention,
    dispatch_decode,
    dispatch_paged_decode,
    dispatch_paged_prefill,
    dispatch_prefill,
)
from repro.layers.attention_layer import chunk_write, kv_quantized
from repro.layers.common import dense_init, rmsnorm, rmsnorm_init
from repro.layers.rotary import apply_rope
from repro.numerics.quant import (
    dequantize_kv,
    fake_quant_kv,
    kv_code_dtype,
    quantize_kv,
)


def mla_init(key, cfg, dtype):
    m = cfg.mla
    d = cfg.d_model
    H = cfg.num_heads
    qk_head = m.qk_nope_dim + m.qk_rope_dim
    ks = jax.random.split(key, 6)
    if m.q_lora_rank is None:
        q = {"w_q": dense_init(ks[0], (d, H, qk_head), dtype)}
    else:
        q = {"w_dq": dense_init(ks[0], (d, m.q_lora_rank), dtype),
             "q_norm": rmsnorm_init(m.q_lora_rank, dtype),
             "w_uq": dense_init(ks[1], (m.q_lora_rank, H, qk_head), dtype)}
    return {
        **q,
        "w_dkv": dense_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_dim), dtype),
        "kv_norm": rmsnorm_init(m.kv_lora_rank, dtype),
        "w_ukv": dense_init(ks[3], (m.kv_lora_rank, H, m.qk_nope_dim + m.v_head_dim), dtype),
        "wo": dense_init(ks[4], (H, m.v_head_dim, d), dtype),
    }


def _mla_q(params, x, cfg, positions):
    """(B, S, d) -> q_nope (B, H, S, nope), roped q_rope (B, H, S, rope)."""
    m = cfg.mla
    if "w_q" in params:
        q = jnp.einsum("bsd,dhk->bhsk", x, params["w_q"])
    else:  # queries through the q-latent
        q_lat = rmsnorm(params["q_norm"], x @ params["w_dq"])
        q = jnp.einsum("bsr,rhk->bhsk", q_lat, params["w_uq"])
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim:]
    return q_nope, apply_rope(q_rope, positions[:, None, :], cfg.rope_base)


def _mla_qkv(params, x, cfg, positions):
    m = cfg.mla
    q_nope, q_rope = _mla_q(params, x, cfg, positions)
    # kv latent + shared rope key
    dkv = x @ params["w_dkv"]
    kv_lat = rmsnorm(params["kv_norm"], dkv[..., : m.kv_lora_rank])
    k_rope = apply_rope(dkv[..., None, :, m.kv_lora_rank:], positions[:, None, :], cfg.rope_base)
    ukv = jnp.einsum("bsr,rhk->bhsk", kv_lat, params["w_ukv"])
    k_nope, v = ukv[..., : m.qk_nope_dim], ukv[..., m.qk_nope_dim:]
    k_rope = jnp.broadcast_to(k_rope, (*k_nope.shape[:-1], m.qk_rope_dim))
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate([k_nope, k_rope], axis=-1)
    return q_full, k_full, v, kv_lat, dkv[..., m.kv_lora_rank:]


def mla_apply(params, x, cfg, *, positions=None, causal=True, window=None):
    B, S, _ = x.shape
    m = cfg.mla
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q, k, v, kv_lat, k_rope_raw = _mla_qkv(params, x, cfg, positions)
    if kv_quantized(cfg):
        # fake-quant the *latents* (the quantity a quantized cache stores)
        # and re-expand, so forward() numerics match a latent-cache
        # round-trip exactly — the MLA twin of the registry's ``*_q`` path
        k_rope = apply_rope(k_rope_raw[:, None, :, :], positions[:, None, :],
                            cfg.rope_base)[:, 0]
        k, v = _expand_latents(
            params, fake_quant_kv(kv_lat, cfg.kv_dtype),
            fake_quant_kv(k_rope, cfg.kv_dtype), cfg)
    scale = 1.0 / np.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    o = dispatch_attention(
        AttentionSpec.from_config(cfg, window=window, kv_dtype="fp32"),
        q, k, v, causal=causal, scale=scale,
    )
    return jnp.einsum("bhsk,hkd->bsd", o, params["wo"])


def mla_init_cache(cfg, batch, max_len, dtype):
    m = cfg.mla
    # latent cache: rank + rope dims per token (the MLA memory win)
    if kv_quantized(cfg):
        cd = kv_code_dtype(cfg.kv_dtype)
        return {
            "kv_lat": jnp.zeros((batch, max_len, m.kv_lora_rank), cd),
            "k_rope": jnp.zeros((batch, max_len, m.qk_rope_dim), cd),
            "kv_lat_scale": jnp.zeros((batch, max_len), jnp.float32),
            "k_rope_scale": jnp.zeros((batch, max_len), jnp.float32),
        }
    return {
        "kv_lat": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, m.qk_rope_dim), dtype),
    }


def mla_decode_step(params, cache, x1, cfg, lengths, *, window=None):
    m = cfg.mla
    B = x1.shape[0]
    x = x1[:, None, :]
    pos = lengths[:, None]
    q, _, _, kv_lat, k_rope_raw = _mla_qkv(params, x, cfg, pos)
    q1 = q[:, :, 0]                                   # (B, H, qk_head)

    def upd(buf, new, p):
        return jax.vmap(
            lambda b, n, pp: jax.lax.dynamic_update_slice(b, n, (pp, 0))
        )(buf, new, p)

    def upd_scale(buf, new, p):  # (B, S) per-token scale buffer
        return jax.vmap(
            lambda b, n, pp: jax.lax.dynamic_update_slice(b, n, (pp,))
        )(buf, new, p)

    k_rope_new = apply_rope(
        k_rope_raw[:, None, :, :], pos[:, None], cfg.rope_base)[:, 0]
    if kv_quantized(cfg):
        # quantize-on-write at the latent level; dequant fused on read just
        # before the up-projection (DESIGN.md §8)
        latq = quantize_kv(kv_lat, cfg.kv_dtype)
        ropeq = quantize_kv(k_rope_new, cfg.kv_dtype)
        new_cache = {
            "kv_lat": upd(cache["kv_lat"], latq.codes, lengths),
            "k_rope": upd(cache["k_rope"], ropeq.codes, lengths),
            "kv_lat_scale": upd_scale(cache["kv_lat_scale"], latq.scale,
                                      lengths),
            "k_rope_scale": upd_scale(cache["k_rope_scale"], ropeq.scale,
                                      lengths),
        }
        kv_lat_c = dequantize_kv(new_cache["kv_lat"],
                                 new_cache["kv_lat_scale"], cfg.kv_dtype)
        k_rope_c = dequantize_kv(new_cache["k_rope"],
                                 new_cache["k_rope_scale"], cfg.kv_dtype)
    else:
        new_cache = {"kv_lat": upd(cache["kv_lat"], kv_lat, lengths),
                     "k_rope": upd(cache["k_rope"], k_rope_new, lengths)}
        kv_lat_c, k_rope_c = new_cache["kv_lat"], new_cache["k_rope"]
    # expand latents for attention (naive MLA decode; absorbed-matmul form is
    # a recorded beyond-paper optimization — EXPERIMENTS.md §Perf)
    k, v = _expand_latents(params, kv_lat_c, k_rope_c, cfg)
    scale = 1.0 / np.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    # expanded K/V are fresh full-precision activations (never a ring
    # buffer, never quantized — the *latents* carry the quant axis), so the
    # registry's quant axis is pinned off; the decode backend itself follows
    # the config (the Pallas flash-decode kernel handles MLA's Dq != Dv)
    spec = AttentionSpec.from_config(cfg, kv_dtype="fp32")
    o = dispatch_decode(spec, q1, k, v, lengths + 1, scale=scale)
    out = jnp.einsum("bhk,hkd->bd", o, params["wo"])
    return new_cache, out


def _expand_latents(params, kv_lat, k_rope, cfg):
    """(B, S, rank)+(B, S, rope) latents -> full (B, H, S, qk_head), (B, H, S, v)."""
    m = cfg.mla
    B, S, _ = kv_lat.shape
    ukv = jnp.einsum("bsr,rhk->bhsk", kv_lat, params["w_ukv"])
    k_nope, v = ukv[..., : m.qk_nope_dim], ukv[..., m.qk_nope_dim:]
    k_rope = jnp.broadcast_to(
        k_rope[:, None], (B, cfg.num_heads, S, m.qk_rope_dim)
    )
    return jnp.concatenate([k_nope, k_rope], axis=-1), v


def mla_init_paged_cache(cfg, pool_tokens, dtype):
    """Flat-pool latent cache (DESIGN.md §7): the pool stores the *latents*
    (kv_lora_rank + qk_rope_dim per physical row), preserving the MLA memory
    win — paging and latent compression compose. Unquantized, one row
    ``kv_lat ‖ k_rope`` a token, the shared latent head the absorbed paged
    kernels read (DESIGN.md §14); quantized kv_dtypes store latent codes
    plus parallel per-token scale pools (DESIGN.md §8)."""
    m = cfg.mla
    if kv_quantized(cfg):
        cd = kv_code_dtype(cfg.kv_dtype)
        return {
            "kv_lat": jnp.zeros((pool_tokens, m.kv_lora_rank), cd),
            "k_rope": jnp.zeros((pool_tokens, m.qk_rope_dim), cd),
            "kv_lat_scale": jnp.zeros((pool_tokens,), jnp.float32),
            "k_rope_scale": jnp.zeros((pool_tokens,), jnp.float32),
        }
    return {"latent": jnp.zeros((pool_tokens, m.kv_lora_rank + m.qk_rope_dim),
                                dtype)}


def _latent_rows(params, x, cfg, positions):
    """(B, S, d) -> the pool rows ``kv_lat ‖ roped k_rope`` (B, S, rank +
    rope) of the chunk's tokens."""
    m = cfg.mla
    dkv = x @ params["w_dkv"]
    kv_lat = rmsnorm(params["kv_norm"], dkv[..., : m.kv_lora_rank])
    k_rope = apply_rope(dkv[..., m.kv_lora_rank:], positions, cfg.rope_base)
    return jnp.concatenate([kv_lat, k_rope], axis=-1)


def _absorbed_q(params, x, cfg, positions):
    """Each head's query absorbed into the latent: ``q_nope · W_UK ‖
    q_rope``, (B, H, S, rank + rope); its score against a latent row equals
    the expanded ``q · k`` of that head."""
    m = cfg.mla
    q_nope, q_rope = _mla_q(params, x, cfg, positions)
    w_uk = params["w_ukv"][..., : m.qk_nope_dim]            # (rank, H, nope)
    q_lat = jnp.einsum("bhsn,rhn->bhsr", q_nope, w_uk)
    return jnp.concatenate([q_lat, q_rope.astype(q_lat.dtype)], axis=-1)


def _absorbed_out(params, o_lat, cfg):
    """Attention over the latent values (B, H, S, rank) -> (B, S, d):
    W_UV per head, then the output projection."""
    w_uv = params["w_ukv"][..., cfg.mla.qk_nope_dim:]       # (rank, H, v)
    o = jnp.einsum("bhsr,rhv->bhsv", o_lat.astype(w_uv.dtype), w_uv)
    return jnp.einsum("bhsv,hvd->bsd", o, params["wo"])


def _scale(cfg):
    return 1.0 / np.sqrt(cfg.mla.qk_nope_dim + cfg.mla.qk_rope_dim)


def mla_paged_decode_step(params, pool, x1, cfg, lengths, rows, write_row,
                          block_tables=None, page_size=0):
    """Single-token MLA decode through the block table.

    Unquantized: the token's latent row is scattered into the pool at
    ``write_row`` and the absorbed query attends to the history's latent
    rows in place (``paged_decode`` dispatch with V inside K's rows). A
    quantized pool is gathered through ``rows``, dequantized and expanded
    to full K/V (DESIGN.md §8), a contiguous dispatch.
    """
    x = x1[:, None, :]
    pos = lengths[:, None]
    if not kv_quantized(cfg):
        latent = scatter_rows(pool["latent"], write_row,
                              _latent_rows(params, x, cfg, pos)[:, 0])
        o = dispatch_paged_decode(
            AttentionSpec.from_config(cfg, kv_dtype="fp32"),
            _absorbed_q(params, x, cfg, pos)[:, :, 0], latent, None, rows,
            lengths + 1, scale=_scale(cfg), block_tables=block_tables,
            page_size=page_size, v_width=cfg.mla.kv_lora_rank)
        return {"latent": latent}, _absorbed_out(params, o[:, :, None],
                                                 cfg)[:, 0]
    q, _, _, kv_lat, k_rope_raw = _mla_qkv(params, x, cfg, pos)
    q1 = q[:, :, 0]                                   # (B, H, qk_head)
    k_rope_new = apply_rope(
        k_rope_raw[:, None, :, :], pos[:, None], cfg.rope_base)[:, 0, 0]
    lat_pool, lat_scale = quant_scatter_rows(
        pool["kv_lat"], pool["kv_lat_scale"], write_row, kv_lat[:, 0],
        kv_dtype=cfg.kv_dtype)
    rope_pool, rope_scale = quant_scatter_rows(
        pool["k_rope"], pool["k_rope_scale"], write_row, k_rope_new,
        kv_dtype=cfg.kv_dtype)
    new_pool = {"kv_lat": lat_pool, "k_rope": rope_pool,
                "kv_lat_scale": lat_scale, "k_rope_scale": rope_scale}
    kv_lat_c = gather_dequant_rows(lat_pool, lat_scale, rows,
                                   cfg.kv_dtype)  # (B, L, rank)
    k_rope_c = gather_dequant_rows(rope_pool, rope_scale, rows,
                                   cfg.kv_dtype)  # (B, L, rope)
    k, v = _expand_latents(params, kv_lat_c, k_rope_c, cfg)
    # the latent pool is the paged object (gathered + dequantized fused
    # above); the expanded K/V decode is a *contiguous* dispatch and, like
    # mla_decode_step, follows the config's decode backend
    spec = AttentionSpec.from_config(cfg, kv_dtype="fp32")
    o = dispatch_decode(spec, q1, k, v, lengths + 1, scale=_scale(cfg))
    out = jnp.einsum("bhk,hkd->bd", o, params["wo"])
    return new_pool, out


def mla_paged_prefill_step(params, pool, x, cfg, lengths, n_valid, rows,
                           chunk_rows, block_tables=None, page_size=0):
    """Chunked MLA prefill through the block table.

    Unquantized: the chunk's absorbed queries attend to the history's
    latent rows and the chunk's own (``paged_prefill`` dispatch with V
    inside K's rows), then the chunk's rows are scattered into the pool. A
    quantized pool: the history's latents are gathered through ``rows``,
    dequantized and expanded once, and the chunk attends to [expanded
    history ++ chunk] (a contiguous dispatch).
    """
    if cfg.window:
        raise NotImplementedError("windowed MLA chunked prefill")
    m = cfg.mla
    B, C, _ = x.shape
    idx = jnp.arange(C)[None, :]
    positions = lengths[:, None] + idx
    chunk_valid = idx < n_valid[:, None]
    flat_rows = chunk_rows.reshape(-1)
    flat_valid = chunk_valid.reshape(-1)
    if not kv_quantized(cfg):
        lat = _latent_rows(params, x, cfg, positions)        # (B, C, ·)
        o = dispatch_paged_prefill(
            AttentionSpec.from_config(cfg, kv_dtype="fp32"),
            _absorbed_q(params, x, cfg, positions), lat[:, None], None,
            pool["latent"], None, rows, q_positions=positions,
            chunk_valid=chunk_valid, lengths=lengths, scale=_scale(cfg),
            block_tables=block_tables, page_size=page_size,
            v_width=m.kv_lora_rank)
        return {"latent": scatter_rows(pool["latent"], flat_rows,
                                       lat.reshape(B * C, -1), flat_valid)
                }, _absorbed_out(params, o, cfg)

    q, k_chunk, v_chunk, kv_lat, k_rope_raw = _mla_qkv(params, x, cfg,
                                                       positions)
    k_rope_chunk = apply_rope(
        k_rope_raw[:, None, :, :], positions[:, None], cfg.rope_base)[:, 0]
    # quantize the chunk's latents once; its queries attend to the same
    # dequantized expansion that the pool (and later decode) will see
    latq = quantize_kv(kv_lat, cfg.kv_dtype)
    ropeq = quantize_kv(k_rope_chunk, cfg.kv_dtype)
    k_chunk, v_chunk = _expand_latents(
        params, dequantize_kv(latq.codes, latq.scale, cfg.kv_dtype),
        dequantize_kv(ropeq.codes, ropeq.scale, cfg.kv_dtype), cfg)
    k_cache, v_cache = _expand_latents(
        params,
        gather_dequant_rows(pool["kv_lat"], pool["kv_lat_scale"], rows,
                            cfg.kv_dtype),
        gather_dequant_rows(pool["k_rope"], pool["k_rope_scale"], rows,
                            cfg.kv_dtype), cfg,
    )
    # the expanded history (gathered into logical order: row j = position
    # j, valid iff j < lengths) and the expanded chunk go to the prefill
    # backend separately — the contiguous dispatch convention, so the
    # config's backend (incl. the Dq != Dv-capable Pallas prefill kernel,
    # DESIGN.md §10) applies unchanged; the *latent* pool stays the paged,
    # quantized object
    o = dispatch_prefill(
        AttentionSpec.from_config(cfg, kv_dtype="fp32"), q, k_cache,
        v_cache, k_chunk, v_chunk, scale=_scale(cfg), lengths=lengths,
        n_valid=n_valid,
    )
    out = jnp.einsum("bhsk,hkd->bsd", o, params["wo"])
    return {
        "kv_lat": scatter_rows(pool["kv_lat"], flat_rows,
                               latq.codes.reshape(B * C, -1), flat_valid),
        "k_rope": scatter_rows(pool["k_rope"], flat_rows,
                               ropeq.codes.reshape(B * C, -1), flat_valid),
        "kv_lat_scale": scatter_rows(pool["kv_lat_scale"], flat_rows,
                                     latq.scale.reshape(-1), flat_valid),
        "k_rope_scale": scatter_rows(pool["k_rope_scale"], flat_rows,
                                     ropeq.scale.reshape(-1), flat_valid),
    }, out


def mla_prefill_step(params, cache, x, cfg, lengths, n_valid):
    """Chunked prefill for the MLA latent cache (DESIGN.md §6).

    x: (B, C, D); lengths: (B,) tokens already resident; n_valid: (B,)
    valid chunk tokens. Writes kv latents + roped shared key for the whole
    chunk, expands the *pre-chunk* cache latents once, and attends the chunk
    queries to [cache ++ chunk]. Returns (new_cache, out (B, C, D)).
    """
    if cfg.window:
        # forward() windows MLA via mla_apply; the latent-cache prefill/decode
        # paths do not mask by window yet — fail loudly rather than diverge
        raise NotImplementedError("windowed MLA chunked prefill")
    m = cfg.mla
    B, C, _ = x.shape
    idx = jnp.arange(C)[None, :]
    positions = lengths[:, None] + idx
    q, k_chunk, v_chunk, kv_lat, k_rope_raw = _mla_qkv(params, x, cfg, positions)
    k_rope_chunk = apply_rope(
        k_rope_raw[:, None, :, :], positions[:, None], cfg.rope_base
    )[:, 0]

    quant = kv_quantized(cfg)
    if quant:
        latq = quantize_kv(kv_lat, cfg.kv_dtype)
        ropeq = quantize_kv(k_rope_chunk, cfg.kv_dtype)
        k_chunk, v_chunk = _expand_latents(
            params, dequantize_kv(latq.codes, latq.scale, cfg.kv_dtype),
            dequantize_kv(ropeq.codes, ropeq.scale, cfg.kv_dtype), cfg)
        k_cache, v_cache = _expand_latents(
            params,
            dequantize_kv(cache["kv_lat"], cache["kv_lat_scale"],
                          cfg.kv_dtype),
            dequantize_kv(cache["k_rope"], cache["k_rope_scale"],
                          cfg.kv_dtype), cfg,
        )
    else:
        k_cache, v_cache = _expand_latents(
            params, cache["kv_lat"], cache["k_rope"], cfg
        )
    # expanded cache + expanded chunk go to the prefill backend separately
    # (slot j = position j, valid iff j < lengths): the masked-XLA backend
    # concatenates, the Pallas kernel reads both segments fused (§10)
    chunk_valid = idx < n_valid[:, None]
    scale = 1.0 / np.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    o = dispatch_prefill(
        AttentionSpec.from_config(cfg, kv_dtype="fp32"), q, k_cache,
        v_cache, k_chunk, v_chunk, scale=scale, lengths=lengths,
        n_valid=n_valid,
    )
    out = jnp.einsum("bhsk,hkd->bsd", o, params["wo"])

    if quant:
        new_cache = {
            "kv_lat": chunk_write(cache["kv_lat"], latq.codes, positions,
                                  chunk_valid, axis=1),
            "k_rope": chunk_write(cache["k_rope"], ropeq.codes, positions,
                                  chunk_valid, axis=1),
            "kv_lat_scale": chunk_write(cache["kv_lat_scale"], latq.scale,
                                        positions, chunk_valid, axis=1),
            "k_rope_scale": chunk_write(cache["k_rope_scale"], ropeq.scale,
                                        positions, chunk_valid, axis=1),
        }
    else:
        new_cache = {
            "kv_lat": chunk_write(cache["kv_lat"], kv_lat, positions,
                                  chunk_valid, axis=1),
            "k_rope": chunk_write(cache["k_rope"], k_rope_chunk, positions,
                                  chunk_valid, axis=1),
        }
    return new_cache, out
