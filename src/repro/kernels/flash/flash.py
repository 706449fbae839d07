"""Pallas TPU kernel: FlashAttention-2 forward, exact and ExpMul variants.

Tiling: grid = (batch*heads, q_blocks, kv_blocks), kv innermost so the
running (m, l, acc) state lives in VMEM scratch across kv steps. Per tile:

  exact : s = qk^T;  p = exp(s - m);  alpha = exp(dm);  acc = acc*alpha + p@v
  expmul: p = 2^{-Log2Exp(s - m)} assembled from bits (integer shift-add, no
          transcendental); the acc/l rescale is an exponent-field integer
          subtraction (apply_pow2_scale). Only the p@v MXU matmul remains in
          floating point — this is the paper's ExpMul datapath mapped onto
          the TPU's VPU/MXU split (DESIGN.md §2).

Causal/local-window blocks that fall fully outside the band are skipped via
``pl.when`` (no VPU/MXU work is issued for them).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash.tile import (
    LANES as _LANES,
    finalize_tiles,
    init_tiles,
    online_softmax_tile,
)


def _fwd_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    scale,
    causal,
    window,
    variant,
    block_q,
    block_k,
    nk,
    kv_len,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        init_tiles(m_scr, l_scr, acc_scr)

    r0 = qi * block_q
    c0 = ki * block_k
    run = c0 < kv_len
    if causal:
        run = run & (c0 < r0 + block_q)
    if window is not None:
        run = run & (c0 + block_k > r0 - window)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)        # (bq, d)
        rows = r0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        cols = c0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = cols < kv_len
        if causal:
            mask = mask & (rows >= cols)
        if window is not None:
            mask = mask & ((rows - cols) < window)
        online_softmax_tile(
            q, k_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32),
            None, None, mask, m_scr, l_scr, acc_scr,
            scale=scale, variant=variant)

    @pl.when(ki == nk - 1)
    def _fin():
        finalize_tiles(o_ref.at[0], l_scr, acc_scr)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "scale", "window", "variant", "block_q", "block_k",
        "num_q_heads", "num_kv_heads", "kv_len", "interpret",
    ),
)
def flash_fwd_pallas(
    q3: jax.Array,   # (B*H, Sq_padded, D)
    k3: jax.Array,   # (B*Hkv, Sk_padded, D)
    v3: jax.Array,
    *,
    causal: bool,
    scale: float,
    window,
    variant: str,
    block_q: int,
    block_k: int,
    num_q_heads: int,
    num_kv_heads: int,
    kv_len: int,
    interpret: bool,
):
    BH, Sq, D = q3.shape
    Sk = k3.shape[1]
    nq = Sq // block_q
    nk = Sk // block_k
    group = num_q_heads // num_kv_heads

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki):
        b = bh // num_q_heads
        h = bh % num_q_heads
        return (b * num_kv_heads + h // group, ki, 0)

    kernel = functools.partial(
        _fwd_kernel,
        scale=scale,
        causal=causal,
        window=window,
        variant=variant,
        block_q=block_q,
        block_k=block_k,
        nk=nk,
        kv_len=kv_len,
    )
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_map),
            pl.BlockSpec((1, block_k, D), kv_map),
            pl.BlockSpec((1, block_k, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), q_map),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q3.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(q3, k3, v3)
