"""Compile every serving kernel for a described TPU v5e, at qwen2-0.5b widths.

Interpret mode, which every other kernel test runs in, ignores TPU tiling,
so a block shape the chip's compiler refuses passes there. These tests
lower each Pallas kernel with ``interpret=False`` for one chip of a
described ``v5e:2x2`` topology (no chip attached) and require the compiled
program to contain the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports this file. Keep these tests in this one file for the same reason.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode.ops import (
    decode_attention_pallas,
    fused_paged_decode_attention_pallas,
    quant_decode_attention_pallas,
    quant_fused_paged_decode_attention_pallas,
)
from repro.kernels.expmul.expmul import expmul_pallas
from repro.kernels.flash.ops import (
    flash_attention_fwd,
    fused_paged_prefill_attention_pallas,
    prefill_attention_pallas,
    quant_fused_paged_prefill_attention_pallas,
    quant_prefill_attention_pallas,
)

CFG = get_config("qwen2-0.5b")
H, HKV, D = CFG.num_heads, CFG.num_kv_heads, CFG.resolved_head_dim()
B = 8          # serving slots
S = 1024       # resident context per slot
C = 128        # prefill chunk
PS = CFG.page_size
MB = S // PS   # block-table width
NB = B * MB    # pool blocks
VALUES = jnp.dtype(CFG.dtype)   # unquantized KV is stored in cfg.dtype
VARIANTS = ("exact", "expmul")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _kv(dtype, *lead):
    """K/V operand shapes: values in cfg.dtype, or int8 codes + f32 scales."""
    if dtype == "int8":
        return [((*lead, D), jnp.int8)] * 2 + [(lead, jnp.float32)] * 2
    return [((*lead, D), VALUES)] * 2


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("kv", ("unquantized", "int8"))
def test_contiguous_decode_compiles(one_chip, kv, variant):
    op = quant_decode_attention_pallas if kv == "int8" \
        else decode_attention_pallas
    _compile(one_chip,
             lambda q, *rest: op(q, *rest, variant=variant, interpret=False),
             ((B, H, D), VALUES), *_kv(kv, B, HKV, S), ((B,), jnp.int32))


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("kv", ("unquantized", "int8"))
def test_paged_decode_compiles(one_chip, kv, variant):
    op = quant_fused_paged_decode_attention_pallas if kv == "int8" \
        else fused_paged_decode_attention_pallas
    _compile(one_chip,
             lambda q, *rest: op(q, *rest, page_size=PS, variant=variant,
                                 interpret=False),
             ((B, H, D), VALUES), *_kv(kv, NB * PS, HKV),
             ((B, MB), jnp.int32), ((B,), jnp.int32))


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("kv", ("unquantized", "int8"))
def test_contiguous_prefill_compiles(one_chip, kv, variant):
    op = quant_prefill_attention_pallas if kv == "int8" \
        else prefill_attention_pallas
    _compile(one_chip,
             lambda q, *rest: op(q, *rest, variant=variant, interpret=False),
             ((B, H, C, D), VALUES), *_kv(kv, B, HKV, S), *_kv(kv, B, HKV, C),
             ((B,), jnp.int32), ((B,), jnp.int32))


# (slots, block-table width, q heads, KV heads): the shapes above, and the
# benchmark's two configurations as served (bench/configs: 64 x 3072-token
# slots of qwen2-0.5b; qwen1.5-0.5b's MHA, whose 16 slots share its kernel)
PAGED_PREFILL_WIDTHS = {
    "smoke": (B, MB, H, HKV),
    "qwen2-0.5b": (64, 3072 // PS, 14, 2),
    "qwen1.5-0.5b": (16, 3072 // PS, 16, 16),
}


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("kv", ("unquantized", "int8"))
@pytest.mark.parametrize("widths", PAGED_PREFILL_WIDTHS)
def test_paged_prefill_compiles(one_chip, widths, kv, variant):
    slots, mb, h, hkv = PAGED_PREFILL_WIDTHS[widths]
    op = quant_fused_paged_prefill_attention_pallas if kv == "int8" \
        else fused_paged_prefill_attention_pallas
    text = _compile(one_chip,
                    lambda q, *rest: op(q, *rest, page_size=PS,
                                        variant=variant, interpret=False),
                    ((slots, h, C, D), VALUES), *_kv(kv, slots, hkv, C),
                    *_kv(kv, slots * mb * PS, hkv), ((slots, mb), jnp.int32),
                    ((slots,), jnp.int32), ((slots,), jnp.int32))
    # the benchmark's kernel roofline finds the kernel by this name
    assert "paged_prefill_fwd_pallas" in text


@pytest.mark.parametrize("variant", VARIANTS)
def test_flash_forward_compiles(one_chip, variant):
    _compile(one_chip,
             lambda q, k, v: flash_attention_fwd(q, k, v, variant=variant,
                                                 interpret=False),
             ((1, H, S, D), VALUES), ((1, HKV, S, D), VALUES),
             ((1, HKV, S, D), VALUES))


def test_expmul_tile_compiles(one_chip):
    _compile(one_chip, lambda x, v: expmul_pallas(x, v, interpret=False),
             ((S,), jnp.float32), ((S, 2 * D), jnp.float32))


# MLA's absorbed latent as moonlight-16b-a3b serves it (bench/configs): one
# shared KV head of kv_lora_rank + rope = 576 columns, V its first 512,
# read from K's page; 16 query heads in the group; 32 slots of 3072 tokens
LATENT = dict(slots=32, mb=3072 // PS, heads=16, dk=576, dv=512)


@pytest.mark.parametrize("variant", VARIANTS)
def test_latent_paged_decode_compiles(one_chip, variant):
    n, mb, h, dk, dv = LATENT.values()
    text = _compile(one_chip,
                    lambda q, pool, bt, ln:
                    fused_paged_decode_attention_pallas(
                        q, pool, None, bt, ln, page_size=PS, variant=variant,
                        v_width=dv, interpret=False),
                    ((n, h, dk), VALUES), ((n * mb * PS, dk), VALUES),
                    ((n, mb), jnp.int32), ((n,), jnp.int32))
    assert "paged_decode_fwd_pallas" in text


@pytest.mark.parametrize("variant", VARIANTS)
def test_latent_paged_prefill_compiles(one_chip, variant):
    n, mb, h, dk, dv = LATENT.values()
    text = _compile(one_chip,
                    lambda q, kn, pool, bt, ln, nv:
                    fused_paged_prefill_attention_pallas(
                        q, kn, None, pool, None, bt, ln, nv, page_size=PS,
                        variant=variant, v_width=dv, interpret=False),
                    ((n, h, C, dk), VALUES), ((n, 1, C, dk), VALUES),
                    ((n * mb * PS, dk), VALUES), ((n, mb), jnp.int32),
                    ((n,), jnp.int32), ((n,), jnp.int32))
    assert "paged_prefill_fwd_pallas" in text


# the expert FFN's grouped matmuls at moonlight-16b-a3b's widths: 8 held
# experts, d 2048 -> 1408 -> 2048, for a decode tick's 32 x 6 routed rows
# and a prefill tick's 32 x 128 x 6
@pytest.mark.parametrize("rows", (32 * 6, 32 * 128 * 6))
def test_expert_gmm_compiles(one_chip, rows):
    from repro.kernels.experts import grouped_ffn, row_tile

    E, d, f = 8, 2048, 1408
    m = rows + -rows % row_tile(rows)
    text = _compile(one_chip,
                    lambda x, wg, wu, wd, gs: grouped_ffn(
                        x, {"w_gate": wg, "w_up": wu, "w_down": wd}, gs,
                        jax.nn.silu, interpret=False),
                    ((m, d), VALUES), ((E, d, f), VALUES), ((E, d, f), VALUES),
                    ((E, f, d), VALUES), ((E,), jnp.int32))
    # the benchmark's expert roofline finds the kernel by this name
    assert "expert_gmm" in text
