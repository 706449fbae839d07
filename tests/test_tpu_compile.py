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


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("kv", ("unquantized", "int8"))
def test_paged_prefill_compiles(one_chip, kv, variant):
    op = quant_fused_paged_prefill_attention_pallas if kv == "int8" \
        else fused_paged_prefill_attention_pallas
    _compile(one_chip,
             lambda q, *rest: op(q, *rest, page_size=PS, variant=variant,
                                 interpret=False),
             ((B, H, C, D), VALUES), *_kv(kv, B, HKV, C),
             *_kv(kv, NB * PS, HKV), ((B, MB), jnp.int32),
             ((B,), jnp.int32), ((B,), jnp.int32))


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
