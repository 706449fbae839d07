"""The benchmark's reference against the program, on the CPU in float32:
the same weights give the same logits, so the reference computes the
model the program serves (a witness for the adapter in
bench/archs/qwen2.py, the rotary convention, the norms and, for ExpMul,
the tiling)."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import model, reference_core, spec  # noqa: E402


def _tiny(variant):
    conf = json.loads((ROOT / "bench/configs/qwen2-0.5b.json").read_text())
    conf.update(hidden_size=128, num_attention_heads=2, num_key_value_heads=1,
                intermediate_size=256, vocab_size=512, num_hidden_layers=2,
                attention_variant=variant, torch_dtype="float32")
    return conf


@pytest.mark.parametrize("variant,impl", [("exact", "ref"),
                                          ("expmul", "pallas")])
def test_reference_logits_match_the_program_in_float32(variant, impl):
    from repro.models.api import forward

    conf = _tiny(variant)
    arch, ref = spec.arch_module(conf), spec.reference_module(conf)
    sz = arch.sizes(conf)
    w = arch.canonical_weights(2**32 + 7, conf)
    params = arch.to_program(w, conf)
    # 16-token key tiles in the program's flash kernel, as in a paged step
    cfg = arch.program_config(conf).replace(
        attention_impl=impl, attention_block_q=16, attention_block_k=16,
        attention_q_chunks=1)
    model.check_program_layout(params, cfg)
    S = 64
    toks = jax.random.randint(jax.random.PRNGKey(1), (S,), 1, 512)
    with jax.default_matmul_precision("highest"):
        got = forward(params, {"tokens": toks[None]}, cfg)[0]
    h = ref.hidden(w, toks, jnp.zeros(S, bool), sz, variant, 16, None)
    want = ref.logits(w, h, None)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert err < 1e-4, err


def test_lone_token_tile_changes_expmul():
    """Splitting a query's own token into a tile of its own changes the
    ExpMul result (so the reference must know which steps did it), and
    changes nothing for exact softmax."""
    S, H, D = 48, 2, 16
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (H, S, D)) for kk in (k1, k2, k3))
    split = jnp.asarray(np.arange(S) % 3 == 1)
    a = reference_core.attn_expmul(q, k, v, 0.25, jnp.zeros(S, bool), 16)
    b = reference_core.attn_expmul(q, k, v, 0.25, split, 16)
    assert float(jnp.max(jnp.abs(a - b))) > 1e-3
    # rows whose own token starts a tile are unchanged by the split
    rows = np.arange(S)[(np.arange(S) % 16 == 0)]
    np.testing.assert_array_equal(np.asarray(a)[:, rows], np.asarray(b)[:, rows])
