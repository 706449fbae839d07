"""Moonlight-16B-A3B (DeepSeek-V3 layers) against its plain reference, at the
registry's SMOKE widths on seeded random weights, float32.

* Serving: chunked paged prefill then paged decode through ``ServeEngine``
  (absorbed latent attention in the fused paged kernels, dropless experts
  over a held share) gives, at every served position, the logits of the
  benchmark's float32 reference (``bench/reference_deepseek_v3.py``, which
  expands the latents the published way). Float32 on both sides differs
  only by the order of sums (about 1e-6 of the logits' spread), so the
  tolerance is 1e-4 of that spread; rounding the latent pool or the expert
  operands to float8 e4m3, the precision below the bfloat16 the
  configuration serves, moves the logits by more than a hundred times that
  and fails it (tested).
* Absorbed and expanded MLA agree at one layer; the held shares of the
  experts, with the shared experts counted once, add up to the uncut
  layer; a token's output does not depend on its batch (no drops); the
  router is DeepSeek-V3's noaux_tc written out by hand.
"""
import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import reference_core, spec  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.kernels.paged import slot_rows, token_rows  # noqa: E402
from repro.layers import mla, moe  # noqa: E402
from repro.serve.engine import ServeEngine  # noqa: E402

SMOKE = get_config("moonlight-16b-a3b", smoke=True)
PAGE = 8
TOL = 1e-4     # of the reference logits' spread (module docstring)


def _conf(held=4, variant="exact"):
    """The benchmark's configuration file at the SMOKE widths, float32,
    holding ``held`` of SMOKE's routed experts."""
    conf = json.loads((ROOT / "bench/configs/moonlight-16b-a3b.json")
                      .read_text())
    m, a = SMOKE.moe, SMOKE.mla
    conf.update(hidden_size=SMOKE.d_model, num_attention_heads=SMOKE.num_heads,
                num_key_value_heads=SMOKE.num_heads,
                num_hidden_layers=SMOKE.num_layers,
                intermediate_size=SMOKE.d_ff, moe_intermediate_size=m.d_ff,
                vocab_size=SMOKE.vocab_size, kv_lora_rank=a.kv_lora_rank,
                qk_rope_head_dim=a.qk_rope_dim, qk_nope_head_dim=a.qk_nope_dim,
                v_head_dim=a.v_head_dim, num_experts_per_tok=m.top_k,
                n_routed_experts=held, torch_dtype="float32",
                attention_variant=variant)
    conf["deployment"] = dict(conf["deployment"],
                              n_routed_experts=m.num_experts)
    return conf


def _fp8(x):
    """Round to float8 e4m3 with one scale per row (the control's rule)."""
    return reference_core.fq(x.astype(jnp.float32), -1, "fp8").astype(x.dtype)


def _serve(conf, prompt, n_new, seed=3):
    """Serve one prompt greedily; return the served tokens and the logits
    each was sampled from."""
    arch = spec.arch_module(conf)
    cfg = arch.program_config(conf)
    params = arch.to_program(arch.canonical_weights(seed, conf), conf)
    eng = ServeEngine(params, cfg, slots=2, max_len=64, chunk_size=8,
                      kv_layout="paged", page_size=PAGE,
                      attention_impl="pallas", temperature=0.0)
    seen = []
    for name in ("_prefill", "_decode"):
        step = getattr(eng, name)

        def wrapped(*a, step=step, name=name):
            logits, state = step(*a)
            seen.append((name, np.asarray(logits[0])))
            return logits, state
        setattr(eng, name, wrapped)
    req = eng.submit(prompt, n_new)
    eng.run()
    # the last prefill chunk samples the first token, each decode the next
    last = max(i for i, (n, _) in enumerate(seen) if n == "_prefill")
    return req.out, np.stack([lg for _, lg in seen[last:]][:len(req.out)])


def _reference_logits(conf, prompt, out, seed=3):
    arch = spec.arch_module(conf)
    ref = spec.reference_module(conf)
    w = arch.canonical_weights(seed, conf)
    toks = np.zeros(64, np.int32)
    toks[:len(prompt) + len(out)] = prompt + out
    sz = arch.sizes(conf)
    with jax.default_matmul_precision("highest"):
        h = ref.hidden(w, jnp.asarray(toks), jnp.zeros(64, bool), sz,
                       conf["attention_variant"], PAGE, None)
        rows = len(prompt) - 1 + np.arange(len(out))
        return np.asarray(ref.logits(w, h[rows], None))


def _gap(conf, monkeypatch=None, quantize=None):
    prompt = list(np.random.default_rng(0).integers(1, 255, 21))
    if quantize == "latent":
        rows = mla._latent_rows
        monkeypatch.setattr(mla, "_latent_rows",
                            lambda *a: _fp8(rows(*a)))
    elif quantize == "experts":
        ffn = moe.grouped_ffn
        monkeypatch.setattr(moe, "grouped_ffn", lambda xs, p, *a: ffn(
            _fp8(xs), {k: _fp8(v) if k.startswith("w_") else v
                       for k, v in p.items()}, *a))
    out, got = _serve(conf, prompt, 6)
    want = _reference_logits(conf, prompt, out)
    return np.max(np.abs(got - want)) / np.ptp(want)


@pytest.mark.parametrize("variant", ["exact", "expmul"])
def test_served_logits_match_the_reference(variant):
    assert _gap(_conf(variant=variant)) < TOL


@pytest.mark.parametrize("quantize", ["latent", "experts"])
def test_float8_operands_fail_the_tolerance(monkeypatch, quantize):
    assert _gap(_conf(), monkeypatch, quantize) > 100 * TOL


def _layer_cfg(**moe_kw):
    cfg = SMOKE.replace(dtype="float32", param_dtype="float32")
    return cfg.replace(moe=dataclasses.replace(cfg.moe, **moe_kw))


@pytest.mark.parametrize("impl", ["pallas", "flash_jnp"])
def test_absorbed_and_expanded_mla_agree(impl):
    cfg = _layer_cfg().replace(attention_impl=impl,
                               attention_variant="exact")
    p = mla.mla_init(jax.random.PRNGKey(1), cfg, jnp.float32)
    B, S = 2, 24
    x = jax.random.normal(jax.random.PRNGKey(2), (B, S, cfg.d_model))
    # expanded K/V (the full-sequence Pallas kernel needs Dk == Dv)
    want = mla.mla_apply(p, x, cfg.replace(attention_impl="flash_jnp"))
    blocks = -(-S // PAGE)
    bt = jnp.arange(B * blocks, dtype=jnp.int32).reshape(B, blocks)
    pool = mla.mla_init_paged_cache(cfg, B * blocks * PAGE, jnp.float32)
    lengths = jnp.zeros(B, jnp.int32)
    chunk = token_rows(bt, jnp.arange(S)[None, :].repeat(B, 0), PAGE)
    _, got = mla.mla_paged_prefill_step(
        p, pool, x, cfg, lengths, jnp.full(B, S), slot_rows(bt, PAGE), chunk,
        bt, PAGE)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_expert_shares_add_up_to_the_uncut_layer():
    cfg = _layer_cfg()
    E = cfg.moe.num_experts
    p = moe.moe_init(jax.random.PRNGKey(4), cfg, jnp.float32)
    p["e_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(5), (E,))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 16, cfg.d_model))
    total = 0.0
    for start, stop in [(0, 3), (3, 4), (4, 8)]:
        share = _layer_cfg(held_experts=(start, stop))
        ps = {k: v[start:stop] if k.startswith("w_") else v
              for k, v in p.items() if k != "shared"}
        total = total + moe.moe_apply(ps, x, share, impl="dropless")
    shared = moe.mlp_apply(p["shared"], x, cfg.activation)
    np.testing.assert_allclose(total + shared, moe.moe_ref(p, x, cfg),
                               rtol=1e-5, atol=1e-5)


def test_a_token_does_not_depend_on_its_batch():
    cfg = _layer_cfg(held_experts=(2, 6))
    p = moe.moe_init(jax.random.PRNGKey(7), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 64, cfg.d_model))
    # every token of the batch is the same as the first but for a little
    # noise, so they all crowd onto the same experts
    crowd = x[:, :1] + 1e-3 * x
    batch = moe.moe_apply(p, crowd, cfg, impl="dropless")
    alone = jnp.concatenate([moe.moe_apply(p, crowd[:, i:i + 1], cfg,
                                           impl="dropless")
                             for i in range(crowd.shape[1])], axis=1)
    np.testing.assert_allclose(batch, alone, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(batch, moe.moe_ref(p, crowd, cfg),
                               rtol=1e-5, atol=1e-5)


def test_router_is_noaux_tc():
    cfg = _layer_cfg()
    m = cfg.moe
    p = moe.moe_init(jax.random.PRNGKey(9), cfg, jnp.float32)
    p["e_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(10),
                                          (m.num_experts,))
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(11),
                                     (32, cfg.d_model)))
    w, ids = moe.route(p, jnp.asarray(x), m)
    router = np.asarray(p["router"], np.float64)
    bias = np.asarray(p["e_bias"], np.float64)
    for t in range(len(x)):
        scores = 1 / (1 + np.exp(-(x[t] @ router)))
        pick = np.argsort(-(scores + bias), kind="stable")[:m.top_k]
        want = scores[pick] / scores[pick].sum() * m.routed_scaling_factor
        assert list(np.asarray(ids[t])) == list(pick)
        np.testing.assert_allclose(np.asarray(w[t]), want, rtol=1e-5)


def test_one_paged_state_covers_the_leading_dense_layer():
    """The leading dense layer's latent pool is the first entry of the one
    stacked cache: pool init, copy-on-write and poisoning reach it."""
    from repro.models.api import (copy_paged_block, init_paged_state,
                                  poison_paged_block)

    cfg = SMOKE
    state = init_paged_state(cfg, slots=2, pool_blocks=4, page_size=PAGE)
    (pool,) = state["caches"]
    assert pool["latent"].shape == (
        cfg.num_layers, 4 * PAGE,
        cfg.mla.kv_lora_rank + cfg.mla.qk_rope_dim)
    rows = jnp.arange(4 * PAGE, dtype=jnp.float32)
    state = {"caches": ({"latent": jnp.broadcast_to(
        rows[None, :, None], pool["latent"].shape).astype(
            pool["latent"].dtype)},)}
    copied = copy_paged_block(state, cfg, 1, 3, page_size=PAGE)
    got = np.asarray(copied["caches"][0]["latent"], np.float32)
    np.testing.assert_array_equal(got[:, 3 * PAGE:, 0],
                                  np.broadcast_to(np.arange(PAGE) + PAGE,
                                                  (cfg.num_layers, PAGE)))
    poisoned = poison_paged_block(state, cfg, 2, page_size=PAGE)
    bad = np.asarray(poisoned["caches"][0]["latent"], np.float32)
    assert np.isnan(bad[:, 2 * PAGE:3 * PAGE]).all()
    assert not np.isnan(bad[:, :2 * PAGE]).any()
