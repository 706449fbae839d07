"""Per-architecture smoke tests: reduced config of the same family, one
forward + one train-grad step on CPU; asserts shapes and finiteness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models.api import (
    decode_step,
    forward,
    init_decode_state,
    init_model,
    loss_fn,
)
from repro.models.inputs import make_batch, token_count


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_and_loss(arch):
    cfg = get_config(arch, smoke=True, dtype="float32", param_dtype="float32")
    key = jax.random.PRNGKey(0)
    params = init_model(key, cfg)
    batch = make_batch(jax.random.PRNGKey(1), cfg, seq_len=64, global_batch=2)
    logits = forward(params, batch, cfg)
    S_text = token_count(cfg, 64)
    S_total = 64 if (cfg.frontend and not cfg.encoder_layers) else S_text
    assert logits.shape == (2, S_total, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))

    loss, grads = jax.value_and_grad(lambda p: loss_fn(p, batch, cfg))(params)
    assert np.isfinite(float(loss)) and float(loss) > 0
    gnorm = jax.tree_util.tree_reduce(
        lambda a, l: a + float(jnp.sum(jnp.abs(l))), grads, 0.0
    )
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_step(arch):
    cfg = get_config(arch, smoke=True, dtype="float32", param_dtype="float32")
    params = init_model(jax.random.PRNGKey(0), cfg)
    B, max_len = 2, 32
    if cfg.encoder_layers:
        state = init_decode_state(cfg, B, max_len, enc_len=cfg.frontend_tokens)
        from repro.models.api import encode_for_decode

        fe = jax.random.normal(
            jax.random.PRNGKey(2), (B, cfg.frontend_tokens, cfg.frontend_dim)
        ).astype(cfg.dtype)
        state = encode_for_decode(
            params, state, fe, jnp.full((B,), cfg.frontend_tokens, jnp.int32), cfg
        )
    else:
        state = init_decode_state(cfg, B, max_len)
    toks = jnp.array([1, 2], jnp.int32)
    lengths = jnp.zeros((B,), jnp.int32)
    for step in range(3):
        logits, state = decode_step(params, state, toks, lengths + step, cfg)
        assert logits.shape == (B, cfg.vocab_size)
        assert np.all(np.isfinite(np.asarray(logits, np.float32)))
        toks = jnp.argmax(logits, -1).astype(jnp.int32)


def test_decode_matches_forward_dense():
    """Teacher-forced decode must reproduce forward logits (qwen2 smoke)."""
    cfg = get_config("qwen2-0.5b", smoke=True, dtype="float32",
                     param_dtype="float32", attention_variant="exact")
    params = init_model(jax.random.PRNGKey(0), cfg)
    B, S = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    full = forward(params, {"tokens": toks}, cfg)
    state = init_decode_state(cfg, B, S)
    outs = []
    for t in range(S):
        logits, state = decode_step(
            params, state, toks[:, t], jnp.full((B,), t, jnp.int32), cfg
        )
        outs.append(logits)
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full), atol=2e-4, rtol=2e-4)


def test_decode_matches_forward_hybrid():
    """Same teacher-forcing check through RG-LRU + local attention."""
    cfg = get_config("recurrentgemma-2b", smoke=True, dtype="float32",
                     param_dtype="float32", attention_variant="exact")
    params = init_model(jax.random.PRNGKey(0), cfg)
    B, S = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    full = forward(params, {"tokens": toks}, cfg)
    state = init_decode_state(cfg, B, S)
    outs = []
    for t in range(S):
        logits, state = decode_step(
            params, state, toks[:, t], jnp.full((B,), t, jnp.int32), cfg
        )
        outs.append(logits)
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full), atol=3e-4, rtol=3e-4)


def test_decode_matches_forward_xlstm():
    """Chunkwise-parallel mLSTM == sequential decode recurrence."""
    cfg = get_config("xlstm-1.3b", smoke=True, dtype="float32",
                     param_dtype="float32")
    params = init_model(jax.random.PRNGKey(0), cfg)
    B, S = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    full = forward(params, {"tokens": toks}, cfg)
    state = init_decode_state(cfg, B, S)
    outs = []
    for t in range(S):
        logits, state = decode_step(
            params, state, toks[:, t], jnp.full((B,), t, jnp.int32), cfg
        )
        outs.append(logits)
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full), atol=3e-4, rtol=3e-4)


def test_expmul_variant_close_to_exact_end_to_end():
    cfg = get_config("gemma-7b", smoke=True, dtype="float32",
                     param_dtype="float32")
    params = init_model(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size)
    lx = forward(params, {"tokens": toks}, cfg.replace(attention_variant="exact"))
    lq = forward(params, {"tokens": toks}, cfg.replace(attention_variant="expmul"))
    # power-of-two softmax weights perturb logits mildly; ranking mostly
    # holds even on a RANDOM-INIT model (whose logits are full of near-ties
    # — trained-model agreement is 100%, see benchmarks/table1_fidelity.py)
    agree = np.mean(
        np.argmax(np.asarray(lx), -1) == np.argmax(np.asarray(lq), -1)
    )
    assert agree > 0.75


def test_param_counts_match_published_class():
    """Total parameters land in the published size class."""
    expected = {
        "gemma-7b": (7.7e9, 9.5e9),       # 8.5B incl. 786M embeddings
        "qwen2-0.5b": (4.4e8, 6.5e8),
        "qwen1.5-0.5b": (4.4e8, 6.8e8),
        "minicpm3-4b": (3.5e9, 4.8e9),
        "moonlight-16b-a3b": (1.55e10, 1.65e10),   # 15.96B published
        "xlstm-1.3b": (1.0e9, 1.8e9),
        "recurrentgemma-2b": (2.2e9, 3.4e9),
        "kimi-k2-1t-a32b": (0.9e12, 1.2e12),
        "arctic-480b": (4.2e11, 5.3e11),
        "llava-next-34b": (3.2e10, 3.7e10),
        "seamless-m4t-medium": (4.5e8, 1.4e9),
    }
    for arch, (lo, hi) in expected.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, f"{arch}: {n:.3e} not in [{lo:.1e}, {hi:.1e}]"
