"""Weights from the seed, and the system under test built from a
configuration file.

The benchmark makes its own weights: one jitted call draws them on the
device, in the dtype they are served in, in a plain layout of its own
(``canonical_weights``). The reference reads that layout; ``to_program``
hands the same arrays to the program in the layout ``repro.models.api``
expects, as a checkpoint loader would.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# configuration keys (Hugging Face names) -> repro ModelConfig fields
SIZE_KEYS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_base",
    "tie_word_embeddings": "tie_embeddings",
}


def key_from_seed(seed: int):
    """A PRNG key from any non-negative whole number, also past 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def sizes(conf: dict) -> dict:
    """The shape numbers the benchmark's own arithmetic uses."""
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return {
        "layers": conf["num_hidden_layers"], "d": d, "heads": h,
        "kv_heads": conf["num_key_value_heads"], "head_dim": d // h,
        "ffn": conf["intermediate_size"], "vocab": conf["vocab_size"],
        "rope_theta": float(conf["rope_theta"]),
        "eps": float(conf["rms_norm_eps"]),
    }


def program_config(conf: dict):
    """The repro ``ModelConfig`` for a configuration file: the registry's
    architecture with every size taken from the file. Raises if the
    architecture is not one this benchmark's reference computes."""
    from repro.configs import get_config

    cfg = get_config(conf["arch"])
    cfg = cfg.replace(**{field: conf[key] for key, field in SIZE_KEYS.items()},
                      attention_variant=conf["attention_variant"],
                      dtype=conf["torch_dtype"],
                      param_dtype=conf["torch_dtype"])
    expect = dict(family="dense", block_pattern=("attn",), activation="swiglu",
                  norm="rmsnorm", qkv_bias=True, mla=None, moe=None,
                  window=None, scale_embeddings=False, logits_softcap=None,
                  head_dim=None)
    wrong = {k: getattr(cfg, k) for k, v in expect.items()
             if getattr(cfg, k) != v}
    if wrong or conf["hidden_act"] != "silu":
        raise ValueError(f"{conf['arch']}: not the dense QKV-bias SwiGLU "
                         f"decoder the reference computes: {wrong}")
    return cfg


@functools.partial(jax.jit, static_argnames=("sz", "dtype"))
def _canonical(key, sz, dtype):
    sz = dict(sz)
    L, d, H, Hkv, D, F, V = (sz[k] for k in (
        "layers", "d", "heads", "kv_heads", "head_dim", "ffn", "vocab"))
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * std).astype(dtype)

    def ones_ish(shape):
        return (1.0 + 0.05 * jax.random.normal(next(ks), shape,
                                               jnp.float32)).astype(dtype)

    return {
        "embed": normal((V, d), 0.02),
        "final_norm": ones_ish((d,)),
        "attn_norm": ones_ish((L, d)),
        "wq": normal((L, d, H * D), d ** -0.5),
        "bq": normal((L, H * D), 0.1),
        "wk": normal((L, d, Hkv * D), d ** -0.5),
        "bk": normal((L, Hkv * D), 0.1),
        "wv": normal((L, d, Hkv * D), d ** -0.5),
        "bv": normal((L, Hkv * D), 0.1),
        "wo": normal((L, H * D, d), (H * D) ** -0.5),
        "mlp_norm": ones_ish((L, d)),
        "w_gate": normal((L, d, F), d ** -0.5),
        "w_up": normal((L, d, F), d ** -0.5),
        "w_down": normal((L, F, d), F ** -0.5),
    }


def canonical_weights(seed: int, conf: dict):
    """The cell's weights, drawn on the device from ``seed`` in the
    configuration's dtype. Norm weights multiply (``x * w``)."""
    sz = tuple(sorted(sizes(conf).items()))
    return _canonical(key_from_seed(seed), sz, conf["torch_dtype"])


def to_program(w: dict, conf: dict) -> dict:
    """The program's parameter tree for canonical weights ``w``. The
    program's norms scale by ``1 + scale``; ``w - 1`` is exact in bf16 for
    weights in [0.5, 2)."""
    sz = sizes(conf)
    L, d, H, Hkv, D = (sz[k] for k in
                       ("layers", "d", "heads", "kv_heads", "head_dim"))

    @jax.jit
    def convert(w):
        def minus_one(x):
            return (x.astype(jnp.float32) - 1.0).astype(x.dtype)

        return {
            "embed": {"table": w["embed"]},
            "final_norm": {"scale": minus_one(w["final_norm"])},
            "units": ({
                "norm_mix": {"scale": minus_one(w["attn_norm"])},
                "mix": {
                    "wq": w["wq"].reshape(L, d, H, D),
                    "wk": w["wk"].reshape(L, d, Hkv, D),
                    "wv": w["wv"].reshape(L, d, Hkv, D),
                    "wo": w["wo"].reshape(L, H, D, d),
                    "bq": w["bq"].reshape(L, H, D),
                    "bk": w["bk"].reshape(L, Hkv, D),
                    "bv": w["bv"].reshape(L, Hkv, D),
                },
                "norm_ffn": {"scale": minus_one(w["mlp_norm"])},
                "ffn": {"w_gate": w["w_gate"], "w_up": w["w_up"],
                        "w_down": w["w_down"]},
            },),
        }

    return convert(w)


def check_program_layout(params, cfg):
    """Raise if ``params`` does not match the tree ``init_model`` builds
    (shapes and dtypes), so that a changed program layout fails loudly."""
    from repro.models.api import init_model

    want = jax.eval_shape(lambda k: init_model(k, cfg), jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError(f"the program's parameter layout changed: expected "
                         f"{want}, built {got}")
