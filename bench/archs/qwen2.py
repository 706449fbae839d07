"""Architecture ``qwen2``: the dense decoder of Qwen2 and Qwen1.5 (pre-norm
RMSNorm, rotary embeddings, grouped-query attention with QKV bias, SwiGLU,
tied embeddings), as the benchmark needs it.

Found by the configuration file's ``model_type`` (``bench/spec.py``):

  sizes(conf)                   the shape numbers the benchmark's
                                arithmetic uses (``layers``, ``vocab``
                                and whatever the rest needs)
  program_config(conf)          the program's ``ModelConfig``; raises for
                                what the reference does not compute
  canonical_weights(seed, conf) weights drawn from the seed on the device,
                                in the layout the reference reads
  to_program(w, conf)           the same arrays in the program's layout
  step_flops(sz, rows)          useful operations of one serving step
  attention_kernel_cost(sz, rows, kv_dtype)
                                (operations, bytes) of one layer's
                                attention kernel call

A step is described by its rows, one ``(L, n, sampled)`` per sequence
(``bench/costs.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.costs import attn_pairs
from bench.model import key_from_seed

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


def nonembed_params(sz: dict) -> int:
    """Parameters outside the embedding (tied, so also the LM head)."""
    d, H, Hkv, D, F = (sz[k] for k in ("d", "heads", "kv_heads", "head_dim",
                                        "ffn"))
    attn = d * H * D + 2 * d * Hkv * D + H * D * d + (H + 2 * Hkv) * D
    return sz["layers"] * (attn + 3 * d * F + 2 * d) + d


def step_flops(sz: dict, rows) -> int:
    """Useful model operations of one step: 2 per parameter per new token,
    the attention's QK and PV products, and the LM head per sampled row.
    Padding, idle rows and logits that were thrown away are not work."""
    per_tok = 2 * nonembed_params(sz)
    attn = 4 * sz["heads"] * sz["head_dim"] * sz["layers"]
    head = 2 * sz["d"] * sz["vocab"]
    return sum(per_tok * n + attn * attn_pairs(L, n) + (head if s else 0)
               for L, n, s in rows)


def kv_token_bytes(sz: dict, kv_dtype: str) -> int:
    """Bytes of one token's K and V for one layer. Only the bfloat16 cache
    the cells serve is counted; a quantized cache comes with its count."""
    if kv_dtype != "bfloat16":
        raise ValueError(f"no byte count for a {kv_dtype!r} cache")
    return 2 * sz["kv_heads"] * sz["head_dim"] * 2


def attention_kernel_cost(sz: dict, rows, kv_dtype: str,
                          act_bytes: int = 2) -> tuple[int, int]:
    """(operations, bytes) of one layer's attention kernel call over
    ``rows``: Q read and O written once, every cached and new K/V token
    read once at the cache's element size, causal pairs counted exactly."""
    H, D = sz["heads"], sz["head_dim"]
    flops = sum(4 * H * D * attn_pairs(L, n) for L, n, _ in rows)
    kvb = kv_token_bytes(sz, kv_dtype)
    nbytes = sum(2 * n * H * D * act_bytes + (L + n) * kvb
                 for L, n, _ in rows)
    return flops, nbytes
