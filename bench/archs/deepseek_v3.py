"""Architecture ``deepseek_v3``: DeepSeek-V3's decoder as Moonlight-16B-A3B
publishes it (pre-norm RMSNorm; latent attention, MLA, with a plain query
projection, ``q_lora_rank`` null; rotary embeddings on split halves; leading
dense SwiGLU layers, then MoE layers of routed experts under the sigmoid
``noaux_tc`` router beside shared experts; untied output head), at one
chip's share of an expert-parallel deployment, as the benchmark needs it.

The configuration file keeps the published numbers under their Hugging
Face names, except ``n_routed_experts``: the routed experts this chip holds
(``reduced``), the first ``n_routed_experts`` of the
``deployment.n_routed_experts`` the router scores. The router keeps its
published width and top-k; the chip adds its own experts' part of the
result, and the reference is given the same share.

Found by the configuration file's ``model_type`` (``bench/spec.py``); the
functions are those of ``bench/archs/qwen2.py``, plus
``expert_kernel_cost(sz, rows)``, the work of one MoE layer's grouped
expert matmuls (``expert_gmm``) over a step's rows.

The weights are laid out as the program's parameters are, layer-stacked
(``dense`` for the leading dense layers, ``moe`` for the rest), so that
``to_program`` re-nests the same arrays and copies nothing but the norms:
the weights are 6.7 GB of a 16 GB chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.costs import attn_pairs
from bench.model import key_from_seed


def sizes(conf: dict) -> dict:
    """The shape numbers the benchmark's own arithmetic uses."""
    return {
        "layers": conf["num_hidden_layers"],
        "dense_layers": conf["first_k_dense_replace"],
        "d": conf["hidden_size"], "heads": conf["num_attention_heads"],
        "rank": conf["kv_lora_rank"], "rope": conf["qk_rope_head_dim"],
        "nope": conf["qk_nope_head_dim"], "v": conf["v_head_dim"],
        "ffn": conf["intermediate_size"],
        "expert_ffn": conf["moe_intermediate_size"],
        "experts": conf["deployment"]["n_routed_experts"],
        "held": conf["n_routed_experts"], "top_k": conf["num_experts_per_tok"],
        "shared": conf["n_shared_experts"],
        "scaling": float(conf["routed_scaling_factor"]),
        "vocab": conf["vocab_size"],
        "rope_theta": float(conf["rope_theta"]),
        "eps": float(conf["rms_norm_eps"]),
    }


def program_config(conf: dict):
    """The repro ``ModelConfig`` for a configuration file: the registry's
    architecture with every size taken from the file and the held experts
    [0, n_routed_experts). Raises for what the reference does not
    compute."""
    from repro.configs import MLAConfig, MoEConfig, get_config

    expect = {"hidden_act": "silu", "q_lora_rank": None, "n_group": 1,
              "topk_group": 1, "topk_method": "noaux_tc",
              "scoring_func": "sigmoid", "norm_topk_prob": True,
              "moe_layer_freq": 1, "attention_bias": False,
              "tie_word_embeddings": False, "rope_scaling": None}
    wrong = {k: conf.get(k) for k, v in expect.items() if conf.get(k) != v}
    if wrong:
        raise ValueError(f"{conf['arch']}: not the DeepSeek-V3 decoder the "
                         f"reference computes: {wrong}")
    sz = sizes(conf)
    cfg = get_config(conf["arch"])
    return cfg.replace(
        num_layers=sz["layers"], first_dense_layers=sz["dense_layers"],
        d_model=sz["d"], num_heads=sz["heads"], num_kv_heads=sz["heads"],
        d_ff=sz["ffn"], vocab_size=sz["vocab"], rope_base=sz["rope_theta"],
        norm_eps=sz["eps"], tie_embeddings=False,
        attention_variant=conf["attention_variant"],
        dtype=conf["torch_dtype"], param_dtype=conf["torch_dtype"],
        mla=MLAConfig(q_lora_rank=None, kv_lora_rank=sz["rank"],
                      qk_rope_dim=sz["rope"], qk_nope_dim=sz["nope"],
                      v_head_dim=sz["v"]),
        moe=MoEConfig(num_experts=sz["experts"], top_k=sz["top_k"],
                      d_ff=sz["expert_ffn"], router="sigmoid",
                      routed_scaling_factor=sz["scaling"],
                      num_shared_experts=sz["shared"],
                      held_experts=(0, sz["held"])))


@functools.partial(jax.jit, static_argnames=("sz", "dtype"))
def _canonical(key, sz, dtype):
    sz = dict(sz)
    d, H, R, P, N, Vh, V = (sz[k] for k in (
        "d", "heads", "rank", "rope", "nope", "v", "vocab"))
    ks = iter(jax.random.split(key, 40))

    def normal(shape, std, dt=dtype):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * std).astype(dt)

    def ones_ish(shape):
        return (1.0 + 0.05 * jax.random.normal(next(ks), shape,
                                               jnp.float32)).astype(dtype)

    def attention(L):
        return {
            "attn_norm": ones_ish((L, d)),
            "wq": normal((L, d, H, N + P), d ** -0.5),
            "wkv_a": normal((L, d, R + P), d ** -0.5),
            "kv_norm": ones_ish((L, R)),
            "wkv_b": normal((L, R, H, N + Vh), R ** -0.5),
            "wo": normal((L, H, Vh, d), (H * Vh) ** -0.5),
            "mlp_norm": ones_ish((L, d)),
        }

    def mlp(prefix, lead, F):
        return {prefix + "gate": normal((*lead, d, F), d ** -0.5),
                prefix + "up": normal((*lead, d, F), d ** -0.5),
                prefix + "down": normal((*lead, F, d), F ** -0.5)}

    Ld, Lm = sz["dense_layers"], sz["layers"] - sz["dense_layers"]
    F, E = sz["expert_ffn"], sz["held"]
    return {
        "embed": normal((V, d), 0.02),
        "lm_head": normal((d, V), d ** -0.5),
        "final_norm": ones_ish((d,)),
        "dense": {**attention(Ld), **mlp("w_", (Ld,), sz["ffn"])},
        "moe": {
            **attention(Lm),
            "router": normal((Lm, d, sz["experts"]), d ** -0.5),
            # the correction bias moves the choice of experts, not their
            # weights; trained values are small, so a small draw
            "e_bias": normal((Lm, sz["experts"]), 0.02, jnp.float32),
            **mlp("w_", (Lm, E), F),
            **mlp("shared_", (Lm,), sz["shared"] * F),
        },
    }


def canonical_weights(seed: int, conf: dict):
    """The cell's weights, drawn on the device from ``seed`` in the
    configuration's dtype (the correction bias in float32, as published).
    Only the held experts are drawn. Norm weights multiply (``x * w``)."""
    sz = tuple(sorted(sizes(conf).items()))
    return _canonical(key_from_seed(seed), sz, conf["torch_dtype"])


def to_program(w: dict, conf: dict) -> dict:
    """The program's parameter tree for canonical weights ``w``: the same
    arrays, re-nested. The program's norms scale by ``1 + scale``; ``w -
    1`` is exact in bf16 for weights in [0.5, 2)."""
    @jax.jit
    def minus_one(x):
        return (x.astype(jnp.float32) - 1.0).astype(x.dtype)

    def block(s, ffn):
        return {
            "norm_mix": {"scale": minus_one(s["attn_norm"])},
            "mix": {"w_q": s["wq"], "w_dkv": s["wkv_a"],
                    "kv_norm": {"scale": minus_one(s["kv_norm"])},
                    "w_ukv": s["wkv_b"], "wo": s["wo"]},
            "norm_ffn": {"scale": minus_one(s["mlp_norm"])},
            "ffn": ffn,
        }

    def mlp(s, prefix):
        return {"w_gate": s[prefix + "gate"], "w_up": s[prefix + "up"],
                "w_down": s[prefix + "down"]}

    m = w["moe"]
    return {
        "embed": {"table": w["embed"], "out": w["lm_head"]},
        "final_norm": {"scale": minus_one(w["final_norm"])},
        "lead": (block(w["dense"], mlp(w["dense"], "w_")),),
        "units": (block(m, {"router": m["router"], "e_bias": m["e_bias"],
                            **mlp(m, "w_"),
                            "shared": mlp(m, "shared_")}),),
    }


def _expected_held_rows(sz: dict, tokens: int) -> float:
    """Routed rows a step's ``tokens`` bring to the held experts: k of the
    router's experts per token, a held share of them."""
    return tokens * sz["top_k"] * sz["held"] / sz["experts"]


def _attention_params(sz: dict) -> int:
    d, H, R, P, N, Vh = (sz[k] for k in ("d", "heads", "rank", "rope",
                                        "nope", "v"))
    return d * H * (N + P) + d * (R + P) + R + R * H * (N + Vh) \
        + H * Vh * d + 2 * d


def step_flops(sz: dict, rows) -> int:
    """Useful model operations of one step, as served: 2 per parameter per
    new token (each layer's attention projections and norms; the dense
    layer's MLP; each MoE layer's router and shared experts, and its held
    experts for the routed rows expected on them), the absorbed attention's
    score and value products over the 576-wide latent per causal pair, and
    the output head per sampled row. Padding, idle rows and logits that
    were thrown away are not work."""
    d, L, Ld = sz["d"], sz["layers"], sz["dense_layers"]
    F = sz["expert_ffn"]
    per_tok = L * _attention_params(sz) + Ld * 3 * d * sz["ffn"] \
        + (L - Ld) * (d * sz["experts"] + 3 * d * sz["shared"] * F) + d
    routed = (L - Ld) * 3 * d * F                # per row on a held expert
    attn = 2 * sz["heads"] * (2 * sz["rank"] + sz["rope"]) * L
    head = 2 * d * sz["vocab"]
    tokens = sum(n for _, n, _ in rows)
    return round(2 * per_tok * tokens
                 + 2 * routed * _expected_held_rows(sz, tokens)
                 + sum(attn * attn_pairs(Lc, n) + (head if s else 0)
                       for Lc, n, s in rows))


def kv_token_bytes(sz: dict, kv_dtype: str) -> int:
    """Bytes of one token's latent row for one layer: kv_lora_rank + rope
    values, V read inside them. Only the bfloat16 cache the cell serves is
    counted."""
    if kv_dtype != "bfloat16":
        raise ValueError(f"no byte count for a {kv_dtype!r} cache")
    return (sz["rank"] + sz["rope"]) * 2


def attention_kernel_cost(sz: dict, rows, kv_dtype: str,
                          act_bytes: int = 2) -> tuple[int, int]:
    """(operations, bytes) of one layer's absorbed latent attention kernel
    call over ``rows``: each head's score over Dk = rank + rope and value
    product over Dv = rank per causal pair; the absorbed queries read and
    the latent outputs written once; every cached and new token's latent
    row read once (one page read serves K and V)."""
    H, Dk, Dv = sz["heads"], sz["rank"] + sz["rope"], sz["rank"]
    flops = sum(2 * H * (Dk + Dv) * attn_pairs(L, n) for L, n, _ in rows)
    row = kv_token_bytes(sz, kv_dtype)
    nbytes = sum(n * H * (Dk + Dv) * act_bytes + (L + n) * row
                 for L, n, _ in rows)
    return flops, nbytes


def _expected_reached(sz: dict, tokens: int) -> float:
    """Held experts a step of ``tokens`` reaches when each token's k
    experts fall among the router's E alike: held x (1 - (1 - k/E)^T)."""
    return sz["held"] * (1 - (1 - sz["top_k"] / sz["experts"]) ** tokens)


def expert_kernel_cost(sz: dict, rows,
                       act_bytes: int = 2) -> tuple[float, float]:
    """(operations, bytes) of one MoE layer's grouped expert matmuls over a
    step's ``rows``: the gate, up and down products of the T·k·held/E rows
    a step of T tokens is expected to bring to the held experts, each such
    row read in and its output written once, and the three weights of
    each held expert the step is expected to reach, read once. Routing
    reaches the held experts as uniform routing would: on a TPU v5e a
    32-row decode tick's grouped matmul found 7.53-7.60 of 8 held experts
    with rows (uniform: 7.66) and 23.8-24.9 routed rows on them (24),
    three seeds (PERF.md)."""
    d, F = sz["d"], sz["expert_ffn"]
    tokens = sum(n for _, n, _ in rows)
    routed = _expected_held_rows(sz, tokens)
    flops = 2 * routed * 3 * d * F
    nbytes = _expected_reached(sz, tokens) * 3 * d * F * act_bytes \
        + 2 * routed * d * act_bytes
    return flops, nbytes
