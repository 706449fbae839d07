"""moonlight-16b-a3b [moe]: 27L d_model=2048 16H vocab=163840 — DeepSeek-V3
layers: latent attention (kv_lora_rank 512, rope 64, nope 128, v 128, no q
compression), one leading dense SwiGLU layer (d_ff 11264), then 26 MoE
layers of 64 routed experts (width 1408, top-6, sigmoid noaux_tc router
with a correction bias, normalised top-k times 2.446) beside 2 shared
experts. [hf:moonshotai/Moonlight-16B-A3B config.json]"""
from repro.configs.base import MLAConfig, MoEConfig, ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    first_dense_layers=1,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,             # MLA: every head reads the shared latent
    d_ff=11264,                  # the leading dense layer
    vocab_size=163840,
    activation="swiglu",
    norm_eps=1e-5,
    rope_base=50000.0,
    mla=MLAConfig(
        q_lora_rank=None,
        kv_lora_rank=512,
        qk_rope_dim=64,
        qk_nope_dim=128,
        v_head_dim=128,
    ),
    moe=MoEConfig(
        num_experts=64,
        top_k=6,
        d_ff=1408,
        router="sigmoid",
        routed_scaling_factor=2.446,
        num_shared_experts=2,
    ),
    tie_embeddings=False,
    max_seq_len=8192,
)

# the same shape at a size the CPU tests run: a plain q projection, one
# leading dense layer, sigmoid routing over 8 experts with 2 shared
SMOKE = CONFIG.replace(
    num_layers=3, d_model=64, num_heads=4, num_kv_heads=4, d_ff=96,
    vocab_size=256, max_seq_len=256,
    mla=MLAConfig(q_lora_rank=None, kv_lora_rank=32, qk_rope_dim=8,
                  qk_nope_dim=16, v_head_dim=16),
    moe=MoEConfig(num_experts=8, top_k=3, d_ff=32, router="sigmoid",
                  routed_scaling_factor=2.446, num_shared_experts=2),
)
