"""Model configuration shared by all 10 assigned architectures.

One dataclass covers the union of features (dense / GQA / MLA / MoE /
RG-LRU hybrid / xLSTM / enc-dec / modality frontends); each
``configs/<arch>.py`` instantiates it with the exact published numbers.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 0
    top_k: int = 0
    d_ff: int = 0                  # per-expert hidden size
    dense_residual: bool = False   # arctic: dense FFN in parallel with MoE
    dense_d_ff: int = 0            # hidden of the dense residual path
    capacity_factor: float = 1.25  # scatter/balanced only; dropless keeps all
    router_dtype: str = "float32"  # router softmax kept exact (DESIGN §4)
    # "softmax": softmax over the top-k logits. "sigmoid": DeepSeek-V3's
    # noaux_tc, sigmoid scores, top-k chosen on score + a per-expert
    # correction bias, weights the unbiased scores of the chosen experts
    # normalised over the k (DESIGN.md §14)
    router: str = "softmax"
    routed_scaling_factor: float = 1.0
    num_shared_experts: int = 0    # one SwiGLU of width num_shared * d_ff
    # the contiguous range [start, stop) of routed experts this layer holds
    # (one chip's share under expert parallelism); None holds all of them
    held_experts: tuple[int, int] | None = None

    @property
    def held_range(self) -> tuple[int, int]:
        return self.held_experts or (0, self.num_experts)

    @property
    def num_held(self) -> int:
        start, stop = self.held_range
        return stop - start


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (MiniCPM3 / DeepSeek-V2 family).
    ``q_lora_rank=None``: queries are a plain projection (DeepSeek-V2-Lite,
    Moonlight)."""
    q_lora_rank: int | None = 768
    kv_lora_rank: int = 256
    qk_rope_dim: int = 32
    qk_nope_dim: int = 64
    v_head_dim: int = 64


@dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"          # dense|moe|hybrid|ssm|encdec|vlm|audio
    num_layers: int = 2
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int | None = None    # default d_model // num_heads
    d_ff: int = 1024
    vocab_size: int = 1000
    activation: str = "swiglu"     # swiglu | geglu | gelu
    qkv_bias: bool = False
    norm: str = "rmsnorm"
    norm_eps: float = 1e-6
    tie_embeddings: bool = True
    scale_embeddings: bool = False  # gemma: embed * sqrt(d_model)
    rope_base: float = 10000.0
    max_seq_len: int = 8192

    moe: MoEConfig | None = None
    mla: MLAConfig | None = None

    # leading dense layers before the (MoE) stack: attention blocks whose
    # FFN is a dense SwiGLU of width d_ff (DeepSeek's first_k_dense_replace)
    first_dense_layers: int = 0

    # hybrid / ssm block patterns; unit repeats to fill num_layers
    block_pattern: tuple[str, ...] = ("attn",)   # attn|rglru|mlstm|slstm
    window: int | None = None                    # local attention window
    lru_width: int | None = None                 # rg-lru state width

    # enc-dec
    encoder_layers: int = 0
    decoder_layers: int = 0

    # modality frontend stub (embeddings are model inputs per assignment)
    frontend: str | None = None    # vision | audio
    frontend_tokens: int = 0
    frontend_dim: int = 0

    # numerics / the paper's technique — consumed via AttentionSpec.from_config
    attention_impl: str = "flash_jnp"      # ref | flash_jnp | pallas
    attention_variant: str = "expmul"      # exact | expmul  (paper default on)
    attention_block_q: int = 128
    attention_block_k: int = 512
    attention_q_chunks: int = 4            # causal block skipping (1 = off)
    attention_decode_impl: str | None = None   # None: derived from impl
    attention_prefill_impl: str | None = None  # None: follows impl family
    # None: follows impl — "pallas" selects the fused paged decode kernel
    # (in-kernel block tables, DESIGN.md §9), otherwise gather_xla
    attention_paged_impl: str | None = None

    # paged KV-cache serving defaults (DESIGN §7; engine args override)
    page_size: int = 16            # tokens per KV block
    pool_blocks: int = 0           # 0: engine fully provisions slots*max_len
    # KV-cache storage dtype (DESIGN §8): "fp32" = unquantized (cache in
    # cfg.dtype); "int8"/"fp8" store codes + per-row f32 scales and route
    # attention through the registry's fused-dequant ``*_q`` backends.
    # Attention-only decoder configs only (recurrent state and encoder
    # K/V are not KV caches — serve.engine.validate_kv_dtype rejects them).
    kv_dtype: str = "fp32"         # fp32 | int8 | fp8
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"
    opt_state_dtype: str = "float32"       # bf16 for the 1T-class models
    remat: bool = True
    scan_layers: bool = True
    logits_softcap: float | None = None

    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.num_heads

    @property
    def group_size(self) -> int:
        return self.num_heads // self.num_kv_heads

    def pattern_for(self, num_layers: int | None = None) -> tuple[str, ...]:
        n = num_layers if num_layers is not None else self.num_layers
        unit = self.block_pattern
        assert n % len(unit) == 0, (n, unit)
        return tuple(unit) * (n // len(unit))

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # -- parameter counting (for 6ND roofline + memory budgeting) -----------
    def param_count(self) -> int:
        return _param_count(self)

    def active_param_count(self) -> int:
        return _param_count(self, active_only=True)


def _attn_params(cfg: ModelConfig) -> int:
    d = cfg.d_model
    hd = cfg.resolved_head_dim()
    if cfg.mla is not None:
        m = cfg.mla
        qk_head = m.qk_nope_dim + m.qk_rope_dim
        if m.q_lora_rank is None:
            p = d * cfg.num_heads * qk_head
        else:
            p = d * m.q_lora_rank + m.q_lora_rank * cfg.num_heads * qk_head
        p += d * (m.kv_lora_rank + m.qk_rope_dim)
        p += m.kv_lora_rank * cfg.num_heads * (m.qk_nope_dim + m.v_head_dim)
        p += cfg.num_heads * m.v_head_dim * d
        return p
    q = d * cfg.num_heads * hd
    kv = 2 * d * cfg.num_kv_heads * hd
    o = cfg.num_heads * hd * d
    b = (cfg.num_heads + 2 * cfg.num_kv_heads) * hd if cfg.qkv_bias else 0
    return q + kv + o + b


def _ffn_params(cfg: ModelConfig, d_ff: int) -> int:
    mult = 3 if cfg.activation in ("swiglu", "geglu") else 2
    return mult * cfg.d_model * d_ff


def _block_params(cfg: ModelConfig, kind: str, active_only: bool,
                  dense: bool = False) -> int:
    d = cfg.d_model
    if kind == "attn":
        p = _attn_params(cfg)
        if cfg.moe is not None and not dense:
            m = cfg.moe
            k = m.top_k if active_only else m.num_experts
            p += k * _ffn_params(cfg, m.d_ff) + d * m.num_experts
            p += _ffn_params(cfg, m.num_shared_experts * m.d_ff)
            if m.dense_residual:
                p += _ffn_params(cfg, m.dense_d_ff)
        elif cfg.d_ff:
            p += _ffn_params(cfg, cfg.d_ff)
        return p + 2 * d
    if kind == "rglru":
        w = cfg.lru_width or d
        # in/out proj (2 branches) + conv4 + gates a/x + lambda + mlp norm
        p = 2 * d * w + 4 * w + 2 * w * w + 3 * w + w * d + 2 * d
        if cfg.d_ff:
            p += _ffn_params(cfg, cfg.d_ff)
        return p
    if kind == "mlstm":
        nh = cfg.num_heads
        inner = int(1.5 * d)
        inner -= inner % nh
        dh = inner // nh
        return 2 * d * inner + 3 * nh * dh * dh + 2 * inner * nh \
            + inner * d + 2 * d
    if kind == "slstm":
        nh = cfg.num_heads
        dh = d // nh
        f = int(4 / 3 * d)
        return 8 * nh * dh * dh + 3 * d * f + 2 * d
    raise ValueError(kind)


def _param_count(cfg: ModelConfig, active_only: bool = False) -> int:
    emb = cfg.vocab_size * cfg.d_model
    out = 0 if cfg.tie_embeddings else cfg.vocab_size * cfg.d_model
    total = emb + out + cfg.d_model  # final norm
    if cfg.encoder_layers:
        for kind in cfg.pattern_for(cfg.encoder_layers):
            total += _block_params(cfg, kind, active_only)
        for _ in range(cfg.decoder_layers):
            total += _block_params(cfg, "attn", active_only) + _attn_params(cfg) + cfg.d_model
        return total
    for i, kind in enumerate(cfg.pattern_for()):
        total += _block_params(cfg, kind, active_only,
                               dense=i < cfg.first_dense_layers)
    if cfg.frontend:
        total += cfg.frontend_dim * cfg.d_model
    return total
