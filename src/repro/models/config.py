"""Architecture configuration: one frozen dataclass drives the whole stack."""
from __future__ import annotations

import dataclasses


def _pad_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int  # query heads; 0 for attention-free archs
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    # --- MoE ---
    n_experts: int = 0  # router outputs: every expert of the layer
    experts_per_token: int = 0
    moe_d_ff: int = 0  # per-expert FFN hidden (d_ff used if 0)
    capacity_factor: float = 1.25  # training layer; serving is dropless
    n_local_experts: int = 0  # experts held here, [0, n); 0 = all
    n_shared_experts: int = 0  # always-on experts, one SwiGLU of n * moe_d_ff
    first_dense_layers: int = 0  # leading layers with a dense d_ff MLP
    router_scoring: str = "softmax"  # softmax | sigmoid (DeepSeek-V3's
    #   noaux_tc: top-k by score + a selection bias, gates from the score)
    routed_scale: float = 1.0  # factor on the routed experts' gates

    # --- attention kind ---
    attn_kind: str = "gqa"  # gqa | mla (latent attention, DeepSeek-V2)
    kv_lora_rank: int = 0  # mla: width of the cached latent c_kv
    qk_nope_dim: int = 0  # mla: per-head query/key width without RoPE
    qk_rope_dim: int = 0  # mla: width of the one rotary key all heads share
    v_head_dim: int = 0  # mla: per-head value width

    # --- attention pattern ---
    window: int = 0  # sliding-window size; 0 = full attention
    layer_pattern: str = "attn"  # attn | ssm | griffin (rec,rec,attn periods)
    local_window: int = 2048  # griffin local-attention window
    encoder_only: bool = False  # bidirectional, no decode step
    causal: bool = True

    # --- SSM (mamba2 SSD) ---
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    conv_width: int = 4

    # --- recurrent (RG-LRU) ---
    lru_width: int = 0  # 0 -> d_model

    # --- frontend stubs (audio/vlm): input_specs provide embeddings ---
    frontend: str = "none"  # none | audio_stub | vision_stub
    n_prefix_tokens: int = 0  # vlm: number of (bidirectional) image tokens

    # --- misc ---
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    use_rope: bool = True
    tie_embeddings: bool = False

    # --- amortized head (the paper's technique) ---
    head_mode: str = "amortized"  # exact | topk_only | amortized
    head_mips: str = "exact"  # exact | ivf | ivfpq | lsh
    head_delta: float = 1e-4
    head_k: int = 0  # 0 -> default_kl(vocab, head_delta)
    head_l: int = 0
    head_use_kernel: bool = False  # Pallas probe/estimator kernels
    head_fused_decode: bool = False  # single-dispatch fused decode step
    #   (kernels/decode_fused.py); bit-identical samples to the unfused
    #   kernel path — see DESIGN.md §10
    head_n_probe: int = 8  # IVF/IVF-PQ clusters probed per query
    head_adaptive_probe: bool = False  # certificate-gated staged widening:
    #   probe head_n_probe_init clusters, widen geometrically (per token)
    #   up to head_n_probe_max only when the gap certificate fails —
    #   DESIGN.md §11
    head_n_probe_init: int = 0  # 0 -> head_n_probe
    head_n_probe_max: int = 0  # 0 -> head_n_probe

    # ------------------------------------------------------------------ #
    @property
    def vocab_padded(self) -> int:
        return _pad_to(self.vocab, 256)

    @property
    def d_attn(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def d_kv(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def d_inner(self) -> int:  # mamba2
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def lru_dim(self) -> int:
        return self.lru_width or self.d_model

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def experts_held(self) -> int:
        return self.n_local_experts or self.n_experts

    @property
    def latent_width(self) -> int:  # mla: [c_kv | k_pe], r + rope values
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def sub_quadratic(self) -> bool:
        """True if the arch supports long-context (500k) decode."""
        return self.layer_pattern in ("ssm", "griffin") or self.window > 0

    @property
    def has_decode(self) -> bool:
        return not self.encoder_only

    def layer_kinds(self) -> list[str]:
        """Per-layer block kind, honoring the layer pattern."""
        if self.layer_pattern == "attn":
            return ["attn"] * self.n_layers
        if self.layer_pattern == "ssm":
            return ["ssm"] * self.n_layers
        if self.layer_pattern == "griffin":
            # (rec, rec, attn) repeating, truncated to n_layers
            kinds = []
            for i in range(self.n_layers):
                kinds.append("attn" if i % 3 == 2 else "rec")
            return kinds
        raise ValueError(self.layer_pattern)

    def mlp_kinds(self) -> list[str]:
        """Per-layer MLP kind: dense | moe | none (mamba blocks)."""
        if self.layer_pattern == "ssm":
            return ["none"] * self.n_layers
        if not self.is_moe:
            return ["dense"] * self.n_layers
        return ["dense" if i < self.first_dense_layers else "moe"
                for i in range(self.n_layers)]

    def scaled(self, **kw) -> "ArchConfig":
        """Reduced config of the same family (smoke tests)."""
        return dataclasses.replace(self, **kw)
