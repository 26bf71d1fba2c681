"""moonlight-16b-a3b [moe]: 27L d=2048, MLA 16H (kv_lora 512, nope 128,
rope 64, v 128), layer 0 dense d_ff=11264, layers 1-26 MoE 64e top-6 of
width 1408 with 2 shared, sigmoid router with selection bias (noaux_tc),
routed scale 2.446, vocab=163840.
[hf:moonshotai/Moonlight-16B-A3B, model_type deepseek_v3]"""
from repro.models.config import ArchConfig

CONFIG = ArchConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=192,  # qk_nope_head_dim + qk_rope_head_dim
    d_ff=11264,
    vocab=163840,
    n_experts=64,
    experts_per_token=6,
    moe_d_ff=1408,
    n_shared_experts=2,
    first_dense_layers=1,
    router_scoring="sigmoid",
    routed_scale=2.446,
    attn_kind="mla",
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    rope_theta=50000.0,
    norm_eps=1e-5,
)


def smoke() -> ArchConfig:
    return CONFIG.scaled(
        n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, head_dim=24,
        d_ff=128, moe_d_ff=32, vocab=512, n_experts=8, experts_per_token=2,
        kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
    )
