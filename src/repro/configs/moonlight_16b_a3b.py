"""moonlight-16b-a3b [moe]: DeepSeek-V3 blocks, latent attention, 64 experts.

27L d_model=2048 16H, MLA (kv_lora_rank 512, q no low rank, qk 128+64, v 128),
layer 0 dense (d_ff 11264), layers 1-26: 64 routed experts of width 1408,
top-6 by sigmoid score + selection bias, normalised, x2.446, plus 2 shared
experts; vocab 163840, untied  [hf:moonshotai/Moonlight-16B-A3B]
"""
from repro.configs.base import ModelConfig, register

MOONLIGHT = register(
    ModelConfig(
        name="moonlight-16b-a3b",
        family="moe",
        num_layers=27,
        d_model=2048,
        num_heads=16,
        num_kv_heads=16,
        d_ff=11264,
        vocab_size=163840,
        act="swiglu",
        rope_theta=50_000.0,
        norm_eps=1e-5,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        num_experts=64,
        experts_per_token=6,
        moe_d_ff=1408,
        first_k_dense=1,
        num_shared_experts=2,
        router_scoring="sigmoid",
        router_bias=True,
        routed_scale=2.446,
        notes="EP: 16 of the 64 experts per chip on a 4-chip host (held_experts=(16 * i, 16))",
    )
)
