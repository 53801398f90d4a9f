"""The program's model for a DeepSeek-V3 configuration file (Moonlight).

Maps the published config keys onto the program's ``ModelConfig`` (latent
attention, a leading dense layer, shared experts, sigmoid routing with a
selection bias, the experts this chip holds) and builds it with the
program's own ``build_model``; the weights come from
``references/deepseek_v3.program_params``.
"""
from __future__ import annotations

from repro.configs.base import ModelConfig
from repro.models.model_zoo import build_model


def model(config: dict):
    experts = int(config.get("n_routed_experts_published", config["n_routed_experts"]))
    first, end = config.get("experts_held", [0, experts])
    cfg = ModelConfig(
        name=config["name"],
        family="moe",
        num_layers=int(config["num_hidden_layers"]),
        d_model=int(config["hidden_size"]),
        num_heads=int(config["num_attention_heads"]),
        num_kv_heads=int(config["num_key_value_heads"]),
        d_ff=int(config["intermediate_size"]),
        vocab_size=int(config["vocab_size"]),
        act="swiglu",
        norm="rmsnorm",
        norm_eps=float(config["rms_norm_eps"]),
        rope_theta=float(config["rope_theta"]),
        tie_embeddings=bool(config.get("tie_word_embeddings", False)),
        kv_lora_rank=int(config["kv_lora_rank"]),
        qk_nope_head_dim=int(config["qk_nope_head_dim"]),
        qk_rope_head_dim=int(config["qk_rope_head_dim"]),
        v_head_dim=int(config["v_head_dim"]),
        num_experts=experts,
        experts_per_token=int(config["num_experts_per_tok"]),
        moe_d_ff=int(config["moe_intermediate_size"]),
        first_k_dense=int(config["first_k_dense_replace"]),
        num_shared_experts=int(config["n_shared_experts"]),
        router_scoring=config["scoring_func"],
        router_bias=config["topk_method"] == "noaux_tc",
        routed_scale=float(config["routed_scaling_factor"]),
        held_experts=(int(first), int(end) - int(first)),
    )
    return build_model(cfg)
