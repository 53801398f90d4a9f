"""The program's model for a dense transformer configuration file.

Maps the published config keys onto the program's ``ModelConfig`` and
builds it with the program's own ``build_model``; the weights come from
``references/dense_transformer.program_params`` (made from the seed, laid
out as the program's parameter tree).
"""
from __future__ import annotations

from repro.configs.base import ModelConfig
from repro.models.model_zoo import build_model


def model(config: dict):
    d, hq = int(config["hidden_size"]), int(config["num_attention_heads"])
    cfg = ModelConfig(
        name=config["name"],
        family="dense",
        num_layers=int(config["num_hidden_layers"]),
        d_model=d,
        num_heads=hq,
        num_kv_heads=int(config["num_key_value_heads"]),
        d_ff=int(config["intermediate_size"]),
        vocab_size=int(config["vocab_size"]),
        d_head=int(config.get("head_dim") or d // hq),
        qkv_bias=config.get("model_type") == "qwen2" or bool(config.get("attention_bias", False)),
        act="swiglu",
        norm="rmsnorm",
        rope_theta=float(config["rope_theta"]),
        tie_embeddings=bool(config.get("tie_word_embeddings", False)),
    )
    return build_model(cfg)
