"""Qwen2 (``model_type: qwen2``): GQA, q/k/v biases, plain RoPE, dense
SwiGLU. Keys the program would have to model beyond these raise: a silent
default under a real model's name would be a guess."""

from __future__ import annotations


def model_config(cfg: dict):
    import jax.numpy as jnp
    from senweaver_ide_tpu.models.config import ModelConfig
    if cfg.get("rope_scaling") or cfg.get("use_sliding_window"):
        raise SystemExit(f"benchmark: {cfg['name']}: rope_scaling / sliding "
                         f"window are not mapped by archs/qwen2.py")
    heads = cfg["num_attention_heads"]
    return ModelConfig(
        name=cfg["name"], vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"], num_heads=heads,
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim", cfg["hidden_size"] // heads),
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        tie_word_embeddings=bool(cfg["tie_word_embeddings"]),
        qkv_bias=bool(cfg["attention_bias"]),
        dtype={"bfloat16": jnp.bfloat16,
               "float32": jnp.float32}[cfg["torch_dtype"]],
        matmul_precision=cfg.get("matmul_precision"))
