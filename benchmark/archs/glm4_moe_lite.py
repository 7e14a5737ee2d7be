"""GLM-4.7-Flash (``model_type: glm4_moe_lite``): multi-head latent
attention, ``first_k_dense_replace`` leading dense layers, then expert layers
of ``n_routed_experts`` routed + ``n_shared_experts`` shared SwiGLU experts
under a sigmoid router with a correction bias (``topk_method: noaux_tc``,
one group). Keys the program would have to model beyond these raise: a
silent default under a real model's name would be a guess. A program from
before its ``ModelConfig`` had these fields fails here, at once."""

from __future__ import annotations


def model_config(cfg: dict):
    import jax.numpy as jnp
    from senweaver_ide_tpu.models.config import ModelConfig
    unmapped = [
        k for k, ok in (
            ("rope_scaling", cfg.get("rope_scaling") is None),
            ("n_group", cfg["n_group"] == 1),
            ("topk_group", cfg["topk_group"] == 1),
            ("partial_rotary_factor", cfg["partial_rotary_factor"] == 1),
            ("topk_method", cfg["topk_method"] == "noaux_tc"),
            ("norm_topk_prob", cfg["norm_topk_prob"] is True),
            ("hidden_act", cfg.get("hidden_act", "silu") == "silu"),
            ("attention_bias", cfg["attention_bias"] is False),
            ("num_key_value_heads",
             cfg["num_key_value_heads"] == cfg["num_attention_heads"]))
        if not ok]
    if unmapped:
        raise SystemExit(f"benchmark: {cfg['name']}: {unmapped} as set are "
                         f"not mapped by archs/glm4_moe_lite.py")
    heads = cfg["num_attention_heads"]
    return ModelConfig(
        name=cfg["name"], vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"], num_heads=heads,
        num_kv_heads=heads,
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        tie_word_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype={"bfloat16": jnp.bfloat16,
               "float32": jnp.float32}[cfg["torch_dtype"]],
        matmul_precision=cfg.get("matmul_precision"),
        num_experts=cfg["n_routed_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_shared_experts=cfg["n_shared_experts"],
        first_dense_layers=cfg["first_k_dense_replace"],
        router_type="sigmoid_bias",
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        kv_lora_rank=cfg["kv_lora_rank"], q_lora_rank=cfg["q_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"])
