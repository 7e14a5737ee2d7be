"""Xing4.0-29B-A4B (``model_type: xing4_0``): GLM-4.7-Flash's blocks —
multi-head latent attention, ``first_k_dense_replace`` leading dense layers,
then ``n_routed_experts`` routed + ``n_shared_experts`` shared SwiGLU experts
under a sigmoid router with a correction bias (``topk_method: noaux_tc``, one
group) — with YaRN rotary scaling and a residual stream of ``hc_mult`` rows
mixed by Sinkhorn maps (``hc_sinkhorn_iters``, ``hc_eps``,
``mhc_h_res_clamp_min/max``). Keys the program would have to model beyond
these raise, a YaRN key it does not know among them: a silent default under
a real model's name would be a guess. A program from before its
``ModelConfig`` had these fields fails here, at once."""

from __future__ import annotations

YARN_KEYS = {"type", "factor", "original_max_position_embeddings",
             "beta_fast", "beta_slow", "mscale", "mscale_all_dim"}


def model_config(cfg: dict):
    import jax.numpy as jnp
    from senweaver_ide_tpu.models.config import ModelConfig, YarnScaling
    rs = cfg.get("rope_scaling") or {}
    unmapped = [
        k for k, ok in (
            ("rope_scaling", rs.get("type") == "yarn"
             and set(rs) == YARN_KEYS),
            ("n_group", cfg["n_group"] == 1),
            ("topk_group", cfg["topk_group"] == 1),
            ("topk_method", cfg["topk_method"] == "noaux_tc"),
            ("scoring_func", cfg["scoring_func"] == "sigmoid"),
            ("norm_topk_prob", cfg["norm_topk_prob"] is True),
            ("moe_layer_freq", cfg["moe_layer_freq"] == 1),
            ("ep_size", cfg["ep_size"] == 1),
            ("hidden_act", cfg.get("hidden_act", "silu") == "silu"),
            ("attention_bias", cfg["attention_bias"] is False),
            ("hc_mult", cfg["hc_mult"] >= 1),
            ("num_key_value_heads",
             cfg["num_key_value_heads"] == cfg["num_attention_heads"]))
        if not ok]
    if unmapped:
        raise SystemExit(f"benchmark: {cfg['name']}: {unmapped} as set are "
                         f"not mapped by archs/xing4_0.py")
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
        rope_scaling=YarnScaling(
            factor=float(rs["factor"]),
            original_max_position=rs["original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"]), mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
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
        v_head_dim=cfg["v_head_dim"],
        hc_mult=cfg["hc_mult"], hc_sinkhorn_iters=cfg["hc_sinkhorn_iters"],
        hc_eps=float(cfg["hc_eps"]),
        mhc_h_res_clamp_min=float(cfg["mhc_h_res_clamp_min"]),
        mhc_h_res_clamp_max=float(cfg["mhc_h_res_clamp_max"]))
