"""Falcon-H1 (``model_type: falcon_h1``): in every block a Mamba-2
state-space mixer (``mamba_d_ssm`` = ``mamba_n_heads`` x ``mamba_d_head``
inner values, state ``mamba_d_state``, ``mamba_n_groups`` groups, a conv of
``mamba_d_conv`` taps with bias, gated grouped RMSNorm with the gate first)
in parallel with GQA attention (plain RoPE) on one normed input, a SwiGLU
MLP, untied embedding and head, and twelve muP multipliers. Keys the
program would have to model beyond these raise: a silent default under a
real model's name would be a guess. A program from before its
``ModelConfig`` had these fields fails here, at once.

Keys with no effect on the forward are checked for the value they have to
have and otherwise not read: ``mamba_expand`` (``mamba_d_ssm`` is given),
``mlp_expansion_factor`` (``intermediate_size`` is given),
``mamba_chunk_size`` (a tile of the published kernel: the recurrence is
exact under any chunking), ``num_logits_to_keep``."""

from __future__ import annotations


def model_config(cfg: dict):
    import jax.numpy as jnp
    from senweaver_ide_tpu.models.config import ModelConfig
    unmapped = [
        k for k, ok in (
            ("attn_layer_indices", cfg["attn_layer_indices"] is None),
            ("rope_scaling", cfg["rope_scaling"] is None),
            ("attention_bias", cfg["attention_bias"] is False),
            ("mlp_bias", cfg["mlp_bias"] is False),
            ("projectors_bias", cfg["projectors_bias"] is False),
            ("mamba_proj_bias", cfg["mamba_proj_bias"] is False),
            ("mamba_conv_bias", cfg["mamba_conv_bias"] is True),
            ("mamba_rms_norm", cfg["mamba_rms_norm"] is True),
            ("mamba_norm_before_gate",
             cfg["mamba_norm_before_gate"] is False),
            ("mamba_use_mlp", cfg["mamba_use_mlp"] is True),
            ("hidden_act", cfg["hidden_act"] == "silu"),
            ("mamba_d_ssm", cfg["mamba_d_ssm"]
             == cfg["mamba_n_heads"] * cfg["mamba_d_head"]),
            ("ssm_multipliers", len(cfg["ssm_multipliers"]) == 5),
            ("mlp_multipliers", len(cfg["mlp_multipliers"]) == 2))
        if not ok]
    if unmapped:
        raise SystemExit(f"benchmark: {cfg['name']}: {unmapped} as set are "
                         f"not mapped by archs/falcon_h1.py")
    return ModelConfig(
        name=cfg["name"], vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        tie_word_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype={"bfloat16": jnp.bfloat16,
               "float32": jnp.float32}[cfg["torch_dtype"]],
        matmul_precision=cfg.get("matmul_precision"),
        mamba_d_ssm=cfg["mamba_d_ssm"], mamba_n_heads=cfg["mamba_n_heads"],
        mamba_d_head=cfg["mamba_d_head"],
        mamba_d_state=cfg["mamba_d_state"],
        mamba_n_groups=cfg["mamba_n_groups"],
        mamba_d_conv=cfg["mamba_d_conv"],
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        lm_head_multiplier=float(cfg["lm_head_multiplier"]),
        attention_in_multiplier=float(cfg["attention_in_multiplier"]),
        attention_out_multiplier=float(cfg["attention_out_multiplier"]),
        key_multiplier=float(cfg["key_multiplier"]),
        ssm_in_multiplier=float(cfg["ssm_in_multiplier"]),
        ssm_out_multiplier=float(cfg["ssm_out_multiplier"]),
        ssm_multipliers=tuple(float(m) for m in cfg["ssm_multipliers"]),
        mlp_multipliers=tuple(float(m) for m in cfg["mlp_multipliers"]))
