"""Phi-4-mini-flash-reasoning (``model_type: phi4flash``, the SambaY
decoder-hybrid-decoder): 4 n layers in a fixed pattern of five kinds —
n (Mamba-1, window attention) pairs, one (Mamba-1, full attention) pair,
n - 1 (gated memory unit, cross attention) pairs — LayerNorm with bias, no
positional term, differential attention, SwiGLU MLPs, a tied embedding. The
program's ``ModelConfig.layer_types`` holds the pattern
(``models.config.sambay_layer_types``). Keys the program would have to model
beyond these raise: a silent default under a real model's name would be a
guess. A program from before its ``ModelConfig`` had these fields fails
here, at once.

The four ``mamba_*`` sizes are not in the published config.json (the
configuration file's ``assumed``); ``mamba_expand`` times ``hidden_size`` is
the mixer's inner width. Keys with no effect on the forward are checked for
the value they have to have: ``embd_pdrop`` and ``resid_pdrop`` 0,
``mlp_bias`` and ``lm_head_bias`` false."""

from __future__ import annotations


def model_config(cfg: dict):
    import jax.numpy as jnp
    from senweaver_ide_tpu.models.config import (ModelConfig,
                                                 sambay_layer_types)
    unmapped = [
        k for k, ok in (
            ("hidden_act", cfg["hidden_act"] == "silu"),
            ("mb_per_layer", cfg["mb_per_layer"] == 2),
            ("num_hidden_layers", cfg["num_hidden_layers"] % 4 == 0),
            ("tie_word_embeddings", cfg["tie_word_embeddings"] is True),
            ("mlp_bias", cfg["mlp_bias"] is False),
            ("lm_head_bias", cfg["lm_head_bias"] is False),
            ("embd_pdrop", cfg["embd_pdrop"] == 0),
            ("resid_pdrop", cfg["resid_pdrop"] == 0),
            ("num_key_value_heads", cfg["num_key_value_heads"] % 2 == 0
             and cfg["num_attention_heads"]
             % cfg["num_key_value_heads"] == 0),
            ("hidden_size",
             cfg["hidden_size"] % cfg["num_attention_heads"] == 0))
        if not ok]
    if unmapped:
        raise SystemExit(f"benchmark: {cfg['name']}: {unmapped} as set are "
                         f"not mapped by archs/phi4flash.py")
    return ModelConfig(
        name=cfg["name"], vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        max_seq_len=cfg["max_position_embeddings"],
        rms_norm_eps=float(cfg["layer_norm_eps"]),
        tie_word_embeddings=True,
        dtype={"bfloat16": jnp.bfloat16,
               "float32": jnp.float32}[cfg["torch_dtype"]],
        matmul_precision=cfg.get("matmul_precision"),
        layer_types=sambay_layer_types(cfg["num_hidden_layers"]),
        layer_window=cfg["sliding_window"],
        mamba_d_ssm=cfg["mamba_expand"] * cfg["hidden_size"],
        mamba_d_state=cfg["mamba_d_state"], mamba_d_conv=cfg["mamba_d_conv"],
        mamba_dt_rank=cfg["mamba_dt_rank"],
        norm="layer", diff_attn=True)
