"""The fused paged step of a hybrid model whose every block holds a Mamba-2
state-space mixer beside GQA attention (``falcon_h1``): decode rows and
prefill chunks in one forward.

Least work for one step that processes ``tokens`` new tokens, samples
``sampled`` of them, whose decoding rows hold ``contexts`` tokens of KV,
and that advanced the recurrent state of ``ssm_rows`` rows (the step's own
count, attr ``ssm_rows`` of its ``engine.step`` span):

bytes: every layer's weights once (attention, the mixer's two projections,
    the MLP); the output head once and ``tokens`` rows of the embedding;
    each decoding row's true context of k and v a layer read once and the
    new tokens' written once; for each advanced row a layer its state
    (heads x head width x state width, float32) read once and written once
    and its conv window (taps - 1 inputs) likewise; one hidden row in and
    out a token. No gather copy, no second pass over a state, nothing for
    rows the step did not advance.
ops:  2 x (matmul weights) a token a layer; the output head only for the
    sampled rows; attention 4 x heads x head width a (decode row, context
    position) a layer (prefill chunks' attention is left out); the state's
    update and readout, 4 x heads x head width x state width a token a
    layer (the chunk's quadratic form is left out).

A LOWER bound on what the chip must move: a share over 100% is a fault of
this count.
"""

from __future__ import annotations


def sizes(cfg: dict, weight_bytes: int = 2, cache_bytes: int = 2,
          state_bytes: int = 4) -> dict:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    inner, heads, p = (cfg["mamba_d_ssm"], cfg["mamba_n_heads"],
                       cfg["mamba_d_head"])
    n, g, k = (cfg["mamba_d_state"], cfg["mamba_n_groups"],
               cfg["mamba_d_conv"])
    conv_dim = inner + 2 * g * n
    attn = d * hq * dh + 2 * d * hkv * dh + hq * dh * d
    mixer = d * (inner + conv_dim + heads) + inner * d
    return {"attn_params": attn, "mixer_params": mixer,
            "mixer_small_params": (k + 1) * conv_dim + 3 * heads + inner,
            "mlp_params": 3 * d * f,
            "layer_params": attn + mixer + 3 * d * f,
            "head_params": cfg["vocab_size"] * d,
            "layers": cfg["num_hidden_layers"],
            "kv_values_per_token": 2 * hkv * dh,
            "attn_ops_per_token_pos": 4 * hq * dh,
            "state_values": heads * p * n,
            "window_values": (k - 1) * conv_dim,
            "weight_bytes": weight_bytes, "cache_bytes": cache_bytes,
            "state_bytes": state_bytes, "hidden": d}


def ops_and_bytes(cfg: dict, tokens: float, sampled: float, contexts: float,
                  ssm_rows: float) -> tuple:
    """(operations, bytes) of one step."""
    s = sizes(cfg)
    layers = s["layers"]
    ops = (2.0 * layers * s["layer_params"] * tokens
           + 2.0 * s["head_params"] * sampled
           + layers * s["attn_ops_per_token_pos"] * contexts
           + layers * 4.0 * s["state_values"] * tokens)
    byts = (s["weight_bytes"] * (layers * s["layer_params"]
                                 + s["head_params"])
            + s["cache_bytes"] * s["kv_values_per_token"] * layers
            * (contexts + tokens)
            + 2.0 * layers * ssm_rows * (
                s["state_bytes"] * s["state_values"]
                + s["cache_bytes"] * s["window_values"])
            + 3 * s["weight_bytes"] * s["hidden"] * tokens)
    return ops, byts


def least_seconds(cfg: dict, peaks: dict, tokens: float, sampled: float,
                  contexts: float, ssm_rows: float) -> float:
    ops, byts = ops_and_bytes(cfg, tokens, sampled, contexts, ssm_rows)
    return max(ops / peaks["bf16_flops_per_s"],
               byts / peaks["hbm_bytes_per_s"])
