"""The fused paged step of a SambaY decoder (``phi4flash``): layers of five
kinds in one forward, decode rows and prefill chunks mixed.

Least work for one step that processes ``tokens`` new tokens, samples
``sampled`` of them, whose decoding rows hold ``contexts`` tokens of KV of
which ``shared`` are read once for several rows (the blocks a group's rows
hold in common, PR 38's group items: the step's own count), whose window
layers read ``window_cols`` columns a layer (each row's trailing
``sliding_window`` positions), and that advanced the recurrent state of
``ssm_rows`` rows:

bytes: every layer's weights once (9 mixers, 9 attention layers, 7 cross
    layers, 7 gated memory units, 32 MLPs); the tied embedding once as the
    output head and ``tokens`` rows of it as the input; the full-attention
    layer's resident k and v read ONCE A PASS by its own pass and by each
    cross layer's (1 + 7 passes over ``contexts - shared`` columns), and
    the new tokens' written once; each window layer's ``window_cols`` read
    once and the new tokens' written once; for each advanced row a mixer
    its state (d_inner x d_state, float32) read and written once and its
    conv window (taps - 1 inputs) likewise; one hidden row in and out a
    token. A column is a token's k and v: 2 x (kv heads x head width).
ops:  2 x (matmul weights) a token; the output head only for the sampled
    rows; attention 4 x heads x head width a (row, column) a pass, both
    maps counted (each of the two maps multiplies its own half of the
    query heads by the key and all of the values: 4 x Hq x dh in all, not
    the zero-padded product the kernel runs); the state's update and
    readout, 6 x d_inner x d_state a token a mixer.

A LOWER bound on what the chip must move: a share over 100% is a fault of
this count.
"""

from __future__ import annotations


def sizes(cfg: dict, weight_bytes: int = 2, cache_bytes: int = 2,
          state_bytes: int = 4) -> dict:
    d, f, layers = (cfg["hidden_size"], cfg["intermediate_size"],
                    cfg["num_hidden_layers"])
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // hq
    inner = cfg["mamba_expand"] * d
    n, k, r = (cfg["mamba_d_state"], cfg["mamba_d_conv"],
               cfg["mamba_dt_rank"])
    half = layers // 2
    kinds = {"mamba": half // 2 + 1, "window": half // 2, "full": 1,
             "gmu": half // 2 - 1, "cross": half // 2 - 1}
    attn = d * hq * dh + 2 * d * hkv * dh + hq * dh * d
    cross = 2 * d * hq * dh
    mixer = d * 2 * inner + inner * (r + 2 * n) + r * inner + inner * d
    gmu = 2 * d * inner
    mlp = 3 * d * f
    return {"kinds": kinds, "attn_params": attn, "cross_params": cross,
            "mixer_params": mixer, "gmu_params": gmu, "mlp_params": mlp,
            "mixer_small_params": (k + 1) * inner + inner * n + 2 * inner,
            "attn_small_params": 4 * dh + 2 * dh,
            "layer_params_total": (
                kinds["mamba"] * mixer
                + (kinds["window"] + kinds["full"]) * attn
                + kinds["cross"] * cross + kinds["gmu"] * gmu
                + layers * mlp),
            "embed_params": cfg["vocab_size"] * d,
            "kv_values_per_token": 2 * hkv * dh,
            "attn_ops_per_column": 4 * hq * dh,
            "state_values": inner * n,
            "window_values": (k - 1) * inner,
            "full_passes": kinds["full"] + kinds["cross"],
            "weight_bytes": weight_bytes, "cache_bytes": cache_bytes,
            "state_bytes": state_bytes, "hidden": d,
            "window": cfg["sliding_window"]}


def ops_and_bytes(cfg: dict, tokens: float, sampled: float, contexts: float,
                  shared: float, window_cols: float,
                  ssm_rows: float) -> tuple:
    """(operations, bytes) of one step."""
    s = sizes(cfg)
    kinds = s["kinds"]
    passes = s["full_passes"]
    ops = (2.0 * s["layer_params_total"] * tokens
           + 2.0 * s["embed_params"] * sampled
           + s["attn_ops_per_column"] * (passes * contexts
                                         + kinds["window"] * window_cols)
           + kinds["mamba"] * 6.0 * s["state_values"] * tokens)
    kv = s["cache_bytes"] * s["kv_values_per_token"]
    byts = (s["weight_bytes"] * (s["layer_params_total"] + s["embed_params"])
            + kv * (passes * (contexts - shared) + kinds["full"] * tokens)
            + kv * kinds["window"] * (window_cols + tokens)
            + 2.0 * kinds["mamba"] * ssm_rows * (
                s["state_bytes"] * s["state_values"]
                + s["cache_bytes"] * s["window_values"])
            + 3 * s["weight_bytes"] * s["hidden"] * tokens)
    return ops, byts


def least_seconds(cfg: dict, peaks: dict, tokens: float, sampled: float,
                  contexts: float, shared: float, window_cols: float,
                  ssm_rows: float) -> float:
    ops, byts = ops_and_bytes(cfg, tokens, sampled, contexts, shared,
                              window_cols, ssm_rows)
    return max(ops / peaks["bf16_flops_per_s"],
               byts / peaks["hbm_bytes_per_s"])
