"""The fused paged step (decode rows and prefill chunks in one forward).

Least work for one step that processes ``tokens`` new tokens, samples
``sampled`` of them, and whose resident rows hold ``contexts`` tokens of KV:

bytes: every weight matrix read once (the embedding table only where it is
    the output head, else ``tokens`` rows of it), the KV of each row's true
    context read once, the new tokens' KV written once, one hidden row in and
    out a token. No padding, no gather copies, no second pool.
ops:  2 x (matmul weights) per token; attention 4 x Hq x Dh per (token,
    context position) for the decode rows (prefill chunks' attention is left
    out: a lower bound); the output head only for the sampled rows.
"""

from __future__ import annotations


def sizes(cfg: dict, weight_bytes: int = 2, kv_bytes: int = 2) -> dict:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg.get("head_dim", d // hq)
    layers, v = cfg["num_hidden_layers"], cfg["vocab_size"]
    per_layer = d * hq * dh * 2 + d * hkv * dh * 2 + 3 * d * f
    return {"layer_matmul_params": layers * per_layer,
            "head_params": v * d,
            "kv_bytes_per_token": layers * 2 * hkv * dh * kv_bytes,
            "attn_ops_per_token_pos": layers * 4 * hq * dh,
            "weight_bytes": weight_bytes, "hidden": d}


def ops_and_bytes(cfg: dict, tokens: float, sampled: float,
                  contexts: float) -> tuple:
    """(operations, bytes) of one step."""
    s = sizes(cfg)
    ops = (2.0 * s["layer_matmul_params"] * tokens
           + 2.0 * s["head_params"] * sampled
           + s["attn_ops_per_token_pos"] * contexts)
    byts = (s["weight_bytes"] * (s["layer_matmul_params"] + s["head_params"])
            + s["kv_bytes_per_token"] * (contexts + tokens)
            + 2 * s["weight_bytes"] * s["hidden"] * tokens)
    return ops, byts


def least_seconds(cfg: dict, peaks: dict, tokens: float, sampled: float,
                  contexts: float) -> float:
    ops, byts = ops_and_bytes(cfg, tokens, sampled, contexts)
    return max(ops / peaks["bf16_flops_per_s"],
               byts / peaks["hbm_bytes_per_s"])
