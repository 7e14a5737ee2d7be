"""The fused paged step of a latent-attention model with expert layers
(``glm4_moe_lite``): decode rows and prefill chunks in one forward.

Least work for one step that processes ``tokens`` new tokens, samples
``sampled`` of them, whose resident rows hold ``contexts`` tokens of latent
cache, and whose expert layers, all together, had ``experts_touched`` expert
banks with at least one token (the step's own count, not an expectation):

bytes: attention, shared-expert, router and dense-layer weights once; the
    routed weights of the touched experts once; the output head once and
    ``tokens`` rows of the embedding; each row's true context of
    ``kv_lora_rank + qk_rope_head_dim`` values a layer read once; the new
    tokens' latent written once; one hidden row in and out a token. No
    padding of the cache row, no gather copies, no expert read twice.
ops:  2 x (active matmul weights) a token: attention, the dense layer's
    FFN, and in an expert layer the router, the shared expert and
    ``num_experts_per_tok`` routed experts; absorbed attention
    2 x heads x ((rank + rope) + rank) a (decode row, context position) a
    layer (prefill chunks' attention is left out: a lower bound); the output
    head only for the sampled rows.
"""

from __future__ import annotations


def sizes(cfg: dict, weight_bytes: int = 2, cache_bytes: int = 2) -> dict:
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, rq, rkv = cfg["v_head_dim"], cfg["q_lora_rank"], cfg["kv_lora_rank"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    attn = (d * rq + rq * heads * (nope + rope) + d * (rkv + rope)
            + rkv * heads * (nope + dv) + heads * dv * d)
    expert = 3 * d * cfg["moe_intermediate_size"]
    return {"attn_params": attn, "expert_params": expert,
            "dense_ffn_params": 3 * d * cfg["intermediate_size"],
            "router_params": d * cfg["n_routed_experts"],
            "shared_params": cfg["n_shared_experts"] * expert,
            "head_params": cfg["vocab_size"] * d,
            "layers": layers, "dense_layers": dense,
            "expert_layers": layers - dense,
            "experts_per_token": cfg["num_experts_per_tok"],
            "latent": rkv + rope,
            "attn_ops_per_token_pos": 2 * heads * ((rkv + rope) + rkv),
            "weight_bytes": weight_bytes, "cache_bytes": cache_bytes,
            "hidden": d}


def ops_and_bytes(cfg: dict, tokens: float, sampled: float, contexts: float,
                  experts_touched: float) -> tuple:
    """(operations, bytes) of one step."""
    s = sizes(cfg)
    always = (s["layers"] * s["attn_params"]
              + s["dense_layers"] * s["dense_ffn_params"]
              + s["expert_layers"] * (s["router_params"]
                                      + s["shared_params"]))
    active = always + s["expert_layers"] * (s["experts_per_token"]
                                            * s["expert_params"])
    ops = (2.0 * active * tokens + 2.0 * s["head_params"] * sampled
           + s["layers"] * s["attn_ops_per_token_pos"] * contexts)
    byts = (s["weight_bytes"] * (always + s["head_params"]
                                 + experts_touched * s["expert_params"])
            + s["cache_bytes"] * s["latent"] * s["layers"]
            * (contexts + tokens)
            + 3 * s["weight_bytes"] * s["hidden"] * tokens)
    return ops, byts


def least_seconds(cfg: dict, peaks: dict, tokens: float, sampled: float,
                  contexts: float, experts_touched: float) -> float:
    ops, byts = ops_and_bytes(cfg, tokens, sampled, contexts, experts_touched)
    return max(ops / peaks["bf16_flops_per_s"],
               byts / peaks["hbm_bytes_per_s"])
