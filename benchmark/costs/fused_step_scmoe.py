"""The fused paged step of a latent-attention model whose layers are
shortcut-connected expert blocks (``longcat_flash``) and whose chip holds a
share of the experts: decode rows and prefill chunks in one forward.

Least work for one step that processes ``tokens`` new tokens, samples
``sampled`` of them, whose resident rows hold ``contexts`` tokens of latent
cache in each attention sublayer, whose expert layers, all together, had
``experts_touched`` HELD expert banks with at least one token and computed
``local_pairs`` (token, held expert) pairs (the step's own counts, not
expectations):

bytes: the two attention sublayers', the two dense FFNs' and the router's
    weights of every layer once; the weights of the touched held experts
    once; the output head's slice once and ``tokens`` rows of the embedding;
    each row's true context of ``kv_lora_rank + qk_rope_head_dim`` values
    read once in each of the ``2 x num_layers`` sublayers; the new tokens'
    latent written once a sublayer; one hidden row in and out a token. No
    padding of the cache row, no gather copies, no expert read twice.
ops:  2 x (matmul weights) a token for attention, the dense FFNs and the
    router; 2 x one expert's weights a ``local_pair`` (an identity pick
    costs no matmul, a pick of an expert held elsewhere none here); absorbed
    attention 2 x heads x ((rank + rope) + rank) a (decode row, context
    position, sublayer) (prefill chunks' attention is left out: a lower
    bound); the output head only for the sampled rows.
"""

from __future__ import annotations


def sizes(cfg: dict, weight_bytes: int = 2, cache_bytes: int = 2) -> dict:
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, rq, rkv = cfg["v_head_dim"], cfg["q_lora_rank"], cfg["kv_lora_rank"]
    attn = (d * rq + rq * heads * (nope + rope) + d * (rkv + rope)
            + rkv * heads * (nope + dv) + heads * dv * d)
    return {"attn_params": attn,
            "dense_ffn_params": 3 * d * cfg["ffn_hidden_size"],
            "expert_params": 3 * d * cfg["expert_ffn_hidden_size"],
            "router_params": d * (cfg["held_experts"]["of"]
                                  + cfg["zero_expert_num"]),
            "head_params": cfg["vocab_size"] * d,
            "layers": cfg["num_layers"], "sublayers": 2 * cfg["num_layers"],
            "latent": rkv + rope,
            "attn_ops_per_token_pos": 2 * heads * ((rkv + rope) + rkv),
            "weight_bytes": weight_bytes, "cache_bytes": cache_bytes,
            "hidden": d}


def ops_and_bytes(cfg: dict, tokens: float, sampled: float, contexts: float,
                  experts_touched: float, local_pairs: float) -> tuple:
    """(operations, bytes) of one step."""
    s = sizes(cfg)
    always = s["layers"] * (2 * s["attn_params"] + 2 * s["dense_ffn_params"]
                            + s["router_params"])
    ops = (2.0 * always * tokens + 2.0 * s["expert_params"] * local_pairs
           + 2.0 * s["head_params"] * sampled
           + s["sublayers"] * s["attn_ops_per_token_pos"] * contexts)
    byts = (s["weight_bytes"] * (always + s["head_params"]
                                 + experts_touched * s["expert_params"])
            + s["cache_bytes"] * s["latent"] * s["sublayers"]
            * (contexts + tokens)
            + 3 * s["weight_bytes"] * s["hidden"] * tokens)
    return ops, byts


def least_seconds(cfg: dict, peaks: dict, tokens: float, sampled: float,
                  contexts: float, experts_touched: float,
                  local_pairs: float) -> float:
    ops, byts = ops_and_bytes(cfg, tokens, sampled, contexts,
                              experts_touched, local_pairs)
    return max(ops / peaks["bf16_flops_per_s"],
               byts / peaks["hbm_bytes_per_s"])
