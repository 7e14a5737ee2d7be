"""The fused paged step of a model whose periods are one gated GQA layer and
``gqa_interval`` gated delta-rule (KDA) layers, every layer's second
sublayer an expert layer of which the chip holds a share (``solar_open2``):
decode rows and prefill chunks in one forward.

Least work for one step that processes ``tokens`` new tokens, samples
``sampled`` of them, whose decoding rows hold ``contexts`` tokens of KV of
which ``shared`` are read once for several rows (the blocks a group's rows
hold in common, PR 38's group items: the step's own count), that advanced
the matrix state of ``ssm_rows`` rows, ``chunk_entries`` of its entries
through the chunked form, and whose expert layers, all together, had
``experts_touched`` HELD expert banks with at least one token and computed
``local_pairs`` (token, held expert) pairs (the step's own counts, not
expectations):

bytes: every layer's weights outside the routed banks once (the GQA layer's
    four projections and its gate; a KDA layer's [W_q | W_k | W_v], W_o, the
    two bottlenecks, beta, the conv; the shared expert; the router); the
    TOUCHED held banks once; the head's slice once and ``tokens`` rows of
    the embedding; the GQA layers' resident k and v read once, a group's
    shared blocks once a GROUP (``contexts - shared`` columns), and the new
    tokens' written once; for each advanced row a KDA layer its state
    (heads x 128 x 128, float32) read once and written once and its conv
    window (taps - 1 inputs of 3 x heads x 128) likewise; one hidden row in
    and out a token. No gather copy, no second pass over a state, no expert
    read twice, nothing for rows the step did not advance.
ops:  2 x (matmul weights outside the banks) a token; 2 x one expert's
    weights a ``local_pair`` (a pick held elsewhere costs nothing here);
    the head only for the sampled rows; attention 4 x heads x head width a
    (decode row, context position) a GQA layer (prefill chunks' attention
    is left out); the state's decay, correction and readout, 6 x heads x
    128 x 128 a token a KDA layer; a chunk's triangles and solve,
    2 x (128 + 128) a head a pair of entries of one chunk, of which a chunk
    entry has at least half a pair (a run is two entries or more).

A LOWER bound on what the chip must move: a share over 100% is a fault of
this count.
"""

from __future__ import annotations


def sizes(cfg: dict, weight_bytes: int = 2, cache_bytes: int = 2,
          state_bytes: int = 4) -> dict:
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    hq, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    lin = cfg["linear_attn_config"]
    heads, dk, taps = (lin["num_heads"], lin["head_dim"],
                       lin["short_conv_kernel_size"])
    w, rank = heads * dk, lin["head_dim"]
    period = cfg["gqa_interval"] + 1
    gqa_layers = layers // period
    kda_layers = layers - gqa_layers
    gqa = d * hq * dh * (3 if cfg["use_gqa_gate"] else 2) + 2 * d * hkv * dh
    kda = 4 * d * w + 2 * (d * rank + rank * w) + d * heads + taps * 3 * w
    expert = 3 * d * cfg["moe_intermediate_size"]
    routed = (cfg.get("held_experts") or {"of": cfg["n_routed_experts"]})[
        "of"]
    every = cfg["n_shared_experts"] * expert + d * routed
    return {"gqa_layers": gqa_layers, "kda_layers": kda_layers,
            "gqa_params": gqa, "kda_params": kda,
            "kda_small_params": 3 * w + heads + dk,
            "shared_and_router_params": every, "expert_params": expert,
            "always_params": (gqa_layers * gqa + kda_layers * kda
                              + layers * every),
            "head_params": cfg["vocab_size"] * d,
            "kv_values_per_token": 2 * hkv * dh,
            "attn_ops_per_token_pos": 4 * hq * dh,
            "state_values": heads * dk * dk,
            "window_values": (taps - 1) * 3 * w,
            "state_ops_per_token": 6 * heads * dk * dk,
            "chunk_ops_per_pair": 2 * heads * (dk + dk),
            "weight_bytes": weight_bytes, "cache_bytes": cache_bytes,
            "state_bytes": state_bytes, "hidden": d}


def ops_and_bytes(cfg: dict, tokens: float, sampled: float, contexts: float,
                  shared: float, ssm_rows: float, chunk_entries: float,
                  experts_touched: float, local_pairs: float) -> tuple:
    """(operations, bytes) of one step."""
    s = sizes(cfg)
    ops = (2.0 * s["always_params"] * tokens
           + 2.0 * s["expert_params"] * local_pairs
           + 2.0 * s["head_params"] * sampled
           + s["gqa_layers"] * s["attn_ops_per_token_pos"] * contexts
           + s["kda_layers"] * (s["state_ops_per_token"] * tokens
                                + s["chunk_ops_per_pair"] * 0.5
                                * chunk_entries))
    byts = (s["weight_bytes"] * (s["always_params"] + s["head_params"]
                                 + experts_touched * s["expert_params"])
            + s["cache_bytes"] * s["kv_values_per_token"] * s["gqa_layers"]
            * (contexts - shared + tokens)
            + 2.0 * s["kda_layers"] * ssm_rows * (
                s["state_bytes"] * s["state_values"]
                + s["cache_bytes"] * s["window_values"])
            + 3 * s["weight_bytes"] * s["hidden"] * tokens)
    return ops, byts


def least_seconds(cfg: dict, peaks: dict, tokens: float, sampled: float,
                  contexts: float, shared: float, ssm_rows: float,
                  chunk_entries: float, experts_touched: float,
                  local_pairs: float) -> float:
    ops, byts = ops_and_bytes(cfg, tokens, sampled, contexts, shared,
                              ssm_rows, chunk_entries, experts_touched,
                              local_pairs)
    return max(ops / peaks["bf16_flops_per_s"],
               byts / peaks["hbm_bytes_per_s"])
