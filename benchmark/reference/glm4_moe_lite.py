"""Plain reference of GLM-4.7-Flash's decoder (``model_type: glm4_moe_lite``):
pre-norm RMSNorm blocks, no biases, untied head; multi-head latent attention
in the EXPANDED form (every position's per-head keys and values are made
from its compressed vector, then ordinary causal softmax attention); layer 0
(``first_k_dense_replace``) a dense SwiGLU, every later layer
``n_routed_experts`` routed SwiGLU experts chosen ``num_experts_per_tok`` a
token by a sigmoid router with a correction bias, plus the shared expert.
float32 throughout at ``highest`` matmul precision, no cache, a few
sequences at a time, one layer at a time and one expert at a time (weights
are widened to float32 where they are used, so never more than one matrix,
or one expert's three, is held widened).

The layer, from the published config keys alone. With normed input h at
position p:

  c_q = RMSNorm(h W_qa); q = c_q W_qb -> per head [q_nope | q_rope];
  q_rope = RoPE_p(q_rope)
  [c | k_r] = h W_kva; c_kv = RMSNorm(c); k_rope = RoPE_p(k_r), one a token
  [k_nope_i | v_i] = c_kv W_kvb per head i; k_i = [k_nope_i | k_rope]
  a = softmax_causal(q_i . k_i / sqrt(nope + rope)); o = concat_i(a v_i) W_o

  s = sigmoid(h W_r); choice = top-k of s + b over all experts; weights
  w_j = s_j / (sum of the chosen s + 1e-20) * routed_scaling_factor
  y = sum_j w_j E_j(h) + S(h); nothing is dropped.

It imports nothing of the program; it reads the program's parameter tree by
leaf name (``dense_layers`` / ``layers`` stacks; ``wq_a``, ``q_a_norm``,
``wq_b``, ``wkv_a``, ``kv_a_norm``, ``wkv_b``, ``wo``; ``router``,
``router_bias_norm`` = b, ``w_gate`` / ``w_up`` / ``w_down`` (E, in, out),
``ws_gate`` / ``ws_up`` / ``ws_down``) and the published keys from the
configuration file.

Departures from the published description, each of no effect on the
mathematics:
- rotary pairs are (i, i + rope/2) (half rotation), not interleaved: a fixed
  permutation of the columns of W_qb and W_kva, and the weights are seeded.
- a block's (token, choice) pairs are grouped by expert, so that an expert
  multiplies only its own tokens, in rounds of ``CAP`` rows an expert until
  every pair is done (no pair is ever left out, whatever the imbalance).
- ``num_nextn_predict_layers`` (the multi-token-prediction head) is not run:
  it is no part of the model's next-token forward.
- ``quant`` (the output check's control) rounds both inputs of every matrix
  product through a lower precision, but not the router's: a W8A8
  deployment keeps its router wide, as the program does.
"""

from __future__ import annotations

import functools
import os
import sys
import time
from typing import Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
# Rows one expert takes in one round of the grouped product.
CAP = 512
# Sequences are padded on the right to a multiple of this before they are
# scored, so that the output check compiles a handful of lengths and not one
# for every 128 (causal: the padding is inert for what comes before it).
PAD_TO = 512
# Margins between the k-th and (k+1)-th of s + b under which the choice is
# counted as fragile (see ``margins``).
MARGIN_EDGES = (1e-3, 3e-3, 1e-2)
_fragile = {"pairs": 0, "under": [0] * len(MARGIN_EDGES)}


def _fake_quant(x, axis: int, quant: Optional[str]):
    """``x`` rounded through ``quant`` with an absmax scale along ``axis``
    (the contraction axis): what a W8A8 path multiplies."""
    if quant is None:
        return x
    absmax = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-12)
    if quant == "fp8":
        s = absmax / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    if quant == "int8":
        s = absmax / 127.0
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    if quant == "bf16":
        return x.astype(jnp.bfloat16).astype(F32)
    raise ValueError(f"unknown control precision {quant!r}")


def _mm(x, w, quant):
    """(S, in) @ (in, out) in float32; with ``quant`` both inputs are rounded
    per token / per output channel first."""
    return jnp.dot(_fake_quant(x, -1, quant),
                   _fake_quant(w.astype(F32), 0, quant), precision=HIGHEST)


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def _rope(x, theta):
    """x (S, H, Dr): rotate pairs (i, i + Dr/2) by position * theta^(-2i/Dr)."""
    s, _, dr = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dr, 2, dtype=F32) / dr))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :dr // 2], x[..., dr // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(h, gate, up, down, quant):
    return _mm(jax.nn.silu(_mm(h, gate, quant)) * _mm(h, up, quant), down,
               quant)


def attention(cfg, quant, x, lp):
    """One sequence x (S, D) -> x + attention(norm(x)), expanded form."""
    s = x.shape[0]
    heads = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    h = _rms_norm(x, lp["attn_norm"], eps)
    c_q = _rms_norm(_mm(h, lp["wq_a"], quant), lp["q_a_norm"], eps)
    q = _mm(c_q, lp["wq_b"], quant).reshape(s, heads, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    ckr = _mm(h, lp["wkv_a"], quant)
    c_kv = _rms_norm(ckr[:, :r], lp["kv_a_norm"], eps)
    k_rope = _rope(ckr[:, None, r:], theta)                  # (S, 1, rope)
    kv = _mm(c_kv, lp["wkv_b"], quant).reshape(s, heads, nope + dv)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (s, heads, rope))], -1)
    scores = jnp.einsum("qhd,khd->hqk", q, k,
                        precision=HIGHEST) / ((nope + rope) ** 0.5)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1),
                     kv[..., nope:], precision=HIGHEST)
    return x + _mm(att.reshape(s, heads * dv), lp["wo"], quant)


def route(cfg, h, lp):
    """h (N, D) normed -> (chosen experts (N, k), their weights (N, k), the
    margin between the k-th and (k+1)-th of s + b, (N,))."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(jnp.dot(h, lp["router"].astype(F32),
                               precision=HIGHEST))
    top, idx = jax.lax.top_k(s + lp["router_bias_norm"].astype(F32), k + 1)
    idx = idx[:, :k]
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx, w * cfg["routed_scaling_factor"], top[:, k - 1] - top[:, k]


def expert_layer(cfg, quant, h, lp):
    """h (N, D), already normed -> (sum_j w_j E_j(h) + S(h), margins (N,)).
    The pairs are sorted by expert; round j gives every expert the j-th
    ``CAP`` of its rows, until the fullest expert is done."""
    n, d = h.shape
    k, e = cfg["num_experts_per_tok"], cfg["n_routed_experts"]
    idx, w, margin = route(cfg, h, lp)
    flat = idx.reshape(n * k)
    order = jnp.argsort(flat)                  # sorted row -> pair
    counts = jnp.zeros((e,), jnp.int32).at[flat].add(1)
    starts = jnp.cumsum(counts) - counts
    cap = min(CAP, n * k)
    lane = jnp.arange(cap)

    def one_round(state):
        j, y = state

        def one_expert(args):
            gate, up, down, start, count = args
            live = j * cap + lane < count
            pair = order[jnp.clip(start + j * cap + lane, 0, n * k - 1)]
            tok = pair // k
            out = _swiglu(h[tok], gate, up, down, quant)
            return tok, out * (w.reshape(n * k)[pair] * live)[:, None]

        toks, outs = jax.lax.map(one_expert, (lp["w_gate"], lp["w_up"],
                                              lp["w_down"], starts, counts))
        return j + 1, y.at[toks.reshape(-1)].add(outs.reshape(-1, d))

    _, y = jax.lax.while_loop(lambda st: st[0] * cap < counts.max(),
                              one_round, (jnp.zeros((), jnp.int32),
                                          jnp.zeros((n, d), F32)))
    if cfg["n_shared_experts"]:
        y = y + _swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"], quant)
    return y, margin


def dense_layer(cfg, quant, x, lp):
    """x (R, S, D) through one leading dense layer."""
    x = jax.vmap(lambda row: attention(cfg, quant, row, lp))(x)
    h = _rms_norm(x, lp["mlp_norm"], cfg["rms_norm_eps"])
    return x + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"], quant)


def moe_layer(cfg, quant, x, lp):
    """x (R, S, D) through one expert layer -> (x', margins (R * S,))."""
    r, s, d = x.shape
    x = jax.vmap(lambda row: attention(cfg, quant, row, lp))(x)
    h = _rms_norm(x, lp["mlp_norm"], cfg["rms_norm_eps"])
    y, margin = expert_layer(cfg, quant, h.reshape(r * s, d), lp)
    return x + y.reshape(r, s, d), margin


def hidden_states(weights, cfg, tokens, quant=None):
    """tokens (R, S) -> (final hidden states before the last norm (R, S, D),
    margins (expert layers, R * S))."""
    x = weights["embed"][tokens].astype(F32)
    if "dense_layers" in weights:
        x, _ = jax.lax.scan(
            lambda x, lp: (dense_layer(cfg, quant, x, lp), None), x,
            weights["dense_layers"])
    return jax.lax.scan(lambda x, lp: moe_layer(cfg, quant, x, lp), x,
                        weights["layers"])


def logits(weights, cfg, tokens, quant=None):
    """Every position's next-token logits (R, S, V): the tests' reading."""
    x, _ = hidden_states(weights, cfg, tokens, quant)
    h = _rms_norm(x, weights["final_norm"], cfg["rms_norm_eps"])
    return jnp.dot(h, weights["lm_head"].astype(F32), precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant", "n_pos"))
def _score(weights, tokens, starts, cfg_items, quant, n_pos):
    cfg = dict(cfg_items)
    x, margins = hidden_states(weights, cfg, tokens, quant)

    def one(args):
        row, toks, start = args
        # logits only where a served token was predicted
        at = start + jnp.arange(n_pos)
        h = _rms_norm(row[jnp.clip(at, 0, row.shape[0] - 1)],
                      weights["final_norm"], cfg["rms_norm_eps"])
        logp = jax.nn.log_softmax(_mm(h, weights["lm_head"], quant), axis=-1)
        nxt = toks[jnp.clip(at + 1, 0, toks.shape[0] - 1)]
        return jnp.take_along_axis(logp, nxt[:, None], axis=-1)[:, 0]

    under = jnp.stack([(margins < edge).sum() for edge in MARGIN_EDGES])
    return jax.lax.map(one, (x, tokens, starts)), under


KEYS = ("num_attention_heads", "rms_norm_eps", "rope_theta",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
        "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
        "norm_topk_prob", "routed_scaling_factor")


def served_logps(weights, cfg: dict, tokens, starts, n_pos: int,
                 quant: Optional[str] = None):
    """For each row of ``tokens`` (R, S) int32 (prompt then served tokens,
    right padded; causal, so padding is inert for the positions before it,
    though it is routed like any token): log p of the token at
    ``starts[r] + 1 + j`` given everything before it, j < n_pos.
    ``starts[r]`` is the prompt's last position."""
    items = tuple((k, cfg[k]) for k in KEYS)
    tokens = jnp.asarray(tokens, jnp.int32)
    tokens = jnp.pad(tokens, ((0, 0), (0, -tokens.shape[1] % PAD_TO)))
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        out, under = _score(weights, tokens, jnp.asarray(starts, jnp.int32),
                            items, quant, n_pos)
    if quant is None:
        layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
        _fragile["pairs"] += int(tokens.size) * layers
        _fragile["under"] = [a + int(b)
                             for a, b in zip(_fragile["under"], under)]
    if os.environ.get("BENCH_REFERENCE_MARGINS"):
        jax.block_until_ready(out)
        print(f"reference glm4_moe_lite: {tuple(tokens.shape)} quant "
              f"{quant} in {time.perf_counter() - t0:.2f} s; {margins()}",
              file=sys.stderr, flush=True)
    return out


def margins() -> dict:
    """Of all (position, expert layer) pairs scored so far without
    ``quant``, the share whose margin between the k-th and (k+1)-th of
    s + b is under each of ``MARGIN_EDGES``: where a hidden state that
    differs by a rounding picks another expert."""
    n = max(_fragile["pairs"], 1)
    return {"pairs": _fragile["pairs"],
            **{f"under_{edge:g}": u / n
               for edge, u in zip(MARGIN_EDGES, _fragile["under"])}}
