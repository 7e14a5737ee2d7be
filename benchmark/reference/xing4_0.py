"""Plain reference of Xing4.0's decoder (``model_type: xing4_0``): GLM-4.7-
Flash's blocks (latent attention in the expanded form, leading dense SwiGLU
layers, then routed + shared experts under a sigmoid router with a
correction bias) with two changes: the rotary part turns at YaRN's
frequencies and the softmax scale carries YaRN's magnitude, and the residual
path is ``hc_mult`` rows wide, every sublayer reading and writing it through
three maps computed from the token's own rows (manifold-constrained
hyper-connections, mHC). float32 throughout at ``highest`` matmul precision,
no cache, a few sequences at a time.

The residual path, from the published config keys (``hc_mult`` n,
``hc_sinkhorn_iters``, ``hc_eps``, ``mhc_h_res_clamp_min/max``). The stream
of a token is X (n, C); after the embedding every row is the embedding,
before the final norm the rows are summed. For each sublayer F (attention
with its RMSNorm, then the FFN with its RMSNorm), with its own phi
(n C, n + n + n n), gates a_pre, a_post, a_res and biases b:

  x~ = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)       (no gain)
  [u_pre | u_post | u_res] = x~ phi
  H_pre  = sigmoid(a_pre u_pre + b_pre)                   (n)
  H_post = 2 sigmoid(a_post u_post + b_post)              (n)
  M_0    = exp(clip(a_res mat(u_res) + b_res, clamp_min, clamp_max))
  M_t    = rows(cols(M_{t-1})); cols(M) = M / (1^T M + hc_eps),
           rows(M) = M / (M 1 + hc_eps); H_res = M_iters   (n, n)
  y  = F(sum_j H_pre[j] X[j])
  X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

``mat`` is row-major: H_res[i, j] comes from u_res[i n + j].

YaRN, on the d = ``qk_rope_head_dim`` rotary values of q and of the one
rotary key a token (``rope_scaling``: ``factor`` s, ``beta_fast``,
``beta_slow``, ``original_max_position_embeddings`` L0, ``mscale``,
``mscale_all_dim``; base = ``rope_theta``):

  f_i = base^(-2 i / d), i < d / 2
  lo = floor(d ln(L0 / (2 pi beta_fast)) / (2 ln base)),
  hi = ceil (d ln(L0 / (2 pi beta_slow)) / (2 ln base)), both held to [0, d-1]
  ramp_i = clip((i - lo) / (hi - lo), 0, 1)
  inv_freq_i = (f_i / s) ramp_i + f_i (1 - ramp_i)
  cos, sin times m(mscale) / m(mscale_all_dim), m(k) = 0.1 k ln s + 1
  softmax scale = (nope + rope)^(-1/2) m(mscale_all_dim)^2

Latent attention, the router and the experts are
``reference/glm4_moe_lite.py``'s equations (its docstring) at this model's
sizes; its matrix product, norm, SwiGLU and expert layer are imported from
there, since they take every size from ``cfg``. This file imports nothing
of the program; it reads the program's parameter tree by leaf name: glm's
leaves, and for each sublayer ``s`` in (``attn``, ``mlp``) ``s_hc_phi``,
``s_hc_gate_norm`` = (a_pre, a_post, a_res), ``s_hc_bias`` (1, n + n + n n)
= [b_pre | b_post | vec(b_res)].

Departures from the published description, each of no effect on the
mathematics unless it says so:
- what the config's keys leave open is settled as the configuration file's
  ``assumed`` says: copy in and sum out at the stream's ends, columns before
  rows, ``hc_eps`` in every divisor, a gain-free norm over all n C values.
- rotary pairs are (i, i + d/2), not interleaved: a fixed permutation of
  the columns of W_qb and W_kva, and the weights are seeded.
- a sequence's queries attend in blocks of ``Q_BLOCK``: at 32 heads and
  4096 positions the whole score tensor is 2.1 GB in float32, twice with
  its softmax, beside 9.8 GB of weights.
- ``num_nextn_predict_layers`` (the multi-token-prediction head) is not run.
- ``quant`` (the output check's control) rounds both inputs of every matrix
  product through a lower precision, but neither the router's nor the
  maps': a W8A8 deployment keeps both wide, as the program does.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from .glm4_moe_lite import (F32, HIGHEST, PAD_TO, _mm, _rms_norm, _swiglu,
                            expert_layer)

# Queries one block of a sequence's attention holds.
Q_BLOCK = 1024


def yarn(cfg) -> tuple:
    """(inv_freq (d/2,) as a tuple of floats, what cos and sin are
    multiplied by, what the softmax scale is multiplied by, lo, hi)."""
    d, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    rs = cfg["rope_scaling"]
    s, l0 = float(rs["factor"]), rs["original_max_position_embeddings"]

    def pair(turns):
        return d * math.log(l0 / (2 * math.pi * turns)) / (2 * math.log(base))

    lo = min(max(math.floor(pair(rs["beta_fast"])), 0), d - 1)
    hi = min(max(math.ceil(pair(rs["beta_slow"])), 0), d - 1)
    inv = []
    for i in range(d // 2):
        f = base ** (-2.0 * i / d)
        ramp = min(max((i - lo) / (hi - lo if hi > lo else 1e-3), 0.0), 1.0)
        inv.append(f / s * ramp + f * (1.0 - ramp))

    def m(k):
        return 0.1 * k * math.log(s) + 1.0 if s > 1 and k else 1.0

    return (tuple(inv), m(rs["mscale"]) / m(rs["mscale_all_dim"]),
            m(rs["mscale_all_dim"]) ** 2, lo, hi)


def _rope(x, inv_freq, magnitude):
    """x (S, H, d): rotate pairs (i, i + d/2) by position * inv_freq_i."""
    s, _, d = x.shape
    ang = jnp.arange(s, dtype=F32)[:, None] * jnp.asarray(inv_freq, F32)
    cos = jnp.cos(ang)[:, None, :] * magnitude
    sin = jnp.sin(ang)[:, None, :] * magnitude
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(cfg, quant, x, lp):
    """One sequence x (S, D), what the residual path reads out ->
    attention(RMSNorm(x)) (S, D), expanded form."""
    s = x.shape[0]
    heads = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r, eps = cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    inv_freq, magnitude, scale_by, _, _ = yarn(cfg)
    h = _rms_norm(x, lp["attn_norm"], eps)
    c_q = _rms_norm(_mm(h, lp["wq_a"], quant), lp["q_a_norm"], eps)
    q = _mm(c_q, lp["wq_b"], quant).reshape(s, heads, nope + rope)
    q = jnp.concatenate(
        [q[..., :nope], _rope(q[..., nope:], inv_freq, magnitude)], -1)
    ckr = _mm(h, lp["wkv_a"], quant)
    c_kv = _rms_norm(ckr[:, :r], lp["kv_a_norm"], eps)
    k_rope = _rope(ckr[:, None, r:], inv_freq, magnitude)     # (S, 1, rope)
    kv = _mm(c_kv, lp["wkv_b"], quant).reshape(s, heads, nope + dv)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (s, heads, rope))], -1)
    scale = scale_by / (nope + rope) ** 0.5
    # a padded length (a multiple of ``PAD_TO``) splits evenly
    qb = s if s <= Q_BLOCK else math.gcd(s, Q_BLOCK)

    def block(args):
        q_blk, first = args
        scores = jnp.einsum("qhd,khd->hqk", q_blk, k,
                            precision=HIGHEST) * scale
        causal = (first + jnp.arange(qb))[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1),
                          kv[..., nope:], precision=HIGHEST)

    att = jax.lax.map(block, (q.reshape(s // qb, qb, heads, nope + rope),
                              jnp.arange(0, s, qb)))
    return _mm(att.reshape(s, heads * dv), lp["wo"], quant)


def sinkhorn(logits, cfg):
    """(..., n, n) logits -> H_res: ``hc_sinkhorn_iters`` rounds, columns
    then rows, of exp(clip(logits))."""
    m = jnp.exp(jnp.clip(logits, cfg["mhc_h_res_clamp_min"],
                         cfg["mhc_h_res_clamp_max"]))
    for _ in range(cfg["hc_sinkhorn_iters"]):
        m = m / (m.sum(-2, keepdims=True) + cfg["hc_eps"])
        m = m / (m.sum(-1, keepdims=True) + cfg["hc_eps"])
    return m


def maps(cfg, x, lp, sub):
    """x (..., n, C) -> H_pre (..., n), H_post (..., n), H_res (..., n, n)."""
    n = cfg["hc_mult"]
    flat = x.reshape(x.shape[:-2] + (n * x.shape[-1],))
    flat = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                                + cfg["rms_norm_eps"])
    u = jnp.dot(flat, lp[f"{sub}_hc_phi"].astype(F32), precision=HIGHEST)
    a_pre, a_post, a_res = lp[f"{sub}_hc_gate_norm"].astype(F32)
    b = lp[f"{sub}_hc_bias"].astype(F32)[0]
    h_pre = jax.nn.sigmoid(a_pre * u[..., :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a_post * u[..., n:2 * n] + b[n:2 * n])
    logits = a_res * u[..., 2 * n:] + b[2 * n:]
    return h_pre, h_post, sinkhorn(
        logits.reshape(logits.shape[:-1] + (n, n)), cfg)


def residual(cfg, x, lp, sub, f):
    """One sublayer ``f`` (its input (..., C) -> its output (..., C))
    through the stream x (..., n, C)."""
    h_pre, h_post, h_res = maps(cfg, x, lp, sub)
    y = f(jnp.einsum("...j,...jc->...c", h_pre, x, precision=HIGHEST))
    return (jnp.einsum("...ij,...jc->...ic", h_res, x, precision=HIGHEST)
            + h_post[..., None] * y[..., None, :])


def layer(cfg, quant, x, lp):
    """x (R, S, n, C) through one layer, dense or expert by its leaves."""
    r, s, _, d = x.shape
    x = residual(cfg, x, lp, "attn", jax.vmap(
        lambda row: attention(cfg, quant, row, lp)))

    def ffn(x_in):
        h = _rms_norm(x_in, lp["mlp_norm"], cfg["rms_norm_eps"])
        if "router" not in lp:
            return _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"], quant)
        y, _margin = expert_layer(cfg, quant, h.reshape(r * s, d), lp)
        return y.reshape(r, s, d)

    return residual(cfg, x, lp, "mlp", ffn)


def hidden_states(weights, cfg, tokens, quant=None):
    """tokens (R, S) -> the stream's rows summed, before the last norm
    (R, S, C)."""
    e = weights["embed"][tokens].astype(F32)
    x = jnp.broadcast_to(e[..., None, :],
                         e.shape[:-1] + (cfg["hc_mult"], e.shape[-1]))
    for stack in ("dense_layers", "layers"):
        if stack in weights:
            x, _ = jax.lax.scan(
                lambda x, lp: (layer(cfg, quant, x, lp), None), x,
                weights[stack])
    return x.sum(-2)


def logits(weights, cfg, tokens, quant=None):
    """Every position's next-token logits (R, S, V): the tests' reading."""
    x = hidden_states(weights, cfg, tokens, quant)
    h = _rms_norm(x, weights["final_norm"], cfg["rms_norm_eps"])
    return jnp.dot(h, weights["lm_head"].astype(F32), precision=HIGHEST)


KEYS = ("num_attention_heads", "rms_norm_eps", "rope_theta",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
        "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
        "norm_topk_prob", "routed_scaling_factor", "hc_mult",
        "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
        "mhc_h_res_clamp_max")


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant", "n_pos"))
def _score(weights, tokens, starts, cfg_items, quant, n_pos):
    cfg = dict(cfg_items)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    x = hidden_states(weights, cfg, tokens, quant)

    def one(args):
        row, toks, start = args
        # logits only where a served token was predicted
        at = start + jnp.arange(n_pos)
        h = _rms_norm(row[jnp.clip(at, 0, row.shape[0] - 1)],
                      weights["final_norm"], cfg["rms_norm_eps"])
        logp = jax.nn.log_softmax(_mm(h, weights["lm_head"], quant), axis=-1)
        nxt = toks[jnp.clip(at + 1, 0, toks.shape[0] - 1)]
        return jnp.take_along_axis(logp, nxt[:, None], axis=-1)[:, 0]

    return jax.lax.map(one, (x, tokens, starts))


def served_logps(weights, cfg: dict, tokens, starts, n_pos: int,
                 quant: Optional[str] = None):
    """For each row of ``tokens`` (R, S) int32 (prompt then served tokens,
    right padded; causal, so padding is inert for the positions before it):
    log p of the token at ``starts[r] + 1 + j`` given everything before it,
    j < n_pos. ``starts[r]`` is the prompt's last position."""
    items = tuple((k, cfg[k]) for k in KEYS) + (
        ("rope_scaling", tuple(sorted(cfg["rope_scaling"].items()))),)
    tokens = jnp.asarray(tokens, jnp.int32)
    tokens = jnp.pad(tokens, ((0, 0), (0, -tokens.shape[1] % PAD_TO)))
    with jax.default_matmul_precision("highest"):
        return _score(weights, tokens, jnp.asarray(starts, jnp.int32), items,
                      quant, n_pos)
