"""Plain reference of LongCat-Flash's decoder (``model_type: longcat_flash``):
pre-norm RMSNorm blocks, no biases, untied head; each layer a
shortcut-connected expert block of TWO multi-head latent attention sublayers
and TWO dense SwiGLU FFNs, with the expert layer reading the first FFN's
normed input and rejoining the stream at the block's end. float32 throughout
at ``highest`` matmul precision, no cache, a few sequences at a time, one
layer, one group of heads and one expert at a time (weights are widened to
float32 where they are used).

The layer, from the published config keys alone. ``x`` the stream, every
norm an RMSNorm with its own gain (``rms_norm_eps``):

  a0 = x  + MLA_0(Norm(x));  u0 = Norm(a0)
  m  = MoE(u0)                                   nothing reads it before x'
  b0 = a0 + FFN_0(u0)                            width ffn_hidden_size
  a1 = b0 + MLA_1(Norm(b0))
  x' = a1 + FFN_1(Norm(a1)) + m

MLA, normed input h at position p, s_q = sqrt(hidden / q_lora_rank) where
``mla_scale_q_lora``, s_kv = sqrt(hidden / kv_lora_rank) where
``mla_scale_kv_lora``:

  c_q = Norm(h W_qa); [q_nope_i | q_rope_i] = s_q (c_q W_qb)_i;
  q_rope_i = RoPE_p(q_rope_i)
  [c | k_r] = h W_kva; c_kv = s_kv Norm(c); k_rope = RoPE_p(k_r), one a
  token, NOT scaled
  [k_nope_i | v_i] = (c_kv W_kvb)_i; k_i = [k_nope_i | k_rope]
  out = concat_i(softmax_causal(q_i . k_i / sqrt(nope + rope)) v_i) W_o

MoE, E real experts then Z = ``zero_expert_num`` identity experts, k =
``moe_topk``, gamma = ``routed_scaling_factor``:

  p = softmax(u W_r) over all E + Z outputs
  S = top-k of p + b            b: the correction bias, for the choice only
  m = gamma [ sum_{j in S, j < E} p_j E_j(u) + (sum_{j in S, j >= E} p_j) u ]
  E_j(u) = (silu(u W_g^j) * (u W_u^j)) W_d^j;  the chosen p as they are

The chip's share: the tree's banks hold the experts [first, first + held)
of the E (``held_experts`` in the configuration file); a real pick outside
that range adds nothing, here as in the program; the identity term is
computed in full. With first = 0 and held = E this is the whole layer.

It imports nothing of the program; it reads the program's parameter tree by
leaf name (``layers``: ``sub0`` and ``sub1``, each a sublayer's
``attn_norm``, ``mlp_norm``, ``wq_a``, ``q_a_norm``, ``wq_b``, ``wkv_a``,
``kv_a_norm``, ``wkv_b``, ``wo`` and its dense FFN's ``w_gate`` / ``w_up`` /
``w_down``; beside them the expert layer's ``router`` (D, E + Z),
``router_bias_norm`` = b, ``w_gate`` / ``w_up`` / ``w_down`` (held, in,
out)) and the published keys from the configuration file.

Departures from the published description, each of no effect on the
mathematics:
- rotary pairs are (i, i + rope/2) (half rotation), not interleaved: a fixed
  permutation of the columns of W_qb and W_kva, and the weights are seeded.
- the heads are attended ``HEADS_AT_ONCE`` at a time, so that a sequence of
  4096 holds 0.5 GB of scores and not 4.3: the same sums.
- every token goes through every held expert and is weighted by the sum of
  its picks of that expert (0 for most): no sort, nothing grouped.
- ``quant`` (the output check's control) rounds both inputs of every matrix
  product through a lower precision, but not the router's: a W8A8
  deployment keeps its router wide, as the program does.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
HEADS_AT_ONCE = 8
# Sequences are padded on the right to a multiple of this before they are
# scored, so that the output check compiles a handful of lengths and not one
# for every 128 (causal: the padding is inert for what comes before it).
PAD_TO = 512


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


def attention(cfg, quant, h, lp):
    """One sequence's normed input h (S, D) -> MLA(h) (S, D), expanded
    form. ``lp``: one sublayer's leaves."""
    s, d = h.shape
    heads = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, rq, r = cfg["v_head_dim"], cfg["q_lora_rank"], cfg["kv_lora_rank"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    s_q = (d / rq) ** 0.5 if cfg["mla_scale_q_lora"] else 1.0
    s_kv = (d / r) ** 0.5 if cfg["mla_scale_kv_lora"] else 1.0
    c_q = _rms_norm(_mm(h, lp["wq_a"], quant), lp["q_a_norm"], eps)
    q = s_q * _mm(c_q, lp["wq_b"], quant).reshape(s, heads, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    ckr = _mm(h, lp["wkv_a"], quant)
    c_kv = s_kv * _rms_norm(ckr[:, :r], lp["kv_a_norm"], eps)
    k_rope = _rope(ckr[:, None, r:], theta)                  # (S, 1, rope)
    kv = _mm(c_kv, lp["wkv_b"], quant).reshape(s, heads, nope + dv)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (s, heads, rope))], -1)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def some_heads(args):
        qh, kh, vh = args                                    # (g, S, .)
        scores = jnp.einsum("hqd,hkd->hqk", qh, kh,
                            precision=HIGHEST) / ((nope + rope) ** 0.5)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, axis=-1),
                          vh, precision=HIGHEST)

    g = min(HEADS_AT_ONCE, heads)
    by_group = lambda a: jnp.moveaxis(a, 1, 0).reshape(
        (heads // g, g) + (s, a.shape[-1]))
    att = jax.lax.map(some_heads,
                      (by_group(q), by_group(k), by_group(kv[..., nope:])))
    att = jnp.moveaxis(att.reshape(heads, s, dv), 0, 1)
    return _mm(att.reshape(s, heads * dv), lp["wo"], quant)


def route(cfg, u, lp):
    """u (N, D) normed -> (picks (N, k) over the E + Z router outputs,
    their weights gamma p_j (N, k))."""
    p = jax.nn.softmax(jnp.dot(u, lp["router"].astype(F32),
                               precision=HIGHEST), axis=-1)
    _, idx = jax.lax.top_k(p + lp["router_bias_norm"].astype(F32),
                           cfg["moe_topk"])
    return idx, (jnp.take_along_axis(p, idx, axis=-1)
                 * cfg["routed_scaling_factor"])


def moe_parts(cfg, quant, u, lp):
    """u (N, D) normed -> (the held experts' part of MoE(u), the identity
    experts' term), each (N, D): their sum is this chip's ``m``."""
    first, routed = cfg["first_expert"], cfg["experts_routed"]
    idx, w = route(cfg, u, lp)

    def one_expert(y, args):
        j, gate, up, down = args
        w_j = jnp.where(idx == first + j, w, 0.0).sum(-1)    # (N,)
        return y + w_j[:, None] * _swiglu(u, gate, up, down, quant), None

    held = lp["w_gate"].shape[0]
    real, _ = jax.lax.scan(one_expert, jnp.zeros(u.shape, F32),
                           (jnp.arange(held), lp["w_gate"], lp["w_up"],
                            lp["w_down"]))
    w_zero = jnp.where(idx >= routed, w, 0.0).sum(-1)
    return real, w_zero[:, None] * u


def layer(cfg, quant, x, lp):
    """x (R, S, D) through one shortcut block."""
    r, s, d = x.shape
    eps = cfg["rms_norm_eps"]
    mla = lambda h, lp_i: jax.vmap(
        lambda row: attention(cfg, quant, row, lp_i))(h)
    ffn = lambda h, lp_i: _swiglu(h.reshape(r * s, d), lp_i["w_gate"],
                                  lp_i["w_up"], lp_i["w_down"],
                                  quant).reshape(r, s, d)
    lp0, lp1 = lp["sub0"], lp["sub1"]
    a0 = x + mla(_rms_norm(x, lp0["attn_norm"], eps), lp0)
    u0 = _rms_norm(a0, lp0["mlp_norm"], eps)
    real, zero = moe_parts(cfg, quant, u0.reshape(r * s, d), lp)
    b0 = a0 + ffn(u0, lp0)
    a1 = b0 + mla(_rms_norm(b0, lp1["attn_norm"], eps), lp1)
    return (a1 + ffn(_rms_norm(a1, lp1["mlp_norm"], eps), lp1)
            + (real + zero).reshape(r, s, d))


def hidden_states(weights, cfg, tokens, quant=None):
    """tokens (R, S) -> final hidden states before the last norm (R, S, D).
    """
    x = weights["embed"][tokens].astype(F32)
    x, _ = jax.lax.scan(lambda x, lp: (layer(cfg, quant, x, lp), None), x,
                        weights["layers"])
    return x


def settings(cfg: dict) -> dict:
    """The keys the mathematics reads, from a configuration file: the
    published ones, and the share (``held_experts``: experts [first, first +
    count) of ``of``; the whole layer where the file has none)."""
    held = cfg.get("held_experts") or {"first": 0,
                                       "of": cfg["n_routed_experts"]}
    if cfg["zero_expert_type"] != "identity":
        raise ValueError(f"zero_expert_type {cfg['zero_expert_type']!r}: "
                         f"only identity experts are written down here")
    return {**{k: cfg[k] for k in KEYS}, "first_expert": held["first"],
            "experts_routed": held["of"]}


def logits(weights, cfg, tokens, quant=None):
    """Every position's next-token logits (R, S, V): the tests' reading."""
    st = settings(cfg)
    with jax.default_matmul_precision("highest"):
        x = hidden_states(weights, st, tokens, quant)
        h = _rms_norm(x, weights["final_norm"], st["rms_norm_eps"])
        return jnp.dot(h, weights["lm_head"].astype(F32), precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant", "n_pos"))
def _score(weights, tokens, starts, cfg_items, quant, n_pos):
    cfg = dict(cfg_items)
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


KEYS = ("num_attention_heads", "rms_norm_eps", "rope_theta",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "q_lora_rank",
        "kv_lora_rank", "mla_scale_q_lora", "mla_scale_kv_lora", "moe_topk",
        "routed_scaling_factor")


def served_logps(weights, cfg: dict, tokens, starts, n_pos: int,
                 quant: Optional[str] = None):
    """For each row of ``tokens`` (R, S) int32 (prompt then served tokens,
    right padded; causal, so padding is inert for the positions before it,
    though it is routed like any token): log p of the token at
    ``starts[r] + 1 + j`` given everything before it, j < n_pos.
    ``starts[r]`` is the prompt's last position."""
    tokens = jnp.asarray(tokens, jnp.int32)
    tokens = jnp.pad(tokens, ((0, 0), (0, -tokens.shape[1] % PAD_TO)))
    with jax.default_matmul_precision("highest"):
        return _score(weights, tokens, jnp.asarray(starts, jnp.int32),
                      tuple(sorted(settings(cfg).items())), quant, n_pos)
