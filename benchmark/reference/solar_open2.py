"""Plain reference of Solar-Open2's decoder (``model_type: solar_open2``):
periods of one gated NoPE GQA layer and ``gqa_interval`` gated delta-rule
(KDA) layers, every layer's second sublayer a mixture of experts with one
shared expert, RMSNorm, no positional term anywhere, an untied head. float32
throughout at ``highest`` matmul precision, no cache, no kernels, no
batching beyond a ``vmap`` over the sequences: the delta rule is the
token-by-token step form (``lax.scan`` over positions), attention a plain
causal softmax, an expert a dense SwiGLU over every token.

With D = ``hidden_size``, H x d = ``num_attention_heads`` x ``head_dim`` in
a GQA layer and ``linear_attn_config``'s ``num_heads`` x ``head_dim`` in a
KDA layer (the published model has 64 x 128 in both), every layer l (from
0):

  x <- x + Mix_l(RMSNorm(x));  x <- x + MoE(RMSNorm(x))        eps rms_norm_eps
  Mix_l is GQA where l is in ``gqa_layers`` (l % (gqa_interval + 1) == 0),
  else KDA.  logits = RMSNorm(x_L) W_head

GQA, gated, NoPE (``use_rope: false``, ``use_gqa_gate: true``), h the
normed input:
  q = h W_q (H x d), k = h W_k, v = h W_v (num_key_value_heads x d); no
  bias, no rotary, no q/k norm; a = softmax(q k^T / sqrt(d) + causal) v;
  out = (a * sigmoid(h W_g)) W_o,  W_g: D -> H x d, elementwise

KDA (gated delta rule with a decay a key channel):
  q~ = silu(conv(h W_q)), k~ = silu(conv(h W_k)), v = silu(conv(h W_v)),
  each D -> H x d; conv a causal depthwise convolution of
  ``short_conv_kernel_size`` taps a channel, zeros before position 0
  q = q~ / sqrt(||q~||^2 + 1e-6) / sqrt(d),  k = k~ / sqrt(||k~||^2 + 1e-6)
  g_t = -exp(A_log_h) * softplus(W_f_up (W_f_down h) + dt_bias)   (H x d)
  beta_t = 2 sigmoid(h W_beta) a head (``kda_allow_neg_eigval``; else 1 x)
  S_0 = 0 a head, (d x d) float32:
    S' = Diag(exp g_t) S_{t-1};  u = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u^T;          o_t = S_t^T q_t
  y_t = W_o [ RMSNorm_head(o_t; gain of d) * sigmoid(W_g_up (W_g_down h) + b) ]

MoE: s = sigmoid(h W_r) (D -> ``experts_routed``, float32, never rounded);
  choice = top-k of s + b; weights s_j / (sum of the chosen s + 1e-20) x
  ``routed_scaling_factor``; expert j: SwiGLU D -> moe_intermediate_size ->
  D; one shared expert of n_shared_experts x that width for every token.
  The chip's share: the tree's banks hold the experts [first, first + held)
  of the routed (``held_experts`` in the configuration file); a pick
  outside that range adds nothing, here as in the program. With first = 0
  and held = all this is the whole layer.

This file imports nothing of the program; it reads the program's parameter
tree by leaf name. ``layers/seg0`` holds the periods, one entry a layer of
the period (``full``, ``kda1``, ``kda2``, ...), each leaf stacked over the
periods: ``attn_norm``, ``mlp_norm``, ``router`` (D, routed),
``router_bias_norm`` = b, ``w_gate`` / ``w_up`` / ``w_down`` (held, in,
out), ``ws_gate`` / ``ws_up`` / ``ws_down`` for every layer; ``wq``,
``wk``, ``wv``, ``wo``, ``w_attn_gate``; ``kda_in`` = [W_q | W_k | W_v],
``kda_conv_w`` (taps, 3 H d), ``kda_f_down``, ``kda_f_up``, ``kda_dt_bias``
(1, H d), ``kda_A_log`` (1, H), ``kda_beta``, ``kda_g_down``, ``kda_g_up``,
``kda_g_bias`` (1, H d), ``kda_o_norm`` (d), ``kda_out``; and ``embed``,
``final_norm``, ``lm_head`` (D, V).

Departures from the published description, each of no effect on the
mathematics unless it says so:
- ``[W_q | W_k | W_v]`` of a KDA layer is one leaf and its three convs one
  conv over its channels: a concatenation of columns.
- what the config's keys leave open is settled as the configuration file's
  ``assumed`` says (the gate elementwise, the bottlenecks' rank = the
  mixer's head size, no conv bias, a bias on W_g_up only, one gain shared
  by the heads, 1 / sqrt(d) on q, the 1e-6 in the normalisation, the
  sigmoid router with a correction bias).
- ``served_logps`` pads the sequences to a multiple of ``PAD_TO`` tokens
  (causal: inert for the positions before the padding).
- a sequence's queries attend in blocks of ``Q_BLOCK``; every token goes
  through every held expert and is weighted by the sum of its picks of
  that expert (0 for most): no sort, nothing grouped.
- ``quant`` (the output check's control) rounds both inputs of every matrix
  product through a lower precision, but not the router's; the conv, the
  recurrence, the softmax, the gates' nonlinearities and the norms are not
  products and stay float32.
- keys of no effect on these equations are not read:
  ``intermediate_size`` (no dense layer: ``first_k_dense_replace`` 0),
  ``rope_theta``, ``partial_rotary_factor`` (no rotary),
  ``max_position_embeddings``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .decoder import F32, _mm, _rms_norm

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256
# sequences are padded to a multiple of this before they are scored: one
# compiled program a padded length, a handful and not one a 128 tokens
PAD_TO = 512
L2_EPS = 1e-6
KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
        "rms_norm_eps", "gqa_interval", "use_gqa_gate",
        "kda_allow_neg_eigval", "num_experts_per_tok",
        "routed_scaling_factor")


def gqa(cfg, quant, h, lp):
    """The gated NoPE GQA layer's mixer for one sequence: h (S, D) -> (S, D).
    """
    s = h.shape[0]
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    rep = hq // hkv
    q = _mm(h, lp["wq"], quant).reshape(s, hkv, rep, d)
    k = _mm(h, lp["wk"], quant).reshape(s, hkv, d)
    v = _mm(h, lp["wv"], quant).reshape(s, hkv, d)
    qb = min(Q_BLOCK, s)
    pad = -s % qb

    def block(i):
        rows = i * qb + jnp.arange(qb)
        qi = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0)))[rows]
        scores = jnp.einsum("qgrd,kgd->grqk", qi, k,
                            precision=HIGHEST) / (d ** 0.5)
        seen = jnp.arange(s)[None, :] <= rows[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", probs, v, precision=HIGHEST)

    a = jax.lax.map(block, jnp.arange((s + pad) // qb))
    a = a.reshape(s + pad, hq * d)[:s]
    if cfg["use_gqa_gate"]:
        a = a * jax.nn.sigmoid(_mm(h, lp["w_attn_gate"], quant))
    return _mm(a, lp["wo"], quant)


def kda_inputs(cfg, quant, h, lp):
    """h (S, D) -> what the recurrence reads: q, k, v, g (S, H, d), beta
    (S, H)."""
    s = h.shape[0]
    heads, d = cfg["kda_heads"], cfg["kda_head_dim"]
    w = lp["kda_conv_w"].astype(F32)                       # (taps, 3 H d)
    taps = w.shape[0]
    proj = _mm(h, lp["kda_in"], quant)
    padded = jnp.pad(proj, ((taps - 1, 0), (0, 0)))
    act = jax.nn.silu(sum(w[j] * padded[j:j + s] for j in range(taps)))
    q, k, v = (act.reshape(s, 3, heads, d)[:, i] for i in range(3))
    unit = lambda x: x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                                  + L2_EPS)
    f = (_mm(_mm(h, lp["kda_f_down"], quant), lp["kda_f_up"], quant)
         + lp["kda_dt_bias"][0].astype(F32))
    g = (-jnp.exp(lp["kda_A_log"][0].astype(F32))[:, None]
         * jax.nn.softplus(f).reshape(s, heads, d))
    beta = jax.nn.sigmoid(_mm(h, lp["kda_beta"], quant)) * (
        2.0 if cfg["kda_allow_neg_eigval"] else 1.0)
    return unit(q) / (d ** 0.5), unit(k), v, g, beta


def delta_rule(q, k, v, g, beta):
    """The recurrence for one sequence, one token at a time from a zero
    state: q, k, v, g (S, H, d), beta (S, H) -> o (S, H, d)."""
    def token(state, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        state = jnp.exp(g_t)[:, :, None] * state            # Diag(alpha) S
        u = b_t[:, None] * (v_t - jnp.sum(state * k_t[:, :, None], axis=1))
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, jnp.sum(state * q_t[:, :, None], axis=1)

    heads, d = q.shape[1:]
    _, o = jax.lax.scan(token, jnp.zeros((heads, d, d), F32),
                        (q, k, v, g, beta))
    return o


def kda(cfg, quant, h, lp):
    """The delta-rule layer's mixer for one sequence: h (S, D) -> (S, D)."""
    s = h.shape[0]
    o = delta_rule(*kda_inputs(cfg, quant, h, lp))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + cfg["rms_norm_eps"])
    o = (o * lp["kda_o_norm"].astype(F32)).reshape(s, -1)
    gate = jax.nn.sigmoid(
        _mm(_mm(h, lp["kda_g_down"], quant), lp["kda_g_up"], quant)
        + lp["kda_g_bias"][0].astype(F32))
    return _mm(o * gate, lp["kda_out"], quant)


def _swiglu(u, gate, up, down, quant):
    return _mm(jax.nn.silu(_mm(u, gate, quant)) * _mm(u, up, quant), down,
               quant)


def route(cfg, u, lp):
    """u (N, D) normed -> (picks (N, k) over the routed experts, their
    weights (N, k))."""
    s = jax.nn.sigmoid(jnp.dot(u, lp["router"].astype(F32),
                               precision=HIGHEST))
    _, idx = jax.lax.top_k(s + lp["router_bias_norm"].astype(F32),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    return idx, (w / (w.sum(-1, keepdims=True) + 1e-20)
                 * cfg["routed_scaling_factor"])


def moe_parts(cfg, quant, u, lp):
    """u (N, D) normed -> (the held experts' part of MoE(u), the shared
    expert's), each (N, D): their sum is what this chip adds."""
    idx, w = route(cfg, u, lp)

    def one_expert(y, args):
        j, gate, up, down = args
        w_j = jnp.where(idx == cfg["first_expert"] + j, w, 0.0).sum(-1)
        return y + w_j[:, None] * _swiglu(u, gate, up, down, quant), None

    held = lp["w_gate"].shape[0]
    routed, _ = jax.lax.scan(one_expert, jnp.zeros(u.shape, F32),
                             (jnp.arange(held), lp["w_gate"], lp["w_up"],
                              lp["w_down"]))
    shared = (_swiglu(u, lp["ws_gate"], lp["ws_up"], lp["ws_down"], quant)
              if "ws_gate" in lp else jnp.zeros_like(routed))
    return routed, shared


def layer(cfg, quant, x, lp, mixer):
    """x (R, S, D) through one layer, ``mixer`` its first sublayer for one
    sequence."""
    r, s, d = x.shape
    eps = cfg["rms_norm_eps"]
    x = x + jax.vmap(lambda row: mixer(
        cfg, quant, _rms_norm(row, lp["attn_norm"], eps), lp))(x)
    u = _rms_norm(x, lp["mlp_norm"], eps).reshape(r * s, d)
    routed, shared = moe_parts(cfg, quant, u, lp)
    return x + (routed + shared).reshape(r, s, d)


def hidden_states(weights, cfg, tokens, quant=None):
    """tokens (R, S) -> final hidden states before the last norm (R, S, D).
    """
    x = weights["embed"][tokens].astype(F32)
    names = ["full"] + [f"kda{j}" for j in range(1, cfg["gqa_interval"] + 1)]
    if cfg["gqa_interval"] == 1:
        names[1] = "kda"

    def period(x, lps):
        for name in names:
            x = layer(cfg, quant, x, lps[name],
                      gqa if name == "full" else kda)
        return x, None

    x, _ = jax.lax.scan(period, x, weights["layers"]["seg0"])
    return x


def settings(cfg: dict) -> dict:
    """The keys the mathematics reads, from a configuration file: the
    published ones, and the share (``held_experts``: experts [first, first +
    count) of ``of``; the whole layer where the file has none)."""
    held = cfg.get("held_experts") or {"first": 0,
                                       "of": cfg["n_routed_experts"]}
    lin = cfg["linear_attn_config"]
    return {**{k: cfg[k] for k in KEYS}, "kda_heads": lin["num_heads"],
            "kda_head_dim": lin["head_dim"], "first_expert": held["first"],
            "experts_routed": held["of"]}


def logits(weights, cfg, tokens, quant=None):
    """Every position's next-token logits (R, S, V): the tests' reading."""
    st = settings(cfg)
    with jax.default_matmul_precision("highest"):
        x = hidden_states(weights, st, jnp.asarray(tokens, jnp.int32), quant)
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
