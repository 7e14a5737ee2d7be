"""Plain reference of Phi-4-mini-flash-reasoning's decoder (``model_type:
phi4flash``, the SambaY decoder-hybrid-decoder of arXiv:2507.06607): layers
of five kinds in a fixed pattern, LayerNorm with bias, no positional term,
differential attention, a tied embedding. float32 throughout at ``highest``
matmul precision, no cache, no kernels: the mixer is the token-by-token
recurrence (``lax.scan`` over positions), attention a plain masked softmax.

With ``L = num_hidden_layers``, ``d = hidden_size``, ``d_i = mamba_expand
d``, ``N = mamba_d_state``, ``R = mamba_dt_rank``, ``K = mamba_d_conv``,
``W = sliding_window``,
``dh = d / num_attention_heads``, layer ``l`` (from 0) is

  l even, l <= L/2        Mamba-1 mixer          (l % mb_per_layer == 0)
  l odd,  l <  L/2        window attention (W)
  l = L/2 + 1             full attention; its k, v are the cross layers'
  l even, l >= L/2 + 2    gated memory unit on m, layer L/2's scan output
  l odd,  l >= L/2 + 3    cross attention over layer L/2 + 1's k, v

  every layer:  x = x + mix_l(LN(x; g1, b1))
                x = x + (silu(u W_gate) * (u W_up)) W_down,  u = LN(x; g2, b2)
  logits = LN(x_L; g, b) E^T                      (E the embedding, tied)
  LN(x) = (x - mean) / sqrt(var + layer_norm_eps) * g + b

  Mamba-1:  [u | z] = h W_in                       (d -> 2 d_i)
    u_t = silu(sum_{j<K} w[j] u_{t-(K-1)+j} + b)   depthwise, causal, zeros
                                                   before position 0
    [r | B | C] = u_t W_x                          (d_i -> R + 2 N)
    dt = softplus(r W_dt + b_dt)                   (R -> d_i)
    A = -exp(A_log)                                (d_i, N)
    S_t = exp(dt_t (x) 1 * A) * S_{t-1} + (dt_t * u_t) (x) B_t,  S_{-1} = 0
    y_t = S_t C_t + D * u_t
    mix = (y_t * silu(z_t)) W_out;   layer L/2 publishes m_t = y_t

  attention (window, full):  q = h W_q, k = h W_k, v = h W_v
    the heads are two sets: q_i (Hq/2 heads), k_i, v_i (Hkv/2 heads), i = 1, 2
    P_i = softmax(q_i k_i^T / sqrt(dh) + mask),  Hq/Hkv query heads a kv head
    a_i = [P_i v_1 | P_i v_2]                      (Hq/2 heads x 2 dh)
    lam0 = 0.8 - 0.6 exp(-0.3 l)
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0
    o = RMSNorm_{2 dh}(a_1 - lam a_2; g_sub) (1 - lam0);  mix = o W_o
    mask: s <= t; a window layer also t - s < W
  cross:  q = h W_q only; k, v are layer L/2 + 1's, positions s <= t; the
    same differential form with the layer's own lam, g_sub, W_o
  gated memory unit:  mix = (silu(h W_in) * m_t) W_out

This file imports nothing of the program; it reads the program's parameter
tree by leaf name. ``layers/seg0`` holds the L/4 (mamba, window) pairs,
``seg1`` the middle (mamba, full) pair, ``seg2`` the (gmu, cross) pairs,
each kind's leaves stacked over its layers: ``attn_norm``, ``attn_norm_bias``,
``mlp_norm``, ``mlp_norm_bias``, ``w_gate``, ``w_up``, ``w_down`` for every
kind; ``ssm_in``, ``ssm_conv_w`` (K, d_i), ``ssm_conv_b``, ``ssm_x``,
``ssm_dt``, ``ssm_dt_bias``, ``ssm_A_log`` (d_i, N), ``ssm_D``, ``ssm_out``;
``wq``, ``wk``, ``wv``, ``wo``, ``attn_lambda`` (dh, 4: lq1, lk1, lq2, lk2),
``attn_sub_norm``; ``gmu_in``, ``gmu_out``; and ``embed``, ``final_norm``,
``final_norm_bias`` (1, d).

Departures from the published description, each of no effect on the
mathematics unless it says so:
- which heads form a set is fixed by the weights' column order, which the
  program chose: ``W_q``'s columns are (kv pair p, set i, head j, dh) and
  ``W_k``'s, ``W_v``'s (kv pair p, set i, dh), so kv pair p is heads
  (k_1[p], k_2[p]). By halves or by pairs is a permutation of columns, and
  the weights are seeded.
- ``[gate | up]`` and ``[q | k | v]`` are separate leaves, a split of the
  published fused matrices' columns.
- what the config's keys leave open is settled as the configuration file's
  ``assumed`` says (the four ``mamba_*`` sizes, m before the gate, the
  sub-norm's epsilon).
- a sequence's queries attend in blocks of ``Q_BLOCK``, and the head and its
  log-sum-exp run over the vocabulary in blocks of ``V_BLOCK`` rows.
- ``quant`` (the output check's control) rounds both inputs of every matrix
  product through a lower precision; the conv, the recurrence, the softmax
  and the norms are not products and stay float32.
- keys of no effect on these equations are not read: ``embd_pdrop``,
  ``resid_pdrop`` (0), ``max_position_embeddings``, ``mlp_bias`` and
  ``lm_head_bias`` false.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .decoder import F32, _fake_quant, _mm

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
V_BLOCK = 16672
KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
        "num_hidden_layers", "layer_norm_eps", "sliding_window",
        "mb_per_layer", "vocab_size", "mamba_d_state", "mamba_d_conv",
        "mamba_expand", "mamba_dt_rank")


def _layer_norm(x, lp, name, eps):
    cen = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(cen * cen, axis=-1, keepdims=True)
    return (cen * jax.lax.rsqrt(var + eps) * lp[name].astype(F32)
            + lp[name + "_bias"].astype(F32))


def _mamba(cfg, quant, h, lp):
    """The Mamba-1 mixer for one sequence: h (S, d) -> (mix (S, d), y
    (S, d_i)), the recurrence one token at a time."""
    s = h.shape[0]
    d_i = cfg["mamba_expand"] * cfg["hidden_size"]
    n, r, k = (cfg["mamba_d_state"], cfg["mamba_dt_rank"],
               cfg["mamba_d_conv"])
    proj = _mm(h, lp["ssm_in"], quant)
    u, z = proj[:, :d_i], proj[:, d_i:]
    w, b = lp["ssm_conv_w"].astype(F32), lp["ssm_conv_b"].astype(F32)
    padded = jnp.pad(u, ((k - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(w[j] * padded[j:j + s] for j in range(k)) + b)
    x = _mm(u, lp["ssm_x"], quant)
    dt = jax.nn.softplus(_mm(x[:, :r], lp["ssm_dt"], quant)
                         + lp["ssm_dt_bias"].astype(F32))
    bb, cc = x[:, r:r + n], x[:, r + n:]
    a = -jnp.exp(lp["ssm_A_log"].astype(F32)).T                 # (N, d_i)

    def token(state, inp):
        # the state held (N, d_i): a sum over N is a sum of N rows
        u_t, dt_t, b_t, c_t = inp
        state = (jnp.exp(dt_t[None, :] * a) * state
                 + (dt_t * u_t)[None, :] * b_t[:, None])
        return state, jnp.sum(state * c_t[:, None], axis=0)

    _, y = jax.lax.scan(token, jnp.zeros((n, d_i), F32), (u, dt, bb, cc),
                        unroll=8)
    y = y + lp["ssm_D"].astype(F32) * u
    return _mm(y * jax.nn.silu(z), lp["ssm_out"], quant), y


def _attention(cfg, quant, h, lp, layer, window, kv=None):
    """Differential attention for one sequence: h (S, d) -> (mix (S, d),
    (k, v)). ``kv``: another layer's (k, v), for a cross layer; ``window``:
    a query reads positions ``t - window < s <= t``, None for all
    ``s <= t``."""
    s = h.shape[0]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["hidden_size"] // hq
    pairs, rep = hkv // 2, hq // hkv
    # (S, pair, set, head in the pair's group, dh)
    q = _mm(h, lp["wq"], quant).reshape(s, pairs, 2, rep, dh)
    if kv is None:
        kv = (_mm(h, lp["wk"], quant).reshape(s, pairs, 2, dh),
              _mm(h, lp["wv"], quant).reshape(s, pairs, 2, dh))
    k, v = kv
    values = v.reshape(s, pairs, 2 * dh)                       # [v_1 | v_2]
    qb = min(Q_BLOCK, s)
    pad = -s % qb

    def block(i):
        rows = i * qb + jnp.arange(qb)
        qi = jnp.pad(q, ((0, pad),) + ((0, 0),) * 4)[rows]
        scores = jnp.einsum("qpirc,kpic->pirqk", qi, k,
                            precision=HIGHEST) / (dh ** 0.5)
        pos = jnp.arange(s)[None, :]
        seen = pos <= rows[:, None]
        if window is not None:
            seen &= rows[:, None] - pos < window
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("pirqk,kpc->qpirc", probs, values,
                          precision=HIGHEST)              # a_i: (.., 2 dh)

    a = jax.lax.map(block, jnp.arange((s + pad) // qb))
    a = a.reshape((s + pad, pairs, 2, rep, 2 * dh))[:s]
    lv = lp["attn_lambda"].astype(F32)
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * layer.astype(F32))
    lam = (jnp.exp(jnp.dot(lv[:, 0], lv[:, 1]))
           - jnp.exp(jnp.dot(lv[:, 2], lv[:, 3])) + lam0)
    o = a[:, :, 0] - lam * a[:, :, 1]                          # (S, p, r, 2dh)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + cfg["layer_norm_eps"])
    o = o * lp["attn_sub_norm"].astype(F32) * (1.0 - lam0)
    return _mm(o.reshape(s, hq * dh), lp["wo"], quant), kv


def _mlp(cfg, quant, x, lp):
    u = _layer_norm(x, lp, "mlp_norm", cfg["layer_norm_eps"])
    act = jax.nn.silu(_mm(u, lp["w_gate"], quant)) * _mm(u, lp["w_up"],
                                                        quant)
    return x + _mm(act, lp["w_down"], quant)


def _sequence_layers(cfg, quant, x, layers):
    """The L layers for one sequence x (S, d) -> (S, d)."""
    eps, half = cfg["layer_norm_eps"], cfg["num_hidden_layers"] // 2
    if cfg["mb_per_layer"] != 2 or cfg["num_hidden_layers"] % 4:
        raise ValueError("the pattern above is mb_per_layer 2 over 4 n "
                         "layers")
    norm = lambda x, lp: _layer_norm(x, lp, "attn_norm", eps)

    def mamba_layer(x, lp):
        mix, y = _mamba(cfg, quant, norm(x, lp), lp)
        return _mlp(cfg, quant, x + mix, lp), y

    def attention_layer(x, lp, layer, window=None, kv=None):
        mix, kv = _attention(cfg, quant, norm(x, lp), lp, layer, window, kv)
        return _mlp(cfg, quant, x + mix, lp), kv

    def self_pair(x, inp):
        lp, i = inp
        x, _ = mamba_layer(x, lp["mamba"])
        x, _ = attention_layer(x, lp["window"], 2 * i + 1,
                               cfg["sliding_window"])
        return x, None

    x, _ = jax.lax.scan(self_pair, x,
                        (layers["seg0"], jnp.arange(half // 2)))
    middle = jax.tree_util.tree_map(lambda a: a[0], layers["seg1"])
    x, m = mamba_layer(x, middle["mamba"])
    x, kv = attention_layer(x, middle["full"], jnp.asarray(half + 1))

    def cross_pair(x, inp):
        lp, i = inp
        g = lp["gmu"]
        gate = jax.nn.silu(_mm(norm(x, g), g["gmu_in"], quant))
        x = _mlp(cfg, quant, x + _mm(gate * m, g["gmu_out"], quant), g)
        x, _ = attention_layer(x, lp["cross"], half + 3 + 2 * i, kv=kv)
        return x, None

    x, _ = jax.lax.scan(cross_pair, x,
                        (layers["seg2"], jnp.arange(half // 2 - 1)))
    return x


def _head_logps(quant, h, embed, nxt):
    """log p of ``nxt`` (M,) under ``softmax(h E^T)``, h (M, d): the head
    and the log-sum-exp over the vocabulary in blocks of ``V_BLOCK`` rows,
    the running maximum carried."""
    v = embed.shape[0]
    vb = min(V_BLOCK, v)
    if v % vb:
        raise ValueError(f"vocabulary {v} is no multiple of {vb}")
    hq = _fake_quant(h, -1, quant)

    def block(i, carry):
        top, total, mine = carry
        w = jax.lax.dynamic_slice(embed, (i * vb, 0), (vb, embed.shape[1]))
        logits = jnp.dot(hq, _fake_quant(w.astype(F32), 1, quant).T,
                         precision=HIGHEST)
        new_top = jnp.maximum(top, logits.max(axis=-1))
        total = (total * jnp.exp(top - new_top)
                 + jnp.exp(logits - new_top[:, None]).sum(axis=-1))
        at = nxt - i * vb
        here = jnp.take_along_axis(
            logits, jnp.clip(at, 0, vb - 1)[:, None], axis=-1)[:, 0]
        mine = jnp.where((at >= 0) & (at < vb), here, mine)
        return new_top, total, mine

    m = h.shape[0]
    top, total, mine = jax.lax.fori_loop(
        0, v // vb, block,
        (jnp.full((m,), -jnp.inf, F32), jnp.zeros((m,), F32),
         jnp.zeros((m,), F32)))
    return mine - top - jnp.log(total)


def _hidden(weights, cfg, quant, tokens):
    x = weights["embed"][tokens].astype(F32)
    return jax.vmap(lambda row: _sequence_layers(cfg, quant, row,
                                                 weights["layers"]))(x)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant", "n_pos"))
def _score(weights, tokens, starts, cfg_items, quant, n_pos):
    cfg = dict(cfg_items)
    x = _hidden(weights, cfg, quant, tokens)
    # logits only where a served token was predicted
    s = tokens.shape[1]
    at = starts[:, None] + jnp.arange(n_pos)[None, :]            # (R, n_pos)
    rows = jnp.take_along_axis(x, jnp.clip(at, 0, s - 1)[..., None], axis=1)
    h = _layer_norm(rows, {"n": weights["final_norm"],
                           "n_bias": weights["final_norm_bias"][0]}, "n",
                    cfg["layer_norm_eps"])
    nxt = jnp.take_along_axis(tokens, jnp.clip(at + 1, 0, s - 1), axis=1)
    logp = _head_logps(quant, h.reshape(-1, h.shape[-1]), weights["embed"],
                       nxt.reshape(-1))
    return logp.reshape(at.shape)


def _items(cfg: dict) -> tuple:
    """The keys the equations read; the four ``mamba_*`` ones are not in
    the published config.json (the configuration file's ``assumed``)."""
    return tuple((k, cfg[k]) for k in KEYS)


def logits(weights, cfg: dict, tokens):
    """Logits at every position, (R, S, V) float32, the head whole: the
    CPU tests' comparison at a small size."""
    cfg = dict(_items(cfg))
    with jax.default_matmul_precision("highest"):
        x = _hidden(weights, cfg, None, jnp.asarray(tokens, jnp.int32))
        h = _layer_norm(x, {"n": weights["final_norm"],
                            "n_bias": weights["final_norm_bias"][0]}, "n",
                        cfg["layer_norm_eps"])
        return jnp.einsum("rsd,vd->rsv", h, weights["embed"].astype(F32),
                          precision=HIGHEST)


def served_logps(weights, cfg: dict, tokens, starts, n_pos: int,
                 quant: Optional[str] = None):
    """For each row of ``tokens`` (R, S) int32 (prompt then served tokens,
    right padded; causal, so padding is inert for the positions before it):
    log p of the token at ``starts[r] + 1 + j`` given everything before it,
    j < n_pos. ``starts[r]`` is the prompt's last position."""
    with jax.default_matmul_precision("highest"):
        return _score(weights, jnp.asarray(tokens, jnp.int32),
                      jnp.asarray(starts, jnp.int32), _items(cfg), quant,
                      n_pos)
