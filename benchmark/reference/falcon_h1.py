"""Plain reference of Falcon-H1's decoder (``model_type: falcon_h1``): in
every block a Mamba-2 state-space mixer IN PARALLEL with GQA attention on
one normed input, their scaled outputs summed into the residual, then a
SwiGLU MLP; untied embedding and head; twelve muP multipliers. float32
throughout at ``highest`` matmul precision, no cache, no chunking of the
recurrence: the mixer is the token-by-token recurrence (``lax.scan`` over
positions), the plainest form and independent of the program's chunked one.

From the published config keys, with ``x`` the residual (``hidden_size``),
``eps = rms_norm_eps``, ``I = mamba_d_ssm``, ``G = mamba_n_groups``,
``N = mamba_d_state``, ``H = mamba_n_heads``, ``P = mamba_d_head``,
``K = mamba_d_conv``, heads ``h`` of group ``g(h) = h // (H / G)``:

  x_0 = E[token] * embedding_multiplier
  h   = RMSNorm_in(x)
  x   = x + ssm_out_multiplier SSM(h)
          + attention_out_multiplier Attn(attention_in_multiplier h)
  x   = x + MLP(RMSNorm_ff(x))
  MLP(u)  = ((u W_up) * silu(mlp_multipliers[0] (u W_gate))) W_down
            * mlp_multipliers[1]
  Attn(u) : q = u W_q, k = (u W_k) key_multiplier, v = u W_v; plain RoPE
            (rope_theta, half rotation, head_dim dims) on q, k; causal
            softmax(q k^T / sqrt(head_dim)) v, num_attention_heads heads
            sharing num_key_value_heads; W_o. No bias.
  logits = RMSNorm_final(x_L) W_head * lm_head_multiplier

  [z | xBC | dt] = (ssm_in_multiplier h) W_in * m
      m = ssm_multipliers[0] on z (I), [1] on x (I), [2] on B (G N),
          [3] on C (G N), [4] on dt (H)
  xBC_t = silu(sum_{j<K} w_conv[j] xBC_{t-(K-1)+j} + b_conv)   depthwise,
          causal, zeros before position 0
  [x | B | C] = xBC  (I, G N, G N);  x -> (H, P);  B, C -> (G, N)
  D_t  = softplus(dt_t + dt_bias);  a_t = exp(D_t A),  A = -exp(A_log)
  S_t[h] = a_t[h] S_{t-1}[h] + D_t[h] x_t[h] (x) B_t[g(h)],   S_{-1} = 0
  y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]
  SSM(h) = (RMSNorm_G(y_t * silu(z_t)) * w_norm) W_out     the norm over
           each of the G equal parts of the I values, no gain but w_norm

This file imports nothing of the program; it reads the program's parameter
tree by leaf name: ``embed``, ``lm_head``, ``final_norm`` and, stacked over
layers, ``attn_norm``, ``mlp_norm``, ``wq``, ``wk``, ``wv``, ``wo``,
``w_gate``, ``w_up``, ``w_down``, ``ssm_in``, ``ssm_conv_w`` (K, I + 2 G N),
``ssm_conv_b``, ``ssm_dt_bias``, ``ssm_A_log``, ``ssm_D``, ``ssm_norm``,
``ssm_out``.

Departures from the published description, each of no effect on the
mathematics unless it says so:
- what the config's keys leave open is settled as the configuration file's
  ``assumed`` says: the grouped form of the gated norm, no clamp on dt,
  leaf names.
- rotary pairs are (i, i + head_dim/2), not interleaved: a fixed permutation
  of the columns of W_q and W_k, and the weights are seeded.
- a sequence's queries attend in blocks of ``Q_BLOCK``, and the head and its
  log-sum-exp run over the vocabulary in blocks of ``V_BLOCK`` columns: the
  head widened to float32 would be 5.35 GB beside 9.65 GB of weights.
- ``quant`` (the output check's control) rounds both inputs of every matrix
  product through a lower precision; the conv, the recurrence and the norms
  are not products and stay float32.
- keys of no effect on these equations are not read: ``mamba_expand``
  (``mamba_d_ssm`` is given), ``mlp_expansion_factor``
  (``intermediate_size`` is given), ``mamba_chunk_size`` (a tile of the
  published kernel), ``attn_layer_indices`` null, ``mamba_use_mlp`` true,
  ``num_logits_to_keep``, the four ``*_bias`` false.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .decoder import F32, _fake_quant, _mm, _rms_norm, _rope

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
V_BLOCK = 16320
KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "rms_norm_eps", "rope_theta", "vocab_size",
        "mamba_d_ssm", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
        "mamba_n_groups", "mamba_d_conv", "embedding_multiplier",
        "lm_head_multiplier", "attention_in_multiplier",
        "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
        "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers")


def _attention(cfg, quant, u, lp):
    """Attn(u) of the docstring for one sequence: u (S, D) -> (S, D)."""
    s = u.shape[0]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, theta = cfg["head_dim"], float(cfg["rope_theta"])   # 1e11: no int32
    q = _rope(_mm(u, lp["wq"], quant).reshape(s, hq, dh), theta)
    k = _rope((_mm(u, lp["wk"], quant)
               * cfg["key_multiplier"]).reshape(s, hkv, dh), theta)
    v = _mm(u, lp["wv"], quant).reshape(s, hkv, dh)
    k, v = (jnp.repeat(a, hq // hkv, axis=1) for a in (k, v))
    qb = min(Q_BLOCK, s)
    pad = -s % qb

    def block(i):
        rows = i * qb + jnp.arange(qb)
        qi = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))[rows]
        scores = jnp.einsum("qhd,khd->hqk", qi, k,
                            precision=HIGHEST) / (dh ** 0.5)
        seen = jnp.arange(s)[None, :] <= rows[:, None]
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1),
                          v, precision=HIGHEST)

    att = jax.lax.map(block, jnp.arange((s + pad) // qb))
    return _mm(att.reshape(s + pad, hq * dh)[:s], lp["wo"], quant)


def _mixer(cfg, quant, h, lp):
    """SSM(h) of the docstring for one sequence: h (S, D) -> (S, D), the
    recurrence one token at a time."""
    s = h.shape[0]
    i, g, n = cfg["mamba_d_ssm"], cfg["mamba_n_groups"], cfg["mamba_d_state"]
    heads, p, k = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                   cfg["mamba_d_conv"])
    mz, mx, mb, mc, mdt = cfg["ssm_multipliers"]
    m = jnp.concatenate([jnp.full((i,), mz, F32), jnp.full((i,), mx, F32),
                         jnp.full((g * n,), mb, F32),
                         jnp.full((g * n,), mc, F32),
                         jnp.full((heads,), mdt, F32)])
    proj = _mm(h * cfg["ssm_in_multiplier"], lp["ssm_in"], quant) * m
    z, xbc, dt = (proj[:, :i], proj[:, i:2 * i + 2 * g * n],
                  proj[:, 2 * i + 2 * g * n:])
    w, b = lp["ssm_conv_w"].astype(F32), lp["ssm_conv_b"].astype(F32)
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(w[j] * padded[j:j + s] for j in range(k)) + b)
    x = xbc[:, :i].reshape(s, heads, p)
    bb = xbc[:, i:i + g * n].reshape(s, g, n)
    cc = xbc[:, i + g * n:].reshape(s, g, n)
    delta = jax.nn.softplus(dt + lp["ssm_dt_bias"].astype(F32))
    a = -jnp.exp(lp["ssm_A_log"].astype(F32))

    def token(state, inp):
        x_t, b_t, c_t, d_t = inp
        b_h = jnp.repeat(b_t, heads // g, axis=0)              # (H, N)
        c_h = jnp.repeat(c_t, heads // g, axis=0)
        state = (jnp.exp(d_t * a)[:, None, None] * state
                 + (d_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        return state, jnp.sum(state * c_h[:, None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((heads, p, n), F32),
                        (x, bb, cc, delta))
    y = y + lp["ssm_D"].astype(F32)[:, None] * x
    gated = (y.reshape(s, i) * jax.nn.silu(z)).reshape(s, g, i // g)
    gated = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    return _mm(gated.reshape(s, i) * lp["ssm_norm"].astype(F32),
               lp["ssm_out"], quant)


def _layer(cfg, quant, x, lp):
    eps = cfg["rms_norm_eps"]
    h = _rms_norm(x, lp["attn_norm"], eps)
    x = (x + cfg["ssm_out_multiplier"] * _mixer(cfg, quant, h, lp)
         + cfg["attention_out_multiplier"]
         * _attention(cfg, quant, h * cfg["attention_in_multiplier"], lp))
    u = _rms_norm(x, lp["mlp_norm"], eps)
    gate, down = cfg["mlp_multipliers"]
    act = _mm(u, lp["w_up"], quant) * jax.nn.silu(
        gate * _mm(u, lp["w_gate"], quant))
    return x + _mm(act, lp["w_down"], quant) * down


def _head_logps(cfg, quant, h, head, nxt):
    """log p of ``nxt`` (M,) under ``softmax(h W_head lm_head_multiplier)``,
    h (M, D): the head and the log-sum-exp over the vocabulary in blocks of
    ``V_BLOCK`` columns, the running maximum carried."""
    v = head.shape[1]
    vb = min(V_BLOCK, v)
    if v % vb:
        raise ValueError(f"vocabulary {v} is no multiple of {vb}")
    hq = _fake_quant(h, -1, quant)

    def block(i, carry):
        top, total, mine = carry
        w = jax.lax.dynamic_slice(head, (0, i * vb), (head.shape[0], vb))
        logits = jnp.dot(hq, _fake_quant(w.astype(F32), 0, quant),
                         precision=HIGHEST) * cfg["lm_head_multiplier"]
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


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant", "n_pos"))
def _score(weights, tokens, starts, cfg_items, quant, n_pos):
    cfg = dict(cfg_items)
    x = weights["embed"][tokens].astype(F32) * cfg["embedding_multiplier"]
    x, _ = jax.lax.scan(
        lambda x, lp: (jax.vmap(lambda row: _layer(cfg, quant, row, lp))(x),
                       None), x, weights["layers"])
    # logits only where a served token was predicted
    s = tokens.shape[1]
    at = starts[:, None] + jnp.arange(n_pos)[None, :]            # (R, n_pos)
    rows = jnp.take_along_axis(x, jnp.clip(at, 0, s - 1)[..., None], axis=1)
    h = _rms_norm(rows, weights["final_norm"], cfg["rms_norm_eps"])
    nxt = jnp.take_along_axis(tokens, jnp.clip(at + 1, 0, s - 1), axis=1)
    logp = _head_logps(cfg, quant, h.reshape(-1, h.shape[-1]),
                       weights["lm_head"], nxt.reshape(-1))
    return logp.reshape(at.shape)


def _items(cfg: dict) -> tuple:
    return tuple((k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
                 for k in KEYS)


def logits(weights, cfg: dict, tokens):
    """Logits at every position, (R, S, V) float32, the head whole: the
    CPU tests' comparison at a small size."""
    cfg = dict(_items(cfg))
    with jax.default_matmul_precision("highest"):
        x = (weights["embed"][jnp.asarray(tokens, jnp.int32)].astype(F32)
             * cfg["embedding_multiplier"])
        x, _ = jax.lax.scan(
            lambda x, lp: (jax.vmap(lambda row: _layer(cfg, None, row,
                                                       lp))(x), None),
            x, weights["layers"])
        h = _rms_norm(x, weights["final_norm"], cfg["rms_norm_eps"])
        return jnp.einsum("rsd,dv->rsv", h, weights["lm_head"].astype(F32),
                          precision=HIGHEST) * cfg["lm_head_multiplier"]


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
