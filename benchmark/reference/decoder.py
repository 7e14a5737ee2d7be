"""Plain reference of the decoder-only models the benchmark serves
(Qwen2: GQA, q/k/v biases, tied embeddings; LLaMA/DeepSeek-Coder: MHA, no
biases, untied head): pre-norm RMSNorm, rotary embeddings in the half-rotation
layout, causal softmax attention, SwiGLU. float32 throughout at ``highest``
matmul precision; a few sequences at a time, one layer at a time (each
layer's weights are widened to float32 inside the scan body, so only one
layer is ever held widened). Follows the published architectures; no departures.

It imports nothing of the program and reads the published config keys from
the configuration file. ``quant`` replaces every matmul's inputs with values
rounded through a lower precision (per-channel absmax scales): the control
of the output check, see ``correct.py``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32


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
                   _fake_quant(w.astype(F32), 0, quant),
                   precision=jax.lax.Precision.HIGHEST)


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def _rope(x, theta):
    """x (S, H, Dh): rotate pairs (i, i + Dh/2) by position * theta^(-2i/Dh)."""
    s, _, dh = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg, quant, x, lp):
    s = x.shape[0]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg.get("head_dim", cfg["hidden_size"] // hq)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = _rms_norm(x, lp["attn_norm"], eps)
    q, k, v = (_mm(h, lp[n], quant) for n in ("wq", "wk", "wv"))
    if cfg["attention_bias"]:
        q, k, v = (a + lp[b].astype(F32)
                   for a, b in ((q, "bq"), (k, "bk"), (v, "bv")))
    q = _rope(q.reshape(s, hq, dh), theta)
    k = _rope(k.reshape(s, hkv, dh), theta)
    v = v.reshape(s, hkv, dh)
    rep = hq // hkv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k,
                        precision=jax.lax.Precision.HIGHEST) / (dh ** 0.5)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v,
                     precision=jax.lax.Precision.HIGHEST)
    x = x + _mm(att.reshape(s, hq * dh), lp["wo"], quant)
    h = _rms_norm(x, lp["mlp_norm"], eps)
    act = jax.nn.silu(_mm(h, lp["w_gate"], quant)) * _mm(h, lp["w_up"], quant)
    return x + _mm(act, lp["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant", "n_pos"))
def _score(weights, tokens, starts, cfg_items, quant, n_pos):
    cfg = dict(cfg_items)
    x = weights["embed"][tokens].astype(F32)                  # (R, S, D)
    x, _ = jax.lax.scan(
        lambda x, lp: (jax.vmap(lambda row: _layer(cfg, quant, row, lp))(x),
                       None), x, weights["layers"])
    head = (weights["embed"].T if cfg["tie_word_embeddings"]
            else weights["lm_head"])

    def one(args):
        row, toks, start = args
        # logits only where a served token was predicted
        at = start + jnp.arange(n_pos)
        h = _rms_norm(row[jnp.clip(at, 0, row.shape[0] - 1)],
                      weights["final_norm"], cfg["rms_norm_eps"])
        logp = jax.nn.log_softmax(_mm(h, head, quant), axis=-1)
        nxt = toks[jnp.clip(at + 1, 0, toks.shape[0] - 1)]
        return jnp.take_along_axis(logp, nxt[:, None], axis=-1)[:, 0]

    return jax.lax.map(one, (x, tokens, starts))


def served_logps(weights, cfg: dict, tokens, starts, n_pos: int,
                 quant: Optional[str] = None):
    """For each row of ``tokens`` (R, S) int32 (prompt then served tokens,
    right padded; causal, so padding is inert): log p of the token at
    ``starts[r] + 1 + j`` given everything before it, j < n_pos.
    ``starts[r]`` is the prompt's last position. The rows go through the
    layers together and through the head one at a time."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "hidden_size", "rms_norm_eps", "rope_theta", "attention_bias",
            "tie_word_embeddings")
    items = tuple((k, cfg[k]) for k in keys if k in cfg)
    with jax.default_matmul_precision("highest"):
        return _score(weights, jnp.asarray(tokens, jnp.int32),
                      jnp.asarray(starts, jnp.int32), items, quant, n_pos)
