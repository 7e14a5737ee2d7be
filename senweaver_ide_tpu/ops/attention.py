"""Attention ops: GQA causal attention with fp32 softmax.

The XLA path below is the reference implementation — einsum-formulated so XLA
tiles the two matmuls onto the MXU and fuses mask+softmax between them. The
Pallas flash-attention kernel (``ops/flash_attention.py``) replaces it for
long sequences; both share this call signature.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30  # large-but-finite: -inf breaks softmax rows that are fully masked
MASKED_THRESHOLD = NEG_INF * 0.5  # scores at/below this count as fully masked


def repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """(B, S, Hkv, D) → (B, S, Hkv*n_rep, D) for GQA."""
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    x = jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, n_rep, d))
    return x.reshape(b, s, h * n_rep, d)


def causal_mask(q_len: int, kv_len: int, q_offset,
                window: Optional[int] = None) -> jnp.ndarray:
    """Boolean mask, True = attend. ``q_offset`` is the absolute position of
    the first query — a scalar (traced or static) giving a (q_len, kv_len)
    mask, or a (B,) vector of per-slot offsets (continuous batching) giving
    (B, q_len, kv_len). ``window`` (sliding-window attention, the
    Mistral-family scheme) additionally bounds each query to its trailing
    ``window`` positions: kv ∈ (q - window, q]."""
    q_offset = jnp.asarray(q_offset)
    if q_offset.ndim == 1:
        q_pos = q_offset[:, None, None] + jnp.arange(q_len)[None, :, None]
        k_pos = jnp.arange(kv_len)[None, None, :]
    else:
        q_pos = q_offset + jnp.arange(q_len)[:, None]
        k_pos = jnp.arange(kv_len)[None, :]
    mask = k_pos <= q_pos
    if window is not None:
        mask = mask & (k_pos > q_pos - window)
    return mask


def attention(
    q: jnp.ndarray,            # (B, Sq, Hq, D)
    k: jnp.ndarray,            # (B, Skv, Hkv, D)
    v: jnp.ndarray,            # (B, Skv, Hkv, D)
    *,
    q_offset=0,
    kv_mask: Optional[jnp.ndarray] = None,   # (B, Skv) or (B, Sq, Skv),
                                             # True = valid
    causal: bool = True,
    window: Optional[int] = None,            # sliding-window width
    scale: Optional[float] = None,           # None: 1 / sqrt(D)
) -> jnp.ndarray:
    """Grouped-query causal attention. Returns (B, Sq, Hq, D).

    The GQA group folds into the einsums (q reshaped to (Hkv, rep)) — K/V
    are NEVER materialized at Hq heads. The repeat_kv formulation cost
    ~24× the cache bytes in decode (rep× heads × fp32 cast) and was the
    dominant share of the r1 decode-throughput gap.

    Numerics: fp32 inputs take the exact path (fp32 casts +
    Precision.HIGHEST — the default precision truncates fp32 operands to
    bf16 on TPU, breaking cache-vs-full decode parity in the fp32 test
    configs). Low-precision inputs (bf16 real models) stay in their native
    dtype on the MXU with fp32 accumulation (preferred_element_type), with
    softmax in fp32 and probabilities cast back for the PV matmul — the
    same contract as the flash kernel.
    """
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    rep = hq // hkv
    qg = q.reshape(b, sq, hkv, rep, d)

    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.asarray(d, dtype=jnp.float32))
    exact = q.dtype == jnp.float32
    if exact:
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg.astype(jnp.float32),
                            k.astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
    else:
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k,
                            preferred_element_type=jnp.float32)
    scores = scores * scale                   # (B, Hkv, rep, Sq, Skv) fp32

    if window is not None and not causal:
        raise ValueError("sliding window requires causal attention")
    if causal:
        mask = causal_mask(sq, k.shape[1], q_offset, window)
        # (q, kv) → (1, 1, 1, q, kv); (B, q, kv) → (B, 1, 1, q, kv)
        mask = mask[None, None, None] if mask.ndim == 2 \
            else mask[:, None, None]
        scores = jnp.where(mask, scores, NEG_INF)
    if kv_mask is not None:
        if kv_mask.ndim == 3:     # per-query validity (ring-cache SWA)
            km = kv_mask[:, None, None, :, :]
        else:
            km = kv_mask[:, None, None, None, :]
        scores = jnp.where(km, scores, NEG_INF)

    probs = jax.nn.softmax(scores, axis=-1)
    if exact:
        out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
    else:
        out = jnp.einsum("bgrqk,bkgd->bqgrd", probs.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
    return out.reshape(b, sq, hq, d).astype(q.dtype)
