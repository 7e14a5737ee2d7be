"""Token sampling: temperature, top-k, top-p — jit/vmap-friendly.

All transforms are static-shape (top-p uses a sorted-cumsum mask rather than
dynamic truncation) so they compile once and run inside decode loops.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def apply_temperature(logits: jnp.ndarray, temperature: float) -> jnp.ndarray:
    return logits / jnp.maximum(temperature, 1e-6)


def apply_top_k(logits: jnp.ndarray, k: int) -> jnp.ndarray:
    """Mask all but the k highest logits (static k)."""
    if k <= 0:
        return logits
    kth = jnp.sort(logits, axis=-1)[..., -k][..., None]
    return jnp.where(logits < kth, NEG_INF, logits)


def apply_top_p(logits: jnp.ndarray, p: float,
                cutoff: Optional[int] = None) -> jnp.ndarray:
    """Nucleus sampling mask: keep the smallest set of tokens with cumulative
    probability ≥ p.

    ``cutoff`` bounds the candidate set to the top-``cutoff`` tokens via
    ``lax.top_k`` instead of fully sorting the vocab — a full 152k-wide
    sort costs milliseconds PER DECODE STEP on TPU. Probabilities come
    from the full-vocab softmax, so the mask is exact whenever the
    p-nucleus fits inside the cutoff (p=0.95 nuclei are typically tens of
    tokens); a nucleus wider than the cutoff is clipped to it."""
    if cutoff is None:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(sorted_probs, axis=-1)
        keep_sorted = (cum - sorted_probs) < p
        kth = jnp.min(jnp.where(keep_sorted, sorted_logits, jnp.inf),
                      axis=-1, keepdims=True)
        return jnp.where(logits < kth, NEG_INF, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    top_probs, _ = jax.lax.top_k(probs, cutoff)          # desc-sorted
    cum = jnp.cumsum(top_probs, axis=-1)
    keep = (cum - top_probs) < p
    pth = jnp.min(jnp.where(keep, top_probs, jnp.inf), axis=-1,
                  keepdims=True)
    return jnp.where(probs < pth, NEG_INF, logits)


def sample_token(
    logits: jnp.ndarray,           # (..., vocab)
    key: jax.Array,
    *,
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
    top_p_cutoff: Optional[int] = 128,
) -> jnp.ndarray:
    """Sample token ids from logits. temperature==0 → greedy argmax.

    top_k <= 0 and top_p outside (0, 1) mean DISABLED (top_p=0 used to
    fall through into the nucleus path, which both masked every token —
    uniform sampling — and paid a full-vocab sort on every decode step).
    ``top_p_cutoff`` selects the bounded-candidate nucleus path (see
    apply_top_p); pass None for the exact full-sort."""
    with jax.named_scope("sample"):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1)
        x = apply_temperature(logits, temperature)
        if top_k > 0:
            x = apply_top_k(x, top_k)
        if 0.0 < top_p < 1.0:
            x = apply_top_p(x, top_p, cutoff=top_p_cutoff)
        return jax.random.categorical(key, x, axis=-1)


def sampled_logprob(logits: jnp.ndarray, token: jnp.ndarray) -> jnp.ndarray:
    """Model log-prob of ``token`` under the UNMODIFIED distribution.

    logits (..., V) fp-any, token (...) int → (...) fp32. This is the
    behavior log-prob GRPO's importance ratio needs: the policy
    network's own log p(token), NOT the temperature/top-k/top-p-shaped
    sampling distribution — it must match ``token_logprobs`` computed
    by the trainer over the same network (training/grpo.py)."""
    with jax.named_scope("sample"):
        logz = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return jnp.take_along_axis(logz, token[..., None], axis=-1)[..., 0]
