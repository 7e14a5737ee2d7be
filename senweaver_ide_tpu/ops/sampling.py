"""Token sampling: temperature, top-k, top-p — jit/vmap-friendly.

All transforms are static-shape (top-p uses a sorted-cumsum mask rather than
dynamic truncation) so they compile once and run inside decode loops.

The nucleus cut reads only the VALUES of its ``cutoff`` largest
probabilities (their cumulative sum gives a threshold, the mask is
``probs < threshold``), so it finds them in two stages and nobody sorts
the vocabulary: each group of 128 columns gives its maximum, the
``cutoff`` groups with the largest maxima are gathered whole, and the
candidates are in those; the same cut again with groups of 16 leaves
2,048 columns to ``lax.top_k`` (``_top_values``, ``_TOP_P_GROUPS``). A
vocabulary too narrow for a cut to leave clearly fewer columns keeps the
single ``lax.top_k``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30
# Columns a group, stage by stage, of the selection in ``_top_values``. By
# measurement on a TPU v5e at f32[48, 151936], k 128 (PERF.md §6, PR 41).
_TOP_P_GROUPS = (128, 16)


def apply_temperature(logits: jnp.ndarray, temperature: float) -> jnp.ndarray:
    return logits / jnp.maximum(temperature, 1e-6)


def apply_top_k(logits: jnp.ndarray, k: int) -> jnp.ndarray:
    """Mask all but the k highest logits (static k)."""
    if k <= 0:
        return logits
    kth = jnp.sort(logits, axis=-1)[..., -k][..., None]
    return jnp.where(logits < kth, NEG_INF, logits)


def _top_values(x: jnp.ndarray, k: int,
                groups: Tuple[int, ...] = _TOP_P_GROUPS) -> jnp.ndarray:
    """The ``k`` largest values along the last axis, descending: what
    ``lax.top_k(x, k)[0]`` returns, bit for bit, with no sort as wide as
    ``x``.

    Two stages: the columns are split into groups of ``groups[0]`` (the
    last one padded with -inf) and each group gives its maximum; the
    ``k`` groups with the largest maxima are gathered whole, and the
    ``k`` largest of their ``k * group`` columns are the result. Exact: a
    group that holds one of the ``k`` largest values has a maximum at
    least as large, and at most ``k`` groups can, so every value above
    the ``k``-th largest is gathered. Where values tie with the ``k``-th
    largest, either every group that holds one is taken or all ``k``
    taken groups hold one: the gathered columns hold enough copies, and
    equal values are one value, so the result does not depend on which
    of the tied groups was taken.

    The gathered columns are cut the same way again with the next, smaller
    group of ``groups``, and ``lax.top_k`` ends it. A cut is made only
    where it leaves clearly fewer columns to sort than it found
    (``2 * (n_groups + k * group) <= width``): a narrow ``x`` is one
    ``lax.top_k``. Widths are static, so the form is chosen when the
    program is traced."""
    for group in groups:
        width = x.shape[-1]
        n_groups = -(-width // group)
        if 2 * (n_groups + k * group) > width:     # else n_groups >= 2 * k
            continue
        if n_groups * group != width:
            pad = [(0, 0)] * (x.ndim - 1) + [(0, n_groups * group - width)]
            x = jnp.pad(x, pad, constant_values=-jnp.inf)
        grouped = x.reshape(*x.shape[:-1], n_groups, group)
        _, best = jax.lax.top_k(grouped.max(axis=-1), k)
        held = jnp.take_along_axis(grouped, best[..., None], axis=-2)
        x = held.reshape(*x.shape[:-1], k * group)
    return jax.lax.top_k(x, k)[0]


def apply_top_p(logits: jnp.ndarray, p: float,
                cutoff: Optional[int] = None) -> jnp.ndarray:
    """Nucleus sampling mask: keep the smallest set of tokens with cumulative
    probability ≥ p.

    ``cutoff`` bounds the candidate set to the ``cutoff`` largest
    probabilities instead of fully sorting the vocab — a full 152k-wide
    sort costs milliseconds PER DECODE STEP on TPU. Probabilities come
    from the full-vocab softmax, so the mask is exact whenever the
    p-nucleus fits inside the cutoff (p=0.95 nuclei are typically tens of
    tokens); a nucleus wider than the cutoff is clipped to it.

    Only the candidates' values are read (never their indices), so they
    come from ``_top_values``: group maxima first, then the ``cutoff``
    groups that can hold them, and ``lax.top_k`` over what those hold. It
    returns the values a ``lax.top_k`` over the whole vocabulary returns,
    ties included (a tie changes which group is read, not which values
    come back), so the mask is the same bit for bit; a vocabulary too
    narrow for two stages to pay (under ~4,700 columns at the default
    cutoff) keeps the single ``lax.top_k``. ``cutoff=None`` is the full
    sort."""
    if cutoff is None:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(sorted_probs, axis=-1)
        keep_sorted = (cum - sorted_probs) < p
        kth = jnp.min(jnp.where(keep_sorted, sorted_logits, jnp.inf),
                      axis=-1, keepdims=True)
        return jnp.where(logits < kth, NEG_INF, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    top_probs = _top_values(probs, cutoff)               # desc-sorted
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
