"""Rotary position embeddings (RoPE), half-rotation layout.

Frequencies are computed in fp32 and applied in fp32 before casting back —
bf16 phase accumulation visibly degrades long-context quality on TPU.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax.numpy as jnp


def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jnp.ndarray:
    """(head_dim/2,) inverse frequencies."""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)


def scale_frequencies_llama3(inv_freq: jnp.ndarray, *, factor: float,
                             low_freq_factor: float, high_freq_factor: float,
                             original_max_position: int) -> jnp.ndarray:
    """Llama-3 NTK-by-parts frequency scaling (HF ``rope_type: llama3``).

    Long-wavelength components (period > original_max_position /
    low_freq_factor) are slowed by ``factor`` — they are the ones that
    would wrap past the original training window; short wavelengths
    (period < original / high_freq_factor) are left untouched; the band
    between interpolates linearly in 1/wavelength. This is what lets
    Llama-3.1/3.2 checkpoints serve 128k contexts from an 8k-trained
    base."""
    wavelen = 2.0 * math.pi / inv_freq
    smooth = ((original_max_position / wavelen) - low_freq_factor) / (
        high_freq_factor - low_freq_factor)
    smooth = jnp.clip(smooth, 0.0, 1.0)
    return (1.0 - smooth) * inv_freq / factor + smooth * inv_freq


def yarn_ramp_edges(head_dim: int, theta: float, *, beta_fast: float,
                    beta_slow: float,
                    original_max_position: int) -> Tuple[int, int]:
    """(lo, hi): the rotary pair that turns ``beta_fast`` times over the
    original window, rounded down, and the one that turns ``beta_slow``
    times, rounded up, both held to [0, head_dim - 1]. Pairs up to ``lo``
    keep their frequency, pairs from ``hi`` on are fully stretched."""
    def pair(turns: float) -> float:
        return (head_dim * math.log(original_max_position
                                    / (2.0 * math.pi * turns))
                / (2.0 * math.log(theta)))
    def held(i: int) -> int:
        return min(max(i, 0), head_dim - 1)
    return held(math.floor(pair(beta_fast))), held(math.ceil(pair(beta_slow)))


def scale_frequencies_yarn(inv_freq: jnp.ndarray, *, head_dim: int,
                           theta: float, factor: float, beta_fast: float,
                           beta_slow: float,
                           original_max_position: int) -> jnp.ndarray:
    """YaRN frequency scaling (HF ``rope_scaling.type: yarn``):
    ``inv_freq_i = (f_i / factor) ramp_i + f_i (1 - ramp_i)`` with
    ``ramp_i = clip((i - lo) / (hi - lo), 0, 1)`` over the pair index i and
    (lo, hi) of :func:`yarn_ramp_edges`."""
    lo, hi = yarn_ramp_edges(head_dim, theta, beta_fast=beta_fast,
                             beta_slow=beta_slow,
                             original_max_position=original_max_position)
    span = float(hi - lo) if hi > lo else 1e-3
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - lo)
                    / span, 0.0, 1.0)
    return inv_freq / factor * ramp + inv_freq * (1.0 - ramp)


def rope_cos_sin(positions: jnp.ndarray, head_dim: int,
                 theta: float = 10000.0,
                 scaling: Optional[object] = None,
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for integer ``positions`` of any shape → (..., head_dim/2).

    ``scaling``: a ``models.config.RopeScaling`` (or any object with its
    fields) enabling Llama-3-style frequency scaling, or a
    ``models.config.YarnScaling`` (told apart by its ``beta_fast``): YaRN's
    frequencies, cos and sin times m(``mscale``) / m(``mscale_all_dim``)."""
    inv_freq = rope_frequencies(head_dim, theta)
    magnitude = 1.0
    if scaling is not None and hasattr(scaling, "beta_fast"):
        inv_freq = scale_frequencies_yarn(
            inv_freq, head_dim=head_dim, theta=theta, factor=scaling.factor,
            beta_fast=scaling.beta_fast, beta_slow=scaling.beta_slow,
            original_max_position=scaling.original_max_position)
        magnitude = (scaling.magnitude(scaling.mscale)
                     / scaling.magnitude(scaling.mscale_all_dim))
    elif scaling is not None:
        inv_freq = scale_frequencies_llama3(
            inv_freq, factor=scaling.factor,
            low_freq_factor=scaling.low_freq_factor,
            high_freq_factor=scaling.high_freq_factor,
            original_max_position=scaling.original_max_position)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    if magnitude != 1.0:
        return jnp.cos(angles) * magnitude, jnp.sin(angles) * magnitude
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Rotate ``x`` of shape (..., seq, heads, head_dim) by per-position tables
    of shape (..., seq, head_dim/2) (broadcast over the heads axis)."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = xf[..., :half], xf[..., half:]
    c = cos[..., None, :]  # add heads axis
    s = sin[..., None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(dtype)
