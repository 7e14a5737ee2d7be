"""Normalization ops. RMSNorm with fp32 accumulation (TPU-friendly: the
reduction runs in fp32 regardless of activation dtype, output cast back)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray, bias: jnp.ndarray,
               eps: float = 1e-5) -> jnp.ndarray:
    """LayerNorm with gain and bias over the last axis, in float32."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    cen = xf - mean
    var = jnp.mean(cen * cen, axis=-1, keepdims=True)
    return (cen * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(dtype)
