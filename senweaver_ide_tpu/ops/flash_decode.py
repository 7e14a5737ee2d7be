"""Flash-decode: Pallas TPU kernel for single-token KV-cache attention.

The decode hot path attends one query per sequence against the whole
cache. The XLA einsum path (ops/attention.py) materializes the
(B, Hkv, rep, 1, Smax) fp32 score tensor in HBM every step; this kernel
streams KV blocks through VMEM against online-softmax scratch state, so
per-step HBM traffic is exactly one read of the (possibly int8-backed,
pre-dequantized) cache block stream plus the (rep, D) output — the
flash-attention recurrence specialized to Sq = 1 with per-sequence
lengths (continuous batching: every slot has its own fill level, and
blocks entirely past a slot's length are skipped, not just masked).

GQA layout: the ``rep = Hq/Hkv`` query heads sharing one KV head form
the sublane axis of a (rep_pad, D) tile, so the per-block matmuls are
(rep_pad, D) @ (D, block_kv) — MXU-shaped even at Sq = 1.

``lengths[b]`` counts VALID cache positions including the current
token's freshly-written k/v (the transformer writes-then-attends).

On non-TPU backends the kernel runs in interpret mode, same as
ops/flash_attention.py (CPU-simulated-mesh tests, SURVEY.md §4).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import MASKED_THRESHOLD as _MASKED
from .attention import NEG_INF


def _fd_kernel(lengths_ref, q_ref, k_ref, v_ref, out_ref,
               acc_ref, m_ref, l_ref, *, scale: float, block_kv: int,
               hkv: int, rep_pad: int):
    """One batch program per KV block; KV heads loop INSIDE the kernel.

    The head axis must stay whole in the K/V block specs: a
    single-head slice (block dim 1 over an Hkv-sized axis) violates the
    Mosaic tiling rule that a block's last two dims be 8/128-divisible
    or equal to the full array dims — observed as a lowering error for
    GQA caches with Hkv < 8 (Qwen: Hkv=2). Rows of the q tile /
    softmax state are the hkv·rep_pad flattened (kv-head, group)
    pairs; each head's (rep_pad, D) q rows hit only its own K/V slab.
    """
    bi = pl.program_id(0)
    ki = pl.program_id(1)
    n_kv = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    length = lengths_ref[bi]
    k_start = ki * block_kv

    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale   # (hkv*rep_pad, D)
        # Per-head scores, stacked back to the flattened row layout.
        s_heads = []
        for h in range(hkv):
            qh = q[h * rep_pad:(h + 1) * rep_pad]            # (rep_pad, D)
            kh = k_ref[0, :, h, :].astype(jnp.float32)       # (blk, D)
            s_heads.append(jax.lax.dot_general(
                qh, kh, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))         # (rep_pad, blk)
        s = jnp.concatenate(s_heads, axis=0)       # (hkv*rep_pad, blk)
        rows = s.shape[0]
        pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                 (rows, block_kv), 1)
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(s > _MASKED, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:] = corr * l_ref[:] + jnp.sum(p, axis=-1, keepdims=True)
        pv_heads = []
        for h in range(hkv):
            ph = p[h * rep_pad:(h + 1) * rep_pad]
            vh = v_ref[0, :, h, :].astype(jnp.float32)       # (blk, D)
            pv_heads.append(jax.lax.dot_general(
                ph, vh, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))         # (rep_pad, D)
        acc_ref[:] = corr * acc_ref[:] + jnp.concatenate(pv_heads, axis=0)
        m_ref[:] = m_new

    # Blocks wholly past this slot's fill level contribute nothing — skip
    # the matmuls, not just the mask (short slots in a long-max pool pay
    # only for what they hold).
    pl.when(k_start < length)(_compute)

    @pl.when(ki == n_kv - 1)
    def _finalize():
        l = l_ref[:]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        out_ref[0] = (acc_ref[:] / safe_l).astype(out_ref.dtype)


def flash_decode(
    q: jax.Array,              # (B, 1, Hq, D) or (B, Hq, D)
    k_cache: jax.Array,        # (B, Smax, Hkv, D)
    v_cache: jax.Array,        # (B, Smax, Hkv, D)
    lengths: jax.Array,        # (B,) or scalar — valid positions incl. new
    *,
    block_kv: int = 128,
    interpret: Optional[bool] = None,
    allow_pad_copy: bool = False,
) -> jax.Array:
    """Single-step cache attention. Returns q's shape.

    ``Smax`` must be a multiple of ``block_kv``: padding here would copy
    BOTH full caches every decode step — more HBM traffic than the einsum
    path this kernel replaces. Size the cache at allocation time instead
    (``allow_pad_copy=True`` opts into the copy for tests/one-offs)."""
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    b, sq, hq, d = q.shape
    if sq != 1:
        raise ValueError(f"flash_decode is Sq=1 only, got Sq={sq}")
    _, smax, hkv, _ = k_cache.shape
    rep = hq // hkv
    rep_pad = max(8, -(-rep // 8) * 8)
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (b,))
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"

    # (B, 1, Hq, D) → (B, Hkv*rep_pad, D): the flattened (kv-head, group)
    # pairs are the sublane axis of each program's q tile.
    qg = q[:, 0].reshape(b, hkv, rep, d)
    if rep_pad != rep:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rep_pad - rep), (0, 0)))
    qg = qg.reshape(b, hkv * rep_pad, d)

    pad_kv = (-smax) % block_kv
    if pad_kv:
        if not allow_pad_copy:
            raise ValueError(
                f"Smax={smax} is not a multiple of block_kv={block_kv}; "
                f"padding would copy the whole KV cache per decode step. "
                f"Allocate the cache block-aligned, or pass "
                f"allow_pad_copy=True to accept the copy.")
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    n_kv = k_cache.shape[1] // block_kv

    kernel = functools.partial(_fd_kernel, scale=1.0 / (d ** 0.5),
                               block_kv=block_kv, hkv=hkv, rep_pad=rep_pad)
    rows = hkv * rep_pad
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, n_kv),
        in_specs=[
            pl.BlockSpec((1, rows, d), lambda b_, ki, _: (b_, 0, 0)),
            # Full head axis per block: a 1-wide head slice would break
            # the Mosaic last-two-dims tiling rule for Hkv < 8.
            pl.BlockSpec((1, block_kv, hkv, d),
                         lambda b_, ki, _: (b_, ki, 0, 0)),
            pl.BlockSpec((1, block_kv, hkv, d),
                         lambda b_, ki, _: (b_, ki, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, d), lambda b_, ki, _: (b_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, d), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * hq * smax * d,
            bytes_accessed=(k_cache.size + v_cache.size) * 2,
            transcendentals=b * hq * smax),
        interpret=interpret,
    )(lengths, qg, k_cache, v_cache)

    out = out.reshape(b, hkv, rep_pad, d)[:, :, :rep, :].reshape(
        b, 1, hq, d)
    return out[:, 0] if squeeze else out
