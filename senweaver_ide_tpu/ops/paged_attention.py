"""Paged flash-decode: Pallas TPU kernel reading KV through block tables.

The paged engine (rollout/paged_kv.py) stores KV in a fixed pool of
``(block_size, Hkv, D)`` blocks; each token's sequence is a list of
physical block ids. The XLA gather path
(``models.transformer._paged_layer``) materializes a contiguous
``(T, MB*BS, Hkv, D)`` copy of every token's blocks in HBM each step;
this kernel instead DMAs each block straight from the pool into VMEM
using the **scalar-prefetched block table in the BlockSpec index maps**
— the `(token, logical_block) -> physical_block` translation happens at
DMA-issue time, so per-step HBM traffic is one streamed read of the
referenced blocks and no gathered intermediate.

Everything else is ``ops/flash_decode.py``: online-softmax scratch
(acc/m/l in VMEM), the GQA ``(kv_head, group)`` sublane layout, block
skipping past each token's fill level, interpret mode off-TPU.

``lengths[t]`` counts valid positions including the freshly-written
current token (write-then-attend, same contract as flash_decode).

**Dequant-fused variant** (``k_scale``/``v_scale`` passed): the pool
holds int8/fp8 payloads plus per-(block, position, head) f32 absmax
scales (rollout/paged_kv.py quantized ladder). The scales ride their
own scalar-prefetched block specs through the SAME table indirection,
and the rescale happens inside the per-block loop right after the
payload's f32 upcast — a quantized block is never materialized at full
width anywhere but the (BS, D) tile being consumed in VMEM, so HBM
traffic per step drops with the payload width. Note Mosaic's int8
min-tile is (32, 128) on the last two dims; sub-tile block_size/D
configs rely on relayout padding (and the interpret path, used by the
CPU test fleet, has no tiling constraint at all).
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


def _pfd_kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref, *refs,
                scale: float, block_size: int, hkv: int, rep_pad: int,
                quantized: bool):
    """One (token, logical block) program. The K/V refs already hold the
    PHYSICAL block — the index maps resolved ``tables_ref`` before the
    DMA — so the body only needs the logical position ``bi * block_size``
    for masking. KV heads loop inside (Mosaic tiling: the head axis must
    stay whole in the block specs for Hkv < 8). With ``quantized`` the
    ref list carries per-block scale tiles and the upcast to f32 is
    immediately rescaled — dequant fused into the block loop."""
    if quantized:
        ks_ref, vs_ref, out_ref, acc_ref, m_ref, l_ref = refs
    else:
        ks_ref = vs_ref = None
        out_ref, acc_ref, m_ref, l_ref = refs
    ti = pl.program_id(0)
    bi = pl.program_id(1)
    n_blk = pl.num_programs(1)

    @pl.when(bi == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    length = lengths_ref[ti]
    k_start = bi * block_size

    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale   # (hkv*rep_pad, D)
        s_heads = []
        for h in range(hkv):
            qh = q[h * rep_pad:(h + 1) * rep_pad]            # (rep_pad, D)
            kh = k_ref[0, :, h, :].astype(jnp.float32)       # (BS, D)
            if quantized:
                kh = kh * ks_ref[0, :, h][:, None]
            s_heads.append(jax.lax.dot_general(
                qh, kh, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))         # (rep_pad, BS)
        s = jnp.concatenate(s_heads, axis=0)       # (hkv*rep_pad, BS)
        rows = s.shape[0]
        pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                 (rows, block_size), 1)
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(s > _MASKED, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:] = corr * l_ref[:] + jnp.sum(p, axis=-1, keepdims=True)
        pv_heads = []
        for h in range(hkv):
            ph = p[h * rep_pad:(h + 1) * rep_pad]
            vh = v_ref[0, :, h, :].astype(jnp.float32)       # (BS, D)
            if quantized:
                vh = vh * vs_ref[0, :, h][:, None]
            pv_heads.append(jax.lax.dot_general(
                ph, vh, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))         # (rep_pad, D)
        acc_ref[:] = corr * acc_ref[:] + jnp.concatenate(pv_heads, axis=0)
        m_ref[:] = m_new

    # Logical blocks wholly past this token's fill level are dead table
    # padding — skip the matmuls entirely.
    pl.when(k_start < length)(_compute)

    @pl.when(bi == n_blk - 1)
    def _finalize():
        l = l_ref[:]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        out_ref[0] = (acc_ref[:] / safe_l).astype(out_ref.dtype)


def paged_flash_decode(
    q: jax.Array,              # (T, Hq, D) — one query per token entry
    k_pool: jax.Array,         # (NB, BS, Hkv, D) — one layer's block pool
    v_pool: jax.Array,         # (NB, BS, Hkv, D)
    tables: jax.Array,         # (T, MB) int32 — physical block per
                               # (token, logical block); dead entries
                               # may hold any in-range id
    lengths: jax.Array,        # (T,) int32 — valid positions incl. new
    *,
    k_scale: Optional[jax.Array] = None,   # (NB, BS, Hkv) f32 absmax
    v_scale: Optional[jax.Array] = None,   # scales for int8/fp8 pools
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Block-table cache attention for the flat paged token batch.
    Returns (T, Hq, D). The KV block size IS the kernel block size —
    the pool was allocated block-aligned, so there is never a pad-copy
    path here (the flash_decode ``Smax % block_kv`` failure mode cannot
    arise by construction). Passing ``k_scale``/``v_scale`` selects the
    dequant-fused variant for quantized pools."""
    t, hq, d = q.shape
    nb, bs, hkv, _ = k_pool.shape
    mb = tables.shape[1]
    rep = hq // hkv
    rep_pad = max(8, -(-rep // 8) * 8)
    quantized = k_scale is not None
    if quantized and v_scale is None:
        raise ValueError("k_scale passed without v_scale")
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (t,))
    tables = jnp.asarray(tables, jnp.int32)
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"

    # (T, Hq, D) → (T, Hkv*rep_pad, D): flattened (kv-head, group) pairs
    # on the sublane axis, same layout as flash_decode.
    qg = q.reshape(t, hkv, rep, d)
    if rep_pad != rep:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rep_pad - rep), (0, 0)))
    qg = qg.reshape(t, hkv * rep_pad, d)

    kernel = functools.partial(_pfd_kernel, scale=1.0 / (d ** 0.5),
                               block_size=bs, hkv=hkv, rep_pad=rep_pad,
                               quantized=quantized)
    rows = hkv * rep_pad
    # The paged trick: the physical block id comes from the scalar-
    # prefetched table at DMA-issue time. Full head axis per block
    # (Mosaic last-two-dims tiling rule). Scale tiles (quantized pools)
    # ride the same indirection.
    pool_spec = pl.BlockSpec(
        (1, bs, hkv, d), lambda ti, bi, tbl, lens: (tbl[ti, bi], 0, 0, 0))
    in_specs = [
        pl.BlockSpec((1, rows, d),
                     lambda ti, bi, tbl, lens: (ti, 0, 0)),
        pool_spec,
        pool_spec,
    ]
    operands = [qg, k_pool, v_pool]
    if quantized:
        scale_spec = pl.BlockSpec(
            (1, bs, hkv), lambda ti, bi, tbl, lens: (tbl[ti, bi], 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [jnp.asarray(k_scale, jnp.float32),
                     jnp.asarray(v_scale, jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # tables, lengths
        grid=(t, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rows, d),
                               lambda ti, bi, tbl, lens: (ti, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, d), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
        ],
    )
    kv_bytes = d * k_pool.dtype.itemsize + (4 if quantized else 0)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, rows, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=4 * t * hq * mb * bs * d,
            bytes_accessed=2 * t * mb * bs * hkv * kv_bytes,
            transcendentals=t * hq * mb * bs),
        interpret=interpret,
    )(tables, lengths, *operands)

    return out.reshape(t, hkv, rep_pad, d)[:, :, :rep, :].reshape(t, hq, d)
