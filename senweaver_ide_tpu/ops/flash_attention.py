"""Flash attention — Pallas TPU kernel with online softmax.

Replaces ``ops.attention.attention`` (the XLA einsum path) for long
sequences: never materializes the (Sq, Skv) score matrix in HBM. Forward is
a Pallas kernel (grid over batch × heads × q-blocks; the innermost
"arbitrary" grid axis streams KV blocks through VMEM against running
(m, l, acc) scratch state); backward is a blockwise ``lax.scan`` recompute
from the saved logsumexp — O(Sq · block_kv) live memory, the standard
flash-attention backward algebra.

Internally everything runs in (B, H, S, D) layout so each VMEM block's
trailing two dims are (block_s, head_dim) — aligned to the (8, 128) fp32
tile. The public wrapper keeps the framework-wide (B, S, H, D) convention.

Parity contract: same semantics as ``ops.attention.attention`` (GQA, causal
with ``q_offset``, optional kv validity mask) plus ``kv_offset`` so ring
attention (``parallel/ring_attention.py``) can reuse the causal logic for
rotated KV chunks. On non-TPU backends the kernel runs in interpret mode
(CPU-simulated-mesh tests, SURVEY.md §4).

Reference role: the reference has no attention kernels at all — its "long
context" story is client-side pruning (``smartContextManager.ts``, SURVEY.md
§5). This kernel is what lets the TPU build train on full-length agent
trajectories instead of pruning them.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import MASKED_THRESHOLD as _MASKED
from .attention import NEG_INF


def _fa_kernel(offsets_ref, q_ref, k_ref, v_ref, bias_ref, out_ref, lse_ref,
               acc_ref, m_ref, l_ref, *, causal: bool,
               window: Optional[int], scale: float,
               block_q: int, block_kv: int):
    """One (batch, head, q-block) program; innermost grid axis = KV block."""
    ki = pl.program_id(3)
    n_kv = pl.num_programs(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_start = offsets_ref[0] + qi * block_q
    k_start = offsets_ref[1] + ki * block_kv

    def _compute():
        q = q_ref[0, 0, :, :].astype(jnp.float32) * scale
        k = k_ref[0, 0, :, :].astype(jnp.float32)
        v = v_ref[0, 0, :, :].astype(jnp.float32)

        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (block_q, block_kv)
        s = s + bias_ref[0, 0, :][None, :]
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
            mask = (k_start + cols) <= (q_start + rows)
            if window is not None:
                # SWA: kv in (q - window, q] (ops/attention.py semantics)
                mask = jnp.logical_and(
                    mask, (k_start + cols) > (q_start + rows) - window)
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:]                                # (block_q, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # Guard fully-masked rows: s == m_new == NEG_INF would exp() to 1.
        p = jnp.where(s > _MASKED, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)                   # (block_q, 1)
        l_ref[:] = corr * l_ref[:] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = corr * acc_ref[:] + jax.lax.dot_general(
            p, v, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    if causal:
        # Skip KV blocks strictly after the q-block's last row — and,
        # under SWA, blocks entirely before every row's window.
        live = k_start <= q_start + block_q - 1
        if window is not None:
            live = jnp.logical_and(
                live, k_start + block_kv - 1 >= q_start - window + 1)
        pl.when(live)(_compute)
    else:
        _compute()

    @pl.when(ki == n_kv - 1)
    def _finalize():
        l = l_ref[:]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        out_ref[0, 0, :, :] = (acc_ref[:] / safe_l).astype(out_ref.dtype)
        lse = jnp.where(l > 0.0, m_ref[:] + jnp.log(safe_l), NEG_INF)
        lse_ref[0, 0, 0, :] = lse[:, 0]


def _fa_forward(q, k, v, bias, offsets, *, causal, window, block_q,
                block_kv, interpret) -> Tuple[jax.Array, jax.Array]:
    """Pallas forward in (B, H, S, D) layout. bias (B, Skv) fp32 additive;
    offsets (2,) int32 [q_offset, kv_offset]. S axes must be multiples of the
    block sizes (wrapper pads). Returns (out (B,Hq,Sq,D), lse (B,Hq,Sq))."""
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    n_rep = hq // hkv
    grid = (b, hq, sq // block_q, skv // block_kv)
    scale = 1.0 / (d ** 0.5)
    # Mosaic requires each block's trailing two dims be (8, 128)-divisible or
    # equal to the array dims — give bias/lse a singleton sublane axis.
    bias3 = bias[:, None, :]                              # (B, 1, Skv)

    kernel = functools.partial(_fa_kernel, causal=causal, window=window,
                               scale=scale,
                               block_q=block_q, block_kv=block_kv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h, qi, ki, _: (b_, h, qi, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b_, h, qi, ki, _: (b_, h // n_rep, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b_, h, qi, ki, _: (b_, h // n_rep, ki, 0)),
            pl.BlockSpec((1, 1, block_kv),
                         lambda b_, h, qi, ki, _: (b_, 0, ki)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h, qi, ki, _: (b_, h, qi, 0)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda b_, h, qi, ki, _: (b_, h, 0, qi)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, 1, sq), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * hq * sq * skv * d,
            bytes_accessed=(q.size + k.size + v.size + q.size) * 2,
            transcendentals=b * hq * sq * skv),
        interpret=interpret,
    )(offsets, q, k, v, bias3)
    return out, lse[:, :, 0, :]


def _fa_backward_blockwise(q, k, v, bias, offsets, out, lse, g, *, causal,
                           window, block_kv):
    """Blockwise flash backward in (B, H, S, D) layout: ``lax.scan`` over KV
    blocks, recomputing p = exp(s − lse) per block. fp32 throughout."""
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    n_rep = hq // hkv
    scale = 1.0 / (d ** 0.5)

    # Keep K/V at Hkv heads and fold the GQA group into the einsums (q heads
    # reshaped to (Hkv, n_rep)) — repeating K/V to Hq in fp32 would multiply
    # live KV memory by n_rep for the whole scan.
    qf = q.astype(jnp.float32).reshape(b, hkv, n_rep, sq, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    gf = g.astype(jnp.float32).reshape(b, hkv, n_rep, sq, d)
    delta = jnp.sum(gf * out.astype(jnp.float32)
                    .reshape(b, hkv, n_rep, sq, d), axis=-1)
    lse_g = lse.reshape(b, hkv, n_rep, sq)

    n_kv = skv // block_kv
    kb = kf.reshape(b, hkv, n_kv, block_kv, d).transpose(2, 0, 1, 3, 4)
    vb = vf.reshape(b, hkv, n_kv, block_kv, d).transpose(2, 0, 1, 3, 4)
    bias_b = bias.reshape(b, n_kv, block_kv).transpose(1, 0, 2)
    q_pos = offsets[0] + jnp.arange(sq, dtype=jnp.int32)

    def body(dq, xs):
        ki, k_blk, v_blk, bias_blk = xs            # k/v_blk: (B,Hkv,blk,D)

        def compute(dq):
            s = jnp.einsum("bgrqd,bgkd->bgrqk", qf, k_blk,
                           precision=jax.lax.Precision.HIGHEST) * scale
            s = s + bias_blk[:, None, None, None, :]
            if causal:
                k_pos = (offsets[1] + ki * block_kv
                         + jnp.arange(block_kv, dtype=jnp.int32))
                mask = k_pos[None, :] <= q_pos[:, None]      # (Sq, block_kv)
                if window is not None:
                    mask = jnp.logical_and(
                        mask, k_pos[None, :] > q_pos[:, None] - window)
                s = jnp.where(mask[None, None, None, :, :], s, NEG_INF)
            # Same fully-masked guard as the forward kernel (lse == NEG_INF).
            p = jnp.where(s > _MASKED, jnp.exp(s - lse_g[..., None]), 0.0)
            dv_blk = jnp.einsum("bgrqk,bgrqd->bgkd", p, gf,
                                precision=jax.lax.Precision.HIGHEST)
            dp = jnp.einsum("bgrqd,bgkd->bgrqk", gf, v_blk,
                            precision=jax.lax.Precision.HIGHEST)
            ds = p * (dp - delta[..., None])
            dq = dq + jnp.einsum("bgrqk,bgkd->bgrqd", ds, k_blk,
                                 precision=jax.lax.Precision.HIGHEST) * scale
            dk_blk = jnp.einsum("bgrqk,bgrqd->bgkd", ds, qf,
                                precision=jax.lax.Precision.HIGHEST) * scale
            return dq, dk_blk, dv_blk

        def skip(dq):
            zero = jnp.zeros((b, hkv, block_kv, d), jnp.float32)
            return dq, zero, zero

        if causal:
            # Mirror the forward kernel's block skip: a KV block strictly
            # after the last query position contributes nothing (p == 0);
            # under SWA, nor does one entirely before every window.
            block_live = (offsets[1] + ki * block_kv) <= (offsets[0] + sq - 1)
            if window is not None:
                block_live = jnp.logical_and(
                    block_live,
                    offsets[1] + ki * block_kv + block_kv - 1
                    >= offsets[0] - window + 1)
            dq, dk_blk, dv_blk = jax.lax.cond(block_live, compute, skip, dq)
        else:
            dq, dk_blk, dv_blk = compute(dq)
        return dq, (dk_blk, dv_blk)

    dq0 = jnp.zeros((b, hkv, n_rep, sq, d), jnp.float32)
    dq, (dk_blks, dv_blks) = jax.lax.scan(
        body, dq0, (jnp.arange(n_kv, dtype=jnp.int32), kb, vb, bias_b))

    dq = dq.reshape(b, hq, sq, d)
    dk = dk_blks.transpose(1, 2, 0, 3, 4).reshape(b, hkv, skv, d)
    dv = dv_blks.transpose(1, 2, 0, 3, 4).reshape(b, hkv, skv, d)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            jnp.zeros_like(bias))


@functools.lru_cache(maxsize=None)
def _make_flash_fn(causal: bool, window: Optional[int], block_q: int,
                   block_kv: int, interpret: bool):
    @jax.custom_vjp
    def fa(q, k, v, bias, offsets):
        out, _ = _fa_forward(q, k, v, bias, offsets, causal=causal,
                             window=window,
                             block_q=block_q, block_kv=block_kv,
                             interpret=interpret)
        return out

    def fwd(q, k, v, bias, offsets):
        out, lse = _fa_forward(q, k, v, bias, offsets, causal=causal,
                               window=window,
                               block_q=block_q, block_kv=block_kv,
                               interpret=interpret)
        return out, (q, k, v, bias, offsets, out, lse)

    def bwd(res, g):
        q, k, v, bias, offsets, out, lse = res
        dq, dk, dv, dbias = _fa_backward_blockwise(
            q, k, v, bias, offsets, out, lse, g, causal=causal,
            window=window, block_kv=block_kv)
        return dq, dk, dv, dbias, None

    fa.defvjp(fwd, bwd)
    return fa


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pad_seq(x: jax.Array, axis: int, multiple: int,
             value: float = 0.0) -> jax.Array:
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def flash_attention(
    q: jax.Array,                       # (B, Sq, Hq, D)
    k: jax.Array,                       # (B, Skv, Hkv, D)
    v: jax.Array,                       # (B, Skv, Hkv, D)
    *,
    q_offset=0,
    kv_offset=0,
    kv_mask: Optional[jax.Array] = None,  # (B, Skv) True = valid
    causal: bool = True,
    window: Optional[int] = None,         # SWA width: kv in (q-window, q]
    block_q: int = 128,
    block_kv: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Drop-in replacement for ``ops.attention.attention``, plus
    ``kv_offset`` for rotated KV chunks (ring attention) and ``window``
    (sliding-window attention — in-kernel band mask with block skipping
    on BOTH edges, so FLOPs scale with window, not sequence). Pads both
    sequence axes to block multiples internally; offsets may be traced
    scalars. Returns (B, Sq, Hq, D) in q.dtype."""
    b, sq, hq, d = q.shape
    skv = k.shape[1]
    if window is not None and not causal:
        raise ValueError("sliding window requires causal attention")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q = min(block_q, _round_up(sq, 16))
    block_kv = min(block_kv, _round_up(skv, 16))

    # (B, S, H, D) → (B, H, S, D) so VMEM blocks are (seq, head_dim)-tiled.
    qt = _pad_seq(q.transpose(0, 2, 1, 3), 2, block_q)
    kt = _pad_seq(k.transpose(0, 2, 1, 3), 2, block_kv)
    vt = _pad_seq(v.transpose(0, 2, 1, 3), 2, block_kv)

    bias = jnp.zeros((b, skv), jnp.float32)
    if kv_mask is not None:
        bias = jnp.where(kv_mask, 0.0, NEG_INF)
    bias = _pad_seq(bias, 1, block_kv, value=NEG_INF)  # pad KV slots masked

    offsets = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                         jnp.asarray(kv_offset, jnp.int32)])

    fa = _make_flash_fn(causal, window, block_q, block_kv, interpret)
    out = fa(qt, kt, vt, bias, offsets)
    return out[:, :, :sq].transpose(0, 2, 1, 3)
