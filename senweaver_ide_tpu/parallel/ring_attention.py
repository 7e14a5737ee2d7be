"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference handles long context purely by client-side pruning
(``smartContextManager.ts``: compaction at 55% usage, SURVEY.md §5); it has
no compute parallelism at all (§2.7). These are the TPU-native layers that
let the framework *train* on full-length agent trajectories instead:

- **Ring attention** (`ring_attention`): sequence axis sharded over the
  ``sp`` mesh axis; each device computes blockwise attention of its local
  query chunk against a KV chunk that rotates around the ring via
  ``lax.ppermute`` (XLA lowers it onto ICI neighbor links), merging partial
  results with a running log-sum-exp. Peak memory O(S²/sp²) per step and the
  KV transfer overlaps with the chunk attention compute.
- **Ulysses** (`ulysses_attention`): ``lax.all_to_all`` swaps the sharded
  axis from sequence to heads, computes full-sequence attention on 1/sp of
  the heads locally, and swaps back. Cheaper collectives for moderate S;
  requires head counts divisible by sp.

Both are plain differentiable JAX written for use INSIDE ``shard_map`` —
autodiff through ``ppermute``/``all_to_all`` gives the backward collectives
for free. ``make_ring_attention`` / ``make_ulysses_attention`` build the
shard_mapped callables for a given mesh.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.attention import MASKED_THRESHOLD as _MASKED
from ..ops.attention import NEG_INF, repeat_kv


def chunk_attention_lse(
    q: jax.Array,                  # (B, Sq, Hq, D)
    k: jax.Array,                  # (B, Skv, Hkv, D)
    v: jax.Array,                  # (B, Skv, Hkv, D)
    *,
    q_offset=0,
    kv_offset=0,
    kv_mask: Optional[jax.Array] = None,
    causal: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Attention over one KV chunk, returning the un-normalized pieces the
    ring merge needs: (out (B,Sq,Hq,D) fp32 — already softmax-normalized
    *within this chunk*, lse (B,Hq,Sq) fp32). Fully-masked rows return
    out = 0, lse = NEG_INF."""
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    k = repeat_kv(k, hq // hkv)
    v = repeat_kv(v, hq // hkv)

    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST) * scale
    if causal:
        q_pos = q_offset + jnp.arange(sq, dtype=jnp.int32)
        k_pos = kv_offset + jnp.arange(k.shape[1], dtype=jnp.int32)
        mask = k_pos[None, :] <= q_pos[:, None]
        s = jnp.where(mask[None, None], s, NEG_INF)
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :], s, NEG_INF)

    m = jnp.max(s, axis=-1)                               # (B, Hq, Sq)
    m_safe = jnp.maximum(m, _MASKED)
    p = jnp.where(s > _MASKED, jnp.exp(s - m_safe[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)                               # (B, Hq, Sq)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    l_safe = jnp.where(l > 0.0, l, 1.0)
    o = o / l_safe.transpose(0, 2, 1)[..., None]
    lse = jnp.where(l > 0.0, m_safe + jnp.log(l_safe), NEG_INF)
    return o, lse


def merge_partials(o_a, lse_a, o_b, lse_b):
    """Log-sum-exp merge of two chunk-normalized partial attentions.
    o: (B, S, H, D) fp32; lse: (B, H, S) fp32. NEG_INF lse = empty chunk."""
    lse_max = jnp.maximum(lse_a, lse_b)
    lse_max_safe = jnp.maximum(lse_max, _MASKED)
    w_a = jnp.where(lse_a > _MASKED, jnp.exp(lse_a - lse_max_safe), 0.0)
    w_b = jnp.where(lse_b > _MASKED, jnp.exp(lse_b - lse_max_safe), 0.0)
    tot = w_a + w_b
    tot_safe = jnp.where(tot > 0.0, tot, 1.0)
    wa = (w_a / tot_safe).transpose(0, 2, 1)[..., None]   # (B, S, H, 1)
    wb = (w_b / tot_safe).transpose(0, 2, 1)[..., None]
    o = o_a * wa + o_b * wb
    lse = jnp.where(tot > 0.0, lse_max_safe + jnp.log(tot_safe), NEG_INF)
    return o, lse


def ring_attention(
    q: jax.Array,                  # (B, S_local, Hq, D) — seq sharded on sp
    k: jax.Array,                  # (B, S_local, Hkv, D)
    v: jax.Array,                  # (B, S_local, Hkv, D)
    *,
    axis_name: str = "sp",
    causal: bool = True,
    kv_mask: Optional[jax.Array] = None,   # (B, S_local) local validity
) -> jax.Array:
    """Ring attention over the ``axis_name`` mesh axis. Must run inside
    ``shard_map`` with the sequence axis sharded on that axis. Device i's
    queries live at absolute positions [i·S_local, (i+1)·S_local)."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    s_local = q.shape[1]
    q_off = idx * s_local

    perm = [(j, (j + 1) % n) for j in range(n)]
    o = jnp.zeros(q.shape[:3] + (v.shape[-1],), jnp.float32)
    lse = jnp.full((q.shape[0], q.shape[2], s_local), NEG_INF, jnp.float32)

    k_cur, v_cur = k, v
    mask_cur = (kv_mask if kv_mask is not None
                else jnp.ones((q.shape[0], s_local), bool))
    for t in range(n):
        src = (idx - t) % n                    # chunk id currently held
        kv_off = src * s_local
        o_t, lse_t = chunk_attention_lse(
            q, k_cur, v_cur, q_offset=q_off, kv_offset=kv_off,
            kv_mask=mask_cur, causal=causal)
        o, lse = merge_partials(o, lse, o_t, lse_t)
        if t < n - 1:
            # Rotate KV (and its validity mask) to the next ring neighbor;
            # XLA schedules the ppermute to overlap with the next chunk.
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
            mask_cur = jax.lax.ppermute(mask_cur, axis_name, perm)
    return o.astype(q.dtype)


def ulysses_attention(
    q: jax.Array,                  # (B, S_local, Hq, D)
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = "sp",
    causal: bool = True,
) -> jax.Array:
    """Ulysses: all-to-all seq↔head reshard, full-sequence local attention on
    Hq/sp heads, reshard back. Head counts must divide by the axis size."""
    from ..ops.attention import attention

    n = jax.lax.axis_size(axis_name)
    if q.shape[2] % n or k.shape[2] % n:
        raise ValueError(
            f"ulysses needs head counts divisible by |{axis_name}|={n}; "
            f"got Hq={q.shape[2]}, Hkv={k.shape[2]}")
    a2a = functools.partial(jax.lax.all_to_all, axis_name=axis_name,
                            split_axis=2, concat_axis=1, tiled=True)
    q_full, k_full, v_full = a2a(q), a2a(k), a2a(v)       # (B, S, H/n, D)
    out = attention(q_full, k_full, v_full, causal=causal)
    return jax.lax.all_to_all(out, axis_name=axis_name, split_axis=1,
                              concat_axis=2, tiled=True)


def _seq_specs(mesh: Mesh, axis_name: str):
    in_spec = P(None, axis_name, None, None)
    return in_spec, in_spec


def make_ring_attention(mesh: Mesh, *, axis_name: str = "sp",
                        causal: bool = True, with_mask: bool = False):
    """shard_mapped ring attention over global (B, S, H, D) arrays whose
    sequence axis is sharded on ``axis_name``. With ``with_mask`` the
    callable takes a fourth (B, S) bool kv-validity argument (sharded the
    same way) — the per-chunk mask rotates around the ring with its KV."""
    spec, out_spec = _seq_specs(mesh, axis_name)
    fn = functools.partial(ring_attention, axis_name=axis_name, causal=causal)
    if with_mask:
        return shard_map(lambda q, k, v, m: fn(q, k, v, kv_mask=m),
                         mesh=mesh,
                         in_specs=(spec, spec, spec, P(None, axis_name)),
                         out_specs=out_spec, check_vma=False)
    return shard_map(lambda q, k, v: fn(q, k, v), mesh=mesh,
                     in_specs=(spec, spec, spec), out_specs=out_spec,
                     check_vma=False)


def make_ulysses_attention(mesh: Mesh, *, axis_name: str = "sp",
                           causal: bool = True):
    spec, out_spec = _seq_specs(mesh, axis_name)
    fn = functools.partial(ulysses_attention, axis_name=axis_name,
                           causal=causal)
    return shard_map(lambda q, k, v: fn(q, k, v), mesh=mesh,
                     in_specs=(spec, spec, spec), out_specs=out_spec,
                     check_vma=False)
