"""Ring + Ulysses sequence parallelism vs dense attention, on the 8-device
CPU-simulated mesh (conftest)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from senweaver_ide_tpu.ops.attention import attention
from senweaver_ide_tpu.parallel import MeshConfig, make_mesh
from senweaver_ide_tpu.parallel.ring_attention import (
    chunk_attention_lse, make_ring_attention, make_ulysses_attention,
    merge_partials)


@pytest.fixture(scope="module")
def sp_mesh():
    return make_mesh(MeshConfig(sp=8))


def _rand_qkv(rng, b, s, hq, hkv, d):
    q = jnp.asarray(rng.standard_normal((b, s, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    return q, k, v


def test_chunk_merge_equals_full(rng):
    """Two-chunk lse merge == attention over the concatenated KV."""
    q, k, v = _rand_qkv(rng, 2, 64, 4, 2, 32)
    ref = attention(q, k, v, causal=True)
    o1, l1 = chunk_attention_lse(q, k[:, :32], v[:, :32], q_offset=0,
                                 kv_offset=0)
    o2, l2 = chunk_attention_lse(q, k[:, 32:], v[:, 32:], q_offset=0,
                                 kv_offset=32)
    merged, _ = merge_partials(o1, l1, o2, l2)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_ring_matches_dense(rng, sp_mesh):
    q, k, v = _rand_qkv(rng, 2, 128, 4, 2, 32)
    ref = attention(q, k, v, causal=True)
    ring = jax.jit(make_ring_attention(sp_mesh))
    np.testing.assert_allclose(np.asarray(ring(q, k, v)), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_ring_non_causal(rng, sp_mesh):
    q, k, v = _rand_qkv(rng, 1, 64, 2, 2, 16)
    ref = attention(q, k, v, causal=False)
    ring = jax.jit(make_ring_attention(sp_mesh, causal=False))
    np.testing.assert_allclose(np.asarray(ring(q, k, v)), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_ring_gradients_match_dense(rng, sp_mesh):
    q, k, v = _rand_qkv(rng, 1, 64, 2, 2, 16)
    ring = make_ring_attention(sp_mesh)

    g_ref = jax.grad(lambda q, k, v: jnp.sum(
        attention(q, k, v, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
    # jitted: eager, every op of the ring's backward is dispatched to the
    # eight devices one by one (197 s of tier-1, PR 42)
    g_ring = jax.jit(jax.grad(lambda q, k, v: jnp.sum(ring(q, k, v) ** 2),
                              argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ref, g_ring):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=1e-4, rtol=1e-4)


def test_ulysses_matches_dense(rng, sp_mesh):
    q, k, v = _rand_qkv(rng, 2, 128, 8, 8, 16)
    ref = attention(q, k, v, causal=True)
    uly = jax.jit(make_ulysses_attention(sp_mesh))
    np.testing.assert_allclose(np.asarray(uly(q, k, v)), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_ulysses_rejects_indivisible_heads(rng, sp_mesh):
    q, k, v = _rand_qkv(rng, 1, 64, 4, 2, 16)   # 4 heads, 8-way sp
    uly = make_ulysses_attention(sp_mesh)
    with pytest.raises(ValueError, match="divisible"):
        uly(q, k, v)
