"""Attention-impl switch (ModelConfig.attn_impl): flash / ring / ulysses
wired into the MODEL and TRAINER paths must match the einsum reference —
this is the integration the round-1 review flagged as missing (flash/SP
were dead code outside their own unit tests)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from senweaver_ide_tpu.models import forward, get_config, init_params
from senweaver_ide_tpu.parallel import MeshConfig, make_mesh
from senweaver_ide_tpu.training import make_train_state, train_step
from senweaver_ide_tpu.training.data import pad_batch_for_mesh


@pytest.fixture(scope="module")
def cfg():
    return get_config("tiny-test")


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 512)


def _logits(params, cfg, tokens, **kw):
    logits, _ = forward(params, cfg, tokens, **kw)
    return np.asarray(logits)


def test_flash_forward_matches_einsum(cfg, params, tokens):
    ref = _logits(params, cfg, tokens)
    flash_cfg = dataclasses.replace(cfg, attn_impl="flash")
    out = _logits(params, flash_cfg, tokens)
    np.testing.assert_allclose(out, ref, atol=2e-4, rtol=2e-4)


def test_flash_grads_match_einsum(cfg, params, tokens):
    def loss(p, c):
        logits, _ = forward(p, c, tokens)
        return jnp.sum(jax.nn.log_softmax(logits) ** 2)

    flash_cfg = dataclasses.replace(cfg, attn_impl="flash")
    g_ref = jax.grad(loss)(params, cfg)
    g_flash = jax.grad(loss)(params, flash_cfg)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=2e-2, rtol=5e-3),
        g_ref, g_flash)


@pytest.mark.parametrize("impl,sp", [("ring", 4), ("ulysses", 2)])
def test_sp_forward_matches_einsum(cfg, params, tokens, impl, sp):
    # ulysses needs head counts (Hkv=2) divisible by sp.
    mesh = make_mesh(MeshConfig(dp=8 // sp, sp=sp))
    ref = _logits(params, cfg, tokens)
    sp_cfg = dataclasses.replace(cfg, attn_impl=impl)
    with mesh:
        out = _logits(params, sp_cfg, tokens, mesh=mesh)
    np.testing.assert_allclose(out, ref, atol=2e-4, rtol=2e-4)


def test_sp_impls_require_mesh(cfg, params, tokens):
    ring_cfg = dataclasses.replace(cfg, attn_impl="ring")
    with pytest.raises(ValueError, match="sp"):
        forward(params, ring_cfg, tokens)


def test_unknown_impl_rejected(cfg, params, tokens):
    bad = dataclasses.replace(cfg, attn_impl="fancy")
    with pytest.raises(ValueError, match="unknown attn_impl"):
        forward(params, bad, tokens)


def test_ring_train_step_matches_einsum(cfg):
    """Full GRPO train step on an sp=2 mesh (ring) vs single-mesh einsum:
    same loss, same updated params."""
    mesh = make_mesh(MeshConfig(dp=2, fsdp=2, sp=2))
    ring_cfg = dataclasses.replace(cfg, attn_impl="ring")
    b, s = 4, 17                      # s-1 = 16 divides sp
    tokens = jax.random.randint(jax.random.PRNGKey(2), (b, s), 0, 512)
    mask = jnp.ones((b, s), jnp.bool_)
    rewards = jnp.linspace(-1.0, 1.0, b)
    group_ids = jnp.zeros((b,), jnp.int32)

    state_ring = make_train_state(ring_cfg, jax.random.PRNGKey(3), mesh,
                                  learning_rate=1e-3)
    state_ref = make_train_state(cfg, jax.random.PRNGKey(3), None,
                                 learning_rate=1e-3)
    state_ring, m_ring = train_step(state_ring, ring_cfg, mesh, tokens, mask,
                                    rewards, group_ids)
    state_ref, m_ref = train_step(state_ref, cfg, None, tokens, mask,
                                  rewards, group_ids)
    assert np.isclose(float(m_ring["loss"]), float(m_ref["loss"]), atol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4),
        state_ring.params, state_ref.params)


def test_pad_batch_for_mesh():
    tokens = np.arange(3 * 10, dtype=np.int32).reshape(3, 10)
    mask = np.ones((3, 10), bool)
    rewards = np.asarray([1.0, -1.0, 0.5], np.float32)
    gids = np.asarray([0, 0, 1], np.int32)
    t, m, r, g = pad_batch_for_mesh(tokens, mask, rewards, gids,
                                    batch_multiple=4, seq_multiple=4,
                                    pad_id=7)
    assert t.shape == (4, 13)         # (13-1) % 4 == 0
    assert not m[3].any() and not m[:, 10:].any()
    assert r[3] == 0.0
    assert g[3] == 2                  # fresh singleton group
    np.testing.assert_array_equal(t[:3, :10], tokens)
    assert (t[3] == 7).all() and (t[:, 10:] == 7).all()
