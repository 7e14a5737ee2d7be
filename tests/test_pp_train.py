"""Pipeline-parallel TRAINING (round-1 review: pp was forward-biased —
no test ran a training step through the pipelined path)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from senweaver_ide_tpu.models import get_config, init_params
from senweaver_ide_tpu.parallel import (MeshConfig, make_named_mesh,
                                        make_pp_train_state, pp_train_step)
from senweaver_ide_tpu.training import make_train_state, train_step


@pytest.fixture(scope="module")
def pp_mesh():
    return make_named_mesh({"pp": 2}, devices=jax.devices()[:2])


def test_pp_train_step_matches_single_device(pp_mesh):
    """One GRPO update through the pp=2 pipeline == the plain train_step:
    same loss, same updated params (stage-split reshape aside)."""
    cfg = get_config("tiny-test")
    params = init_params(cfg, jax.random.PRNGKey(0))
    b, s = 4, 24
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, 512)
    mask = jnp.ones((b, s), jnp.bool_)
    rewards = jnp.linspace(-1.0, 1.0, b)
    gids = jnp.zeros((b,), jnp.int32)

    pp_state = make_pp_train_state(cfg, jax.random.PRNGKey(0), pp_mesh,
                                   learning_rate=1e-3, params=params)
    ref_state = make_train_state(cfg, jax.random.PRNGKey(0), None,
                                 learning_rate=1e-3, params=params)

    pp_state, pp_m = pp_train_step(pp_state, cfg, pp_mesh, tokens, mask,
                                   rewards, gids, n_microbatches=2)
    ref_state, ref_m = train_step(ref_state, cfg, None, tokens, mask,
                                  rewards, gids)
    assert np.isclose(float(pp_m["loss"]), float(ref_m["loss"]), atol=1e-5)
    assert np.isclose(float(pp_m["grad_norm"]), float(ref_m["grad_norm"]),
                      rtol=1e-4)
    # Updated params match after undoing the stage split.
    L = cfg.num_layers
    for name, ref_leaf in ref_state.params["layers"].items():
        pp_leaf = np.asarray(pp_state.params["layers"][name])
        merged = pp_leaf.reshape((L,) + pp_leaf.shape[2:])
        np.testing.assert_allclose(merged, np.asarray(ref_leaf),
                                   atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(pp_state.params["embed"]),
                               np.asarray(ref_state.params["embed"]),
                               atol=2e-5, rtol=2e-5)
    assert int(pp_state.step) == 1


def _assert_1f1b_matches_gpipe(cfg, mesh, *, key, b, s, n_microbatches,
                               masked_prefix=0):
    """Shared parity contract: 1F1B == GPipe on loss, grad_norm, and
    EVERY param group (per-layer, embed scatter, lm_head/norm — the
    first/last-stage specials)."""
    params = init_params(cfg, jax.random.PRNGKey(key))
    tokens = jax.random.randint(jax.random.PRNGKey(key + 1), (b, s), 0,
                                512)
    mask = jnp.ones((b, s), jnp.bool_)
    if masked_prefix:
        mask = mask.at[:, :masked_prefix].set(False)
    rewards = jnp.linspace(-1.0, 1.0, b)
    gids = jnp.asarray(np.repeat(np.arange(b // 2), 2), jnp.int32)

    st_g = make_pp_train_state(cfg, jax.random.PRNGKey(key), mesh,
                               learning_rate=1e-3, params=params)
    st_i = make_pp_train_state(cfg, jax.random.PRNGKey(key), mesh,
                               learning_rate=1e-3, params=params)
    st_g, m_g = pp_train_step(st_g, cfg, mesh, tokens, mask, rewards,
                              gids, n_microbatches=n_microbatches,
                              schedule="gpipe")
    st_i, m_i = pp_train_step(st_i, cfg, mesh, tokens, mask, rewards,
                              gids, n_microbatches=n_microbatches,
                              schedule="1f1b")
    assert np.isclose(float(m_i["loss"]), float(m_g["loss"]), atol=1e-5)
    assert np.isclose(float(m_i["grad_norm"]), float(m_g["grad_norm"]),
                      rtol=1e-4)
    for name, g_leaf in st_g.params["layers"].items():
        np.testing.assert_allclose(np.asarray(st_i.params["layers"][name]),
                                   np.asarray(g_leaf), atol=2e-5,
                                   rtol=2e-5)
    for group in ("embed", "lm_head", "final_norm"):
        np.testing.assert_allclose(np.asarray(st_i.params[group]),
                                   np.asarray(st_g.params[group]),
                                   atol=2e-5, rtol=2e-5)


def test_pp_1f1b_matches_gpipe(pp_mesh):
    """The 1F1B schedule computes the SAME update as GPipe autodiff —
    same loss, same grads (via grad_norm), same updated params — while
    bounding resident activations by pipeline depth (min(M, 2K) saved
    stage inputs) instead of all M microbatches."""
    _assert_1f1b_matches_gpipe(get_config("tiny-test"), pp_mesh, key=4,
                               b=8, s=20, n_microbatches=4,
                               masked_prefix=4)


def test_pp_1f1b_fewer_microbatches_than_depth(pp_mesh):
    """M < K degenerate case still computes the right update (buffer is
    M slots; schedule is mostly bubble — correctness must not depend on
    steady state being reached)."""
    cfg = get_config("tiny-test")
    params = init_params(cfg, jax.random.PRNGKey(6))
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 12), 0, 512)
    mask = jnp.ones((2, 12), jnp.bool_)
    rewards = jnp.asarray([1.0, -1.0])
    gids = jnp.zeros((2,), jnp.int32)
    st_g = make_pp_train_state(cfg, jax.random.PRNGKey(6), pp_mesh,
                               params=params)
    st_i = make_pp_train_state(cfg, jax.random.PRNGKey(6), pp_mesh,
                               params=params)
    st_g, m_g = pp_train_step(st_g, cfg, pp_mesh, tokens, mask, rewards,
                              gids, n_microbatches=1, schedule="gpipe")
    st_i, m_i = pp_train_step(st_i, cfg, pp_mesh, tokens, mask, rewards,
                              gids, n_microbatches=1, schedule="1f1b")
    assert np.isclose(float(m_i["loss"]), float(m_g["loss"]), atol=1e-5)


def test_pp_unknown_schedule_rejected(pp_mesh):
    cfg = get_config("tiny-test")
    st = make_pp_train_state(cfg, jax.random.PRNGKey(8), pp_mesh)
    with pytest.raises(ValueError, match="unknown pipeline schedule"):
        pp_train_step(st, cfg, pp_mesh,
                      jnp.zeros((2, 8), jnp.int32),
                      jnp.ones((2, 8), jnp.bool_),
                      jnp.zeros((2,)), jnp.zeros((2,), jnp.int32),
                      schedule="interleaved-nope")


def test_pp_two_steps_keep_improving(pp_mesh):
    """The pipelined optimizer actually descends (loss changes across
    steps, params keep moving)."""
    cfg = get_config("tiny-test")
    state = make_pp_train_state(cfg, jax.random.PRNGKey(2), pp_mesh,
                                learning_rate=1e-2)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0, 512)
    mask = jnp.ones((4, 16), jnp.bool_)
    rewards = jnp.asarray([1.0, -1.0, 0.5, -0.5])
    gids = jnp.asarray([0, 0, 1, 1], jnp.int32)
    p0 = np.asarray(jax.tree_util.tree_leaves(state.params)[0])
    state, m1 = pp_train_step(state, cfg, pp_mesh, tokens, mask, rewards,
                              gids)
    state, m2 = pp_train_step(state, cfg, pp_mesh, tokens, mask, rewards,
                              gids)
    p2 = np.asarray(jax.tree_util.tree_leaves(state.params)[0])
    assert int(state.step) == 2
    assert not np.allclose(p0, p2)
    assert np.isfinite(float(m2["loss"]))


def test_pp_1f1b_four_stages():
    """Deeper pipeline (K=4): the interleave schedule and ring-buffer
    sizing must hold when warmup/cooldown dominate (K=4 stages, M=4
    microbatches — 1 layer per stage on a 4-layer config); same shared
    parity contract as the K=2 case."""
    import dataclasses
    cfg = dataclasses.replace(get_config("tiny-test"), num_layers=4)
    mesh4 = make_named_mesh({"pp": 4}, devices=jax.devices()[:4])
    _assert_1f1b_matches_gpipe(cfg, mesh4, key=9, b=8, s=16,
                               n_microbatches=4)
