"""The decode rows' one-pass kernel (``ops/state_step.py``) against the
plain pass it replaces on a TPU (``ops.ssm._advance_single``,
``ops.delta_rule._advance_single``), interpreted at tiny shapes; and the
set-up pin: a fused step lowered for a TPU holds ONE kernel body a form,
however many layers of a period call it.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from senweaver_ide_tpu import obs
from senweaver_ide_tpu.models import init_params
from senweaver_ide_tpu.models.config import get_config
from senweaver_ide_tpu.ops import delta_rule, paged_attention, ssm, state_step
from senweaver_ide_tpu.rollout import EngineConfig, RolloutEngine
from senweaver_ide_tpu.rollout.engine import _paged_fused_step
from senweaver_ide_tpu.rollout.paged_kv import init_paged_pool
from senweaver_ide_tpu.rollout.sampler import SampleParams

# seven rows (a multiple of nothing) over a leaf of nine, three layers
ROWS, STATE_ROWS, LAYERS, LAYER = 7, 9, 3, 1
# the flat batch: row 0 a decode row, row 1 a decode row at position 0 (its
# dirty state must not show), row 2 no entry, row 3 a run of three, row 4 a
# decode row whose write is dropped (no entry kept), row 5 a decode row,
# row 6 a run of two at position 0
SEQ_ROW = [0, 1, 3, 3, 3, 4, 5, 6, 6]
POSITIONS = [9, 0, 4, 5, 6, 7, 30, 0, 1]
KEEP = [True, True, True, True, True, False, True, True, True]
ONE = [0, 1, 5]


def _plan():
    return ssm.plan_runs(jnp.asarray(SEQ_ROW, jnp.int32),
                         jnp.asarray(POSITIONS, jnp.int32),
                         jnp.asarray(KEEP), ROWS)


def _draws(key, shapes):
    keys = jax.random.split(key, len(shapes))
    return [jax.random.normal(k, s, jnp.float32)
            for k, s in zip(keys, shapes)]


def _mamba2(key, heads, p, n, groups):
    t = len(SEQ_ROW)
    state, dt, x, b, c = _draws(key, [
        (LAYERS, STATE_ROWS, heads, p, n), (t, heads), (t, heads, p),
        (t, groups, n), (t, groups, n)])
    dt = jax.nn.softplus(dt)
    da = -dt * jnp.linspace(0.5, 8.0, heads)
    args = (da, dt, x.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
            c.astype(jnp.bfloat16))
    return state, ssm._advance_single, state_step.state_step_mamba2, args


def _delta(key, heads, k_dim, v_dim):
    t = len(SEQ_ROW)
    state, q, k, v, g, beta = _draws(key, [
        (LAYERS, STATE_ROWS, heads, k_dim, v_dim), (t, heads, k_dim),
        (t, heads, k_dim), (t, heads, v_dim), (t, heads, k_dim),
        (t, heads)])
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    # a strong decay and a correction at the edge of its range
    g = -jnp.abs(g).at[0].set(5.0)
    beta = (2.0 * jax.nn.sigmoid(beta)).at[6].set(1.999)
    return (state, delta_rule._advance_single, state_step.state_step_delta,
            (q, k, v, g, beta))


# a head block of 8 heads: two grid steps a row
FORMS = {
    "mamba2": functools.partial(_mamba2, heads=16, p=8, n=128, groups=2),
    "delta": functools.partial(_delta, heads=16, k_dim=8, v_dim=128),
}
BLOCK = 8 * 8 * 128 * 4


@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_kernel_is_the_plain_pass(form, monkeypatch):
    """Both forms against the file's own ``_advance_single``: the rows
    advanced and their outputs to float32 rounding; a row at position 0
    over a dirty state from zero; rows with no entry, with a dropped
    write or with a longer run, the leaf's snapshot rows and the other
    layers bit-equal on return."""
    monkeypatch.setattr(state_step, "BLOCK_BYTES", BLOCK)
    state, plain, kernel, args = FORMS[form](jax.random.PRNGKey(7))
    plan, layer = _plan(), jnp.asarray(LAYER, jnp.int32)
    assert np.flatnonzero(np.asarray(plan.row_len) == 1).tolist() == ONE
    want_state, want_out = plain(state, layer, plan, *args)
    got_state, got_out = kernel(state, layer, plan.row_last, plan.row_len,
                                plan.row_fresh, *args, interpret=True)
    np.testing.assert_allclose(got_out, want_out, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got_state, want_state, rtol=1e-5, atol=1e-6)
    assert float(jnp.max(jnp.abs(want_out[jnp.asarray(ONE)]))) > 0.1
    # the row at position 0 saw nothing of what it held
    zeroed, _ = kernel(state.at[LAYER, 1].set(0.0), layer, plan.row_last,
                       plan.row_len, plan.row_fresh, *args, interpret=True)
    np.testing.assert_array_equal(got_state[LAYER, 1], zeroed[LAYER, 1])
    still = np.ones((LAYERS, STATE_ROWS), bool)
    still[LAYER, ONE] = False
    np.testing.assert_array_equal(np.asarray(got_state)[still],
                                  np.asarray(state)[still])
    idle = np.setdiff1d(np.arange(ROWS), ONE)
    assert not np.asarray(got_out)[idle].any()


@pytest.mark.parametrize("form", sorted(FORMS))
def test_a_step_with_no_decode_row_changes_nothing(form, monkeypatch):
    """Every grid step rests on one block, which goes back as it came."""
    monkeypatch.setattr(state_step, "BLOCK_BYTES", BLOCK)
    state, _, kernel, args = FORMS[form](jax.random.PRNGKey(3))
    plan = ssm.plan_runs(jnp.asarray(SEQ_ROW, jnp.int32),
                         jnp.asarray(POSITIONS, jnp.int32),
                         jnp.asarray(KEEP) & (jnp.asarray(SEQ_ROW) == 3),
                         ROWS)
    got_state, got_out = kernel(state, jnp.asarray(LAYER, jnp.int32),
                                plan.row_last, plan.row_len, plan.row_fresh,
                                *args, interpret=True)
    np.testing.assert_array_equal(got_state, state)
    assert not np.asarray(got_out).any()


# ---- the set-up pin: one kernel body a step program ------------------------

@pytest.mark.parametrize("name,kernel,calls", [
    # two periods of (full, kda, kda, kda) in one scan: three call sites
    ("tiny-solar-open2-test", "state_step_delta", 3),
    # every block's mixer in one scan: one call site
    ("tiny-falcon-h1-test", "state_step_mamba2", 1),
])
def test_a_step_program_lowers_the_kernel_once(name, kernel, calls,
                                               monkeypatch):
    """``_paged_fused_step`` lowered for a TPU (no chip: the text alone):
    every layer of a period calls ONE private function, which holds the
    ONE Mosaic body of the program. A call site a layer would be lowered
    (table widths x token widths) times in every run's set-up: PR 44's 48
    lowerings, +8.2 s of the solar cell's ``setup_s`` (ROADMAP B5)."""
    monkeypatch.setattr(paged_attention, "on_tpu", lambda: True)
    monkeypatch.setattr(state_step, "_TRACED", set())
    c = get_config(name)
    s = jax.ShapeDtypeStruct
    params = jax.eval_shape(functools.partial(init_params, c),
                            jax.random.PRNGKey(0))
    pool = jax.eval_shape(lambda: init_paged_pool(
        c, 24, 8, state_rows=7, step_tokens=20))
    # a jit's cache of traces does not know that ``on_tpu`` was patched:
    # no trace from before comes in, and none of these stays behind
    jitted = (_paged_fused_step, state_step.state_step_delta,
              state_step.state_step_mamba2)
    for f in jitted:
        f.clear_cache()
    try:
        text = _paged_fused_step.trace(
            params, c, s((6, 20), jnp.int32), s((5, 4), jnp.int32), pool,
            s((2,), jnp.uint32), s((5,), jnp.int32),
            SampleParams(temperature=1.0, top_p=1.0), None).lower(
                lowering_platforms=("tpu",)).as_text()
        # the ops layer's choice, where the engine reads it
        assert state_step.traced(pool.rows.ssm.shape)
    finally:
        for f in jitted:
            f.clear_cache()
    assert len(re.findall(r"tpu_custom_call", text)) == 1
    assert len(re.findall(f'kernel_name = "{kernel}"', text)) == 1
    assert len(re.findall(rf"func\.func private @{kernel}\b", text)) == 1
    assert len(re.findall(rf"call @{kernel}\b", text)) == calls


# ---- how often it engages: the step's attr and the counter -----------------

@pytest.mark.parametrize("engaged", [False, True])
def test_the_step_counts_the_rows_it_advanced_in_one_pass(engaged,
                                                          monkeypatch):
    """``engine.step`` attr ``state_rows_one_pass`` beside ``ssm_rows`` and
    the counter ``senweaver_state_rows_one_pass_total``: the step's decode
    rows where a step program was traced with the kernel over the pool's
    state leaf (``state_step.traced``: the ops layer notes it when it
    traces the call, the engine holds no copy of the rule), 0 where the
    plain pass runs (here, on the CPU; ``engaged`` writes the note)."""
    monkeypatch.setattr(state_step, "_TRACED", set())
    obs._reset_for_tests()
    obs.enable()
    try:
        c = get_config("tiny-falcon-h1-test")
        e = RolloutEngine(
            init_params(c, jax.random.PRNGKey(0)), c, num_slots=4,
            max_len=64, sample=SampleParams(temperature=0.0, top_p=1.0),
            engine_config=EngineConfig(block_size=8, step_tokens=16))
        if engaged:
            state_step._TRACED.add(e.pool.rows.ssm.shape)
        for prompt in ([5, 6, 7], [8, 9, 10, 11, 12]):
            e.submit(prompt, max_new_tokens=5)
        e.run()
        steps = [s.attrs for s in obs.get_tracer().spans()
                 if s.name == "engine.step" and "entries" in s.attrs]
        rows = [a["state_rows_one_pass"] for a in steps]
        total = obs.get_registry().counter(
            "senweaver_state_rows_one_pass_total").value()
        if not engaged:
            assert not any(rows) and total == 0
            return
        assert rows == [a["decode_rows"] for a in steps]
        assert 0 < sum(rows) == total < sum(a["ssm_rows"] for a in steps)
    finally:
        obs._reset_for_tests()
