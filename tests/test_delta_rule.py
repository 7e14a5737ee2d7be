"""The gated delta rule with a decay a key channel (``ops/delta_rule.py``):
the chunked WY / UT form (``kda_dense``, ``kda_flat``'s long runs) and the
decode rows' one pass against the token-by-token step form (``kda_step``),
which is the equations as written. Everything float32 on the CPU; the two
forms differ by summation order alone (a chunk's triangular solve and
products against a sum a token), measured 2e-7 to 6e-7 on outputs of
magnitude ~0.6, so ``TOL`` = 5e-6; a state rounded to bfloat16 once is
1e-3 away.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from senweaver_ide_tpu.ops import delta_rule as dr
from senweaver_ide_tpu.ops import ssm as ssm_ops

TOL = 5e-6
H, K, V = 3, 16, 8


def inputs(key, t, g_scale=1.0, beta_lo=0.0):
    """q, k of the model's norms (k unit, q unit / sqrt(K)), v ~ N(0, 1),
    g = -g_scale softplus(n) <= 0, beta in (beta_lo, 2)."""
    ks = jax.random.split(key, 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (t, H, K))) / K ** 0.5
    k = unit(jax.random.normal(ks[1], (t, H, K)))
    v = jax.random.normal(ks[2], (t, H, V))
    g = -g_scale * jax.nn.softplus(jax.random.normal(ks[3], (t, H, K)))
    beta = beta_lo + (2.0 - beta_lo) * jax.nn.sigmoid(
        jax.random.normal(ks[4], (t, H)))
    return q, k, v, g, beta


def stepwise(q, k, v, g, beta, s0=None):
    """One sequence, one token at a time -> (o (T, H, V), the last state).
    """
    def token(s, x):
        return dr.kda_step(s, *x)

    s0 = jnp.zeros((H, K, V)) if s0 is None else s0
    s, o = jax.lax.scan(token, s0, (q, k, v, g, beta))
    return o, s


@pytest.mark.parametrize("t,chunk", [(150, 64), (64, 64), (37, 16), (5, 64)])
def test_kda_dense_equals_the_step_form(t, chunk):
    x = inputs(jax.random.PRNGKey(t), t)
    want, _ = stepwise(*x)
    got = dr.kda_dense(*(a[None] for a in x), chunk=chunk)[0]
    assert float(jnp.abs(got - want).max()) < TOL
    assert float(jnp.abs(want).max()) > 0.1


def test_a_chunk_of_strong_decays_neither_overflows_nor_loses_the_result():
    """g = -5 a token over a 64-token chunk: ``exp(-G)`` is e^320, past
    float32 after 18 tokens; the decays enter as ``exp(G_i - G_j)``, i >=
    j, and the chunk equals the step form."""
    x = list(inputs(jax.random.PRNGKey(1), 64))
    x[3] = jnp.full_like(x[3], -5.0)
    want, _ = stepwise(*x)
    got = dr.kda_dense(*(a[None] for a in x))[0]
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got - want).max()) < TOL
    assert float(jnp.exp(5.0 * 64)) == float("inf")


def test_beta_above_one_flips_the_state_along_k_and_the_forms_agree():
    """beta in (1, 2): ``I - beta k k^T`` has the eigenvalue 1 - beta < 0
    along k (``kda_allow_neg_eigval``); with no decay the state may grow
    where a plain delta rule (beta <= 1) cannot, and chunked and step form
    still agree."""
    x = list(inputs(jax.random.PRNGKey(2), 96, beta_lo=1.0))
    assert float(x[4].min()) > 1.0
    x[3] = jnp.zeros_like(x[3])
    want, s = stepwise(*x)
    got = dr.kda_dense(*(a[None] for a in x))[0]
    assert float(jnp.abs(got - want).max()) < TOL * max(
        1.0, float(jnp.abs(want).max()))
    # the rank-one factor alone: S' = (I - beta k k^T) S has k^T S' =
    # (1 - beta) k^T S
    k, beta = x[1][0, 0], x[4][0, 0]
    s1, _ = dr.kda_step(s[0], jnp.zeros((K,)), k, jnp.zeros((V,)),
                        jnp.zeros((K,)), beta)
    assert np.allclose(k @ s1, (1.0 - beta) * (k @ s[0]), atol=1e-5)


# ---- the flat batch over row-addressed state -------------------------------

ROWS = 5


def flat(runs, key, state=None, width=60):
    """One ``kda_flat`` call over ``runs`` = [(row, first position, n)],
    laid out one after the other and padded to ``width`` (one compiled
    program for all the tests) with entries not kept on row 0. -> (inputs
    by run, o by run, state')."""
    total = sum(n for _, _, n in runs)
    x = inputs(key, width)
    rows, pos, keep = [], [], []
    for row, start, n in runs:
        rows += [row] * n
        pos += list(range(start, start + n))
        keep += [True] * n
    pad = width - total
    rows, pos, keep = rows + [0] * pad, pos + [0] * pad, keep + [False] * pad
    plan = ssm_ops.plan_runs(jnp.asarray(rows, jnp.int32),
                             jnp.asarray(pos, jnp.int32),
                             jnp.asarray(keep), num_rows=ROWS)
    if state is None:
        state = jnp.zeros((2, ROWS + 1, H, K, V))
    o, state = jax.jit(dr.kda_flat, static_argnames=("chunk",))(
        *x, state, jnp.asarray(1), jnp.asarray(rows, jnp.int32), plan,
        chunk=16)
    out, at = [], 0
    for _row, _start, n in runs:
        out.append((tuple(a[at:at + n] for a in x), o[at:at + n]))
        at += n
    return out, o, state


def test_kda_flat_in_runs_of_uneven_length_equals_the_step_form():
    """Three calls: rows 1 and 3 prefill in runs of 37 and 5 (three chunks
    of 16 and a part of one), row 2 decodes beside them from a state an
    earlier call left; then every row continues, one of them by a run that
    ends a chunk exactly. Each row's outputs and final state are the step
    form's over the row's whole history."""
    calls = [[(2, 0, 9)],
             [(1, 0, 37), (2, 9, 1), (3, 0, 5)],
             [(3, 5, 1), (1, 37, 16), (2, 10, 1), (4, 0, 1)]]
    history = {r: [] for r in range(ROWS)}
    got = {r: [] for r in range(ROWS)}
    state = None
    for i, runs in enumerate(calls):
        out, _, state = flat(runs, jax.random.PRNGKey(10 + i), state)
        for (row, _s, _n), (x, o) in zip(runs, out):
            history[row].append(x)
            got[row].append(o)
    for row in (1, 2, 3, 4):
        x = tuple(jnp.concatenate(parts) for parts in zip(*history[row]))
        want, s = stepwise(*x)
        assert float(jnp.abs(jnp.concatenate(got[row]) - want).max()) < TOL
        assert float(jnp.abs(state[1, row] - s).max()) < TOL
    # the other layer and the rows with no entry were never written
    assert not np.asarray(state[0]).any() and not np.asarray(
        state[1, 0]).any()


def test_entries_not_kept_advance_nothing():
    """Padding (addressed to row 0, not kept) beside a run, and a call of
    padding alone, leave every row they name bit-equal; their outputs are
    zero."""
    _, _, state = flat([(0, 0, 7), (2, 0, 1)], jax.random.PRNGKey(3))
    before = np.asarray(state)
    assert np.abs(before[1, 0]).max() > 0
    _, o, state = flat([(1, 0, 3)], jax.random.PRNGKey(4), state)
    assert np.array_equal(np.asarray(state)[1, [0, 2]], before[1, [0, 2]])
    assert not np.asarray(o[3:]).any()
    _, o, state2 = flat([], jax.random.PRNGKey(5), state)
    assert np.array_equal(np.asarray(state2), np.asarray(state))
    assert not np.asarray(o).any()


@pytest.mark.parametrize("n", [1, 20])
def test_a_row_reused_at_position_0_sees_nothing_of_its_last_tenant(n):
    """Row 2 holds a state; a run that starts at position 0 in the same
    row (a decode row's one entry, or a prefill run) equals the step form
    from a zero state: no program clears a row."""
    _, _, state = flat([(2, 0, 30)], jax.random.PRNGKey(6))
    out, _, state = flat([(2, 0, n)], jax.random.PRNGKey(7), state)
    (x, o), = out
    want, s = stepwise(*x)
    assert float(jnp.abs(o - want).max()) < TOL
    assert float(jnp.abs(state[1, 2] - s).max()) < TOL


def test_a_bfloat16_state_fails_the_tolerance():
    """The state rounded to bfloat16 once between two runs moves the
    second run's outputs by 50 x TOL and more: the comparison tells a
    state held under float32."""
    out, _, state = flat([(1, 0, 24)], jax.random.PRNGKey(8))
    low = state.astype(jnp.bfloat16).astype(jnp.float32)
    (x, exact), = flat([(1, 24, 8)], jax.random.PRNGKey(9), state)[0]
    (_, rounded), = flat([(1, 24, 8)], jax.random.PRNGKey(9), low)[0]
    first = out[0][0]
    want, _ = stepwise(*(jnp.concatenate([a, b]) for a, b in zip(first, x)))
    assert float(jnp.abs(exact - want[24:]).max()) < TOL
    assert float(jnp.abs(rounded - want[24:]).max()) > 50 * TOL
