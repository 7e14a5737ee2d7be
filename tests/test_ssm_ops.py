"""``ops/ssm.py``: the Mamba-2 mixer's causal conv with a carried window and
its selective scan, flat (the fused step's entries over row-addressed state)
and dense (whole sequences from a zero state), against the equations one
token at a time in numpy float64. The four invariants the flat form holds
to, each with its test, and the flat form against the dense one on a mixed
batch. float32; tiny sizes; CPU."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from senweaver_ide_tpu.ops import ssm


# ---- the four invariants ---------------------------------------------------

def _mixer_inputs(seed, t, h=4, p=8, g=2, n=16, big=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (t, h)) + (6.0 if big
                                                             else -2.0))
    a = -jnp.exp(jax.random.uniform(ks[2], (h,), minval=1.0 if big else 0.0,
                                    maxval=4.0 if big else 2.0))
    b = jax.random.normal(ks[3], (t, g, n))
    c = jax.random.normal(ks[4], (t, g, n))
    d = jax.random.normal(ks[5], (h,))
    return x, dt, a, b, c, d


def _recurrence(x, dt, a, b, c, d, state=None):
    """The equations one token at a time, in numpy float64."""
    x, dt, a, b, c, d = (np.asarray(v, np.float64)
                         for v in (x, dt, a, b, c, d))
    t, h, p = x.shape
    rep = h // b.shape[1]
    s = (np.zeros((h, p, b.shape[2])) if state is None
         else np.asarray(state, np.float64))
    ys = []
    for i in range(t):
        bh, ch = np.repeat(b[i], rep, 0), np.repeat(c[i], rep, 0)
        s = (np.exp(dt[i] * a)[:, None, None] * s
             + (dt[i][:, None] * x[i])[:, :, None] * bh[:, None, :])
        ys.append((s * ch[:, None, :]).sum(-1) + d[:, None] * x[i])
    return np.stack(ys), s


@functools.partial(jax.jit, static_argnames=("num_rows",))
def _scan(x, dt, a, b, c, d, state, rows, positions, keep, layer, num_rows):
    plan = ssm.plan_runs(rows, positions, keep, num_rows)
    return ssm.scan_flat(x, dt, a, b, c, d, state, layer, rows, plan)


def _flat(x, dt, a, b, c, d, state, rows, positions, keep, layer=1,
          num_rows=4):
    return _scan(x, dt, a, b, c, d, state,
                 jnp.asarray(list(rows), jnp.int32),
                 jnp.asarray(list(positions), jnp.int32),
                 jnp.asarray(list(keep), bool), jnp.int32(layer),
                 num_rows=num_rows)


@functools.partial(jax.jit, static_argnames=("num_rows",))
def _conv(xbc, w, bias, window, rows, positions, layer, num_rows=4):
    plan = ssm.plan_runs(rows, positions, jnp.ones(rows.shape, bool),
                         num_rows)
    return ssm.conv_flat(xbc, w, bias, window, layer, rows, plan)


def _garbage_state(seed=9, layers=2, rows=5, h=4, p=8, n=16):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (layers, rows, h, p, n))


def test_position_0_starts_from_zero_whatever_the_row_held():
    """A run of 5 and a run of 1, both from position 0, on rows full of
    another tenant's state: the outputs and the states left are those of a
    zero start; the conv likewise starts from a zero window."""
    x, dt, a, b, c, d = _mixer_inputs(0, 6)
    state = _garbage_state()
    rows, pos = [2, 2, 2, 2, 2, 3], [0, 1, 2, 3, 4, 0]
    y, new = _flat(x, dt, a, b, c, d, state, rows, pos, [True] * 6)
    y5, s5 = _recurrence(x[:5], dt[:5], a, b[:5], c[:5], d)
    y1, s1 = _recurrence(x[5:], dt[5:], a, b[5:], c[5:], d)
    np.testing.assert_allclose(y, np.concatenate([y5, y1]), atol=2e-5)
    np.testing.assert_allclose(new[1, 2], s5, atol=2e-5)
    np.testing.assert_allclose(new[1, 3], s1, atol=2e-5)
    # every other row and the other layer: untouched, to the bit
    keep = np.ones((2, 5), bool)
    keep[1, 2] = keep[1, 3] = False
    assert np.array_equal(np.asarray(new)[keep], np.asarray(state)[keep])
    xbc = jax.random.normal(jax.random.PRNGKey(3), (6, 12))
    w = jax.random.normal(jax.random.PRNGKey(4), (4, 12))
    bias = jax.random.normal(jax.random.PRNGKey(5), (12,))
    window = jax.random.normal(jax.random.PRNGKey(6), (2, 5, 3, 12))
    out, win = _conv(xbc, w, bias, window, jnp.asarray(rows, jnp.int32),
                     jnp.asarray(pos, jnp.int32), jnp.int32(1))
    want5 = ssm.conv_dense(xbc[None, :5], w, bias)[0]
    want1 = ssm.conv_dense(xbc[None, 5:], w, bias)[0]
    np.testing.assert_allclose(out, np.concatenate([want5, want1]),
                               atol=1e-6)
    np.testing.assert_array_equal(win[1, 2], xbc[2:5])
    np.testing.assert_array_equal(win[1, 3, :2], np.zeros((2, 12)))
    np.testing.assert_array_equal(win[1, 3, 2], xbc[5])


def test_padding_and_dropped_writes_advance_nothing():
    """A step of pure padding (row 0, position 0, write dropped) leaves
    every row's state bit-equal; so does a dropped entry beside a kept one
    (``tests/test_falcon_h1.py`` checks the same through
    ``forward_paged``, the window too)."""
    x, dt, a, b, c, d = _mixer_inputs(1, 4)
    state = _garbage_state()
    _, new = _flat(x, dt, a, b, c, d, state, [0] * 4, [0] * 4, [False] * 4)
    assert np.array_equal(np.asarray(new), np.asarray(state))
    _, new = _flat(x, dt, a, b, c, d, state, [1, 0, 0, 0], [7, 0, 0, 0],
                   [True, False, False, False])
    assert np.array_equal(np.asarray(new[:, 0]), np.asarray(state[:, 0]))
    assert not np.array_equal(np.asarray(new[1, 1]), np.asarray(state[1, 1]))


def test_a_run_in_chunks_is_the_whole_run():
    """12 tokens as runs of 7 and 5 in two calls (the second continuing the
    row's stored state and window) and as one run of 12: the same outputs
    and the same state, and both the recurrence's."""
    x, dt, a, b, c, d = _mixer_inputs(2, 12)
    state = _garbage_state()
    every = lambda n: [True] * n
    y12, s12 = _flat(x, dt, a, b, c, d, state, [1] * 12, range(12),
                     every(12))
    cut = lambda lo, hi: (x[lo:hi], dt[lo:hi], a, b[lo:hi], c[lo:hi], d)
    y7, s7 = _flat(*cut(0, 7), state, [1] * 7, range(7), every(7))
    y5, s5 = _flat(*cut(7, 12), s7, [1] * 5, range(7, 12), every(5))
    want_y, want_s = _recurrence(x, dt, a, b, c, d)
    np.testing.assert_allclose(np.concatenate([y7, y5]), want_y, atol=2e-5)
    np.testing.assert_allclose(y12, want_y, atol=2e-5)
    np.testing.assert_allclose(s5[1, 1], want_s, atol=2e-5)
    np.testing.assert_allclose(s12[1, 1], want_s, atol=2e-5)
    xbc = jax.random.normal(jax.random.PRNGKey(3), (12, 12))
    w = jax.random.normal(jax.random.PRNGKey(4), (4, 12))
    bias = jnp.zeros((12,))
    window = jnp.ones((2, 5, 3, 12))
    rows = jnp.ones((12,), jnp.int32)

    def conv(lo, hi, window):
        return _conv(xbc[lo:hi], w, bias, window, rows[lo:hi],
                     jnp.arange(lo, hi, dtype=jnp.int32), jnp.int32(0))

    o1, win = conv(0, 2, window)     # shorter than the window: it shifts
    o2, win = conv(2, 3, win)
    o3, win = conv(3, 12, win)
    np.testing.assert_allclose(np.concatenate([o1, o2, o3]),
                               ssm.conv_dense(xbc[None], w, bias)[0],
                               atol=1e-6)
    np.testing.assert_array_equal(win[0, 1], xbc[9:12])


def test_decays_are_exponentials_of_differences_in_float32():
    """Steps so large that a token keeps e^-300 of the state and less: the
    products of decays over a run underflow to 0 and a ratio of two of
    them is 0/0. Differences of cumulative sums stay finite and equal the
    recurrence (in float64) wherever it has anything left to say, for the
    flat form (runs of 9 and 1) and the dense one."""
    x, dt, a, b, c, d = _mixer_inputs(3, 10, big=True)
    assert float(jnp.exp(jnp.cumsum(dt * a, 0))[-1].max()) == 0.0
    state = _garbage_state()
    y, new = _flat(x, dt, a, b, c, d, state, [1] * 9 + [2],
                   list(range(3, 12)) + [5], [True] * 10)
    y9, s9 = _recurrence(x[:9], dt[:9], a, b[:9], c[:9], d, state[1, 1])
    y1, s1 = _recurrence(x[9:], dt[9:], a, b[9:], c[9:], d, state[1, 2])
    assert np.isfinite(np.asarray(y)).all()
    assert np.isfinite(np.asarray(new)).all()
    scale = float(np.abs(y9).max())
    np.testing.assert_allclose(y, np.concatenate([y9, y1]),
                               atol=1e-5 * scale)
    np.testing.assert_allclose(new[1, 1], s9, atol=1e-5 * scale)
    np.testing.assert_allclose(new[1, 2], s1, atol=1e-5 * scale)
    dense = ssm.scan_dense(x[None], dt[None], a, b[None], c[None], d,
                           chunk=4)[0]
    want, _ = _recurrence(x, dt, a, b, c, d)
    np.testing.assert_allclose(dense, want, atol=1e-5 * scale)


# ---- the flat form against the dense form ----------------------------------

def test_flat_batch_of_decode_rows_and_prefill_runs_equals_dense():
    """Four sequences through ``scan_dense``; the same through
    ``scan_flat``: first their heads as prefill runs, then ONE mixed batch
    — rows 0 and 2 decode one token from their stored states, rows 1 and 3
    prefill runs of 9 and 6 that continue theirs, padding between and
    behind."""
    n = 20
    seqs = [_mixer_inputs(10 + i, n) for i in range(4)]
    a, d = seqs[0][2], seqs[0][5]
    x, dt, b, c = (jnp.stack([s[i] for s in seqs]) for i in (0, 1, 3, 4))
    dense = ssm.scan_dense(x, dt, a, b, c, d, chunk=8)
    heads = [7, 4, 11, 2]
    state = _garbage_state(rows=6)
    cat = lambda v, parts: jnp.concatenate(
        [v[r, lo:hi] for r, lo, hi in parts])
    first = [(r, 0, heads[r]) for r in range(4)]
    rows = sum(([r] * (hi - lo) for r, lo, hi in first), [])
    pos = sum((list(range(lo, hi)) for _r, lo, hi in first), [])
    y0, state = _flat(cat(x, first), cat(dt, first), a, cat(b, first),
                      cat(c, first), d, state, rows, pos, [True] * len(rows),
                      num_rows=5)
    mixed = [(0, 7, 8), (2, 11, 12), (1, 4, 13), (3, 2, 8)]
    rows = sum(([r] * (hi - lo) for r, lo, hi in mixed), [])
    pos = sum((list(range(lo, hi)) for _r, lo, hi in mixed), [])
    keep = [True] * len(rows)
    # two dropped entries in the middle, on row 0, and three behind
    at = 2
    pad = lambda v: jnp.concatenate([v[:at], jnp.ones((2,) + v.shape[1:]),
                                     v[at:], jnp.ones((3,) + v.shape[1:])])
    rows = rows[:at] + [0, 0] + rows[at:] + [0, 0, 0]
    pos = pos[:at] + [0, 0] + pos[at:] + [0, 0, 0]
    keep = keep[:at] + [False] * 2 + keep[at:] + [False] * 3
    y1, state = _flat(pad(cat(x, mixed)), pad(cat(dt, mixed)), a,
                      pad(cat(b, mixed)), pad(cat(c, mixed)), d, state, rows,
                      pos, keep, num_rows=5)
    kept = np.asarray(y1)[np.asarray(keep)]
    np.testing.assert_allclose(kept, cat(dense, mixed), atol=2e-5)
    np.testing.assert_allclose(y0, cat(dense, first), atol=2e-5)
