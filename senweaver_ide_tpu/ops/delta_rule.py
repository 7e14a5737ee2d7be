"""The gated delta rule with a decay a key channel (KDA): a linear-attention
mixer whose state is a matrix a head, multiplied every token by a
rank-one correction and a diagonal decay. Plain XLA.

One set of equations, a head (q, k in R^K, k of unit length; v in R^V; g in
R^K the token's log decay, <= 0; beta in (0, 2); S in R^(K x V), float32):

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                                              S_{-1} = 0

The step form (``kda_step``), which the decode rows run and the tests hold
everything else to:

    S' = Diag(exp g) S;  u = beta (v - S'^T k);  S = S' + k u^T;  o = S^T q

``ops/ssm.py``'s mixers decay a state elementwise and ADD an outer product:
a run of entries is then a masked matrix product (the duality form). Here
every token's correction reads the state the token before left, so a run of
C entries is the WY / UT form (``_chunk``): with G_i the cumulative log decay
inside the chunk and S_0 the state it starts from,

    A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)        j <  i
    P_ij =        sum_c q_ic k_jc exp(G_ic - G_jc)        j <= i
    (I + A) U = beta (V - (exp(G) * K) S_0)               unit lower triangle
    O   = (exp(G) * Q) S_0 + P U
    S_C = Diag(exp G_C) S_0 + (exp(G_C - G) * K)^T U

exact, float32. A decay enters as ``exp`` of a DIFFERENCE of cumulative
sums, masked to i >= j before the ``exp`` (there the difference is <= 0):
never as ``1 / exp(G_j)``, which overflows float32 after a dozen strongly
decaying tokens.

Two entry points, as ``ops/ssm.py`` has them. *Dense* (``kda_dense``): whole
``(B, S)`` sequences from a zero state, a ``lax.scan`` over chunks. *Flat*
(``kda_flat``): the fused step's ``T`` entries over the row-addressed leaf
``(L, rows, H, K, V)`` float32, on the same ``RunPlan`` and under the same
rules: an entry at position 0 starts from a zero state whatever the row
held; an entry that is not kept advances nothing; a run that continues a
row starts from the row's stored state. Every decode row (a run of one
entry) advances in one pass over the rows' states (``_advance_single``; on
a TPU ``ops.state_step.state_step_delta``, the same step with a head's
state in VMEM: read once, written once); each longer run takes one trip of
a loop that reads its row's state once, carries it through the run's
chunks, and writes it back once.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from . import state_step
from .ssm import RunPlan

_HI = jax.lax.Precision.HIGHEST
CHUNK = 64


def kda_step(s: jax.Array, q: jax.Array, k: jax.Array, v: jax.Array,
             g: jax.Array, beta: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One token. s (..., K, V) f32; q, k, g (..., K); v (..., V); beta
    (...,) -> (s', o (..., V))."""
    s = jnp.exp(g)[..., None] * s
    u = beta[..., None] * (v - jnp.sum(s * k[..., None], axis=-2))
    s = s + k[..., None] * u[..., None, :]
    return s, jnp.sum(s * q[..., None], axis=-2)


def _chunk(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
           beta: jax.Array, s0: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """C entries of one run from the state ``s0`` (H, K, V) f32. q, k, g
    (C, H, K) f32; v (C, H, V) f32; beta (C, H) f32. An entry with
    ``beta = 0`` and ``g = 0`` changes nothing (a run's padded tail).
    -> (o (C, H, V), the state after the chunk)."""
    c = q.shape[0]
    cum = jnp.cumsum(g, axis=0)                                  # (C, H, K)
    i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    # both triangles in one reduction over the channels: rows [k | q]
    # against the keys, so the (., C, H, K) decays are made once and
    # consumed where they are made
    lhs = jnp.concatenate([k, q], axis=0)                        # (2C, H, K)
    lower = jnp.concatenate([i > j, i >= j], axis=0)             # (2C, C)
    diff = jnp.tile(cum, (2, 1, 1))[:, None] - cum[None]      # (2C, C, H, K)
    decay = jnp.exp(jnp.where(lower[:, :, None, None], diff, -jnp.inf))
    both = jnp.sum(lhs[:, None] * k[None] * decay, axis=-1)      # (2C, C, H)
    a = jnp.moveaxis(both[:c] * beta[:, None], -1, 0)            # (H, C, C)
    p = jnp.moveaxis(both[c:], -1, 0)
    decayed = jnp.exp(cum)
    rhs = beta[..., None] * (v - jnp.einsum(
        "chk,hkv->chv", decayed * k, s0, precision=_HI))
    u = jax.scipy.linalg.solve_triangular(
        a + jnp.eye(c, dtype=a.dtype), jnp.moveaxis(rhs, 1, 0), lower=True,
        unit_diagonal=True)                                      # (H, C, V)
    o = (jnp.einsum("chk,hkv->chv", decayed * q, s0, precision=_HI)
         + jnp.einsum("hij,hjv->ihv", p, u, precision=_HI))
    tail = jnp.exp(cum[-1][None] - cum) * k                      # (C, H, K)
    s1 = (jnp.exp(cum[-1])[..., None] * s0
          + jnp.einsum("chk,hcv->hkv", tail, u, precision=_HI))
    return o, s1


def _f32(*xs):
    return tuple(x.astype(jnp.float32) for x in xs)


# -- dense: whole sequences from a zero state ------------------------------

def kda_dense(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
              beta: jax.Array, chunk: int = CHUNK) -> jax.Array:
    """The gated delta rule over whole sequences from a zero state. q, k, g
    (B, S, H, K); v (B, S, H, V); beta (B, S, H) -> o (B, S, H, V) f32. The
    sequence runs in chunks of ``chunk`` positions, the state carried
    between them (a padded tail has beta 0 and g 0)."""
    s = q.shape[1]
    c = min(chunk, s)
    pad = -s % c
    n = (s + pad) // c

    def one_seq(*xs):
        split = lambda x: jnp.pad(
            x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape(
                (n, c) + x.shape[1:])
        s0 = jnp.zeros(q.shape[2:] + v.shape[-1:], jnp.float32)

        def step(state, inp):
            o, state = _chunk(*inp, state)
            return state, o

        _, o = jax.lax.scan(step, s0, tuple(map(split, xs)))
        return o.reshape((n * c,) + o.shape[2:])[:s]

    return jax.vmap(one_seq)(*_f32(q, k, v, g, beta))


# -- flat: the fused step's entries over row-addressed state ---------------

def _advance_single(state: jax.Array, layer: jax.Array, plan: RunPlan,
                    q, k, v, g, beta):
    """Every row whose run is ONE entry (a decode row), the step form over
    all rows' states at once: the decayed state is reduced against the
    entry's k and q together (``o = S'^T q + (k . q) u``), then updated.
    Rows with no entry or a longer run keep their state to the bit.
    -> (state', o (R, H, V) f32, zero for the rows it did not advance)."""
    r = plan.row_len.shape[0]
    e = plan.row_last
    one = plan.row_len == 1
    s = state[layer, :r]                                      # (R, H, K, V)
    s0 = jnp.where(plan.row_fresh[:, None, None, None], 0.0, s)
    sd = jnp.exp(g[e])[..., None] * s0
    sk = jnp.sum(sd * k[e][..., None], axis=-2)                  # (R, H, V)
    sq = jnp.sum(sd * q[e][..., None], axis=-2)
    u = beta[e][..., None] * (v[e] - sk)
    o = sq + jnp.sum(k[e] * q[e], axis=-1, keepdims=True) * u
    s1 = sd + k[e][..., None] * u[..., None, :]
    state = state.at[layer, :r].set(
        jnp.where(one[:, None, None, None], s1, s))
    return state, jnp.where(one[:, None, None], o, 0.0)


def kda_flat(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
             beta: jax.Array, state: jax.Array, layer: jax.Array,
             seq_row: jax.Array, plan: RunPlan, chunk: int = CHUNK):
    """``kda_dense`` for the flat batch over the rows' stored states. q, k,
    g (T, H, K); v (T, H, V); beta (T, H); state (L, rows, H, K, V) f32.
    A run's kept entries are contiguous in the batch (``plan_runs``).
    -> (o (T, H, V) f32, zero where an entry is not kept; state'). The
    scopes ``kda.step`` (the decode rows' pass) and ``kda.chunk`` (the
    long runs' loop) are docs/observability.md's."""
    t = q.shape[0]
    q, k, v, g, beta = _f32(q, k, v, g, beta)
    with jax.named_scope("kda.step"):
        if state_step.one_pass(state):
            state, o_row = state_step.state_step_delta(
                state, layer, plan.row_last, plan.row_len, plan.row_fresh,
                q, k, v, g, beta)
        else:
            state, o_row = _advance_single(state, layer, plan, q, k, v, g,
                                           beta)
    one = plan.row_len == 1
    o = jnp.where((plan.keep & one[seq_row])[:, None, None], o_row[seq_row],
                  0.0)

    # the prefill chunks: a long run's entries ``chunk`` at a time
    pad = lambda x: jnp.pad(x, ((0, chunk),) + ((0, 0),) * (x.ndim - 1))
    q_p, k_p, v_p, g_p, b_p = map(pad, (q, k, v, g, beta))

    def one_run(i, carry):
        state, o = carry
        row = plan.long_rows[i]
        n = plan.row_len[row]
        start = plan.row_last[row] - n + 1
        st = jnp.where(plan.row_fresh[row], 0.0, state[layer, row])

        def block(m, carry):
            st, o = carry
            at = start + m * chunk
            cut = lambda x: jax.lax.dynamic_slice_in_dim(x, at, chunk)
            live = m * chunk + jnp.arange(chunk) < n
            o_c, st = _chunk(
                cut(q_p), cut(k_p), cut(v_p),
                jnp.where(live[:, None, None], cut(g_p), 0.0),
                jnp.where(live[:, None], cut(b_p), 0.0), st)
            return st, jax.lax.dynamic_update_slice_in_dim(
                o, jnp.where(live[:, None, None], o_c, cut(o)), at, 0)

        st, o = jax.lax.fori_loop(0, -(-n // chunk), block, (st, o))
        return state.at[layer, row].set(st), o

    with jax.named_scope("kda.chunk"):
        state, o = jax.lax.fori_loop(0, plan.n_long, one_run,
                                     (state, pad(o)))
    return o[:t], state
