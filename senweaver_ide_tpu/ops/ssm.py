"""The state-space mixers' sequence operators: the causal depthwise conv
with a carried window, Mamba-2's selective scan in its
state-space-duality form, and Mamba-1's (``scan1_dense``, ``scan1_flat``,
further down) as a scan over a run's entries. Plain XLA.

One set of equations (ISSUE 32, "The equations"; heads ``h`` of group
``g(h)`` read that group's ``B`` and ``C``):

    xBC_t = silu(sum_j w[j] * in_{t-(K-1)+j} + b)        zeros before 0
    a_t[h] = exp(dt_t[h] A[h])
    S_t[h] = a_t[h] S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g(h)]     S_{-1} = 0
    y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]

and two entry points over them. *Dense* (``conv_dense``, ``scan_dense``):
whole ``(B, S)`` sequences from a zero state, in chunks. *Flat*
(``conv_flat``, ``scan_flat``): the fused step's ``T`` entries, each a
(row, position) pair, over row-addressed state leaves — ``ssm``
``(L, rows, H, P, N)`` float32 and ``conv`` ``(L, rows, K-1, C)`` — read
and written at ``(layer, row)``. The entries a call keeps of one row are one
contiguous run in rising positions (the engine's ``_assemble_paged_plan``
builds them so; ``plan_runs`` cuts the batch once a step, outside the layer
scan, as ``ops.paged_attention.plan_rows`` does for attention).

What the flat form holds to (``tests/test_falcon_h1.py``):

* an entry at position 0 starts from a zero state and a zero window,
  whatever the row held: a reused row is never cleared by a program of its
  own;
* an entry that is not kept (padding, a dropped write) advances nothing:
  the rows' leaves come back bit-equal;
* a run's later entries see its earlier ones, and a run that continues a
  row starts from the row's stored state and window: prefill in chunks is
  whole prefill;
* a decay is ``exp`` of a difference of within-run cumulative sums of
  ``dt A`` in float32, never a ratio of products.

A row's state is read and written ONCE a run, never gathered once an
entry. Inside a run the quadratic (duality) form over the step's entries,
masked to pairs of one run; a run of one entry (a decode row) advances its
state in one elementwise pass over all rows (``_advance_single``; on a TPU
``ops.state_step.state_step_mamba2``, which reads the output out of the
same visit that writes the state); each
longer run (a prefill chunk) takes one trip of a loop that reads its row's
state, serves the run's readout and update as two products over the
entries, and writes the row back (``_advance_long``).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from . import state_step

_HI = jax.lax.Precision.HIGHEST


class RunPlan(NamedTuple):
    """A flat batch cut into its rows' runs (``plan_runs``)."""
    keep: jax.Array        # (T,) bool — the entry advances its row
    same: jax.Array        # (T, T) bool — j is kept, in i's run, j <= i
    cum_w: jax.Array       # (T, T) float32 — ``same`` as 0/1
    local: jax.Array       # (T,) int32 — index of the entry in its run
    fresh: jax.Array       # (T,) bool — the entry's run starts at position 0
    row_last: jax.Array    # (R,) int32 — flat index of the row's last entry
    row_len: jax.Array     # (R,) int32 — kept entries of the row (0: none)
    row_fresh: jax.Array   # (R,) bool — the row's run starts at position 0
    long_rows: jax.Array   # (R,) int32 — rows whose run is 2+ entries, first
    n_long: jax.Array      # () int32 — how many of them


def plan_runs(seq_row: jax.Array, positions: jax.Array, keep: jax.Array,
              num_rows: int) -> RunPlan:
    """Cut the flat batch into runs: the kept entries of one row, which the
    caller lays out as one contiguous stretch in rising positions."""
    t = seq_row.shape[0]
    idx = jnp.arange(t, dtype=jnp.int32)
    same = (keep[:, None] & keep[None, :]
            & (seq_row[:, None] == seq_row[None, :])
            & (idx[None, :] <= idx[:, None]))
    local = same.sum(axis=1).astype(jnp.int32) - 1
    fresh = keep & (positions - local == 0)
    member = keep[:, None] & (seq_row[:, None]
                              == jnp.arange(num_rows, dtype=jnp.int32))
    row_len = member.sum(axis=0).astype(jnp.int32)
    row_last = jnp.max(jnp.where(member, idx[:, None], 0), axis=0)
    row_fresh = (row_len > 0) & fresh[row_last]
    is_long = row_len >= 2
    long_rows = jnp.argsort(~is_long, stable=True).astype(jnp.int32)
    return RunPlan(keep, same, same.astype(jnp.float32), local, fresh,
                   row_last, row_len, row_fresh, long_rows,
                   is_long.sum().astype(jnp.int32))


def _per_head(a: jax.Array, heads: int) -> jax.Array:
    """(..., G, N) by group -> (..., H, N) by head: heads
    ``g H/G .. (g+1) H/G - 1`` read group g."""
    return jnp.repeat(a, heads // a.shape[-2], axis=-2)


def _intra(same: jax.Array, cum: jax.Array, dt: jax.Array, x: jax.Array,
           b: jax.Array, c: jax.Array) -> jax.Array:
    """What the entries of one run give each other, the duality form:
    ``y_i = sum_{j <= i} exp(cum_i - cum_j) dt_j (C_i . B_j) x_j`` over the
    pairs ``same`` marks. cum, dt (T, H) f32; x (T, H, P); b, c (T, G, N).
    -> (T, H, P) f32."""
    h = x.shape[1]
    prec = _HI if b.dtype == jnp.float32 else None
    g = jnp.einsum("ign,jgn->gij", c, b, precision=prec,
                   preferred_element_type=jnp.float32)
    diff = cum.T[:, :, None] - cum.T[:, None, :]                # (H, T, T)
    decay = jnp.exp(jnp.where(same[None], diff, -jnp.inf))
    m = jnp.repeat(g, h // g.shape[0], axis=0) * decay * dt.T[:, None, :]
    return jnp.einsum("hij,jhp->ihp", m, x.astype(jnp.float32),
                      precision=_HI)


def _readout(cum: jax.Array, c_h: jax.Array, s0: jax.Array) -> jax.Array:
    """What a run's entries read of the state it started from:
    ``exp(cum_i) C_i . S0``. c_h (T, H, N) f32; s0 (H, P, N) f32."""
    return jnp.exp(cum)[..., None] * jnp.einsum(
        "ihn,hpn->ihp", c_h, s0, precision=_HI)


def _advance(cum: jax.Array, cum_last: jax.Array, dt: jax.Array,
             x: jax.Array, b_h: jax.Array, s0: jax.Array,
             member: jax.Array) -> jax.Array:
    """The state after a run: ``exp(cum_last) S0 + sum_i exp(cum_last -
    cum_i) dt_i x_i (x) B_i`` over the entries ``member`` marks."""
    w = jnp.exp(jnp.where(member[:, None], cum_last[None] - cum, -jnp.inf))
    wx = (w * dt)[..., None] * x.astype(jnp.float32)
    return (jnp.exp(cum_last)[:, None, None] * s0
            + jnp.einsum("ihp,ihn->hpn", wx, b_h, precision=_HI))


# -- dense: whole sequences from a zero state ------------------------------

def conv_dense(xbc: jax.Array, w: jax.Array,
               b: Optional[jax.Array]) -> jax.Array:
    """Causal depthwise conv over (B, S, C), zeros before position 0:
    ``out_t = sum_j w[j] in_{t-(K-1)+j} + b``, summed in float32. w (K, C),
    b (C,) or None: no bias. -> (B, S, C) f32."""
    k, s = w.shape[0], xbc.shape[1]
    w = w.astype(jnp.float32)
    bias = None if b is None else b.astype(jnp.float32)
    padded = jnp.pad(xbc.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(w[j] * padded[:, j:j + s] for j in range(k))
    return out if bias is None else out + bias


def scan_dense(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
               c: jax.Array, d: jax.Array, chunk: int = 64) -> jax.Array:
    """The selective scan over whole sequences from a zero state. x
    (B, S, H, P); dt (B, S, H) f32, after the softplus; a (H,) f32, negative;
    b, c (B, S, G, N); d (H,). -> y (B, S, H, P) f32. The sequence runs in
    chunks of ``chunk`` positions: the duality form inside one, the state
    carried between them (a padded tail has dt 0: it decays nothing and
    adds nothing)."""
    s, h = x.shape[1:3]
    q = min(chunk, s)
    pad = -s % q
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),)
                               * (v.ndim - 2)) for v in (x, dt, b, c))
    n = (s + pad) // q
    same = jnp.tril(jnp.ones((q, q), bool))
    every = jnp.ones((q,), bool)

    def one_chunk(state, inp):
        xs, dts, bs, cs = inp                       # one sequence's chunk
        cum = jnp.cumsum(dts * a, axis=0)
        b_h = _per_head(bs, h).astype(jnp.float32)
        c_h = _per_head(cs, h).astype(jnp.float32)
        y = (_intra(same, cum, dts, xs, bs, cs) + _readout(cum, c_h, state))
        return _advance(cum, cum[-1], dts, xs, b_h, state, every), y

    def one_seq(xs, dts, bs, cs):
        split = lambda v: v.reshape((n, q) + v.shape[1:])
        s0 = jnp.zeros((h, x.shape[-1], b.shape[-1]), jnp.float32)
        _, y = jax.lax.scan(one_chunk, s0, tuple(map(split,
                                                     (xs, dts, bs, cs))))
        return y.reshape((n * q,) + y.shape[2:])

    y = jax.vmap(one_seq)(x, dt.astype(jnp.float32), b, c)[:, :s]
    return y + d[:, None] * x[:, :s].astype(jnp.float32)


# -- flat: the fused step's entries over row-addressed state ---------------

def conv_flat(xbc: jax.Array, w: jax.Array, b: Optional[jax.Array],
              conv: jax.Array, layer: jax.Array, seq_row: jax.Array,
              plan: RunPlan) -> Tuple[jax.Array, jax.Array]:
    """``conv_dense`` for the flat batch: xbc (T, C) the entries' conv
    inputs, ``conv`` (L, rows, K-1, C) each row's last K-1 inputs, oldest
    first. An entry's earlier inputs are its run's earlier entries and,
    before those, its row's window — zeros for a run that starts at
    position 0. -> (out (T, C) f32, conv') with the window of every row that
    has a kept entry moved on, every other row's untouched."""
    k, t = w.shape[0], xbc.shape[0]
    r = plan.row_len.shape[0]
    win = conv[layer, :r]                                    # (R, K-1, C)
    mine = jnp.where(plan.fresh[:, None, None], 0, win[seq_row])
    w = w.astype(jnp.float32)
    bias = None if b is None else b.astype(jnp.float32)
    out = w[k - 1] * xbc.astype(jnp.float32)
    if bias is not None:
        out = out + bias
    for back in range(1, k):
        earlier = jnp.pad(xbc, ((back, 0), (0, 0)))[:t]
        slot = jnp.clip(k - 1 - back + plan.local, 0, k - 2)
        stored = jnp.take_along_axis(mine, slot[:, None, None], axis=1)[:, 0]
        out = out + w[k - 1 - back] * jnp.where(
            (plan.local >= back)[:, None], earlier,
            stored).astype(jnp.float32)
    old = jnp.where(plan.row_fresh[:, None, None], 0, win)
    new = []
    for m in range(k - 1):
        back = k - 2 - m                    # how far before the last entry
        kept = jnp.take_along_axis(
            old, jnp.clip(plan.row_len + m, 0, k - 2)[:, None, None],
            axis=1)[:, 0]
        new.append(jnp.where(
            (plan.row_len > back)[:, None],
            xbc[jnp.clip(plan.row_last - back, 0, t - 1)], kept))
    new = jnp.where((plan.row_len > 0)[:, None, None],
                    jnp.stack(new, axis=1).astype(conv.dtype), win)
    return out, conv.at[layer, :r].set(new)


def _advance_single(ssm: jax.Array, layer: jax.Array, plan: RunPlan,
                    da: jax.Array, dt: jax.Array, x: jax.Array,
                    b: jax.Array, c: jax.Array):
    """Every row whose run is ONE entry (a decode row), in one elementwise
    pass over the rows' states: read, decay, add the entry's outer product,
    write; and what the entry reads of the state it started from. Rows with
    no entry or a longer run keep their state to the bit. -> (ssm', z
    (R, H, P) f32)."""
    r, h = plan.row_len.shape[0], x.shape[1]
    e = plan.row_last
    one = plan.row_len == 1
    s = ssm[layer, :r]                                       # (R, H, P, N)
    s0 = jnp.where(plan.row_fresh[:, None, None, None], 0.0, s)
    decay = jnp.exp(da[e])                                   # (R, H)
    b_h = _per_head(b[e], h).astype(jnp.float32)             # (R, H, N)
    c_h = _per_head(c[e], h).astype(jnp.float32)
    dx = dt[e][..., None] * x[e].astype(jnp.float32)         # (R, H, P)
    z = decay[..., None] * jnp.sum(s0 * c_h[:, :, None, :], axis=-1)
    s1 = decay[..., None, None] * s0 + dx[..., None] * b_h[:, :, None, :]
    ssm = ssm.at[layer, :r].set(
        jnp.where(one[:, None, None, None], s1, s))
    return ssm, jnp.where(one[:, None, None], z, 0.0)


def _advance_long(ssm: jax.Array, layer: jax.Array, plan: RunPlan,
                  seq_row: jax.Array, cum: jax.Array, dt: jax.Array,
                  x: jax.Array, b: jax.Array, c: jax.Array):
    """Every run of two or more entries (a prefill chunk), one trip of a
    loop each: the row's state is read once, serves the run's readout and
    its update as two products over the step's entries (the run's own
    marked), and is written back once. No trip where every run is one
    entry. -> (ssm', z (T, H, P) f32)."""
    h = x.shape[1]
    b_h = _per_head(b, h).astype(jnp.float32)                # (T, H, N)
    c_h = _per_head(c, h).astype(jnp.float32)

    def one_run(i, carry):
        ssm, z = carry
        row = plan.long_rows[i]
        member = plan.keep & (seq_row == row)
        s0 = jnp.where(plan.row_fresh[row], 0.0, ssm[layer, row])
        z = z + jnp.where(member[:, None, None], _readout(cum, c_h, s0), 0.0)
        s1 = _advance(cum, cum[plan.row_last[row]], dt, x, b_h, s0, member)
        return ssm.at[layer, row].set(s1), z

    z0 = jnp.zeros(x.shape, jnp.float32)
    return jax.lax.fori_loop(0, plan.n_long, one_run, (ssm, z0))


def scan_flat(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
              c: jax.Array, d: jax.Array, ssm: jax.Array, layer: jax.Array,
              seq_row: jax.Array, plan: RunPlan):
    """``scan_dense`` for the flat batch over the rows' stored states. x
    (T, H, P); dt (T, H) f32, after the softplus; a (H,) f32; b, c
    (T, G, N); d (H,); ssm (L, rows, H, P, N) f32. -> (y (T, H, P) f32,
    ssm')."""
    da = jnp.where(plan.keep[:, None], dt * a, 0.0)
    # within-run inclusive sums of dt A: each is a sum of its own run's
    # terms alone, so a long batch costs a decay no precision
    cum = jnp.einsum("ij,jh->ih", plan.cum_w, da, precision=_HI)
    y = _intra(plan.same, cum, dt, x, b, c)
    if state_step.one_pass(ssm):
        ssm, z_row = state_step.state_step_mamba2(
            ssm, layer, plan.row_last, plan.row_len, plan.row_fresh, da, dt,
            x, b, c)
    else:
        ssm, z_row = _advance_single(ssm, layer, plan, da, dt, x, b, c)
    ssm, z_long = _advance_long(ssm, layer, plan, seq_row, cum, dt, x, b, c)
    y = y + z_long + jnp.where(plan.keep[:, None, None], z_row[seq_row], 0.0)
    return y + d[:, None] * x.astype(jnp.float32), ssm


# -- Mamba-1: a decay a (channel, state) pair ------------------------------
#
#     S_t = exp(dt_t (x) 1 * A) * S_{t-1} + (dt_t * u_t) (x) B_t     S_{-1} = 0
#     y_t = S_t C_t + D * u_t
#
# with A (I, N): every one of a channel's N state values decays at its own
# rate, so the duality form over a step's entries (one scalar decay a head
# a token) does not exist, and a run is a scan over its entries. The same
# ``RunPlan`` and the same rules as above (position 0 starts from zero; an
# entry not kept advances nothing; a run continues its row's stored state;
# a decay is ``exp`` of a float32 product). The state is held (N, I): the
# channels on the lanes, no padding of a 16-wide axis to 128.

# entries of a long run that one trip of its loop advances, unrolled
_RUN_BLOCK = 8


def _step1(state, dt_t, u_t, b_t, c_t, a_t):
    """One token of Mamba-1. state (..., N, I) f32; dt_t, u_t (..., I) f32;
    b_t, c_t (..., N) f32; a_t (N, I) f32 -> (state', y_t (..., I))."""
    state = (jnp.exp(dt_t[..., None, :] * a_t) * state
             + (dt_t * u_t)[..., None, :] * b_t[..., :, None])
    return state, jnp.sum(state * c_t[..., :, None], axis=-2)


def scan1_dense(u: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                c: jax.Array, d: jax.Array) -> jax.Array:
    """Mamba-1's selective scan over whole sequences from a zero state,
    one position at a time. u (B, S, I); dt (B, S, I) f32, after the
    softplus; a (I, N) f32, negative; b, c (B, S, N); d (I,). -> y
    (B, S, I) f32."""
    f32 = lambda v: jnp.swapaxes(v.astype(jnp.float32), 0, 1)
    a_t = a.astype(jnp.float32).T

    def token(state, inp):
        u_t, dt_t, b_t, c_t = inp
        return _step1(state, dt_t, u_t, b_t, c_t, a_t)

    s0 = jnp.zeros((u.shape[0],) + a_t.shape, jnp.float32)
    _, y = jax.lax.scan(token, s0, (f32(u), f32(dt), f32(b), f32(c)))
    return jnp.swapaxes(y, 0, 1) + d.astype(jnp.float32) * u.astype(
        jnp.float32)


def scan1_flat(u: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
               c: jax.Array, d: jax.Array, ssm: jax.Array, layer: jax.Array,
               seq_row: jax.Array, plan: RunPlan):
    """``scan1_dense`` for the flat batch over the rows' stored states. u
    (T, I); dt (T, I) f32, after the softplus; a (I, N) f32; b, c (T, N);
    d (I,); ssm (L, rows, N, I) f32. -> (y (T, I) f32, ssm').

    Every row whose run is ONE entry (a decode row) advances in one
    elementwise pass over the rows' states. Each longer run (a prefill
    chunk) takes one trip of a loop: its row's state is read once, carried
    through the run's entries ``_RUN_BLOCK`` at a time, and written back
    once. A run's kept entries are contiguous in the batch (``plan_runs``).
    """
    t = u.shape[0]
    r = plan.row_len.shape[0]
    a_t = a.astype(jnp.float32).T                            # (N, I)
    uf, bf, cf = (v.astype(jnp.float32) for v in (u, b, c))
    # the decode rows
    e = plan.row_last
    one = plan.row_len == 1
    s = ssm[layer, :r]                                       # (R, N, I)
    s0 = jnp.where(plan.row_fresh[:, None, None], 0.0, s)
    s1, y_row = _step1(s0, dt[e], uf[e], bf[e], cf[e], a_t)
    ssm = ssm.at[layer, :r].set(jnp.where(one[:, None, None], s1, s))
    y = jnp.where((plan.keep & one[seq_row])[:, None], y_row[seq_row], 0.0)

    # the prefill chunks
    pad = lambda v: jnp.pad(v, ((0, _RUN_BLOCK), (0, 0)))
    dt_p, u_p, b_p, c_p = pad(dt), pad(uf), pad(bf), pad(cf)

    def one_run(i, carry):
        ssm, y = carry
        row = plan.long_rows[i]
        n = plan.row_len[row]
        start = plan.row_last[row] - n + 1
        st = jnp.where(plan.row_fresh[row], 0.0, ssm[layer, row])

        def block(k, carry):
            st, y = carry
            at = start + k * _RUN_BLOCK
            cut = lambda v: jax.lax.dynamic_slice_in_dim(v, at, _RUN_BLOCK)
            dts, us, bs, cs, old = (cut(dt_p), cut(u_p), cut(b_p), cut(c_p),
                                    cut(y))
            out = []
            for j in range(_RUN_BLOCK):
                live = k * _RUN_BLOCK + j < n
                nxt, y_j = _step1(st, dts[j], us[j], bs[j], cs[j], a_t)
                st = jnp.where(live, nxt, st)
                out.append(jnp.where(live, y_j, old[j]))
            return st, jax.lax.dynamic_update_slice_in_dim(
                y, jnp.stack(out), at, 0)

        st, y = jax.lax.fori_loop(0, -(-n // _RUN_BLOCK), block, (st, y))
        return ssm.at[layer, row].set(st), y

    ssm, y = jax.lax.fori_loop(0, plan.n_long, one_run, (ssm, pad(y)))
    return y[:t] + d.astype(jnp.float32) * uf, ssm


def gated_norm(y: jax.Array, z: jax.Array, weight: jax.Array, groups: int,
               eps: float, dtype) -> jax.Array:
    """Mamba-2's gated RMSNorm with the gate first
    (``norm_before_gate`` false): ``RMSNorm_G(y * silu(z)) * weight``, the
    norm over each of the ``groups`` equal parts of the last axis, in
    float32. y (..., I) f32, z (..., I) -> (..., I) in ``dtype``."""
    g = y * jax.nn.silu(z.astype(jnp.float32))
    parts = g.reshape(g.shape[:-1] + (groups, g.shape[-1] // groups))
    parts = parts * jax.lax.rsqrt(
        jnp.mean(parts * parts, axis=-1, keepdims=True) + eps)
    return (parts.reshape(g.shape) * weight.astype(jnp.float32)).astype(dtype)
