"""The decode rows' step over their stored recurrent state as ONE pass: a
Pallas kernel that holds a block of a row's heads in VMEM, so the float32
state is read once and written once, in place.

``ops/ssm.py`` and ``ops/delta_rule.py`` advance every row whose run is
one entry (a decode row) with their plain-XLA ``_advance_single``. As
compiled for a TPU that is two passes over the rows' states: one fusion
reduces them against the entry (the readout), one reads them again and
rewrites them. Here a grid step reads a (row, head block) of the state
leaf, decays it, adds the entry's rank-one term, reads the output out of
the same visit and writes the block back where it lay
(``input_output_aliases`` on the leaf). Two forms of one algorithm, the
files' own equations in float32:

    Mamba-2 (``state_step_mamba2``; S (P, N) a head):
        z = decay (S0 C);  S1 = decay S0 + dx (x) B
    delta rule (``state_step_delta``; S (K, V) a head):
        S' = Diag(exp g) S0;  u = beta (v - S'^T k);  S1 = S' + k u^T
        o = S'^T q + (k . q) u

What both hold to (``tests/test_state_step.py``, against
``_advance_single``): a row at position 0 starts from zero whatever it
held; a row with no entry or with a longer run is NEVER VISITED — the grid
walks a prefetched list of the rows to advance, a grid step past the list
stays on the last block visited (no fetch, no write), and under the alias
a block that is not visited keeps its contents to the bit. (A step with no
row to advance rests on one block and copies it through untouched.)

The layer index and the rows' vectors reach the kernel as scalar-prefetch
operands, never as static arguments, and the call lives in ONE jitted
function a form whose every argument is an array: the three delta-rule
layers of a period call the same jaxpr, which JAX lowers once a step
program — a Mosaic call site a layer is lowered (table widths x token
widths) times in every run's set-up (ROADMAP B5). A kernel's body is a
``lax.fori_loop`` over the heads of a block (Mamba-2) or one batched
expression over them (the delta rule), so its jaxpr does not grow with the
block; the heads a grid step takes follow VMEM (``BLOCK_BYTES``), not the
trace.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import paged_attention

# bytes of state a grid step holds: in and out, double-buffered, four of
# them lie in VMEM beside the delta form's temporaries of a block's size (a
# delta head is 64 KB, a Mamba-2 head 128 KB: 16 and 8 heads a step)
BLOCK_BYTES = 1 << 20

# the leaves' shapes a kernel was traced over in this process: what the
# engine's ``state_rows_one_pass`` reads, so that it says what the step
# programs hold and not what a second copy of the rule would choose
_TRACED: set = set()


def one_pass(state: jax.Array) -> bool:
    """Whether the decode rows' step over ``state`` runs as the kernel:
    on a TPU, over a float32 leaf of a matrix a head (``(L, rows, H, ., .)``:
    Mamba-2's and the delta rule's; Mamba-1's ``(L, rows, N, I)`` has no
    head axis and keeps its pass)."""
    return (paged_attention.on_tpu() and state.ndim == 5
            and state.dtype == jnp.float32)


def traced(shape: tuple) -> bool:
    """Whether a step program traced in this process advances the decode
    rows of a state leaf of ``shape`` through the kernel."""
    return tuple(shape) in _TRACED


def _heads_a_step(heads: int, head_bytes: int) -> int:
    """Heads of one grid step: the most that divide ``heads`` within
    ``BLOCK_BYTES``, in whole sublane tiles of 8 (the entries' vectors are
    cut ``(heads a step, width)``) unless the block takes every head."""
    fits = [n for n in range(1, heads + 1)
            if heads % n == 0 and (n % 8 == 0 or n == heads)
            and n * head_bytes <= BLOCK_BYTES]
    return max(fits) if fits else heads


def _mamba2_kernel(layer_ref, rows_ref, n_ref, fresh_ref, decay_ref, s_ref,
                   dx_ref, b_ref, c_ref, out_ref, z_ref):
    i, first = _place(rows_ref, s_ref)

    @pl.when(i < n_ref[0])
    def _():
        fresh = fresh_ref[rows_ref[i]] != 0

        def head(h, carry):
            at = pl.ds(h, 1)
            s0 = jnp.where(fresh, 0.0, s_ref[0, 0, h])          # (P, N)
            decay = decay_ref[first + h]
            z = decay * jnp.sum(s0 * c_ref[0, at, :], axis=-1,
                                keepdims=True)                   # (P, 1)
            z_ref[0, at, :] = z.reshape(1, -1)
            out_ref[0, 0, h] = (decay * s0 + dx_ref[0, at, :].reshape(-1, 1)
                                * b_ref[0, at, :])
            return carry

        jax.lax.fori_loop(0, s_ref.shape[2], head, 0)

    _rest(i, n_ref, s_ref, out_ref)


def _delta_kernel(layer_ref, rows_ref, n_ref, fresh_ref, s_ref, q_ref,
                  k_ref, v_ref, g_ref, beta_ref, out_ref, o_ref):
    i = pl.program_id(0)

    @pl.when(i < n_ref[0])
    def _():
        # one batched expression over the block's heads: decay, k and q
        # scale a head's state along K, its sublanes, and turning a
        # ``(1, K)`` operand into a column a head at a time costs more
        # than the two passes this replaces (PERF.md, PR 45); here the
        # block's ``(heads, K)`` operands are turned once
        s0 = jnp.where(fresh_ref[rows_ref[i]] != 0, 0.0, s_ref[0, 0])
        k_rows, q_rows = k_ref[0], q_ref[0]                      # (hb, K)
        k, q = k_rows[..., None], q_rows[..., None]              # (hb, K, 1)
        sd = jnp.exp(g_ref[0])[..., None] * s0                   # (hb, K, V)
        sk = jnp.sum(sd * k, axis=1)                             # (hb, V)
        sq = jnp.sum(sd * q, axis=1)
        u = beta_ref[0] * (v_ref[0] - sk)
        o_ref[0] = sq + jnp.sum(k_rows * q_rows, axis=-1, keepdims=True) * u
        out_ref[0, 0] = sd + k * u[:, None, :]

    _rest(i, n_ref, s_ref, out_ref)


def _place(rows_ref, s_ref):
    """A grid step's place in the list of rows, and where its first head
    lies in the rows' per-head scalars ``(R x H,)``: they ride in SMEM, a
    scalar a (row, head)."""
    i = pl.program_id(0)
    block = rows_ref[i] * pl.num_programs(1) + pl.program_id(1)
    return i, block * s_ref.shape[2]


def _rest(i, n_ref, s_ref, out_ref):
    """A step with no row to advance: every grid step rests on one block,
    which goes back as it came."""
    @pl.when((n_ref[0] == 0) & (i == 0) & (pl.program_id(1) == 0))
    def _():
        out_ref[...] = s_ref[...]


def _walk(kernel, name, state, layer, row_len, row_fresh, scalars, vectors,
          out_width, interpret):
    """``kernel`` over the (row, head block)s of the rows whose run is one
    entry. ``scalars``: arrays ``(R, H)`` float32 of a scalar a (row,
    head), prefetched; ``vectors``: the rows' operands ``(R, H, width)``
    float32, cut like the state. -> (state', the rows' output
    ``(R, H, out_width)`` float32, zero for the rows not advanced)."""
    if not interpret:
        _TRACED.add(state.shape)
    heads, a, b = state.shape[2:]
    r = row_len.shape[0]
    hb = _heads_a_step(heads, a * b * 4)
    blocks = heads // hb
    one = row_len == 1
    rows = jnp.argsort(~one, stable=True).astype(jnp.int32)
    n = one.sum().astype(jnp.int32).reshape(1)

    def at(i, j, layer, rows, n, *_):
        # (row, head block) of a grid step; past the list: the last block
        # visited, again
        return (rows[jnp.minimum(i, jnp.maximum(n[0] - 1, 0))],
                jnp.where(i < n[0], j, blocks - 1))

    state_spec = pl.BlockSpec(
        (1, 1, hb, a, b),
        lambda i, j, layer, *rest: (layer[0], *at(i, j, layer, *rest), 0, 0))
    row_spec = lambda width: pl.BlockSpec(
        (1, hb, width), lambda *args: (*at(*args), 0))
    state, out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4 + len(scalars), grid=(r, blocks),
            in_specs=[state_spec] + [row_spec(v.shape[-1]) for v in vectors],
            out_specs=[state_spec, row_spec(out_width)]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((r, heads, out_width), jnp.float32)],
        input_output_aliases={4 + len(scalars): 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=4 * hb * a * b * 4 + (16 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=8 * r * heads * a * b, transcendentals=r * heads * a,
            bytes_accessed=2 * r * heads * a * b * 4),
        name=name, interpret=interpret,
    )(layer.astype(jnp.int32).reshape(1), rows, n,
      row_fresh.astype(jnp.int32), *(v.reshape(-1) for v in scalars), state,
      *vectors)
    # a block the walk never reached holds nothing
    return state, jnp.where(one[:, None, None], out, 0.0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def state_step_mamba2(ssm: jax.Array, layer: jax.Array, row_last: jax.Array,
                      row_len: jax.Array, row_fresh: jax.Array,
                      da: jax.Array, dt: jax.Array, x: jax.Array,
                      b: jax.Array, c: jax.Array, interpret: bool = False):
    """``ops.ssm._advance_single`` as one pass. ssm (L, rows, H, P, N)
    f32; the run plan's ``row_last``, ``row_len``, ``row_fresh`` (R,); da,
    dt (T, H) f32; x (T, H, P); b, c (T, G, N). -> (ssm', z (R, H, P)
    f32)."""
    h = x.shape[1]
    e = row_last
    per_head = lambda v: jnp.repeat(v[e], h // v.shape[-2],
                                    axis=-2).astype(jnp.float32)
    return _walk(
        _mamba2_kernel, "state_step_mamba2", ssm, layer, row_len, row_fresh,
        (jnp.exp(da[e]),),
        (dt[e][..., None] * x[e].astype(jnp.float32), per_head(b),
         per_head(c)), x.shape[-1], interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def state_step_delta(state: jax.Array, layer: jax.Array, row_last: jax.Array,
                     row_len: jax.Array, row_fresh: jax.Array, q: jax.Array,
                     k: jax.Array, v: jax.Array, g: jax.Array,
                     beta: jax.Array, interpret: bool = False):
    """``ops.delta_rule._advance_single`` as one pass. state
    (L, rows, H, K, V) f32; the run plan's ``row_last``, ``row_len``,
    ``row_fresh`` (R,); q, k, g (T, H, K) f32; v (T, H, V) f32; beta
    (T, H) f32. -> (state', o (R, H, V) f32)."""
    e = row_last
    return _walk(
        _delta_kernel, "state_step_delta", state, layer, row_len, row_fresh,
        (), (q[e], k[e], v[e], g[e], beta[e][..., None]), v.shape[-1],
        interpret)
