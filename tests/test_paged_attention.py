"""Paged-attention Pallas kernel (interpret mode) vs the gather+einsum
reference the engine's default paged path uses: block-table indirection,
GQA grouping, ragged lengths, block skipping; the latent pool's one-leaf
form among the same cases. Through ``forward_paged`` and the engine:
``tests/test_paged_attention_engine.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from senweaver_ide_tpu.ops.attention import attention
from senweaver_ide_tpu.ops.paged_attention import paged_flash_decode


def _mk(t, nb, bs, mb, hq, hkv, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (t, hq, d), jnp.float32)
    k_pool = jax.random.normal(ks[1], (nb, bs, hkv, d), jnp.float32)
    v_pool = jax.random.normal(ks[2], (nb, bs, hkv, d), jnp.float32)
    tables = jax.random.randint(ks[3], (t, mb), 0, nb)
    return q, k_pool, v_pool, tables


def _ref(q, k_pool, v_pool, tables, lengths):
    """Gather the tables into contiguous per-token sequences and run the
    einsum cache attention — exactly models.transformer._paged_layer's
    non-kernel path."""
    t, mb = tables.shape
    _, bs, hkv, d = k_pool.shape
    k_seq = k_pool[tables].reshape(t, mb * bs, hkv, d)
    v_seq = v_pool[tables].reshape(t, mb * bs, hkv, d)
    valid = jnp.arange(mb * bs)[None, :] < lengths[:, None]
    return attention(q[:, None], k_seq, v_seq, q_offset=lengths - 1,
                     kv_mask=valid, causal=True)[:, 0]


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
def test_matches_gather_reference(hq, hkv):
    t, nb, bs, mb, d = 5, 9, 16, 4, 16
    q, k_pool, v_pool, tables = _mk(t, nb, bs, mb, hq, hkv, d)
    lengths = jnp.asarray([1, 17, 33, 64, 50], jnp.int32)
    out = paged_flash_decode(q, k_pool, v_pool, tables, lengths,
                             interpret=True)
    ref = _ref(q, k_pool, v_pool, tables, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_aliased_blocks_shared_prefix():
    """Several tokens reading THROUGH the same physical blocks (the COW
    shared-prefix shape) must each see the same keys."""
    t, nb, bs, mb, d, hq, hkv = 4, 6, 8, 3, 16, 4, 2
    q, k_pool, v_pool, _ = _mk(t, nb, bs, mb, hq, hkv, d, seed=3)
    # every token's table aliases the same two prefix blocks, then a
    # private third
    tables = jnp.asarray([[0, 1, 2 + i % 3] for i in range(t)],
                         jnp.int32)
    lengths = jnp.asarray([20, 24, 17, 21], jnp.int32)
    out = paged_flash_decode(q, k_pool, v_pool, tables, lengths,
                             interpret=True)
    ref = _ref(q, k_pool, v_pool, tables, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_scalar_length_broadcasts():
    t, nb, bs, mb, d, hq, hkv = 3, 5, 8, 2, 16, 4, 2
    q, k_pool, v_pool, tables = _mk(t, nb, bs, mb, hq, hkv, d, seed=4)
    out = paged_flash_decode(q, k_pool, v_pool, tables, 12,
                             interpret=True)
    ref = _ref(q, k_pool, v_pool, tables,
               jnp.full((t,), 12, jnp.int32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_length_one_skips_dead_blocks():
    """A fresh row (length 1) must ignore every block past the first —
    garbage in dead table entries cannot contaminate the output."""
    t, nb, bs, mb, d, hq, hkv = 2, 4, 8, 4, 16, 4, 2
    q, k_pool, v_pool, tables = _mk(t, nb, bs, mb, hq, hkv, d, seed=5)
    tables = tables.at[:, 0].set(jnp.asarray([0, 1]))  # live blocks
    lengths = jnp.asarray([1, 1], jnp.int32)
    out = paged_flash_decode(q, k_pool, v_pool, tables, lengths,
                             interpret=True)
    # poison all non-first blocks: output must not move
    poison = jnp.full_like(k_pool, 1e4)
    k_bad = k_pool.at[2:].set(poison[2:])
    v_bad = v_pool.at[2:].set(poison[2:])
    tables_bad = tables.at[:, 1:].set(3)
    out_bad = paged_flash_decode(q, k_bad, v_bad, tables_bad, lengths,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_bad),
                               atol=2e-5, rtol=2e-5)


# ---- paged_attention_rows: the flat batch, a run of one row at a time -----

from unittest import mock

from senweaver_ide_tpu.ops import paged_attention
from senweaver_ide_tpu.ops.paged_attention import (
    paged_attention_rows, paged_latent_attention_rows, plan_rows)

LAYERS, NB, BS, MB, ROWS = 3, 80, 4, 12, 6
# The block-size axis: the batches below at blocks of 4 with a table 12
# wide, and at a block the engine resolves for a narrow KV row
# (``paged_kv.resolve_block_size``) with a table 4 wide, where every run
# starts at twice its position: it still begins inside a block and ends in
# a later one. block size -> (table width, position scale)
LAYOUTS = {BS: (MB, 1), 32: (4, 2)}


def _private_tables(mb=MB):
    """Six rows of ``mb`` blocks, no block twice."""
    return np.random.default_rng(0).permutation(NB)[:ROWS * mb].reshape(
        ROWS, mb)


# name -> (seq_row, positions[, tables]); a row's entries are in order.
def _flat_batches(bs=BS):
    mb, at = LAYOUTS[bs]
    chunk = lambda row, lo, n: ([row] * n, list(range(at * lo, at * lo + n)))
    rows = lambda row, pos: (row, [at * p for p in pos])
    cat = lambda *parts: tuple(np.asarray(sum((p[i] for p in parts), []),
                                          np.int32) for i in (0, 1))
    tables = _private_tables(mb)
    forked = tables.copy()
    shared = -(-20 * at // bs)          # the blocks of a prompt of 20 (40):
    forked[1, :shared] = forked[0, :shared]   # rows 1, 2 share row 0's, a
    forked[2, :shared] = forked[0, :shared]   # forked group's prompt
    return {
        "decode-rows": cat(rows([0, 1, 2, 3, 4, 5], [5, 0, 40, 17, 47, 16])),
        # 19 and 7 queries: tiles of 4 with a remainder, chunks of 2 blocks
        "two-chunks": cat(rows([0], [9]), chunk(1, 7, 19), chunk(3, 0, 7),
                          rows([5], [30])),
        "verify-window": cat(rows([0, 1], [12, 3]), chunk(2, 30, 5),
                             chunk(4, 8, 4)),
        "tail-padding": cat(rows([2, 3], [21, 6]), chunk(5, 3, 6),
                            rows([0] * 5, [0] * 5)),
        "forked-blocks": cat(rows([0, 1, 2], [19, 21, 23]), chunk(3, 0, 5))
        + (forked,),
        # row 1 decodes, row 4 has a chunk, then row 1's verify tail comes
        # in a second run: two segments of one row
        "row-in-two-runs": cat(chunk(1, 10, 3), chunk(4, 2, 6),
                               ([1, 1], [at * 10 + 3, at * 10 + 4])),
        "rows-of-length-one": cat(rows([3, 1, 0], [0, 0, 0])),
    }


# The latent pool's form, as ``_paged_mla_attend`` hands it over: ONE leaf
# of rows 640 wide whose first 512 columns are the value too (GLM-4.7-
# Flash's 20 heads, row and rank), and the model's scale, which is not
# ``1 / sqrt(640)``. In the parametrisations below ``hkv == LATENT``
# stands for it.
LATENT = "latent"
LATENT_ROW, LATENT_RANK, LATENT_SCALE = 640, 512, 1.0 / 16.0


def _gather_reference(q, k_leaf, v_leaf, layer, tables, seq_row, positions):
    """``_paged_layer``'s gather path: every entry's whole table width.
    Without a ``v_leaf``, ``_paged_mla_attend``'s: scores against the whole
    row at the model's scale, the sum over the same rows cut to the rank."""
    t, width = q.shape[0], tables.shape[1] * k_leaf.shape[2]
    tbl = tables[seq_row]
    valid = jnp.arange(width)[None, :] < positions[:, None] + 1
    if v_leaf is None:
        prec = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
                else None)
        seq = k_leaf[layer, tbl].reshape(t, width, k_leaf.shape[-1])
        scores = jnp.einsum("thc,tsc->ths", q, seq, precision=prec,
                            preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(jnp.where(
            valid[:, None, :], scores * LATENT_SCALE, -1e30), axis=-1)
        return jnp.einsum(
            "ths,tsc->thc", probs.astype(q.dtype), seq, precision=prec,
            preferred_element_type=jnp.float32)[..., :LATENT_RANK].astype(
                q.dtype)
    k_seq, v_seq = (leaf[layer, tbl].reshape((t, width) + leaf.shape[3:])
                    for leaf in (k_leaf, v_leaf))
    return attention(q[:, None], k_seq, v_seq, q_offset=positions,
                     kv_mask=valid, causal=True)[:, 0]


def _rows_case(batch, hq, hkv, dtype, d=16, bs=BS):
    """A batch of ``_flat_batches`` or of ``SHARE_GROUPS`` (below), its
    queries and a pool that holds every block its tables name."""
    seq_row, positions, *tables = (SHARE_GROUPS.get(batch)
                                   or _flat_batches(bs)[batch])[:3]
    tables = jnp.asarray(
        tables[0] if tables else _private_tables(LAYOUTS[bs][0]), jnp.int32)
    latent = hkv == LATENT
    if latent:
        hkv, d = 1, LATENT_ROW
    ks = jax.random.split(jax.random.PRNGKey(hq * 10 + hkv), 3)
    k_leaf, v_leaf = (
        jax.random.normal(k, (LAYERS, max(NB, int(tables.max()) + 1), bs,
                              hkv, d), jnp.float32).astype(dtype)
        for k in ks[:2])
    q = jax.random.normal(ks[2], (len(seq_row), hq, d),
                          jnp.float32).astype(dtype)
    return (q, k_leaf, None if latent else v_leaf,
            jnp.asarray(1, jnp.int32), tables, jnp.asarray(seq_row),
            jnp.asarray(positions))


def _run_rows(q, k_leaf, v_leaf, layer, tables, seq_row, positions,
              plan=None):
    bs = k_leaf.shape[2]
    if plan is None:
        plan = plan_rows(seq_row, positions, block_size=bs,
                         table_width=tables.shape[1], q_tile=4)
    # a score tile two blocks wide, so a run takes several compute steps
    with mock.patch.object(paged_attention, "TILE_COLS",
                           2 * bs * k_leaf.shape[3]):
        if v_leaf is None:
            return paged_latent_attention_rows(
                q, k_leaf, layer, tables, positions, plan,
                scale=LATENT_SCALE, value_dim=LATENT_RANK, interpret=True)
        return paged_attention_rows(q, k_leaf, v_leaf, layer, tables,
                                    positions, plan, interpret=True)


HEADS = [(4, 4), (4, 2), (8, 1), (12, 2), (20, LATENT)]


def _with_a_larger_block(batches, larger):
    """(batch, block size): every batch at blocks of 4, and the ``larger``
    ones again at the larger block, for each head shape."""
    return ([(b, BS) for b in batches]
            + [(b, bs) for b in larger for bs in LAYOUTS if bs != BS])


@pytest.mark.parametrize("hq,hkv", HEADS)
@pytest.mark.parametrize("batch,bs", _with_a_larger_block(
    list(_flat_batches()), ["two-chunks", "forked-blocks"]))
def test_rows_kernel_matches_gather_f32(batch, bs, hq, hkv):
    args = _rows_case(batch, hq, hkv, jnp.float32, bs=bs)
    np.testing.assert_allclose(np.asarray(_run_rows(*args)),
                               np.asarray(_gather_reference(*args)),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("hq,hkv", HEADS)
@pytest.mark.parametrize("batch,bs", _with_a_larger_block(
    ["two-chunks", "forked-blocks", "row-in-two-runs"],
    ["row-in-two-runs"]))
def test_rows_kernel_matches_gather_bf16(batch, bs, hq, hkv):
    """bf16 operands, f32 scores and accumulator, like ops/attention: a
    bf16 ulp or two of an O(1) output."""
    args = _rows_case(batch, hq, hkv, jnp.bfloat16, bs=bs)
    got, want = _run_rows(*args), _gather_reference(*args)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


@pytest.mark.parametrize("hq,hkv,q_tile,chunk", [
    (12, 2, 32, 16), (28, 4, 16, 8), (16, 8, 32, 4), (32, 8, 16, 4),
    (16, 16, 32, 2), (32, 32, 16, 1), (64, 8, 8, 4), (8, 1, 32, 32)])
def test_score_tile_follows_the_head_shape(hq, hkv, q_tile, chunk):
    """The kernel's tile of a compute step, (queries x padded heads) by
    (blocks x positions x kv heads), stays within 512 x 512 at every
    preset's heads: a fixed 32 queries x 16 blocks ran out of VMEM on a
    v5e from 8 kv heads on. The cells' shape keeps 32 x 16."""
    from senweaver_ide_tpu.ops.paged_attention import (blocks_per_chunk,
                                                       query_tile)
    assert (query_tile(hq), blocks_per_chunk(16, hkv)) == (q_tile, chunk)
    assert q_tile * -(-hq // 16) * 16 <= 512 and chunk * 16 * hkv <= 512
    # one block a step at the least, however large a block is
    assert blocks_per_chunk(64, 32) == 1 and query_tile(1024) == 1


@pytest.mark.parametrize("hq,hkv", [(32, 8), (16, 16)])
def test_rows_kernel_with_the_tile_its_heads_give(hq, hkv):
    """Many kv heads, the tile sizes as ``forward_paged`` derives them
    (no override): 16 or 32 queries an item, the chunk a table wide."""
    from senweaver_ide_tpu.ops.paged_attention import query_tile
    q, k_leaf, v_leaf, layer, tables, seq_row, positions = _rows_case(
        "two-chunks", hq, hkv, jnp.float32)
    plan = plan_rows(seq_row, positions, block_size=BS, table_width=MB,
                     q_tile=query_tile(hq))
    got = paged_attention_rows(q, k_leaf, v_leaf, layer, tables, positions,
                               plan, interpret=True)
    want = _gather_reference(q, k_leaf, v_leaf, layer, tables, seq_row,
                             positions)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_plan_rows_cuts_runs_into_items():
    seq_row, positions = _flat_batches()["two-chunks"]
    plan = plan_rows(jnp.asarray(seq_row), jnp.asarray(positions),
                     block_size=BS, table_width=MB, q_tile=4)
    n = int(plan.num_items[0])
    # a decode row, 19 queries as 4+4+4+4+3, 7 as 4+3, a decode row
    assert n == 9
    assert plan.row[:n].tolist() == [0, 1, 1, 1, 1, 1, 3, 3, 5]
    assert plan.q0[:n].tolist() == [0, 1, 5, 9, 13, 17, 20, 24, 27]
    assert plan.count[:n].tolist() == [1, 4, 4, 4, 4, 3, 4, 3, 1]
    # blocks up to each ITEM's last position: 9, 10, 14, 18, 22, 25, 3, 6, 30
    assert plan.blocks[:n].tolist() == [3, 3, 4, 5, 6, 7, 1, 2, 8]
    assert not plan.count[n:].any() and not plan.blocks[n:].any()


@pytest.mark.parametrize("bs", list(LAYOUTS))
@pytest.mark.parametrize("hq,hkv", [(4, 2), (20, LATENT)])
def test_rows_kernel_reads_no_dead_block(hq, hkv, bs):
    """Blocks past a run's last position are not read: NaN in them (which
    a masked column would still carry into the weighted sum), and any id
    in the dead table entries, cannot move the output."""
    q, k_leaf, v_leaf, layer, tables, seq_row, positions = _rows_case(
        "verify-window", hq, hkv, jnp.float32, bs=bs)
    want = _run_rows(q, k_leaf, v_leaf, layer, tables, seq_row, positions)
    live = np.zeros(k_leaf.shape[1], bool)
    for row, pos in zip(np.asarray(seq_row), np.asarray(positions)):
        live[np.asarray(tables)[row, :pos // bs + 1]] = True
    poison = jnp.where(jnp.asarray(live)[None, :, None, None, None], 0,
                       jnp.nan)
    dead = np.arange(tables.shape[1])[None, :] > np.asarray(
        jax.ops.segment_max(positions, seq_row, ROWS))[:, None] // bs
    got = _run_rows(q, k_leaf + poison,
                    None if v_leaf is None else v_leaf + poison, layer,
                    jnp.where(dead, 7, tables), seq_row, positions)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ---- share groups: rows whose tables hold the same blocks attend them once


def _forked_tables(groups, mb=MB, rows=12):
    """``rows`` rows of ``mb`` private blocks, then for each of ``groups``
    (donor, followers, blocks) the followers' leading ``blocks`` made the
    donor's: a rollout group's fork, a grafted prefix."""
    tables = np.random.default_rng(1).permutation(200)[:rows * mb].reshape(
        rows, mb)
    for donor, followers, blocks in groups:
        for f in followers:
            tables[f, :blocks] = tables[donor, :blocks]
    return tables


def _decode(rows, positions):
    return list(rows), list(positions)


def _window(row, lo, n):
    return [row] * n, list(range(lo, lo + n))


def _batch(*parts):
    return tuple(np.asarray(sum((p[i] for p in parts), []), np.int32)
                 for i in (0, 1))


# name -> (seq_row, positions, tables, group items, block reads saved);
# blocks of 4, a table 12 wide, up to 8 members a group item. A row's last
# live block is never shared: it is the one being written.
SHARE_GROUPS = {
    # a rollout group: 8 rows over a prompt of 5 blocks, each further on
    "group-of-8": _batch(_decode(range(8), [21, 25, 30, 22, 41, 33, 27, 47]))
    + (_forked_tables([(0, range(1, 8), 5)]), 1, 5 * 7),
    "partial-group-of-3": _batch(_decode([4, 2, 7], [13, 19, 14]),
                                 _decode([5], [9]))
    + (_forked_tables([(2, [4, 7], 3)]), 1, 3 * 2),
    # two groups whose members alternate in the batch, sharing 5 and 2
    # blocks; the second's last member comes before the first's
    "interleaved-groups": _batch(
        _decode([0, 8, 1, 9, 2, 10, 3], [22, 9, 31, 12, 20, 8, 27]))
    + (_forked_tables([(0, [1, 2, 3], 5), (8, [9, 10], 2)]), 2,
       5 * 3 + 2 * 2),
    # 11 members: a group item of 8 and one of 3 over the same blocks
    "group-of-11": _batch(_decode(range(11), range(17, 39, 2)))
    + (_forked_tables([(0, range(1, 11), 4)]), 2, 4 * 7 + 4 * 2),
    # 9 members: the ninth is alone in its tile and stays a plain item
    "group-of-9": _batch(_decode(range(9), range(17, 35, 2)))
    + (_forked_tables([(0, range(1, 9), 4)]), 1, 4 * 7),
    # a group beside a prefill chunk (19 queries: tiles of 4 and a
    # remainder whose stores run over the next entries) and a verify window
    "beside-chunk-and-window": _batch(
        _decode([0], [22]), _window(5, 7, 19), _decode([1, 2], [29, 21]),
        _window(6, 30, 5), _decode([3], [25]), _decode([0] * 3, [0] * 3))
    + (_forked_tables([(0, [1, 2, 3], 5)]), 1, 5 * 3),
    # row 2 copied its boundary block on write and diverges one block
    # early: a row goes with the mates of its DEEPEST common block, so the
    # other three share all five and row 2, left without one, reads alone
    "diverges-a-block-early": _batch(_decode([0, 1, 2, 3], [22, 26, 24, 30]))
    + ((lambda t: (t.__setitem__((2, 4), 199), t)[1])(
        _forked_tables([(0, [1, 2, 3], 5)])), 1, 5 * 2),
    # two of four diverge a block early, the boundary block their own: two
    # groups, and each shares what its members do
    "two-diverge-early": _batch(_decode([0, 1, 2, 3], [22, 26, 24, 30]))
    + ((lambda t: (t.__setitem__((slice(2, 4), 4), t[4, 4]), t)[1])(
        _forked_tables([(0, [1, 2, 3], 5)])), 2, 5 + 5),
    # a member still inside the forked prompt's last block (a follower's
    # first step) may share only what lies before it: one block less than
    # its mates have in common, so for this step they go without it
    "member-inside-the-prompt": _batch(_decode([0, 1, 2], [22, 18, 26]))
    + (_forked_tables([(0, [1, 2], 5)]), 1, 5),
    # all of them inside it: the blocks before it are the group's
    "group-inside-the-prompt": _batch(_decode([0, 1, 2], [19, 18, 17]))
    + (_forked_tables([(0, [1, 2], 5)]), 1, 4 * 2),
    # a system prompt under every row and a group's prompt above it: a row
    # groups with those it shares the MOST with; row 7 has no such mate
    "prefix-under-groups": _batch(
        _decode([0, 1, 2, 3, 4, 5, 7], [30, 33, 28, 31, 29, 35, 17]))
    + (_forked_tables([(0, range(1, 8), 2), (0, [1, 2], 6),
                       (3, [4, 5], 5)]), 2, 6 * 2 + 5 * 2),
    # forks, but each row's only live block past the fork is its last
    # (nothing before it is shared by two LIVE rows): today's items
    "unshared": _batch(_decode([0, 1, 2, 3], [5, 17, 9, 30]),
                       _window(4, 2, 6))
    + (_forked_tables([]), 0, 0),
    "forked-but-first-block-only": _batch(_decode([0, 1, 2], [1, 2, 3]))
    + (_forked_tables([(0, [1, 2], 1)]), 0, 0),
}


def _host_recount(seq_row, positions, tables, bs, group_tile):
    """(group items, block reads saved) from the tables, the slow way:
    rows a and b have in common their leading equal ids, never past the
    block before either's last; a row's mates share its deepest common
    block; a group is cut in batch order into tiles of ``group_tile``."""
    single = [t for t in range(len(seq_row))
              if (t == 0 or seq_row[t - 1] != seq_row[t])
              and (t + 1 == len(seq_row) or seq_row[t + 1] != seq_row[t])]
    ent = {int(seq_row[t]): t for t in single}
    may = {r: positions[t] // bs for r, t in ent.items()}

    def common(a, b):
        n = 0
        while (n < min(may[a], may[b]) and tables[a, n] == tables[b, n]):
            n += 1
        return n

    depth = {a: max([common(a, b) for b in ent if b != a], default=0)
             for a in ent}
    groups = {}
    for a in sorted(ent, key=ent.get):
        if depth[a]:
            groups.setdefault(tables[a, depth[a] - 1], []).append(a)
    items = saved = 0
    for rows in groups.values():
        shared = min(common(a, b) for a in rows for b in rows if a != b
                     ) if len(rows) > 1 else 0
        for i in range(0, len(rows), group_tile):
            n = len(rows[i:i + group_tile])
            if n > 1 and shared:
                items, saved = items + 1, saved + shared * (n - 1)
    return items, saved


def _run_grouped(*args, group_tile=8):
    """``_run_rows`` under the plan that has the tables: (output, plan)."""
    *_, tables, seq_row, positions = args
    plan = plan_rows(seq_row, positions, block_size=BS, table_width=MB,
                     q_tile=4, tables=tables, group_tile=group_tile)
    return _run_rows(*args, plan=plan), plan


@pytest.mark.parametrize("hq,hkv", [(12, 2), (20, LATENT)])
@pytest.mark.parametrize("name", list(SHARE_GROUPS))
def test_share_groups_match_the_gather(name, hq, hkv):
    """Rows whose tables begin with the same physical blocks attend them
    in one group item and their own blocks after it (dense and latent
    form): the gather's numbers, and the plan's counts are a host
    recount's from the tables."""
    args = _rows_case(name, hq, hkv, jnp.float32)
    got, plan = _run_grouped(*args)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_gather_reference(*args)),
                               atol=2e-5, rtol=2e-5)
    seq_row, positions, tables, items, saved = SHARE_GROUPS[name]
    assert (int(plan.group_items), int(plan.kv_blocks_saved)) == (
        items, saved) == _host_recount(seq_row, positions, tables, BS, 8)
    n = int(plan.num_items[0])
    assert int((plan.slot[:n] == -2).sum()) == items
    # every entry is stored by exactly one item
    stores = plan.slot[:n] != -2
    owned = np.concatenate([np.arange(q0, q0 + c) for q0, c, s in zip(
        plan.q0[:n].tolist(), plan.count[:n].tolist(), stores.tolist())
        if s])
    assert sorted(owned.tolist()) == list(range(len(seq_row)))


@pytest.mark.parametrize("name", ["group-of-8", "interleaved-groups",
                                  "beside-chunk-and-window"])
def test_share_groups_match_the_gather_bf16(name):
    args = _rows_case(name, 12, 2, jnp.bfloat16)
    got, _ = _run_grouped(*args)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(_gather_reference(*args), np.float32), atol=2e-2)


@pytest.mark.parametrize("name", ["unshared", "forked-but-first-block-only"]
                         + [b for b in _flat_batches()
                            if b != "forked-blocks"])
def test_an_unshared_batch_plans_the_items_it_did(name):
    """No two decode rows hold a block in common: the plan with the tables
    is the plan without them, item for item, and no group item."""
    if name in SHARE_GROUPS:
        seq_row, positions, tables, *_ = SHARE_GROUPS[name]
    else:
        (seq_row, positions), tables = (_flat_batches()[name],
                                        _private_tables())
    kw = dict(block_size=BS, table_width=MB, q_tile=4)
    old = plan_rows(jnp.asarray(seq_row), jnp.asarray(positions), **kw)
    new = plan_rows(jnp.asarray(seq_row), jnp.asarray(positions), **kw,
                    tables=jnp.asarray(tables), group_tile=8)
    t = len(seq_row)
    for field in ("row", "q0", "count", "first", "blocks", "slot"):
        assert getattr(new, field)[:t].tolist() == getattr(
            old, field).tolist(), field
        assert not getattr(new, field)[t:].any()
    assert not old.first.any() and int(new.num_items[0]) == int(
        old.num_items[0])
    n = int(old.num_items[0])
    assert (old.slot[:n] == -1).all()
    assert int(new.group_items) == int(new.kv_blocks_saved) == 0
    assert (new.group_tile, old.group_tile) == (8, 0)


def test_group_tile_follows_the_head_shape():
    """8 members a group item where 8 x the padded heads fit the score
    tile's 512 rows (qwen 128 head rows, falcon / glm / xing 256, longcat
    512), fewer above; a group tile of 2 cuts a group of 8 in four."""
    from senweaver_ide_tpu.ops.paged_attention import group_tile
    assert [group_tile(h) for h in (12, 20, 32, 64, 128, 512)] == [
        8, 8, 8, 8, 4, 1]
    args = _rows_case("group-of-8", 12, 2, jnp.float32)
    got, plan = _run_grouped(*args, group_tile=2)
    assert (int(plan.group_items), int(plan.kv_blocks_saved)) == (4, 5 * 4)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_gather_reference(*args)),
                               atol=2e-5, rtol=2e-5)


# ---- the block's size: one rule of the KV row's bytes and the row's length


def _kv_shape(name):
    """A configuration's KV shape: a benchmark cell's from its own file,
    else a preset's."""
    from senweaver_ide_tpu.models.config import PRESETS, get_config
    if name in PRESETS:
        return get_config(name)
    from benchmark import manifest
    return manifest.model_config(manifest.load_json(
        manifest.HERE, "configs", f"{name}.json"))


@pytest.mark.parametrize("config,kv_dtype,max_len,row_bytes,want", [
    # the four cells: 12/2 x 128 bf16; 20/4 x 128; the latent row, 576
    # values stored 640 wide, under 20 and under 32 heads
    ("qwen2.5-coder-1.5b", "bf16", 1024, 512, 128),
    ("falcon-h1-34b-instruct", "bf16", 4096, 1024, 64),
    ("glm-4.7-flash", "bf16", 4096, 1280, 64),
    ("xing4.0-29b-a4b", "bf16", 4096, 1280, 64),
    # a one-byte pool stores twice the tokens in the same copy, up to 128
    ("qwen2.5-coder-1.5b", "fp8", 1024, 256, 128),
    ("falcon-h1-34b-instruct", "int8", 4096, 512, 128),
    # 8 kv heads: 32 tokens are a copy of 64 KiB; 32 kv heads: a block of
    # 16 is one of 128 KiB already
    ("qwen3-8b", "bf16", 4096, 2048, 32),
    ("deepseek-coder-6.7b", "bf16", 4096, 8192, 16),
    # a short row keeps 8 blocks; never under 16 tokens
    ("qwen2.5-coder-1.5b", "bf16", 4096, 512, 128),
    ("qwen2.5-coder-1.5b", "bf16", 512, 512, 64),
    ("qwen2.5-coder-1.5b", "bf16", 256, 512, 32),
    ("qwen2.5-coder-1.5b", "bf16", 64, 512, 16),
    # the tiny presets' float32 rows are narrow: their size is the row's
    # length alone, so most tests' engines (max_len <= 128) keep 16
    ("tiny-test", "bf16", 128, None, 16),
    ("tiny-test", "bf16", 1024, None, 128)])
def test_block_size_follows_the_row(config, kv_dtype, max_len, row_bytes,
                                    want):
    """``EngineConfig.block_size`` None: the smallest power of two of
    tokens, 16 to 128, whose copy of one payload leaf reaches
    ``COPY_TARGET_BYTES``, halved while the row would hold under 8
    blocks."""
    from senweaver_ide_tpu.rollout import paged_kv
    c = _kv_shape(config)
    got_bytes = paged_kv.kv_row_bytes(c, kv_dtype)
    if row_bytes is not None:
        assert got_bytes == row_bytes
    bs = paged_kv.resolve_block_size(got_bytes, max_len)
    assert bs == want and isinstance(bs, int)
    # what the rule promises, whatever the target's value
    assert 16 <= bs <= 128 and bs & (bs - 1) == 0
    assert bs == 16 or max_len // bs >= 8
    assert (bs == 16 or bs // 2 * got_bytes < paged_kv.COPY_TARGET_BYTES)
    # the pool's own leaf says the same bytes a token
    pool = jax.eval_shape(lambda: paged_kv.init_paged_pool(
        c, 2, bs, kv_dtype, state_rows=2 if c.ssm else 0))
    assert (np.prod(pool.k.shape[3:]) * pool.k.dtype.itemsize == got_bytes)


@pytest.mark.parametrize("block_size,max_len,want", [
    (None, 64, 16), (None, 1024, 128), (8, 64, 8), (64, 1024, 64)])
def test_engine_config_reads_back_the_resolved_block(block_size, max_len,
                                                     want):
    """An explicit ``block_size`` is taken as it is; None is resolved at
    construction, and ``engine.engine_config`` holds the int the pool was
    built with (the benchmark's warm-up reads it there)."""
    from senweaver_ide_tpu.models.config import get_config
    from senweaver_ide_tpu.models.transformer import init_params
    from senweaver_ide_tpu.rollout.engine import EngineConfig, RolloutEngine
    c = get_config("tiny-test")
    eng = RolloutEngine(
        init_params(c, jax.random.PRNGKey(0)), c, num_slots=2,
        max_len=max_len, engine_config=EngineConfig(block_size=block_size))
    bs = eng.engine_config.block_size
    assert type(bs) is int and bs == want
    assert eng.pool.block_size == eng._alloc.block_size == bs
    # the same tokens in the pool whatever the block: (slots + 4) rows
    assert eng.pool.num_blocks * bs == 6 * max_len
    assert EngineConfig().block_size is None
