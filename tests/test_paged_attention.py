"""Paged-attention Pallas kernel (interpret mode) vs the gather+einsum
reference the engine's default paged path uses: block-table indirection,
GQA grouping, ragged lengths, block skipping."""

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

from senweaver_ide_tpu.models import transformer as tf
from senweaver_ide_tpu.ops import paged_attention
from senweaver_ide_tpu.models.config import get_config
from senweaver_ide_tpu.ops.paged_attention import (paged_attention_rows,
                                                   plan_rows)
from senweaver_ide_tpu.rollout.engine import EngineConfig, RolloutEngine
from senweaver_ide_tpu.rollout.paged_kv import init_paged_pool
from senweaver_ide_tpu.rollout.sampler import SampleParams

LAYERS, NB, BS, MB, ROWS = 3, 80, 4, 12, 6


def _private_tables():
    """Six rows of twelve blocks, no block twice."""
    return np.random.default_rng(0).permutation(NB)[:ROWS * MB].reshape(
        ROWS, MB)


# name -> (seq_row, positions[, tables]); a row's entries are in order.
def _flat_batches():
    chunk = lambda row, lo, n: ([row] * n, list(range(lo, lo + n)))
    cat = lambda *parts: tuple(np.asarray(sum((p[i] for p in parts), []),
                                          np.int32) for i in (0, 1))
    tables = _private_tables()
    forked = tables.copy()
    forked[1, :5] = forked[0, :5]       # rows 1, 2 share row 0's first five
    forked[2, :5] = forked[0, :5]       # blocks: a forked group's prompt
    return {
        "decode-rows": cat(([0, 1, 2, 3, 4, 5], [5, 0, 40, 17, 47, 16])),
        # 19 and 7 queries: tiles of 4 with a remainder, chunks of 2 blocks
        "two-chunks": cat(([0], [9]), chunk(1, 7, 19), chunk(3, 0, 7),
                          ([5], [30])),
        "verify-window": cat(([0, 1], [12, 3]), chunk(2, 30, 5),
                             chunk(4, 8, 4)),
        "tail-padding": cat(([2, 3], [21, 6]), chunk(5, 3, 6),
                            ([0] * 5, [0] * 5)),
        "forked-blocks": cat(([0, 1, 2], [19, 21, 23]), chunk(3, 0, 5))
        + (forked,),
        # row 1 decodes, row 4 has a chunk, then row 1's verify tail comes
        # in a second run: two segments of one row
        "row-in-two-runs": cat(chunk(1, 10, 3), chunk(4, 2, 6),
                               chunk(1, 13, 2)),
        "rows-of-length-one": cat(([3, 1, 0], [0, 0, 0])),
    }


def _gather_reference(q, k_leaf, v_leaf, layer, tables, seq_row, positions):
    """``_paged_layer``'s gather path: every entry's whole table width."""
    t, width = q.shape[0], tables.shape[1] * k_leaf.shape[2]
    tbl = tables[seq_row]
    k_seq, v_seq = (leaf[layer, tbl].reshape((t, width) + leaf.shape[3:])
                    for leaf in (k_leaf, v_leaf))
    valid = jnp.arange(width)[None, :] < positions[:, None] + 1
    return attention(q[:, None], k_seq, v_seq, q_offset=positions,
                     kv_mask=valid, causal=True)[:, 0]


def _rows_case(batch, hq, hkv, dtype, d=16):
    seq_row, positions, *tables = _flat_batches()[batch]
    tables = jnp.asarray(tables[0] if tables else _private_tables(),
                         jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(hq * 10 + hkv), 3)
    k_leaf, v_leaf = (
        jax.random.normal(k, (LAYERS, NB, BS, hkv, d),
                          jnp.float32).astype(dtype) for k in ks[:2])
    q = jax.random.normal(ks[2], (len(seq_row), hq, d),
                          jnp.float32).astype(dtype)
    return (q, k_leaf, v_leaf, jnp.asarray(1, jnp.int32), tables,
            jnp.asarray(seq_row), jnp.asarray(positions))


def _run_rows(q, k_leaf, v_leaf, layer, tables, seq_row, positions):
    plan = plan_rows(seq_row, positions, block_size=BS,
                     table_width=tables.shape[1], q_tile=4)
    # a score tile two blocks wide, so a run takes several compute steps
    with mock.patch.object(paged_attention, "TILE_COLS",
                           2 * BS * k_leaf.shape[3]):
        return paged_attention_rows(q, k_leaf, v_leaf, layer, tables,
                                    positions, plan, interpret=True)


HEADS = [(4, 4), (4, 2), (8, 1), (12, 2)]


@pytest.mark.parametrize("hq,hkv", HEADS)
@pytest.mark.parametrize("batch", list(_flat_batches()))
def test_rows_kernel_matches_gather_f32(batch, hq, hkv):
    args = _rows_case(batch, hq, hkv, jnp.float32)
    np.testing.assert_allclose(np.asarray(_run_rows(*args)),
                               np.asarray(_gather_reference(*args)),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("hq,hkv", HEADS)
@pytest.mark.parametrize("batch", ["two-chunks", "forked-blocks",
                                   "row-in-two-runs"])
def test_rows_kernel_matches_gather_bf16(batch, hq, hkv):
    """bf16 operands, f32 scores and accumulator, like ops/attention: a
    bf16 ulp or two of an O(1) output."""
    args = _rows_case(batch, hq, hkv, jnp.bfloat16)
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


def test_rows_kernel_reads_no_dead_block():
    """Blocks past a run's last position are not read: poison in them, and
    any id in the dead table entries, cannot move the output."""
    q, k_leaf, v_leaf, layer, tables, seq_row, positions = _rows_case(
        "verify-window", 4, 2, jnp.float32)
    want = _run_rows(q, k_leaf, v_leaf, layer, tables, seq_row, positions)
    live = np.zeros(k_leaf.shape[1], bool)
    for row, pos in zip(np.asarray(seq_row), np.asarray(positions)):
        live[np.asarray(tables)[row, :pos // BS + 1]] = True
    poison = jnp.where(jnp.asarray(live)[None, :, None, None, None], 0, 1e4)
    dead = np.arange(MB)[None, :] > np.asarray(
        jax.ops.segment_max(positions, seq_row, ROWS))[:, None] // BS
    got = _run_rows(q, k_leaf + poison, v_leaf + poison, layer,
                    jnp.where(dead, 7, tables), seq_row, positions)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def _tiny():
    return get_config("tiny-test")


def test_forward_paged_kernel_matches_gather_prefill_then_decode():
    """Chunked prefill of two rows, then a decode step at every position
    up to the table's end (with a dropped write riding each step): the
    kernel path's logits and pool equal the gather path's to f32
    rounding."""
    c = _tiny()
    params = tf.init_params(c, jax.random.PRNGKey(0))
    bs, mb = 4, 6
    tables = jnp.asarray([[3, 8, 1, 10, 5, 7], [2, 9, 4, 11, 0, 6]],
                         jnp.int32)
    nb = 12
    run = jax.jit(tf.forward_paged,
                  static_argnames=("config", "use_kernel"))
    pools = {uk: init_paged_pool(c, nb, bs) for uk in (False, True)}
    toks = np.random.default_rng(1).integers(1, c.vocab_size, (2, bs * mb))

    def step(seq_row, positions, drop):
        seq_row, positions = np.asarray(seq_row), np.asarray(positions)
        block = np.asarray(tables)[seq_row, positions // bs]
        batch = dict(
            tokens=jnp.asarray(toks[seq_row, positions], jnp.int32),
            tables=tables, seq_row=jnp.asarray(seq_row, jnp.int32),
            positions=jnp.asarray(positions, jnp.int32),
            write_block=jnp.asarray(np.where(drop, nb, block), jnp.int32),
            write_off=jnp.asarray(positions % bs, jnp.int32))
        out = {}
        for uk in (False, True):
            out[uk], pools[uk] = run(params, config=c, pool=pools[uk],
                                     use_kernel=uk, **batch)
        live = ~np.asarray(drop)
        np.testing.assert_allclose(np.asarray(out[True])[live],
                                   np.asarray(out[False])[live],
                                   atol=1e-5, rtol=1e-5)
        for a, b in zip(pools[True], pools[False]):
            if a is not None:
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-5, rtol=1e-5)

    # prefill: row 0 takes 7 then 4 tokens, row 1 takes 5 beside them
    step([0] * 7 + [1] * 2, list(range(7)) + [0, 1], [False] * 9)
    step([0] * 4 + [1] * 3, list(range(7, 11)) + [2, 3, 4], [False] * 7)
    # decode: both rows a step, and a padding entry on the drop sentinel
    for i in range(bs * mb - 11):
        step([0, 1, 0], [11 + i, 5 + i, 0], [False, False, True])


@pytest.mark.parametrize("sample", ["greedy", "sampled"])
def test_engine_logps_with_the_kernel_equal_teacher_forcing(sample):
    """``paged_kernel=True`` through the engine (interpreted here):
    chunked prefill, decode rows and a forked group, each served token's
    log p against the teacher-forced ``forward``."""
    c = _tiny()
    params = tf.init_params(c, jax.random.PRNGKey(2))
    eng = RolloutEngine(
        params, c, num_slots=4, max_len=64, seed=3,
        sample=SampleParams(temperature=0.0 if sample == "greedy" else 1.0),
        engine_config=EngineConfig(paged_kernel=True, step_tokens=8))
    assert eng.kv_layout == "paged"
    prompts = [list(range(1, 14)), [7, 7, 7]]
    rids = [eng.submit(p, max_new_tokens=9) for p in prompts]
    group_prompt = list(range(30, 51))
    rids += eng.submit_group(group_prompt, 2, max_new_tokens=6)
    prompts += [group_prompt] * 2
    eng.run()
    for p, rid in zip(prompts, rids):
        out = eng.result(rid)
        seq = jnp.asarray([p + out], jnp.int32)
        logits = tf.forward(params, c, seq)[0]
        logp = jax.nn.log_softmax(logits[0].astype(jnp.float32), axis=-1)
        want = [float(logp[len(p) - 1 + i, tok])
                for i, tok in enumerate(out)]
        np.testing.assert_allclose(eng.result_logps(rid), want, atol=2e-4)
    eng._alloc.check_leaks()


@pytest.mark.parametrize("paged_kernel,widths", [(True, {8}),
                                                 (None, {1, 2, 4, 8})])
def test_table_keeps_one_width_where_the_kernel_reads_the_pool(
        paged_kernel, widths):
    """The gather's cost follows the table's width, so the table is cut
    to a ladder of widths, a compiled program each; the kernel reads a
    row's live blocks whatever the width, so there the table stays
    ``blocks_per_row`` wide and the step has one shape a batch width."""
    c = _tiny()
    params = tf.init_params(c, jax.random.PRNGKey(2))
    eng = RolloutEngine(
        params, c, num_slots=2, max_len=32, seed=3,
        engine_config=EngineConfig(paged_kernel=paged_kernel, block_size=4,
                                   step_tokens=8))
    eng.submit(list(range(1, 6)), max_new_tokens=24)
    seen = set()
    while eng.has_work:
        seen.add(eng._tables_device().shape[1])
        eng.step()
    assert seen == widths
