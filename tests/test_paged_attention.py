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
    seq_row, positions, *tables = _flat_batches(bs)[batch]
    tables = jnp.asarray(
        tables[0] if tables else _private_tables(LAYOUTS[bs][0]), jnp.int32)
    latent = hkv == LATENT
    if latent:
        hkv, d = 1, LATENT_ROW
    ks = jax.random.split(jax.random.PRNGKey(hq * 10 + hkv), 3)
    k_leaf, v_leaf = (
        jax.random.normal(k, (LAYERS, NB, bs, hkv, d),
                          jnp.float32).astype(dtype) for k in ks[:2])
    q = jax.random.normal(ks[2], (len(seq_row), hq, d),
                          jnp.float32).astype(dtype)
    return (q, k_leaf, None if latent else v_leaf,
            jnp.asarray(1, jnp.int32), tables, jnp.asarray(seq_row),
            jnp.asarray(positions))


def _run_rows(q, k_leaf, v_leaf, layer, tables, seq_row, positions):
    bs = k_leaf.shape[2]
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
