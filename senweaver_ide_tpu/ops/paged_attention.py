"""Paged attention: Pallas TPU kernels reading KV through block tables.

The paged engine (rollout/paged_kv.py) stores KV in a fixed pool of
``(block_size, Hkv, D)`` blocks; each sequence is a list of physical
block ids. The XLA gather path (``models.transformer._paged_layer``)
materializes, for EVERY entry of the flat batch, a contiguous copy of its
sequence's whole table width. The kernels here read the blocks where
they lie.

``paged_attention_rows`` is the one the fused step runs on an
unquantized dense pool, ``paged_latent_attention_rows`` its form for a
latent pool (below). It works **per sequence row, not per entry**: a
*segment* is a maximal run of entries with the same ``seq_row`` (a decode
row is a segment of one, a prefill chunk or a verify window a segment of
n), and a segment's queries attend together over that row's live blocks,
``ceil((max position + 1) / block_size)`` of them, streamed from the
pool leaf AS STORED — stacked over layers, the layer a prefetched scalar —
into VMEM by the kernel's own double-buffered DMAs, a chunk of blocks a
compute step, with an online softmax; each query is masked by
its own position. Nothing of size ``T x table_width x block_size`` is
written anywhere, and no layer is sliced out of the pool.

How it is laid out:

- ``plan_rows`` (plain XLA, once a step, outside the layer scan) finds
  the segments from ``seq_row`` and cuts them into *items*: a segment of
  one query is one item, a longer one ``ceil(n / q_tile)`` items of up
  to ``q_tile`` queries. A row that appears in two runs is two segments:
  slower, never wrong. Tail padding (row 0, position 0) is one segment of
  one block.
- *The group item.* Decode rows whose tables begin with the same
  physical blocks (a rollout group's forked prompt, a grafted prefix, a
  ``fork_request`` tree) would each stream and multiply those blocks:
  eight times a step for a group of eight. ``plan_rows``, given the
  tables, finds such rows from the ids alone (no flag from the engine;
  the rows need be neither adjacent nor in order) and plans, for up to
  ``group_tile`` of them, ONE group item: their queries, folded into
  the rows of one product as a tile of queries is, over the blocks they
  share, read through one member's table. It leaves each member's
  ``m``, ``l``, ``acc`` in scratch and stores nothing; each member's
  *tail item* follows, takes up its member's state, goes on over the
  row's own blocks from the first unshared one and stores: the same
  online softmax over the same positions, reduced in another order. A
  row's last live block is never shared (it is being written), so every
  tail has a block. A chunk's K and V are the MXU's weights, loaded a
  compute step whatever rows they serve: on a v5e an item of 8 queries
  costs r x ONE single-query item over the same blocks, r = 1.12 at
  12/2 heads (128 head rows), 1.63 at 20/4 (256), 1.88 at a latent
  model's 20 heads (256) and 2.76 at 64 (512) (my chip run, PR 38, the
  kernel before the change; 1.35 / 1.82 / 2.58 / 4.08 less a call's
  floor), where the rows one by one cost 8 x.
- The kernel is ONE program (grid of 1) that loops over every (item,
  chunk of its blocks) in order. While chunk g is computed, chunk g+1
  (the same item's next, or the next item's first) is in flight into
  the other half of the buffers. A block's DMA is issued only if the
  item covers it.
- A block arrives as the pool holds it, ``(block_size, Hkv, D)`` with
  the kv heads interleaved position by position, and is used like that:
  the chunk is read as ``(blocks * block_size * Hkv, D)`` rows, every
  query head takes its scores against all of them in one product, and
  the mask keeps the columns of a head's own kv head (``col % Hkv``).
  The other columns get probability 0, so the PV product over the same
  interleaved rows is the grouped one. No strided load, no per-head
  loop; a tile of queries pays ``Hkv`` times the products. On a v5e a
  copy costs ~25 ns (latent ~37) whatever it carries and a (position,
  kv head) column 1.7-3.0 ns: hence ``paged_kv.resolve_block_size``.
- The score tile is bounded whatever the heads (``query_tile``,
  ``blocks_per_chunk``): with many kv heads a compute step takes fewer
  blocks. Mosaic needs head rows of whole 128-lane tiles to cut a
  block out of the pool: ``forward_paged`` keeps the gather for a
  ``head_dim`` that is no multiple of 128.
- Numerics are ``ops/attention.py``'s: low-precision operands on the MXU
  with f32 accumulation, scores, softmax and the output accumulator in
  f32, probabilities cast to the value dtype for the PV product; f32
  inputs take the exact path (``Precision.HIGHEST``).

*The latent pool* (multi-head latent attention, ``_paged_mla_attend``) has
ONE payload leaf, ``(L, num_blocks, block_size, 1, row)``: a token's
``[c_kv | k_rope | 0...]`` row is the key of every head and, in its
first ``kv_lora_rank`` columns, the value too. The same program with
these parameters: one DMA a block into one buffer (handing the dense
form the leaf twice would stream every block twice), the scores against
the whole row, the weighted sum over the lane-aligned window of the
same VMEM rows that holds the value, and the scale the model's
(``1 / sqrt`` of its q/k head width, not of the row's). One "kv head"
under all the query heads, so an item is ``query_tile(Hq)`` queries and
a compute step ``blocks_per_chunk(block_size, 1)`` blocks. On a v5e at
GLM-4.7-Flash's shapes (20 heads, rows 640 wide, rank 512, 48 rows at
contexts 1-3.8k; my chip runs, PR 29 and 33): 0.59 ms a layer for a
48-entry step at blocks of 16, 0.38 at 64 (the gather and its two reads:
1.72 ms), 0.77 / 0.53 ms for a 192-entry one (6.88).

``paged_flash_decode`` is the one-query-a-row form over ONE layer's
pool: unquantized, it is ``paged_attention_rows`` with every entry its
own row. With ``k_scale``/``v_scale`` it is the **dequant-fused**
kernel for int8/fp8 pools (rollout/paged_kv.py quantized ladder): one
(token, logical block) program with the physical block id resolved in
the BlockSpec index maps, the scales riding their own specs through the
same indirection and the rescale fused after the payload's upcast.

``lengths[t]`` counts valid positions including the freshly-written
current token (write-then-attend).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import MASKED_THRESHOLD as _MASKED
from .attention import NEG_INF

# The score tile of a compute step: the head rows of an item's queries by
# the (position, kv head) columns of a chunk of blocks, f32, with its
# mask and probabilities beside it in VMEM. Both sides follow the head
# shape (``query_tile``, ``blocks_per_chunk``): 12/2 heads and blocks of
# 16 give 32 queries x 16 blocks, 32/8 give 16 x 4, 32/32 give 16 x 1.
# Unbounded, a tile of 32 queries x 16 blocks ran out of VMEM from 8 kv
# heads on. On a v5e, 28 layers at the cells' shapes (12/2 x 128, blocks
# of 16; my chip runs, PR 27): 6.1 ms a 48-row step at 16 blocks a chunk,
# 6.5 at 8, 9.0 at 4 (the gather: 14.6); the 192-entry step 7.6 ms at
# (32, 16), 8.5 at (32, 8) (the gather: 55.6). Blocks of 128: 4.2 (PR 33).
TILE_ROWS = 512
TILE_COLS = 512
# head rows a query takes in the tile: whole bf16 sublane tiles, so a
# tile of queries folds into the rows of one product without a relayout
_HEAD_ROWS = 16


def _head_rows(num_q_heads: int) -> int:
    return -(-num_q_heads // _HEAD_ROWS) * _HEAD_ROWS


def query_tile(num_q_heads: int) -> int:
    """Queries an item of a multi-query segment holds, for ``plan_rows``:
    as many as fill the score tile's rows."""
    return max(1, TILE_ROWS // _head_rows(num_q_heads))


def group_tile(num_q_heads: int) -> int:
    """Members of a share group that one group item attends together,
    for ``plan_rows``: 8, a rollout group's size, or as many as fill the
    score tile's rows where 8 do not fit (1: no group item)."""
    return min(8, query_tile(num_q_heads))


def blocks_per_chunk(block_size: int, num_kv_heads: int) -> int:
    """Pool blocks a compute step streams: as many as fill the score
    tile's columns. Where ONE block is most of a tile and not all of it
    (10 kv heads x 32 positions = 320 of 512 columns), three: alone it
    would pay a compute step's floor for 60% of a tile. On a v5e at 40
    query heads over 10 kv heads x 128, blocks of 32, 48 decode rows at
    contexts ~2.5k (my chip run, PR 39; ms a call by blocks a step, 1 / 2
    / 3 / 4): a group's rows over forked tables 1.29 / 0.98 / 0.89 /
    0.88, a 512-position window over rings 0.74 / 0.64 / 0.59 / 0.62,
    with a 145-entry chunk beside 3.80 / 2.71 / 2.44 / 2.37 and 1.28 /
    1.04 / 0.95 / 0.99. Every block of 64, 256 or 512 columns (the heads
    a preset had before) divides the tile and streams as it did."""
    cols = block_size * num_kv_heads
    if cols < TILE_COLS < 2 * cols:
        return 3
    return max(1, TILE_COLS // cols)


def on_tpu() -> bool:
    """Whether programs traced now run on a TPU: what chooses the kernel
    over the XLA gather in ``forward_paged``, and Mosaic over interpret
    mode here."""
    return jax.default_backend() == "tpu"


# a plan item's ``slot``: an item that starts its own softmax and stores
# it, a group item (starts one a member, stores none); 0 and up is a tail
# item, which takes up the group item's state of that member
_FRESH, _GROUP = -1, -2


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["row", "q0", "count", "first", "blocks",
                                "slot", "num_items", "kv_blocks_saved",
                                "group_items"],
                   meta_fields=["q_tile", "group_tile", "window"])
@dataclasses.dataclass(frozen=True)
class RowPlan:
    """The flat batch cut into the kernel's items, int32 vectors one
    entry an item: item i attends queries ``[q0, q0 + count)`` of the
    flat batch, all of table row ``row``, over its logical blocks
    ``[first, blocks)``. Ungrouped items lie in the order of the batch
    with ``first`` 0 and ``slot`` -1; the entries past ``num_items`` hold
    no work. A *group item* (``slot`` -2) attends its ``count`` members'
    queries over the ``blocks`` blocks they share, read through ``row``'s
    table, and stores nothing; the ``count`` items after it are its
    members' *tail items*, each one query (``q0``) over its own row from
    block ``first`` (the group's ``blocks``) on, which takes up the group
    item's softmax of member ``slot`` and stores the whole. A group item
    and its tails lie where the last of its members lies in the batch.
    ``kv_blocks_saved`` and ``group_items`` (scalars) count, over the
    group items, blocks x (members - 1) and 1. ``q_tile`` and
    ``group_tile`` (static) are the most queries an item and the most
    members a group item hold: the kernel sizes its tiles by them.
    ``window`` (static, 0: none): the plan of a window layer, whose items
    start at the first block that any of their queries' trailing
    ``window`` positions lie in, and whose queries the kernel masks to
    those positions."""
    row: jax.Array
    q0: jax.Array
    count: jax.Array
    first: jax.Array
    blocks: jax.Array
    slot: jax.Array
    num_items: jax.Array      # (1,)
    kv_blocks_saved: jax.Array
    group_items: jax.Array
    q_tile: int
    group_tile: int = 0
    window: int = 0


def plan_rows(seq_row: jax.Array, positions: jax.Array, *,
              block_size: int, table_width: int, q_tile: int,
              tables: Optional[jax.Array] = None,
              group_tile: int = 0, window: int = 0) -> RowPlan:
    """Find the segments of a flat batch on the device and cut them into
    items of up to ``q_tile`` queries (``query_tile`` of the model's
    heads; see the module docstring). A boundary is where
    ``seq_row[t] != seq_row[t-1]``; an item covers the blocks up to the
    largest position among ITS queries, so a chunk's early tiles read
    less than its last.

    With the batch's ``tables`` and a ``group_tile`` of 2 or more
    (``group_tile`` of the model's heads) the single-query segments whose
    table rows begin with the same physical blocks are found too, and
    attended as group items and tail items (``RowPlan``). Rows a and b
    have ``common(a, b)`` leading blocks in common: the run of equal ids,
    never past the block before either's last live one (a row's last
    block is its own: it is being written). A row's *key* is the deepest
    block it has in common with any other row; the rows of one key are a
    share group, in the batch's order cut into group items of up to
    ``group_tile``, and the group's ``shared`` is the least ``common``
    among them — the same from whichever member it is taken, since a
    common prefix is an ultrametric — so every member's first ``shared``
    blocks ARE the first member's, whatever the tables hold. A batch in
    which no two such rows share a block plans the items it did without
    ``tables``. A dozen small ops over (rows x rows) and one over (rows
    x rows x table width), once a step (the fused step lowers them for
    every shape it compiles).

    ``window`` > 0 plans a window layer: query t reads positions
    ``(positions[t] - window, positions[t]]`` alone, so an item starts at
    the block its FIRST query's window starts in (a segment's positions
    rise). Such rows are rings that share nothing: no ``tables``."""
    t = seq_row.shape[0]
    seq_row = seq_row.astype(jnp.int32)
    positions = positions.astype(jnp.int32)
    idx = jnp.arange(t, dtype=jnp.int32)
    start = jnp.concatenate(
        [jnp.ones((1,), bool), seq_row[1:] != seq_row[:-1]])
    # an entry opens an item where its segment starts and every q_tile
    # entries after
    seg_q0 = jax.lax.cummax(jnp.where(start, idx, 0))
    opens = (idx - seg_q0) % q_tile == 0
    item = jnp.cumsum(opens.astype(jnp.int32)) - 1        # entry -> item
    # per item its last position and last entry, read back at the entry
    # that opens it
    last, hi = jax.ops.segment_max(
        jnp.stack([positions, idx], 1), item, num_segments=t,
        indices_are_sorted=True)[item].T
    live = lambda pos: jnp.clip((pos + block_size) // block_size, 1,
                                table_width)
    # every entry's own item (the one it opens, if it opens one), how many
    # items it gives and where its own goes
    count, first, blocks = hi - idx + 1, jnp.zeros_like(idx), live(last)
    if window:
        if tables is not None:
            raise ValueError("a window plan takes no tables: its rows are "
                             "rings, which share no block")
        first = jnp.maximum(positions - window + 1, 0) // block_size
    slot = jnp.full_like(idx, _FRESH)
    gives = opens.astype(jnp.int32)
    grouping = tables is not None and group_tile >= 2
    if not grouping:
        n = t
        dest = jnp.where(opens, item, n)
        saved = group_items = jnp.zeros((), jnp.int32)
    else:
        # a group item for every two entries at most, and ``group_tile``
        # entries of slack: a group item reads its members' entries out
        # of the items after it
        n = t + t // 2 + group_tile
        r = tables.shape[0]
        rows = jnp.arange(r, dtype=jnp.int32)
        # a row's single-query segment (its last, had it two), if any
        single = start & jnp.concatenate([start[1:], jnp.ones((1,), bool)])
        ent = jnp.full((r,), -1, jnp.int32).at[
            jnp.where(single, seq_row, r)].max(idx, mode="drop")
        row_live = live(positions[jnp.maximum(ent, 0)])
        may_share = jnp.where(ent >= 0, row_live - 1, 0)
        tables = tables.astype(jnp.int32)
        run = jnp.min(jnp.where(
            tables[:, None, :] == tables[None, :, :], table_width,
            jnp.arange(table_width, dtype=jnp.int32)), axis=-1)
        me = rows[:, None] == rows[None, :]
        common = jnp.where(me, 0, jnp.minimum(
            run, jnp.minimum(may_share[:, None], may_share[None, :])))
        depth = jnp.max(common, axis=1)
        key = jnp.where(depth > 0,
                        tables[rows, jnp.maximum(depth - 1, 0)], -1 - rows)
        same = key[:, None] == key[None, :]               # a row with itself
        shared = jnp.min(jnp.where(same & ~me, common, table_width), axis=1)
        rank = jnp.sum(same & (ent[None, :] < ent[:, None]), axis=1)
        tile = rank // group_tile
        mates = same & (tile[:, None] == tile[None, :])
        members = jnp.sum(mates, axis=1)
        last_ent = jnp.max(jnp.where(mates, ent[None, :], -1), axis=1)
        grouped = (ent >= 0) & (shared > 0) & (members > 1)
        # back to the entries: a grouped row's entry gives no item but at
        # the last of its mates, which gives the group item and the tails
        of_row = lambda x: x[seq_row]
        mine = single & (of_row(ent) == idx) & of_row(grouped)
        member = of_row(rank % group_tile)
        leads = mine & (member == 0)
        at = of_row(jnp.maximum(last_ent, 0))
        gives = jnp.where(mine, jnp.where(at == idx, of_row(members) + 1, 0),
                          gives)
        off = jnp.cumsum(gives) - gives
        dest = jnp.where(mine, off[at] + 1 + member,
                         jnp.where(opens, off, n))
        # a member's own item is its tail
        count = jnp.where(mine, 1, count)
        first = jnp.where(mine, of_row(shared), first)
        blocks = jnp.where(mine, of_row(row_live), blocks)
        slot = jnp.where(mine, member, slot)
        group_items = jnp.sum(leads.astype(jnp.int32))
        saved = jnp.sum(jnp.where(
            leads, of_row(shared) * (of_row(members) - 1), 0))
    items = jnp.zeros((n, 6), jnp.int32).at[dest].set(
        jnp.stack([seq_row, idx, count, first, blocks, slot], 1),
        mode="drop")
    if grouping:
        items = items.at[jnp.where(leads, off[at], n)].set(
            jnp.stack([seq_row, idx, of_row(members), jnp.zeros_like(idx),
                       of_row(shared), jnp.full_like(idx, _GROUP)], 1),
            mode="drop")
    row, q0, count, first, blocks, slot = items.T
    return RowPlan(row=row, q0=q0, count=count, first=first, blocks=blocks,
                   slot=slot, num_items=jnp.sum(gives)[None],
                   kv_blocks_saved=saved, group_items=group_items,
                   q_tile=q_tile, group_tile=group_tile if grouping else 0,
                   window=window)


def _rows_kernel(layer_ref, tables_ref, row_ref, q0_ref, count_ref,
                 first_ref, blocks_ref, slot_ref, num_ref,
                 pos_ref,                                # scalars (SMEM)
                 q_ref, *refs,
                 scale: float, block_size: int, hkv: int, rep: int,
                 hq_pad: int, q_tile: int, group_tile: int, chunk: int,
                 exact: bool, leaves: int, window: int = 0):
    """The whole flat batch in one program; see the module docstring.

    ``refs`` are the pool's ``leaves`` payload leaves in HBM (k and v, or
    the one latent leaf whose rows hold both), the output, a buffer a
    leaf, then ``sems, acc_ref, m_ref, l_ref, qpos_ref, qgrp_ref``. A
    buffer is ``(2, chunk) + a block's shape``: two
    slots of one chunk each. One loop runs over every (item, chunk) in
    order; step g computes out of slot ``g % 2`` what step g-1 started
    into it, after starting its own successor into the other. Every
    started DMA is waited for by the step that computes it, which
    rebuilds the same descriptors from the same scalars. A group item
    leaves its members' ``m``, ``l``, ``acc`` in the scratch rows, member
    s at ``[s * hq_pad, (s + 1) * hq_pad)``, and stores nothing; the tail
    items, which follow it, each move their member's rows to the front in
    place of starting fresh, go on over their own blocks and store. The
    DMA code is traced three times and the compute three (one query, a
    group's tile, a tile of queries) whatever the sizes: the step's
    lowering time is part of ``setup_s``."""
    hbm, out_ref, bufs = refs[:leaves], refs[leaves], refs[leaves + 1:-6]
    sems, acc_ref, m_ref, l_ref, qpos_ref, qgrp_ref = refs[-6:]
    n_cols = chunk * block_size * hkv
    layer = layer_ref[0]
    num_items = num_ref[0]
    precision = jax.lax.Precision.HIGHEST if exact else None

    def for_blocks(item, c, slot, act):
        """``act`` on the copies (one a leaf) of each block of chunk ``c``
        of ``item`` that the item covers, into ``slot``: a loop, so the
        program's size does not grow with the chunk."""
        row = row_ref[item]
        first = first_ref[item] + c * chunk

        def body(b, _):
            phys = tables_ref[row, first + b]
            act([pltpu.make_async_copy(leaf.at[layer, phys],
                                       buf.at[slot, b], sems.at[i, slot])
                 for i, (leaf, buf) in enumerate(zip(hbm, bufs))])
            return 0

        jax.lax.fori_loop(
            0, jnp.minimum(chunk, blocks_ref[item] - first), body, 0)

    def start(copies):
        for copy in copies:
            copy.start()

    def wait(copies):
        for copy in copies:
            copy.wait()

    # A slot's rows that no DMA has filled yet must hold numbers: their
    # columns are masked, and 0 x stale is 0 only if stale is finite.
    for buf in bufs:
        buf[...] = jnp.zeros_like(buf)

    @pl.when(num_items > 0)
    def _first():
        for_blocks(0, 0, 0, start)

    def attend(item, c, slot, last, queries: int, group: bool = False):
        """Chunk ``c`` of an item of ``queries`` (static) query slots,
        ``hq_pad`` head rows each; ``group``: a group item, whose queries
        are its tail items'. Rows past the item's count, and the
        padded heads, compute and are not kept: what the last chunk
        stores of them is overwritten by the items that own those
        entries, which come later (a group item and its tails lie where
        its last member does, and own single entries at or before it)."""
        rows = queries * hq_pad
        q0 = q0_ref[item]

        @pl.when(c == 0)
        def _init():
            def fresh():
                m_ref[:rows] = jnp.full((rows, 1), NEG_INF, jnp.float32)
                l_ref[:rows] = jnp.zeros((rows, 1), jnp.float32)
                acc_ref[:rows] = jnp.zeros((rows, acc_ref.shape[-1]),
                                           jnp.float32)

            if queries > 1 or group_tile < 2:
                fresh()
            else:
                member = slot_ref[item]
                pl.when(member < 0)(fresh)

                @pl.when(member >= 0)
                def _take_up():
                    # a tail item: its member's rows of the group item
                    its = pl.ds(pl.multiple_of(member * hq_pad, hq_pad),
                                hq_pad)
                    m_ref[:rows] = m_ref[its]
                    l_ref[:rows] = l_ref[its]
                    acc_ref[:rows] = acc_ref[its]

            if group:
                # the members' queries: the tail items' entries
                for s in range(queries):
                    qgrp_ref[s * hq_pad:(s + 1) * hq_pad] = q_ref[
                        q0_ref[item + 1 + s]]
            elif queries > 1:
                # each row's own position, kept for the item's chunks
                which = jax.lax.broadcasted_iota(
                    jnp.int32, (rows, 1), 0) // hq_pad
                qpos_ref[...] = jax.lax.fori_loop(
                    0, queries,
                    lambda i, acc: jnp.where(which == i, pos_ref[q0 + i],
                                             acc),
                    jnp.zeros((rows, 1), jnp.int32))

        if group:
            # every member is past the shared blocks: the one limit is
            # theirs, the last position they hold
            q = qgrp_ref[...]
            q_pos = blocks_ref[item] * block_size - 1
        elif queries == 1:
            q = q_ref[q0]                                  # (hq_pad, D)
            q_pos = pos_ref[q0]
        else:
            q = q_ref[pl.ds(q0, queries)].reshape(rows, q_ref.shape[-1])
            q_pos = qpos_ref[...]
        # a column is (position, kv head), heads innermost; a row's kv
        # head is its head's group
        col = jax.lax.broadcasted_iota(jnp.int32, (1, n_cols), 1)
        head = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) % hq_pad
        own = (col % hkv) == (head // rep)          # (rows, n_cols)
        col_pos = col // hkv + (first_ref[item] + c * chunk) * block_size
        seen = col_pos <= q_pos
        if window:
            # a window layer: a query reads its trailing ``window``
            # positions alone (the plan starts the item at the first block
            # any of its queries reads)
            seen = jnp.logical_and(seen, col_pos > q_pos - window)
        k, *v = (buf.at[slot].reshape(n_cols, buf.shape[-1])[...]
                 for buf in bufs)
        # a latent row's leading columns are its value: the window of the
        # same VMEM rows, as wide as the accumulator
        v = v[0] if v else k[:, :acc_ref.shape[-1]]
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision) * scale                   # (rows, n_cols)
        s = jnp.where(jnp.logical_and(own, seen), s, NEG_INF)
        m_prev = m_ref[:rows]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(s > _MASKED, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l = corr * l_ref[:rows] + jnp.sum(p, axis=-1, keepdims=True)
        acc = corr * acc_ref[:rows] + jax.lax.dot_general(
            p if exact else p.astype(v.dtype), v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        m_ref[:rows] = m_new
        l_ref[:rows] = l
        acc_ref[:rows] = acc
        if group:
            return

        @pl.when(last)
        def _store():
            res = (acc / jnp.where(l > 0.0, l, 1.0)).astype(out_ref.dtype)
            if queries == 1:
                out_ref[q0] = res
            else:
                out_ref[pl.ds(q0, queries)] = res.reshape(
                    queries, hq_pad, res.shape[-1])

    def step(carry):
        g, item, c = carry
        slot = g % 2
        last = first_ref[item] + (c + 1) * chunk >= blocks_ref[item]
        nxt_item = jnp.where(last, item + 1, item)
        nxt_c = jnp.where(last, 0, c + 1)

        @pl.when(nxt_item < num_items)
        def _prefetch():
            for_blocks(nxt_item, nxt_c, 1 - slot, start)

        for_blocks(item, c, slot, wait)
        if q_tile == 1 and group_tile < 2:
            attend(item, c, slot, last, 1)
        else:
            single = count_ref[item] == 1
            pl.when(single)(lambda: attend(item, c, slot, last, 1))
            many = jnp.logical_not(single)
            if group_tile > 1:
                grouped = slot_ref[item] == _GROUP
                pl.when(grouped)(lambda: attend(item, c, slot, last,
                                                group_tile, group=True))
                many = jnp.logical_and(many, jnp.logical_not(grouped))
            if q_tile > 1:
                pl.when(many)(lambda: attend(item, c, slot, last, q_tile))
        return g + 1, nxt_item, nxt_c

    jax.lax.while_loop(lambda carry: carry[1] < num_items, step,
                       (jnp.int32(0), jnp.int32(0), jnp.int32(0)))


def paged_attention_rows(
    q: jax.Array,              # (T, Hq, D) — one query per entry
    k_leaf: jax.Array,         # (L, NB, BS, Hkv, D) — the pool leaf as
    v_leaf: jax.Array,         #   stored, every layer
    layer: jax.Array,          # () int32 — this block's index into them
    tables: jax.Array,         # (R, MB) int32 — physical block per
                               # (row, logical block)
    positions: jax.Array,      # (T,) int32 — each query's own position
    plan: RowPlan,             # plan_rows(seq_row, positions, ...)
    *,
    scale: Optional[float] = None,   # None: 1 / sqrt(D)
    kv_heads: Optional[int] = None,  # None: the leaves' own head axis
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Attention of a flat paged batch over its rows' blocks, read in
    place (module docstring). Query t sees positions ``<= positions[t]``
    of its row, and under a window plan (``plan.window``) only its
    trailing ``window`` of them. Returns ``(T, Hq, D)``.

    ``kv_heads``: the leaves are stored FOLDED,
    ``(L, NB, BS * f, Hkv / f, D)`` for the same bytes in the same order
    as ``(L, NB, BS, Hkv, D)`` (``rollout.paged_kv.stored_kv_heads``: a
    head axis of 10 would be padded to 16 sublanes in HBM, and Mosaic
    cannot cut a block out of that), and ``kv_heads`` is the true
    ``Hkv``. The kernel reads a block as ``BS * Hkv`` rows either way."""
    d = q.shape[-1]
    return _attend_rows(q, (k_leaf, v_leaf), layer, tables, positions, plan,
                        scale=scale or 1.0 / (d ** 0.5), value_dim=d,
                        name="paged_attention_rows", interpret=interpret,
                        kv_heads=kv_heads)


def paged_latent_attention_rows(
    q: jax.Array,              # (T, Hq, W) — the absorbed query, its tail
                               # zero where the row's is
    leaf: jax.Array,           # (L, NB, BS, 1, W) — the latent pool's one
                               # payload leaf as stored, every layer
    layer: jax.Array,          # () int32
    tables: jax.Array,         # (R, MB) int32
    positions: jax.Array,      # (T,) int32
    plan: RowPlan,             # plan_rows(seq_row, positions, ...)
    *,
    scale: float,              # the model's: 1 / sqrt(its q/k head width)
    value_dim: int,            # leading columns of a row that are its value
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``paged_attention_rows`` over a latent pool (module docstring):
    the one leaf serves both products, a block streamed once. Returns the
    weighted sums of the rows' first ``value_dim`` columns,
    ``(T, Hq, value_dim)``."""
    return _attend_rows(q, (leaf,), layer, tables, positions, plan,
                        scale=scale, value_dim=value_dim,
                        name="paged_latent_attention_rows",
                        interpret=interpret)


def _attend_rows(q, leaves, layer, tables, positions, plan, *, scale,
                 value_dim, name, interpret, kv_heads=None):
    """The one program behind ``paged_attention_rows`` (``leaves`` k and
    v) and ``paged_latent_attention_rows`` (one leaf, the value a row's
    leading ``value_dim`` columns)."""
    t, hq, d = q.shape
    _, _, bs, hkv, _ = leaves[0].shape
    if kv_heads:
        bs, hkv = bs * hkv // kv_heads, kv_heads
    rep = hq // hkv
    exact = q.dtype == jnp.float32
    hq_pad = _head_rows(hq)
    q_tile, group = plan.q_tile, plan.group_tile
    chunk = min(blocks_per_chunk(bs, hkv), tables.shape[1])
    if interpret is None:
        interpret = not on_tpu()
    # q_tile rows of slack: a tile that starts near the end reads and
    # writes past T
    qp = jnp.pad(q, ((0, q_tile), (0, hq_pad - hq), (0, 0)))
    pos = jnp.pad(positions.astype(jnp.int32), (0, q_tile))
    rows = max(q_tile, group) * hq_pad
    # the accumulator's columns: the value's, in whole 128-lane tiles so
    # the window of a latent row is cut on a tile's edge
    acc_dim = min(-(-value_dim // 128) * 128, d)
    kernel = functools.partial(
        _rows_kernel, scale=scale, block_size=bs, hkv=hkv,
        rep=rep, hq_pad=hq_pad, q_tile=q_tile, group_tile=group,
        chunk=chunk, exact=exact, leaves=len(leaves), window=plan.window)
    if leaves[0].shape[3] == 1:
        # a lone kv head is no axis of a block: Mosaic cannot cut a
        # packed (block_size, 1, D) window out of the pool
        leaves = tuple(leaf.reshape(leaf.shape[:3] + (d,))
                       for leaf in leaves)
    block = leaves[0].shape[2:]
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    live_bytes = (len(leaves) * t * tables.shape[1] * bs * hkv * d
                  * leaves[0].dtype.itemsize)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=10,
            grid=(1,),
            in_specs=[vmem] + [hbm] * len(leaves),
            out_specs=vmem,
            scratch_shapes=[
                *(pltpu.VMEM((2, chunk) + block, leaf.dtype)
                  for leaf in leaves),
                pltpu.SemaphoreType.DMA((len(leaves), 2)),
                pltpu.VMEM((rows, acc_dim), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((q_tile * hq_pad, 1), jnp.int32),
                pltpu.VMEM((max(group, 1) * hq_pad, d), q.dtype),
            ]),
        out_shape=jax.ShapeDtypeStruct(qp.shape[:2] + (acc_dim,), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # q and the output are whole in VMEM, beside 16 MiB for the
            # rest: past the default limit for a long flat batch
            vmem_limit_bytes=min(2 * qp.size * qp.dtype.itemsize
                                 + (16 << 20), 100 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=4 * t * hq * tables.shape[1] * bs * d // 2,
            bytes_accessed=live_bytes // 2,
            transcendentals=t * hq * tables.shape[1] * bs // 2),
        name=name,
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      jnp.asarray(tables, jnp.int32), plan.row, plan.q0, plan.count,
      plan.first, plan.blocks, plan.slot, plan.num_items, pos, qp, *leaves)
    return out[:t, :hq, :value_dim]


def _pfd_kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref, ks_ref,
                vs_ref, out_ref, acc_ref, m_ref, l_ref, *,
                scale: float, block_size: int, hkv: int, rep_pad: int):
    """One (token, logical block) program over a QUANTIZED pool. The K/V
    refs already hold the PHYSICAL block — the index maps resolved
    ``tables_ref`` before the DMA — so the body only needs the logical
    position ``bi * block_size`` for masking. KV heads loop inside
    (Mosaic tiling: the head axis must stay whole in the block specs for
    Hkv < 8). The per-block scale tiles rescale the payload right after
    its f32 upcast — dequant fused into the block loop."""
    ti = pl.program_id(0)
    bi = pl.program_id(1)
    n_blk = pl.num_programs(1)

    @pl.when(bi == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    length = lengths_ref[ti]
    k_start = bi * block_size

    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale   # (hkv*rep_pad, D)
        s_heads = []
        for h in range(hkv):
            qh = q[h * rep_pad:(h + 1) * rep_pad]            # (rep_pad, D)
            kh = k_ref[0, :, h, :].astype(jnp.float32)       # (BS, D)
            kh = kh * ks_ref[0, :, h][:, None]
            s_heads.append(jax.lax.dot_general(
                qh, kh, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))         # (rep_pad, BS)
        s = jnp.concatenate(s_heads, axis=0)       # (hkv*rep_pad, BS)
        rows = s.shape[0]
        pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                 (rows, block_size), 1)
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(s > _MASKED, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:] = corr * l_ref[:] + jnp.sum(p, axis=-1, keepdims=True)
        pv_heads = []
        for h in range(hkv):
            ph = p[h * rep_pad:(h + 1) * rep_pad]
            vh = v_ref[0, :, h, :].astype(jnp.float32)       # (BS, D)
            vh = vh * vs_ref[0, :, h][:, None]
            pv_heads.append(jax.lax.dot_general(
                ph, vh, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))         # (rep_pad, D)
        acc_ref[:] = corr * acc_ref[:] + jnp.concatenate(pv_heads, axis=0)
        m_ref[:] = m_new

    # Logical blocks wholly past this token's fill level are dead table
    # padding — skip the matmuls entirely.
    pl.when(k_start < length)(_compute)

    @pl.when(bi == n_blk - 1)
    def _finalize():
        l = l_ref[:]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        out_ref[0] = (acc_ref[:] / safe_l).astype(out_ref.dtype)


def paged_flash_decode(
    q: jax.Array,              # (T, Hq, D) — one query per token entry
    k_pool: jax.Array,         # (NB, BS, Hkv, D) — one layer's block pool
    v_pool: jax.Array,         # (NB, BS, Hkv, D)
    tables: jax.Array,         # (T, MB) int32 — physical block per
                               # (token, logical block); dead entries
                               # may hold any in-range id
    lengths: jax.Array,        # (T,) int32 — valid positions incl. new
    *,
    k_scale: Optional[jax.Array] = None,   # (NB, BS, Hkv) f32 absmax
    v_scale: Optional[jax.Array] = None,   # scales for int8/fp8 pools
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Block-table cache attention with one query a table row. Returns
    (T, Hq, D). The KV block size IS the kernel block size — the pool
    was allocated block-aligned, so there is never a pad-copy path here
    (an ``Smax % block_kv`` remainder cannot arise by
    construction). Unquantized, it is ``paged_attention_rows`` with
    every entry a row of its own; passing ``k_scale``/``v_scale``
    selects the dequant-fused kernel for quantized pools. Note Mosaic's
    int8 min-tile is (32, 128) on the last two dims; sub-tile
    block_size/D configs rely on relayout padding (and the interpret
    path, used by the CPU test fleet, has no tiling constraint at
    all)."""
    t, hq, d = q.shape
    nb, bs, hkv, _ = k_pool.shape
    mb = tables.shape[1]
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (t,))
    tables = jnp.asarray(tables, jnp.int32)
    if k_scale is None:
        positions = lengths - 1
        plan = plan_rows(jnp.arange(t, dtype=jnp.int32), positions,
                         block_size=bs, table_width=mb, q_tile=1)
        return paged_attention_rows(
            q, k_pool[None], v_pool[None], jnp.zeros((), jnp.int32),
            tables, positions, plan, interpret=interpret)
    if v_scale is None:
        raise ValueError("k_scale passed without v_scale")
    rep = hq // hkv
    rep_pad = max(8, -(-rep // 8) * 8)
    if interpret is None:
        interpret = not on_tpu()

    # (T, Hq, D) → (T, Hkv*rep_pad, D): flattened (kv-head, group) pairs
    # on the sublane axis.
    qg = q.reshape(t, hkv, rep, d)
    if rep_pad != rep:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rep_pad - rep), (0, 0)))
    qg = qg.reshape(t, hkv * rep_pad, d)

    kernel = functools.partial(_pfd_kernel, scale=1.0 / (d ** 0.5),
                               block_size=bs, hkv=hkv, rep_pad=rep_pad)
    rows = hkv * rep_pad
    # The paged trick: the physical block id comes from the scalar-
    # prefetched table at DMA-issue time. Full head axis per block
    # (Mosaic last-two-dims tiling rule). The scale tiles ride the same
    # indirection.
    pool_spec = pl.BlockSpec(
        (1, bs, hkv, d), lambda ti, bi, tbl, lens: (tbl[ti, bi], 0, 0, 0))
    scale_spec = pl.BlockSpec(
        (1, bs, hkv), lambda ti, bi, tbl, lens: (tbl[ti, bi], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # tables, lengths
        grid=(t, mb),
        in_specs=[
            pl.BlockSpec((1, rows, d),
                         lambda ti, bi, tbl, lens: (ti, 0, 0)),
            pool_spec, pool_spec, scale_spec, scale_spec],
        out_specs=pl.BlockSpec((1, rows, d),
                               lambda ti, bi, tbl, lens: (ti, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, d), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
        ],
    )
    kv_bytes = d * k_pool.dtype.itemsize + 4
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, rows, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=4 * t * hq * mb * bs * d,
            bytes_accessed=2 * t * mb * bs * hkv * kv_bytes,
            transcendentals=t * hq * mb * bs),
        interpret=interpret,
    )(tables, lengths, qg, k_pool, v_pool,
      jnp.asarray(k_scale, jnp.float32), jnp.asarray(v_scale, jnp.float32))

    return out.reshape(t, hkv, rep_pad, d)[:, :, :rep, :].reshape(t, hq, d)
