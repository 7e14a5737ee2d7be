"""Paged KV cache: block pool, free-list allocator, copy-on-write tables.

The slot engine gives every request a contiguous ``(max_len, Hkv, Dh)``
stripe per layer, so a 40-token chat turn strands the same HBM as a
2048-token rollout and a shared prefix is materialized by copying its
buffer into each consumer's stripe. This module is the vLLM-style
alternative (PagedAttention economics, see PAPERS.md): KV lives in a
fixed device pool of fixed-size **blocks**

    pool.k / pool.v : (L, num_blocks, block_size, Hkv, Dh)

and each request owns a host-side **block table** — a list of physical
block ids, one per ``block_size`` span of its sequence. Attention reads
through the ``(request, logical_block) -> physical_block`` indirection
(``models.transformer.forward_paged``); capacity is governed by the
:class:`BlockAllocator`:

* **free-list allocation** — O(1) alloc/release of whole blocks; any
  free block serves any request, so there is no external fragmentation
  (the only waste is the partially-filled last block per sequence,
  tracked by the ``senweaver_kv_fragmentation`` gauge).
* **refcounted sharing** — a shared prefix is installed into a request
  by *grafting*: ``fork`` bumps the refcount of every prefix block and
  returns a new table that aliases them. Zero bytes move.
* **copy-on-write** — the first write into a shared block
  (``cow_target`` returns a fresh destination when refcount > 1)
  triggers exactly one block copy (:func:`copy_blocks`); full prefix
  blocks are never copied, only the partial boundary block a consumer
  diverges into.
* **typed backpressure** — :class:`BlocksExhausted` when the pool runs
  dry, so the engine can preempt-by-recomputation and the admission
  plane can shed instead of OOMing the device.

The allocator is pure host bookkeeping (ints in lists — no device sync
anywhere) guarded by its own reentrant lock, so the engine lock and the
allocator lock nest in a fixed order (engine → allocator). Device data
only moves through the jitted helpers at the bottom
(:func:`copy_blocks`, :func:`install_blocks`, :func:`gather_blocks` and
their quantization-preserving twins), each a single scatter/gather on
the pool.

**Quantized KV ladder** (``EngineConfig.kv_dtype``): the pool can store
int8/fp8 payloads plus per-(block, position, head) f32 absmax scales —
roughly 2×/2× the effective block capacity per HBM byte. Quantization
happens at write time inside the engine's one fused step and at install
time here; every consumer that needs full-width KV (prefix export,
slot-layout interop) goes through :func:`gather_blocks`, which
dequantizes, while the host tier / migration / quantized export path
uses :func:`gather_blocks_quant`/:func:`install_blocks_quant` to ship
the raw bytes + scales.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import threading
from typing import Any, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from ..models.config import refuse
from ..models.transformer import (ModelConfig, dequantize_pool_kv,
                                  quantize_pool_kv)
from ..obs.runtime_profile import ProfiledFunction

# The serving-wide KV precision ladder (EngineConfig.kv_dtype). "bf16"
# means "full width": the pool stores the model dtype (bf16 on TPU,
# f32 in the CPU test configs). int8/fp8 store quantized payloads plus
# per-(token, head) f32 absmax scales.
KV_DTYPES = ("bf16", "int8", "fp8")

_FP8_DTYPE = jnp.float8_e4m3fn


def kv_payload_dtype(name: str):
    """Payload dtype for one quantized rung of the ladder."""
    if name == "int8":
        return jnp.int8
    if name == "fp8":
        return _FP8_DTYPE
    raise ValueError(f"unknown quantized kv_dtype {name!r}; "
                     f"expected one of {KV_DTYPES}")


def resolve_kv_dtypes(num_layers: int, kv_dtype: str,
                      kv_dtype_per_layer=None):
    """Validate the precision ladder → ``(payload_dtype | None, hi_layers)``.

    ``payload_dtype`` is None for a full-width pool. A per-layer
    override must be a contiguous "bf16" PREFIX (the ``hi_layers``
    full-width layers, where quantization divergence concentrates)
    followed by one uniform quantized dtype — arbitrary interleavings
    would need per-layer pool pytrees and buy nothing the prefix split
    doesn't."""
    if kv_dtype not in KV_DTYPES:
        raise ValueError(f"kv_dtype must be one of {KV_DTYPES}, "
                         f"got {kv_dtype!r}")
    if kv_dtype_per_layer is None:
        if kv_dtype == "bf16":
            return None, 0
        return kv_payload_dtype(kv_dtype), 0
    per = tuple(kv_dtype_per_layer)
    if len(per) != num_layers:
        raise ValueError(
            f"kv_dtype_per_layer has {len(per)} entries for "
            f"{num_layers} layers")
    for name in per:
        if name not in KV_DTYPES:
            raise ValueError(f"kv_dtype_per_layer entry {name!r} not "
                             f"in {KV_DTYPES}")
    n_hi = 0
    while n_hi < num_layers and per[n_hi] == "bf16":
        n_hi += 1
    tail = set(per[n_hi:])
    if not tail:
        return None, 0          # all-bf16 override → plain pool
    if len(tail) != 1:
        raise ValueError(
            "kv_dtype_per_layer must be a contiguous 'bf16' prefix "
            f"followed by one uniform quantized dtype, got {per}")
    (qname,) = tail
    if kv_dtype != "bf16" and qname != kv_dtype:
        raise ValueError(
            f"kv_dtype_per_layer tail {qname!r} contradicts "
            f"kv_dtype={kv_dtype!r}")
    return kv_payload_dtype(qname), n_hi


class BlocksExhausted(RuntimeError):
    """The block pool cannot satisfy an allocation. Typed so the engine
    can preempt/requeue and the admission plane can shed on it, the way
    ``QueueFull`` sheds queue pressure."""

    def __init__(self, requested: int, free: int, num_blocks: int):
        super().__init__(
            f"KV block pool exhausted: requested {requested} block(s), "
            f"{free} free of {num_blocks}")
        self.requested = requested
        self.free = free
        self.num_blocks = num_blocks


class StateRows(NamedTuple):
    """Row-addressed recurrent state, the pool's second kind of state
    beside the block-addressed KV: one fixed-size array a row a layer,
    overwritten every token, so it is never shared by refcount
    (``BlockAllocator.fork``) and never split on write (``cow_target``) —
    only copied, whole, row to row (:func:`copy_state_rows`). Rows
    ``0..num_slots-1`` belong to the engine's rows (state row r goes with
    table row r); the rows behind them hold snapshots (a group's state at
    its fork). A descriptor of its own, not a case of the block leaves:
    the block movers (``copy_blocks``, ``install_blocks*``,
    ``gather_blocks*``) never touch it, and a later kind of per-row or
    per-window state can sit beside it the same way.

    ``ssm`` ``(L, rows, H, P, N)`` float32: a state-space mixer's state
    (Mamba-1's: ``(L, rows, N, I)``, the channels on the lanes; a
    delta-rule layer's: a matrix a head, ``(L, rows, H, K, V)``,
    ``ops/delta_rule.py``); ``conv`` ``(L, rows, K-1, C)``: the last K-1
    inputs of its conv, oldest first (``ops/ssm.py``). L counts the layers
    that hold such state: all of them, or a layer pattern's layers of that
    kind (:func:`_state_leaves`). A pattern without "window" layers is
    served by this class as it is; one with them by :class:`RingRows`."""

    ssm: jnp.ndarray
    conv: jnp.ndarray

    @property
    def num_rows(self) -> int:
        return self.ssm.shape[1]

    @property
    def nbytes(self) -> int:
        """Device bytes of all rows, all layers: held whole, whatever the
        rows do."""
        return sum(int(a.size) * jnp.dtype(a.dtype).itemsize for a in self)


class RingRows(NamedTuple):
    """:class:`StateRows` of a layer pattern (``ModelConfig.layer_types``)
    with "window" layers: the mixers' ``ssm`` (Mamba-1's:
    ``(Lm, rows, N, I)``, the channels on the lanes) and ``conv``, and
    beside them, addressed by the same rows and copied by the same
    :func:`copy_state_rows`, the window layers' rings.

    ``win_k`` / ``win_v`` ``(Lw, rows, capacity x f, Hkv / f, D)``: one
    ring a row a layer: position p's k and v lie at ring slot
    ``p % capacity``, ``capacity`` = the window plus a step's entries
    rounded up to whole blocks, so that a step's writes never reach a
    position one of its queries still reads (:func:`window_capacity`). A
    ring is a row's like the state: copied whole at a fork, never shared.
    The attention kernels read it as ``capacity / block_size`` blocks a row
    through a table that is a constant of the shapes
    (``models.transformer.ring_tables``); the head axis is stored folded as
    the block leaves' is (:func:`stored_kv_heads`)."""

    ssm: jnp.ndarray
    conv: jnp.ndarray
    win_k: jnp.ndarray
    win_v: jnp.ndarray

    num_rows = StateRows.num_rows
    nbytes = StateRows.nbytes


class PagedKVPool(NamedTuple):
    """The device-side block pool. ``k``/``v`` are
    ``(L, num_blocks, block_size, Hkv, Dh)``; block 0..num_blocks-1 are
    real, and writers address "drop this write" as block id
    ``num_blocks`` (out of range → ``mode="drop"`` scatter no-op).

    A QUANTIZED pool (``kv_dtype`` int8/fp8) stores the payload in
    ``k``/``v`` at reduced width plus per-(block, position, head) f32
    absmax scales in ``k_scale``/``v_scale``
    ``(Lq, num_blocks, block_size, Hkv)``. With a
    ``kv_dtype_per_layer`` override the first ``hi_layers`` layers
    live full-width in ``k_hi``/``v_hi`` and the payload tensors hold
    only the quantized tail (``Lq = L - hi_layers``). All shape- and
    None-derived properties are static under jit.

    ``rows`` holds what is addressed by row and not by block
    (:class:`StateRows`, or :class:`RingRows` where window layers' rings
    sit beside the state); None for a model whose only state is KV."""

    k: jnp.ndarray
    v: jnp.ndarray
    k_scale: Optional[jnp.ndarray] = None
    v_scale: Optional[jnp.ndarray] = None
    k_hi: Optional[jnp.ndarray] = None
    v_hi: Optional[jnp.ndarray] = None
    rows: Optional[Any] = None

    @property
    def num_blocks(self) -> int:
        return self.k.shape[1]

    @property
    def block_size(self) -> int:
        return self.k.shape[2]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def hi_layers(self) -> int:
        return 0 if self.k_hi is None else self.k_hi.shape[0]

    @property
    def num_layers(self) -> int:
        return self.hi_layers + self.k.shape[0]

    @property
    def full_dtype(self):
        """The full-width dtype this pool dequantizes to."""
        if self.k_hi is not None:
            return self.k_hi.dtype
        return self.k.dtype if self.k_scale is None else jnp.bfloat16


class BlockPayload(NamedTuple):
    """Pool-native payload of a set of blocks — the quantization-
    preserving unit of KV movement (host-RAM tier, migration
    checkpoints, quantized prefix export). Fields mirror
    :class:`PagedKVPool` with the pool axis replaced by the gathered
    block axis ``(·, n, block_size, ...)``; arrays may be device or
    numpy (both sides of a swap)."""

    k: Any
    v: Any
    k_scale: Any = None
    v_scale: Any = None
    k_hi: Any = None
    v_hi: Any = None


def stored_kv_heads(kv_heads: int) -> int:
    """The head axis a payload leaf is STORED with. XLA:TPU tiles a
    leaf's last two axes, and a head axis that is neither a multiple of 8
    nor 1, 2 or 4 is padded to the next 8 in HBM (10 heads: 16 sublanes,
    1.6 x the bytes, and Mosaic cannot cut a block out of the padded
    axis). Such a leaf folds ``f = kv_heads / stored`` heads into the
    position axis: ``(L, NB, BS x f, stored, D)``, the same bytes in the
    same order as ``(L, NB, BS, kv_heads, D)``, tiled compactly
    (``ops.paged_attention.paged_attention_rows``'s ``kv_heads``). Every
    head count a preset had before the first folded one (1, 2, 4, 8, 16,
    32) is stored as it is."""
    if kv_heads % 8 == 0:
        return kv_heads
    return next(h for h in (4, 2, 1) if kv_heads % h == 0)


def window_capacity(config: ModelConfig, block_size: int,
                    step_tokens: int) -> int:
    """Positions a "window" layer's ring holds a row: ``layer_window`` plus
    the most entries one row can have in a step, in whole blocks. A step
    writes its entries and then attends: entry i of a chunk at positions
    ``p .. p + n - 1`` overwrites position ``p + i - capacity``, and the
    chunk's first query still reads from ``p - window + 1``: safe while
    ``capacity >= window + n - 1``. It does not grow with ``max_len``."""
    return -(-(config.layer_window + step_tokens) // block_size) * block_size


@dataclasses.dataclass(frozen=True)
class CacheKind:
    """What one kind of layer holds in the pool (:func:`cache_kinds`)."""
    kind: str            # "kv" | "window" | "ssm" | "conv"
    addressed: str       # "block": by the allocator's tables; "row"
    layers: int
    unit_bytes: int      # bytes a layer of a token (block-addressed, and
                         # the rings) or of a row (state)
    units: int           # tokens a row holds in the rings; else 1

    def nbytes(self, num_blocks: int, block_size: int, rows: int) -> int:
        """Device bytes of this kind in a pool of that geometry."""
        n = (num_blocks * block_size if self.addressed == "block"
             else rows * self.units)
        return self.layers * n * self.unit_bytes


def cache_kinds(config: ModelConfig, block_size: int = 16,
                step_tokens: int = 0, kv_dtype: str = "bf16",
                kv_dtype_per_layer=None) -> List[CacheKind]:
    """A descriptor a kind of cache ``config``'s layers hold, in the order
    of the pool's leaves: the block-addressed KV of its attention layers
    (a layer pattern: its "full" layers alone; bytes without a quantized
    rung's scales), then what is row-addressed — the rings of its
    "window" layers, the state and the conv window of its mixers. A layer
    that appears in none holds nothing ("gmu", "cross"). The pool's
    constructors, :func:`kv_row_bytes` and the engine's gauges read
    these."""
    c = config
    item = jnp.dtype(c.dtype).itemsize
    payload, n_hi = resolve_kv_dtypes(c.attn_layers, kv_dtype,
                                      kv_dtype_per_layer)
    leaf = (c.latent_row_dim if c.mla
            else c.cache_kv_heads * c.cache_head_dim)
    kv_item = jnp.dtype(payload or c.dtype).itemsize
    out = [CacheKind("kv", "block", c.attn_layers,
                     leaf * kv_item * (1 if c.mla else 2), 1)]
    if c.pattern and c.kind_layers("window"):
        out.append(CacheKind(
            "window", "row", c.kind_layers("window"), 2 * leaf * item,
            window_capacity(c, block_size, step_tokens)))
    out += [CacheKind(kind, "row", shape[0], math.prod(shape[2:])
                      * jnp.dtype(dtype).itemsize, 1)
            for kind, (shape, dtype) in _state_leaves(c, 1).items()]
    return out


def _state_leaves(config: ModelConfig, num_rows: int) -> dict:
    """``{"ssm": (shape, dtype), "conv": (shape, dtype)}`` of ``config``'s
    row-addressed mixer state, ``(layers of the kind, rows, ...)``: the
    state in float32 (it is a sum over thousands of steps), the conv's
    window in the serving dtype (it holds projection outputs as they
    are). Mamba-2 in every block ``(H, P, N)``; a pattern's Mamba-1 layers
    ``(N, I)``, the channels on the lanes; its delta-rule layers a matrix a
    head ``(H, K, V)`` and the window of [q | k | v]. Empty where the
    configuration has no such mixer."""
    c = config
    if not c.ssm:
        return {}
    if c.kind_layers("kda"):
        mixers = c.kind_layers("kda")
        state = (c.kda_num_heads, c.kda_head_dim, c.kda_head_dim)
        window = (c.kda_conv - 1, 3 * c.kda_dim)
    else:
        mixers = c.kind_layers("mamba") if c.pattern else c.num_layers
        state = ((c.mamba_d_state, c.mamba_d_ssm) if c.mamba_dt_rank
                 else (c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state))
        window = (c.mamba_d_conv - 1, c.ssm_conv_dim)
    return {"ssm": ((mixers, num_rows) + state, jnp.float32),
            "conv": ((mixers, num_rows) + window, c.dtype)}


def init_state_rows(config: ModelConfig, num_rows: int,
                    block_size: int = 16, step_tokens: int = 0):
    """Zeroed row-addressed state for ``config``'s mixers
    (:func:`_state_leaves`): a :class:`StateRows`, or where a layer pattern
    has "window" layers a :class:`RingRows` with their rings beside it,
    sized by ``block_size`` and ``step_tokens`` (:func:`window_capacity`).
    A pattern's leaves lead with the layers of their own kind."""
    c = config
    leaves = {name: jnp.zeros(shape, dtype) for name, (shape, dtype)
              in _state_leaves(c, num_rows).items()}
    if not (c.pattern and c.kind_layers("window")):
        return StateRows(**leaves)
    stored = stored_kv_heads(c.cache_kv_heads)
    ring = (c.kind_layers("window"), num_rows,
            window_capacity(c, block_size, step_tokens)
            * (c.cache_kv_heads // stored), stored, c.cache_head_dim)
    return RingRows(win_k=jnp.zeros(ring, c.dtype),
                    win_v=jnp.zeros(ring, c.dtype), **leaves)


def init_paged_pool(config: ModelConfig, num_blocks: int,
                    block_size: int, kv_dtype: str = "bf16",
                    kv_dtype_per_layer=None,
                    state_rows: int = 0,
                    step_tokens: int = 0) -> PagedKVPool:
    """Zeroed pool sized for ``config``. ``kv_dtype`` selects the
    serving precision ladder rung; ``kv_dtype_per_layer`` optionally
    keeps a bf16 prefix of layers full-width (see
    :func:`resolve_kv_dtypes`). The legacy slot-cache int8 switch
    (``config.kv_quant``) is a different mechanism — the engine still
    falls back to the slot layout there.

    A latent-attention configuration (``config.mla``) caches ONE vector a
    token a layer, ``[c_kv | k_rope]``: its payload is the single leaf
    ``k`` ``(L, num_blocks, block_size, 1, latent_row_dim)`` (the latent
    padded to whole 128-lane tiles, ``ModelConfig.latent_row_dim``) — no
    kv-head axis to speak of and no separate values — and ``v`` keeps the
    block axes
    with a last axis of width 0, so that every mover below stays one
    ``tree_map`` (or one indexed update a leaf) over the same leaves and
    moves no byte for it. The quantized ladder has no latent form yet.

    A configuration with recurrent state (``config.ssm``) gets
    ``state_rows`` rows of it beside the blocks (``PagedKVPool.rows``,
    :func:`init_state_rows`); every other configuration's pool is what it
    was.

    A layer pattern (``config.layer_types``) holds block-addressed KV for
    its "full" layers alone, the head axis stored folded
    (:func:`stored_kv_heads`), and beside its mixers' state the rings of
    its "window" layers, sized by ``step_tokens``
    (:func:`window_capacity`); the quantized ladder has no form for it
    yet."""
    if config.pattern and (kv_dtype != "bf16"
                           or kv_dtype_per_layer is not None):
        refuse(config, "init_paged_pool(kv_dtype=)")
    if config.ssm and state_rows <= 0:
        raise ValueError(
            f"{config.name}: a pool for a model with recurrent state "
            f"needs state_rows > 0")
    pool = _init_block_pool(config, num_blocks, block_size, kv_dtype,
                            kv_dtype_per_layer)
    if not config.ssm:
        return pool
    return pool._replace(rows=init_state_rows(config, state_rows, block_size,
                                              step_tokens))


def _init_block_pool(config: ModelConfig, num_blocks: int, block_size: int,
                     kv_dtype: str, kv_dtype_per_layer) -> PagedKVPool:
    """``init_paged_pool``'s block-addressed leaves."""
    hkv, dh = config.cache_kv_heads, config.cache_head_dim
    if config.pattern:
        # the one forward that writes and reads a folded head axis
        fold = hkv // stored_kv_heads(hkv)
        block_size, hkv = block_size * fold, hkv // fold
    # attention layers: two a layer in a shortcut block
    num_layers = config.attn_layers
    payload, n_hi = resolve_kv_dtypes(num_layers, kv_dtype,
                                      kv_dtype_per_layer)
    if config.mla:
        if payload is not None:
            refuse(config, "init_paged_pool(kv_dtype=)")
        shape = (num_layers, num_blocks, block_size, 1)
        return PagedKVPool(
            k=jnp.zeros(shape + (config.latent_row_dim,), dtype=config.dtype),
            v=jnp.zeros(shape + (0,), dtype=config.dtype))
    if payload is None:
        shape = (num_layers, num_blocks, block_size, hkv, dh)
        return PagedKVPool(k=jnp.zeros(shape, dtype=config.dtype),
                           v=jnp.zeros(shape, dtype=config.dtype))
    lq = num_layers - n_hi
    qshape = (lq, num_blocks, block_size, hkv, dh)
    sshape = qshape[:-1]
    hi_shape = (n_hi, num_blocks, block_size, hkv, dh)
    return PagedKVPool(
        k=jnp.zeros(qshape, dtype=payload),
        v=jnp.zeros(qshape, dtype=payload),
        k_scale=jnp.zeros(sshape, jnp.float32),
        v_scale=jnp.zeros(sshape, jnp.float32),
        k_hi=jnp.zeros(hi_shape, config.dtype) if n_hi else None,
        v_hi=jnp.zeros(hi_shape, config.dtype) if n_hi else None)


# What one DMA of the attention kernels should carry, in bytes: a block of
# ONE payload leaf in ONE layer, the unit ``ops/paged_attention.py``'s
# ``_rows_kernel`` copies. The kernel starts and waits for each copy, and a
# copy costs it about as much whatever it carries; the rest of its time
# follows the tokens it reads. The kernel alone on a v5e, 48 decode rows,
# ms a layer by tokens a block (my chip runs, PR 33; PERF.md §6):
#
#   tokens a block              16      32      64      128     256
#   12/2 x 128, rows of 1024    0.2138  0.1777  0.1601  0.1515  0.1458
#   20/4 x 128, rows of 4096    1.1568  0.9746  0.8822  0.8467  (0.6649)
#   latent row of 640, 4096     0.5924  0.4554  0.3836  0.3680  0.3724
#
# (in brackets: one block fills two score tiles there, another program).
# The smallest target after which no shape gains 5% more: copies of 64
# KiB are 128 / 64 / 64 tokens (-5.4 / -9.5 / -15.8% on the step before,
# -3.8 / -4.0 / -4.1% on the step after).
COPY_TARGET_BYTES = 64 << 10


def kv_row_bytes(config: ModelConfig, kv_dtype: str = "bf16",
                 kv_dtype_per_layer=None) -> int:
    """Bytes one token takes in one payload leaf of one layer, AS STORED:
    ``Hkv x head_dim`` of the pool's dtype (a quantized rung stores one
    byte a value), or the latent pool's one padded row."""
    kv = cache_kinds(config, kv_dtype=kv_dtype,
                     kv_dtype_per_layer=kv_dtype_per_layer)[0]
    return kv.unit_bytes // (1 if config.mla else 2)


def resolve_block_size(row_bytes: int, max_len: int) -> int:
    """Tokens a block holds when nobody said (``EngineConfig.block_size``
    None): the smallest power of two, from 16 to 128, whose copy
    (``block_size x row_bytes``, see :func:`kv_row_bytes`) reaches
    ``COPY_TARGET_BYTES``, halved while a row of ``max_len`` tokens would
    hold fewer than 8 blocks (a short row's last block is most of its
    waste), never under 16. One rule of what the pool stores and how long a
    row is; no model is named."""
    bs = 16
    while bs < 128 and bs * row_bytes < COPY_TARGET_BYTES:
        bs *= 2
    while bs > 16 and max_len // bs < 8:
        bs //= 2
    return bs


def pool_bytes_per_block(pool: PagedKVPool) -> int:
    """Device bytes one block occupies across every pool tensor
    (payload + scales + full-width prefix) — the unit the allocator's
    byte gauges multiply by."""
    total = 0
    for a in pool._replace(rows=None):
        if a is not None:
            total += int(a.size) * jnp.dtype(a.dtype).itemsize
    return total // pool.num_blocks


class BlockAllocator:
    """Host-side free-list + refcount bookkeeping for one
    :class:`PagedKVPool`. All methods are O(blocks touched); none
    touches the device. Thread-safe behind its own reentrant lock (the
    engine calls it under the engine lock; lock order is always
    engine → allocator)."""

    def __init__(self, num_blocks: int, block_size: int, *,
                 registry=None, bytes_per_block: int = 0):
        if num_blocks <= 0 or block_size <= 0:
            raise ValueError("num_blocks and block_size must be positive")
        self.num_blocks = num_blocks
        self.block_size = block_size
        # Device bytes per block (payload + scales + full-width prefix,
        # see pool_bytes_per_block). 0 = unknown; the byte gauges then
        # publish 0 and block counts remain the only capacity signal.
        self.bytes_per_block = int(bytes_per_block)
        self._swapped_blocks = 0
        self._lock = threading.RLock()
        # LIFO free list: recently-freed blocks are re-used first (their
        # pool lines are warmest in HBM/cache).
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))  # guarded-by: _lock
        self._ref: List[int] = [0] * num_blocks  # guarded-by: _lock
        self._counters: Dict[str, int] = {  # guarded-by: _lock
            "allocs": 0, "releases": 0, "grafts": 0, "cow_copies": 0,
            "exhaustions": 0, "install_copies": 0, "evictions": 0,
            "swap_outs": 0, "swap_ins": 0}
        if registry is None:
            from ..obs import get_registry
            registry = get_registry()
        self._blocks_total_gauge = registry.gauge(
            "senweaver_kv_blocks_total",
            "KV block-pool capacity of the most recently updated engine.")
        self._blocks_free_gauge = registry.gauge(
            "senweaver_kv_blocks_free",
            "Free KV blocks in the pool.")
        self._util_gauge = registry.gauge(
            "senweaver_kv_pool_utilization",
            "Fraction of KV blocks currently allocated (0..1).")
        self._frag_gauge = registry.gauge(
            "senweaver_kv_fragmentation",
            "Internal fragmentation: fraction of allocated KV-block "
            "capacity holding no token (partial last blocks).")
        self._cow_total = registry.counter(
            "senweaver_kv_cow_copies_total",
            "Copy-on-write block copies (first divergent write into a "
            "shared block).")
        self._graft_total = registry.counter(
            "senweaver_kv_prefix_grafts_total",
            "Prefix installs served by block-table graft (refcount bump, "
            "zero KV bytes copied).")
        self._install_copy_total = registry.counter(
            "senweaver_kv_install_copies_total",
            "Prefix installs that copied KV buffers into place (slot "
            "layout, or paged cross-engine import scatter).")
        self._exhaustion_total = registry.counter(
            "senweaver_kv_exhaustion_rejections_total",
            "Allocations refused because the block pool was exhausted "
            "(preemptions + admission rejections).")
        self._eviction_total = registry.counter(
            "senweaver_kv_evictions_total",
            "Prefix entries dropped by scored eviction (cold, unshared: "
            "cheapest to recompute).")
        self._swap_out_total = registry.counter(
            "senweaver_kv_swaps_out_total",
            "KV blocks swapped from the device pool to the host-RAM "
            "tier (warm prefixes under pressure).")
        self._swap_in_total = registry.counter(
            "senweaver_kv_swaps_in_total",
            "KV blocks restored from the host-RAM tier into the device "
            "pool (on-demand prefix reuse).")
        self._swapped_gauge = registry.gauge(
            "senweaver_kv_swapped_blocks",
            "KV blocks currently resident only in the host-RAM tier.")
        # Byte-denominated twins of the block gauges: with mixed-dtype
        # pools during a precision-ladder rollout, a block on an int8
        # replica holds ~half the bytes of one on a bf16 replica, so
        # fleet capacity math must happen in bytes. The block-count
        # gauges above stay as compatibility aliases.
        self._bytes_device_gauge = registry.gauge(
            "senweaver_kv_bytes_device",
            "Device bytes held by allocated KV blocks (payload + "
            "scales + full-width prefix layers).")
        self._bytes_host_gauge = registry.gauge(
            "senweaver_kv_bytes_host",
            "Host-RAM bytes held by KV blocks swapped to the host "
            "tier.")
        self._publish_gauges()

    # -- introspection (reads; callers may race, values are advisory) ----
    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return self.num_blocks - len(self._free)

    @property
    def used_bytes(self) -> int:
        """Device bytes held by allocated blocks (0 when the allocator
        was built without a ``bytes_per_block``)."""
        return self.used_blocks * self.bytes_per_block

    @property
    def swapped_bytes(self) -> int:
        """Host-tier bytes held by swapped-out blocks."""
        return self._swapped_blocks * self.bytes_per_block

    def refcount(self, block: int) -> int:
        return self._ref[block]

    def counters(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counters)

    def blocks_for(self, num_tokens: int) -> int:
        """Blocks needed to hold ``num_tokens`` positions."""
        return -(-num_tokens // self.block_size)

    def check_leaks(self) -> None:
        """Assert the pool is fully free (every table released). Used
        by tests as the refcount-leak tripwire. Fork-aware: the message
        separates multiply-referenced (shared fork spine) blocks from
        singly-held ones, so a leaked group fork reads differently from
        a plain unreleased table."""
        with self._lock:
            if len(self._free) != self.num_blocks:
                held = [i for i, r in enumerate(self._ref) if r > 0]
                shared = [(i, r) for i, r in enumerate(self._ref)
                          if r > 1]
                detail = (f"; {len(shared)} shared (block, refs): "
                          f"{shared[:8]}" if shared else "")
                held_bytes = (f" ({len(held) * self.bytes_per_block} "
                              f"device bytes)"
                              if self.bytes_per_block else "")
                raise AssertionError(
                    f"KV block leak: {len(held)} block(s) still "
                    f"referenced{held_bytes}: {held[:16]}{detail}")

    # -- allocation ------------------------------------------------------
    def alloc(self, n: int) -> List[int]:
        """``n`` fresh blocks at refcount 1, or :class:`BlocksExhausted`
        (all-or-nothing: a partial grant would deadlock two requests
        each holding half the pool)."""
        with self._lock:
            if n > len(self._free):
                self._counters["exhaustions"] += 1
                self._exhaustion_total.inc()
                raise BlocksExhausted(n, len(self._free), self.num_blocks)
            blocks = [self._free.pop() for _ in range(n)]
            for b in blocks:
                self._ref[b] = 1
            self._counters["allocs"] += n
            self._publish_gauges()
            return blocks

    def retain(self, blocks: Sequence[int]) -> None:
        """Refcount bump for every block (sharing, not ownership
        transfer)."""
        with self._lock:
            for b in blocks:
                if self._ref[b] <= 0:
                    raise ValueError(f"retain of free block {b}")
                self._ref[b] += 1

    def release(self, blocks: Sequence[int]) -> None:
        """Drop one reference per block; blocks reaching refcount 0
        return to the free list. Ids at/above ``num_blocks`` are the
        dropped-write sentinel (see the engine's rescore path) — never
        refcounted, so they are skipped here, not a double-free."""
        with self._lock:
            for b in blocks:
                if b >= self.num_blocks:
                    continue                    # dropped-write sentinel
                if self._ref[b] <= 0:
                    raise ValueError(f"release of free block {b}")
                self._ref[b] -= 1
                if self._ref[b] == 0:
                    self._free.append(b)
                    self._counters["releases"] += 1
            self._publish_gauges()

    def fork(self, table: Sequence[int]) -> List[int]:
        """A new table aliasing every block of ``table`` — the
        **graft**: a shared prefix installs into a consumer with zero
        device bytes moved. Divergence is handled lazily by
        :meth:`cow_target` at first write.

        Sentinel-safe: a table can carry the ``write_block=num_blocks``
        dropped-write sentinel (the out-of-range scatter target rescue
        prefills aim at). The sentinel is preserved positionally in the
        returned table but never refcounted — ``self._ref`` has exactly
        ``num_blocks`` entries, so refcounting it would be an
        IndexError (and a leak in spirit even if it weren't)."""
        return self.fork_n(table, 1)[0]

    def fork_n(self, table: Sequence[int], n: int) -> List[List[int]]:
        """``n`` independent aliases of ``table`` in one lock pass —
        the group-rollout fork: one shared prompt spine, ``n`` GRPO
        completions. Each returned table is a separate list carrying
        one reference per real block (``n`` refcount bumps total per
        block, ``n`` grafts counted); sentinel ids are preserved but
        never refcounted. All-or-nothing: a free block anywhere in the
        table raises before any refcount moves."""
        if n <= 0:
            return []
        with self._lock:
            real = [b for b in table if b < self.num_blocks]
            for b in real:
                if self._ref[b] <= 0:
                    raise ValueError(f"fork of free block {b}")
            for b in real:
                self._ref[b] += n
            self._counters["grafts"] += n
            self._graft_total.inc(n)
            return [list(table) for _ in range(n)]

    def cow_target(self, block: int) -> Optional[int]:
        """Copy-on-write check before writing into ``block``: None when
        the caller owns it exclusively (write in place), else a fresh
        block the caller must :func:`copy_blocks` into and point its
        table at (the old reference is released here). May raise
        :class:`BlocksExhausted` — the shared block is untouched then."""
        with self._lock:
            if self._ref[block] <= 0:
                raise ValueError(f"cow_target of free block {block}")
            if self._ref[block] == 1:
                return None
            fresh = self.alloc(1)[0]
            # Drop our reference to the shared block only after the
            # fresh one is granted, so exhaustion leaves state intact.
            self.release([block])
            self._counters["cow_copies"] += 1
            self._cow_total.inc()
            return fresh

    def count_install_copy(self, n: int = 1) -> None:
        """Account a buffer-copy prefix install (the non-graft path)."""
        with self._lock:
            self._counters["install_copies"] += n
            self._install_copy_total.inc(n)

    def count_eviction(self, n: int = 1) -> None:
        """Account ``n`` prefix entries dropped by scored eviction."""
        with self._lock:
            self._counters["evictions"] += n
            self._eviction_total.inc(n)

    def count_swap_out(self, nblk: int) -> None:
        """Account ``nblk`` blocks tiered device → host."""
        with self._lock:
            self._counters["swap_outs"] += nblk
            self._swap_out_total.inc(nblk)

    def count_swap_in(self, nblk: int) -> None:
        """Account ``nblk`` blocks restored host → device."""
        with self._lock:
            self._counters["swap_ins"] += nblk
            self._swap_in_total.inc(nblk)

    def set_swapped_blocks(self, n: int) -> None:
        """Publish how many blocks live only in the host tier (the
        block-count gauge is the compatibility alias; the authoritative
        ledger is the byte gauge beside it)."""
        with self._lock:
            self._swapped_blocks = n
            self._swapped_gauge.set(n)
            self._bytes_host_gauge.set(n * self.bytes_per_block)

    # -- gauges ----------------------------------------------------------
    def _publish_gauges(self) -> None:
        # guarded-by: caller
        free = len(self._free)
        self._blocks_total_gauge.set(self.num_blocks)
        self._blocks_free_gauge.set(free)
        used = self.num_blocks - free
        self._util_gauge.set(used / self.num_blocks)
        self._bytes_device_gauge.set(used * self.bytes_per_block)

    def publish_fragmentation(self, used_tokens: int) -> None:
        """Internal-fragmentation gauge: ``used_tokens`` positions live
        across ``used_blocks * block_size`` allocated capacity; the
        difference is stranded tail space in partial last blocks."""
        with self._lock:
            cap = self.used_blocks * self.block_size
            frac = 0.0 if cap == 0 else 1.0 - (used_tokens / cap)
            self._frag_gauge.set(max(0.0, frac))


class PagedSeqKV:
    """One sequence's paged cache: a private pool + allocator + table.

    The speculative decoder's verify path uses this instead of a
    contiguous ``KVCache``: each verify round writes up to ``k`` draft
    tokens past the accepted prefix, and a rejection must ROLL BACK —
    :meth:`truncate` releases every block past the accepted length, so
    rejected drafts can never leak pool capacity (the contiguous path's
    metadata-only truncate has no blocks to leak; here the free list is
    the proof, checked by ``allocator.check_leaks`` in tests)."""

    def __init__(self, config: ModelConfig, *, max_len: int,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 registry=None, kv_dtype: str = "bf16",
                 kv_dtype_per_layer=None):
        if num_blocks is None:
            num_blocks = -(-max_len // block_size)
        self.pool = init_paged_pool(config, num_blocks, block_size,
                                    kv_dtype=kv_dtype,
                                    kv_dtype_per_layer=kv_dtype_per_layer)
        self.allocator = BlockAllocator(
            num_blocks, block_size, registry=registry,
            bytes_per_block=pool_bytes_per_block(self.pool))
        self.max_blocks = -(-max_len // block_size)
        self.table: List[int] = []
        self.length = 0

    def ensure(self, new_len: int) -> None:
        """Grow the table to cover positions ``< new_len``."""
        need = self.allocator.blocks_for(new_len)
        if need > len(self.table):
            self.table.extend(self.allocator.alloc(need - len(self.table)))

    def truncate(self, length: int) -> None:
        """Roll back to ``length`` valid tokens, RELEASING every block
        past the boundary (the paged analogue of resetting
        ``KVCache.length``; stale data inside the kept partial block is
        masked by the validity window, same as the contiguous path)."""
        keep = self.allocator.blocks_for(length)
        if keep < len(self.table):
            self.allocator.release(self.table[keep:])
            del self.table[keep:]
        self.length = length

    def free(self) -> None:
        """Release the whole table (end of generation)."""
        self.truncate(0)

    def tables_array(self) -> jnp.ndarray:
        """Dense (1, max_blocks) int32 table row for forward_paged."""
        row = self.table + [0] * (self.max_blocks - len(self.table))
        return jnp.asarray([row], jnp.int32)


# -- device-side block movement (the only jitted code here) --------------

@functools.partial(jax.jit, donate_argnames=("pool",))
def copy_blocks(pool: PagedKVPool, src: jnp.ndarray,
                dst: jnp.ndarray) -> PagedKVPool:
    """Copy pool blocks ``src[i] -> dst[i]`` (both ``(n,)`` int32) in
    one gather+scatter per tensor — the COW copy. tree_map covers every
    pool tensor (payload, scales, full-width prefix), so quantize-at-
    write commutes with COW: a copied block carries its scales with
    it. Row-addressed state (``pool.rows``) has no blocks and passes
    through."""
    return jax.tree_util.tree_map(
        lambda a: a.at[:, dst].set(a[:, src]),
        pool._replace(rows=None))._replace(rows=pool.rows)


@functools.partial(jax.jit, donate_argnames=("pool",))
def copy_state_rows(pool: PagedKVPool, src: jnp.ndarray,
                    dst: jnp.ndarray) -> PagedKVPool:
    """Copy the row-addressed state ``src[i] -> dst[i]`` (both ``(n,)``
    int32) over all layers: the snapshot of a group's state at its fork
    and its install into a follower's row are both this one program (one
    shape for ``n`` = 1). The block leaves pass through."""
    return pool._replace(rows=jax.tree_util.tree_map(
        lambda a: a.at[:, dst].set(a[:, src]), pool.rows))


@functools.partial(jax.jit, donate_argnames=("pool",))
def install_blocks(pool: PagedKVPool, k_buf: jnp.ndarray,
                   v_buf: jnp.ndarray, dst: jnp.ndarray) -> PagedKVPool:
    """Scatter FULL-WIDTH buffers ``(L, n, block_size, Hkv, Dh)`` into
    pool blocks ``dst`` ``(n,)`` — the cross-engine prefix import.
    Quantized pools quantize at install (same absmax math as the fused
    step's quantize-at-write, so installed and decoded blocks hold
    bit-identical payloads); the ``hi_layers`` prefix stays full
    width."""
    if pool.k_scale is None:
        return pool._replace(
            k=pool.k.at[:, dst].set(k_buf.astype(pool.k.dtype)),
            v=pool.v.at[:, dst].set(v_buf.astype(pool.v.dtype)))
    n_hi = pool.hi_layers
    upd = {}
    if n_hi:
        upd["k_hi"] = pool.k_hi.at[:, dst].set(
            k_buf[:n_hi].astype(pool.k_hi.dtype))
        upd["v_hi"] = pool.v_hi.at[:, dst].set(
            v_buf[:n_hi].astype(pool.v_hi.dtype))
    kq, ks = quantize_pool_kv(k_buf[n_hi:], pool.k.dtype)
    vq, vs = quantize_pool_kv(v_buf[n_hi:], pool.v.dtype)
    upd["k"] = pool.k.at[:, dst].set(kq)
    upd["v"] = pool.v.at[:, dst].set(vq)
    upd["k_scale"] = pool.k_scale.at[:, dst].set(ks)
    upd["v_scale"] = pool.v_scale.at[:, dst].set(vs)
    return pool._replace(**upd)


@functools.partial(jax.jit, donate_argnames=("pool",))
def install_blocks_quant(pool: PagedKVPool, payload: BlockPayload,
                         dst: jnp.ndarray) -> PagedKVPool:
    """Scatter a pool-native :class:`BlockPayload` into blocks ``dst``
    — the quantization-preserving inverse of
    :func:`gather_blocks_quant` (host-tier restore, migration install,
    quantized prefix import). The payload layout must match the pool's
    (same ladder rung); mismatches are a caller bug surfaced here."""
    if (payload.k_scale is None) != (pool.k_scale is None) or \
            (payload.k_hi is None) != (pool.k_hi is None):
        raise ValueError(
            "BlockPayload quantization layout does not match the pool "
            "(payload must come from a pool on the same kv_dtype rung)")
    upd = {"k": pool.k.at[:, dst].set(
               jnp.asarray(payload.k, pool.k.dtype)),
           "v": pool.v.at[:, dst].set(
               jnp.asarray(payload.v, pool.v.dtype))}
    if pool.k_scale is not None:
        upd["k_scale"] = pool.k_scale.at[:, dst].set(
            jnp.asarray(payload.k_scale, jnp.float32))
        upd["v_scale"] = pool.v_scale.at[:, dst].set(
            jnp.asarray(payload.v_scale, jnp.float32))
    if pool.k_hi is not None:
        upd["k_hi"] = pool.k_hi.at[:, dst].set(
            jnp.asarray(payload.k_hi, pool.k_hi.dtype))
        upd["v_hi"] = pool.v_hi.at[:, dst].set(
            jnp.asarray(payload.v_hi, pool.v_hi.dtype))
    return pool._replace(**upd)


@functools.partial(jax.jit, static_argnames=("dtype",))
def gather_blocks(pool: PagedKVPool, idx: jnp.ndarray, dtype=None):
    """Contiguous FULL-WIDTH ``(L, n*block_size, Hkv, Dh)`` view of
    pool blocks ``idx`` ``(n,)`` — the prefix export. Quantized pools
    dequantize here (and re-prepend the full-width prefix layers), so
    every caller sees the same fleet-wide layout regardless of the
    replica's ladder rung. ``dtype`` overrides the output dtype
    (defaults to the pool's full-width dtype)."""
    bs = pool.k.shape[2]
    n = idx.shape[0]
    if dtype is None:
        dtype = pool.full_dtype

    def flat(a):
        return a[:, idx].reshape(a.shape[0], n * bs, *a.shape[3:])

    if pool.k_scale is None:
        return flat(pool.k).astype(dtype), flat(pool.v).astype(dtype)
    k = dequantize_pool_kv(flat(pool.k), flat(pool.k_scale), dtype)
    v = dequantize_pool_kv(flat(pool.v), flat(pool.v_scale), dtype)
    if pool.k_hi is not None:
        k = jnp.concatenate([flat(pool.k_hi).astype(dtype), k], axis=0)
        v = jnp.concatenate([flat(pool.v_hi).astype(dtype), v], axis=0)
    return k, v


@jax.jit
def gather_blocks_quant(pool: PagedKVPool,
                        idx: jnp.ndarray) -> BlockPayload:
    """Raw block-layout payload of pool blocks ``idx`` — quantized
    payloads STAY quantized (int8/fp8 bytes + scales), halving host-
    tier footprint and migration/export wire bytes relative to the
    dequantizing :func:`gather_blocks`."""
    def grab(a):
        return None if a is None else a[:, idx]
    return BlockPayload(k=grab(pool.k), v=grab(pool.v),
                        k_scale=grab(pool.k_scale),
                        v_scale=grab(pool.v_scale),
                        k_hi=grab(pool.k_hi), v_hi=grab(pool.v_hi))


# Runtime observatory wiring (obs/runtime_profile.py): block movement is
# the prefix import/export + COW cost the KV-economics roadmap item
# needs numbers for. The block-count ladder makes a handful of
# signatures per pool shape legitimate; only unbounded growth storms.
# the pool is out of the signature scan and nothing waits: a copy is
# ordered on the device by the next step's donation of the pool, behind
# a fused step that is still in flight
copy_blocks = ProfiledFunction(copy_blocks, "paged_kv.copy",
                               skip_args=(0,), block=False,
                               storm_threshold=32)
copy_state_rows = ProfiledFunction(copy_state_rows, "paged_kv.copy_state",
                                   skip_args=(0,), block=False,
                                   storm_threshold=32)
install_blocks = ProfiledFunction(install_blocks, "paged_kv.install",
                                  storm_threshold=32)
install_blocks_quant = ProfiledFunction(
    install_blocks_quant, "paged_kv.install_quant", storm_threshold=32)
gather_blocks = ProfiledFunction(gather_blocks, "paged_kv.gather",
                                 storm_threshold=32)
gather_blocks_quant = ProfiledFunction(
    gather_blocks_quant, "paged_kv.gather_quant", storm_threshold=32)
