"""Continuous-batching rollout engine — slot-pool decoding on one KV cache.

The reference fans rollouts out as concurrent HTTPS requests to provider
APIs (``agentScheduler.ts`` chunked ``Promise.allSettled``, max 3-8 parallel
— SURVEY.md §2.7). The TPU equivalent keeps ONE resident batch on device:
the batch axis is a pool of ``num_slots`` decode slots sharing a single
(L, num_slots, max_len, Hkv, Dh) KV cache with per-slot lengths
(``KVCache.length`` as a (B,) vector — models/transformer.py scatter path).

- ``submit()`` queues a request; free slots are prefilled one at a time
  (prompt padded to a power-of-two bucket to bound recompilation).
- ``step()`` decodes ONE token for every active slot in a single jitted
  call — new requests join the batch the moment a slot frees up, so chip
  utilization does not drain between rollouts (the "sampler/trainer overlap"
  half of SURVEY.md §7's systems risk).
- Finished slots (eos / budget) are recycled immediately.

The agent loop (rollout/agent_loop.py) drives this engine: each agent turn
submits a prompt and consumes streamed tokens, so many agent conversations
interleave on one chip like the reference's 8 parallel subagents interleave
on one event loop (``subagentToolService.ts:33-36``).
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.config import ModelConfig, refuse
from ..models.transformer import (KVCache, Params, forward, forward_paged,
                                  init_kv_cache, reads_pool_in_place)
from ..obs import get_registry, get_tracer
from ..obs.runtime_profile import (ProfiledFunction, get_profiler,
                                   profiled_device_get)
from ..obs.tracing import noop_span
from ..ops import state_step
from ..ops.sampling import sample_token, sampled_logprob
from .kv_pressure import (HostPrefix, PrefixCandidate, dequantize_host,
                          pick_victim, should_tier)
from .paged_kv import (BlockAllocator, BlockPayload, BlocksExhausted,
                       PagedKVPool, cache_kinds, copy_blocks,
                       copy_state_rows,
                       gather_blocks, gather_blocks_quant, init_paged_pool,
                       install_blocks, install_blocks_quant, kv_row_bytes,
                       pool_bytes_per_block, resolve_block_size,
                       resolve_kv_dtypes)
from .sampler import SampleParams


class QueueFull(RuntimeError):
    """submit() refused: the engine's bounded queue is at ``max_queue``.

    Raised instead of silently growing the backlog so an admission layer
    (serve/admission.py) can shed load explicitly; the unbounded default
    (``max_queue=None``) keeps the legacy enqueue-anything behavior."""


class PrefixImportError(ValueError):
    """import_prefix() refused a foreign KV buffer: shape, dtype,
    quantization flavor, or recorded length doesn't match this engine's
    pool layout. Typed so a fleet-level broadcast (serve/prefix_store.py)
    can catch it and degrade to a local lazy prefill instead of serving
    from a corrupt cache."""


def _bucket(n: int, minimum: int = 16) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


def _slice_slot(cache: KVCache, slot: jax.Array,
                length: jax.Array) -> KVCache:
    """View one slot of the pool as a B=1 sub-cache at ``length``."""
    L, _, cap, hkv, dh = cache.k.shape
    sub_k = jax.lax.dynamic_slice(
        cache.k, (0, slot, 0, 0, 0), (L, 1, cap, hkv, dh))
    sub_v = jax.lax.dynamic_slice(
        cache.v, (0, slot, 0, 0, 0), (L, 1, cap, hkv, dh))
    if cache.quantized:          # int8 pool: slice the scales alongside
        return KVCache(
            k=sub_k, v=sub_v, length=length,
            k_scale=jax.lax.dynamic_slice(
                cache.k_scale, (0, slot, 0, 0), (L, 1, cap, hkv)),
            v_scale=jax.lax.dynamic_slice(
                cache.v_scale, (0, slot, 0, 0), (L, 1, cap, hkv)))
    return KVCache(k=sub_k, v=sub_v, length=length)


def _writeback_slot(cache: KVCache, sub: KVCache, slot: jax.Array,
                    new_len: jax.Array) -> KVCache:
    """Write a B=1 sub-cache back into the pool; set the slot length."""
    new_k = jax.lax.dynamic_update_slice(cache.k, sub.k, (0, slot, 0, 0, 0))
    new_v = jax.lax.dynamic_update_slice(cache.v, sub.v, (0, slot, 0, 0, 0))
    new_ks = new_vs = None
    if cache.quantized:
        new_ks = jax.lax.dynamic_update_slice(cache.k_scale, sub.k_scale,
                                              (0, slot, 0, 0))
        new_vs = jax.lax.dynamic_update_slice(cache.v_scale, sub.v_scale,
                                              (0, slot, 0, 0))
    return KVCache(k=new_k, v=new_v,
                   length=cache.length.at[slot].set(new_len),
                   k_scale=new_ks, v_scale=new_vs)


@functools.partial(jax.jit, static_argnames=("config",),
                   donate_argnames=("cache",))
def _prefill_slot(params: Params, config: ModelConfig, tokens: jax.Array,
                  true_len: jax.Array, cache: KVCache,
                  slot: jax.Array) -> tuple[jax.Array, KVCache]:
    """Prefill one slot. tokens: (1, S_bucket) right-padded; returns
    (last-real-token logits (V,), updated pool cache)."""
    max_len = cache.k.shape[2]
    sub = _slice_slot(cache, slot, jnp.zeros((), jnp.int32))

    # Mask padding so it can't be attended during prefill; padded positions
    # are overwritten by subsequent decode steps before they become visible.
    kv_pos = jnp.arange(max_len)[None, :]
    attn_mask = kv_pos < true_len
    logits, sub = forward(params, config, tokens, cache=sub,
                          attn_mask=attn_mask, fresh_cache=True)
    last = logits[0, true_len - 1, :]
    return last, _writeback_slot(cache, sub, slot, true_len)


@functools.partial(jax.jit, static_argnames=("config",),
                   donate_argnames=("cache",))
def _prefill_slots_batched(params: Params, config: ModelConfig,
                           tokens: jax.Array, true_lens: jax.Array,
                           cache: KVCache,
                           slots: jax.Array) -> tuple[jax.Array, KVCache]:
    """Prefill N fresh slots in ONE forward. tokens: (N, S_bucket)
    right-padded; true_lens/slots: (N,). Returns ((N, V) last-real-token
    logits, updated pool cache).

    The serial-prefill fix (r2 weak item: queued requests prefilled one
    at a time, draining decode while the pool idled): same-bucket queued
    requests batch into one MXU-friendly pass. Fresh slots need no
    gather — their sub-cache starts as zeros — and the writeback is one
    scatter per tensor over the slot axis. Duplicate slot indices are
    legal ONLY with identical rows (the scheduler pads the batch by
    repeating row 0)."""
    L = cache.k.shape[0]
    cap = cache.k.shape[2]
    n = tokens.shape[0]
    sub = init_kv_cache(config, n, cap, quantized=cache.quantized)
    kv_pos = jnp.arange(cap)[None, :]
    attn_mask = kv_pos < true_lens[:, None]            # (N, cap)
    logits, sub = forward(params, config, tokens, cache=sub,
                          attn_mask=attn_mask, fresh_cache=True)
    last = jnp.take_along_axis(
        logits, (true_lens - 1)[:, None, None], axis=1)[:, 0, :]
    new_k = cache.k.at[:, slots].set(sub.k)
    new_v = cache.v.at[:, slots].set(sub.v)
    new_ks = new_vs = None
    if cache.quantized:
        new_ks = cache.k_scale.at[:, slots].set(sub.k_scale)
        new_vs = cache.v_scale.at[:, slots].set(sub.v_scale)
    return last, KVCache(k=new_k, v=new_v,
                         length=cache.length.at[slots].set(true_lens),
                         k_scale=new_ks, v_scale=new_vs)


@functools.partial(jax.jit, static_argnames=("config", "fresh"),
                   donate_argnames=("cache",))
def _prefill_slot_chunk(params: Params, config: ModelConfig,
                        tokens: jax.Array, cache: KVCache,
                        slot: jax.Array, *,
                        fresh: bool) -> tuple[jax.Array, KVCache]:
    """One EXACT-SIZE prefill chunk into a slot at its current length.

    The ring-pool long-prompt path: padded chunks are off the table — a
    pad token physically written into the ring gets attributed a real
    position by the modular validity mask (silent corruption), so the
    prompt is instead decomposed into exact chunks (cap-sized + a
    powers-of-two remainder ladder, bounding the compile set to
    log2(cap) shapes). ``fresh`` marks the first chunk of a reset slot.
    """
    start = cache.length[slot]
    sub = _slice_slot(cache, slot, start)
    logits, sub = forward(params, config, tokens, cache=sub,
                          fresh_cache=fresh)
    return (logits[0, -1, :],
            _writeback_slot(cache, sub, slot, start + tokens.shape[1]))


@functools.partial(jax.jit, donate_argnames=("cache",))
def _install_prefix(cache: KVCache, prefix: KVCache,
                    slot: jax.Array) -> KVCache:
    """Copy a cached prefix's KV (one pool-slot-shaped buffer) into a
    slot — HBM copy instead of recomputing the shared prompt prefix."""
    return _writeback_slot(cache, prefix, slot, prefix.length)


def _chunk_sizes(n: int, cap: int) -> list:
    """n = (n // cap) full chunks + a descending powers-of-two ladder."""
    sizes = [cap] * (n // cap)
    r = n % cap
    p = 1
    while p * 2 <= max(r, 1):
        p *= 2
    while r > 0:
        while p > r:
            p //= 2
        sizes.append(p)
        r -= p
    return sizes


@functools.partial(jax.jit, static_argnames=("config", "sample"),
                   donate_argnames=("cache",))
def _pool_decode_step(params: Params, config: ModelConfig, cur_tok: jax.Array,
                      active: jax.Array, cache: KVCache, key: jax.Array,
                      sample: SampleParams):
    """One decode step over the whole pool. cur_tok/active: (num_slots,).
    Inactive slots compute garbage that is discarded; their lengths hold.
    Also returns each sampled token's model log-prob (the behavior
    logp GRPO's importance ratio trains against — ops/sampling.py
    sampled_logprob), captured here where the logits are already in
    hand instead of re-running the policy later."""
    logits, new_cache = forward(params, config, cur_tok[:, None], cache=cache)
    logits = logits[:, -1, :]
    next_tok = sample_token(logits, key, temperature=sample.temperature,
                            top_k=sample.top_k, top_p=sample.top_p)
    next_tok = jnp.where(active, next_tok, cur_tok)
    logp = sampled_logprob(logits, next_tok)
    length = jnp.where(active, new_cache.length, cache.length)
    return next_tok, logp, KVCache(k=new_cache.k, v=new_cache.v,
                                   length=length,
                                   k_scale=new_cache.k_scale,
                                   v_scale=new_cache.v_scale)


# bits of the plan's sixth row (``_paged_fused_step``)
FEED_TAKE, FEED_PUT = 1, 2


def _head_entries(entries: int, rows: int, all_logits: bool) -> int:
    """How many of a fused step's ``entries`` pay the final norm, the
    head and the sampler: the ``rows`` that can hold a sampler where the
    step is wider than that, every entry where it is not or where each
    entry's argmax is read (``all_logits``: a plan with verify entries).
    ``_paged_fused_step`` builds its program by it and the host counts
    by it (``engine.step`` attr ``head_entries``)."""
    return entries if all_logits or entries <= rows else rows


@functools.partial(jax.jit,
                   static_argnames=("config", "sample", "use_kernel",
                                    "all_logits"),
                   donate_argnames=("pool",))
def _paged_fused_step(params: Params, config: ModelConfig,
                      plan: jax.Array, tables: jax.Array,
                      pool: PagedKVPool,
                      key: jax.Array, cur: jax.Array, sample: SampleParams,
                      use_kernel: Optional[bool],
                      adapters=None, adapter_ids=None,
                      all_logits: bool = False):
    """One fused paged step over a flat token batch: decode rows and
    exact-size chunked-prefill segments share the same forward under a
    static token budget (``plan.shape[1]``). ``plan`` is the host's
    six int32 vectors as the rows of ONE ``(6, T)`` array — tokens,
    seq_row, positions, write_block, write_off, feed — so the call
    ingests one host array for them, not six. Each entry writes its
    k/v through ``(write_block, write_off)`` — padding/rescore entries
    address the out-of-range sentinel block and are dropped by the
    scatter. ``key`` is the engine's key: it is split HERE, as the
    host used to split it before every call (the same threefry split
    in the same order, so a seed's tokens are what they were), and the
    next key is the fourth result — no program of its own between two
    steps. ``cur`` is the rows' current tokens, ``(num_slots,)`` int32,
    carried from step to step as the key is (the fifth result): an
    entry whose ``feed`` has ``FEED_TAKE`` set is fed ``cur`` of its
    row in place of its ``tokens`` (a decode row whose last sample is
    not on the host yet: the step before this one is still running),
    and one with ``FEED_PUT`` leaves its sample there (decode rows,
    the last entry of a completing prefill). So a step can be launched
    before the last one's tokens are home, and it is the same program
    when it is not. Sampling happens in-jit, over the entries somebody
    reads: the host keeps only its samplers (decode rows, the final
    token of a completing prefill), which are exactly the entries with
    ``FEED_PUT``, at most one a row. A step wider than the rows
    (``T > num_slots``: prefill chunks ride it) finds each row's such
    entry on the device, from the plan it already has, and the final
    norm, the head, the sampler and the log-prob run over those
    ``num_slots`` entries alone (``forward_paged``'s ``logit_entries``;
    a row with none reads an entry that is nobody's and its sample is
    dropped; a layer pattern's trailing layers that write no cache run
    over those entries too);
    the samples and log-probs are scattered back to their entries'
    places in ``(T,)`` outputs, so the host reads them as it always
    did. A step as wide as the rows gathers nothing, and
    ``all_logits=True`` (static; a plan with verify entries, whose every
    entry's argmax is read) keeps every entry's head at any width. ONE
    batched device_get per step covers first tokens and decode tokens
    alike. With an adapter pool
    attached, ``adapters`` (fixed-shape rank-ladder banks) and
    ``adapter_ids`` (per-rung (T,) slot vectors, null slot 0 for base
    rows) ride every call, so tenant churn reuses the same compiled
    signatures. The pool rides through as the whole PagedKVPool pytree:
    on quantized ladders (EngineConfig.kv_dtype) the same fused step
    quantizes each entry's k/v at write time and scatters payload +
    absmax scales through the SAME sentinel-guarded indices — no extra
    device round-trips, no new compile per occupancy bucket (the scale
    tensors are shape-static alongside the payloads)."""
    key, step_key = jax.random.split(key)
    tokens, seq_row, positions, write_block, write_off, feed = plan
    tokens = jnp.where((feed & FEED_TAKE) > 0, cur[seq_row], tokens)
    t, rows = tokens.shape[0], cur.shape[0]
    samplers = None
    if _head_entries(t, rows, all_logits) < t:
        # each row's sampler: its one entry that puts, else ``t`` (out of
        # range: the head reads a clamped entry, the scatters below drop
        # its sample)
        samplers = jnp.full((rows,), t, jnp.int32).at[
            jnp.where((feed & FEED_PUT) > 0, seq_row, rows)].set(
                jnp.arange(t, dtype=jnp.int32), mode="drop")
    has_kda = config.kind_layers("kda") > 0
    logits, pool, *stats = forward_paged(
        params, config, tokens, pool=pool,
        tables=tables, seq_row=seq_row, positions=positions,
        write_block=write_block, write_off=write_off,
        use_kernel=use_kernel, adapters=adapters, adapter_ids=adapter_ids,
        with_moe_stats=config.num_experts > 0,
        with_mhc_stats=config.hc_mult > 0, with_attn_stats=True,
        with_kda_stats=has_kda, logit_entries=samplers)
    kda = stats.pop() if has_kda else None
    shared = stats.pop()
    next_tok = sample_token(logits, step_key, temperature=sample.temperature,
                            top_k=sample.top_k, top_p=sample.top_p)
    logp = sampled_logprob(logits, next_tok)
    if samplers is not None:
        # row r's sample and log-prob go back to their entry's place
        next_tok = jnp.zeros((t,), next_tok.dtype).at[samplers].set(
            next_tok, mode="drop")
        logp = jnp.zeros((t,), logp.dtype).at[samplers].set(
            logp, mode="drop")
    # a row has at most one entry that puts: the others' index is out of
    # range and the scatter drops them
    cur = cur.at[jnp.where((feed & FEED_PUT) > 0, seq_row, cur.shape[0])
                 ].set(next_tok.astype(cur.dtype), mode="drop")
    if config.hc_mult:
        # A multi-stream model's step also says how far from doubly
        # stochastic its worst H_res was: one float behind the step's
        # log-probs, as the expert counts ride behind its tokens
        # (``_note_mhc_step`` reads it).
        logp = jnp.concatenate([logp, stats.pop()[None].astype(logp.dtype)])
    if stats:
        # An expert model's step also says what its routing did: the
        # counts of ``MoEStats`` (two, or four where the layer holds a
        # share of the router's experts) ride BEHIND the step's tokens in
        # the same array, so the one fetch brings them and nothing is
        # dispatched or waited for on their account (``_note_moe_step``
        # reads them). Every consumer of the tokens indexes entries below
        # ``T``.
        next_tok = jnp.concatenate(
            [next_tok, jnp.stack([n for n in stats[0] if n is not None]
                                 ).astype(next_tok.dtype)])
    # Last, for every model: what the attention kernels' plan found among
    # the rows' tables (``forward_paged``: block reads not made, group
    # items), the same way (``_collect`` cuts them off again).
    next_tok = jnp.concatenate([next_tok, shared.astype(next_tok.dtype)])
    if kda is not None:
        # A delta-rule model's step also says what its mixers did, behind
        # everything else: the entries its chunked form took behind the
        # tokens, the largest readout behind the log-probs
        # (``_note_kda_step`` reads and cuts them).
        next_tok = jnp.concatenate(
            [next_tok, kda[0][None].astype(next_tok.dtype)])
        logp = jnp.concatenate([logp, kda[1][None].astype(logp.dtype)])
    return next_tok, logp, pool, key, cur


@functools.partial(jax.jit, static_argnames=("config", "k", "use_kernel"),
                   donate_argnames=("pool",))
def _draft_propose_scan(params: Params, config: ModelConfig,
                        cur_tok: jax.Array, base_pos: jax.Array,
                        spec_mask: jax.Array, tables: jax.Array,
                        pool: PagedKVPool,
                        k: int, use_kernel: Optional[bool]):
    """Greedy draft proposal loop, entirely on device: ``k`` sequential
    draft-model decode steps over every speculating row at once
    (``spec_mask``), each feeding its own argmax back in. One device
    call and ONE host transfer replace k round-trips; ``k`` is static
    so every speculation depth is its own pre-compiled bucket. Rows
    outside the mask write to the sentinel block (dropped by the
    scatter) and their proposals are ignored by the host. Returns
    ``(proposals (R, k) int32, pool')``."""
    r = tables.shape[0]
    mb = tables.shape[1]
    nb = pool.k.shape[1]
    bs = pool.k.shape[2]
    seq_row = jnp.arange(r, dtype=jnp.int32)

    def body(carry, _i):
        p, tok, pos = carry
        lb = jnp.clip(pos // bs, 0, mb - 1)
        wb = jnp.where(spec_mask & (pos // bs < mb),
                       tables[seq_row, lb], nb)
        logits, p = forward_paged(
            params, config, tok, pool=p, tables=tables,
            seq_row=seq_row, positions=pos, write_block=wb,
            write_off=pos % bs, use_kernel=use_kernel)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nxt = jnp.where(spec_mask, nxt, tok)
        return (p, nxt, pos + 1), nxt

    (pool, _tok, _pos), props = jax.lax.scan(
        body, (pool, cur_tok, base_pos),
        jnp.arange(k, dtype=jnp.int32))
    return props.T, pool


@functools.partial(jax.jit, static_argnames=("config", "use_kernel"),
                   donate_argnames=("pool",))
def _draft_feed_step(params: Params, config: ModelConfig,
                     tokens: jax.Array, tables: jax.Array,
                     seq_row: jax.Array, positions: jax.Array,
                     write_block: jax.Array, write_off: jax.Array,
                     pool: PagedKVPool,
                     use_kernel: Optional[bool]):
    """Draft-cache catch-up: run the draft model over a flat token
    batch purely for its KV writes (logits discarded, no transfer).
    This is how the draft reaches lockstep with the target after
    prefill, continuations, preemption resume, rollback, or a depth-0
    stretch — the host replays the already-known token stream."""
    _logits, pool = forward_paged(
        params, config, tokens, pool=pool,
        tables=tables, seq_row=seq_row, positions=positions,
        write_block=write_block, write_off=write_off,
        use_kernel=use_kernel)
    return pool


# Runtime observatory wiring (obs/runtime_profile.py): the two step
# drivers keep their compile/retrace ledger and device-time histograms
# under these names. Params/config (args 0-1) are shape-stable and
# skipped from the per-call signature scan; the fused step's storm
# threshold covers its LEGITIMATE compile ladder (power-of-two table
# widths x token-batch widths x speculation depths) so only unbounded
# retraces trip it. The draft propose/feed steps get the same
# treatment: their ladders are (table-bucket x depth) and
# (table-bucket x feed-width bucket) respectively. The fused step is
# the one wrap that does not block: its pool, key and rows' current
# tokens (args 4-6) are shape-stable too, and the engine, which knows
# when a step's tokens are on the host (``_collect``), reports the
# step's time to this ledger.
_pool_decode_step = ProfiledFunction(
    _pool_decode_step, "engine.decode_step", skip_args=(0, 1))
_paged_fused_step = ProfiledFunction(
    _paged_fused_step, "engine.fused_step", skip_args=(0, 1, 4, 5, 6),
    block=False, storm_threshold=64)
_draft_propose_scan = ProfiledFunction(
    _draft_propose_scan, "engine.spec_propose", skip_args=(0, 1),
    storm_threshold=32)
_draft_feed_step = ProfiledFunction(
    _draft_feed_step, "engine.spec_feed", skip_args=(0, 1),
    storm_threshold=32)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Engine KV-layout knobs, separate from the model's ModelConfig.

    ``kv_layout="paged"`` (default) serves from a fixed block pool
    (rollout/paged_kv.py): block-table attention, graft-based shared
    prefixes (refcount bump instead of HBM copy), and token-level
    chunked prefill interleaved with decode in one fused step.
    ``"slots"`` is the legacy contiguous per-slot cache. Paged silently
    falls back to slots where the block pool has no equivalent yet —
    int8 KV (``kv_quant``), sliding-window ring caches, TP-sharded
    meshes — ``engine.kv_layout`` reports the effective layout and
    ``engine.kv_layout_fallback`` the reason.

    ``block_size`` left None is resolved when the engine is built, from
    what the engine can see: the bytes of the pool's KV row and
    ``max_len`` (``paged_kv.resolve_block_size``; docs/serving.md "Paged
    KV cache"). ``engine.engine_config.block_size`` holds the resolved
    int."""

    kv_layout: str = "paged"
    # tokens per KV block; the partial last block of each sequence is
    # the only internal fragmentation (senweaver_kv_fragmentation). None
    # = by what the pool stores (paged_kv.resolve_block_size): a block's
    # copy costs the attention kernels about the same whatever it
    # carries, so a block holds as many tokens as make one payload
    # leaf's copy paged_kv.COPY_TARGET_BYTES, 16 to 128, fewer where
    # max_len is short. ``engine.engine_config`` holds the resolved int.
    # The block is also the unit of prefix grafts, of copy-on-write and
    # of the host tier. An explicit value is taken as it is.
    block_size: Optional[int] = None
    # pool capacity in blocks; None = (num_slots + 4) rows' worth —
    # slot-cache parity plus headroom for shared prefixes, which live
    # in the same pool here instead of separate slot-shaped buffers
    num_blocks: Optional[int] = None
    # per-step token budget for the fused decode+prefill batch; None =
    # max(4 * num_slots, 64). Decode rows are always admitted (the
    # budget cannot starve resident requests); the remainder fills
    # with exact-size prefill segments.
    step_tokens: Optional[int] = None
    # None = by what the code sees (models.transformer.forward_paged):
    # on a TPU an unquantized dense pool of 128-wide heads is read in
    # place by the Pallas kernel ops.paged_attention.paged_attention_rows
    # and a latent pool by its one-leaf form, everything else by the XLA
    # gather. True / False force the kernels on or off (interpreted off
    # the TPU): a test override, not a serving knob.
    paged_kernel: Optional[bool] = None
    # Host-RAM tier for warm prefixes (rollout/kv_pressure.py): under
    # pool pressure, warm/shared prefixes swap to host numpy buffers
    # and restore on demand via the install scatter; False degrades to
    # evict-only (the preempt-heavy PR-10 ladder, kept for benching).
    host_tier: bool = True
    # Preemption-starvation cap: a request preempted this many times
    # becomes non-preemptible (it either finishes or, when even a
    # whole-pool allocation cannot fit it, truncate-finishes) —
    # counted in senweaver_kv_preemption_storms_total.
    max_preempts: int = 3
    # Quantized KV ladder (docs/serving.md "Quantized KV ladder"):
    # "bf16" stores blocks at full model width; "int8"/"fp8" store
    # quantized payloads + per-(block, position, head) absmax scales,
    # roughly doubling effective pool capacity per chip. Quantization
    # happens at write time inside the ONE jitted fused step; decode
    # reads dequantize fused inside the paged-attention block loop.
    # Paged layout only (the slot layout has its own kv_quant knob).
    kv_dtype: str = "bf16"
    # Per-layer override, e.g. ("bf16", "bf16", "int8", ...): a
    # contiguous full-width prefix keeps the early layers (where
    # quantization divergence concentrates) exact while the tail rides
    # the ladder. Must be num_layers long, a bf16 prefix followed by
    # one uniform quantized run (rollout/paged_kv.resolve_kv_dtypes).
    kv_dtype_per_layer: Optional[tuple] = None


@dataclasses.dataclass
class _PrefillJob:
    """Host cursor for one request's token-level chunked prefill. The
    step assembler feeds ``toks`` into fused steps in exact-size
    segments; ``pos`` is the absolute position of ``toks[0]``."""

    toks: List[int]
    pos: int
    # sample the request's first output from the LAST fed token's row
    sample_last: bool
    # rescore-only job: the positions already hold this k/v (imported
    # prefix without donor logits) — writes are dropped so a SHARED
    # boundary block is not COW-split just to recompute logits
    drop_writes: bool = False
    # when not sampling (preemption resume), restore this token as the
    # row's decode cursor instead of emitting anything
    after_tok: Optional[int] = None
    # a group donor's prefill over prompt[:-1] for a model with recurrent
    # state: when it completes the engine captures the group's fork (the
    # table and a snapshot of the state rows) and the donor goes on with
    # this token, the prompt's last, as a one-token job of its own
    fork_then: Optional[int] = None


class _RowPreempted(Exception):
    """Internal: the row being assembled lost its blocks to
    reclamation and was requeued — skip it for this step."""


class _PlanWaits(Exception):
    """Internal: the plan being assembled ran out of blocks while a step
    is in flight. Reclaiming may preempt, and a preempted request is
    rebuilt from its tokens: the step in flight is collected first, then
    the plan is assembled again (what it allocated so far stays in the
    tables)."""


@dataclasses.dataclass
class _FlyingStep:
    """A fused step that was launched and whose tokens are not on the
    host yet: what :meth:`RolloutEngine._collect` needs to fetch them
    and hand them to their requests."""

    step: int                   # its number (``decode_steps`` at launch)
    toks: jax.Array
    logps: jax.Array
    # (entry, request, is its first token): the decode rows and the
    # completing prefills that sample, in entry order
    samplers: list
    spec_rows: list
    used: int                   # entries in use (an expert model's count)
    t_launch: float             # the profiler's clock at launch
    # the ``engine.step`` span that launched it (None: tracing off): the
    # step's value attrs are set on it when the tokens are home, beside
    # its ``used`` and ``entries``, so a reader joins them by step
    span: Any = None


class _DraftMetricsView:
    """Registry adapter for the draft block allocator: re-prefixes the
    ``senweaver_kv_*`` series to ``senweaver_spec_draft_kv_*`` so the
    draft pool's bookkeeping doesn't overwrite the target pool's
    gauges."""

    def __init__(self, registry):
        self._registry = registry

    @staticmethod
    def _rename(name: str) -> str:
        return name.replace("senweaver_kv_", "senweaver_spec_draft_kv_")

    def gauge(self, name, desc=""):
        return self._registry.gauge(     # metric-name: senweaver_spec_draft_kv_*
            self._rename(name), desc)

    def counter(self, name, desc=""):
        return self._registry.counter(   # metric-name: senweaver_spec_draft_kv_*
            self._rename(name), desc)


@dataclasses.dataclass
class _SpecState:
    """Host-side state for fused speculative decoding (one per engine,
    created by :meth:`RolloutEngine.enable_speculation`). All fields
    are guarded by the engine lock."""

    params: Params
    config: ModelConfig
    controller: object          # SpecController / FixedDepth duck type
    alloc: BlockAllocator       # draft KV block pool bookkeeping
    version: int = 0            # draft weight version (publish fence)
    # target publishes seen vs. the target version the draft was last
    # distilled/installed against: staleness = target_version - synced
    target_version: int = 0
    draft_synced_at: int = 0
    # WeightPublisher.begin already stamped the in-flight publish; the
    # engine-level install consumes the stamp instead of double-counting
    publish_pending: bool = False
    ema: float = 0.0            # acceptance-rate EMA (reset on publish)
    ema_init: bool = False
    depth_applied: int = 0      # controller depth used by the last step
    # verification outcomes for the online distiller (training/
    # draft_distill.py): bounded ring of {context, targets, accepted}
    ctx_window: int = 64
    outcomes: Deque = dataclasses.field(
        default_factory=lambda: deque(maxlen=512))
    depth_gauge: object = None
    accept_gauge: object = None
    staleness_gauge: object = None
    wasted_total: object = None


@dataclasses.dataclass
class _Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    eos_id: Optional[int]
    tokens: List[int] = dataclasses.field(default_factory=list)
    # model log-prob of each emitted token AT SAMPLE TIME (the behavior
    # policy logp for GRPO importance ratios), parallel to `tokens`
    logps: List[float] = dataclasses.field(default_factory=list)
    done: bool = False
    # sampled entries launched for this request whose tokens are not on
    # the host yet (0 between serial steps, up to 2 while a step runs
    # ahead), and whether the last token it may have is among the
    # launched: budget and context bound are counts of LAUNCHED tokens,
    # so no entry is planned for a request that is closing
    inflight: int = 0
    closing: bool = False
    slot: Optional[int] = None
    prefix_id: Optional[int] = None
    # hold_slot: keep the slot (and its KV) reserved after finishing so
    # a follow-up turn can continue from it (submit(continue_from=rid)).
    hold_slot: bool = False
    # full token history resident in the slot's cache EXCLUDING the
    # final sampled token (whose k/v is only written when it is fed) —
    # set when the request finishes while holding its slot.
    held_history: Optional[List[int]] = None
    # times this request lost its blocks to preempt-by-recomputation;
    # at EngineConfig.max_preempts it becomes non-preemptible
    preempt_count: int = 0
    # live-migration freeze (rollout/migration.py): a paused request
    # is skipped by the step assembler, the speculation planner, and
    # the scheduler — its state cannot advance between the migration
    # snapshot and the coordinator's release/resume decision.
    paused: bool = False
    # multi-tenant LoRA: the tenant key this request decodes under, and
    # the pool binding (rung, slot, version) resolved at SUBMIT time —
    # held for the request's whole life (incl. across preemption), so a
    # mid-decode publish is picked up only by the NEXT request.
    adapter: Optional[str] = None
    adapter_binding: Optional[object] = None
    # Group-shared rollout (submit_group): followers of a GRPO group
    # graft the donor's prefilled prompt spine instead of paying their
    # own prefill. `group_grafted` latches once so a preempted follower
    # cannot double-decrement the group's pending count on reschedule.
    group: Optional["_GroupShare"] = None
    group_grafted: bool = False
    # Tree-structured rollout lineage (fork_request): the rid this
    # request branched from, the parent's emitted-token count at the
    # branch point, and the branch depth (root submits are depth 0).
    parent_rid: Optional[int] = None
    branch_pos: Optional[int] = None
    branch_depth: int = 0
    # The request's life on time.perf_counter_ns(), stamped whether or
    # not tracing is on (a request submitted before a trace begins still
    # has to know its submit time when its first token falls inside it):
    # made, first placed in a row (kept across preemption), first token
    # in hand, finished; `row` is the row it last ran in (`slot` is
    # cleared at the finish). Read by the request.* spans.
    t_submit_ns: int = dataclasses.field(
        default_factory=time.perf_counter_ns)
    t_scheduled_ns: Optional[int] = None
    t_first_token_ns: Optional[int] = None
    t_done_ns: Optional[int] = None
    row: Optional[int] = None

    @property
    def launched(self) -> int:
        """Tokens sampled for this request, delivered or in flight."""
        return len(self.tokens) + self.inflight


@dataclasses.dataclass
class _GroupShare:
    """Shared-prefill bookkeeping for one GRPO group (guarded by the
    engine lock). The donor request prefills the group's prompt ONCE;
    when that prefill completes — before the donor's first sampled
    token is written, so the block table is the pure prompt spine —
    the engine captures an engine-retained fork of the table and
    enqueues the waiting followers. Each follower grafts the spine
    with a refcount bump (zero KV bytes moved) and rescores only the
    last prompt token. ``degraded`` flips if the donor dies before
    capture (preemption storm, migration release): followers fall back
    to plain unshared prefills — slower, never inexact.

    A model with recurrent state has no position to rescore: its donor's
    prefill is cut at ``prompt[:-1]``, the capture there also copies the
    donor's state rows into snapshot row ``state_row``, and donor and
    followers alike feed ``prompt[-1]`` as a one-token job with its
    write kept, each follower from its own copy of the snapshot."""

    gid: int
    prompt_len: int
    donor_rid: int
    spine: Optional[List[int]] = None    # engine-retained table fork
    spine_len: int = 0
    waiters: List["_Request"] = dataclasses.field(default_factory=list)
    pending: int = 0                     # followers not yet grafted
    degraded: bool = False
    state_row: Optional[int] = None      # snapshot row (recurrent state)


class RolloutEngine:
    """Slot-pool continuous batching over a shared KV cache."""

    def __init__(self, params: Params, config: ModelConfig, *,
                 num_slots: int = 8, max_len: int = 2048,
                 sample: SampleParams = SampleParams(),
                 eos_id: Optional[int] = None, seed: int = 0,
                 mesh=None, max_prefixes: int = 8,
                 max_queue: Optional[int] = None,
                 engine_config: Optional[EngineConfig] = None,
                 adapter_pool=None):
        self.config = config
        self.num_slots = num_slots
        # Sliding-window configs serve from a ring cache: the pool holds
        # `ring_capacity` slots per sequence (the SWA memory win), and
        # prompts must fit one ring chunk — `max_len` is clamped so the
        # submit() guard reports the real bound. Decode past the window
        # keeps working indefinitely (modular writes).
        from ..models.transformer import _is_ring, ring_capacity
        self.max_len = max_len = ring_capacity(config, max_len)
        self._ring = _is_ring(config, max_len)
        # Decode stop bound, fixed for the engine's lifetime: a ring pool
        # never runs out of slots (modular writes) and is bounded by the
        # model's position budget; an absolute pool stops at capacity.
        # Public contract for clients (EnginePolicyClient): the longest
        # context this engine can serve — the model's position budget on
        # ring pools (chunked prefill), the pool size on absolute ones.
        self.context_bound = (config.max_seq_len
                              if self._ring else max_len)
        # What this form of model has no form for is refused by name
        # (models.config.UNSUPPORTED), never fallen back from to the slot
        # layout below.
        ec = engine_config or EngineConfig()
        for asked, mechanism in (
                (ec.kv_layout == "slots", "RolloutEngine(kv_layout='slots')"),
                (config.kv_quant, "RolloutEngine(config.kv_quant)"),
                (ec.kv_dtype != "bf16" or ec.kv_dtype_per_layer is not None,
                 "RolloutEngine(kv_dtype=)"),
                (self._ring, "RolloutEngine(config.sliding_window)"),
                (mesh is not None, "RolloutEngine(mesh=)"),
                (adapter_pool is not None, "RolloutEngine(adapter_pool=)")):
            if asked:
                refuse(config, mechanism)
        self.sample = sample
        self.eos_id = eos_id
        # Optional tensor-parallel serving: params take the Megatron
        # layout and the KV cache shards its head axis over 'tp'
        # (SURVEY.md §2.7 'continuous-batching sampler with TP-sharded
        # KV cache'); jit then compiles collectives from the shardings.
        self.mesh = mesh
        # One-chip replicas of a fleet: params the caller COMMITTED to
        # one device (jax.device_put(params, dev)) name this engine's
        # chip. Its own state — PRNG key, block pool, published params —
        # is then created on and committed to that device; left
        # uncommitted it would be allocated on the default device
        # (every replica's pool on chip 0) and each step's key split
        # would run there.
        self._device = None
        if mesh is None:
            leaf = jax.tree_util.tree_leaves(params)[0]
            if getattr(leaf, "committed", False) and len(leaf.devices()) == 1:
                (self._device,) = leaf.devices()
        self.params = self._place_params(params)
        self._key = self._on_device(lambda: jax.random.PRNGKey(seed))
        # KV layout: paged block pool by default; the layouts the pool
        # has no equivalent for yet fall back to the slot cache.
        self.engine_config = ec
        if self.engine_config.block_size is None:
            self.engine_config = dataclasses.replace(
                self.engine_config, block_size=resolve_block_size(
                    kv_row_bytes(config, self.engine_config.kv_dtype,
                                 self.engine_config.kv_dtype_per_layer),
                    max_len))
        requested = self.engine_config.kv_layout
        if requested not in ("paged", "slots"):
            raise ValueError(f"unknown kv_layout {requested!r}")
        fallback = None
        if requested == "paged":
            if config.kv_quant:
                fallback = "kv_quant int8 cache"
            elif self._ring:
                fallback = "sliding-window ring cache"
            elif mesh is not None:
                fallback = "tensor-parallel KV sharding"
        self.kv_layout = ("slots" if requested == "slots" or fallback
                          else "paged")
        self.kv_layout_fallback = fallback
        # Quantized-ladder validation happens up front (and regardless
        # of layout): a silently-ignored kv_dtype on a slots fallback
        # would serve at double the memory the operator budgeted for.
        self._kv_payload_dtype, self._kv_hi_layers = resolve_kv_dtypes(
            config.attn_layers, self.engine_config.kv_dtype,
            self.engine_config.kv_dtype_per_layer)
        if (self._kv_payload_dtype is not None
                and self.kv_layout != "paged"):
            raise ValueError(
                "EngineConfig.kv_dtype quantized ladder needs the paged "
                "KV layout"
                + (f" (fell back to slots: {fallback})" if fallback
                   else " (kv_layout='slots' has its own kv_quant knob)"))
        # Multi-tenant LoRA (rollout/adapter_pool.py): the pool's banks
        # + per-row slot ids ride the ONE jitted paged step. Paged-only:
        # the slot path has no flat-token gather to hook.
        if adapter_pool is not None and self.kv_layout != "paged":
            raise ValueError(
                "adapter_pool needs the paged KV layout"
                + (f" (fell back to slots: {fallback})" if fallback else ""))
        self.adapter_pool = adapter_pool
        if self.kv_layout == "slots":
            shape = (config.num_layers, num_slots, max_len,
                     config.num_kv_heads, config.head_dim)
            quantized = config.kv_quant
            k0 = jnp.zeros(shape, jnp.int8 if quantized else config.dtype)
            v0 = jnp.zeros(shape, jnp.int8 if quantized else config.dtype)
            ks0 = vs0 = None
            if quantized:
                ks0 = jnp.zeros(shape[:-1], jnp.float32)
                vs0 = jnp.zeros(shape[:-1], jnp.float32)
            if mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec
                from ..parallel.sharding import KV_CACHE_SPEC, restrict_spec
                cache_sharding = NamedSharding(mesh,
                                               restrict_spec(KV_CACHE_SPEC,
                                                             mesh))
                k0 = jax.device_put(k0, cache_sharding)
                v0 = jax.device_put(v0, cache_sharding)
                if quantized:
                    # scales lack the head_dim axis; same layout otherwise
                    scale_spec = PartitionSpec(*KV_CACHE_SPEC[:-1])
                    scale_sharding = NamedSharding(
                        mesh, restrict_spec(scale_spec, mesh))
                    ks0 = jax.device_put(ks0, scale_sharding)
                    vs0 = jax.device_put(vs0, scale_sharding)
            self.cache = KVCache(k=k0, v=v0,
                                 length=jnp.zeros((num_slots,), jnp.int32),
                                 k_scale=ks0, v_scale=vs0)
            self.cur_tok = jnp.zeros((num_slots,), jnp.int32)
            self._storm_total = None
        else:
            bs = max(1, int(self.engine_config.block_size))
            self._blocks_per_row = -(-max_len // bs)
            nb = self.engine_config.num_blocks
            if nb is None:
                nb = (num_slots + 4) * self._blocks_per_row
            # Pool before allocator: the allocator's byte ledger
            # (senweaver_kv_bytes_{device,host}) needs the pool's
            # per-block footprint, which the kv_dtype ladder shrinks.
            # Recurrent state: one row of it for each engine row (state
            # row r goes with table row r) and the snapshot rows behind:
            # a group's state at its fork waits in one until its last
            # follower has copied it; with none free a group degrades to
            # unshared prefills.
            n_snap = max(2, num_slots // 6) if config.ssm else 0
            st = self.engine_config.step_tokens
            self._step_tokens = max(
                num_slots, int(st) if st else max(4 * num_slots, 64))
            self.pool = self._on_device(lambda: init_paged_pool(
                config, nb, bs,
                kv_dtype=self.engine_config.kv_dtype,
                kv_dtype_per_layer=self.engine_config.kv_dtype_per_layer,
                state_rows=num_slots + n_snap if config.ssm else 0,
                step_tokens=self._step_tokens))
            self._state_snap_free: List[int] = list(  # guarded-by: _lock
                range(num_slots + n_snap - 1, num_slots - 1, -1))
            # (src, dst) row copies asked for since the last fused step:
            # dispatched together in the next step's plan phase
            self._state_copies: List[tuple] = []    # guarded-by: _lock
            self._state_copies_total = None
            if config.ssm:
                reg = get_registry()
                self._state_copies_total = reg.counter(
                    "senweaver_ssm_state_copies_total",
                    "Recurrent-state rows copied: a group's snapshot at "
                    "its fork and each follower's install of it.")
                reg.gauge(
                    "senweaver_ssm_state_bytes",
                    "Device bytes of the pool's row-addressed recurrent "
                    "state (engine rows and snapshot rows, all layers)."
                ).set(self.pool.rows.nbytes)
                # the decode rows that went through the one-pass kernel
                # (ops/state_step.py): counted where a step program traced
                # here holds the kernel over this leaf, which the ops
                # layer alone decides, at trace time
                self._state_shape = self.pool.rows.ssm.shape
                self._state_one_pass_total = reg.counter(
                    "senweaver_state_rows_one_pass_total",
                    "Decode rows whose recurrent state the fused steps "
                    "advanced in one pass (ops/state_step.py's kernel: "
                    "read once, written once); 0 where the plain pass "
                    "ran.")
            # What the pool holds by kind of cache (block-addressed KV,
            # window rings, state, conv windows), once: a reader of the
            # gauges has the bytes of each descriptor.
            kind_bytes = get_registry().gauge(
                "senweaver_kv_cache_kind_bytes",
                "Device bytes of the pool by kind of cache "
                "(paged_kv.cache_kinds): kv, window, ssm, conv.",
                labelnames=("kind",))
            self.cache_kind_bytes = {
                k.kind: k.nbytes(nb, bs, num_slots + n_snap)
                for k in cache_kinds(
                    config, bs, self._step_tokens,
                    self.engine_config.kv_dtype,
                    self.engine_config.kv_dtype_per_layer)}
            for kind, n_bytes in self.cache_kind_bytes.items():
                kind_bytes.set(n_bytes, kind=kind)
            self._alloc = BlockAllocator(
                nb, bs, registry=get_registry(),
                bytes_per_block=pool_bytes_per_block(self.pool))
            # What one copy of the attention kernels carries: a block of
            # one payload leaf in one layer, as the pool stores it. With
            # the step's kv_blocks x payload leaves a trace reader has
            # copies a step and bytes a copy (engine.step attrs).
            self._kv_copy_bytes = (int(np.prod(self.pool.k.shape[2:]))
                                   * self.pool.k.dtype.itemsize)
            get_registry().gauge(
                "senweaver_kv_block_size",
                "Tokens a KV pool block holds: EngineConfig.block_size as "
                "resolved at construction.").set(bs)
            self._storm_total = get_registry().counter(
                "senweaver_kv_preemption_storms_total",
                "Requests preempted EngineConfig.max_preempts times and "
                "latched non-preemptible (starvation guard).")
            self.cache = None
            self.cur_tok = None
            # host-side block table + fill level + decode cursor per row
            self._tables: List[List[int]] = [[] for _ in range(num_slots)]  # guarded-by: _lock
            self._row_len: List[int] = [0] * num_slots  # guarded-by: _lock
            self._cur_tok_host: List[int] = [0] * num_slots  # guarded-by: _lock
            # the same cursor on the device, carried through the fused
            # step like the key: what a decode row is fed while its last
            # sample is still on its way to the host
            self._cur_tok_dev = self._on_device(
                lambda: jnp.zeros((num_slots,), jnp.int32))
            self._prefill_jobs: Dict[int, _PrefillJob] = {}  # guarded-by: _lock
            # None: forward_paged chooses by what it sees (the platform
            # and the pool's leaves); True / False force it, for tests
            self._use_paged_kernel = self.engine_config.paged_kernel
            # The gather copies every entry's whole table width, so the
            # table is trimmed to a ladder of widths, one compiled
            # program each. The kernel reads a row's live blocks
            # whatever the table's width: there the table keeps its full
            # width, and the ladder's programs are never built (each
            # would lower the Mosaic kernel again: 0.3 s a shape of
            # set-up on a v5e host, PERF.md §6 PR 27).
            self._table_ladder = not (
                self.pool.k_scale is None
                and reads_pool_in_place(config, self._use_paged_kernel))
            # An expert model's fused step reports its routing behind the
            # step's tokens (``_paged_fused_step``); counters whether or
            # not span tracing is on, like the step counters below.
            self._moe_counters = None
            if config.num_experts > 0:
                reg = get_registry()
                self._moe_counters = (
                    reg.counter(
                        "senweaver_moe_assignments_total",
                        "(token, choice) pairs the fused step's expert "
                        "layers were given, a layer: entries in use x "
                        "experts per token."),
                    reg.counter(
                        "senweaver_moe_experts_touched_total",
                        "Expert banks with at least one token, summed "
                        "over the expert layers and the fused steps."),
                    reg.counter(
                        "senweaver_moe_expert_banks_total",
                        "Expert banks the fused steps could have touched: "
                        "expert layers x experts a step."),
                    reg.gauge(
                        "senweaver_moe_expert_load_max",
                        "Largest number of tokens on one expert in any "
                        "layer of the last fused step."))
                if config.expert_share:
                    self._moe_counters += (
                        reg.counter(
                            "senweaver_moe_zero_picks_total",
                            "Picks that fell on identity experts (no "
                            "bank, no matmul), summed over the expert "
                            "layers and the fused steps."),
                        reg.counter(
                            "senweaver_moe_local_pairs_total",
                            "Picks that fell on an expert this chip "
                            "holds: the pairs its grouped products "
                            "computed."))
            # A delta-rule model's fused step reports its mixers the same
            # way, last behind the step's tokens and log-probs.
            self._kda_meters = None
            if config.kind_layers("kda"):
                self._kda_meters = (
                    get_registry().counter(
                        "senweaver_kda_chunk_entries_total",
                        "Entries of the fused steps that went through the "
                        "delta rule's chunked form (prefill runs of two or "
                        "more entries)."),
                    get_registry().gauge(
                        "senweaver_kda_readout_absmax",
                        "Largest |o| any delta-rule layer read out of its "
                        "state, before the head norm, in the last fused "
                        "step."))
            # A multi-stream model's fused step reports its residual
            # maps the same way, behind the step's log-probs.
            self._mhc_gauge = None
            if config.hc_mult:
                self._mhc_gauge = get_registry().gauge(
                    "senweaver_mhc_sinkhorn_err",
                    "Largest |row sum - 1| or |column sum - 1| of any "
                    "residual mixing map H_res in the last fused step.")
        self._slot_req: List[Optional[_Request]] = [None] * num_slots  # guarded-by: _lock
        # rid holding each slot's KV across turns (hold_slot), or None
        self._slot_held: List[Optional[int]] = [None] * num_slots  # guarded-by: _lock
        # monotonic hold sequence per slot: eviction drops the OLDEST
        self._hold_seq = 0
        self._slot_hold_seq: List[int] = [0] * num_slots  # guarded-by: _lock
        # serving observability (read via stats()): how often the reuse
        # machinery actually engages — the metricsService-style counters
        # for the engine plane (SURVEY.md §5 observability).
        self._stats = {"prefills": 0, "prefill_tokens": 0,  # guarded-by: _lock
                       "batched_prefills": 0, "batched_prefill_slots": 0,
                       "prefix_installs": 0, "prefix_tokens_reused": 0,
                       "prefix_evictions": 0, "prefix_prefills": 0,
                       "prefix_imports": 0, "prefix_exports": 0,
                       "prefix_cache_hits": 0, "prefix_cache_misses": 0,
                       "continuations": 0, "continuation_delta_tokens": 0,
                       "decode_steps": 0, "tokens_emitted": 0,
                       "hold_evictions": 0, "kv_preemptions": 0,
                       "prefix_swap_outs": 0, "prefix_swap_ins": 0,
                       "kv_preemption_storms": 0,
                       "prefix_host_exports": 0,
                       "spec_rounds": 0, "spec_proposed": 0,
                       "spec_accepted": 0, "spec_wasted": 0,
                       "spec_feed_tokens": 0, "spec_rollbacks": 0,
                       "migrations_out": 0, "migrations_in": 0,
                       "group_prefills": 0, "group_forks": 0,
                       "group_prefill_tokens_avoided": 0,
                       "group_degrades": 0, "branch_forks": 0}
        # Live migration (rollout/migration.py): when the fleet
        # attaches a MigrationCoordinator it flips this on, and the
        # pressure ladder OFFERS a capped request for migration (one
        # preempt, rid surfaced via take_pressure_migrations) before
        # falling back to truncate-finish. Default off: standalone
        # engines keep the legacy ladder exactly.
        self.migrate_on_pressure = False
        self._pressure_migrations: List[int] = []  # guarded-by: _lock
        self._migration_offered: set = set()       # guarded-by: _lock
        # Bounded admission (None = legacy unbounded): submit() raises
        # QueueFull past this many QUEUED requests — in-flight slots and
        # continuations (which bypass the queue) don't count.
        self.max_queue = max_queue
        self._queue: Deque[_Request] = deque()  # guarded-by: _lock
        self._requests: Dict[int, _Request] = {}  # guarded-by: _lock
        self._next_rid = 0                      # guarded-by: _lock
        # Group-shared rollout (submit_group): live groups by gid —
        # entries drop once the last follower grafts or the group
        # degrades to unshared prefills.
        self._groups: Dict[int, _GroupShare] = {}  # guarded-by: _lock
        self._next_gid = 0                      # guarded-by: _lock
        # Tokens sampled during prefill, to be surfaced by the next step().
        self._pending_emits: Dict[int, List[int]] = {}  # guarded-by: _lock
        # Prefix cache: shared prompt prefixes (the agent system prompt)
        # prefilled ONCE into a pool-slot-shaped KV buffer and HBM-copied
        # into each slot that reuses them (replacing recompute).
        self._prefixes: Dict[int, tuple] = {}   # guarded-by: _lock
        self._prefix_by_tokens: Dict[tuple, int] = {}  # guarded-by: _lock
        self._next_prefix_id = 0                # guarded-by: _lock
        # HBM budget for registered prefixes: each holds one pool-slot-
        # shaped KV buffer, so COUNT is the natural budget unit. LRU
        # eviction mirrors hold eviction — dropped prefixes silently
        # fall back to a full prefill (and auto_prefix clients
        # re-register on the KeyError).
        self.max_prefixes = max(1, int(max_prefixes))
        self._prefix_last_use: Dict[int, int] = {}  # guarded-by: _lock
        self._prefix_use_seq = 0                # guarded-by: _lock
        # How often each prefix was grafted/exported — the tier-or-
        # evict signal (kv_pressure.should_tier).
        self._prefix_use_count: Dict[int, int] = {}  # guarded-by: _lock
        # Host-RAM tier: pid -> HostPrefix for prefixes whose entry
        # blocks were swapped out (paged entry becomes None). Restored
        # on demand by _restore_prefix via the install scatter.
        self._prefix_host: Dict[int, "HostPrefix"] = {}  # guarded-by: _lock
        # Preemption-storm latch: rids already counted as storm-capped,
        # so the counter fires once per starved request.
        self._storm_rids: set = set()           # guarded-by: _lock
        # Fused speculation (enable_speculation): draft model + its own
        # block pool, in lockstep with the target rows. None = off.
        self._spec: Optional[_SpecState] = None  # guarded-by: _lock
        self._draft_tables: List[List[int]] = []  # guarded-by: _lock
        self._draft_len: List[int] = []         # guarded-by: _lock
        self._draft_pool = None                 # guarded-by: _lock
        # fleet load signal (remaining decode tokens) pushed by the
        # serving replica for the depth controller; None = standalone
        self._spec_fleet_tokens: Optional[float] = None  # guarded-by: _lock
        # Per-step counters, published whether or not span tracing is on
        # (instruments fetched once, as the speculative counters are).
        reg = get_registry()
        self._steps_total = reg.counter(
            "senweaver_engine_decode_steps_total",
            "Pool decode steps executed.")
        self._tokens_total = reg.counter(
            "senweaver_engine_tokens_total",
            "Tokens emitted by the rollout engine.")
        self._kv_blocks_total = reg.counter(
            "senweaver_engine_kv_blocks_read_total",
            "KV pool blocks the fused steps' attention had to cover: for "
            "each run of one row's entries in a step, the blocks up to "
            "its last position. The kernel reads a long run's blocks once "
            "a tile of queries, and the blocks that decode rows share "
            "once for up to 8 of them "
            "(senweaver_engine_kv_blocks_shared_total fewer).")
        self._kv_blocks_shared_total = reg.counter(
            "senweaver_engine_kv_blocks_shared_total",
            "KV pool block reads the fused steps' attention did not make: "
            "where decode rows hold the same physical blocks (a group's "
            "prompt, a grafted prefix, a fork) the kernel reads them once "
            "for up to 8 rows; for each such item, blocks x (rows - 1).")
        self._host_syncs_total = reg.counter(
            "senweaver_engine_step_host_syncs_total",
            "Points of a paged step at which the host blocked on the "
            "device: the fetch of the step's tokens (one a step), and the "
            "proposals' fetch where speculation runs.")
        # the last assembled plan's share of that counter, for the
        # engine.step span's attr kv_blocks
        self._kv_blocks_step = 0                # guarded-by: _lock
        self._head_entries_total = reg.counter(
            "senweaver_engine_head_entries_total",
            "Entries of the fused steps that paid the final norm, the "
            "head and the sampler: a step wider than the rows (prefill "
            "chunks ride it) pays them for one entry a row, a step as wide "
            "as the rows or one with verify entries for every entry.")
        # likewise, for the attr head_entries
        self._head_entries_step = 0             # guarded-by: _lock
        # Is span tracing on? Asked once at the top of a step and true
        # only inside it: the step's span sites and the request.* spans
        # of phases that end in the step read it instead of asking again.
        self._trace_on = False                  # guarded-by: _lock
        # the profiler's clock when the last fused step's results were
        # on the host (0.0: none yet), for the next step's unqueued time
        self._fetched_at = 0.0                  # guarded-by: _lock
        # the fused step that is launched and not collected, if any: set
        # between two step() calls only while the engine runs ahead
        self._flying: Optional[_FlyingStep] = None  # guarded-by: _lock
        self._run_ahead_total = reg.counter(
            "senweaver_engine_steps_run_ahead_total",
            "Fused steps launched before the step before them had its "
            "tokens on the host (the engine was saturated: a request "
            "queued, or no row free).")
        # Many agent loops (subagent threads) drive one engine: all state
        # mutation is serialized; concurrency = slots, not host threads.
        self._lock = threading.RLock()

    def _place_params(self, params: Params) -> Params:
        if self.mesh is None:
            if self._device is None:
                return params
            return jax.device_put(params, self._device)
        from ..parallel.sharding import shard_params
        return shard_params(params, self.mesh)

    def _on_device(self, make):
        """Build engine-owned arrays: directly on this replica's chip
        and committed there when it has one, else wherever JAX puts
        them."""
        if self._device is None:
            return make()
        with jax.default_device(self._device):
            return jax.device_put(make(), self._device)

    def update_params(self, params: Params) -> None:
        """On-policy weight sync: the trainer hands over fresh params
        between rounds (sampler/trainer overlap, SURVEY.md §7). KV cache
        and in-flight requests are untouched — callers should sync at
        round boundaries when slots are idle.

        Registered prefixes are DROPPED: their KV was computed by the
        old policy and would silently mix policies if reused. Clients
        holding a prefix_id get a KeyError on next use and re-register
        (EnginePolicyClient does this automatically).

        If the engine is serving int8-quantized weights
        (``models.quantize``), the trainer's full-precision publish is
        re-quantized here — the actor/learner bridge keeps the serving
        representation stable across weight syncs."""
        from ..models.quantize import is_quantized, quantize_weights_int8
        if is_quantized(self.params) and not is_quantized(params):
            params = quantize_weights_int8(params)
        with self._lock:
            self._drain()
            self.params = self._place_params(params)
            # release_prefix (not .clear()) so the paged layout also
            # drops the prefixes' block refcounts back to the pool.
            for pid in list(self._prefixes):
                self.release_prefix(pid)
            # Held conversation KV is old-policy state for the same
            # reason: continuations after a sync must re-prefill.
            for slot in range(self.num_slots):
                self._drop_hold(slot)
            # The draft is now distilled against a dead policy: stamp
            # it stale and reset the acceptance EMA (mirroring the
            # prefix drop above) — unless the fleet publisher already
            # stamped this publish at begin() time.
            if self._spec is not None:
                if self._spec.publish_pending:
                    self._spec.publish_pending = False
                else:
                    self._spec_mark_stale()

    # -- fused speculative decoding ----------------------------------------

    def enable_speculation(self, draft_params: Params,
                           draft_config: ModelConfig, *,
                           controller=None, depth: Optional[int] = None,
                           num_blocks: Optional[int] = None,
                           version: int = 0) -> None:
        """Turn on fused speculative decoding: a draft model proposes
        up to ``depth`` tokens per row and the target verifies them
        INSIDE the engine's single jitted step, sharing the
        ``step_tokens`` budget with chunked prefill and continuous
        batching. Greedy acceptance (proposal == target argmax) keeps
        outputs byte-identical to non-speculative decode.

        ``controller`` picks the depth per step from load
        (spec_controller.SpecController, the default); ``depth`` pins a
        fixed depth instead. The draft serves from its own block pool
        (``num_blocks``; default sized like the target's) whose
        gauges publish under ``senweaver_spec_draft_kv_*``."""
        from .spec_controller import FixedDepth, SpecController
        refuse(self.config, "enable_speculation", also=draft_config)
        if self.kv_layout != "paged":
            raise ValueError(
                "fused speculation needs the paged KV layout (engine "
                f"fell back to slots: {self.kv_layout_fallback})")
        if self.sample.temperature > 0:
            raise ValueError(
                "fused speculation is greedy-only: construct the "
                "engine with sample.temperature == 0")
        if draft_config.vocab_size != self.config.vocab_size:
            raise ValueError(
                f"draft vocab {draft_config.vocab_size} != target "
                f"vocab {self.config.vocab_size}")
        with self._lock:
            self._drain()      # a verify window needs the values
            if controller is None:
                controller = (FixedDepth(int(depth)) if depth is not None
                              else SpecController())
            bs = self._alloc.block_size
            nb = int(num_blocks) if num_blocks else (
                (self.num_slots + 2) * self._blocks_per_row)
            reg = get_registry()
            self._spec = _SpecState(
                params=draft_params, config=draft_config,
                controller=controller, version=int(version),
                alloc=BlockAllocator(nb, bs,
                                     registry=_DraftMetricsView(reg)),
                depth_gauge=reg.gauge(
                    "senweaver_spec_depth",
                    "Applied speculation depth of the most recently "
                    "stepped engine (0 = speculation off)."),
                accept_gauge=reg.gauge(
                    "senweaver_spec_acceptance_rate",
                    "EMA of the draft-token acceptance rate (reset on "
                    "weight publish)."),
                staleness_gauge=reg.gauge(
                    "senweaver_spec_draft_staleness",
                    "Target weight publishes since the draft was last "
                    "republished (0 = draft tracks the policy)."),
                wasted_total=reg.counter(
                    "senweaver_spec_wasted_draft_tokens_total",
                    "Draft tokens proposed but rejected by "
                    "verification (pure wasted draft+verify work)."))
            self._draft_pool = init_paged_pool(draft_config, nb, bs)
            self._draft_tables = [[] for _ in range(self.num_slots)]
            self._draft_len = [0] * self.num_slots
            self._spec.staleness_gauge.set(0.0)

    def update_draft_params(self, params: Params, *,
                            version: Optional[int] = None) -> None:
        """Install republished draft weights (the online distiller's
        output). Draft rows are dropped — their KV came from the old
        draft — and re-fed from the host token stream by catch-up; the
        acceptance EMA restarts so the gauge reflects the new draft.
        Never blocks on in-flight requests: draft weights cannot
        affect output correctness, only the acceptance rate."""
        with self._lock:
            sp = self._spec
            if sp is None:
                raise RuntimeError("enable_speculation() first")
            sp.params = params
            sp.version = sp.version + 1 if version is None else int(version)
            sp.draft_synced_at = sp.target_version
            for row in range(self.num_slots):
                self._draft_release_row(row)
            self._spec_reset_ema()
            sp.staleness_gauge.set(0.0)

    # -- multi-tenant adapters ----------------------------------------------

    def publish_adapter(self, adapter_id: str, lora, *,
                        version: Optional[int] = None) -> int:
        """No-drain per-tenant adapter publish: hand the pool a new
        host copy under the tenant's monotonic ``adapter_version``.
        Nothing resident changes — in-flight requests finish on the
        binding they acquired at submit, the next submit for this
        tenant uploads the new version on demand. Unlike
        ``update_params`` this drops NO prefixes and stamps NO draft
        stale: the base policy is untouched."""
        if self.adapter_pool is None:
            raise RuntimeError("engine has no adapter_pool")
        with self._lock:
            self._drain()
        return self.adapter_pool.publish(adapter_id, lora, version=version)

    def has_adapter(self, adapter_id: Optional[str]) -> bool:
        """True when a tenant adapter is published (host copy held);
        submit(adapter_id=...) will decode under it."""
        return (self.adapter_pool is not None
                and self.adapter_pool.has(adapter_id))

    def adapter_resident(self, adapter_id: str) -> bool:
        """True when the tenant's CURRENT version occupies a device
        slot (the router's warm-affinity signal)."""
        return (self.adapter_pool is not None
                and self.adapter_pool.resident(adapter_id))

    def adapter_stats(self) -> Dict[str, object]:
        return ({} if self.adapter_pool is None
                else self.adapter_pool.stats())

    def spec_note_publish_begin(self) -> None:
        """Fleet hook (serve/weights.py WeightPublisher.begin): the
        policy is about to change — version-stamp the draft stale and
        reset the acceptance EMA NOW, mirroring how prefix refcounts
        are dropped, instead of trusting stats from a draft that no
        longer matches the policy being rolled out."""
        with self._lock:
            if self._spec is None:
                return
            self._spec.publish_pending = True
            self._spec_mark_stale()

    def _spec_mark_stale(self) -> None:
        # guarded-by: caller
        sp = self._spec
        sp.target_version += 1
        self._spec_reset_ema()
        sp.staleness_gauge.set(sp.target_version - sp.draft_synced_at)

    def _spec_reset_ema(self) -> None:
        # guarded-by: caller
        sp = self._spec
        sp.ema = 0.0
        sp.ema_init = False
        sp.accept_gauge.set(0.0)

    def set_spec_depth(self, depth: int) -> None:
        """Pin the speculation depth (tests, manual override)."""
        with self._lock:
            sp = self._spec
            if sp is None:
                raise RuntimeError("enable_speculation() first")
            if hasattr(sp.controller, "force_depth"):
                sp.controller.force_depth(depth)
            else:
                sp.controller.value = int(depth)

    def note_decode_load(self, remaining_tokens: float) -> None:
        """Serving-replica hook: push the router's remaining-decode-
        token gauge for this replica so the depth controller sees fleet
        load, not just local occupancy."""
        with self._lock:
            self._spec_fleet_tokens = float(remaining_tokens)

    def drain_spec_outcomes(self) -> List[dict]:
        """Hand the buffered verification outcomes (context, the
        target-chosen tokens, accepted count) to the online distiller
        and clear the ring."""
        with self._lock:
            if self._spec is None:
                return []
            out = list(self._spec.outcomes)
            self._spec.outcomes.clear()
            return out

    def spec_stats(self) -> Dict[str, object]:
        """Speculation snapshot: depth, acceptance EMA, staleness,
        proposal/acceptance counters."""
        with self._lock:
            sp = self._spec
            if sp is None:
                return {"enabled": False}
            return {
                "enabled": True,
                "depth": sp.depth_applied,
                "acceptance_ema": sp.ema if sp.ema_init else None,
                "draft_version": sp.version,
                "draft_staleness": sp.target_version - sp.draft_synced_at,
                "rounds": self._stats["spec_rounds"],
                "proposed": self._stats["spec_proposed"],
                "accepted": self._stats["spec_accepted"],
                "wasted_draft_tokens": self._stats["spec_wasted"],
                "draft_feed_tokens": self._stats["spec_feed_tokens"],
                "draft_blocks_free": sp.alloc.free_blocks,
            }

    def spec_check_leaks(self) -> None:
        """Tripwire for tests: after all rows release, the DRAFT pool
        must be fully free too (rollback/preemption/finish paths)."""
        with self._lock:
            if self._spec is not None:
                self._spec.alloc.check_leaks()

    # -- public API ---------------------------------------------------------

    def submit(self, prompt: List[int], *, max_new_tokens: int = 128,
               prefix_id: Optional[int] = None,
               eos_id: Optional[int] = None,
               hold_slot: bool = False,
               continue_from: Optional[int] = None,
               adapter_id: Optional[str] = None) -> int:
        with self._lock:
            return self._submit(prompt, max_new_tokens=max_new_tokens,
                                prefix_id=prefix_id,
                                eos_id=eos_id, hold_slot=hold_slot,
                                continue_from=continue_from,
                                adapter_id=adapter_id)

    def _submit(self, prompt: List[int], *, max_new_tokens: int,
                eos_id: Optional[int],
                prefix_id: Optional[int] = None,
                hold_slot: bool = False,
                continue_from: Optional[int] = None,
                adapter_id: Optional[str] = None) -> int:
        # guarded-by: caller
        if not prompt:
            raise ValueError("empty prompt")
        if continue_from is not None:
            if adapter_id is not None:
                raise ValueError("continuations inherit the held slot's "
                                 "KV; submit adapter decodes fresh")
            return self._submit_continuation(
                prompt, max_new_tokens=max_new_tokens, eos_id=eos_id,
                hold_slot=hold_slot, continue_from=continue_from)
        if adapter_id is not None and self.adapter_pool is None:
            raise ValueError("engine has no adapter_pool")
        # Ring pools accept prompts past the window (chunked prefill
        # keeps only the trailing window, like the model itself);
        # absolute pools must hold the whole prompt. context_bound is
        # exactly that distinction (set at construction).
        if len(prompt) >= self.context_bound:
            raise ValueError(
                f"prompt length {len(prompt)} ≥ engine max_len bound "
                f"{self.context_bound}")
        if (self.max_queue is not None
                and len(self._queue) >= self.max_queue):
            raise QueueFull(
                f"engine queue at max_queue={self.max_queue} "
                f"({len(self._queue)} queued)")
        if prefix_id is not None:
            if prefix_id not in self._prefixes:
                raise KeyError(f"unknown prefix_id {prefix_id}")
            p_tokens = self._prefixes[prefix_id][0]
            if prompt[:len(p_tokens)] != p_tokens:
                raise ValueError(
                    "prompt does not start with the registered prefix "
                    f"(prefix_id {prefix_id}, {len(p_tokens)} tokens)")
        binding = None
        if adapter_id is not None:
            # Resolve the tenant's CURRENT adapter version to a device
            # slot now, and hold it for the request's whole life: a
            # publish that lands mid-decode is picked up only by the
            # next request. Raises KeyError (unpublished tenant) or
            # AdapterPoolFull before any engine state is touched.
            binding = self.adapter_pool.acquire(adapter_id)
        rid = self._next_rid
        self._next_rid += 1
        req = _Request(rid=rid, prompt=list(prompt),
                       max_new_tokens=max_new_tokens,
                       eos_id=self.eos_id if eos_id is None else eos_id,
                       prefix_id=prefix_id, hold_slot=hold_slot,
                       adapter=adapter_id, adapter_binding=binding)
        self._requests[rid] = req
        # Enqueue only — scheduling happens at the next step() boundary,
        # so a BURST of submissions (concurrent agent threads, a GRPO
        # group) lands in the queue together and same-bucket prefills
        # batch into one forward instead of each submit eagerly grabbing
        # a slot solo.
        self._queue.append(req)
        return rid

    def submit_group(self, prompt: List[int], group_size: int, *,
                     max_new_tokens: int = 128,
                     eos_id: Optional[int] = None,
                     adapter_id: Optional[str] = None) -> List[int]:
        """Submit a GRPO group of ``group_size`` decodes of one shared
        ``prompt``, paying exactly ONE prefill. The first member (the
        donor) takes the normal chunked prefill; when it completes —
        before the donor's first sampled token is written, so the table
        is the pure prompt spine — the engine captures a fork of the
        table and each follower grafts it (refcount bump, zero KV bytes
        moved) plus a one-token dropped-write rescore of the last
        prompt token: the same logits the donor sampled its first token
        from, so greedy outputs are bitwise-identical to ``group_size``
        independent submits. Divergence into the shared boundary block
        COW-splits on first write. If the donor dies before capture
        (preemption with emitted tokens, migration release), followers
        degrade to plain unshared prefills — exactness is never traded
        for sharing.

        Followers pin the donor's adapter binding (``retain``), so a
        publish landing mid-group cannot mix policy versions across the
        tree. Requires the paged KV layout. Returns the group's rids,
        donor first."""
        if group_size < 1:
            raise ValueError(f"group_size {group_size} < 1")
        if self.kv_layout != "paged":
            raise ValueError("submit_group requires the paged KV layout")
        with self._lock:
            donor_rid = self._submit(prompt,
                                     max_new_tokens=max_new_tokens,
                                     eos_id=eos_id, adapter_id=adapter_id)
            if group_size == 1:
                return [donor_rid]
            donor = self._requests[donor_rid]
            gid = self._next_gid
            self._next_gid += 1
            group = _GroupShare(gid=gid, prompt_len=len(prompt),
                                donor_rid=donor_rid,
                                pending=group_size - 1)
            self._groups[gid] = group
            donor.group = group
            rids = [donor_rid]
            for _ in range(group_size - 1):
                binding = None
                if donor.adapter_binding is not None:
                    # version-exact pin of the donor's binding: the
                    # donor's ref keeps the slot alive under the engine
                    # lock, so this cannot miss
                    binding = self.adapter_pool.retain(
                        donor.adapter_binding)
                rid = self._next_rid
                self._next_rid += 1
                req = _Request(rid=rid, prompt=list(prompt),
                               max_new_tokens=max_new_tokens,
                               eos_id=(self.eos_id if eos_id is None
                                       else eos_id),
                               adapter=adapter_id,
                               adapter_binding=binding,
                               group=group)
                self._requests[rid] = req
                # NOT queued: a follower waits on the spine capture so
                # its scheduling can never race the donor's prefill
                group.waiters.append(req)
                rids.append(rid)
            return rids

    def fork_request(self, rid: int, *, token: Optional[int] = None,
                     max_new_tokens: Optional[int] = None) -> int:
        """Branch a new decode off an in-flight request's current
        position (tree-structured rollout). The child shares the
        parent's KV spine via a refcounted table fork — zero bytes
        copied; either side's next write into the shared boundary
        block COW-splits it. Two modes:

        * ``token=None`` — sampled continuation: the child adopts the
          parent's last sampled token as its own first emission and
          decodes an alternative suffix after that shared token.
        * ``token=T`` — forced branch: ``T`` REPLACES the parent's
          last sampled token in the child's stream (exploring an
          alternative at a high-entropy position, or injecting a
          tool-call boundary token); the child's first sampled token
          comes from feeding ``T``.

        Either way the child decodes under the parent's PINNED adapter
        version, and its greedy output is bitwise-identical to
        independently submitting the same stream as a fresh prompt.
        When no free row exists the child enters the queue and builds
        its context through the standard recompute path — unshared but
        exact. Raises ``KeyError`` for unknown rids and ``ValueError``
        for requests that are done, paused, or still prefilling."""
        if self.kv_layout != "paged":
            raise ValueError("fork_request requires the paged KV layout")
        refuse(self.config, "fork_request")
        with self._lock:
            self._drain()
            parent = self._requests.get(rid)
            if parent is None:
                raise KeyError(f"unknown rid {rid}")
            if parent.done or parent.paused:
                raise ValueError(
                    f"rid {rid} is not an active decode (done/paused)")
            if rid in self._prefill_jobs or not parent.tokens:
                raise ValueError(f"rid {rid} is still prefilling")
            binding = None
            if parent.adapter_binding is not None:
                binding = self.adapter_pool.retain(parent.adapter_binding)
            budget = (max_new_tokens if max_new_tokens is not None
                      else parent.max_new_tokens)
            crid = self._next_rid
            self._next_rid += 1
            # the shared spine is everything whose k/v is resident:
            # prompt + tokens[:-1] (the last sampled token is written
            # only when it is fed)
            spine = list(parent.prompt) + parent.tokens[:-1]
            if token is None:
                child = _Request(rid=crid, prompt=spine,
                                 max_new_tokens=budget,
                                 eos_id=parent.eos_id,
                                 tokens=[parent.tokens[-1]],
                                 logps=[parent.logps[-1]],
                                 adapter=parent.adapter,
                                 adapter_binding=binding,
                                 parent_rid=rid,
                                 branch_pos=len(parent.tokens),
                                 branch_depth=parent.branch_depth + 1)
            else:
                child = _Request(rid=crid, prompt=spine + [int(token)],
                                 max_new_tokens=budget,
                                 eos_id=parent.eos_id,
                                 adapter=parent.adapter,
                                 adapter_binding=binding,
                                 parent_rid=rid,
                                 branch_pos=len(parent.tokens),
                                 branch_depth=parent.branch_depth + 1)
            self._requests[crid] = child
            row = parent.slot
            free = self._free_slots()
            if row is not None and self._tables[row] and free:
                crow = free[0]
                plen = self._row_len[row]
                nblk = self._alloc.blocks_for(plen)
                child.slot = crow
                self._mark_scheduled(child, crow)
                self._slot_req[crow] = child
                self._tables[crow] = self._alloc.fork(
                    self._tables[row][:nblk])
                self._row_len[crow] = plen
                self._stats["branch_forks"] += 1
                self._stats["group_prefill_tokens_avoided"] += plen
                if token is None:
                    # immediately a decode row: feed the adopted token
                    # next step (its write COW-splits the shared block)
                    self._cur_tok_host[crow] = child.tokens[-1]
                else:
                    # rescore path with REAL writes: feed the forced
                    # token at the branch position and sample from it
                    self._stats["prefill_tokens"] += 1
                    self._prefill_jobs[crid] = _PrefillJob(
                        toks=[int(token)], pos=plen, sample_last=True)
            else:
                # no shareable row: queue the child; tokens non-empty
                # takes the preemption-resume replay, a forced token
                # takes a plain full prefill — both unshared and exact
                self._queue.append(child)
            return crid

    @property
    def has_work(self) -> bool:
        with self._lock:
            return (bool(self._queue) or self._flying is not None
                    or any(r is not None for r in self._slot_req))

    def step(self) -> Dict[int, List[int]]:
        """Advance the pool by one decode step. Returns {rid: [tokens]} for
        every token that came home since the previous step() — including
        tokens sampled during prefill (a request can emit its first token
        and, if it immediately hits eos, never appear in a later step).

        WHEN a token is returned. An under-loaded engine (a free row and
        an empty queue after the step's admissions) returns a step's
        tokens from the call that launched it. A saturated paged engine (a
        request still queued, or no row free) runs ONE STEP AHEAD: the
        call launches step k+1 and returns the tokens of step k, launched
        by the call before it, so the device never waits for the host
        between two steps; no admission is delayed by it, since a later
        arrival would have waited for a row anyway. Either way a token is
        in ``result()`` / ``result_logps()`` exactly when a ``step()``
        has returned it, ``is_done(rid)`` turns true with the request's
        LAST token (an EOS one step after it was sampled, where the next
        entry was already launched: that sample is dropped), and
        ``has_work`` stays true while a step is in flight. Entries that
        read or move a request's tokens or rows (``fork_request``,
        ``pause_request``, checkpoints, ``update_params``, ...) first
        bring the step in flight home; its tokens are returned by the
        next ``step()``."""
        with self._lock:
            return self._step()

    def _step(self) -> Dict[int, List[int]]:
        # guarded-by: caller
        self._trace_on = get_tracer().active()
        span = get_tracer().span if self._trace_on else noop_span
        try:
            if self.kv_layout == "paged":
                return self._step_paged(span)
            self._schedule()
            emitted = self._pending_emits
            self._pending_emits = {}
            active_list = [r is not None for r in self._slot_req]
            if not any(active_list):
                return emitted
            with span("engine.decode_step", active=sum(active_list)):
                active = jnp.asarray(active_list)
                self._key, step_key = jax.random.split(self._key)
                next_tok, logp, self.cache = _pool_decode_step(
                    self.params, self.config, self.cur_tok, active, self.cache,
                    step_key, self.sample)
                self.cur_tok = next_tok
                self._stats["decode_steps"] += 1
                # ONE batched device→host transfer per decode step (the
                # analysis JIT110 budget): three separate np.asarray calls
                # were three blocking roundtrips. device_get still blocks on
                # the device step, so the span spans the actual decode, not
                # just its dispatch.
                toks, logps, lengths = profiled_device_get(
                    (next_tok, logp, self.cache.length),
                    fn="engine.decode_step")
            self._steps_total.inc()
            self._tokens_total.inc(sum(active_list))
            for slot, req in enumerate(self._slot_req):
                if req is None:
                    continue
                tok = int(toks[slot])
                req.tokens.append(tok)
                req.logps.append(float(logps[slot]))
                self._stats["tokens_emitted"] += 1
                emitted.setdefault(req.rid, []).append(tok)
                hit_eos = req.eos_id is not None and tok == req.eos_id
                out_of_budget = len(req.tokens) >= req.max_new_tokens
                out_of_cache = int(lengths[slot]) >= self.context_bound - 1
                if hit_eos or out_of_budget or out_of_cache:
                    self._finish_request(req, slot)
            self._schedule()
            return emitted
        finally:
            self._trace_on = False

    def run(self) -> Dict[int, List[int]]:
        """Drive until all submitted requests finish."""
        while self.has_work:
            self.step()
        return {rid: r.tokens for rid, r in self._requests.items()}

    def stats(self) -> Dict[str, int]:
        """Serving counters: prefill volume, prefix/continuation reuse,
        decode throughput inputs, hold evictions."""
        from ..models.quantize import is_quantized
        with self._lock:
            out = dict(self._stats)
            out["weight_quant"] = int(is_quantized(self.params))
            out["queue_depth"] = len(self._queue)
            out["slots_active"] = sum(r is not None
                                      for r in self._slot_req)
            out["kv_paged"] = int(self.kv_layout == "paged")
            if self.kv_layout == "paged":
                for name, val in self._alloc.counters().items():
                    out[f"kv_{name}"] = val
                out["kv_blocks_total"] = self._alloc.num_blocks
                out["kv_blocks_free"] = self._alloc.free_blocks
                out["kv_pressure"] = (self._alloc.used_blocks
                                      / self._alloc.num_blocks)
                out["kv_swapped_blocks"] = sum(
                    hp.num_blocks for hp in self._prefix_host.values())
                out["kv_dtype"] = self.engine_config.kv_dtype
                out["kv_bytes_per_block"] = self._alloc.bytes_per_block
                out["kv_bytes_device"] = self._alloc.used_bytes
                out["kv_bytes_host"] = self._alloc.swapped_bytes
                if self.pool is not None and self.pool.rows is not None:
                    out["state_bytes_device"] = self.pool.rows.nbytes
            if self.adapter_pool is not None:
                ap = self.adapter_pool.stats()
                out["adapters_published"] = len(ap["adapters"])
                out["adapter_installs"] = int(ap["installs"])
                out["adapter_evictions"] = int(ap["evictions"])
            return out

    @property
    def kv_pressure(self) -> float:
        """Pool utilization 0..1 — the proactive-backpressure signal
        the admission/autoscale planes watermark on (0.0 for the slot
        layout, which has no block pool to exhaust)."""
        if self.kv_layout != "paged":
            return 0.0
        return self._alloc.used_blocks / self._alloc.num_blocks

    @property
    def queue_depth(self) -> int:
        """Requests submitted but not yet scheduled into a slot."""
        with self._lock:
            return len(self._queue)

    def result(self, rid: int) -> List[int]:
        with self._lock:
            return list(self._requests[rid].tokens)

    def result_logps(self, rid: int) -> List[float]:
        """Behavior log-prob of each emitted token (parallel to
        result()): the model's own log p(token) captured at sample time
        — what GRPO's importance ratio divides by, with no second
        forward pass (ops/sampling.py sampled_logprob)."""
        with self._lock:
            return list(self._requests[rid].logps)

    def is_done(self, rid: int) -> bool:
        with self._lock:
            return self._requests[rid].done

    def _submit_continuation(self, prompt: List[int], *,
                             max_new_tokens: int, eos_id: Optional[int],
                             hold_slot: bool, continue_from: int) -> int:
        # guarded-by: caller
        """Multi-turn continuation: append only the NEW tokens to a held
        slot's resident KV (hold_slot=True on the previous turn), instead
        of re-prefilling the whole conversation. ``prompt`` is the FULL
        token stream; the engine verifies it extends the held history
        byte-exactly and prefills just the delta."""
        prev = self._requests.get(continue_from)
        if prev is None or not prev.done or prev.held_history is None:
            raise ValueError(
                f"continue_from={continue_from}: request not finished "
                f"while holding a slot")
        try:
            slot = self._slot_held.index(continue_from)
        except ValueError:
            raise ValueError(
                f"continue_from={continue_from}: slot already released")
        history = prev.held_history
        if (len(prompt) <= len(history)
                or prompt[:len(history)] != history):
            raise ValueError(
                "prompt does not extend the held conversation "
                f"({len(history)} resident tokens); release the slot "
                "and submit a full prefill instead")
        if len(prompt) >= self.context_bound:
            raise ValueError(
                f"prompt length {len(prompt)} ≥ engine max_len bound "
                f"{self.context_bound}")
        delta = prompt[len(history):]

        rid = self._next_rid
        self._next_rid += 1
        req = _Request(rid=rid, prompt=list(prompt),
                       max_new_tokens=max_new_tokens,
                       eos_id=self.eos_id if eos_id is None else eos_id,
                       hold_slot=hold_slot, slot=slot)
        # The held KV was computed under prev's adapter binding, so the
        # continuation inherits it (ownership transfers; released when
        # this request finishes without holding).
        req.adapter = prev.adapter
        req.adapter_binding = prev.adapter_binding
        prev.adapter_binding = None
        self._requests[rid] = req
        self._mark_scheduled(req, slot)
        self._slot_held[slot] = None
        self._slot_req[slot] = req
        if self.kv_layout == "paged":
            # The held row's blocks stay resident (row_len ==
            # len(history)); the delta becomes a chunked-prefill job
            # fed by the next fused steps. A boundary block the
            # original turn shared with a prefix COW-splits on the
            # delta's first write, not here.
            self._prefill_jobs[rid] = _PrefillJob(
                toks=list(delta), pos=len(history), sample_last=True)
            self._stats["continuations"] += 1
            self._stats["continuation_delta_tokens"] += len(delta)
            return rid
        slot_arr = jnp.asarray(slot, jnp.int32)
        with get_tracer().span("engine.prefill_continuation", slot=slot,
                               delta_tokens=len(delta)):
            last_logits = self._prefill_chunks(slot_arr, delta,
                                               fresh_first=False)
        self._stats["continuations"] += 1
        self._stats["continuation_delta_tokens"] += len(delta)
        self._emit_first_token(req, slot, last_logits)
        return rid

    def release_slot(self, rid: int) -> None:
        """Free a slot held by a finished hold_slot request."""
        with self._lock:
            self._drain()
            try:
                slot = self._slot_held.index(rid)
            except ValueError:
                return
            self._drop_hold(slot)
            self._schedule()

    def register_prefix(self, tokens: List[int]) -> int:
        """Prefill ``tokens`` once; return a prefix_id for submit().

        The prefix KV lives in a one-slot buffer shaped like the pool;
        submit(prompt, prefix_id=...) requires the prompt to START with
        exactly these tokens and prefills only the suffix. The big win
        is the agent system prompt: every rollout episode shares it, and
        a slot install becomes one HBM copy instead of a prefill pass.

        Cost model: the suffix prefills through the exact-size chunk
        ladder (each distinct chunk shape compiles once), so the win
        materializes when the prefix is long relative to the suffix —
        exactly the agent-loop shape (multi-k-token system prompt,
        short user turn). Content-identical registrations dedup to one
        buffer; ``update_params`` invalidates all prefixes (their KV
        belongs to the old policy) and auto_prefix clients re-register.
        """
        refuse(self.config, "register_prefix")
        with self._lock:
            if not tokens:
                raise ValueError("empty prefix")
            if len(tokens) >= self.max_len:
                raise ValueError(
                    f"prefix length {len(tokens)} ≥ pool capacity "
                    f"{self.max_len}")
            key = tuple(tokens)
            if key in self._prefix_by_tokens:   # content dedup: many
                pid = self._prefix_by_tokens[key]    # clients, one buffer
                self._touch_prefix(pid)
                return pid
            # HBM budget: evict the least-recently-used prefix before
            # allocating another slot-shaped buffer.
            while len(self._prefixes) >= self.max_prefixes:
                lru = min(self._prefix_last_use,
                          key=self._prefix_last_use.get)
                self.release_prefix(lru)
                self._stats["prefix_evictions"] += 1
            from .sampler import prefill        # jitted, donates cache
            sub = init_kv_cache(self.config, 1, self.max_len)
            last = None
            pos = 0
            for i, size in enumerate(_chunk_sizes(len(tokens),
                                                  self.max_len)):
                chunk = jnp.asarray(tokens[pos:pos + size], jnp.int32)
                last, sub = prefill(self.params, self.config,
                                    chunk[None, :], sub,
                                    fresh_cache=(i == 0))
                pos += size
            pid = self._next_prefix_id
            self._next_prefix_id += 1
            if self.kv_layout == "paged":
                # Paged prefixes live in the shared pool: scatter the
                # freshly-prefilled buffer into dedicated blocks once;
                # every consumer then grafts the table (refcount bump,
                # zero bytes) instead of HBM-copying a slot buffer.
                nblk = self._alloc.blocks_for(len(tokens))
                blocks = self._alloc_blocks_evicting(nblk)
                k_buf, v_buf = self._blockify(sub, nblk)
                self.pool = install_blocks(self.pool, k_buf, v_buf,
                                           jnp.asarray(blocks, jnp.int32))
                entry = blocks
            else:
                # the B=1 cache IS the pool's slot layout (L, 1, cap, ..)
                entry = sub
            self._prefixes[pid] = (list(tokens), entry,
                                   jax.device_get(last[0]))
            self._prefix_by_tokens[key] = pid
            self._touch_prefix(pid)
            self._stats["prefix_prefills"] += 1
            return pid

    def export_prefix(self, prefix_id: int):
        """Hand out a registered prefix for installation into ANOTHER
        engine (serve/prefix_store.py one-prefill broadcast): returns
        ``(tokens, kv, last_logits)`` — the token list, the one-slot
        KVCache buffer, and the final-token logits as a host (V,) array.

        The KV buffer is shared by reference, which is safe: JAX arrays
        are immutable and the jitted paths donate only the POOL cache,
        never a prefix buffer. Raises KeyError if the prefix was evicted
        or invalidated (callers re-register, same as submit())."""
        refuse(self.config, "export_prefix")
        with self._lock:
            self._drain()      # the gather reads the pool
            if prefix_id not in self._prefixes:
                raise KeyError(f"unknown prefix_id {prefix_id}")
            tokens, entry, last = self._prefixes[prefix_id]
            self._touch_prefix(prefix_id)
            self._stats["prefix_exports"] += 1
            if self.kv_layout == "paged":
                if entry is None:
                    # host-tiered: serve the broadcast straight from
                    # the host buffers — late replicas backfill from
                    # RAM without forcing a swap-in on the donor (the
                    # receiving engine's install scatter ingests host
                    # numpy directly)
                    entry = self._export_host(prefix_id)
                    self._stats["prefix_host_exports"] += 1
                else:
                    # The fleet contract speaks contiguous one-slot
                    # buffers (slot engines import them as-is; paged
                    # peers re-blockify): gather the table into that
                    # layout.
                    entry = self._export_blocks(tokens, entry)
            return list(tokens), entry, last

    def prefix_in_host_tier(self, prefix_id: int) -> bool:
        """True when the prefix's KV currently lives only in the
        host-RAM tier (serve/prefix_store.py counts backfills served
        from host separately from device exports)."""
        with self._lock:
            return prefix_id in self._prefix_host

    def import_prefix(self, tokens: List[int], kv: KVCache,
                      last_logits=None) -> int:
        """Adopt a prefix KV computed by a peer engine — the receive side
        of the fleet broadcast. Instead of re-prefilling ``tokens``, the
        peer's one-slot buffer is device-placed (``jax.device_put`` is a
        device-to-device copy when source and target differ, a no-op
        aliasing when they share a device) and registered in this
        engine's prefix cache under a fresh prefix_id, LRU-accounted
        exactly like a locally-prefilled one.

        The buffer must match this pool's slot layout bit-for-bit —
        shape (L, 1, max_len, Hkv, Dh), dtype, quantization flavor, and
        recorded length == len(tokens) — anything else raises
        :class:`PrefixImportError` (serving attention over a mismatched
        buffer would be silent garbage). ``last_logits`` is the donor's
        final-token logits; without it, a zero-suffix submit recomputes
        the last position (one-token prefill) on first use."""
        refuse(self.config, "import_prefix")
        with self._lock:
            if not tokens:
                raise ValueError("empty prefix")
            if len(tokens) >= self.max_len:
                raise ValueError(
                    f"prefix length {len(tokens)} ≥ pool capacity "
                    f"{self.max_len}")
            key = tuple(tokens)
            if key in self._prefix_by_tokens:   # already resident here
                pid = self._prefix_by_tokens[key]
                self._touch_prefix(pid)
                return pid
            if self.kv_layout == "paged":
                L = self.pool.num_layers
                hkv, dh = self.pool.k.shape[3], self.pool.k.shape[4]
                # Two acceptable flavors on a UNIFORMLY quantized pool:
                # a matching quantized buffer (int8/fp8 payload + scales
                # splice straight in — the cross-replica backfill stays
                # quantized end to end) or a full-width one (quantized
                # at install time by the write scatter). Mixed-ladder
                # pools (bf16 prefix layers) only take full width —
                # a foreign uniform payload can't express the prefix —
                # so a quantized broadcast is dequantized at the door
                # (payload × scale, one elementwise pass) rather than
                # bounced; a heterogeneous-ladder fleet still shares
                # prefixes, it just pays full width on the wide rungs.
                if (kv.quantized and self.pool.quantized
                        and self.pool.hi_layers == 0):
                    pool_dtype = self.pool.k.dtype
                    pool_quant = True
                else:
                    pool_dtype = self.config.dtype
                    pool_quant = False
                    if kv.quantized:
                        kv = KVCache(
                            k=(kv.k.astype(jnp.float32)
                               * kv.k_scale[..., None]).astype(pool_dtype),
                            v=(kv.v.astype(jnp.float32)
                               * kv.v_scale[..., None]).astype(pool_dtype),
                            length=kv.length)
            else:
                L, _, _, hkv, dh = self.cache.k.shape
                pool_dtype = self.cache.k.dtype
                pool_quant = bool(self.cache.quantized)
            want = (L, 1, self.max_len, hkv, dh)
            if tuple(kv.k.shape) != want or tuple(kv.v.shape) != want:
                raise PrefixImportError(
                    f"prefix KV shape {tuple(kv.k.shape)}/"
                    f"{tuple(kv.v.shape)} != pool slot layout {want}")
            if kv.k.dtype != pool_dtype:
                raise PrefixImportError(
                    f"prefix KV dtype {kv.k.dtype} != pool dtype "
                    f"{pool_dtype}")
            if bool(kv.quantized) != pool_quant:
                raise PrefixImportError(
                    f"prefix quantization {kv.quantized} != pool "
                    f"quantization {pool_quant}")
            if pool_quant:
                want_s = (L, 1, self.max_len, hkv)
                if (tuple(kv.k_scale.shape) != want_s
                        or tuple(kv.v_scale.shape) != want_s):
                    raise PrefixImportError(
                        f"prefix KV scale shape {tuple(kv.k_scale.shape)}/"
                        f"{tuple(kv.v_scale.shape)} != {want_s}")
            # One batched admission sync: the declared-length check and
            # the first-token logits come over in a single transfer.
            got = jax.device_get(
                (kv.length,) if last_logits is None
                else (kv.length, last_logits))
            kv_len = int(got[0])
            last = got[1] if len(got) > 1 else None
            if kv_len != len(tokens):
                raise PrefixImportError(
                    f"prefix KV records length {kv_len} but "
                    f"{len(tokens)} tokens were declared")
            while len(self._prefixes) >= self.max_prefixes:
                lru = min(self._prefix_last_use,
                          key=self._prefix_last_use.get)
                self.release_prefix(lru)
                self._stats["prefix_evictions"] += 1
            if self.kv_layout == "paged":
                # The one unavoidable buffer copy of the paged prefix
                # plane: foreign KV must be scattered into pool blocks
                # ONCE per import; every request install after that is
                # a graft. Counted so the fleet test can assert the
                # zero-copy-per-request property from the counters.
                nblk = self._alloc.blocks_for(len(tokens))
                blocks = self._alloc_blocks_evicting(nblk)
                idx = jnp.asarray(blocks, jnp.int32)
                if pool_quant:
                    # quantized splice: int8/fp8 bytes + scales land in
                    # the pool as-is — no dequant/requant round trip
                    payload = BlockPayload(
                        k=self._blockify_arr(kv.k, nblk),
                        v=self._blockify_arr(kv.v, nblk),
                        k_scale=self._blockify_arr(kv.k_scale, nblk),
                        v_scale=self._blockify_arr(kv.v_scale, nblk))
                    self.pool = install_blocks_quant(self.pool, payload,
                                                     idx)
                else:
                    k_buf, v_buf = self._blockify(kv, nblk)
                    self.pool = install_blocks(self.pool, k_buf, v_buf,
                                               idx)
                self._alloc.count_install_copy(nblk)
                placed = blocks
            elif self.mesh is not None:
                # TP pool: place like any fresh array; jit resharding
                # handles the KV-spec layout at first install.
                placed = jax.device_put(kv)
            else:
                dev = next(iter(self.cache.k.devices()))
                placed = jax.device_put(kv, dev)
            pid = self._next_prefix_id
            self._next_prefix_id += 1
            self._prefixes[pid] = (list(tokens), placed, last)
            self._prefix_by_tokens[key] = pid
            self._touch_prefix(pid)
            self._stats["prefix_imports"] += 1
            return pid

    def _touch_prefix(self, pid: int) -> None:
        # guarded-by: caller
        self._prefix_use_seq += 1
        self._prefix_last_use[pid] = self._prefix_use_seq
        self._prefix_use_count[pid] = (
            self._prefix_use_count.get(pid, 0) + 1)

    def release_prefix(self, prefix_id: int) -> None:
        """Free a registered prefix's KV buffer. In the paged layout
        this drops the prefix's reference on each of its blocks;
        consumers that grafted the table keep their own references, so
        an in-flight request survives its donor's eviction (blocks
        return to the pool only when the LAST table drops them). A
        host-tiered prefix (blocks swapped out) just drops its host
        buffers — there are no pool references left to release."""
        with self._lock:
            entry = self._prefixes.pop(prefix_id, None)
            self._prefix_last_use.pop(prefix_id, None)
            self._prefix_use_count.pop(prefix_id, None)
            hp = self._prefix_host.pop(prefix_id, None)
            if entry is not None:
                self._prefix_by_tokens.pop(tuple(entry[0]), None)
                if self.kv_layout == "paged" and entry[1] is not None:
                    self._alloc.release(entry[1])
            if hp is not None:
                self._alloc.set_swapped_blocks(
                    self._swapped_blocks_total())

    # -- live migration (rollout/migration.py) -------------------------------

    def checkpoint_request(self, rid: int, *, pause: bool = True):
        """Snapshot an in-flight request into a portable
        :class:`~.migration.DecodeCheckpoint` (non-destructive; the
        request is left PAUSED so its state cannot advance between
        snapshot and the coordinator's release/resume). The freeze +
        snapshot happen atomically under the engine lock."""
        refuse(self.config, "checkpoint_request")
        from .migration import checkpoint_from_engine
        with self._lock:
            self._drain()
            return checkpoint_from_engine(self, rid, pause=pause)

    def restore_request(self, ckpt) -> int:
        """Install a peer's checkpoint under a fresh rid and return
        it: one install scatter when a free row + matching block
        layout exist, otherwise a front-of-queue requeue that resumes
        through the preemption-recompute replay. Either way the
        resumed output is token-exact versus never migrating."""
        refuse(self.config, "restore_request")
        from .migration import restore_into_engine
        with self._lock:
            self._drain()
            rid = restore_into_engine(self, ckpt)
            self._schedule()
            return rid

    def release_request(self, rid: int) -> bool:
        """Forget a migrated-away request (post-ack cleanup): drop its
        row/blocks, adapter binding, queue entry, and pending emits.
        Idempotent — unknown rids return False."""
        from .migration import release_from_engine
        with self._lock:
            self._drain()
            req = self._requests.get(rid)
            if req is not None:
                # a group donor migrated away before the spine capture
                # cannot deliver it here — its followers prefill
                # locally; a released follower surrenders its graft
                # slot so the retained spine cannot strand
                self._group_degrade_if_uncaptured(req)
                self._group_forget_follower(req)
            out = release_from_engine(self, rid)
            self._schedule()
            return out

    def pause_request(self, rid: int) -> None:
        """Freeze one request (migration prepare): skipped by the step
        assembler, the speculation planner, and the scheduler."""
        from .migration import set_paused
        with self._lock:
            self._drain()
            set_paused(self, rid, True)

    def resume_request(self, rid: int) -> None:
        """Unfreeze a paused request (migration aborted — the fence
        tripped, the install failed, or the target died): it resumes
        decoding HERE, token-exactly, as if never frozen."""
        from .migration import set_paused
        with self._lock:
            set_paused(self, rid, False)

    def take_pressure_migrations(self) -> List[int]:
        """Drain the rids the pressure ladder offered for migration
        instead of truncate-finishing (paused, blocks already freed).
        The fleet coordinator either migrates each or resumes it
        locally; a resumed request that caps out again truncates."""
        with self._lock:
            if self._pressure_migrations:   # a fleet asks every tick
                self._drain()
            out = [rid for rid in self._pressure_migrations
                   if rid in self._requests
                   and not self._requests[rid].done]
            self._pressure_migrations = []
            return out

    # -- internals ----------------------------------------------------------

    def _flush_state_copies(self, span) -> int:
        # guarded-by: caller
        """Dispatch the row copies asked for since the last fused step
        (a group's snapshot, its followers' installs), in order, each ONE
        shape-static program over all layers: before the step that reads
        or overwrites any of the rows. Returns how many."""
        copies, self._state_copies = self._state_copies, []
        for src, dst in copies:
            with span("engine.state_copy", src=src, dst=dst):
                self.pool = copy_state_rows(
                    self.pool, np.asarray([src], np.int32),
                    np.asarray([dst], np.int32))
        if copies:
            self._state_copies_total.inc(len(copies))
        return len(copies)

    def _group_release_fork(self, g: "_GroupShare") -> None:
        # guarded-by: caller
        """The last follower has its fork: drop the engine's retained
        spine (the followers' own forks keep the blocks alive) and free
        the snapshot row, whose copies are already queued in order."""
        if g.spine is not None:
            self._alloc.release(g.spine)
            g.spine = None
        if g.state_row is not None:
            self._state_snap_free.append(g.state_row)
            g.state_row = None
        self._groups.pop(g.gid, None)

    def _group_capture(self, req: "_Request", row: int) -> None:
        # guarded-by: caller
        """The donor's row holds exactly what the group shares: capture
        an engine-retained fork of its table (released when the last
        follower grafts) and wake the waiters. A model with recurrent
        state also snapshots the row's state; with no snapshot row free
        the group degrades to unshared prefills, as with a dead donor."""
        g = req.group
        if self.config.ssm:
            if not self._state_snap_free:
                self._group_degrade_if_uncaptured(req)
                return
            g.state_row = self._state_snap_free.pop()
            self._state_copies.append((row, g.state_row))
        g.spine = self._alloc.fork(self._tables[row])
        g.spine_len = self._row_len[row]
        self._stats["group_prefills"] += 1
        for w in g.waiters:
            if not w.done:
                self._queue.append(w)
        g.waiters = []

    def _emit_first_token(self, req: "_Request", slot: int,
                          last_logits) -> None:
        # guarded-by: caller
        """Sample and book-keep a request's first token after prefill
        (used by both fresh prefills and turn continuations)."""
        self._key, tok_key = jax.random.split(self._key)
        tok0 = sample_token(last_logits[None, :], tok_key,
                            temperature=self.sample.temperature,
                            top_k=self.sample.top_k,
                            top_p=self.sample.top_p)
        # One batched sync for (token, logprob) — not an int() plus a
        # separate float(), which would be two device roundtrips.
        tok0_h, logp0_h = jax.device_get(
            (tok0[0], sampled_logprob(last_logits, tok0[0])))
        tok0_i = int(tok0_h)
        req.tokens.append(tok0_i)
        req.logps.append(float(logp0_h))
        self._stats["tokens_emitted"] += 1
        self._tokens_total.inc()
        self._mark_first_token(req)
        self._pending_emits.setdefault(req.rid, []).append(tok0_i)
        if self.kv_layout == "paged":
            self._cur_tok_host[slot] = tok0_i
        else:
            self.cur_tok = self.cur_tok.at[slot].set(tok0_i)
        if ((req.eos_id is not None and tok0_i == req.eos_id)
                or req.max_new_tokens <= 1):
            self._finish_request(req, slot)

    def _mark_scheduled(self, req: "_Request", row: int) -> None:
        # guarded-by: caller
        """A request was placed in a row. The first placement is the end
        of its queue phase (a preempted request keeps it); one that
        arrives with tokens in hand (a fork's adopted token, a migrated
        request) has no prefill phase."""
        req.row = row
        if req.t_scheduled_ns is not None:
            return
        req.t_scheduled_ns = time.perf_counter_ns()
        if req.tokens:
            req.t_first_token_ns = req.t_scheduled_ns
        if self._trace_on:
            self._record_phase(req, "request.queue", req.t_submit_ns,
                               req.t_scheduled_ns)

    def _mark_first_token(self, req: "_Request") -> None:
        # guarded-by: caller
        if req.t_first_token_ns is not None:
            return
        req.t_first_token_ns = time.perf_counter_ns()
        if self._trace_on and req.t_scheduled_ns is not None:
            self._record_phase(req, "request.prefill", req.t_scheduled_ns,
                               req.t_first_token_ns)

    def _record_phase(self, req: "_Request", name: str, start_ns: int,
                      end_ns: int) -> None:
        # guarded-by: caller
        """One phase of a request's life as a span; the three of one
        request share ``trace_id``. Only phases that end inside a step
        that found tracing on are recorded."""
        g = req.group
        get_tracer().record_span(
            name, start_ns, end_ns, trace_id=f"req-{req.rid}",
            rid=req.rid, row=req.row, prompt_tokens=len(req.prompt),
            output_tokens=len(req.tokens),
            # a submit_group follower that took the donor's spine
            grafted=(g is not None and req.rid != g.donor_rid
                     and not g.degraded),
            prefix_hit=req.prefix_id is not None,
            preempts=req.preempt_count)

    def _group_degrade_if_uncaptured(self, req: "_Request") -> None:
        # guarded-by: caller
        """Group donor died before the spine was captured (preemption
        with emitted tokens, storm truncate-finish, migration release):
        enqueue the waiting followers as plain unshared prefills.
        Slower, never inexact. No-op for non-donors and for groups
        whose spine already landed (followers hold their own forks)."""
        g = req.group
        if (g is None or req.rid != g.donor_rid or g.degraded
                or g.spine is not None or not g.waiters):
            return
        g.degraded = True
        self._stats["group_degrades"] += 1
        for w in g.waiters:
            if not w.done:
                self._queue.append(w)
        g.waiters = []
        self._groups.pop(g.gid, None)

    def _group_forget_follower(self, req: "_Request") -> None:
        # guarded-by: caller
        """A follower left the group without grafting (migration
        release while queued/waiting): count its graft slot down so
        the engine-retained spine fork cannot be stranded, and drop it
        from the waiter list so a later capture cannot re-enqueue a
        dead request."""
        g = req.group
        if g is None or req.rid == g.donor_rid or req.group_grafted:
            return
        req.group_grafted = True
        g.waiters = [w for w in g.waiters if w.rid != req.rid]
        g.pending -= 1
        if g.pending <= 0:
            self._group_release_fork(g)

    def _finish_request(self, req: "_Request", slot: int) -> None:
        # guarded-by: caller
        """End a request whose last token is on the host: it leaves its
        row (held or freed) and is done."""
        self._close_request(req, slot)
        self._mark_done(req)

    def _close_request(self, req: "_Request", slot: int) -> None:
        # guarded-by: caller
        """The structural half of a request's end: its last token is
        launched (or known), so no further entry is planned for it — it
        leaves its row, which is held for a continuation or freed. It is
        DONE only when that token is delivered (:meth:`_mark_done`)."""
        req.closing = True
        self._group_degrade_if_uncaptured(req)
        self._group_forget_follower(req)
        self._slot_req[slot] = None
        if self.kv_layout == "paged":
            self._prefill_jobs.pop(req.rid, None)
        # Held conversations keep their adapter binding (the resident
        # KV was computed under it; a continuation inherits it).
        if (req.adapter_binding is not None and not req.hold_slot
                and self.adapter_pool is not None):
            self.adapter_pool.release(req.adapter_binding)
            req.adapter_binding = None
        if req.hold_slot:
            self._slot_held[slot] = req.rid
            self._hold_seq += 1
            self._slot_hold_seq[slot] = self._hold_seq
        else:
            req.slot = None
            if self.kv_layout == "paged":
                self._release_row(slot)

    def _mark_done(self, req: "_Request") -> None:
        # guarded-by: caller
        """The value half: the request's last token has been delivered."""
        req.done = True
        req.t_done_ns = time.perf_counter_ns()
        if self._trace_on and req.t_first_token_ns is not None:
            self._record_phase(req, "request.decode",
                               req.t_first_token_ns, req.t_done_ns)
        if req.hold_slot and req.slot is not None:
            # The LAST sampled token's k/v is not yet written (tokens
            # are fed on the step AFTER they are sampled), so the
            # resident history excludes it — a continuation's delta
            # naturally begins with that token. (An EOS met while one
            # more entry was in flight did write it: the row's length
            # steps back over it, and the delta's first write lands on
            # it. Only k/v can be written twice: where the row has
            # recurrent state no such entry is ever launched,
            # ``_state_needs_values``.)
            req.held_history = list(req.prompt) + req.tokens[:-1]
            if self.kv_layout == "paged":
                self._row_len[req.slot] = len(req.held_history)

    def _drop_hold(self, slot: int) -> None:
        # guarded-by: caller
        """Invalidate a held conversation and free its slot."""
        rid = self._slot_held[slot]
        if rid is None:
            return
        prev = self._requests[rid]
        prev.held_history = None
        prev.slot = None
        if prev.adapter_binding is not None and self.adapter_pool is not None:
            self.adapter_pool.release(prev.adapter_binding)
            prev.adapter_binding = None
        self._slot_held[slot] = None
        if self.kv_layout == "paged":
            self._release_row(slot)

    def _prefill_chunks(self, slot_arr, tokens: List[int],
                        fresh_first: bool):
        """Exact-size chunk chain into a slot at its current length;
        returns the last chunk's final-token logits."""
        last_logits = None
        pos = 0
        for i, size in enumerate(_chunk_sizes(len(tokens), self.max_len)):
            chunk = jnp.asarray(tokens[pos:pos + size], jnp.int32)[None, :]
            last_logits, self.cache = _prefill_slot_chunk(
                self.params, self.config, chunk, self.cache, slot_arr,
                fresh=(fresh_first and i == 0))
            pos += size
        return last_logits

    def _free_slots(self) -> List[int]:
        return [s for s in range(self.num_slots)
                if self._slot_req[s] is None and self._slot_held[s] is None]

    def _schedule(self) -> None:
        # guarded-by: caller
        """Prefill queued requests into free slots (continuous batching).

        Same-bucket fresh prefills at the queue front batch into ONE
        forward (``_prefill_slots_batched``); prefix installs, ring
        long-prompt chains, and odd-bucket singles take the single-slot
        paths. FIFO order is preserved — batching only groups a
        CONSECUTIVE run of compatible requests."""
        if self.kv_layout == "paged":
            return self._schedule_paged()
        if self._queue and all(self._slot_held[s] is not None
                               for s in range(self.num_slots)):
            # Every slot held (none active) with work queued: nothing
            # will ever free a slot, so run()/chat() would LIVELOCK.
            # Held KV is droppable cache — evict the oldest hold; its
            # conversation falls back to a full prefill on its next
            # turn. (A merely ACTIVE slot needs no eviction: it frees
            # itself when its request finishes.)
            oldest = min(range(self.num_slots),
                         key=lambda s: self._slot_hold_seq[s])
            self._drop_hold(oldest)
            self._stats["hold_evictions"] += 1
        while self._queue:
            free = self._free_slots()
            if not free:
                return
            req = self._queue[0]
            if (req.prefix_id is not None
                    and req.prefix_id not in self._prefixes):
                # The prefix was invalidated while this request sat in
                # the queue (update_params drops old-policy KV, the LRU
                # budget evicts). Fall back to a full prefill — raising
                # here would corrupt an unrelated caller's step().
                req.prefix_id = None
                self._stats["prefix_cache_misses"] += 1
            if req.prefix_id is not None or (
                    len(req.prompt) >= self.max_len and self._ring):
                self._queue.popleft()
                self._schedule_single(req, free[0])
                continue
            # Gather the batchable run: consecutive fresh prefills
            # sharing this request's bucket, one per free slot.
            bucket = min(_bucket(len(req.prompt)), self.max_len)
            group = [req]
            for r in list(self._queue)[1:len(free)]:
                if (r.prefix_id is None
                        and not (len(r.prompt) >= self.max_len
                                 and self._ring)
                        and min(_bucket(len(r.prompt)), self.max_len)
                        == bucket):
                    group.append(r)
                else:
                    break
            for _ in group:
                self._queue.popleft()
            if len(group) == 1:
                self._schedule_single(group[0], free[0])
            else:
                self._schedule_batch(group, free[:len(group)], bucket)

    def _schedule_single(self, req: "_Request", slot: int) -> None:
        with get_tracer().span("engine.prefill", slot=slot,
                               tokens=len(req.prompt),
                               prefix=req.prefix_id is not None):
            self._schedule_single_impl(req, slot)

    def _schedule_single_impl(self, req: "_Request", slot: int) -> None:
        # guarded-by: caller
        req.slot = slot
        self._mark_scheduled(req, slot)
        self._slot_req[slot] = req
        true_len = len(req.prompt)
        self._stats["prefills"] += 1
        if req.prefix_id is not None:
            # Shared-prefix path: HBM-copy the cached prefix KV into
            # the slot, then exact-chunk-prefill only the suffix.
            p_tokens, p_cache, p_last = self._prefixes[req.prefix_id]
            self._touch_prefix(req.prefix_id)
            slot_arr = jnp.asarray(slot, jnp.int32)
            self.cache = _install_prefix(self.cache, p_cache, slot_arr)
            self._stats["prefix_installs"] += 1
            self._stats["prefix_cache_hits"] += 1
            self._stats["prefix_tokens_reused"] += len(p_tokens)
            suffix = req.prompt[len(p_tokens):]
            # prefill_tokens = tokens actually COMPUTED (the prefix
            # itself arrived by HBM copy)
            self._stats["prefill_tokens"] += len(suffix)
            if suffix:
                last_logits = self._prefill_chunks(slot_arr, suffix,
                                                   fresh_first=False)
            elif p_last is not None:
                last_logits = jnp.asarray(p_last)
            else:
                # Imported prefix without donor logits: re-feed the last
                # prefix token at its own position (rewind the cursor by
                # one) to recompute the final logits — a 1-token prefill,
                # not a full pass; the rewritten k/v is bit-identical.
                self.cache = self.cache._replace(
                    length=self.cache.length.at[slot].set(true_len - 1))
                last_logits = self._prefill_chunks(
                    slot_arr, [req.prompt[-1]], fresh_first=False)
                self._stats["prefill_tokens"] += 1
        elif true_len >= self.max_len and self._ring:
            # Long prompt on a ring pool: exact-size chunk chain
            # (see _prefill_slot_chunk). Reset the slot's stale
            # length first — the chain reads it as its write cursor.
            self.cache = self.cache._replace(
                length=self.cache.length.at[slot].set(0))
            slot_arr = jnp.asarray(slot, jnp.int32)
            last_logits = self._prefill_chunks(slot_arr, req.prompt,
                                               fresh_first=True)
            self._stats["prefill_tokens"] += true_len
        else:
            bucket = min(_bucket(true_len), self.max_len)
            padded = req.prompt + [0] * (bucket - true_len)
            tokens = jnp.asarray(padded, jnp.int32)[None, :]
            last_logits, self.cache = _prefill_slot(
                self.params, self.config, tokens,
                jnp.asarray(true_len, jnp.int32), self.cache,
                jnp.asarray(slot, jnp.int32))
            self._stats["prefill_tokens"] += true_len
        self._emit_first_token(req, slot, last_logits)

    def _schedule_batch(self, group: List["_Request"], slots: List[int],
                        bucket: int) -> None:
        """One batched forward prefills the whole group. The batch is
        padded to a power of two by REPEATING row 0 (duplicate slot +
        identical data = benign scatter), bounding the compile set to
        (log2 slots × bucket ladder) shapes."""
        with get_tracer().span("engine.prefill_batch", slots=len(group),
                               bucket=bucket):
            self._schedule_batch_impl(group, slots, bucket)

    def _schedule_batch_impl(self, group: List["_Request"],
                             slots: List[int], bucket: int) -> None:
        # guarded-by: caller
        n = len(group)
        n_pad = 1
        while n_pad < n:
            n_pad *= 2
        rows, lens, slot_ids = [], [], []
        for req, slot in zip(group, slots):
            req.slot = slot
            self._mark_scheduled(req, slot)
            self._slot_req[slot] = req
            rows.append(req.prompt + [0] * (bucket - len(req.prompt)))
            lens.append(len(req.prompt))
            slot_ids.append(slot)
            self._stats["prefills"] += 1
            self._stats["prefill_tokens"] += len(req.prompt)
        for _ in range(n_pad - n):
            rows.append(rows[0])
            lens.append(lens[0])
            slot_ids.append(slot_ids[0])
        last, self.cache = _prefill_slots_batched(
            self.params, self.config,
            jnp.asarray(rows, jnp.int32),
            jnp.asarray(lens, jnp.int32), self.cache,
            jnp.asarray(slot_ids, jnp.int32))
        self._stats["batched_prefills"] += 1
        self._stats["batched_prefill_slots"] += n
        for i, (req, slot) in enumerate(zip(group, slots)):
            self._emit_first_token(req, slot, last[i])

    # -- paged layout (rollout/paged_kv.py block pool) -----------------------

    def _release_row(self, row: int) -> None:
        # guarded-by: caller
        """Drop the row's reference on every block of its table (and
        the draft pool's mirror row when speculation is on)."""
        if self._tables[row]:
            self._alloc.release(self._tables[row])
        self._tables[row] = []
        self._row_len[row] = 0
        if self._spec is not None:
            self._draft_release_row(row)

    # -- fused-speculation internals ----------------------------------------

    def _draft_release_row(self, row: int) -> None:
        # guarded-by: caller
        sp = self._spec
        if sp is None or not self._draft_tables:
            return
        if self._draft_tables[row]:
            sp.alloc.release(self._draft_tables[row])
        self._draft_tables[row] = []
        self._draft_len[row] = 0

    def _draft_ensure_range(self, row: int, pos: int, n: int) -> bool:
        # guarded-by: caller
        """Make positions ``pos .. pos+n-1`` writable in the draft
        row's table (append-only — the draft pool has no sharing, so
        no COW). Returns False on draft-pool exhaustion: the row
        simply doesn't speculate this step (never preempts — the
        draft pool must not disturb target scheduling)."""
        sp = self._spec
        bs = sp.alloc.block_size
        table = self._draft_tables[row]
        for j in range(n):
            lb = (pos + j) // bs
            if lb < len(table):
                continue
            if lb > len(table):
                return False
            try:
                table.append(sp.alloc.alloc(1)[0])
            except BlocksExhausted:
                return False
        return True

    def _draft_tables_device(self) -> np.ndarray:
        # guarded-by: caller
        """Dense draft block-table array, power-of-two bucketed like
        :meth:`_tables_device` (host numpy for the same one-transfer
        ingest reason)."""
        widest = max((len(t) for t in self._draft_tables), default=0)
        mb = 1
        while mb < widest:
            mb *= 2
        mb = min(self._blocks_per_row, mb)
        arr = np.zeros((self.num_slots, mb), np.int32)
        for s, tbl in enumerate(self._draft_tables):
            if tbl:
                arr[s, :len(tbl)] = tbl
        return arr

    def _spec_observe_depth(self) -> int:
        # guarded-by: caller
        """Feed the controller this step's load signals; returns the
        applied (hysteresis-filtered) ladder depth."""
        sp = self._spec
        active = sum(r is not None for r in self._slot_req)
        occupancy = min(1.0, (active + len(self._queue)) / self.num_slots)
        kv_pressure = self._alloc.used_blocks / self._alloc.num_blocks
        k = sp.controller.observe(
            occupancy=occupancy, kv_pressure=kv_pressure,
            decode_tokens=self._spec_fleet_tokens,
            num_slots=self.num_slots)
        sp.depth_applied = k
        sp.depth_gauge.set(k)
        return k

    def _spec_catch_up(self) -> None:
        # guarded-by: caller
        """Replay already-known tokens into draft rows that fell behind
        the target (fresh prefill, continuation delta, preemption
        resume, rollback, depth-0 stretch), under the step-token
        budget. One draft forward for all lagging rows."""
        sp = self._spec
        bs = sp.alloc.block_size
        budget = self._step_tokens
        entries = []                    # (tok, row, pos, wb, wo)
        advanced = []                   # (row, n)
        for row in range(self.num_slots):
            req = self._slot_req[row]
            if req is None or req.paused or req.rid in self._prefill_jobs:
                continue
            if budget <= 0:
                break
            gap = self._row_len[row] - self._draft_len[row]
            if gap < 0:
                # target rolled behind the draft outside a spec round
                # (shouldn't happen): resync by dropping the draft row
                self._draft_release_row(row)
                gap = self._row_len[row]
            if gap == 0:
                continue
            stream = req.prompt + req.tokens[:-1]
            start = self._draft_len[row]
            n = min(gap, budget)
            if not self._draft_ensure_range(row, start, n):
                continue
            table = self._draft_tables[row]
            for j in range(n):
                p = start + j
                entries.append((stream[p], row, p, table[p // bs],
                                p % bs))
            advanced.append((row, n))
            budget -= n
        if not entries:
            return
        t = _bucket(len(entries), max(16, self.num_slots))
        nb = sp.alloc.num_blocks
        toks = np.zeros((t,), np.int32)
        rows = np.zeros((t,), np.int32)
        pos = np.zeros((t,), np.int32)
        wb = np.full((t,), nb, np.int32)    # sentinel-padded
        wo = np.zeros((t,), np.int32)
        for i, (tok, r, p, b, o) in enumerate(entries):
            toks[i], rows[i], pos[i], wb[i], wo[i] = tok, r, p, b, o
        self._draft_pool = _draft_feed_step(
            sp.params, sp.config, toks, self._draft_tables_device(),
            rows, pos, wb, wo, self._draft_pool,
            self._use_paged_kernel)
        for row, n in advanced:
            self._draft_len[row] += n
        self._stats["spec_feed_tokens"] += len(entries)

    def _spec_begin_step(self) -> tuple:
        # guarded-by: caller
        """Pre-step speculation phase: observe load → depth, catch the
        draft cache up, then run the on-device draft proposal scan for
        every row in lockstep. Returns ``(depth, {row: proposals})``
        — empty plan when speculation is off or depth is 0."""
        sp = self._spec
        if sp is None:
            return 0, {}
        k = self._spec_observe_depth()
        self._spec_catch_up()
        if k <= 0:
            return 0, {}
        rows = []
        for row in range(self.num_slots):
            req = self._slot_req[row]
            if (req is None or req.paused
                    or req.rid in self._prefill_jobs
                    or not req.tokens):
                continue
            p = self._row_len[row]
            if self._draft_len[row] != p:
                continue        # draft not in lockstep yet
            if p + k > self.max_len or p + 1 >= self.context_bound - 1:
                continue        # would finish this step anyway
            if not self._draft_ensure_range(row, p, k):
                continue        # draft pool pressure: skip, don't block
            rows.append(row)
        if not rows:
            return k, {}
        r = self.num_slots
        cur = np.zeros((r,), np.int32)
        base = np.zeros((r,), np.int32)
        mask = np.zeros((r,), bool)
        for row in rows:
            cur[row] = self._cur_tok_host[row]
            base[row] = self._row_len[row]
            mask[row] = True
        props_dev, self._draft_pool = _draft_propose_scan(
            sp.params, sp.config, cur, base, mask,
            self._draft_tables_device(), self._draft_pool,
            k, self._use_paged_kernel)
        props = profiled_device_get(props_dev, fn="engine.spec_propose")
        self._host_syncs_total.inc()
        plan = {}
        for row in rows:
            plan[row] = [int(x) for x in props[row]]
            self._draft_len[row] = self._row_len[row] + k
        return k, plan

    def _spec_rollback(self, row: int, new_len: int) -> None:
        # guarded-by: caller
        """Truncate both the target row and its draft mirror to the
        verified prefix: blocks past ``blocks_for(new_len)`` go back to
        their pools (the PagedSeqKV.truncate contract — stale entries
        in the kept partial block sit at positions the causal mask
        never reads and the next write overwrites)."""
        keep = self._alloc.blocks_for(new_len)
        table = self._tables[row]
        if len(table) > keep:
            self._alloc.release(table[keep:])
            del table[keep:]
        self._row_len[row] = new_len
        sp = self._spec
        dtable = self._draft_tables[row]
        dkeep = sp.alloc.blocks_for(new_len)
        if len(dtable) > dkeep:
            sp.alloc.release(dtable[dkeep:])
            del dtable[dkeep:]
        self._draft_len[row] = min(self._draft_len[row], new_len)
        self._stats["spec_rollbacks"] += 1

    def _preempt_row(self, row: int) -> None:
        # guarded-by: caller
        """Preemption-by-recomputation (the BlocksExhausted response):
        release the row's blocks and requeue its request at the FRONT.
        Rescheduling re-prefills prompt + already-emitted tokens and
        resumes decode from the last sampled token — the request loses
        work, never tokens."""
        req = self._slot_req[row]
        self._slot_req[row] = None
        req.slot = None
        # prefix reuse was already credited once; a resume re-prefills
        # the full stream rather than double-counting an install
        req.prefix_id = None
        self._prefill_jobs.pop(req.rid, None)
        self._release_row(row)
        self._queue.appendleft(req)
        self._stats["kv_preemptions"] += 1
        req.preempt_count += 1
        if req.tokens:
            # an uncaptured group donor preempted AFTER emitting tokens
            # resumes through the recompute replay and can never again
            # present a pure-prompt spine — degrade the followers now.
            # A donor preempted mid-prefill (no tokens) simply redoes
            # the full prefill and the capture still fires.
            self._group_degrade_if_uncaptured(req)
        if (req.preempt_count >= self.engine_config.max_preempts
                and req.rid not in self._storm_rids):
            # starvation latch: this request is now non-preemptible
            # (counted once per rid, not once per further near-miss)
            self._storm_rids.add(req.rid)
            self._stats["kv_preemption_storms"] += 1
            if self._storm_total is not None:
                self._storm_total.inc()

    def _prefix_candidates(self) -> List[PrefixCandidate]:
        # guarded-by: caller
        """Resident (device-backed) prefix entries as scoring
        candidates; swapped-out entries hold no pool blocks and cannot
        be victims."""
        out = []
        for pid, (tokens, blocks, _last) in self._prefixes.items():
            if blocks is None:
                continue
            consumers = max(
                (self._alloc.refcount(b) - 1 for b in blocks),
                default=0)
            out.append(PrefixCandidate(
                pid=pid, num_tokens=len(tokens),
                num_blocks=len(blocks), consumers=consumers,
                last_use=self._prefix_last_use.get(pid, 0),
                use_count=self._prefix_use_count.get(pid, 0)))
        return out

    def _evict_or_tier_prefix(self) -> bool:
        # guarded-by: caller
        """Scored prefix reclamation (kv_pressure.pick_victim): drop or
        host-tier the entry the pool can best afford to lose. Unshared
        prefixes always go before shared ones, cold-and-cheap before
        hot-and-expensive; warm/shared victims swap to the host tier
        (restorable) while cold unshared ones are simply evicted."""
        victim = pick_victim(self._prefix_candidates(),
                             self._prefix_use_seq)
        if victim is None:
            return False
        if should_tier(victim, host_tier=self.engine_config.host_tier):
            try:
                self._swap_out_prefix(victim.pid)
                return True
            except Exception:
                # torn swap (chaos, device loss): the entry is still
                # fully resident — fall through to plain eviction so
                # reclamation still makes progress
                pass
        self.release_prefix(victim.pid)
        self._stats["prefix_evictions"] += 1
        self._alloc.count_eviction()
        return True

    def _swapped_blocks_total(self) -> int:
        # guarded-by: caller
        return sum(hp.num_blocks for hp in self._prefix_host.values())

    def _swap_out_prefix(self, pid: int) -> None:
        # guarded-by: caller
        """Tier a resident prefix to host RAM: gather its blocks into
        contiguous buffers, land them on the host, and only then flip
        the bookkeeping (entry -> None, blocks released). Any failure
        before the flip leaves the prefix fully resident and the pool
        untouched — a swap can tear but never half-apply."""
        tokens, blocks, last = self._prefixes[pid]
        nblk = len(blocks)
        # gather_blocks_quant keeps the pool's storage flavor: on a
        # quantized ladder the host tier holds int8/fp8 bytes + scales
        # (half the host RAM per block), on bf16 the full payload —
        # and the layout is already blockified, so no host reshape.
        payload = gather_blocks_quant(self.pool,
                                      np.asarray(blocks, np.int32))
        host = profiled_device_get(payload, "engine.swap_out")
        np_of = lambda a: None if a is None else np.asarray(a)
        # -- point of no return: pure host bookkeeping from here ------
        self._prefix_host[pid] = HostPrefix(
            k=np_of(host.k), v=np_of(host.v),
            num_tokens=len(tokens),
            k_scale=np_of(host.k_scale), v_scale=np_of(host.v_scale),
            k_hi=np_of(host.k_hi), v_hi=np_of(host.v_hi))
        self._prefixes[pid] = (tokens, None, last)
        self._alloc.release(blocks)
        self._alloc.count_swap_out(nblk)
        self._alloc.set_swapped_blocks(self._swapped_blocks_total())
        self._stats["prefix_swap_outs"] += 1

    def _restore_prefix(self, pid: int) -> bool:
        # guarded-by: caller
        """Swap a host-tiered prefix back into the pool (the same
        install scatter the cross-engine import uses — host numpy
        feeds pjit directly). False when the pool cannot grant the
        blocks even after reclamation: the caller degrades to a full
        prefill and the host copy is KEPT for the next attempt."""
        tokens, _blocks, last = self._prefixes[pid]
        hp = self._prefix_host[pid]
        nblk = hp.num_blocks
        try:
            blocks = self._alloc_blocks_evicting(nblk)
        except BlocksExhausted:
            return False
        try:
            # same storage flavor back in: quantized payloads splice
            # without a requant, full-width ones scatter as before
            self.pool = install_blocks_quant(
                self.pool,
                BlockPayload(k=hp.k, v=hp.v, k_scale=hp.k_scale,
                             v_scale=hp.v_scale, k_hi=hp.k_hi,
                             v_hi=hp.v_hi),
                np.asarray(blocks, np.int32))
        except Exception:
            self._alloc.release(blocks)
            raise
        self._prefixes[pid] = (tokens, blocks, last)
        del self._prefix_host[pid]
        self._alloc.count_swap_in(nblk)
        self._alloc.set_swapped_blocks(self._swapped_blocks_total())
        self._stats["prefix_swap_ins"] += 1
        return True

    def _reclaim_blocks(self, row: int, committed,
                        allow_preempt: bool = True) -> bool:
        # guarded-by: caller
        """Free pool capacity, cheapest casualty first — the pressure
        ladder (docs/serving.md "KV memory hierarchy"): held
        conversations (pure cache — the continuation re-prefills), then
        scored prefix eviction/tiering (kv_pressure: cold unshared
        entries drop, warm/shared ones swap to host), then the youngest
        other active request still under the preemption cap (recompute
        preemption). Returns False when nothing further can be
        reclaimed for ``row`` — including after preempting ``row``
        itself."""
        held = [s for s in range(self.num_slots)
                if self._slot_held[s] is not None]
        if held:
            oldest = min(held, key=lambda s: self._slot_hold_seq[s])
            self._drop_hold(oldest)
            self._stats["hold_evictions"] += 1
            return True
        if self._evict_or_tier_prefix():
            return True
        if not allow_preempt:
            return False
        cap = self.engine_config.max_preempts
        victims = [s for s in range(self.num_slots)
                   if s != row and s not in committed
                   and self._slot_req[s] is not None
                   and self._slot_req[s].preempt_count < cap]
        if victims:
            youngest = max(victims, key=lambda s: self._slot_req[s].rid)
            self._preempt_row(youngest)
            return True
        if row >= 0 and self._slot_req[row] is not None:
            req = self._slot_req[row]
            need = self._alloc.blocks_for(
                len(req.prompt) + len(req.tokens) + 1)
            if need > self._alloc.num_blocks or req.preempt_count >= cap:
                # could never fit even with the pool to itself, or the
                # request already burned its preemption budget and
                # every other row is capped too: truncate-finish
                # instead of requeue-livelock — the request completes
                # (short), it is never lost. With a fleet migrator
                # attached, offer the request for migration FIRST
                # (one preempt frees the blocks, tokens survive); a
                # second trip through this branch — no replica took
                # it — truncates as before, so no livelock.
                if (self.migrate_on_pressure
                        and req.rid not in self._migration_offered):
                    self._migration_offered.add(req.rid)
                    self._pressure_migrations.append(req.rid)
                    # paused so the scheduler cannot bounce it straight
                    # back into the freed row (and re-cap it) before
                    # the coordinator's pump decides; the coordinator
                    # resumes it if no replica has headroom
                    req.paused = True
                    self._preempt_row(row)
                else:
                    self._finish_request(req, row)
            else:
                self._preempt_row(row)
        return False

    def _ensure_block(self, row: int, pos: int, committed) -> int:
        # guarded-by: caller
        """Make position ``pos`` writable in ``row``'s table: append a
        fresh block at the table boundary, or COW-split a shared block
        on the first divergent write into it. Reclaims capacity on
        exhaustion; raises :class:`_RowPreempted` once ``row`` itself
        had to yield its blocks."""
        table = self._tables[row]
        lb = pos // self._alloc.block_size
        while True:
            try:
                if lb == len(table):
                    table.append(self._alloc.alloc(1)[0])
                elif lb < len(table):
                    tgt = self._alloc.cow_target(table[lb])
                    if tgt is not None:
                        # the donor's refcount keeps the source block
                        # alive; ours moved to `tgt` inside cow_target.
                        # Nothing waits for the copy: the pool's
                        # donation orders it behind the step in flight
                        # and before the next, and its two ids enter as
                        # host arrays (`idle_gap_copies_ms` reads the
                        # span)
                        with (get_tracer().span if self._trace_on
                              else noop_span)("engine.cow_copy"):
                            self.pool = copy_blocks(
                                self.pool,
                                np.asarray([table[lb]], np.int32),
                                np.asarray([tgt], np.int32))
                        table[lb] = tgt
                else:
                    raise AssertionError(
                        f"non-contiguous write: pos {pos} into table "
                        f"of {len(table)} block(s)")
                return table[lb]
            except BlocksExhausted:
                if self._flying is not None:
                    raise _PlanWaits()
                if not self._reclaim_blocks(row, committed):
                    raise _RowPreempted(row)

    def _alloc_blocks_evicting(self, n: int) -> List[int]:
        # guarded-by: caller
        """Allocate ``n`` blocks for a prefix install, evicting holds
        and LRU prefixes (never preempting active requests) until the
        pool can grant them."""
        while True:
            try:
                return self._alloc.alloc(n)
            except BlocksExhausted:
                if not self._reclaim_blocks(-1, frozenset(),
                                            allow_preempt=False):
                    raise

    def _blockify_arr(self, a, nblk: int):
        # guarded-by: caller
        """Reshape one contiguous one-slot tensor (L, 1, cap, ...) into
        the block layout (L, nblk, block_size, ...) — payloads and the
        quantized ladder's (L, 1, cap, Hkv) scale planes alike."""
        bs = self._alloc.block_size
        need = nblk * bs
        a = a[:, 0]
        if need > a.shape[1]:
            pad = [(0, 0), (0, need - a.shape[1])] + \
                [(0, 0)] * (a.ndim - 2)
            a = jnp.pad(a, pad)
        return a[:, :need].reshape(a.shape[0], nblk, bs, *a.shape[2:])

    def _blockify(self, kv: KVCache, nblk: int):
        # guarded-by: caller
        """Reshape a contiguous one-slot buffer (L, 1, cap, Hkv, Dh)
        into (L, nblk, block_size, Hkv, Dh) for install_blocks."""
        return (self._blockify_arr(kv.k, nblk),
                self._blockify_arr(kv.v, nblk))

    @staticmethod
    def _unblockify_to(a, cap: int, xp=jnp):
        """Block layout (L, nblk, bs, ...) -> one-slot (L, 1, cap, ...),
        zero-padded past the gathered blocks."""
        l, nblk, bs = a.shape[:3]
        a = a.reshape(l, nblk * bs, *a.shape[3:])
        if a.shape[1] < cap:
            pad = [(0, 0), (0, cap - a.shape[1])] + \
                [(0, 0)] * (a.ndim - 2)
            a = xp.pad(a, pad)
        return a[:, None, :cap]

    def _export_blocks(self, tokens: List[int],
                       blocks: List[int]) -> KVCache:
        # guarded-by: caller
        """Materialize a prefix's block table as the contiguous
        one-slot buffer the fleet prefix contract speaks. Uniformly
        quantized pools export the QUANTIZED flavor (payload + scales —
        the broadcast ships half the bytes and a matching peer splices
        it without a requant); mixed-ladder pools dequantize to the
        model dtype, which any peer can ingest."""
        idx = jnp.asarray(blocks, jnp.int32)
        cap = self.max_len
        length = jnp.full((1,), len(tokens), jnp.int32)
        pool = self.pool
        if pool.quantized and pool.hi_layers == 0:
            p = gather_blocks_quant(pool, idx)
            return KVCache(
                k=self._unblockify_to(p.k, cap),
                v=self._unblockify_to(p.v, cap),
                k_scale=self._unblockify_to(p.k_scale, cap),
                v_scale=self._unblockify_to(p.v_scale, cap),
                length=length)
        k, v = gather_blocks(pool, idx, dtype=self.config.dtype)
        if k.shape[1] < cap:
            pad = ((0, 0), (0, cap - k.shape[1]), (0, 0), (0, 0))
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        return KVCache(k=k[:, None, :cap], v=v[:, None, :cap],
                       length=length)

    def _export_host(self, pid: int) -> KVCache:
        # guarded-by: caller
        """Fleet-contract one-slot buffer built from a host-tiered
        prefix — all numpy, zero device traffic on the donor; the
        importer's install scatter ingests host arrays directly.
        Quantized host payloads export quantized (same flavor rule as
        _export_blocks); mixed-ladder ones dequantize on the host."""
        hp = self._prefix_host[pid]
        cap = self.max_len
        length = np.full((1,), hp.num_tokens, np.int32)
        if hp.k_scale is not None and hp.k_hi is None:
            return KVCache(
                k=self._unblockify_to(hp.k, cap, xp=np),
                v=self._unblockify_to(hp.v, cap, xp=np),
                k_scale=self._unblockify_to(hp.k_scale, cap, xp=np),
                v_scale=self._unblockify_to(hp.v_scale, cap, xp=np),
                length=length)
        k, v = dequantize_host(hp, np.dtype(self.config.dtype))
        if k.shape[1] < cap:
            pad = ((0, 0), (0, cap - k.shape[1]), (0, 0), (0, 0))
            k, v = np.pad(k, pad), np.pad(v, pad)
        return KVCache(k=k[:, None, :cap], v=v[:, None, :cap],
                       length=length)

    def _tables_device(self) -> jnp.ndarray:
        # guarded-by: caller
        """Dense (num_slots, mb) int32 block-table array for the fused
        step. On the gather path it is trimmed to the widest resident
        table and bucketed to a power of two (a bounded compile ladder,
        like _chunk_sizes, so at most log2(blocks_per_row) shapes
        compile): attention cost then tracks the LONGEST live sequence
        instead of always paying the full blocks_per_row width. Where
        the kernel reads the pool in place the width costs nothing and
        stays blocks_per_row: one shape. Unused entries hold 0 and are
        never read past each row's fill level (the validity mask in
        the gather path, the blocks an item covers in the kernel)."""
        mb = self._blocks_per_row
        if self._table_ladder:
            widest = max((len(t) for t in self._tables), default=0)
            mb = 1
            while mb < widest:
                mb *= 2
            mb = min(self._blocks_per_row, mb)
        arr = np.zeros((self.num_slots, mb), np.int32)
        for s, tbl in enumerate(self._tables):
            if tbl:
                arr[s, :len(tbl)] = tbl
        # returned as a HOST array on purpose: pjit ingests numpy
        # directly (one C++ transfer), where a jnp.asarray here would
        # pay full op-by-op dispatch before the step even launches
        return arr

    def _schedule_paged(self) -> None:
        # guarded-by: caller
        """Paged admission: assign queued requests to free rows and
        turn their prompts into chunked-prefill jobs. No device work
        happens here — prefix installs are table grafts, and all
        prefill compute is interleaved into the fused steps under the
        step-token budget. Paused (migration-frozen) requests are
        lifted out of the queue for the duration and put back at the
        front — they keep their place but cannot be scheduled."""
        paused = None
        if any(r.paused for r in self._queue):
            paused = [r for r in self._queue if r.paused]
            self._queue = deque(r for r in self._queue if not r.paused)
        try:
            self._schedule_paged_inner()
        finally:
            if paused:
                self._queue.extendleft(reversed(paused))

    def _schedule_paged_inner(self) -> None:
        # guarded-by: caller
        if self._queue and all(self._slot_held[s] is not None
                               for s in range(self.num_slots)):
            # same livelock guard as the slot scheduler: all slots held
            # and work queued — evict the oldest hold
            oldest = min(range(self.num_slots),
                         key=lambda s: self._slot_hold_seq[s])
            self._drop_hold(oldest)
            self._stats["hold_evictions"] += 1
        while self._queue:
            free = self._free_slots()
            if not free:
                return
            req = self._queue[0]
            if (req.prefix_id is not None
                    and req.prefix_id not in self._prefixes):
                req.prefix_id = None
                self._stats["prefix_cache_misses"] += 1
            self._queue.popleft()
            self._schedule_paged_row(req, free[0])

    def _schedule_paged_row(self, req: "_Request", row: int) -> None:
        # guarded-by: caller
        req.slot = row
        self._slot_req[row] = req
        self._mark_scheduled(req, row)
        g = req.group
        group_graft = (g is not None and g.spine is not None
                       and not g.degraded and req.rid != g.donor_rid
                       and not req.tokens)
        if not group_graft:
            self._stats["prefills"] += 1
        if req.adapter_binding is not None and req.prefix_id is not None:
            # Shared prefixes are BASE-policy KV: any adapter target
            # perturbs the residual stream and hence every later
            # layer's k/v, so grafting a base-computed prefix under an
            # adapter would silently mix policies. Exactness first —
            # adapter rows take the full adapter-aware prefill.
            req.prefix_id = None
            self._stats["prefix_cache_misses"] += 1
        if group_graft:
            # Group-shared rollout: graft the donor's pure-prompt spine
            # (refcount bump, zero KV bytes moved) and rescore ONLY the
            # last prompt token with writes DROPPED — its k/v is
            # already resident, and these are the same logits the donor
            # sampled its first token from, so greedy decode is
            # bitwise-identical to an unshared prefill. The follower's
            # first real write COW-splits the shared boundary block.
            self._tables[row] = self._alloc.fork(g.spine)
            self._row_len[row] = g.spine_len
            self._stats["group_forks"] += 1
            self._stats["prefill_tokens"] += 1
            if g.state_row is None:
                self._stats["group_prefill_tokens_avoided"] += (
                    g.spine_len - 1)
                self._prefill_jobs[req.rid] = _PrefillJob(
                    toks=[req.prompt[-1]], pos=g.spine_len - 1,
                    sample_last=True, drop_writes=True)
            else:
                # Recurrent state: the spine ends BEFORE the prompt's
                # last token. Install a copy of the state there and feed
                # that token with its write kept (a shared boundary block
                # COW-splits now, one token earlier than above): the
                # donor's own flow, from a bit-equal state.
                self._state_copies.append((g.state_row, row))
                self._stats["group_prefill_tokens_avoided"] += g.spine_len
                self._prefill_jobs[req.rid] = _PrefillJob(
                    toks=[req.prompt[-1]], pos=g.spine_len,
                    sample_last=True)
            if not req.group_grafted:
                # a preempted-then-rescheduled follower re-grafts but
                # must not double-decrement the pending count
                req.group_grafted = True
                g.pending -= 1
                if g.pending <= 0 and g.spine is not None:
                    # last follower grafted
                    self._group_release_fork(g)
            return
        if req.tokens:
            # preemption resume: recompute prompt + everything emitted
            # except the last token (whose k/v is written when it is
            # fed), then decode from that token — no re-emission
            stream = list(req.prompt) + req.tokens[:-1]
            self._stats["prefill_tokens"] += len(stream)
            self._prefill_jobs[req.rid] = _PrefillJob(
                toks=stream, pos=0, sample_last=False,
                after_tok=req.tokens[-1])
            return
        if req.prefix_id is not None:
            p_tokens, p_blocks, p_last = self._prefixes[req.prefix_id]
            if p_blocks is None:
                # host-tiered prefix: swap it back in on demand; if the
                # pool cannot grant the blocks even after reclamation,
                # degrade to a full prefill (the host copy is kept for
                # the next consumer)
                if self._restore_prefix(req.prefix_id):
                    p_tokens, p_blocks, p_last = (
                        self._prefixes[req.prefix_id])
                else:
                    req.prefix_id = None
                    self._stats["prefix_cache_misses"] += 1
                    self._stats["prefill_tokens"] += len(req.prompt)
                    self._prefill_jobs[req.rid] = _PrefillJob(
                        toks=list(req.prompt), pos=0, sample_last=True)
                    return
            self._touch_prefix(req.prefix_id)
            # THE graft: the install is a refcount bump on the prefix's
            # blocks — zero KV bytes move (vs the slot layout's
            # _install_prefix HBM copy). Divergence into the shared
            # boundary block COW-splits at first write.
            self._tables[row] = self._alloc.fork(p_blocks)
            self._row_len[row] = len(p_tokens)
            self._stats["prefix_installs"] += 1
            self._stats["prefix_cache_hits"] += 1
            self._stats["prefix_tokens_reused"] += len(p_tokens)
            suffix = req.prompt[len(p_tokens):]
            self._stats["prefill_tokens"] += len(suffix)
            if suffix:
                self._prefill_jobs[req.rid] = _PrefillJob(
                    toks=list(suffix), pos=len(p_tokens),
                    sample_last=True)
            elif p_last is not None:
                self._emit_first_token(req, row, jnp.asarray(p_last))
            else:
                # imported prefix without donor logits: rescore the
                # last prefix token in place with writes DROPPED — the
                # k/v is already resident, and rewriting it would
                # COW-split a shared boundary block for nothing
                self._stats["prefill_tokens"] += 1
                self._prefill_jobs[req.rid] = _PrefillJob(
                    toks=[req.prompt[-1]], pos=len(p_tokens) - 1,
                    sample_last=True, drop_writes=True)
            return
        self._stats["prefill_tokens"] += len(req.prompt)
        if (self.config.ssm and g is not None and req.rid == g.donor_rid
                and g.spine is None and not g.degraded and g.waiters):
            if len(req.prompt) < 2:
                # nothing before the last token to share
                self._group_degrade_if_uncaptured(req)
            else:
                # the donor of a group over recurrent state: prefill up
                # to the last token, fork there, then feed it
                self._prefill_jobs[req.rid] = _PrefillJob(
                    toks=list(req.prompt[:-1]), pos=0, sample_last=False,
                    fork_then=req.prompt[-1])
                return
        self._prefill_jobs[req.rid] = _PrefillJob(
            toks=list(req.prompt), pos=0, sample_last=True)

    def _assemble_paged_plan(self, spec_plan=None, depth: int = 0):
        # guarded-by: caller
        """Build the flat token batch for one fused step: one decode
        entry per active row — or ``depth`` verify entries for rows
        with draft proposals (``spec_plan``) — then exact-size
        chunked-prefill segments round-robined in row order under the
        remaining token budget. Returns None when there is nothing to
        run."""
        nb = self._alloc.num_blocks
        bs = self._alloc.block_size
        toks_l: List[int] = []
        rows_l: List[int] = []
        pos_l: List[int] = []
        wb_l: List[int] = []
        wo_l: List[int] = []
        feed_l: List[int] = []     # FEED_TAKE | FEED_PUT, an entry
        decode_rows = []           # (entry_idx, row, req)
        spec_rows = []             # (entry_idx, row, req, proposals, start)
        job_rows = []              # (row, req, job, n, last_idx, wrote)
        committed: set = set()
        kv_blocks = 0              # blocks the step's attention covers
        for row in range(self.num_slots):
            req = self._slot_req[row]
            if req is None or req.paused or req.rid in self._prefill_jobs:
                continue
            p = self._row_len[row]
            props = spec_plan.get(row) if spec_plan else None
            if props:
                # verify window: [pending] + proposals[:-1] — entry i's
                # logits are the target's argmax judging proposal i
                feed = [self._cur_tok_host[row]] + list(props[:-1])
                staged = []
                try:
                    for j, ftok in enumerate(feed):
                        wb = self._ensure_block(row, p + j, committed)
                        staged.append((ftok, p + j, wb, (p + j) % bs))
                except _RowPreempted:
                    continue
                spec_rows.append((len(toks_l), row, req, list(props), p))
                for ftok, fp, wb, wo in staged:
                    toks_l.append(ftok)
                    rows_l.append(row)
                    pos_l.append(fp)
                    wb_l.append(wb)
                    wo_l.append(wo)
                    feed_l.append(0)
                kv_blocks += (p + len(feed) - 1) // bs + 1
                committed.add(row)
                continue
            try:
                wb = self._ensure_block(row, p, committed)
            except _RowPreempted:
                continue
            kv_blocks += p // bs + 1
            decode_rows.append((len(toks_l), row, req))
            if req.inflight:
                # its last sample is still on its way to the host: the
                # device feeds the row its own copy
                toks_l.append(0)
                feed_l.append(FEED_TAKE | FEED_PUT)
            else:
                toks_l.append(self._cur_tok_host[row])
                feed_l.append(FEED_PUT)
            rows_l.append(row)
            pos_l.append(p)
            wb_l.append(wb)
            wo_l.append(p % bs)
            committed.add(row)
        budget = max(0, self._step_tokens - len(toks_l))
        for row in range(self.num_slots):
            req = self._slot_req[row]
            if req is None or req.paused or budget <= 0:
                continue
            job = self._prefill_jobs.get(req.rid)
            if job is None:
                continue
            n = min(len(job.toks), budget)
            staged = []
            try:
                for j in range(n):
                    p = job.pos + j
                    if job.drop_writes:
                        wb, wo = nb, 0
                    else:
                        wb = self._ensure_block(row, p, committed)
                        wo = p % bs
                    staged.append((job.toks[j], p, wb, wo))
            except _RowPreempted:
                continue
            base = len(toks_l)
            for tok, p, wb, wo in staged:
                toks_l.append(tok)
                rows_l.append(row)
                pos_l.append(p)
                wb_l.append(wb)
                wo_l.append(wo)
                feed_l.append(0)
            if job.sample_last and n == len(job.toks):
                feed_l[-1] = FEED_PUT    # its first token: the row's current
            wrote = 0 if job.drop_writes else n
            job_rows.append((row, req, job, n, base + n - 1, wrote))
            kv_blocks += (job.pos + n - 1) // bs + 1
            committed.add(row)
            budget -= n
        if not toks_l:
            return None
        if len(job_rows) >= 2:
            # several requests' prefill segments shared one forward —
            # the token-level analogue of _prefill_slots_batched
            self._stats["batched_prefills"] += 1
            self._stats["batched_prefill_slots"] += len(job_rows)
        # Padded batch width ladder: each (prefill?, depth) pair is ONE
        # jit signature, so the retrace ledger stays at one compile per
        # (table-width bucket, depth) — num_slots*depth always covers
        # every verify window plus the non-speculating decode rows.
        if spec_rows:
            t = self.num_slots * max(1, depth)
            if job_rows:
                t = max(t, self._step_tokens)
        else:
            t = self.num_slots if not job_rows else self._step_tokens
        n_real = len(toks_l)
        # the tail padding is one more run, of row 0's first block
        self._kv_blocks_step = kv_blocks + (n_real < t)
        self._kv_blocks_total.inc(self._kv_blocks_step)
        self._head_entries_step = _head_entries(t, self.num_slots,
                                                bool(spec_rows))
        self._head_entries_total.inc(self._head_entries_step)
        while len(toks_l) < t:
            toks_l.append(0)
            rows_l.append(0)
            pos_l.append(0)
            wb_l.append(nb)      # sentinel block: write dropped
            wo_l.append(0)
            feed_l.append(0)
        # Per-rung adapter slot ids, parallel to the token batch: each
        # real entry gathers its request's bound slot (null slot 0 for
        # base rows and all padding). Built on EVERY step when a pool
        # is attached — the vectors' shapes track the existing t
        # ladder, so tenant churn cannot mint a new jit signature.
        aid = None
        if self.adapter_pool is not None:
            aid = [[0] * len(toks_l)
                   for _ in range(self.adapter_pool.num_rungs)]
            for i in range(n_real):
                req = self._slot_req[rows_l[i]]
                b = req.adapter_binding if req is not None else None
                if b is not None:
                    for j, s in enumerate(b.slot_ids):
                        aid[j][i] = s
        return (toks_l, rows_l, pos_l, wb_l, wo_l, feed_l, decode_rows,
                spec_rows, job_rows, aid)

    def _step_paged(self, span) -> Dict[int, List[int]]:
        # guarded-by: caller
        """One ``step()`` of the paged layout: plan and launch ONE fused
        step, then collect (fetch and deliver) what has to be home before
        the call returns. Serially that is the step just launched: plan,
        launch, advance, fetch, emit. Where the engine is saturated
        (:meth:`_saturated`) the step stays in flight and the call
        collects the one launched by the call BEFORE it instead, so the
        next plan and launch overlap the device: plan k+1, launch k+1,
        advance k+1, fetch k, emit k. Each phase is a span
        (docs/observability.md); ``span`` is the tracer's ``span`` when
        the step found tracing on, else the no-op."""
        with span("engine.step", step=self._stats["decode_steps"]) as st:
            with span("engine.plan") as sp:
                rows0 = list(self._slot_req) if sp is not None else ()
                with span("engine.schedule"):
                    self._schedule()
                emitted = self._pending_emits
                self._pending_emits = {}
                depth, spec_plan = self._spec_begin_step()
                try:
                    with span("engine.assemble_plan"):
                        plan = self._assemble_paged_plan(spec_plan, depth)
                except _PlanWaits:
                    # the pool is exhausted: stand down, then reclaim
                    self._collect(span, self._flying, emitted)
                    with span("engine.assemble_plan"):
                        plan = self._assemble_paged_plan(spec_plan, depth)
                if sp is not None:
                    sp.set_attr("admitted", self._placed_since(rows0))
                n_copies = (self._flush_state_copies(span)
                            if self._state_copies else 0)
                if plan is not None:
                    (toks_l, rows_l, pos_l, wb_l, wo_l, feed_l, decode_rows,
                     spec_rows, job_rows, adapter_ids) = plan
                    adapters = None
                    if adapter_ids is not None:
                        # Fixed-shape banks + (T,)-ladder id vectors ride
                        # every call — the only adapter-dependent state
                        # the jit sees.
                        adapters = self.adapter_pool.banks()
                        adapter_ids = tuple(np.asarray(g, np.int32)
                                            for g in adapter_ids)
                    with span("engine.tables"):
                        tables = self._tables_device()
                    # host numpy in, device out: the six plan vectors
                    # enter the jit as the rows of ONE numpy array (one
                    # C++ ingest; jnp.asarray here would cost a full
                    # dispatch a step)
                    vectors = np.asarray(
                        (toks_l, rows_l, pos_l, wb_l, wo_l, feed_l),
                        np.int32)
            prev = self._flying
            if plan is None:
                # nothing to launch; whatever is in flight comes home
                if prev is not None:
                    self._collect(span, prev, emitted)
                return emitted
            used = (len(decode_rows) + sum(j[3] for j in job_rows)
                    + sum(len(r[3]) for r in spec_rows))
            if st is not None:
                st.set_attr("entries", len(toks_l))
                st.set_attr("used", used)
                st.set_attr("head_entries", self._head_entries_step)
                st.set_attr("decode_rows", len(decode_rows))
                st.set_attr("prefill_tokens", sum(j[3] for j in job_rows))
                st.set_attr("table_width", int(tables.shape[1]))
                st.set_attr("kv_blocks", self._kv_blocks_step)
                st.set_attr("block_size", self._alloc.block_size)
                st.set_attr("kv_copy_bytes", self._kv_copy_bytes)
                st.set_attr("queue_depth", len(self._queue))
                st.set_attr("rows_active", len(decode_rows)
                            + len(spec_rows) + len(job_rows))
                st.set_attr("launches", 1)
                st.set_attr("ahead", int(prev is not None))
                if self.config.ssm:
                    # rows whose recurrent state the step reads and writes
                    st.set_attr("ssm_rows", len(decode_rows)
                                + sum(1 for j in job_rows if j[5]))
                    st.set_attr("state_rows_one_pass", len(decode_rows)
                                if state_step.traced(self._state_shape)
                                else 0)
                    st.set_attr("ssm_state_copies", n_copies)
                if self.config.pattern:
                    self._note_pattern_step(st, vectors, used, n_copies)
            t_launch = get_profiler().begin_step("engine.fused_step")
            if st is not None and t_launch and (prev is not None
                                                or self._fetched_at):
                # no fused step of THIS engine was in flight from its
                # last step's fetch to this launch: emit, the caller,
                # plan, copies (and the wait for work, where there was
                # none). None of it where the last step still runs.
                st.set_attr("unqueued_ms", 0.0 if prev is not None else
                            (t_launch - self._fetched_at) * 1_000.0)
            toks, logps = self._launch_paged(span, vectors, tables, adapters,
                                             adapter_ids, bool(spec_rows))
            with span("engine.advance"):
                samplers = self._advance_paged(decode_rows, job_rows)
                self._publish_fragmentation()
            self._flying = fly = _FlyingStep(
                step=self._stats["decode_steps"], toks=toks, logps=logps,
                samplers=samplers, spec_rows=spec_rows, used=used,
                t_launch=t_launch, span=st)
            self._stats["decode_steps"] += 1
            if prev is not None:
                self._run_ahead_total.inc()
                self._collect(span, prev, emitted)
            # the step's ONE trailing schedule: the rows its advance and
            # the delivery above freed (what a serial collect below
            # frees by EOS, the next plan's leading schedule fills)
            self._schedule_traced(span)
            if (self._spec is not None or not self._saturated()
                    or self._state_needs_values(fly)):
                self._collect(span, fly, emitted)
            return emitted

    def _note_pattern_step(self, st, plan, used: int, n_copies: int) -> None:
        # guarded-by: caller
        """A layer pattern's attrs of one fused step on its span, from the
        ``used`` leading entries of its ``plan`` (the ``(6, T)`` vectors):
        columns of cache (a token's k and v in one layer) the step's
        entries attend, by the kind of layer that reads them — a window
        layer their trailing window, the full layer the context, the cross
        layers the full layer's context again each — and the rings a
        fork's row copies moved (a state-row copy carries the row's ring
        in every window layer). The pattern's trailing segments that hold
        nothing (``ModelConfig.readers_from``) run over the entries the
        head runs over (``cross_entries``): in a step that gathers its
        samplers (``_head_entries``) their cross layers attend the
        samplers' contexts alone."""
        c = self.config
        ctx = plan[2, :used].astype(np.int64) + 1
        full = int(ctx.sum())
        tail = c.readers_from < len(c.layer_types)
        tail_cross = c.kind_layers("cross", c.readers_from)
        gathers = tail and self._head_entries_step < plan.shape[1]
        read = (int(ctx[(plan[5, :used] & FEED_PUT) > 0].sum()) if gathers
                else full)
        cols = {"window": (int(np.minimum(ctx, c.layer_window).sum())
                           * c.kind_layers("window")),
                "full": full * c.kind_layers("full"),
                "cross": (full * (c.kind_layers("cross") - tail_cross)
                          + read * tail_cross)}
        for kind, n in cols.items():
            st.set_attr("kv_columns_" + kind, n)
        st.set_attr("kv_columns", sum(cols.values()))
        st.set_attr("cross_entries", self._head_entries_step if tail
                    else plan.shape[1])
        st.set_attr("window_row_copies",
                    n_copies * c.kind_layers("window"))

    def _schedule_traced(self, span) -> None:
        # guarded-by: caller
        with span("engine.schedule") as sp:
            rows0 = list(self._slot_req) if sp is not None else ()
            self._schedule()
            if sp is not None:
                sp.set_attr("admitted", self._placed_since(rows0))

    def _state_needs_values(self, fly: _FlyingStep) -> bool:
        # guarded-by: caller
        """Must this step's tokens be home before the next plan? A held
        row that ends on EOS keeps its cache for a continuation. Launched
        ahead, the next step would feed that EOS token to the row once
        more: the k/v it writes is rewritten by the continuation's first
        entry and comes out the same, but a recurrent state that has
        consumed a token cannot step back over it. So a step in which a
        held request of a model with such state may sample its EOS comes
        home first (a request that is closing has no further entry)."""
        return bool(self.config.ssm) and any(
            req.hold_slot and req.eos_id is not None and not req.closing
            for _idx, req, _first in fly.samplers)

    def _saturated(self) -> bool:
        # guarded-by: caller
        """May the step just launched stay in flight while the next one
        is planned? Only where that delays no admission: a step launched
        ahead cannot admit a request that arrives after its plan was
        made, so the engine runs ahead iff, after the step's
        ``_schedule()``, a request is still queued or no row is free —
        a later arrival would have waited behind the queue, or for a
        row, in any case. An under-loaded engine (a free row, an empty
        queue) keeps the serial order."""
        return (not self._free_slots()
                or any(not r.paused for r in self._queue))

    def _launch_paged(self, span, vectors, tables, adapters, adapter_ids,
                      all_logits):
        # guarded-by: caller
        """Enqueue the fused step on the plan's ``(6, T)`` array and ask
        for its tokens' copy to the host: ONE program for the runtime,
        which also splits the engine's key and carries the rows' current
        tokens, and one transfer queued behind it on the device. Nothing
        here waits: the pool is ordered by the next step's donation, the
        tokens by ``engine.fetch``. The span's self time (less the
        wrapper's ``.dispatch`` child) is the wrapper's bookkeeping and
        the two transfer requests. On the v5e host a new shape's
        lowering time follows the summed frame sizes from ``step()``
        down to this call (PERF.md §6, PR 24, 31 and 36): this frame and
        ``_step_paged``'s keep the size the warm-up was last measured at
        on the chip (``tests/test_engine_launch_path.py`` pins the sum;
        ROADMAP D10). The two unused locals that made it up since PR 36
        went to ``all_logits`` and its place on the stack (PR 40): the
        next local here or there moves the pin, with a measured warm
        ``setup_s`` beside it."""
        with span("engine.launch") as sp:
            (next_tok, logp, self.pool, self._key,
             self._cur_tok_dev) = _paged_fused_step(
                self.params, self.config, vectors, tables, self.pool,
                self._key, self._cur_tok_dev, self.sample,
                self._use_paged_kernel,
                adapters=adapters, adapter_ids=adapter_ids,
                all_logits=all_logits)
            next_tok.copy_to_host_async()
            logp.copy_to_host_async()
            if sp is not None:
                sp.set_attr("host_arrays", 2 + len(adapter_ids or ()))
        return next_tok, logp

    def _drain(self) -> None:
        # guarded-by: caller
        """Bring the step in flight home, if there is one: every public
        entry that reads or moves a request's tokens or rows between two
        steps starts here, and then sees what a serial engine would
        show. The tokens wait in ``_pending_emits`` for the next
        ``step()`` to return them."""
        if self._flying is None:
            return
        self._trace_on = get_tracer().active()
        try:
            self._collect(get_tracer().span if self._trace_on
                          else noop_span, self._flying,
                          self._pending_emits)
        finally:
            self._trace_on = False

    def _collect(self, span, fly: _FlyingStep,
                 emitted: Dict[int, List[int]]) -> None:
        # guarded-by: caller
        """Fetch a launched step's tokens and deliver them: the one
        point where the host waits for the device. Scheduling into the
        rows it frees is the caller's."""
        if fly is self._flying:
            self._flying = None
        with span("engine.fetch", of_step=fly.step) as sp:
            # ONE batched device→host transfer per fused step (the
            # analysis JIT110 budget), covering decode tokens AND the
            # first tokens of completing prefills, on a copy that was
            # asked for at launch.
            t_wait = time.perf_counter()
            toks, logps = profiled_device_get((fly.toks, fly.logps),
                                              fn="engine.fused_step")
            if sp is not None:
                sp.set_attr("wait_ms",
                            (time.perf_counter() - t_wait) * 1_000.0)
                sp.set_attr("bytes", int(toks.nbytes + logps.nbytes))
            self._host_syncs_total.inc()
        # launch to fetch; the time from the last fetch to this step's
        # launch was unqueued, where the launch came after it
        self._fetched_at = get_profiler().end_step(
            "engine.fused_step", fly.t_launch, self._fetched_at)
        if self._kda_meters is not None:
            toks, logps = self._note_kda_step(fly.span, toks, logps)
        # the step's last two: the block reads its attention did not make
        # because rows that hold the same blocks attended them together
        toks, (saved, group_items) = toks[:-2], toks[-2:]
        self._kv_blocks_shared_total.inc(int(saved))
        if fly.span is not None:
            fly.span.set_attr("kv_blocks_saved", int(saved))
            fly.span.set_attr("attn_group_items", int(group_items))
        if self._moe_counters is not None:
            self._note_moe_step(fly.span, toks, fly.used)
        if self._mhc_gauge is not None:
            self._note_mhc_step(fly.span, logps)
        with span("engine.emit") as sp:
            n_emitted, n_finished = self._deliver(fly, toks, logps, emitted)
            self._steps_total.inc()
            self._tokens_total.inc(n_emitted)
            if sp is not None:
                sp.set_attr("emitted", n_emitted)
                sp.set_attr("finished", n_finished)

    def _note_moe_step(self, st, toks, used: int) -> None:
        # guarded-by: caller
        """An expert model's step: publish what its routing did. The
        device's counts arrive behind the step's tokens in the fetched
        array (``_paged_fused_step``: ``MoEStats``' two, or its four where
        the layer holds a share of the router's experts); the pairs
        offered are the host's own count, entries in use (``used``) x
        experts per token, and the banks those HELD."""
        touched, load_max, *share = (
            int(n) for n in toks[-4 if self.config.expert_share else -2:])
        assignments = used * self.config.num_experts_per_tok
        n_banks = self.config.num_expert_layers * self.config.num_experts
        pairs, banks, banks_total, peak, *share_counters = self._moe_counters
        pairs.inc(assignments)
        banks.inc(touched)
        banks_total.inc(n_banks)
        peak.set(load_max)
        for counter, n in zip(share_counters, share):
            counter.inc(n)
        if st is not None:
            st.set_attr("expert_assignments", assignments)
            st.set_attr("experts_touched", touched)
            st.set_attr("expert_banks", n_banks)
            st.set_attr("expert_load_max", load_max)
            if share:
                # every pick of the step, all expert layers: what the two
                # device counts are out of
                st.set_attr("expert_picks", assignments
                            * self.config.num_expert_layers)
                st.set_attr("zero_picks", share[0])
                st.set_attr("local_pairs", share[1])

    def _note_kda_step(self, st, toks, logps):
        # guarded-by: caller
        """A delta-rule model's step: publish what arrived last behind the
        step's tokens (the entries its chunked form took) and log-probs
        (the largest readout), and cut both off."""
        entries, absmax = int(toks[-1]), float(logps[-1])
        counter, gauge = self._kda_meters
        counter.inc(entries)
        gauge.set(absmax)
        if st is not None:
            st.set_attr("kda_chunk_entries", entries)
            st.set_attr("kda_readout_absmax", absmax)
        return toks[:-1], logps[:-1]

    def _note_mhc_step(self, st, logps) -> None:
        # guarded-by: caller
        """A multi-stream model's step: publish the Sinkhorn error that
        arrived behind the step's log-probs (``_paged_fused_step``)."""
        err = float(logps[-1])
        self._mhc_gauge.set(err)
        if st is not None:
            st.set_attr("mhc_ds_err", err)

    def _publish_fragmentation(self) -> None:
        # guarded-by: caller
        used_tokens = sum(self._row_len[s] for s in range(self.num_slots)
                          if self._tables[s])
        for _p_tokens, p_blocks, _last in self._prefixes.values():
            if p_blocks is not None:  # host-tiered entries hold no pool
                used_tokens += len(_p_tokens)
        self._alloc.publish_fragmentation(used_tokens)

    def _placed_since(self, rows0) -> int:
        # guarded-by: caller
        """Rows that hold a request they did not hold in ``rows0``, a copy
        of ``_slot_req``: the placements since (a span's attr; worked
        out only where tracing is on)."""
        return sum(r is not None and r is not r0
                   for r0, r in zip(rows0, self._slot_req))

    def _advance_paged(self, decode_rows, job_rows) -> list:
        # guarded-by: caller
        """The structural half of a step, right after its launch: what
        needs only "the step was launched" — the rows' lengths, the
        prefill jobs' progress and completion, a group's capture and its
        followers, and every end that is a COUNT of launched tokens
        (``max_new_tokens``, the context bound): such a request leaves
        its row here, and the row is free for the queue's next request
        as it was when tokens came home first. Returns the step's
        samplers, ``(entry, request, is its first token)`` in entry
        order, for :meth:`_deliver`."""
        samplers = []
        if self.config.ssm and state_step.traced(self._state_shape):
            self._state_one_pass_total.inc(len(decode_rows))
        for idx, row, req in decode_rows:
            samplers.append((idx, req, False))
            req.inflight += 1
            self._row_len[row] += 1
            if (req.launched >= req.max_new_tokens
                    or self._row_len[row] >= self.context_bound - 1):
                self._close_request(req, row)
        for row, req, job, n, last_idx, wrote in job_rows:
            self._row_len[row] += wrote
            job.toks = job.toks[n:]
            job.pos += n
            if job.toks:
                continue
            self._prefill_jobs.pop(req.rid, None)
            g = req.group
            uncaptured = (g is not None and req.rid == g.donor_rid
                          and g.spine is None and not g.degraded
                          and not req.launched)
            if job.fork_then is not None:
                # Recurrent state: the donor stands before the prompt's
                # last token, table and state alike. Fork here, then go
                # on with that token as a job of one (as each follower
                # will, from its copy of the state).
                if uncaptured:
                    self._group_capture(req, row)
                self._prefill_jobs[req.rid] = _PrefillJob(
                    toks=[job.fork_then], pos=job.pos, sample_last=True)
                continue
            if uncaptured and job.sample_last and not self.config.ssm:
                # Donor prefill just completed and its first sampled
                # token is NOT yet written (tokens are fed the step
                # after sampling): the table is the pure prompt spine.
                # The donor's own next write COW-splits the boundary
                # block.
                self._group_capture(req, row)
            if job.sample_last:
                samplers.append((last_idx, req, True))
                req.inflight += 1
                if req.max_new_tokens <= 1:
                    self._close_request(req, row)
            else:
                self._cur_tok_host[row] = job.after_tok
        return samplers

    def _deliver(self, fly: _FlyingStep, toks, logps,
                 emitted: Dict[int, List[int]]) -> tuple:
        # guarded-by: caller
        """The value half of a step, when its tokens are home: hand them
        to their REQUESTS (a row may have changed hands since the
        launch). EOS is a value: a request that meets it ends here, and
        where a later step was launched ahead with one more entry for
        its row, that sample is dropped when it arrives. A request is
        done when its last token is delivered, not before. Speculative
        windows (never launched ahead: accepting needs the values) roll
        their rows back here too. Returns (tokens emitted, requests
        finished)."""
        n_emitted = n_finished = 0
        for idx, req, first in fly.samplers:
            req.inflight -= 1
            if req.done:
                continue                # ended by an earlier token
            tok = int(toks[idx])
            req.tokens.append(tok)
            req.logps.append(float(logps[idx]))
            self._stats["tokens_emitted"] += 1
            n_emitted += 1
            emitted.setdefault(req.rid, []).append(tok)
            if first:
                self._mark_first_token(req)
            hit_eos = req.eos_id is not None and tok == req.eos_id
            if not req.closing:
                self._cur_tok_host[req.slot] = tok
                if hit_eos:
                    self._close_request(req, req.slot)
            if hit_eos or (req.closing and not req.inflight):
                self._mark_done(req)
                n_finished += 1
        if fly.spec_rows:
            n_emitted += self._deliver_spec(fly.spec_rows, toks, logps,
                                            emitted)
        return n_emitted, n_finished

    def _deliver_spec(self, spec_rows, toks, logps,
                      emitted: Dict[int, List[int]]) -> int:
        # guarded-by: caller
        """Accept each verify window's agreed prefix and roll both caches
        back to it. Returns the number of tokens emitted."""
        n_emitted = 0
        total_proposed = total_accepted = 0
        for base, row, req, props, start in spec_rows:
            k = len(props)
            # greedy acceptance: walk the verify window until the
            # target's argmax disagrees with the proposal; the
            # disagreeing argmax IS the correction token, so every
            # round emits >= 1 token and outputs stay byte-identical
            # to non-speculative greedy decode
            window = []
            for i in range(k):
                tok = int(toks[base + i])
                window.append((tok, float(logps[base + i])))
                if tok != props[i]:
                    break
            accepted = sum(1 for (tok, _), pr in zip(window, props)
                           if tok == pr)
            total_proposed += k
            total_accepted += accepted
            self._stats["spec_rounds"] += 1
            self._stats["spec_proposed"] += k
            self._stats["spec_accepted"] += accepted
            self._stats["spec_wasted"] += k - accepted
            # distillation harvest: the target-chosen continuation of
            # the pre-round stream (accepted run + the correction)
            sp = self._spec
            sp.wasted_total.inc(k - accepted)
            stream_before = req.prompt + req.tokens
            sp.outcomes.append({
                "context": stream_before[-sp.ctx_window:],
                "targets": [tok for tok, _ in window],
                "accepted": accepted,
                "proposed": k,
            })
            finish = False
            emitted_row = 0
            for tok, lp in window:
                req.tokens.append(tok)
                req.logps.append(lp)
                self._stats["tokens_emitted"] += 1
                n_emitted += 1
                emitted.setdefault(req.rid, []).append(tok)
                emitted_row += 1
                hit_eos = req.eos_id is not None and tok == req.eos_id
                out_of_budget = len(req.tokens) >= req.max_new_tokens
                out_of_cache = (start + emitted_row
                                >= self.context_bound - 1)
                if hit_eos or out_of_budget or out_of_cache:
                    finish = True
                    break
            self._cur_tok_host[row] = req.tokens[-1]
            # roll BOTH caches back to the verified prefix (the fed
            # window is exactly the emitted stream, so the new length
            # is start + tokens actually emitted)
            self._spec_rollback(row, start + emitted_row)
            if finish:
                self._finish_request(req, row)
        if total_proposed:
            sp = self._spec
            rate = total_accepted / total_proposed
            sp.ema = (rate if not sp.ema_init
                      else 0.9 * sp.ema + 0.1 * rate)
            sp.ema_init = True
            sp.accept_gauge.set(sp.ema)
        return n_emitted
