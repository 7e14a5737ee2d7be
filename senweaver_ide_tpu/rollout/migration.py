"""Live migration of in-flight decodes — the rollout half.

The fleet had three sanctioned ways to hurt a request: the KV pressure
ladder truncate-finishes at the preempt cap, eager no-drain publishes
degrade to classic draining when patience runs out, and autoscale
scale-down is drain-then-kill. All three become scheduling problems
once an in-flight decode can MOVE: checkpoint its block table +
sampler state here, graft it onto another replica, resume
token-exactly there (serve/scheduler.py runs the two-phase handoff).

:class:`DecodeCheckpoint` is the portable unit — everything a peer
engine needs to continue the decode bit-for-bit:

- the request's host state (prompt, emitted tokens, behavior logps,
  budget/eos, preemption accounting);
- the KV block contents gathered host-side in the SAME blockified
  layout (and the same storage flavor — a quantized ladder ships
  int8/fp8 bytes + scales, format v2) the host tier and the
  cross-engine prefix broadcast speak
  (``paged_kv.gather_blocks_quant``), so restore is one install
  scatter;
- the engine RNG key and the engine-wide sampler params (restore
  refuses a sampler mismatch — a migrated greedy decode must stay
  greedy);
- the adapter binding as ``(tenant id, adapter version)`` — restore
  re-acquires on the target and REFUSES if the tenant's current
  version moved (a cross-version adapter splice would silently mix
  policies, exactly like grafting a base prefix under an adapter);
- the ``(epoch, version)`` weight fence stamped by the serve layer,
  so a publish landing between snapshot and restore is detected
  before any KV is spliced across policies.

Speculative draft state is deliberately DROPPED: the target's draft
pool resyncs through the existing catch-up replay
(``engine._spec_catch_up`` re-feeds ``prompt + tokens[:-1]``), which
is bit-exact by construction.

Two restore paths, both token-exact:

- **fast path** — a free row + matching block layout: allocate
  blocks (evicting holds/prefixes, never preempting), one
  ``install_blocks`` scatter, flip the row bookkeeping to resume
  decode from the checkpointed cursor;
- **recompute path** — anything else (no KV payload, no free row,
  pool exhausted, foreign block size): requeue at the FRONT; the
  scheduler's existing preemption-resume replay re-prefills
  ``prompt + tokens[:-1]`` and decodes from ``tokens[-1]``, emitting
  nothing twice.

Every function here takes the engine with its lock already held via
the thin ``RolloutEngine.checkpoint_request`` / ``restore_request`` /
``release_request`` wrappers; this module is an engine-private
collaborator, split out so the serve layer imports the checkpoint
type without pulling the whole engine.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import numpy as np

from ..obs.runtime_profile import profiled_device_get
from .paged_kv import (BlockPayload, BlocksExhausted,
                       gather_blocks_quant, install_blocks_quant)

# Bump when the checkpoint schema changes; restore refuses a foreign
# format instead of guessing (a half-understood checkpoint resumed
# wrong is corruption, a refused one is a local finish on the source).
# v2 added the quantized-KV ladder fields (kv_dtype, hi_layers, scale
# and full-width-prefix payloads); v1 checkpoints still decode — their
# defaults mean "full-width payload", which is exactly what they carry.
CHECKPOINT_FORMAT = 2
_ACCEPTED_FORMATS = (1, 2)


class MigrationError(RuntimeError):
    """A checkpoint or restore was refused — unknown/finished rid,
    non-paged layout, sampler/model mismatch, moved adapter version,
    or a foreign checkpoint format. The coordinator responds by
    resuming the request where it already lives (never lost)."""


@dataclasses.dataclass
class DecodeCheckpoint:
    """Portable, versioned snapshot of one in-flight decode."""

    format_version: int
    rid: int
    prompt: List[int]
    tokens: List[int]
    logps: List[float]
    max_new_tokens: int
    eos_id: Optional[int]
    preempt_count: int
    # engine-wide sampler params at snapshot time; restore validates
    # equality (token-exactness is meaningless across samplers)
    temperature: float
    top_k: int
    top_p: float
    # engine RNG key (host uint32[2]) — carried for completeness;
    # greedy decode (the token-exact contract) never consults it
    rng_key: Optional[np.ndarray] = None
    # multi-tenant LoRA binding: restore re-acquires and refuses a
    # version drift (no cross-version adapter splice)
    adapter_id: Optional[str] = None
    adapter_version: Optional[int] = None
    # (epoch, version) weight fence, stamped by the serve layer at
    # snapshot; the coordinator aborts the handoff when the target's
    # resident version differs (no cross-version KV splice)
    weight_epoch: int = 0
    weight_version: int = 0
    # serve-layer deadline accounting rides along untouched
    deadline: Optional[float] = None
    # KV payload: positions 0..kv_len-1 of the row, blockified
    # (L, nblk, block_size, Hkv, Dh) host arrays — None when the
    # request was queued/mid-prefill (restore recomputes instead)
    kv_len: int = 0
    block_size: int = 0
    kv_k: Optional[np.ndarray] = None
    kv_v: Optional[np.ndarray] = None
    # Quantized-KV ladder (format v2): the payload is stored in the
    # SOURCE pool's flavor — ``kv_dtype`` names the ladder rung,
    # ``hi_layers`` how many early layers ride full-width, the scale
    # planes are (Lq, nblk, block_size, Hkv) f32, and kv_k/kv_v hold
    # int8/fp8 bytes for the quantized layers. Restore onto a replica
    # with a DIFFERENT ladder falls back to recompute-prefill — a
    # cross-flavor splice would requant already-lossy payloads.
    kv_dtype: str = "bf16"
    hi_layers: int = 0
    kv_k_scale: Optional[np.ndarray] = None
    kv_v_scale: Optional[np.ndarray] = None
    kv_k_hi: Optional[np.ndarray] = None
    kv_v_hi: Optional[np.ndarray] = None

    def with_fence(self, *, epoch: int, version: int,
                   deadline: Optional[float] = None) -> "DecodeCheckpoint":
        """Serve-layer stamp: the weight fence (and optionally the
        request deadline) recorded against the SOURCE replica at
        snapshot time."""
        return dataclasses.replace(self, weight_epoch=int(epoch),
                                   weight_version=int(version),
                                   deadline=deadline)

    def to_wire(self) -> Dict[str, Any]:
        """Plain dict for the rpc codec (ndarrays ride the ``__nd__``
        tag); ``from_wire`` round-trips it."""
        out = dataclasses.asdict(self)
        # asdict deep-copies ndarrays via copy.deepcopy — fine, but
        # keep the originals to avoid the copy on the hot path
        for name in ("rng_key", "kv_k", "kv_v", "kv_k_scale",
                     "kv_v_scale", "kv_k_hi", "kv_v_hi"):
            out[name] = getattr(self, name)
        return out

    @classmethod
    def from_wire(cls, wire: Dict[str, Any]) -> "DecodeCheckpoint":
        if not isinstance(wire, dict):
            raise MigrationError(
                f"checkpoint wire payload is {type(wire).__name__}, "
                "not a dict")
        fmt = wire.get("format_version")
        if fmt not in _ACCEPTED_FORMATS:
            raise MigrationError(
                f"checkpoint format {fmt!r} not in supported "
                f"{_ACCEPTED_FORMATS} — refusing to guess")
        names = {f.name for f in dataclasses.fields(cls)}
        unknown = set(wire) - names
        if unknown:
            raise MigrationError(
                f"checkpoint carries unknown fields {sorted(unknown)}")
        kw = dict(wire)
        kw["prompt"] = [int(t) for t in kw["prompt"]]
        kw["tokens"] = [int(t) for t in kw["tokens"]]
        kw["logps"] = [float(x) for x in kw["logps"]]
        return cls(**kw)


def checkpoint_from_engine(engine, rid: int, *,
                           pause: bool = True) -> DecodeCheckpoint:
    """Snapshot one in-flight request (engine lock held by caller).

    Non-destructive: the source keeps the request (paused when
    ``pause``) until the coordinator releases or resumes it — the
    retain-until-ack half of the exactly-once handoff. An actively
    decoding row gets its KV gathered host-side (ONE batched
    device→host transfer, same shape discipline as the swap-out
    path); a queued or mid-prefill request snapshots host state only
    and restores by recomputation."""
    if engine.kv_layout != "paged":
        raise MigrationError(
            "live migration needs the paged KV layout (engine fell "
            f"back to slots: {engine.kv_layout_fallback})")
    req = engine._requests.get(rid)
    if req is None:
        raise MigrationError(f"unknown rid {rid}")
    if req.done:
        raise MigrationError(f"rid {rid} already finished")
    if req.hold_slot:
        raise MigrationError(
            f"rid {rid} holds its slot for a continuation; held KV "
            "is bound to this engine and cannot migrate")
    if pause:
        req.paused = True
    row = req.slot
    kv_rows = (row is not None and rid not in engine._prefill_jobs
               and bool(req.tokens) and bool(engine._tables[row]))
    bs = engine._alloc.block_size
    kv_len = 0
    kv_k = kv_v = None
    ks = vs = khi = vhi = None
    nblk = 0
    if kv_rows:
        # Gather ONLY the blocks covering live positions, and note that
        # the gather itself materializes the payload into fresh host
        # buffers: a row whose table shares forked blocks (group
        # follower, tree branch) checkpoints an UNSHARED deep copy, so
        # restoring it elsewhere can never splice a sibling leaf's
        # later COW writes. The source row's refcounts are untouched
        # until the coordinator's release.
        kv_len = engine._row_len[row]
        nblk = min(len(engine._tables[row]),
                   engine._alloc.blocks_for(kv_len))
        blocks = engine._tables[row][:nblk]
        # Already blockified AND still in the pool's storage flavor:
        # a quantized ladder ships int8/fp8 bytes + scales over the
        # wire (half the transfer), a bf16 one the full payload.
        p = gather_blocks_quant(engine.pool,
                                np.asarray(blocks, np.int32))
        payload = (p, engine._key)
    else:
        payload = (engine._key,)
    host = profiled_device_get(payload, fn="engine.migrate_out")
    if kv_rows:
        p_h, key_h = host
        np_of = lambda a: None if a is None else np.asarray(a)
        kv_k, kv_v = np_of(p_h.k), np_of(p_h.v)
        ks, vs = np_of(p_h.k_scale), np_of(p_h.v_scale)
        khi, vhi = np_of(p_h.k_hi), np_of(p_h.v_hi)
    else:
        (key_h,) = host
    sample = engine.sample
    engine._stats["migrations_out"] += 1
    return DecodeCheckpoint(
        format_version=CHECKPOINT_FORMAT, rid=rid,
        prompt=list(req.prompt), tokens=list(req.tokens),
        logps=list(req.logps), max_new_tokens=req.max_new_tokens,
        eos_id=req.eos_id, preempt_count=req.preempt_count,
        temperature=float(sample.temperature), top_k=int(sample.top_k),
        top_p=float(sample.top_p), rng_key=np.asarray(key_h),
        adapter_id=req.adapter,
        adapter_version=(None if req.adapter_binding is None
                         else int(req.adapter_binding.version)),
        kv_len=kv_len, block_size=bs, kv_k=kv_k, kv_v=kv_v,
        kv_dtype=engine.engine_config.kv_dtype,
        hi_layers=engine.pool.hi_layers,
        kv_k_scale=ks, kv_v_scale=vs, kv_k_hi=khi, kv_v_hi=vhi)


def _validate_pool_layout(engine, ckpt: DecodeCheckpoint) -> None:
    """Model-level compatibility: a KV payload whose layer/head/dim
    layout or dtype differs came from a DIFFERENT model — always an
    error, never a silent recompute. (The kv_dtype LADDER fence is the
    caller's: a ladder mismatch is a legal recompute fallback, so this
    only runs once the flavors already agree.)"""
    l, _nblk, _bs, hkv, dh = ckpt.kv_k.shape
    l += 0 if ckpt.kv_k_hi is None else int(ckpt.kv_k_hi.shape[0])
    _nb, _pbs, phkv, pdh = engine.pool.k.shape[1:]
    pl = engine.pool.num_layers
    if (l, hkv, dh) != (pl, phkv, pdh):
        raise MigrationError(
            f"checkpoint KV layout (L={l}, Hkv={hkv}, Dh={dh}) != "
            f"target pool (L={pl}, Hkv={phkv}, Dh={pdh})")
    if ckpt.kv_k.dtype != np.dtype(engine.pool.k.dtype):
        raise MigrationError(
            f"checkpoint KV dtype {ckpt.kv_k.dtype} != target pool "
            f"dtype {engine.pool.k.dtype}")


def restore_into_engine(engine, ckpt: DecodeCheckpoint) -> int:
    """Install a checkpoint under a FRESH rid (engine lock held by
    caller) and return it. Fast path: free row + matching block size
    → one install scatter; otherwise requeue at the front and let the
    preemption-resume replay recompute — both token-exact."""
    if not isinstance(ckpt, DecodeCheckpoint):
        ckpt = DecodeCheckpoint.from_wire(ckpt)
    if ckpt.format_version not in _ACCEPTED_FORMATS:
        raise MigrationError(
            f"checkpoint format {ckpt.format_version} not in supported "
            f"{_ACCEPTED_FORMATS}")
    if engine.kv_layout != "paged":
        raise MigrationError(
            "live migration needs the paged KV layout (engine fell "
            f"back to slots: {engine.kv_layout_fallback})")
    sample = engine.sample
    ours = (float(sample.temperature), int(sample.top_k),
            float(sample.top_p))
    theirs = (float(ckpt.temperature), int(ckpt.top_k),
              float(ckpt.top_p))
    if ours != theirs:
        raise MigrationError(
            f"sampler mismatch: checkpoint {theirs} != engine {ours} "
            "— resumed output could not be token-exact")
    if len(ckpt.prompt) >= engine.context_bound:
        raise MigrationError(
            f"prompt length {len(ckpt.prompt)} ≥ target context bound "
            f"{engine.context_bound}")
    binding = None
    if ckpt.adapter_id is not None:
        if engine.adapter_pool is None:
            raise MigrationError(
                f"checkpoint bound to adapter {ckpt.adapter_id!r} but "
                "target engine has no adapter_pool")
        try:
            binding = engine.adapter_pool.acquire(ckpt.adapter_id)
        except Exception as e:
            raise MigrationError(
                f"adapter {ckpt.adapter_id!r} unavailable on target: "
                f"{e}")
        if int(binding.version) != int(ckpt.adapter_version):
            engine.adapter_pool.release(binding)
            raise MigrationError(
                f"adapter {ckpt.adapter_id!r} moved to version "
                f"{binding.version} (checkpoint bound v"
                f"{ckpt.adapter_version}) — no cross-version splice")
    from .engine import _Request
    rid = engine._next_rid
    engine._next_rid += 1
    req = _Request(rid=rid, prompt=list(ckpt.prompt),
                   max_new_tokens=ckpt.max_new_tokens,
                   eos_id=ckpt.eos_id, adapter=ckpt.adapter_id,
                   adapter_binding=binding)
    req.tokens = list(ckpt.tokens)
    req.logps = list(ckpt.logps)
    req.preempt_count = ckpt.preempt_count
    engine._requests[rid] = req
    installed = False
    expect_len = len(ckpt.prompt) + len(ckpt.tokens) - 1
    # kv_dtype fence: a payload in a different ladder flavor (or with a
    # different full-width layer split) NEVER splices — requantizing an
    # already-lossy payload compounds the error budget silently. The
    # recompute path re-prefills exactly instead.
    ladder_ok = (ckpt.kv_dtype == engine.engine_config.kv_dtype
                 and int(ckpt.hi_layers) == engine.pool.hi_layers)
    if (ckpt.kv_k is not None and ckpt.kv_len > 0 and req.tokens
            and ckpt.kv_len == expect_len and ladder_ok):
        _validate_pool_layout(engine, ckpt)
        nblk = int(ckpt.kv_k.shape[1])
        free = engine._free_slots()
        if (free and ckpt.block_size == engine._alloc.block_size
                and nblk >= engine._alloc.blocks_for(ckpt.kv_len)):
            try:
                blocks = engine._alloc_blocks_evicting(nblk)
            except BlocksExhausted:
                blocks = None   # pool full even after reclaim: recompute
            if blocks is not None:
                try:
                    engine.pool = install_blocks_quant(
                        engine.pool,
                        BlockPayload(k=ckpt.kv_k, v=ckpt.kv_v,
                                     k_scale=ckpt.kv_k_scale,
                                     v_scale=ckpt.kv_v_scale,
                                     k_hi=ckpt.kv_k_hi,
                                     v_hi=ckpt.kv_v_hi),
                        np.asarray(blocks, np.int32))
                except Exception:
                    engine._alloc.release(blocks)
                    raise
                engine._alloc.count_install_copy(nblk)
                row = free[0]
                req.slot = row
                engine._mark_scheduled(req, row)
                engine._slot_req[row] = req
                engine._tables[row] = list(blocks)
                engine._row_len[row] = int(ckpt.kv_len)
                engine._cur_tok_host[row] = req.tokens[-1]
                installed = True
    if not installed:
        # Recompute path: front of the queue (the request already did
        # work); the scheduler's tokens-nonempty resume replays
        # prompt + tokens[:-1] and decodes from tokens[-1].
        engine._queue.appendleft(req)
    engine._stats["migrations_in"] += 1
    return rid


def release_from_engine(engine, rid: int) -> bool:
    """Forget a request post-handoff (engine lock held by caller):
    drop its row/blocks, adapter binding, queue entry, and pending
    emits. Idempotent — an unknown rid returns False (the release may
    race a retry or a completion)."""
    req = engine._requests.pop(rid, None)
    if req is None:
        return False
    try:
        engine._queue.remove(req)
    except ValueError:
        pass
    if engine.kv_layout == "paged":
        engine._prefill_jobs.pop(rid, None)
    engine._pending_emits.pop(rid, None)
    if req.adapter_binding is not None and engine.adapter_pool is not None:
        engine.adapter_pool.release(req.adapter_binding)
        req.adapter_binding = None
    row = req.slot
    if (row is not None and engine.kv_layout == "paged"
            and engine._slot_req[row] is req):
        engine._slot_req[row] = None
        engine._release_row(row)
    req.slot = None
    req.done = True
    return True


def set_paused(engine, rid: int, paused: bool) -> None:
    """Freeze/unfreeze one request (engine lock held by caller): a
    paused request is skipped by the step assembler, the speculation
    planner, and the scheduler — its state cannot advance between
    snapshot and release/resume."""
    req = engine._requests.get(rid)
    if req is None:
        raise MigrationError(f"unknown rid {rid}")
    if req.done:
        raise MigrationError(f"rid {rid} already finished")
    req.paused = bool(paused)
