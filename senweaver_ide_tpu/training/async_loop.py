"""Sampler/trainer overlap: the pipelined GRPO driver.

SURVEY.md §7 names "sampler/trainer overlap" the main systems risk for
the tokens/sec/chip metric: ``grpo_round`` is strictly collect → train,
so the chip idles through every host-side phase of collection (tool
execution, agent-loop bookkeeping) and the host idles through the train
step. This driver runs them as a two-stage pipeline (the Podracer
"Sebulba" split, PAPERS.md): a collector thread drives rollout sessions
for round N+1 while the device trains on round N's batch.

Staleness is bounded by the queue depth (``prefetch``): a batch is at
most ``prefetch`` updates behind the params that train on it. Two
correction modes:

- ``importance_correction=True`` (default): the behavior params that
  collected each batch are held in a BOUNDED version-keyed LRU
  (:class:`~.experience.BehaviorParamsCache`) and the batch's
  ``old_logp`` is computed under them just before the update, so the
  clipped objective's importance ratio is exact. Residency is
  O(cache capacity) param trees no matter how far the collector runs
  ahead; when a batch's behavior version has aged out, the step
  degrades to the ratio-1 approximation under the current params —
  counted (``senweaver_grpo_behavior_ratio_one_fallbacks_total``),
  never crashed.
- ``importance_correction=False``: ``old_logp = stop_grad(current)``
  (ratio 1), the standard 1-step-stale approximation.

Weight publication: each update stages its params for ``publish_params``
(wire it to ``RolloutEngine.update_params``), and the collector applies
the latest staged set at its next ROUND BOUNDARY — never mid-round, so
the retained ``behavior_params`` snapshot is exactly what every episode
in the round sampled under (a mid-round swap would silently break the
importance correction for episodes finishing after it). Publications
coalesce (latest wins); the final update's params are always flushed
when ``run`` returns — the single-chip analogue of the disaggregated
actor/learner weight transfer (RLAX; reference semantic: the APO cycle's
"apply optimized prompt to the live agent", apoService.ts:1219-1264,
upgraded to weights).
"""

from __future__ import annotations

import functools
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from .data import make_batch, make_batch_logps, place_batch_for_mesh
from .experience import BehaviorParamsCache, BehaviorParamsEvicted
from .grpo import GRPOConfig, token_logprobs
from .rl_loop import EpisodeRecord, collect_group_trajectories
from .trainer import TrainState, train_step


@functools.partial(jax.jit, static_argnames=("config",))
def _behavior_logp(params, config, tokens: jax.Array) -> jax.Array:
    """Token logprobs of ``tokens`` under the (frozen) behavior policy;
    ratio positions are later selected by the objective's own mask."""
    from ..models.transformer import forward
    logits, _ = forward(params, config, tokens[:, :-1])
    return token_logprobs(logits, tokens[:, 1:])


def behavior_logp_batched(params, config, tokens: jax.Array,
                          accum_steps: int = 1) -> jax.Array:
    """Behavior logps with the SAME microbatch split as the update:
    a whole-batch forward materializes (B, S-1, V) logits — the exact
    allocation accum_steps was sized to avoid. Batch must be
    accum-divisible (place_batch_for_mesh guarantees it)."""
    b = tokens.shape[0]
    if accum_steps <= 1 or b % accum_steps != 0:
        return _behavior_logp(params, config, tokens)
    mb = b // accum_steps
    import jax.numpy as _jnp
    return _jnp.concatenate(
        [_behavior_logp(params, config, tokens[i * mb:(i + 1) * mb])
         for i in range(accum_steps)], axis=0)


@dataclass
class AsyncRoundResult:
    state: TrainState
    metrics: Dict[str, float]
    episodes: List[EpisodeRecord]
    staleness: int            # updates between collection and training
    collect_wait_s: float     # trainer time spent waiting for a batch


@dataclass
class _Collected:
    trajectories: list
    episodes: List[EpisodeRecord]
    # Version stamp only — the params themselves live in the trainer's
    # bounded BehaviorParamsCache, NOT on the queue item (an unbounded
    # reference per in-flight batch was the old host-memory leak when
    # the collector outran the trainer).
    behavior_version: int
    collect_s: float = field(default=0.0)


class AsyncGRPOTrainer:
    """Two-stage pipelined GRPO: collection overlaps the train step."""

    def __init__(self, state: TrainState, model_config, mesh,
                 make_session: Callable[[], "RolloutSession"],
                 tasks: Sequence[str], *,
                 group_size: int = 4,
                 pad_id: int = 0,
                 max_len: Optional[int] = None,
                 grpo_config: GRPOConfig = GRPOConfig(),
                 reward_override=None,
                 max_parallel: int = 8,
                 accum_steps: int = 1,
                 ppo_epochs: int = 1,
                 prefetch: int = 1,
                 importance_correction: bool = True,
                 behavior_cache_size: Optional[int] = None,
                 publish_params: Optional[Callable[[object], None]] = None,
                 metrics_service=None,
                 lora_base=None,
                 ref_params=None):
        self.state = state
        self.model_config = model_config
        self.mesh = mesh
        self.make_session = make_session
        self.tasks = list(tasks)
        self.group_size = group_size
        self.pad_id = pad_id
        self.max_len = max_len
        self.grpo_config = grpo_config
        self.reward_override = reward_override
        self.max_parallel = max_parallel
        self.accum_steps = accum_steps
        if ppo_epochs < 1:
            raise ValueError(f"ppo_epochs must be >= 1, got {ppo_epochs}")
        self.ppo_epochs = ppo_epochs
        self.importance_correction = importance_correction
        self.publish_params = publish_params
        self.metrics_service = metrics_service
        # LoRA: state.params are ONLY the adapters over this frozen base
        # (training/lora.py); behavior snapshots and publishes carry the
        # MATERIALIZED policy so logp recomputation and engines see full
        # weights, while the train step differentiates adapters only.
        self.lora_base = lora_base
        # Frozen/rolling reference for the k3-KL term (grpo_round's
        # ref_params analogue): a FULL policy tree; combined with
        # grpo_config.kl_coef > 0 it anchors long runs against drift.
        # Swap via set_ref_params at round boundaries for a rolling
        # anchor.
        self.ref_params = ref_params

        self._queue: "queue.Queue[_Collected]" = queue.Queue(
            maxsize=max(1, prefetch))
        # Bounded behavior-params residency: every version the collector
        # may still train against is cached here by version; anything
        # older is evicted (typed, counted) and its batches degrade to
        # ratio-1. Default capacity covers the pipeline depth plus the
        # batch currently training and one staged publish.
        self.behavior_cache = BehaviorParamsCache(
            behavior_cache_size if behavior_cache_size is not None
            else max(2, prefetch) + 2)
        self.behavior_cache.put(0, self._merged_view(state.params))
        self._publish_lock = threading.Lock()
        # Staged (version, params) awaiting publication; the collector
        # applies it at round boundaries. _applied_behavior is the last
        # APPLIED pair — what the serving engine is actually running —
        # and is only touched by _flush_pending_publish (collector
        # thread, or run()'s finally after the collector joined).
        self._pending_publish: Optional[tuple] = None
        self._applied_behavior: tuple = (0,
                                         self._merged_view(state.params))
        self._version = 0
        self._stop = threading.Event()
        self._error: Optional[BaseException] = None
        self._rounds_wanted = 0
        self._thread: Optional[threading.Thread] = None

    # -- collector side ---------------------------------------------------
    def _flush_pending_publish(self) -> None:
        """Apply the latest staged publication (if any) to the engine and
        remember it as the live behavior snapshot."""
        with self._publish_lock:
            pending = self._pending_publish
            self._pending_publish = None
        if pending is not None and self.publish_params is not None:
            pending = (pending[0], self._folded_view(pending[1]))
            self.publish_params(pending[1])
            self._applied_behavior = pending
            # The cache, not the queue item, is what _train_on reads
            # the behavior params back from (bounded residency).
            self.behavior_cache.put(pending[0], pending[1])

    def set_ref_params(self, ref_params) -> None:
        """Swap the KL anchor (rolling-anchor pattern); takes effect on
        the next train round. Pass a FULL policy tree (materialized for
        LoRA)."""
        self.ref_params = ref_params

    def _merged_view(self, params):
        """Zero-copy full-policy view (dict union): what behavior-logp
        recompute and no-publish collection consume — forward() applies
        adapter leaves directly, so no weight fold is needed."""
        if self.lora_base is None:
            return params
        from .lora import merge_lora
        return merge_lora(self.lora_base, params)

    def _folded_view(self, params):
        """Materialized full weights — ONLY for actual publication to an
        engine. Folding is O(full model); it runs at flush time so
        latest-wins coalescing never burns a discarded fold, and at most
        one folded copy is resident."""
        if self.lora_base is None:
            return params
        from .lora import materialize_lora
        return materialize_lora(self.lora_base, params, self.model_config)

    def _collect_loop(self) -> None:
        produced = 0
        try:
            while not self._stop.is_set() and produced < self._rounds_wanted:
                # Apply any params published since the last round BEFORE
                # sampling starts: publication is deferred to collection
                # round boundaries (see _train_on) so every episode in a
                # round was sampled under exactly the (version, params)
                # snapshot recorded here — a mid-round engine weight swap
                # would make the retained behavior_params wrong for the
                # episodes that finished after it.
                self._flush_pending_publish()
                if self.publish_params is not None:
                    # The engine serves exactly the last APPLIED pair —
                    # never a racy read of live trainer state (a train
                    # step may complete between the flush and here).
                    version, params = self._applied_behavior
                else:
                    # No publication channel: sessions read trainer state
                    # directly, so the live reference IS the behavior.
                    version = self._version
                    # reference for full FT; zero-copy merge for LoRA
                    params = self._merged_view(self.state.params)
                    self.behavior_cache.put(version, params)
                t0 = time.monotonic()
                trajectories, episodes = collect_group_trajectories(
                    self.make_session, self.tasks,
                    group_size=self.group_size,
                    reward_override=self.reward_override,
                    max_parallel=self.max_parallel)
                for ep in episodes:
                    # (epoch, version) behavior stamp — the in-process
                    # pipeline has no lease, so epoch stays 0.
                    ep.behavior_version = version
                item = _Collected(trajectories, episodes, version,
                                  collect_s=time.monotonic() - t0)
                while not self._stop.is_set():
                    try:
                        self._queue.put(item, timeout=0.2)
                        produced += 1
                        break
                    except queue.Full:
                        continue
        except BaseException as e:   # surfaced by run()
            self._error = e
            self._stop.set()

    # -- trainer side -----------------------------------------------------
    def run(self, num_rounds: int) -> List[AsyncRoundResult]:
        """Train ``num_rounds`` updates with pipelined collection."""
        self._rounds_wanted = num_rounds
        self._thread = threading.Thread(target=self._collect_loop,
                                        name="grpo-collector", daemon=True)
        self._thread.start()
        results: List[AsyncRoundResult] = []
        try:
            for _ in range(num_rounds):
                t_wait = time.monotonic()
                while True:
                    if self._error is not None:
                        raise RuntimeError(
                            "rollout collector failed") from self._error
                    try:
                        item = self._queue.get(timeout=0.2)
                        break
                    except queue.Empty:
                        continue
                wait_s = time.monotonic() - t_wait
                results.append(self._train_on(item, wait_s))
        finally:
            self._stop.set()
            self._thread.join(timeout=30)
            # Collector is down — flush the last pending publication so
            # the serving engine always ends on the final params even
            # though intermediate publishes coalesce (latest wins). If
            # the join timed out (a wedged session), the collector still
            # owns publication: flushing here would race its next round
            # boundary and reintroduce the mid-round swap.
            if not self._thread.is_alive():
                self._flush_pending_publish()
        return results

    def _train_on(self, item: _Collected,
                  wait_s: float) -> AsyncRoundResult:
        staleness = self._version - item.behavior_version
        if not item.trajectories:
            return AsyncRoundResult(self.state, {}, item.episodes,
                                    staleness, wait_s)
        tokens, mask, rewards, group_ids = make_batch(
            item.trajectories, pad_id=self.pad_id, max_len=self.max_len)
        recorded = (make_batch_logps(item.trajectories, tokens, mask)
                    if self.importance_correction else None)
        # Shared explicit mesh placement (same path as grpo_round —
        # GSPMD propagation alone broadcasts host batches to all
        # devices before resharding).
        tokens, mask, rewards, group_ids, old_logp = place_batch_for_mesh(
            self.mesh, tokens, mask, rewards, group_ids, recorded,
            pad_id=self.pad_id, accum_steps=self.accum_steps)
        if (old_logp is None
                and (self.ppo_epochs > 1
                     or (self.importance_correction and staleness > 0))):
            # Multi-epoch updates REQUIRE frozen behavior logps —
            # without them epochs 2+ recompute ratio==1 against the
            # already-updated params and clipping never engages — so
            # they are computed here regardless of the
            # importance_correction flag (which governs only the
            # 1-epoch staleness case). Microbatched like the update.
            try:
                behavior = self.behavior_cache.get(item.behavior_version)
            except BehaviorParamsEvicted:
                # Collector outran the trainer past the cache bound:
                # degrade to ratio-1 under the CURRENT params (counted),
                # instead of crashing or pinning unbounded param trees.
                self.behavior_cache.note_ratio_one_fallback()
                behavior = self._merged_view(self.state.params)
            old_logp = behavior_logp_batched(behavior,
                                             self.model_config, tokens,
                                             self.accum_steps)

        ref_logp = None
        ref = self.ref_params     # single read: set_ref_params may swap
        if ref is not None and self.grpo_config.kl_coef > 0.0:
            ref_logp = behavior_logp_batched(ref, self.model_config,
                                             tokens, self.accum_steps)
        for _ in range(self.ppo_epochs):
            self.state, metrics = train_step(
                self.state, self.model_config, self.mesh, tokens, mask,
                rewards, group_ids, old_logp=old_logp, ref_logp=ref_logp,
                grpo_config=self.grpo_config,
                accum_steps=self.accum_steps, lora_base=self.lora_base)
        self._version += 1
        if self.publish_params is not None:
            # Defer to the collector's next round boundary (latest wins):
            # swapping engine weights mid-collection would invalidate the
            # behavior_params snapshot for in-flight episodes. Version
            # and params are staged TOGETHER so the collector's applied
            # snapshot is always a coherent pair.
            with self._publish_lock:
                # adapters staged raw; the O(model) fold happens at
                # flush (once per APPLIED publish, not per train round)
                self._pending_publish = (self._version, self.state.params)

        out = {k: float(v) for k, v in metrics.items()}
        if self.metrics_service is not None:
            ep = [e.reward for e in item.episodes]
            self.metrics_service.capture("Async GRPO Round", {
                "episodes": len(item.episodes),
                "staleness": staleness,
                "collect_s": round(item.collect_s, 3),
                "trainer_wait_s": round(wait_s, 3),
                "reward_mean": sum(ep) / max(len(ep), 1),
                **{k: round(v, 6) for k, v in out.items()},
            })
        return AsyncRoundResult(self.state, out, item.episodes,
                                staleness, wait_s)
