"""Jit-compiled 9-dimension chatMode-adaptive reward head.

Bit-level semantic port of ``_computeRewardSignals``
(``common/traceCollectorService.ts:668-788``). The TS implementation builds a
*variable-length* list of (name, value) dims — dims appear only when their
denominators are nonzero — and renormalizes weights over the *present* dims
(:777-784). The TPU design keeps a fixed-width ``(9,)`` dim vector plus a
``(9,)`` presence mask, so the computation is branchless, jittable, and
vmappable over a trace batch, while ``finalReward`` is numerically identical
to the TS weighted renormalized sum.

Threshold tables (traceCollectorService.ts:701-762, BASELINE.md):

==========================  =================  =================
quantity                    agent mode         normal mode
==========================  =================  =================
tool-fail severe/mod/minor  5 / 3 / 2          3 / 2 / 1
tool-count exc/good/fair    8 / 15 / 25        3 / 6 / 10
token exc/good/fair         5k / 15k / 30k     2k / 5k / 10k
LLM-call threshold T        3                  1
turn threshold T            3                  2
==========================  =================  =================
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.runtime_profile import ProfiledFunction
from ..traces import features as F
from ..traces.schema import Trace
from ..traces.features import trace_features

# Dim indices in the fixed-width reward vector.
D_USER_FEEDBACK = 0
D_TASK_COMPLETION = 1
D_TOOL_SUCCESS_RATE = 2
D_TOOL_CALL_RELIABILITY = 3
D_TOOL_CALL_EFFICIENCY = 4
D_TOOL_DURATION_EFFICIENCY = 5
D_RESPONSE_EFFICIENCY = 6
D_TOKEN_EFFICIENCY = 7
D_CONVERSATION_EFFICIENCY = 8
N_DIMS = 9

DIM_NAMES = (
    "user_feedback",
    "task_completion",
    "tool_success_rate",
    "tool_call_reliability",
    "tool_call_efficiency",
    "tool_duration_efficiency",
    "response_efficiency",
    "token_efficiency",
    "conversation_efficiency",
)

# Module constants are NUMPY on purpose: a jnp.array here would initialise
# the JAX backend at import, and on a TPU host the first process to do that
# owns the chip — a control server or report script that merely imports
# this package must not take it from the trainer.

# finalReward weights (traceCollectorService.ts:766-776).
WEIGHTS = np.array([0.25, 0.18, 0.12, 0.08, 0.05, 0.05, 0.08, 0.08, 0.11],
                   dtype=np.float32)

# Threshold tables, row 0 = normal, row 1 = agent.
_FAIL_T = np.array([[3.0, 2.0, 1.0], [5.0, 3.0, 2.0]],
                   np.float32)                   # severe/moderate/minor
_COUNT_T = np.array([[3.0, 6.0, 10.0], [8.0, 15.0, 25.0]],
                    np.float32)                  # excellent/good/fair
_TOKEN_T = np.array([[2000.0, 5000.0, 10000.0],
                     [5000.0, 15000.0, 30000.0]],
                    np.float32)                  # excellent/good/fair
_LLM_T = np.array([1.0, 3.0], np.float32)
_TURN_T = np.array([2.0, 3.0], np.float32)


class RewardOutput(NamedTuple):
    """Fixed-width reward head output for one trace (or a batch when vmapped)."""

    dims: jax.Array      # (9,) dim values; 0 where absent
    mask: jax.Array      # (9,) 1.0 where the dim is present
    final_reward: jax.Array  # () weight-renormalized sum over present dims


def reward_head(feat: jax.Array) -> RewardOutput:
    """Compute the 9-dim reward vector from one ``(N_FEATURES,)`` feature row.

    Pure, branchless; ``jax.vmap(reward_head)`` scores a whole trace store.
    """
    feat = feat.astype(jnp.float32)
    agent = feat[F.F_IS_AGENT].astype(jnp.int32)  # 0 normal / 1 agent
    fb = feat[F.F_FEEDBACK]
    ended = feat[F.F_ENDED] > 0.5
    has_err = feat[F.F_HAS_ERRORS] > 0.5
    tool_calls = feat[F.F_TOOL_CALLS]
    tool_ok = feat[F.F_TOOL_OK]
    tool_fail = feat[F.F_TOOL_FAIL]
    tool_dur = feat[F.F_TOOL_DURATION_MS]
    llm_calls = feat[F.F_LLM_CALLS]
    tokens = feat[F.F_TOKENS]
    turns = jnp.minimum(feat[F.F_USER_MSGS], feat[F.F_ASSISTANT_MSGS])
    good = fb > 0.5

    # Dim 1: user feedback (ref :677-679). Always present.
    d_feedback = fb

    # Dim 2: task completion (ref :682-692). Always present. The TS applies
    # the branches in source order, so `good` overrides everything.
    d_completion = jnp.float32(0.5)
    d_completion = jnp.where(ended & ~has_err, 0.8, d_completion)
    d_completion = jnp.where(has_err, -0.5, d_completion)
    d_completion = jnp.where(good, 1.0, d_completion)

    # Dim 3: tool success rate → [-1, 1] (ref :697-698).
    safe_calls = jnp.maximum(tool_calls, 1.0)
    d_success = (tool_ok / safe_calls) * 2.0 - 1.0

    # Dim 4: tool-call reliability, adaptive fail thresholds (ref :701-708).
    ft = jnp.asarray(_FAIL_T)[agent]
    d_reliability = jnp.where(
        tool_fail >= ft[0], -1.0,
        jnp.where(tool_fail >= ft[1], -0.5,
                  jnp.where(tool_fail >= ft[2], -0.2, 1.0)))

    # Dim 5: tool-call count efficiency (ref :710-718).
    ct = jnp.asarray(_COUNT_T)[agent]
    d_count = jnp.where(
        tool_calls > ct[2], -0.8,
        jnp.where(tool_calls > ct[1], -0.3,
                  jnp.where(tool_calls > ct[0], 0.3, 1.0)))

    # Dim 5b: tool duration efficiency, avg-duration bands (ref :721-729).
    avg_dur = tool_dur / safe_calls
    d_duration = jnp.where(
        avg_dur > 10000.0, -0.5,
        jnp.where(avg_dur > 3000.0, 0.0,
                  jnp.where(avg_dur > 1000.0, 0.5, 1.0)))

    # Dim 6: response efficiency (ref :733-737).
    llm_t = jnp.asarray(_LLM_T)[agent]
    d_response = jnp.maximum(
        -1.0, 1.0 - jnp.maximum(0.0, llm_calls - llm_t) * 0.4)

    # Dim 7: token efficiency (ref :740-749).
    tt = jnp.asarray(_TOKEN_T)[agent]
    d_token = jnp.where(
        tokens > tt[2], -0.5,
        jnp.where(tokens > tt[1], 0.0,
                  jnp.where(tokens > tt[0], 0.5, 1.0)))

    # Dim 8: conversation efficiency, turn bands (ref :752-763).
    turn_t = jnp.asarray(_TURN_T)[agent]
    d_turns = jnp.where(
        turns > turn_t * 3.0, -0.8,
        jnp.where(turns > turn_t * 2.0, -0.3,
                  jnp.where(turns > turn_t, 0.3, 1.0)))

    dims = jnp.stack([d_feedback, d_completion, d_success, d_reliability,
                      d_count, d_duration, d_response, d_token, d_turns])

    # Presence mask — dims appear only when denominators are nonzero
    # (ref: `if (s.totalToolCalls > 0)` :696, `totalToolDurationMs > 0` :720,
    # `totalLLMCalls > 0` :732, `totalTokens > 0` :739, `turns > 0` :755).
    has_tools = tool_calls > 0.0
    mask = jnp.stack([
        jnp.float32(1.0),                       # user_feedback: always
        jnp.float32(1.0),                       # task_completion: always
        has_tools.astype(jnp.float32),          # tool_success_rate
        has_tools.astype(jnp.float32),          # tool_call_reliability
        has_tools.astype(jnp.float32),          # tool_call_efficiency
        (has_tools & (tool_dur > 0.0)).astype(jnp.float32),
        (llm_calls > 0.0).astype(jnp.float32),  # response_efficiency
        (tokens > 0.0).astype(jnp.float32),     # token_efficiency
        (turns > 0.0).astype(jnp.float32),      # conversation_efficiency
    ])

    dims = dims * mask
    total_w = jnp.sum(WEIGHTS * mask)
    final = jnp.sum(dims * WEIGHTS) / jnp.maximum(total_w, 1e-12)
    return RewardOutput(dims=dims, mask=mask, final_reward=final)


# Jitted batch scorer: (B, N_FEATURES) -> RewardOutput of (B, 9)/(B, 9)/(B,).
# Profiled (obs/runtime_profile.py): batch-size variety is the expected
# retrace axis here — the ledger shows whether callers bucket batches.
reward_head_batch = ProfiledFunction(
    jax.jit(jax.vmap(reward_head)), "reward.head_batch",
    storm_threshold=32)
_reward_head_jit = jax.jit(reward_head)


def score_trace(trace: Trace) -> float:
    """Score one host-side trace in place, mirroring the reference's mutation
    of ``trace.summary`` (``_computeRewardSignals`` writes ``rewardDimensions``
    + ``finalReward``, traceCollectorService.ts:786-787)."""
    out = _reward_head_jit(jnp.asarray(trace_features(trace)))
    dims, mask = jax.device_get(out.dims), jax.device_get(out.mask)
    trace.summary.reward_dimensions = [
        {"name": DIM_NAMES[i], "value": float(dims[i])}
        for i in range(N_DIMS) if mask[i] > 0.5
    ]
    trace.summary.final_reward = float(jax.device_get(out.final_reward))
    return trace.summary.final_reward


def score_traces(traces) -> jax.Array:
    """Batch-score traces; returns the (B,) finalReward vector and updates
    each host trace's summary."""
    from ..traces.features import batch_features

    feats = batch_features(traces)
    if feats.shape[0] == 0:
        return jnp.zeros((0,), dtype=jnp.float32)
    out = reward_head_batch(jnp.asarray(feats))
    dims = jax.device_get(out.dims)
    masks = jax.device_get(out.mask)
    finals = jax.device_get(out.final_reward)
    for i, tr in enumerate(traces):
        tr.summary.reward_dimensions = [
            {"name": DIM_NAMES[j], "value": float(dims[i, j])}
            for j in range(N_DIMS) if masks[i, j] > 0.5
        ]
        tr.summary.final_reward = float(finals[i])
    return out.final_reward
