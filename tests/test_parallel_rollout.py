"""Parallel rollout collection (round-1 review): episodes must drive
the engine's slot pool CONCURRENTLY, not one session at a time."""

import threading
import time

from senweaver_ide_tpu.agents.llm import LLMResponse, LLMUsage
from senweaver_ide_tpu.models import get_config
from senweaver_ide_tpu.models.tokenizer import ByteTokenizer
from senweaver_ide_tpu.models.transformer import init_params
from senweaver_ide_tpu.rollout import (EnginePolicyClient, RolloutEngine,
                                       RolloutSession)
from senweaver_ide_tpu.training.rl_loop import collect_group_trajectories

import jax
import numpy as np


class SlowScriptedClient:
    """Answers instantly but sleeps long enough that serial execution is
    provably distinguishable from parallel; tracks peak overlap."""

    current = 0
    peak = 0
    _lock = threading.Lock()
    call_log: list

    def __init__(self):
        self.call_log = []

    def chat(self, messages, *, temperature=None, max_tokens=None):
        cls = SlowScriptedClient
        with cls._lock:
            cls.current += 1
            cls.peak = max(cls.peak, cls.current)
        try:
            time.sleep(0.05)
            self.call_log.append(([1, 2, 3], [4, 5]))
            return LLMResponse(text="done", usage=LLMUsage(10, 2),
                               model="scripted")
        finally:
            with cls._lock:
                cls.current -= 1


def test_collection_overlaps_and_orders_deterministically(tmp_path):
    SlowScriptedClient.peak = 0
    n = [0]

    def make_session():
        n[0] += 1
        return RolloutSession(SlowScriptedClient(),
                              str(tmp_path / f"ws{n[0]}"),
                              include_tool_definitions=False)

    trajs, episodes = collect_group_trajectories(
        make_session, ["task A", "task B"], group_size=2, max_parallel=4)
    assert SlowScriptedClient.peak >= 2          # real overlap happened
    assert [(e.task_idx,) for e in episodes] == [(0,), (0,), (1,), (1,)]
    assert len(trajs) == 4
    assert all(t.group_id in (0, 1) for t in trajs)


def test_shared_engine_keeps_multiple_slots_busy(tmp_path):
    """The done-criterion: ≥2 engine slots concurrently active
    while collecting over ONE shared continuous-batching engine."""
    config = get_config("tiny-test")
    params = init_params(config, jax.random.PRNGKey(0))
    tok = ByteTokenizer()
    engine = RolloutEngine(params, config, num_slots=4, max_len=2048,
                           eos_id=None, seed=0)

    peak_active = [0]
    orig_step = engine._step

    def instrumented_step():
        active = sum(r is not None for r in engine._slot_req)
        peak_active[0] = max(peak_active[0], active)
        return orig_step()

    engine._step = instrumented_step

    n = [0]

    def make_session():
        n[0] += 1
        client = EnginePolicyClient(engine, tok, default_max_new_tokens=16,
                                    record_calls=True)
        return RolloutSession(client, str(tmp_path / f"ws{n[0]}"),
                              include_tool_definitions=False)

    trajs, episodes = collect_group_trajectories(
        make_session, ["short task"], group_size=3, max_parallel=4)
    assert peak_active[0] >= 2
    assert len(episodes) == 3
    assert all(e.n_calls >= 1 for e in episodes)


def test_grpo_round_on_sp_mesh_shards_batch(tmp_path):
    """grpo_round's explicit device_put must not crash on an sp>1 mesh:
    S is padded to k·sp+1 (training length divisible), so the (B, S)
    arrays place batch-only and reshard onto sp in-graph."""
    import dataclasses

    from senweaver_ide_tpu.parallel import MeshConfig, make_mesh
    from senweaver_ide_tpu.training import make_train_state
    from senweaver_ide_tpu.training.rl_loop import grpo_round

    config = dataclasses.replace(get_config("tiny-test"), attn_impl="ring")
    mesh = make_mesh(MeshConfig(dp=2, fsdp=2, sp=2))
    state = make_train_state(config, jax.random.PRNGKey(0), mesh,
                             learning_rate=1e-3)
    n = [0]

    def make_session():
        n[0] += 1
        return RolloutSession(SlowScriptedClient(),
                              str(tmp_path / f"sp{n[0]}"),
                              include_tool_definitions=False)

    out = grpo_round(state, config, mesh, make_session, ["t1", "t2"],
                     group_size=2,
                     reward_override=lambda ti, g, s: float(g))
    assert np.isfinite(out.metrics["loss"])
    assert len(out.episodes) == 4


def test_max_parallel_one_is_sequential(tmp_path):
    SlowScriptedClient.peak = 0
    n = [0]

    def make_session():
        n[0] += 1
        return RolloutSession(SlowScriptedClient(),
                              str(tmp_path / f"ws{n[0]}"),
                              include_tool_definitions=False)

    collect_group_trajectories(make_session, ["t"], group_size=3,
                               max_parallel=1)
    assert SlowScriptedClient.peak == 1
