"""PerformanceMonitor thresholds + jax.profiler capture + grpo_round
wiring (round-1 review / SURVEY §5 tracing)."""

import os

import jax
import numpy as np

from senweaver_ide_tpu.agents.llm import LLMResponse, LLMUsage
from senweaver_ide_tpu.models import get_config
from senweaver_ide_tpu.rollout import RolloutSession
from senweaver_ide_tpu.services import MetricsService, PerformanceMonitor
from senweaver_ide_tpu.services.perf_monitor import profile_capture
from senweaver_ide_tpu.training import make_train_state
from senweaver_ide_tpu.training.rl_loop import grpo_round


def test_threshold_warning_captured():
    metrics = MetricsService()
    pm = PerformanceMonitor(metrics, thresholds_ms={"slow_stage": 5.0})
    pm.record_ms("slow_stage", 12.0, detail="x")
    pm.record_ms("slow_stage", 2.0)
    assert len(pm.warnings) == 1
    w = pm.warnings[0]
    assert w["stage"] == "slow_stage" and w["value"] == 12.0
    assert metrics.captured_count == 1
    assert pm.snapshot()["slow_stage"] == 2.0


def test_token_threshold():
    pm = PerformanceMonitor(token_thresholds={"system_message_tokens": 10})
    pm.record_tokens("system_message_tokens", 50)
    assert pm.warnings and pm.warnings[0]["unit"] == "tokens"


def test_record_tokens_lands_in_snapshot():
    """Token stages must show up in timings/snapshot like ms stages do
    (they were previously dropped on the floor)."""
    pm = PerformanceMonitor(token_thresholds={"system_message_tokens": 10})
    pm.record_tokens("system_message_tokens", 50)
    pm.record_tokens("prompt_tokens", 7)       # no threshold configured
    assert pm.timings["system_message_tokens"] == 50.0
    assert pm.snapshot()["prompt_tokens"] == 7.0


def test_registry_bridge_observes_stages():
    from senweaver_ide_tpu.obs import MetricsRegistry
    reg = MetricsRegistry()
    pm = PerformanceMonitor(thresholds_ms={"slow": 1.0}, registry=reg)
    pm.record_ms("slow", 4.0)
    pm.record_ms("ok", 0.5)
    hist = reg.get("senweaver_stage_ms")
    assert hist.snapshot(stage="slow")["count"] == 1
    assert hist.snapshot(stage="ok")["count"] == 1
    warns = reg.get("senweaver_perf_warnings_total")
    assert warns.value(stage="slow") == 1
    assert warns.value(stage="ok") == 0


def test_default_monitor_bridges_to_global_registry():
    """One exporter, not two: a bare PerformanceMonitor() lands its
    stages in the process-global obs registry (the /metrics endpoint),
    and registry=False is the explicit opt-out."""
    from senweaver_ide_tpu import obs
    obs._reset_for_tests()
    try:
        pm = PerformanceMonitor()
        pm.record_ms("bridge_check", 3.0)
        hist = obs.get_registry().get("senweaver_stage_ms")
        assert hist is not None
        assert hist.snapshot(stage="bridge_check")["count"] == 1

        off = PerformanceMonitor(registry=False)
        off.record_ms("unbridged", 1.0)
        assert hist.snapshot(stage="unbridged")["count"] == 0
        assert off.snapshot()["unbridged"] == 1.0
    finally:
        obs._reset_for_tests()


def test_stage_context_manager():
    pm = PerformanceMonitor()
    with pm.stage("batch_build"):
        pass
    assert "batch_build" in pm.timings


def test_profile_capture_writes_trace(tmp_path):
    with profile_capture(str(tmp_path / "prof")):
        np.asarray(jax.jit(lambda x: x * 2)(jax.numpy.ones((8, 8))))
    found = []
    for root, _, files in os.walk(tmp_path / "prof"):
        found += files
    assert found                       # trace events landed on disk


def test_profile_capture_noop_without_dir():
    with profile_capture(None):
        pass


def test_session_records_sysmsg_stage(tmp_path):
    class C:
        def chat(self, messages, **kw):
            return LLMResponse(text="ok", usage=LLMUsage(1, 1))

    pm = PerformanceMonitor()
    s = RolloutSession(C(), str(tmp_path / "ws"), perf_monitor=pm,
                       include_tool_definitions=False)
    s.system_message()
    assert "system_message_prep" in pm.timings
    s.close()


def test_grpo_round_wires_monitor_and_profiler(tmp_path):
    class C:
        def __init__(self):
            self.call_log = []

        def chat(self, messages, **kw):
            self.call_log.append(([1, 2], [3, 4]))
            return LLMResponse(text="done", usage=LLMUsage(5, 2))

    config = get_config("tiny-test")
    state = make_train_state(config, jax.random.PRNGKey(0), None,
                             learning_rate=1e-3)
    pm = PerformanceMonitor()
    n = [0]

    def make_session():
        n[0] += 1
        return RolloutSession(C(), str(tmp_path / f"ws{n[0]}"),
                              include_tool_definitions=False)

    out = grpo_round(state, config, None, make_session, ["t"],
                     group_size=2, perf_monitor=pm,
                     profile_dir=str(tmp_path / "prof"),
                     reward_override=lambda ti, g, s: float(g))
    assert np.isfinite(out.metrics["loss"])
    for stage in ("rollout_collect", "batch_build", "train_step"):
        assert stage in pm.timings
    assert any(files for _, _, files in os.walk(tmp_path / "prof"))
