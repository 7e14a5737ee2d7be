"""GRPO training-health observatory (PR 9): the jitted diagnostics
head (rank spectrum / credit entropy / zero groups / NaN safety), the
threshold detectors + monitor surfaces (gauges, ring, worst-K), the
streak-hysteresis mitigations (RLOO, token credit, group size), the
chaos path (NaN rewards vetoed AND counted), and jit purity."""

import json
import math
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from senweaver_ide_tpu import analysis, obs
from senweaver_ide_tpu.models import get_config
from senweaver_ide_tpu.resilience import (REASON_NONFINITE_LOSS,
                                          FaultPlan, FaultSpec,
                                          HealthMitigator,
                                          MITIGATION_GROUP_SIZE,
                                          MITIGATION_LEAVE_ONE_OUT,
                                          ResilienceConfig)
from senweaver_ide_tpu.training import (GroupSizeScheduler, grpo_round,
                                        make_train_state,
                                        token_credit_weights)
from senweaver_ide_tpu.training.diagnostics import (
    DiagnosticsConfig, advantage_stats, dispatch_round_health,
    finalize_round_health)
from senweaver_ide_tpu.training.grpo import (GRPOConfig,
                                             group_relative_advantages,
                                             grpo_objective)


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs._reset_for_tests()
    yield
    obs._reset_for_tests()


@pytest.fixture(scope="module")
def tiny_rl():
    cfg = get_config("tiny-test")
    state = make_train_state(cfg, jax.random.PRNGKey(0), None,
                             learning_rate=1e-3)
    return cfg, state


def _health(rewards, gids, mask, config=DiagnosticsConfig(), **kw):
    return finalize_round_health(
        dispatch_round_health(np.asarray(rewards, dtype=np.float32),
                              np.asarray(gids), np.asarray(mask),
                              config=config, **kw))


def _degenerate_batch(groups=6, group_size=4, seq=16):
    """All groups reward-tied (or epsilon-split under the std floor) and
    sharing one mask profile — the advantage matrix collapses."""
    b = groups * group_size
    gids = np.repeat(np.arange(groups), group_size)
    rewards = np.ones(b, dtype=np.float32)
    rewards[-group_size:] = (0.0, 0.0, 0.0, 1e-7)
    mask = np.zeros((b, seq), dtype=bool)
    lens = (seq, seq - 4, seq - 8, seq - 12)
    for g in range(groups):
        for i in range(group_size):
            mask[g * group_size + i, : lens[i]] = True
    return rewards, gids, mask


def _healthy_batch(groups=6, group_size=4, seq=16, seed=0):
    rng = np.random.default_rng(seed)
    b = groups * group_size
    gids = np.repeat(np.arange(groups), group_size)
    rewards = rng.normal(size=b).astype(np.float32)
    mask = np.zeros((b, seq), dtype=bool)
    for row in range(b):
        mask[row, : int(rng.integers(4, seq + 1))] = True
    return rewards, gids, mask


# ---- diagnostics head: rank spectrum / entropy / degeneracy ----

def test_degenerate_batch_collapses_rank_and_zero_groups():
    h = _health(*_degenerate_batch())
    assert h["zero_advantage_group_fraction"] > 0.5
    assert h["rank_fraction"] <= 0.25
    assert h["effective_rank"] >= 1.0
    triggers = obs.evaluate_health(h)
    assert "rank_collapse" in triggers
    assert "zero_groups" in triggers


def test_healthy_batch_trips_nothing():
    h = _health(*_healthy_batch())
    assert h["zero_advantage_group_fraction"] <= 0.5
    assert h["rank_fraction"] > 0.25
    assert h["nonfinite_reward_fraction"] == 0.0
    assert obs.evaluate_health(h) == []


def test_rank_fraction_bounded_and_participation_sane():
    h = _health(*_healthy_batch(seed=3))
    assert 0.0 < h["rank_fraction"] <= 1.0 + 1e-6
    assert h["participation_ratio"] >= 1.0
    assert h["top_singular_value"] > 0.0


def test_credit_entropy_spread_vs_concentrated():
    # Entropy of the |advantage| mass over the batch's masked tokens,
    # normalized to [0, 1]. Spread mass -> near 1; mass pinched onto a
    # couple of tokens (signal group masked 1 token each, zero-signal
    # group carrying the mask bulk) -> near 0 + credit_collapse trip.
    gids = np.zeros(4, dtype=np.int64)
    rewards = np.array([1.0, -1.0, 0.5, -0.5], dtype=np.float32)
    uniform = np.ones((4, 8), dtype=bool)
    h_u = _health(rewards, gids, uniform)
    assert h_u["credit_entropy"] > 0.9

    gids2 = np.array([0, 0, 1, 1])
    rewards2 = np.array([1.0, -1.0, 0.0, 0.0], dtype=np.float32)
    seq = 64
    conc = np.zeros((4, seq), dtype=bool)
    conc[0, 0] = conc[1, 1] = True    # the only tokens with |adv| > 0
    conc[2:, :] = True                # tied group holds the mask bulk
    h_c = _health(rewards2, gids2, conc)
    assert h_c["credit_entropy"] < 0.2
    assert h_c["credit_entropy"] < h_u["credit_entropy"]
    assert "credit_collapse" in obs.evaluate_health(h_c)


def test_nonfinite_rewards_reported_not_propagated():
    rewards, gids, mask = _healthy_batch()
    rewards = rewards.copy()
    rewards[0] = np.nan
    rewards[5] = np.inf
    h = _health(rewards, gids, mask)
    assert h["nonfinite_reward_fraction"] == pytest.approx(2 / 24)
    for key, v in h.items():
        assert math.isfinite(v), (key, v)
    assert "nonfinite_rewards" in obs.evaluate_health(h)


# ---- legacy advantage_stats wrapper (pinned contract + NaN safety) ----

def test_advantage_stats_pinned_values():
    s = advantage_stats([1.0, 1.0, 0.0, 2.0], [0, 0, 1, 1])
    assert s["groups"] == 2
    assert s["zero_advantage_group_fraction"] == pytest.approx(0.5)
    assert s["advantage_std"] == pytest.approx(math.sqrt(0.5))
    tied = advantage_stats([3.0] * 4, [0, 0, 1, 1])
    assert tied["zero_advantage_group_fraction"] == 1.0
    assert tied["advantage_std"] == 0.0
    assert advantage_stats([], [])["groups"] == 0
    assert advantage_stats([1.0], [0, 1])["groups"] == 0


def test_advantage_stats_nan_safe():
    s = advantage_stats([float("nan"), 1.0, 0.0, 2.0], [0, 0, 1, 1])
    assert s["nonfinite_reward_fraction"] == pytest.approx(0.25)
    assert math.isfinite(s["advantage_std"])
    assert math.isfinite(s["zero_advantage_group_fraction"])


# ---- mitigation math: RLOO + token credit + grad sparsity ----

def test_leave_one_out_advantages_match_closed_form():
    rewards = jnp.array([1.0, 2.0, 3.0, 7.0])
    gids = jnp.array([0, 0, 0, 1])
    adv = group_relative_advantages(rewards, gids, 2, leave_one_out=True)
    # adv_i = r_i - mean(others) = (n/(n-1)) * (r_i - mean)
    np.testing.assert_allclose(np.asarray(adv[:3]),
                               [-1.5, 0.0, 1.5], atol=1e-6)
    assert float(adv[3]) == 0.0      # n=1 group centers to zero


def test_token_credit_weights_mean_one_and_monotone():
    mask = jnp.array([[True] * 6 + [False] * 2,
                      [False] * 8])
    w = token_credit_weights(mask, 0.9)
    row = np.asarray(w[0])
    assert row[:6].mean() == pytest.approx(1.0, abs=1e-5)
    assert np.all(np.diff(row[:6]) > 0)   # later tokens carry more credit
    assert np.asarray(w[1]).sum() == 0.0  # empty row stays zeros
    uniform = token_credit_weights(mask, 1.0)
    np.testing.assert_allclose(np.asarray(uniform[0][:6]), 1.0, atol=1e-6)


def test_grpo_objective_reports_grad_sparsity():
    b, s = 4, 6
    logp = jnp.zeros((b, s))
    old = jnp.zeros((b, s))
    mask = jnp.ones((b, s), dtype=bool)
    adv = jnp.array([0.0, 0.0, 0.0, 2.0])   # 3 of 4 rows contribute nothing
    _, metrics = grpo_objective(logp, old, adv, mask, GRPOConfig())
    assert metrics["grad_sparsity"] == pytest.approx(0.75)
    adv2 = jnp.array([1.0, -1.0, 2.0, -2.0])
    _, m2 = grpo_objective(logp, old, adv2, mask, GRPOConfig())
    assert m2["grad_sparsity"] == 0.0


def test_loo_changes_degenerate_spectrum():
    batch = _degenerate_batch()
    base = _health(*batch)
    loo = _health(*batch, config=DiagnosticsConfig(leave_one_out=True))
    ratio = base["top_singular_value"] / max(loo["top_singular_value"],
                                             1e-30)
    assert ratio > 10.0 or ratio < 0.1


# ---- detectors + monitor surfaces ----

def test_evaluate_health_disabled_detector_never_trips():
    h = {"rank_fraction": 0.01, "kl_to_anchor": 99.0}
    cfg = obs.TrainingHealthConfig(rank_fraction_min=None, kl_max=0.5)
    assert obs.evaluate_health(h, cfg) == ["kl_drift"]
    assert obs.evaluate_health({}, cfg) == []   # missing keys never trip


def test_monitor_gauges_ring_and_worst_k(tmp_path):
    monitor = obs.get_health_monitor()
    registry = obs.get_registry()
    healthy = _health(*_healthy_batch())
    bad = _health(*_degenerate_batch())
    assert monitor.observe(healthy, round_index=0) == []
    triggers = monitor.observe(bad, round_index=1)
    assert "rank_collapse" in triggers
    assert registry.get("senweaver_grpo_health_rank_fraction").value() \
        == pytest.approx(bad["rank_fraction"])
    assert registry.get("senweaver_grpo_health_rounds_total").value() == 2
    trig = registry.get("senweaver_grpo_health_triggers_total")
    totals = {k[0]: v for k, v in trig.samples().items()}
    assert totals.get("rank_collapse") == 1
    # score: round 2 tripped some but not all enabled detectors
    score = registry.get("senweaver_grpo_health_score").value()
    assert 0.0 < score < 1.0
    # ring oldest-first; worst-K leads with the tripped round
    hist = monitor.history()
    assert len(hist) == 2 and hist[0]["triggers"] == []
    worst = monitor.worst_rounds()
    assert worst[0]["triggers"] == triggers
    path = monitor.export_jsonl(str(tmp_path / "ring.jsonl"))
    with open(path) as f:
        ring = [json.loads(line) for line in f if line.strip()]
    assert len(ring) == 2
    assert ring[1]["health"]["rank_fraction"] == \
        pytest.approx(bad["rank_fraction"])
    summary = monitor.summary()
    assert summary["rounds"] == 2
    assert summary["trigger_counts"]["rank_collapse"] == 1


def test_record_round_publishes_health():
    telemetry = obs.StepTelemetry()
    h = _health(*_degenerate_batch())
    out = telemetry.record_round(
        collect_s=0.1, batch_build_s=0.01, train_s=0.05,
        batch_tokens=64, episodes=4,
        health=h, health_triggers=obs.evaluate_health(h),
        round_index=0)
    assert "rank_collapse" in out["health_triggers"]
    assert obs.get_health_monitor().summary()["rounds"] == 1
    # the PR-8 gauges stay live from the richer health dict
    reg = obs.get_registry()
    assert reg.get("senweaver_grpo_zero_advantage_group_fraction") \
        .value() == pytest.approx(h["zero_advantage_group_fraction"])


# ---- mitigator hysteresis + scheduler ----

def test_mitigator_streak_enable_disable():
    m = HealthMitigator(enabled=True, trigger_rounds=2)
    cfg = GRPOConfig()
    eff, ev = m.apply(cfg, ["rank_collapse"])
    assert not eff.leave_one_out and ev == []      # streak 1: observe
    eff, ev = m.apply(cfg, ["rank_collapse"])
    assert eff.leave_one_out                        # streak 2: enable
    assert "mitigation_enabled:leave_one_out" in ev
    assert m.effective(cfg).leave_one_out           # sticky between rounds
    eff, ev = m.apply(cfg, [])
    assert eff.leave_one_out and ev == []          # quiet 1: still on
    eff, ev = m.apply(cfg, [])
    assert not eff.leave_one_out                    # quiet 2: disable
    assert "mitigation_disabled:leave_one_out" in ev


def test_mitigator_vetoes_once_per_streak_when_gated_off():
    registry = obs.get_registry()
    m = HealthMitigator(enabled=False, trigger_rounds=1)
    _, ev1 = m.apply(GRPOConfig(), ["rank_collapse"])
    assert "mitigation_vetoed:leave_one_out" in ev1
    _, ev2 = m.apply(GRPOConfig(), ["rank_collapse"])
    assert ev2 == []                                # same streak: once
    _, _ = m.apply(GRPOConfig(), [])                # streak breaks
    _, ev3 = m.apply(GRPOConfig(), ["rank_collapse"])
    assert "mitigation_vetoed:leave_one_out" in ev3
    mits = registry.get("senweaver_grpo_health_mitigations_total")
    totals = {k: v for k, v in mits.samples().items()}
    assert totals[("leave_one_out", "vetoed")] == 2


def test_mitigator_post_step_triggers_feed_next_round():
    m = HealthMitigator(enabled=True, trigger_rounds=1)
    m.note_post_step(["grad_sparsity"])
    eff, ev = m.apply(GRPOConfig(), [])
    assert eff.token_level_advantages
    assert "mitigation_enabled:token_level_advantages" in ev


def test_group_size_scheduler_doubles_and_decays():
    s = GroupSizeScheduler(4, max_size=16)
    assert s.update(True) == (8, ["group_size_increased:8"])
    assert s.update(True) == (16, ["group_size_increased:16"])
    assert s.update(True) == (16, [])               # saturated
    assert s.update(False) == (8, ["group_size_decreased:8"])
    assert s.update(False) == (4, ["group_size_decreased:4"])
    assert s.update(False) == (4, [])               # back at base
    reg = obs.get_registry()
    assert reg.get("senweaver_grpo_group_size").value() == 4.0


def test_mitigator_from_config_respects_gates():
    res = ResilienceConfig(health_mitigations=True,
                           mitigate_group_size=True,
                           health_trigger_rounds=1)
    m = HealthMitigator.from_config(res)
    _, ev = m.apply(GRPOConfig(), ["zero_groups"])
    assert m.group_size_active()
    assert any(e == f"mitigation_enabled:{MITIGATION_GROUP_SIZE}"
               for e in ev)
    assert m.active[MITIGATION_LEAVE_ONE_OUT]


# ---- chaos: NaN rounds vetoed AND counted ----

class _TurnOut:
    def __init__(self):
        self.trace = None
        self.loop = types.SimpleNamespace(steps=1)


class _TinySession:
    def __init__(self, log):
        self.client = types.SimpleNamespace(call_log=[])
        self.closed = False
        self.thread_id = "tiny"
        log.append(self)

    def run_turn(self, task):
        self.client.call_log.append(([1, 2, 3], [4, 5]))
        return _TurnOut()

    def close(self):
        self.closed = True


def test_nan_round_vetoed_and_health_counted(tiny_rl):
    cfg, state = tiny_rl
    log = []
    plan = FaultPlan([FaultSpec(0, 0, 0, "nan_reward")])
    res = ResilienceConfig(episode_retries=0)

    def reward(ti, g, session):
        return 1.0 if g % 2 == 0 else -1.0

    out = grpo_round(state, cfg, None,
                     plan.wrap_factory(lambda: _TinySession(log)), ["t"],
                     group_size=2, max_len=256, max_parallel=1,
                     resilience=res,
                     reward_override=plan.wrap_reward(reward))
    assert out.update_skipped == REASON_NONFINITE_LOSS
    assert "nonfinite_rewards" in out.health_triggers
    assert out.health["nonfinite_reward_fraction"] > 0.0
    assert f"update_skipped:{REASON_NONFINITE_LOSS}" in out.health_events
    reg = obs.get_registry()
    skips = reg.counter("senweaver_guard_skips_total",
                        labelnames=("reason",))
    assert skips.value(reason=REASON_NONFINITE_LOSS) == 1
    trig = reg.get("senweaver_grpo_health_triggers_total")
    totals = {k[0]: v for k, v in trig.samples().items()}
    assert totals.get("nonfinite_rewards") == 1


def test_healthy_round_populates_health(tiny_rl):
    cfg, state = tiny_rl
    log = []
    rewards = iter([1.0, -1.0, 0.5, -0.5])

    out = grpo_round(state, cfg, None,
                     lambda: _TinySession(log), ["a", "b"],
                     group_size=2, max_len=256, max_parallel=1,
                     reward_override=lambda ti, g, s: next(rewards))
    assert out.update_skipped is None
    for key in ("rank_fraction", "credit_entropy", "grad_sparsity",
                "policy_entropy", "kl_to_anchor"):
        assert key in out.health, key
        assert math.isfinite(out.health[key])
    assert out.health["groups"] == 2.0


# ---- jit purity + the observatory end to end ----

def test_jit_lint_no_new_findings():
    lint = analysis.run_package()
    assert not lint.new, [f.format() for f in lint.new]


def test_degenerate_rounds_enable_loo_and_the_report_reads_the_ring(
        tmp_path, capsys):
    """One healthy round, then three degenerate ones through monitor,
    mitigator and scheduler together: round 1 observes, round 2 turns
    leave-one-out on, and ``scripts/training_health_report.py`` reads
    the exported ring back."""
    import importlib.util
    import pathlib

    monitor = obs.get_health_monitor()
    assert monitor.observe(_health(*_healthy_batch()),
                           round_index=0) == []

    grpo_config = GRPOConfig()
    mitigator = HealthMitigator(enabled=True,
                                allow={MITIGATION_LEAVE_ONE_OUT: True},
                                trigger_rounds=2)
    scheduler = GroupSizeScheduler(4, max_size=16)
    rounds = []
    for r in range(1, 4):
        health = _health(*_degenerate_batch(),
                         config=DiagnosticsConfig.from_grpo(
                             mitigator.effective(grpo_config)))
        triggers = obs.evaluate_health(health, monitor.config)
        effective, events = mitigator.apply(grpo_config, triggers)
        monitor.observe(health, round_index=r, triggers=triggers,
                        events=events)
        scheduler.update(mitigator.group_size_active())
        rounds.append((health, triggers, events))
    assert {"rank_collapse", "zero_groups"} <= set(rounds[0][1])
    assert rounds[0][2] == []
    assert "mitigation_enabled:leave_one_out" in rounds[1][2]
    assert effective.leave_one_out
    assert obs.get_registry().get(
        "senweaver_grpo_health_rank_fraction").value() \
        == rounds[-1][0]["rank_fraction"]

    ring = monitor.export_jsonl(str(tmp_path / "ring.jsonl"))
    path = (pathlib.Path(__file__).resolve().parents[1] / "scripts"
            / "training_health_report.py")
    spec = importlib.util.spec_from_file_location("thr_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main([ring]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["rounds"] == 4
    assert report["trigger_counts"]["rank_collapse"] >= 3
    assert report["worst_rounds"][0]["triggers"]
    assert mod.main([str(tmp_path / "absent.jsonl")]) == 2
