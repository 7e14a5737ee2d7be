"""Runtime performance observatory (obs/runtime_profile.py): the
compile/retrace ledger, device-time windows, transfer accounting, HBM
watermark sampling, and the engine retrace-regression gate.

The load-bearing claims under test:
- the ledger attributes compiles to distinct abstract signatures and
  proves (not assumes) that steady-state calls stop compiling,
- the storm detector separates a healthy bucket ladder (compile-once,
  amortized) from a per-call retrace pattern,
- transfer accounting sees host->device feeds (np.ndarray args) and
  device->host reads (profiled_device_get),
- memory sampling degrades gracefully on CPU (no memory_stats) to
  live-buffer accounting with a ``backend`` label, never raising,
- the engine's paged fused step compiles exactly once per shape bucket
  across varying occupancy — the runtime counterpart of the static
  JIT201-203 lints.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import senweaver_ide_tpu.obs as obs
from senweaver_ide_tpu.obs.runtime_profile import (ProfiledFunction,
                                                   get_profiler,
                                                   profiled_device_get,
                                                   sample_memory, wrap)


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs._reset_for_tests()
    yield
    obs._reset_for_tests()


# ---------------------------------------------------------------------------
# ledger: calls, compiles, signatures
# ---------------------------------------------------------------------------

def test_ledger_counts_calls_compiles_signatures():
    f = wrap(jax.jit(lambda x: x * 2), "t.ledger")
    for _ in range(3):
        f(jnp.ones((4,)))
    snap = get_profiler().ledger()["t.ledger"]
    assert snap["calls"] == 3
    assert snap["compiles"] == 1
    assert len(snap["signatures"]) == 1
    assert snap["signatures"][0]["compiles"] == 1
    assert snap["signatures"][0]["calls"] == 3

    f(jnp.ones((8,)))          # new abstract signature -> one compile
    snap = get_profiler().ledger()["t.ledger"]
    assert snap["compiles"] == 2
    assert len(snap["signatures"]) == 2


def test_compile_wall_time_attributed():
    f = wrap(jax.jit(lambda x: (x @ x).sum()), "t.walltime")
    f(jnp.ones((16, 16)))
    snap = get_profiler().ledger()["t.walltime"]
    # jax.monitoring compile events land in the frame around the first
    # call; steady calls must not add compile time.
    assert snap["compile_ms"] > 0.0
    before = snap["compile_ms"]
    f(jnp.ones((16, 16)))
    assert get_profiler().ledger()["t.walltime"]["compile_ms"] == before


def test_step_time_recorded_for_blocking_wrap():
    f = wrap(jax.jit(lambda x: x + 1), "t.step")
    f(jnp.ones((4,)))
    snap = get_profiler().ledger()["t.step"]
    assert snap["blocking"] is True
    assert snap["last_step_ms"] > 0.0


def test_a_caller_times_the_step_of_a_non_blocking_wrap():
    """A non-blocking wrapper sees the dispatch alone. A caller that knows
    when the results reached the host times the step (``begin_step`` /
    ``end_step``, as ``RolloutEngine._step_paged`` does for
    ``engine.fused_step``): the ledger's step times and the histogram are
    then the caller's, one observation a call."""
    import time
    f = wrap(jax.jit(lambda x: x + 1), "t.report", block=False)
    prof = get_profiler()
    for pause in (0.02, 0.03):
        t0 = prof.begin_step("t.report")
        out = f(jnp.ones((4,)))
        time.sleep(pause)
        np.asarray(out)
        prof.end_step("t.report", t0)
    snap = prof.ledger()["t.report"]
    assert snap["blocking"] is False
    assert snap["calls"] == 2 and snap["compiles"] == 1
    assert snap["last_step_ms"] >= 30.0 and snap["step_ms_sum"] >= 50.0
    hist = obs.get_registry().get("senweaver_runtime_step_ms")
    got = hist.snapshot(fn="t.report")
    assert got["count"] == 2 and got["sum"] >= 50.0
    # the wrapper alone, as the trainer's wrap runs: it times the dispatch
    g = wrap(jax.jit(lambda x: x * 3), "t.own", block=False)
    g(jnp.ones((4,)))
    assert prof.ledger()["t.own"]["last_step_ms"] > 0.0
    assert hist.snapshot(fn="t.own")["count"] == 1
    # a blocking wrap keeps its own times whoever claims them
    h = wrap(jax.jit(lambda x: x - 3), "t.blocks")
    prof.end_step("t.blocks", prof.begin_step("t.blocks"))   # no ledger yet
    h(jnp.ones((4,)))
    assert hist.snapshot(fn="t.blocks")["count"] == 1
    get_profiler().set_enabled(False)
    assert prof.begin_step("t.report") == 0.0
    prof.end_step("t.report", 0.0)
    assert prof.ledger()["t.report"]["calls"] == 2


def test_unqueued_and_step_time_tile_the_span_of_the_steps():
    """Between one ``end_step`` and its caller's next ``begin_step`` the
    caller had no step in flight: that time is ``unqueued_ms_sum`` and one
    histogram observation, from the second step on, with no clock read of
    its own — so the two sums tile first begin to last end exactly."""
    import time
    wrap(jax.jit(lambda x: x + 1), "t.tile", block=False)(jnp.ones((4,)))
    prof = get_profiler()
    own = prof.ledger()["t.tile"]["step_ms_sum"]    # the wrapper's dispatch
    begins, ends = [], [0.0]
    for pause in (0.0, 0.02, 0.005, 0.03):
        time.sleep(pause)                       # the host between steps
        begins.append(prof.begin_step("t.tile"))
        time.sleep(0.01)                        # the step in flight
        ends.append(prof.end_step("t.tile", begins[-1], ends[-1]))
    between = [(b - e) * 1e3 for b, e in zip(begins[1:], ends[1:])]
    assert between[0] >= 20.0 and between[2] >= 30.0 > between[1] >= 5.0
    snap = prof.ledger()["t.tile"]
    assert snap["unqueued_ms_sum"] == pytest.approx(sum(between), abs=0.002)
    assert snap["unqueued_ms_sum"] + snap["step_ms_sum"] - own == \
        pytest.approx((ends[-1] - begins[0]) * 1e3, abs=0.005)
    hist = obs.get_registry().get("senweaver_runtime_unqueued_ms")
    got = hist.snapshot(fn="t.tile")
    assert got["count"] == 3
    assert got["sum"] == pytest.approx(snap["unqueued_ms_sum"], abs=0.002)
    text = obs.get_registry().render()
    assert 'senweaver_runtime_unqueued_ms_count{fn="t.tile"} 3' in text
    # a step begun before the last one ended (a caller that runs a step
    # ahead) had one in flight all the time: 0 is observed, never a
    # negative reading
    t = prof.begin_step("t.tile")
    assert prof.end_step("t.tile", t, t + 1.0) > t
    assert hist.snapshot(fn="t.tile")["count"] == 4
    assert hist.snapshot(fn="t.tile")["sum"] == pytest.approx(got["sum"])
    # off: nothing is read, nothing returned
    prof.set_enabled(False)
    assert prof.end_step("t.tile", prof.begin_step("t.tile"), t) == 0.0
    assert prof.ledger()["t.tile"]["unqueued_ms_sum"] == \
        snap["unqueued_ms_sum"]


def test_two_callers_of_one_name_each_count_their_own_unqueued_time():
    """Several engines step in one process (``serve/replica.py``'s stepper
    threads), all under ``engine.fused_step``: one's launch precedes
    another's fetch. Each hands ``end_step`` its OWN last reading, so the
    name's sum is the sum of the callers' sums and no observation is
    negative, however the threads interleave."""
    import threading
    import time
    wrap(jax.jit(lambda x: x + 1), "t.two", block=False)(jnp.ones((4,)))
    prof = get_profiler()
    hist = obs.get_registry().get("senweaver_runtime_unqueued_ms")
    mine = {}

    def engine(tag, host_s, step_s, steps):
        last, total = 0.0, 0.0
        for _ in range(steps):
            time.sleep(host_s)
            t = prof.begin_step("t.two")
            time.sleep(step_s)                  # the other one ends in here
            if last:
                total += (t - last) * 1e3
            last = prof.end_step("t.two", t, last)
        mine[tag] = total

    threads = [threading.Thread(target=engine, args=a) for a in
               (("a", 0.002, 0.011, 9), ("b", 0.003, 0.007, 12))]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    snap = prof.ledger()["t.two"]
    got = hist.snapshot(fn="t.two")
    assert got["count"] == (9 - 1) + (12 - 1)
    assert mine["a"] >= 8 * 2.0 and mine["b"] >= 11 * 3.0
    assert snap["unqueued_ms_sum"] == pytest.approx(
        mine["a"] + mine["b"], abs=0.01)
    assert got["sum"] == pytest.approx(snap["unqueued_ms_sum"], abs=0.01)


def test_the_engines_unqueued_time_is_live_with_tracing_off():
    """``RolloutEngine._step_paged`` brackets every fused step, tracing on
    or off: the ledger's two sums tile the loop from the first launch to
    the last fetch, and nothing was recorded."""
    from senweaver_ide_tpu.models import init_params, tiny_test
    from senweaver_ide_tpu.rollout import EngineConfig, RolloutEngine
    config = tiny_test()
    eng = RolloutEngine(init_params(config, jax.random.PRNGKey(0)), config,
                        num_slots=2, max_len=32,
                        engine_config=EngineConfig(kv_layout="paged",
                                                   block_size=4))
    prof = get_profiler()
    begins = []
    begin = prof.begin_step
    prof.begin_step = lambda name: begins.append(begin(name)) or begins[-1]
    eng.submit([5, 9, 2, 7], max_new_tokens=6)
    while eng.has_work:
        eng.step()
    assert not obs.is_enabled() and obs.get_tracer().spans() == []
    snap = prof.ledger()["engine.fused_step"]
    steps = eng.stats()["decode_steps"]
    assert len(begins) == steps > 3
    assert snap["unqueued_ms_sum"] > 0.0
    assert snap["unqueued_ms_sum"] + snap["step_ms_sum"] == pytest.approx(
        (eng._fetched_at - begins[0]) * 1e3, abs=0.01)
    hist = obs.get_registry().get("senweaver_runtime_unqueued_ms")
    assert hist.snapshot(fn="engine.fused_step")["count"] == steps - 1


def test_the_engines_step_ms_is_launch_to_fetch_by_its_own_report():
    """``engine.fused_step`` does not block in its wrapper: ``step_ms`` is
    what ``_step_paged`` reports, launch to tokens on the host, so it
    covers ``engine.fetch``'s wait."""
    from senweaver_ide_tpu.models import init_params, tiny_test
    from senweaver_ide_tpu.rollout import EngineConfig, RolloutEngine
    config = tiny_test()
    eng = RolloutEngine(init_params(config, jax.random.PRNGKey(0)), config,
                        num_slots=2, max_len=32,
                        engine_config=EngineConfig(kv_layout="paged"))
    obs.enable()
    eng.submit([3, 1, 4, 1, 5], max_new_tokens=4)
    eng.run()
    snap = get_profiler().ledger()["engine.fused_step"]
    spans = obs.get_tracer().spans()
    waits = [s.attrs["wait_ms"] for s in spans if s.name == "engine.fetch"]
    assert snap["blocking"] is False and snap["calls"] == len(waits) == 4
    assert snap["step_ms_sum"] >= sum(waits) > 0.0
    assert snap["last_step_ms"] >= waits[-1]
    assert not any(s.name == "engine.fused_step.wait" for s in spans)


# ---------------------------------------------------------------------------
# retrace storms
# ---------------------------------------------------------------------------

def test_storm_fires_on_per_call_retraces():
    f = wrap(jax.jit(lambda x: x * 2), "t.storm", storm_threshold=4)
    for n in range(1, 11):
        f(jnp.ones((n,)))      # every call a fresh shape
    snap = get_profiler().ledger()["t.storm"]
    assert snap["compiles"] == 10
    assert snap["storms"] > 0
    events = get_profiler().storm_events
    assert any(e["fn"] == "t.storm" for e in events)
    m = obs.get_registry().get("senweaver_runtime_retrace_storms_total")
    assert m is not None and m.value(fn="t.storm") > 0


def test_no_storm_on_amortized_bucket_ladder():
    # A bucket ladder compiles a handful of shapes ONCE and then reuses
    # them — calls greatly outnumber compiles, the detector stays
    # quiet. This is the wrap-site contract: storm_threshold must be
    # sized ABOVE the legitimate ladder (engine.fused_step uses 64 for
    # exactly this reason); then warmup never trips and only a
    # per-call retrace pattern can reach the threshold.
    f = wrap(jax.jit(lambda x: x + 1), "t.ladder", storm_threshold=6)
    for _ in range(10):
        for n in (4, 8, 16, 32, 64):
            f(jnp.ones((n,)))
    snap = get_profiler().ledger()["t.ladder"]
    assert snap["compiles"] == 5
    assert snap["calls"] == 50
    assert snap["storms"] == 0


# ---------------------------------------------------------------------------
# transfers
# ---------------------------------------------------------------------------

def test_h2d_accounting_counts_numpy_args():
    f = wrap(jax.jit(lambda x: x.sum()), "t.h2d")
    f(np.ones((8, 8), np.float32))           # 256 B host feed
    snap = get_profiler().ledger()["t.h2d"]
    assert snap["h2d_bytes"] == 8 * 8 * 4
    f(jnp.ones((8, 8)))                       # device arg: no host feed
    assert get_profiler().ledger()["t.h2d"]["h2d_bytes"] == 8 * 8 * 4
    m = obs.get_registry().get("senweaver_runtime_transfer_bytes_total")
    assert m.value(fn="t.h2d", direction="h2d") == 8 * 8 * 4


def test_d2h_accounting_via_profiled_device_get():
    x = jnp.ones((16,), jnp.float32)
    host = profiled_device_get((x, x), fn="t.d2h")
    assert isinstance(host, tuple)
    snap = get_profiler().ledger()["t.d2h"]
    assert snap["d2h_bytes"] == 2 * 16 * 4


def test_skip_args_keeps_signature_coarse():
    # Shape-stable trees (params) are excluded from the per-call scan;
    # a retrace they DO cause is still counted via the cache-size delta.
    f = ProfiledFunction(jax.jit(lambda p, x: x * p["w"].sum()),
                         "t.skip", skip_args=(0,))
    f({"w": jnp.ones((4,))}, jnp.ones((2,)))
    f({"w": jnp.ones((8,))}, jnp.ones((2,)))   # param retrace
    snap = get_profiler().ledger()["t.skip"]
    assert len(snap["signatures"]) == 1        # coarse signature
    assert snap["compiles"] == 2               # ...but compiles seen


# ---------------------------------------------------------------------------
# memory sampling (satellite: CPU degrade + backend label)
# ---------------------------------------------------------------------------

def test_memory_sampling_degrades_on_cpu_without_raising():
    keep = jnp.ones((64, 64), jnp.float32)    # something live to count
    out = sample_memory()
    assert "cpu" in out
    cpu = out["cpu"]
    # CPU devices return None from memory_stats(): the sampler must
    # fall back to live-array accounting, not raise.
    assert cpu["source"] == "live_arrays"
    assert cpu["bytes_in_use"] > 0
    assert cpu["peak_bytes"] >= cpu["bytes_in_use"] > 0
    del keep


def test_memory_gauges_carry_backend_label():
    sample_memory()
    reg = obs.get_registry()
    for name in ("senweaver_runtime_hbm_bytes_in_use",
                 "senweaver_runtime_hbm_watermark_bytes",
                 "senweaver_runtime_live_buffer_bytes"):
        m = reg.get(name)
        assert m is not None, name
        assert m.value(backend="cpu") is not None, name


def test_watermark_is_monotone_across_samples():
    s1 = sample_memory()["cpu"]["peak_bytes"]
    s2 = sample_memory()["cpu"]["peak_bytes"]
    assert s2 >= s1 or s1 == 0


# ---------------------------------------------------------------------------
# cost analysis (opt-in)
# ---------------------------------------------------------------------------

def test_cost_analysis_records_flops_when_enabled():
    get_profiler().set_cost_analysis(True)
    f = wrap(jax.jit(lambda a, b: a @ b), "t.cost")
    f(jnp.ones((16, 16)), jnp.ones((16, 16)))
    fpc = get_profiler().flops_per_call("t.cost")
    assert fpc == pytest.approx(2 * 16 * 16 * 16, rel=0.5)
    snap = get_profiler().ledger()["t.cost"]
    assert snap["flops_per_call"] == fpc
    util = get_profiler().utilization("t.cost")
    assert util is not None and util["achieved_flops_per_sec"] > 0


@pytest.mark.parametrize("kind,peaks", [
    ("TPU v5 lite", {"flops_per_s": 197e12, "bytes_per_s": 819e9}),
    ("TPU v9 unheard of", {}), (None, {})],
    ids=["v5e", "unknown-kind", "no-device"])
def test_utilization_comes_from_the_device_kinds_published_peaks(
        monkeypatch, kind, peaks):
    """One table in the program, keyed by ``device_kind``: the v5e's
    peaks as ``benchmark/peaks.json`` has them; a kind the table lacks
    publishes the achieved rate and no utilization, never a guess. No
    environment variable is read."""
    from senweaver_ide_tpu.obs import runtime_profile as rp
    monkeypatch.setenv("SENWEAVER_PEAK_FLOPS", "1.0")
    monkeypatch.setenv("SENWEAVER_PEAK_BYTES_PER_SEC", "1.0")
    monkeypatch.setattr(rp, "device_kind", lambda: kind)
    assert rp.device_peaks() == peaks
    get_profiler().set_cost_analysis(True)
    f = wrap(jax.jit(lambda a, b: a @ b), "t.peaks")
    f(jnp.ones((16, 16)), jnp.ones((16, 16)))
    util = get_profiler().utilization("t.peaks")
    assert util["achieved_flops_per_sec"] > 0
    gauge = obs.get_registry().get("senweaver_runtime_roofline_utilization")
    if peaks:
        assert util["utilization"] == pytest.approx(
            util["achieved_flops_per_sec"] / 197e12)
        assert 0.0 < gauge.value(fn="t.peaks", resource="flops") < 1.0
        assert 0.0 < gauge.value(fn="t.peaks", resource="bytes") < 1.0
    else:
        assert "utilization" not in util
        assert "senweaver_runtime_roofline_utilization{" not in \
            obs.get_registry().render()


def test_the_programs_peaks_are_the_benchmarks():
    import json
    import os
    from senweaver_ide_tpu.obs.runtime_profile import DEVICE_PEAKS
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "peaks.json")) as f:
        bench = json.load(f)
    assert set(DEVICE_PEAKS) == set(bench)
    for kind, row in DEVICE_PEAKS.items():
        assert row == {"flops_per_s": bench[kind]["bf16_flops_per_s"],
                       "bytes_per_s": bench[kind]["hbm_bytes_per_s"]}


def test_cost_analysis_off_by_default():
    f = wrap(jax.jit(lambda x: x * 2), "t.nocost")
    f(jnp.ones((4,)))
    assert get_profiler().flops_per_call("t.nocost") is None


def test_measured_mfu_replaces_analytic_in_telemetry():
    from senweaver_ide_tpu.obs.telemetry import StepTelemetry

    get_profiler().set_cost_analysis(True)
    # Stand in for the profiled GRPO step: any jitted fn under the
    # ledger name telemetry reads.
    f = wrap(jax.jit(lambda a, b: a @ b), "trainer.grpo_step")
    f(jnp.ones((16, 16)), jnp.ones((16, 16)))

    t = StepTelemetry(param_count=1000)
    out = t.record_round(collect_s=1.0, batch_build_s=0.1, train_s=0.5,
                         batch_tokens=64, ppo_epochs=2)
    assert out["mfu_source"] == "cost_analysis"
    assert out["step_flops_per_sec"] == pytest.approx(
        2 * 16 * 16 * 16 * 2 / 0.5, rel=0.5)


def test_analytic_mfu_fallback_without_cost_analysis():
    from senweaver_ide_tpu.obs.telemetry import StepTelemetry

    t = StepTelemetry(param_count=1000)
    out = t.record_round(collect_s=1.0, batch_build_s=0.1, train_s=0.5,
                         batch_tokens=64, ppo_epochs=1)
    assert out["mfu_source"] == "analytic"
    assert out["step_flops_per_sec"] == pytest.approx(
        6.0 * 1000 * 64 / 0.5)


# ---------------------------------------------------------------------------
# wrapper mechanics
# ---------------------------------------------------------------------------

def test_disabled_profiler_is_pass_through():
    get_profiler().set_enabled(False)
    f = wrap(jax.jit(lambda x: x + 1), "t.off")
    out = f(jnp.ones((4,)))
    assert out.shape == (4,)
    assert "t.off" not in get_profiler().ledger()


def test_reset_for_tests_swaps_profiler():
    f = wrap(jax.jit(lambda x: x + 1), "t.reset")
    f(jnp.ones((4,)))
    assert "t.reset" in get_profiler().ledger()
    obs._reset_for_tests()
    assert get_profiler().ledger() == {}


def test_wrapper_delegates_attributes():
    jitted = jax.jit(lambda x: x + 1)
    f = ProfiledFunction(jitted, "t.attrs")
    assert f.wrapped is jitted
    assert f.__wrapped__ is jitted
    # jit surface stays reachable (lower/trace/etc. via delegation)
    assert callable(f.lower)


def test_export_jsonl_roundtrip(tmp_path):
    import json

    f = wrap(jax.jit(lambda x: x * 3), "t.export")
    f(jnp.ones((4,)))
    path = tmp_path / "runtime.jsonl"
    n = get_profiler().export_jsonl(str(path))
    assert n == 1
    rec = json.loads(path.read_text().strip())
    assert rec["fn"] == "t.export"
    assert rec["compiles"] == 1


# ---------------------------------------------------------------------------
# the engine retrace-regression gate (satellite 2)
# ---------------------------------------------------------------------------

def test_engine_fused_step_compiles_once_per_bucket():
    """Across multi-batch paged decode with varying occupancy and
    block-table fill, every fused-step signature compiles at most once,
    the signature set stays within the expected bucket ladder, and a
    repeat of the same workload adds ZERO compiles. A distinctive
    vocab_size keeps this test's jit cache cold even when other engine
    tests ran earlier in the process."""
    from senweaver_ide_tpu.models import init_params, tiny_test
    from senweaver_ide_tpu.rollout import EngineConfig, RolloutEngine
    from senweaver_ide_tpu.rollout.sampler import SampleParams

    config = dataclasses.replace(tiny_test(), vocab_size=97)
    params = jax.block_until_ready(
        init_params(config, jax.random.PRNGKey(0)))
    greedy = SampleParams(temperature=0.0, top_k=0, top_p=1.0)

    def workload(prompt_lens):
        eng = RolloutEngine(
            params, config, num_slots=4, max_len=96, sample=greedy,
            engine_config=EngineConfig(kv_layout="paged"))
        for i, n in enumerate(prompt_lens):
            eng.submit([(i * 5 + j) % 90 + 2 for j in range(n)],
                       max_new_tokens=8)
        eng.run()

    def fused_snapshot():
        return get_profiler().ledger().get(
            "engine.fused_step",
            {"calls": 0, "compiles": 0, "storms": 0, "signatures": []})

    workload([5])                       # low occupancy
    workload([4, 7, 11, 6])             # full pool, varied fill
    snap = fused_snapshot()
    assert snap["calls"] > 0
    # Exactly-once per shape bucket: no signature recompiled.
    for sig in snap["signatures"]:
        assert sig["compiles"] <= 1, sig
    assert snap["compiles"] == sum(
        s["compiles"] for s in snap["signatures"])
    # The power-of-two trim bounds the ladder; varied occupancy must
    # not mint per-width signatures beyond it.
    assert len(snap["signatures"]) <= 8, snap["signatures"]
    assert snap["storms"] == 0

    before = snap["compiles"]
    workload([4, 7, 11, 6])             # identical workload, warm cache
    after = fused_snapshot()
    assert after["compiles"] == before, (
        "repeat workload recompiled the fused step: "
        f"{after['signatures']}")
    assert after["calls"] > snap["calls"]
    assert after["storms"] == 0


def test_speculation_depth_sweep_compiles_once_per_bucket():
    """ISSUE 12 retrace gate: sweeping the speculation depth ladder
    0 -> 2 -> 8 -> 4 -> 0 -> 8 across varying occupancy compiles each
    of engine.fused_step / engine.spec_propose / engine.spec_feed at
    most ONCE per (bucket, depth) signature, and repeating the sweep
    adds ZERO compiles — a depth change lands on a pre-compiled bucket,
    never a retrace. Distinctive vocab keeps the jit cache cold."""
    from senweaver_ide_tpu.models import init_params, tiny_test
    from senweaver_ide_tpu.rollout import EngineConfig, RolloutEngine
    from senweaver_ide_tpu.rollout.sampler import SampleParams

    config = dataclasses.replace(tiny_test(), vocab_size=89)
    params = jax.block_until_ready(
        init_params(config, jax.random.PRNGKey(0)))
    draft_cfg = dataclasses.replace(config, num_layers=2,
                                    name="tiny-draft")
    draft = jax.block_until_ready(
        init_params(draft_cfg, jax.random.PRNGKey(1)))
    greedy = SampleParams(temperature=0.0, top_k=0, top_p=1.0)
    SWEEP = [0, 2, 8, 4, 0, 8]
    SPEC_FNS = ("engine.fused_step", "engine.spec_propose",
                "engine.spec_feed")

    def workload(prompt_lens):
        eng = RolloutEngine(
            params, config, num_slots=4, max_len=96, sample=greedy,
            engine_config=EngineConfig(kv_layout="paged"))
        eng.enable_speculation(draft, draft_cfg, depth=SWEEP[0])
        for i, n in enumerate(prompt_lens):
            eng.submit([(i * 5 + j) % 80 + 2 for j in range(n)],
                       max_new_tokens=8)
        step = 0
        while eng.has_work:
            eng.step()
            step += 1
            if step < len(SWEEP):
                eng.set_spec_depth(SWEEP[step])
        eng._alloc.check_leaks()
        eng.spec_check_leaks()

    def snapshot():
        led = get_profiler().ledger()
        return {k: led[k] for k in SPEC_FNS if k in led}

    workload([5])                       # low occupancy
    workload([4, 7, 11, 6])             # full pool, varied fill
    snap = snapshot()
    assert set(snap) == set(SPEC_FNS)   # all three hot paths exercised
    for name, rec in snap.items():
        for sig in rec["signatures"]:
            assert sig["compiles"] <= 1, (name, sig)
        assert rec["storms"] == 0
    # Bounded ladder: (occupancy-bucket x depth) signatures only.
    assert len(snap["engine.fused_step"]["signatures"]) <= 10
    assert len(snap["engine.spec_propose"]["signatures"]) <= 8

    before = {k: v["compiles"] for k, v in snap.items()}
    workload([4, 7, 11, 6])             # identical sweep, warm cache
    after = snapshot()
    for name in SPEC_FNS:
        assert after[name]["compiles"] == before[name], (
            f"repeat depth sweep recompiled {name}: "
            f"{after[name]['signatures']}")
        assert after[name]["storms"] == 0


# ---------------------------------------------------------------------------
# spans: <name>.dispatch and <name>.wait around the wrapped call
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block", [True, False], ids=["block", "async"])
def test_wrapper_emits_dispatch_and_wait_spans(block):
    """An enabled tracer sees the wrapped call as ``.dispatch`` and, when
    the wrapper blocks, its ``block_until_ready`` as ``.wait`` — children
    of whatever span is open around the call."""
    f = wrap(jax.jit(lambda x: x * 2), "t.spans", block=block)
    obs.enable()
    with obs.get_tracer().span("caller"):
        f(jnp.ones((4,)))
    spans = obs.get_tracer().spans()
    caller = next(s for s in spans if s.name == "caller")
    kids = [s for s in spans if s.parent_id == caller.span_id]
    assert [s.name for s in kids] == (
        ["t.spans.dispatch", "t.spans.wait"] if block
        else ["t.spans.dispatch"])
    for s in kids:
        assert caller.start_ns <= s.start_ns <= s.end_ns <= caller.end_ns
    if block:
        assert kids[0].end_ns <= kids[1].start_ns


def test_wrapper_spans_follow_the_caller_not_the_profiler(monkeypatch):
    """The wrapper never asks the profiler whether a session runs: with
    the tracer disabled its spans are on under an open span only, so a
    caller that found tracing off pays nothing here."""
    from jax.profiler import TraceAnnotation
    f = wrap(jax.jit(lambda x: x + 1), "t.quiet")
    f(jnp.ones((4,)))
    asked = []
    monkeypatch.setattr(TraceAnnotation, "is_enabled",
                        staticmethod(lambda: asked.append(1) or False))
    f(jnp.ones((4,)))
    assert asked == [] and obs.get_tracer().spans() == []
    # inside a session (is_enabled true) a span opened by the caller is
    # on, and the wrapper's two ride under it
    monkeypatch.setattr(TraceAnnotation, "is_enabled",
                        staticmethod(lambda: True))
    with obs.get_tracer().span("caller"):
        f(jnp.ones((4,)))
    assert [s.name for s in obs.get_tracer().spans()] == [
        "t.quiet.dispatch", "t.quiet.wait", "caller"]


def test_disabled_profiler_is_a_plain_pass_through_with_no_spans():
    f = wrap(jax.jit(lambda x: x + 1), "t.off")
    get_profiler().set_enabled(False)
    obs.enable()
    with obs.get_tracer().span("caller"):
        f(jnp.ones((4,)))
    assert [s.name for s in obs.get_tracer().spans()] == ["caller"]
