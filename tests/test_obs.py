"""obs/ subsystem: span tracing, metrics registry, training telemetry.

Covers the tracer's contextvar nesting + cross-thread propagation, the
disabled no-op fast path, histogram/exposition math against the
Prometheus text format, Chrome-trace validity, and — the capstone — a
real grpo_round on the tiny stack emitting nested spans and throughput
metrics end-to-end.
"""

import json
import threading
from concurrent.futures import ThreadPoolExecutor

import pytest

from senweaver_ide_tpu import obs
from senweaver_ide_tpu.obs import (MetricsRegistry, SpanRecord,
                                   StepTelemetry, Tracer, estimate_mfu,
                                   load_span_jsonl)


@pytest.fixture(autouse=True)
def fresh_obs():
    obs._reset_for_tests()
    yield
    obs._reset_for_tests()


# ---- tracing: nesting + ids ----

def test_span_nesting_assigns_parent_and_trace_ids():
    t = Tracer(enabled=True)
    with t.span("outer", tasks=2):
        with t.span("inner"):
            pass
    spans = {s.name: s for s in t.spans()}
    assert set(spans) == {"outer", "inner"}
    outer, inner = spans["outer"], spans["inner"]
    assert outer.parent_id is None
    assert inner.parent_id == outer.span_id
    assert inner.trace_id == outer.trace_id
    assert outer.attrs == {"tasks": 2}
    assert inner.duration_ms <= outer.duration_ms


def test_sibling_spans_get_distinct_traces_at_top_level():
    t = Tracer(enabled=True)
    with t.span("a"):
        pass
    with t.span("b"):
        pass
    a, b = t.spans()
    assert a.trace_id != b.trace_id        # no shared root → new traces


def test_span_records_exception_and_reraises():
    t = Tracer(enabled=True)
    with pytest.raises(ValueError):
        with t.span("boom"):
            raise ValueError("nope")
    (s,) = t.spans()
    assert s.attrs["error"] == "ValueError: nope"


def test_capture_attach_propagates_across_threads():
    t = Tracer(enabled=True)
    with t.span("round"):
        ctx = t.capture()

        def worker(i):
            with t.attach(ctx):
                with t.span("episode", i=i):
                    pass

        with ThreadPoolExecutor(max_workers=4) as pool:
            list(pool.map(worker, range(8)))
    spans = t.spans()
    root = next(s for s in spans if s.name == "round")
    episodes = [s for s in spans if s.name == "episode"]
    assert len(episodes) == 8
    assert all(e.trace_id == root.trace_id for e in episodes)
    assert all(e.parent_id == root.span_id for e in episodes)
    # Without attach, a pool thread would have started a fresh trace.


def test_disabled_tracer_is_shared_noop():
    t = Tracer(enabled=False)
    from senweaver_ide_tpu.obs.tracing import _NOOP
    assert t.span("x") is _NOOP
    assert t.span("y", k=1) is _NOOP          # same object, no allocation
    with t.span("z"):
        pass
    assert t.spans() == []
    assert t.attach(("tid", "sid")) is _NOOP


def test_traced_decorator_uses_global_tracer():
    calls = []

    @obs.traced("my.fn")
    def fn(x):
        calls.append(x)
        return x * 2

    assert fn(3) == 6                          # disabled: plain call
    assert obs.get_tracer().spans() == []
    obs.enable()
    assert fn(4) == 8
    (s,) = obs.get_tracer().spans()
    assert s.name == "my.fn"
    assert calls == [3, 4]


def test_max_spans_bounds_memory_and_counts_drops():
    t = Tracer(enabled=True, max_spans=5)
    for i in range(9):
        with t.span(f"s{i}"):
            pass
    spans = t.spans()
    assert len(spans) == 5
    assert spans[0].name == "s4"               # oldest dropped first
    assert t.summary()["dropped_spans"] == 4


# ---- tracing: exporters ----

def test_jsonl_stream_and_reload(tmp_path):
    path = str(tmp_path / "spans.jsonl")
    t = Tracer(enabled=True, jsonl_path=path)
    with t.span("outer"):
        with t.span("inner", k="v"):
            pass
    t.close()
    loaded = load_span_jsonl(path)
    assert [s.name for s in loaded] == ["inner", "outer"]  # finish order
    assert loaded[0].attrs == {"k": "v"}
    assert loaded[0].parent_id == loaded[1].span_id
    # Torn tail line is skipped, not fatal.
    with open(path, "a") as f:
        f.write('{"name": "torn')
    assert len(load_span_jsonl(path)) == 2


def test_export_jsonl_roundtrip(tmp_path):
    t = Tracer(enabled=True)
    with t.span("a", n=1):
        pass
    path = t.export_jsonl(str(tmp_path / "dump.jsonl"))
    (s,) = load_span_jsonl(path)
    assert isinstance(s, SpanRecord) and s.name == "a"


def test_chrome_trace_is_valid_trace_event_json(tmp_path):
    t = Tracer(enabled=True)
    with t.span("grpo_round"):
        with t.span("train_step"):
            pass
    path = t.write_chrome_trace(str(tmp_path / "trace.json"))
    doc = json.loads(open(path).read())
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    complete = [e for e in events if e["ph"] == "X"]
    meta = [e for e in events if e["ph"] == "M"]
    assert {e["name"] for e in complete} == {"grpo_round", "train_step"}
    for e in complete:
        assert e["cat"] == "senweaver"
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert e["args"]["trace_id"] and e["args"]["span_id"]
    # Nesting is recoverable: child interval within parent interval.
    child = next(e for e in complete if e["name"] == "train_step")
    parent = next(e for e in complete if e["name"] == "grpo_round")
    assert parent["ts"] <= child["ts"]
    assert child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 1e3
    assert meta and meta[0]["name"] == "thread_name"


def test_summary_aggregates_by_name():
    t = Tracer(enabled=True)
    for _ in range(3):
        with t.span("step"):
            pass
    s = t.summary(top=2)
    assert s["total_spans"] == 3
    assert s["by_name"]["step"]["count"] == 3
    assert len(s["slowest"]) == 2
    assert s["slowest"][0]["duration_ms"] >= s["slowest"][1]["duration_ms"]


# ---- metrics: counter / gauge ----

def test_counter_labels_and_monotonicity():
    r = MetricsRegistry()
    c = r.counter("senweaver_events_total", "events",
                  labelnames=("event",))
    c.inc(event="a")
    c.inc(2, event="a")
    c.inc(event="b")
    assert c.value(event="a") == 3
    assert c.value(event="b") == 1
    assert c.value(event="missing") == 0
    with pytest.raises(ValueError):
        c.inc(-1, event="a")
    with pytest.raises(ValueError):
        c.inc(wrong_label="a")


def test_gauge_set_inc_dec():
    r = MetricsRegistry()
    g = r.gauge("senweaver_queue_depth", "depth")
    assert g.value() is None
    g.set(5)
    g.inc(2)
    g.dec()
    assert g.value() == 6


def test_registry_idempotent_and_type_checked():
    r = MetricsRegistry()
    c1 = r.counter("x_total", "x")
    c2 = r.counter("x_total", "x")
    assert c1 is c2
    with pytest.raises(ValueError):
        r.gauge("x_total")
    with pytest.raises(ValueError):
        r.counter("x_total", labelnames=("other",))
    assert r.get("x_total") is c1 and r.get("nope") is None


def test_metrics_registry_thread_safety():
    r = MetricsRegistry()
    c = r.counter("n_total")

    def work():
        for _ in range(1000):
            c.inc()

    threads = [threading.Thread(target=work) for _ in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert c.value() == 8000


# ---- metrics: histogram ----

def test_histogram_bucket_math_cumulative():
    r = MetricsRegistry()
    h = r.histogram("lat_ms", "latency", buckets=(10, 100, 1000))
    for v in (5, 7, 50, 500, 5000):
        h.observe(v)
    snap = h.snapshot()
    assert snap["buckets"] == {10.0: 2, 100.0: 3, 1000.0: 4,
                               float("inf"): 5}
    assert snap["sum"] == 5562.0
    assert snap["count"] == 5
    # Boundary: value == upper bound lands IN that bucket (le semantics).
    h2 = r.histogram("edge_ms", buckets=(10, 100))
    h2.observe(10)
    assert h2.snapshot()["buckets"][10.0] == 1


def test_histogram_render_prometheus_lines():
    r = MetricsRegistry()
    h = r.histogram("lat_ms", "latency", labelnames=("stage",),
                    buckets=(10, 100))
    h.observe(50, stage="train")
    lines = h.render()
    assert 'lat_ms_bucket{stage="train",le="10"} 0' in lines
    assert 'lat_ms_bucket{stage="train",le="100"} 1' in lines
    assert 'lat_ms_bucket{stage="train",le="+Inf"} 1' in lines
    assert 'lat_ms_sum{stage="train"} 50' in lines
    assert 'lat_ms_count{stage="train"} 1' in lines


def test_registry_render_exposition_format():
    r = MetricsRegistry()
    r.counter("senweaver_rounds_total", "Completed rounds.").inc(3)
    r.gauge("senweaver_tokens_per_sec", "tput",
            labelnames=("phase",)).set(123.5, phase="train")
    text = r.render()
    assert "# HELP senweaver_rounds_total Completed rounds.\n" in text
    assert "# TYPE senweaver_rounds_total counter\n" in text
    assert "senweaver_rounds_total 3\n" in text
    assert "# TYPE senweaver_tokens_per_sec gauge\n" in text
    assert 'senweaver_tokens_per_sec{phase="train"} 123.5\n' in text
    assert text.endswith("\n")


def test_label_escaping():
    r = MetricsRegistry()
    c = r.counter("e_total", labelnames=("msg",))
    c.inc(msg='say "hi"\nnow\\then')
    (line,) = c.render()
    assert line == 'e_total{msg="say \\"hi\\"\\nnow\\\\then"} 1'


def test_registry_snapshot_json_friendly():
    r = MetricsRegistry()
    r.counter("c_total", labelnames=("k",)).inc(k="a")
    r.histogram("h_ms", buckets=(10,)).observe(5)
    snap = r.snapshot()
    assert snap["c_total"]["values"] == {"a": 1.0}
    assert snap["h_ms"]["values"][""] == {"sum": 5.0, "count": 1}
    json.dumps(snap)                           # must serialize


# ---- telemetry ----

def test_estimate_mfu():
    # 6 * 1e9 params * 1000 tokens / (1 s * 1.2e13 flops) = 0.5
    assert estimate_mfu(10**9, 1000, 1.0, 1.2e13) == pytest.approx(0.5)
    assert estimate_mfu(10**9, 1000, 0.0, 1.2e13) == 0.0


def test_step_telemetry_publishes_round(monkeypatch):
    monkeypatch.setenv("SENWEAVER_PEAK_FLOPS", "7.0")     # read by nothing
    r = MetricsRegistry()
    tele = StepTelemetry(r, param_count=1000, peak_flops=1e9)
    out = tele.record_round(collect_s=2.0, batch_build_s=0.5,
                            train_s=1.0, batch_tokens=512,
                            completion_tokens=100, episodes=4,
                            trajectories=6, ppo_epochs=2)
    assert out["tokens_per_sec"] == pytest.approx(1024.0)
    assert out["collect_tokens_per_sec"] == pytest.approx(50.0)
    assert out["step_flops_per_sec"] == pytest.approx(6.0 * 1000 * 1024)
    assert out["mfu"] == pytest.approx(6.0 * 1000 * 1024 / 1e9)
    assert r.get("senweaver_tokens_per_sec").value(phase="train") \
        == pytest.approx(1024.0)
    assert r.get("senweaver_rounds_total").value() == 1
    assert r.get("senweaver_episodes_total").value() == 4
    assert r.get("senweaver_trajectories_total").value() == 6
    assert r.get("senweaver_train_step_ms").snapshot()["count"] == 1
    assert r.get("senweaver_stage_seconds").value(stage="collect") == 2.0
    # Second round reuses the same instruments (idempotent registry).
    tele2 = StepTelemetry(r, param_count=1000)
    tele2.record_round(collect_s=1.0, batch_build_s=0.1, train_s=0.5,
                       batch_tokens=256)
    assert r.get("senweaver_rounds_total").value() == 2


@pytest.mark.parametrize("kind,peak", [("TPU v5 lite", 197e12),
                                       ("TPU v9 unheard of", None)])
def test_step_telemetry_peak_flops_from_the_device_kind(monkeypatch, kind,
                                                        peak):
    """No constructor argument: the device kind's published peak
    (``runtime_profile.DEVICE_PEAKS``), and none for a kind the table
    lacks — the environment is not asked."""
    from senweaver_ide_tpu.obs import runtime_profile
    monkeypatch.setenv("SENWEAVER_PEAK_FLOPS", "2e9")
    monkeypatch.setattr(runtime_profile, "device_kind", lambda: kind)
    tele = StepTelemetry(MetricsRegistry(), param_count=10)
    assert tele.peak_flops == peak
    out = tele.record_round(collect_s=1.0, batch_build_s=0.1, train_s=0.5,
                            batch_tokens=64)
    assert ("mfu" in out) == (peak is not None)


# ---- legacy bridges ----

def test_metrics_service_bridge_and_cached_handle(tmp_path):
    from senweaver_ide_tpu.services.metrics import (MetricsService,
                                                    load_jsonl_metrics)
    r = MetricsRegistry()
    path = str(tmp_path / "events.jsonl")
    with MetricsService(jsonl_path=path, registry=r) as ms:
        ms.capture("Round Completed", {"round": 1})
        ms.capture("Round Completed", {"round": 2})
        fh = ms._fh
        assert fh is not None                  # handle cached, not reopened
        ms.capture("Other Event")
        assert ms._fh is fh
        # Flushed per capture: visible to a reader before close().
        assert len(load_jsonl_metrics(path)) == 3
    assert ms._fh is None                      # context exit closed it
    c = r.get("senweaver_events_total")
    assert c.value(event="Round Completed") == 2
    assert c.value(event="Other Event") == 1
    ms.capture("After Close")                  # reopens transparently
    assert len(load_jsonl_metrics(path)) == 4
    ms.close()


def test_perf_monitor_bridge():
    from senweaver_ide_tpu.services.perf_monitor import PerformanceMonitor
    r = MetricsRegistry()
    mon = PerformanceMonitor(thresholds_ms={"fast": 1.0}, registry=r)
    mon.record_ms("fast", 5.0)
    mon.record_ms("fast", 0.5)
    h = r.get("senweaver_stage_ms")
    assert h.snapshot(stage="fast")["count"] == 2
    assert r.get("senweaver_perf_warnings_total").value(stage="fast") == 1


def test_trace_collector_bridge_gated_on_enabled():
    from senweaver_ide_tpu.traces.collector import TraceCollector
    col = TraceCollector()
    col.record_user_message("t", 0, "hi")      # disabled: no counter
    assert obs.get_registry().get("senweaver_trace_spans_total") is None
    obs.enable()
    col.record_user_message("t", 1, "again")
    c = obs.get_registry().get("senweaver_trace_spans_total")
    assert c is not None and c.value(type="user_message") == 1


# ---- end-to-end: grpo_round emits spans + metrics ----

def test_grpo_round_emits_spans_and_metrics(tmp_path):
    import jax

    from senweaver_ide_tpu.models import get_config
    from senweaver_ide_tpu.models.tokenizer import ByteTokenizer
    from senweaver_ide_tpu.rollout import (EnginePolicyClient,
                                           RolloutEngine, RolloutSession)
    from senweaver_ide_tpu.training import grpo_round, make_train_state

    config = get_config("tiny-test")
    state = make_train_state(config, jax.random.PRNGKey(0), None,
                             learning_rate=1e-3)
    tok = ByteTokenizer()
    jsonl = str(tmp_path / "spans.jsonl")
    obs.enable(span_jsonl=jsonl)
    made = []

    def make_session():
        engine = RolloutEngine(state.params, config, num_slots=2,
                               max_len=4096, eos_id=tok.eos_id,
                               seed=len(made))
        client = EnginePolicyClient(engine, tok, model_name="tiny-test",
                                    default_max_new_tokens=8,
                                    record_calls=True)
        s = RolloutSession(client, str(tmp_path / f"ws{len(made)}"),
                           include_tool_definitions=False)
        made.append(s)
        return s

    def reward(task_idx, g, session):
        return 1.0 if g % 2 == 0 else -1.0

    out = grpo_round(state, config, None, make_session, ["task"],
                     group_size=2, pad_id=tok.pad_id, max_len=2048,
                     reward_override=reward)
    assert int(out.state.step) == int(state.step) + 1

    # Spans: nested collect / batch_build / train_step under grpo_round.
    spans = obs.get_tracer().spans()
    by_name = {s.name: s for s in spans}
    for name in ("grpo_round", "collect", "batch_build", "train_step",
                 "episode"):
        assert name in by_name, f"missing span {name}"
    root = by_name["grpo_round"]
    for name in ("collect", "batch_build", "train_step"):
        assert by_name[name].parent_id == root.span_id
        assert by_name[name].trace_id == root.trace_id
    assert by_name["episode"].trace_id == root.trace_id  # crossed threads
    # Engine spans fired under the collect phase.
    assert any(s.name.startswith("engine.") for s in spans)

    # Live JSONL stream captured them too.
    assert {s.name for s in load_span_jsonl(jsonl)} >= {
        "grpo_round", "collect", "train_step"}

    # Chrome trace is valid and loadable.
    trace_path = obs.get_tracer().write_chrome_trace(
        str(tmp_path / "trace.json"))
    doc = json.loads(open(trace_path).read())
    assert any(e["name"] == "grpo_round" and e["ph"] == "X"
               for e in doc["traceEvents"])

    # Metrics: throughput + counters visible in the exposition text.
    text = obs.get_registry().render()
    assert 'senweaver_tokens_per_sec{phase="train"}' in text
    assert "senweaver_train_step_ms_bucket" in text
    assert "senweaver_rounds_total 1" in text
    assert "senweaver_episodes_total 2" in text
    assert "senweaver_engine_tokens_total" in text


# ---- obs_report CLI ----

def test_obs_report_cli(tmp_path, capsys):
    import importlib.util
    import os

    t = Tracer(enabled=True)
    for ms, name in ((1, "collect"), (2, "collect"), (10, "train_step")):
        t._record(SpanRecord(name=name, trace_id="t", span_id=str(ms),
                             parent_id=None, start_s=0.0,
                             duration_ms=float(ms), thread="main", tid=1))
    path = t.export_jsonl(str(tmp_path / "spans.jsonl"))

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "obs_report", os.path.join(root, "scripts", "obs_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main([path]) == 0
    out = capsys.readouterr().out
    assert "collect" in out and "train_step" in out
    assert mod.main(["/nonexistent/spans.jsonl"]) == 2
