"""The two readers of the program's own spans, on hand-made span lists,
and through the CPU rehearsal: the new metrics appear in a traced run and
only there."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.manifest import HERE, ROOT, load_json
from benchmark.readers import program_span, program_span_ratio


def span(name, ms, span_id="", parent_id=None, **attrs):
    return types.SimpleNamespace(name=name, duration_ms=ms,
                                 span_id=span_id or name, parent_id=parent_id,
                                 attrs=attrs)


SPANS = [
    span("engine.step", 30.0, "s1", used=20, entries=48),
    span("engine.launch", 26.0, "l1", "s1"),
    span("engine.fused_step.dispatch", 0.5, "d1", "l1"),
    span("engine.fused_step.wait", 25.0, "w1", "l1"),
    span("engine.step", 90.0, "s2", used=150, entries=192),
    span("engine.launch", 84.0, "l2", "s2"),
    span("engine.fused_step.dispatch", 0.75, "d2", "l2"),
    span("engine.fused_step.wait", 82.0, "w2", "l2"),
    span("engine.step", 31.0, "s3", used=22, entries=48),
    span("engine.launch", 27.5, "l3", "s3"),
    span("engine.fused_step.wait", 26.0, "w3", "l3"),
    span("engine.step", 1.0, "s4"),               # no plan: no attrs
    span("request.prefill", 80.0), span("request.prefill", 120.0),
    span("request.prefill", 160.0), span("request.prefill", 200.0),
    span("request.prefill", 400.0),
]


@pytest.fixture
def recorded(monkeypatch):
    monkeypatch.setattr(program_span, "recorded", lambda r: SPANS)
    monkeypatch.setattr(program_span_ratio, "recorded", lambda r: SPANS)


def test_median_p75_and_self_time(recorded):
    read = program_span.read
    assert read(None, {"span": "engine.launch", "stat": "median_ms"}) == 27.5
    assert read(None, {"span": "request.prefill", "stat": "p75_ms"}) == 200.0
    # less the named children only: 1.0, 2.0, 1.5
    assert read(None, {"span": "engine.launch", "stat": "median_ms",
                       "minus_children": ["engine.fused_step.wait"]}) == 1.5
    # all of its children: the self time 0.5, 1.25, 1.5
    assert read(None, {"span": "engine.launch", "stat": "median_ms",
                       "minus_children": ["engine.fused_step.wait",
                                          "engine.fused_step.dispatch"]}
                ) == 1.25
    # a child of another parent is not taken off
    assert program_span.durations_ms(
        SPANS, "engine.step", ("engine.fused_step.wait",)) == [
            30.0, 90.0, 31.0, 1.0]


def test_ratio_sums_before_it_divides(recorded):
    got = program_span_ratio.read(None, {"span": "engine.step",
                                         "num": "used", "den": "entries"})
    assert got == pytest.approx(100.0 * (20 + 150 + 22) / (48 + 192 + 48))


def test_no_such_span_reads_none(recorded):
    assert program_span.read(None, {"span": "engine.plan",
                                    "stat": "median_ms"}) is None
    assert program_span_ratio.read(None, {"span": "engine.plan",
                                          "num": "used",
                                          "den": "entries"}) is None
    assert program_span_ratio.read(None, {"span": "engine.step",
                                          "num": "used",
                                          "den": "nothing"}) is None


def record(traced=2, trace=object()):
    """A run's record with four window steps of 10 ms from t = 100 s, the
    last ``traced`` of them inside the profiler session."""
    steps = [(100.0 + 0.01 * i, 100.01 + 0.01 * i, 1, 0, 0.0)
             for i in range(4)]
    return types.SimpleNamespace(
        trace=trace, traced_steps=steps[4 - traced:] if traced else [],
        window=types.SimpleNamespace(steps=steps, t1=100.04))


def test_only_spans_that_ended_in_the_traced_part_count():
    """The tracer is the process's own: what an earlier profiler session
    or ``obs.enable()`` left in it, and a span that ended before the trace
    began, are not the run's."""
    from senweaver_ide_tpu.obs import get_tracer
    tracer = get_tracer()
    s = lambda t: int(t * 1e9)
    tracer.record_span("t.step", s(50.0), s(50.03), trace_id="x")    # stale
    tracer.record_span("t.step", s(100.011), s(100.019), trace_id="x",
                       used=1, entries=4)                            # before
    tracer.record_span("t.step", s(100.021), s(100.029), trace_id="x",
                       used=2, entries=4)
    tracer.record_span("t.step", s(100.031), s(100.037), trace_id="x",
                       used=4, entries=4)
    # begun before the trace, ended in it: a request's phase
    tracer.record_span("t.prefill", s(100.005), s(100.025), trace_id="x")
    tracer.record_span("t.step", s(100.2), s(100.3), trace_id="x")   # after
    r = record(traced=2)
    assert [x.attrs.get("used") for x in program_span.recorded(r)
            if x.name == "t.step"] == [2, 4]
    assert program_span.read(r, {"span": "t.step", "stat": "median_ms"}
                             ) == pytest.approx(7.0)
    assert program_span.read(r, {"span": "t.prefill", "stat": "p75_ms"}
                             ) == pytest.approx(20.0)
    assert program_span_ratio.read(r, {"span": "t.step", "num": "used",
                                       "den": "entries"}) == 75.0
    # all four steps traced: the second span counts too
    assert program_span_ratio.read(record(traced=4), {
        "span": "t.step", "num": "used", "den": "entries"}
        ) == pytest.approx(100.0 * 7 / 12)


@pytest.mark.parametrize("r", [record(trace=None), record(traced=0)],
                         ids=["trace-0", "no-traced-step"])
def test_a_run_with_no_traced_part_reads_none(r):
    """A ``--trace 0`` run reads None whatever the process's tracer
    holds."""
    from senweaver_ide_tpu.obs import get_tracer
    get_tracer().record_span("t.step", int(100.021e9), int(100.029e9),
                             trace_id="x", used=1, entries=2)
    assert program_span.recorded(r) == []
    assert program_span.read(r, {"span": "t.step",
                                 "stat": "median_ms"}) is None
    assert program_span_ratio.read(r, {"span": "t.step", "num": "used",
                                       "den": "entries"}) is None


NEW = {"qwen1.5b-chat-open": {
           "engine_plan_ms_per_step.itl", "engine_launch_ms_per_step.itl",
           "engine_emit_ms_per_step.itl", "step_entry_fill.itl",
           "request_prefill_ms_p75.ttft"},
       "qwen1.5b-grpo-rollout-sat": {
           "engine_plan_ms_per_step.rollout",
           "engine_launch_ms_per_step.rollout",
           "engine_emit_ms_per_step.rollout", "step_entry_fill.rollout"}}


def test_manifest_names_the_nine_with_their_files():
    doc = load_json(ROOT, "BENCHMARK.json")
    ours = [m for m in doc["per_layer"] if m["source"] == "program_span"]
    assert {m["name"] for m in ours} == set().union(*NEW.values())
    for m in ours:
        (cell,) = m["workloads"]
        assert m["name"] in NEW[cell] and m["layer"] == "engine host"
        spec = load_json(HERE, "layer_metrics", m["name"] + ".json")
        assert spec["reader"] in ("program_span", "program_span_ratio")


@pytest.mark.parametrize("cell", sorted(NEW))
def test_rehearsal_reports_them_traced_and_only_traced(cell):
    """The CPU rehearsal (a profiler session on the CPU backend turns the
    program's spans on): names only, never a number."""
    seen = {}
    for trace in ("0", "1"):
        out = subprocess.run(
            [sys.executable, os.path.join(HERE, "run.py"), "--rehearse",
             "--workload", cell, "--seed", "2147483659", "--seconds", "3",
             "--trace", trace, "--trace-seconds", "1"],
            capture_output=True, text=True, timeout=600, cwd=ROOT,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert out.returncode == 0, out.stderr[-2000:]
        line = json.loads(out.stdout.strip().splitlines()[-1])
        assert line["correct"] and line["metrics"] == {}
        seen[trace] = set(line["rehearsal"])
    assert NEW[cell] <= seen["1"]
    assert not (set().union(*NEW.values()) & seen["0"])
