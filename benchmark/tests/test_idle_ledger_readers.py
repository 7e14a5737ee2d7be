"""The idle ledger's two readers: the adapter on the recorded v5e trace plus
hand-made spans, the manifest's eighteen entries, and the CPU rehearsal, where
a trace has no device plane: the ``device_trace`` metrics read None there
(and are left out of the line), ``engine_unqueued_share.*`` needs the spans
alone and is reported; a ``--trace 0`` run reports none of them."""

import gzip
import importlib
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import trace_reduce as tr
from benchmark.manifest import HERE, ROOT, Manifest, load_json
from benchmark.readers import idle_inside_programs, idle_ledger

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tiny_v5e.xplane.pb.gz")
ROLLOUT = ["qwen1.5b-grpo-rollout-sat", "glm4.7-flash-grpo-rollout-ctx4k",
           "xing4.0-grpo-rollout-ctx4k", "falcon-h1-34b-grpo-rollout-ctx4k"]
ITL = ["qwen1.5b-chat-open"]
PARTS = {"idle_gap_ms": ("gap", "device"),
         "idle_gap_emit_ms": ("emit", "engine host"),
         "idle_gap_plan_ms": ("plan", "engine host"),
         "idle_gap_copies_ms": ("copies", "engine host"),
         "idle_gap_dispatch_ms": ("dispatch", "engine host"),
         "idle_gap_caller_ms": ("caller", "entry"),
         "idle_gap_runtime_ms": ("runtime", "device")}
OTHERS = {"idle_inside_programs_share": "fused step",
          "engine_unqueued_share": "engine host"}
NAMES = sorted(f"{n}.{sfx}" for n in list(PARTS) + list(OTHERS)
               for sfx in ("rollout", "itl"))
US = 1_000
# what every ``idle_gap_*`` spec hands the reader, less its part: the
# yardstick names the program's spans, the program does not name them for it
ARGS = {"module": "paged_fused_step",
        "spans": {"step": "engine.step", "launch": "engine.launch",
                  "fetch": "engine.fetch",
                  "dispatch": "engine.fused_step.dispatch",
                  "copies": ["engine.cow_copy", "engine.state_copy"]}}
UNQUEUED = {"part": "unqueued_share", "spans": {"step": "engine.step"},
            "attr": "unqueued_ms"}


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    with gzip.open(DATA, "rb") as f:
        return tr.reduce_planes(
            ProfileData.from_serialized_xspace(f.read()).planes)


def span(name, start, end, sid, parent=None, **attrs):
    return types.SimpleNamespace(name=name, span_id=sid, parent_id=parent,
                                 start_ns=int(start), end_ns=int(end),
                                 duration_ms=(end - start) / 1e6,
                                 attrs=attrs)


def step_spans(k, run_start, run_dur, host_minus_device):
    """Step ``k`` around one device run: plan 200 us, dispatch 700, the
    run starts 300 after the dispatch's end, the fetch returns 1000 after
    the run's end, emit 170, and 1.0 ms of ``unqueued_ms``."""
    run0 = run_start + host_minus_device
    d1 = run0 - 300 * US
    l0 = d1 - 700 * US
    s0 = l0 - 200 * US
    f1 = run0 + run_dur + 1000 * US
    sid = f"s{k}"
    return [span("engine.plan", s0, l0, f"p{k}", sid),
            span("engine.launch", l0, d1 + 200 * US, f"l{k}", sid),
            span("engine.fused_step.dispatch", l0 + US, d1, f"d{k}",
                 f"l{k}"),
            span("engine.fetch", d1 + 201 * US, f1, f"f{k}", sid),
            span("engine.step", s0, f1 + 170 * US, sid, unqueued_ms=1.0)]


def record(trace, spans, monkeypatch):
    monkeypatch.setattr(idle_ledger, "recorded", lambda r: spans)
    return types.SimpleNamespace(trace=trace)


def test_the_recorded_trace_holds_one_fused_step_too_few_to_keep(
        recorded, monkeypatch, capsys):
    """The fixture's one run of the fused step pairs with one hand-made
    step, and a ledger keeps no first and no last pair: None, and the log
    says why."""
    (run,) = recorded.modules["jit__paged_fused_step"]
    r = record(recorded, step_spans(0, run[0], run[1], 420_000), monkeypatch)
    args = dict(ARGS, part="gap")
    assert idle_ledger.read(r, args) is None
    assert "idle_ledger: 1 paired steps" in capsys.readouterr().out
    # built once a run: the second metric does not log again
    assert idle_ledger.read(r, dict(args, part="emit")) is None
    assert capsys.readouterr().out == ""
    # the counter's reader needs no device run, and more than one step
    assert idle_ledger.read(r, UNQUEUED) is None
    two = step_spans(0, run[0], run[1], 0) + step_spans(
        1, run[0] + 12_000 * US, run[1], 0)
    assert idle_ledger.read(record(recorded, two, monkeypatch),
                            UNQUEUED) == \
        pytest.approx(100 * 1.0 / 12.0)
    # idle inside the programs: their runs' time the ops did not fill
    runs_s = sum(d for v in recorded.modules.values() for _s, d in v) / 1e9
    assert idle_inside_programs.read(r, {}) == pytest.approx(
        100 * (runs_s - recorded.busy_s) / recorded.window_s)
    assert 0.0 <= idle_inside_programs.read(r, {}) < 1.0


def test_the_adapter_on_the_recorded_run_repeated_and_hand_made_spans(
        recorded, monkeypatch, capsys):
    """Seven steps of the recorded run, 12 ms apart, the trace's two other
    programs run in the gap before the fourth: every part is what the
    spans were made to say, whatever the offset between the clocks."""
    (run,) = recorded.modules["jit__paged_fused_step"]
    period = 12_000 * US
    modules = {"jit__paged_fused_step": [(run[0] + k * period, run[1])
                                         for k in range(7)]}
    small = {n: v[0][1] for n, v in recorded.modules.items()
             if n != "jit__paged_fused_step"}
    assert len(small) == 2
    for i, (n, d) in enumerate(small.items()):
        modules[n] = [(run[0] + 3 * period - (i + 1) * 500 * US, d)]
    trace = types.SimpleNamespace(modules=modules, busy_s=recorded.busy_s,
                                  window_s=recorded.window_s)
    for off in (420_000, -5_000_000_000):
        spans = [s for k in range(7)
                 for s in step_spans(k, run[0] + k * period, run[1], off)]
        r = record(trace, spans, monkeypatch)
        got = {p: idle_ledger.read(r, dict(ARGS, part=p))
               for p, _layer in PARTS.values()}
        other = sum(small.values()) / 1e6 / 4      # one gap of the kept four
        assert got["gap"] == pytest.approx((period - run[1]) / 1e6 - other)
        assert got["emit"] == pytest.approx(0.170)
        assert got["plan"] == pytest.approx(0.200)
        assert got["copies"] == 0.0
        assert got["dispatch"] == pytest.approx(0.700)
        assert got["caller"] == pytest.approx(
            (period - run[1]) / 1e6 - 2.370)
        assert got["runtime"] == pytest.approx(1.300 - other)
        assert sum(v for p, v in got.items() if p != "gap") == \
            pytest.approx(got["gap"])
        summary = json.loads(capsys.readouterr().out.split(
            "idle_ledger: ", 1)[1])
        assert summary["pairs"] == 7 and summary["steps"] == 4
        assert summary["floored"] == 0
        lo, hi = summary["offset_window_ms"]
        assert lo <= off / 1e6 <= hi and hi - lo == pytest.approx(2.0)
        assert summary["runtime_start_return_ms_at_lower"] == \
            pytest.approx([-0.7, 2.0])
        assert summary["runtime_start_return_ms_at_upper"] == \
            pytest.approx([1.3, 0.0])


def test_no_trace_no_module_and_a_wrong_join_read_none(recorded,
                                                       monkeypatch, capsys):
    args = dict(ARGS, part="gap")
    assert idle_ledger.read(record(None, [], monkeypatch), args) is None
    assert idle_inside_programs.read(types.SimpleNamespace(trace=None),
                                     {}) is None
    empty = types.SimpleNamespace(modules={}, busy_s=0.0, window_s=1.0)
    assert idle_ledger.read(record(empty, [], monkeypatch), args) is None
    assert idle_inside_programs.read(types.SimpleNamespace(trace=empty),
                                     {}) is None
    assert capsys.readouterr().out == ""
    # a program from before the spans: runs, and no step to pair them with
    (run,) = recorded.modules["jit__paged_fused_step"]
    assert idle_ledger.read(record(recorded, [], monkeypatch), args) is None
    assert "0 engine.step spans" in capsys.readouterr().out
    assert idle_ledger.read(record(recorded, [], monkeypatch),
                            UNQUEUED) is None


def test_manifest_appends_eighteen_with_files_readers_and_cells():
    doc = load_json(ROOT, "BENCHMARK.json")
    ours = [m for m in doc["per_layer"] if m["name"] in NAMES]
    assert sorted(m["name"] for m in ours) == NAMES and len(NAMES) == 18
    # appended: nothing the benchmark had comes after them
    assert doc["per_layer"][-18:] == ours
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    for m in ours:
        base, sfx = m["name"].rsplit(".", 1)
        cells = ROLLOUT if sfx == "rollout" else ITL
        assert m["workloads"] == cells
        assert m["moves"] == ("rollout_tok_s" if sfx == "rollout"
                              else "itl_p99_ms")
        for cell in cells:
            assert cell in e2e[m["moves"]]["workloads"]
            assert m in Manifest(cell).per_layer()
        assert m["better"] == "lower"
        spec = load_json(HERE, "layer_metrics", m["name"] + ".json")
        assert spec["layer"] == m["layer"]
        reader = importlib.import_module(
            "benchmark.readers." + spec["reader"])
        assert callable(reader.read)
        if base in PARTS:
            part, layer = PARTS[base]
            assert spec == {"layer": layer, "reader": "idle_ledger",
                            "args": dict(ARGS, part=part)}
            assert (m["unit"], m["source"]) == ("ms", "device_trace")
        elif base == "engine_unqueued_share":
            assert spec["args"] == UNQUEUED
            assert (m["unit"], m["source"], m["layer"]) == (
                "%", "program_counter", "engine host")
        else:
            assert spec["reader"] == "idle_inside_programs"
            assert (m["unit"], m["source"], m["layer"]) == (
                "%", "device_trace", "fused step")
    # the set of program_span metrics is another test's to hold at nine
    assert not any(m["source"] == "program_span" for m in ours)


@pytest.mark.parametrize("cell,sfx", [("qwen1.5b-grpo-rollout-sat",
                                       "rollout"),
                                      ("qwen1.5b-chat-open", "itl")])
def test_rehearsal_reports_the_counter_traced_and_nothing_untraced(cell,
                                                                   sfx):
    """A CPU trace has no device plane, so no run of a program: the
    ``device_trace`` metrics read None without raising and the line leaves
    them out; the program's counter is there. Names only, never a number."""
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
    ours = {n for n in NAMES if n.endswith("." + sfx)}
    assert seen["1"] & ours == {f"engine_unqueued_share.{sfx}"}
    assert not seen["0"] & set(NAMES)
