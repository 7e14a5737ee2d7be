"""Distributed tracing + SLO timelines (PR 8): propagation units, the
timeline/SLO accounting layer, and fleet-level end-to-end stitching —
over loopback, over a REAL HTTP socket, and under network chaos.

The load-bearing invariants:

- one RPC → one stitched trace: the server span's ``parent_id`` is the
  client-attempt span that physically carried it, across processes;
- retried/replayed RPCs ANNOTATE spans (``replay=True``) but never
  duplicate timelines — exactly one finished timeline per request, no
  matter how many times chaos replays the path;
- the per-priority ``senweaver_serve_*_seconds`` histograms and the
  violation/exemplar machinery populate from real fleet traffic.
"""

import json

import jax
import pytest

from senweaver_ide_tpu import obs
from senweaver_ide_tpu.models import init_params, tiny_test
from senweaver_ide_tpu.obs.propagation import (TraceContext, extract,
                                               format_traceparent, inject,
                                               parse_traceparent,
                                               server_span)
from senweaver_ide_tpu.obs.slo import SLOConfig, SLOTarget, SLOTracker
from senweaver_ide_tpu.obs.timeline import (RequestTimeline,
                                            TimelineRecorder)
from senweaver_ide_tpu.obs.tracing import Tracer
from senweaver_ide_tpu.resilience import (NetworkFault, NetworkFaultPlan,
                                          RetryPolicy)
from senweaver_ide_tpu.rollout import RolloutEngine
from senweaver_ide_tpu.rollout.sampler import SampleParams
from senweaver_ide_tpu.serve import (Completed, EngineRpcHandler,
                                     HttpTransport, LoopbackTransport,
                                     RemoteReplica, ServingFleet,
                                     serve_engine_http)

GREEDY = SampleParams(temperature=0.0, top_k=0, top_p=1.0)
FAST = RetryPolicy(max_retries=3, base_delay_s=0.0, jitter=False)


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs._reset_for_tests()
    yield
    obs._reset_for_tests()


@pytest.fixture(scope="module")
def model():
    config = tiny_test()
    params = init_params(config, jax.random.PRNGKey(0))
    return params, config


class FakeClock:
    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


def make_fleet(model, n, *, clock, plan=None, slo=None, max_retries=4,
               probe_interval_s=0.0):
    """N remote replicas over wire-honest loopback transports."""
    params, config = model
    handlers, replicas = [], []
    for i in range(n):
        h = EngineRpcHandler(RolloutEngine(params, config, num_slots=2,
                                           max_len=64, sample=GREEDY))
        r = RemoteReplica(
            f"replica-{i}",
            LoopbackTransport(h, target=f"replica-{i}", fault_plan=plan,
                              wire_codec=True),
            policy=FAST, clock=clock, sleep=lambda s: None)
        handlers.append(h)
        replicas.append(r)
    fleet = ServingFleet(replicas, clock=clock, retry_base_delay_s=0.0,
                         max_retries=max_retries,
                         probe_interval_s=probe_interval_s, slo=slo)
    return fleet, handlers


def pump(fleet, clock, rounds=200, dt=0.01):
    for _ in range(rounds):
        if not fleet.pending():
            return
        clock.advance(dt)
        fleet.step()
    raise AssertionError("fleet did not drain")


# ---- propagation units ---------------------------------------------------

def test_traceparent_roundtrip_and_malformed():
    header = format_traceparent("abc123", "def456")
    assert header == "00-abc123-def456-01"
    assert parse_traceparent(header) == ("abc123", "def456", True)
    assert parse_traceparent(
        format_traceparent("t", "s", sampled=False)) == ("t", "s", False)
    for bad in (None, 42, "", "00-only-three", "01-t-s-01",
                "00--s-01", "00-t--01", "00-t-s-zz",
                "00-t-s-01-extra"):
        assert parse_traceparent(bad) is None


def test_inject_requires_enabled_tracer_and_active_span():
    t = Tracer(enabled=False)
    assert inject(t) is None                  # disabled
    t = Tracer(enabled=True)
    assert inject(t) is None                  # enabled, but no span open
    with t.span("client.op"):
        wire = inject(t)
        assert set(wire) == {"traceparent", "wall_s", "mono_s"}
        trace_id, span_id, sampled = parse_traceparent(
            wire["traceparent"])
        assert (trace_id, span_id) == t.capture()
        assert sampled


def test_extract_is_tolerant():
    assert extract(None) is None
    assert extract("00-t-s-01") is None       # must be the frame dict
    assert extract({}) is None
    assert extract({"traceparent": "garbage"}) is None
    ctx = extract({"traceparent": "00-t-s-01",
                   "wall_s": "nan-ish", "mono_s": None})
    assert ctx is not None and ctx.wall_s == 0.0  # bad anchors zeroed
    ctx = extract({"traceparent": "00-t-s-01", "wall_s": 12.5,
                   "mono_s": 3.25})
    assert ctx == TraceContext(trace_id="t", span_id="s",
                               wall_s=12.5, mono_s=3.25)


def test_server_span_attaches_under_remote_context():
    t = Tracer(enabled=True)
    with t.span("rpc.client.submit"):
        wire = inject(t)
    client = t.spans()[-1]
    with server_span(t, wire, "rpc.server.submit", method="submit") as sp:
        assert sp is not None
        sp.set_attr("replay", True)
    server = t.spans()[-1]
    assert server.trace_id == client.trace_id
    assert server.parent_id == client.span_id
    assert server.attrs["remote"] is True
    assert "clock_skew_s" in server.attrs
    assert server.attrs["replay"] is True
    # No propagated context → a local root, no remote/skew annotation.
    with server_span(t, None, "rpc.server.health"):
        pass
    root = t.spans()[-1]
    assert root.parent_id is None and "remote" not in root.attrs
    # Disabled tracer → yields None, records nothing, never raises.
    off = Tracer(enabled=False)
    with server_span(off, wire, "rpc.server.submit") as sp:
        assert sp is None
    assert off.spans() == []


# ---- timeline / SLO units ------------------------------------------------

def test_timeline_derives_slo_quantities():
    tl = RequestTimeline(ticket=1, priority="interactive")
    assert tl.mark("admitted", 10.0)
    assert tl.mark("queue_exit", 10.2)
    assert tl.mark("dispatched", 10.3)
    assert tl.mark("first_token", 10.5)
    assert not tl.mark("first_token", 99.0)   # first-wins
    tl.tokens = 5
    tl.mark("completed", 11.3)
    d = tl.derive(publish_windows=[(10.9, 11.1), (50.0, 60.0)])
    assert d["queue_wait_s"] == pytest.approx(0.2)
    assert d["ttft_s"] == pytest.approx(0.5)
    assert d["e2e_s"] == pytest.approx(1.3)
    assert d["tpot_s"] == pytest.approx(0.8 / 4)  # (end-first)/(tokens-1)
    assert d["publish_pause_s"] == pytest.approx(0.2)  # overlap only


def test_recorder_exactly_once_finish_and_metrics():
    clock = FakeClock()
    slo = SLOTracker(SLOConfig(exemplar_k=4))
    rec = TimelineRecorder(clock=clock, slo=slo)
    rec.begin(7, "interactive")
    assert rec.live_count() == 1
    assert rec.mark(7, "first_token", clock.advance(0.1))
    assert not rec.mark(7, "first_token", clock.advance(0.1))
    rec.event(7, "retry", attempt=1)
    tl = rec.finish_completed(7, clock.advance(0.1), tokens=3,
                              replica_id="replica-0", attempts=1)
    assert tl is not None and tl.outcome == "completed"
    # Second finish (a replayed completion) finds nothing to pop.
    assert rec.finish_completed(7) is None
    assert rec.live_count() == 0
    reg = obs.get_registry()
    assert reg.get("senweaver_serve_timelines_total").value(
        outcome="completed") == 1
    # Unknown tickets never raise into the dispatch path.
    assert rec.mark(999, "first_token") is False
    rec.event(999, "retry")
    assert rec.finish_completed(999) is None


def test_slo_tracker_violations_burn_and_exemplars(tmp_path):
    cfg = SLOConfig(interactive=SLOTarget(ttft_s=0.1, e2e_s=1.0),
                    exemplar_k=2)
    slo = SLOTracker(cfg)

    def finished(ticket, ttft, e2e):
        tl = RequestTimeline(ticket=ticket, priority="interactive")
        tl.mark("admitted", 0.0)
        tl.mark("first_token", ttft)
        tl.tokens = 2
        tl.mark("completed", e2e)
        tl.derive([])
        return tl

    assert slo.observe(finished(1, ttft=0.05, e2e=0.5)) == []
    assert slo.observe(finished(2, ttft=0.2, e2e=0.5)) == ["ttft_s"]
    assert set(slo.observe(finished(3, ttft=0.3, e2e=2.0))) == \
        {"ttft_s", "e2e_s"}
    reg = obs.get_registry()
    viol = reg.get("senweaver_serve_slo_violations_total")
    assert viol.value(priority="interactive", slo="ttft_s") == 2
    assert viol.value(priority="interactive", slo="e2e_s") == 1
    summary = slo.summary()
    cls = summary["per_class"]["interactive"]
    assert cls["requests"] == 3 and cls["violating"] == 2
    assert cls["burn_ratio"] == pytest.approx(2 / 3)
    # K=2 keeps the WORST two: both violators, worst first.
    ex = slo.exemplars()
    assert [e["ticket"] for e in ex] == [3, 2]
    assert all(e["violations"] for e in ex)
    path = slo.export_jsonl(str(tmp_path / "ex.jsonl"))
    lines = [json.loads(ln) for ln in open(path)]
    assert [e["ticket"] for e in lines] == [3, 2]


def test_tracer_dropped_spans_counter():
    t = Tracer(enabled=True, max_spans=2)
    for i in range(5):
        with t.span(f"s{i}"):
            pass
    assert t.summary()["dropped_spans"] == 3
    assert obs.get_registry().get(
        "senweaver_obs_spans_dropped_total").value() == 3


# ---- fleet end-to-end: loopback stitching --------------------------------

def test_loopback_fleet_single_stitched_trace_per_request(model):
    obs.enable()
    clock = FakeClock()
    fleet, handlers = make_fleet(model, 2, clock=clock)
    tickets = [fleet.submit([3 + i, 5 + i, 7 + i], max_new_tokens=4,
                            priority="interactive")
               for i in range(2)]
    tickets.append(fleet.submit([9, 11], max_new_tokens=4))
    pump(fleet, clock)
    assert all(isinstance(fleet.outcome(t), Completed) for t in tickets)

    stitch = obs.stitch_summary(obs.get_tracer().spans())
    assert stitch["server_spans"] > 0
    assert stitch["unstitched_server_spans"] == 0
    assert stitch["cross_process_traces"] >= len(tickets)
    # Spot-check one submit RPC: server span hangs off the exact client
    # attempt that carried it, in the same trace.
    spans = obs.get_tracer().spans()
    server = next(s for s in spans if s.name == "rpc.server.submit")
    client = next(s for s in spans if s.span_id == server.parent_id)
    assert client.name == "rpc.client.submit"
    assert client.trace_id == server.trace_id
    assert server.attrs.get("remote") is True

    # The per-priority seconds histograms populated for BOTH classes.
    reg = obs.get_registry()
    for name in ("senweaver_serve_ttft_seconds",
                 "senweaver_serve_e2e_seconds",
                 "senweaver_serve_queue_wait_seconds"):
        hist = reg.get(name)
        assert hist.snapshot(priority="interactive")["count"] == 2
        assert hist.snapshot(priority="train_rollout")["count"] == 1
    # Each finished timeline carries the trace id of its dispatch tree.
    ex = fleet.slo.exemplars()
    assert len(ex) == len(tickets)
    assert all(e["trace_id"] for e in ex)
    trace_ids = {s.trace_id for s in spans}
    assert all(e["trace_id"] in trace_ids for e in ex)


def test_http_end_to_end_stitches_and_fills_histograms(model):
    """One replica across a REAL loopback HTTP socket with tracing on:
    the trace field survives the JSON codec and the server-side spans
    stitch under their client attempts."""
    obs.enable()
    params, config = model
    server, port = serve_engine_http(EngineRpcHandler(
        RolloutEngine(params, config, num_slots=2, max_len=64,
                      sample=GREEDY)))
    try:
        fleet = ServingFleet([RemoteReplica(
            "replica-0",
            HttpTransport(f"http://127.0.0.1:{port}", timeout_s=30.0,
                          target="replica-0"),
            policy=RetryPolicy(max_retries=1, base_delay_s=0.01))])
        t = fleet.submit([5, 9, 2, 7], max_new_tokens=4,
                         priority="interactive")
        fleet.run()
        assert isinstance(fleet.outcome(t), Completed)
    finally:
        server.shutdown()

    stitch = obs.stitch_summary(obs.get_tracer().spans())
    assert stitch["server_spans"] > 0
    assert stitch["unstitched_server_spans"] == 0
    assert stitch["cross_process_traces"] >= 1
    # The wall-clock anchors crossed the wire: every remote server span
    # carries a skew estimate (same host here, so it is tiny but real).
    skewed = [s for s in obs.get_tracer().spans()
              if s.attrs.get("remote")]
    assert skewed and all("clock_skew_s" in s.attrs for s in skewed)
    hist = obs.get_registry().get("senweaver_serve_e2e_seconds")
    assert hist.snapshot(priority="interactive")["count"] == 1


# ---- chaos: replayed RPCs never double-count -----------------------------

def test_drop_response_chaos_one_timeline_one_execution(model):
    """Lost submit RESPONSE: the server executed, the client retried,
    the idempotency cache replayed. One request must yield exactly one
    server execution, one finished timeline, and a replay-annotated
    (not duplicated) server span."""
    obs.enable()
    clock = FakeClock()
    plan = NetworkFaultPlan([
        NetworkFault(kind="drop_response", method="submit", call_idx=0)])
    fleet, handlers = make_fleet(model, 1, clock=clock, plan=plan)
    t = fleet.submit([5, 9, 2], max_new_tokens=4, priority="interactive")
    pump(fleet, clock)
    assert isinstance(fleet.outcome(t), Completed)

    assert sum(h.executed.get("submit", 0) for h in handlers) == 1
    assert sum(h.replays for h in handlers) >= 1
    reg = obs.get_registry()
    assert reg.get("senweaver_serve_timelines_total").value(
        outcome="completed") == 1
    assert fleet.timelines.live_count() == 0
    assert reg.get("senweaver_serve_slo_requests_total").value(
        priority="interactive") == 1

    submits = [s for s in obs.get_tracer().spans()
               if s.name == "rpc.server.submit"]
    executed = [s for s in submits if not s.attrs.get("replay")]
    replayed = [s for s in submits if s.attrs.get("replay")]
    assert len(executed) == 1 and len(replayed) >= 1
    # The replay span still stitches into the SAME trace as the retry
    # attempt that triggered it.
    assert all(s.parent_id for s in replayed)


def test_drop_request_chaos_one_timeline(model):
    """Lost submit REQUEST (never executed): pure client retry — no
    replay, one execution, one timeline."""
    obs.enable()
    clock = FakeClock()
    plan = NetworkFaultPlan([
        NetworkFault(kind="drop", method="submit", call_idx=0)])
    fleet, handlers = make_fleet(model, 1, clock=clock, plan=plan)
    t = fleet.submit([5, 9, 2], max_new_tokens=4)
    pump(fleet, clock)
    assert isinstance(fleet.outcome(t), Completed)
    assert sum(h.executed.get("submit", 0) for h in handlers) == 1
    assert sum(h.replays for h in handlers) == 0
    assert obs.get_registry().get(
        "senweaver_serve_timelines_total").value(outcome="completed") == 1
    assert fleet.timelines.live_count() == 0


def test_failover_records_event_not_second_timeline(model):
    """Replica death mid-request: the fleet fails the request over to a
    survivor — the timeline records the failover as an EVENT and still
    finishes exactly once."""
    obs.enable()
    clock = FakeClock()
    plan = NetworkFaultPlan()
    # Health probes are the partition detector — they need an interval.
    fleet, handlers = make_fleet(model, 2, clock=clock, plan=plan,
                                 probe_interval_s=1.0, max_retries=6)
    t = fleet.submit([5, 9, 2, 7], max_new_tokens=4,
                     priority="interactive")
    fleet.step()                              # dispatched somewhere
    holder = fleet._requests[t].replica_id
    plan.partition(holder)
    pump(fleet, clock, rounds=120, dt=1.0)
    assert isinstance(fleet.outcome(t), Completed)
    reg = obs.get_registry()
    assert reg.get("senweaver_serve_timelines_total").value(
        outcome="completed") == 1
    assert fleet.timelines.live_count() == 0
    (ex,) = fleet.slo.exemplars()
    names = [e["event"] for e in ex["events"]]
    assert any(n in ("failover", "retry") for n in names)
    assert ex["attempts"] >= 1
    # The dispatched milestone was re-marked on retry but first-wins
    # kept ONE timestamp.
    assert "dispatched" in ex["milestones"]


# ---- telemetry satellites ------------------------------------------------

def test_advantage_stats_flags_degenerate_groups():
    stats = obs.advantage_stats([1.0, 1.0, 0.0, 2.0], [0, 0, 1, 1])
    assert stats["groups"] == 2
    assert stats["zero_advantage_group_fraction"] == pytest.approx(0.5)
    assert stats["advantage_std"] == pytest.approx(0.5 ** 0.5)
    # All-identical rewards: every group degenerate, zero spread.
    stats = obs.advantage_stats([3.0] * 4, [0, 0, 1, 1])
    assert stats["zero_advantage_group_fraction"] == 1.0
    assert stats["advantage_std"] == 0.0
    # Empty / mismatched inputs are bookkeeping no-ops, not raises.
    assert obs.advantage_stats([], [])["groups"] == 0
    assert obs.advantage_stats([1.0], [0, 1])["groups"] == 0


def test_record_round_publishes_advantage_gauges():
    tel = obs.StepTelemetry(registry=obs.get_registry())
    out = tel.record_round(
        collect_s=1.0, batch_build_s=0.1, train_s=0.5,
        batch_tokens=128, episodes=4,
        advantage_stats={"zero_advantage_group_fraction": 0.25,
                         "advantage_std": 0.7, "groups": 4})
    assert out["zero_advantage_group_fraction"] == 0.25
    assert out["advantage_std"] == 0.7
    reg = obs.get_registry()
    assert reg.get(
        "senweaver_grpo_zero_advantage_group_fraction").value() == 0.25
    assert reg.get("senweaver_grpo_advantage_std").value() == 0.7
