"""Fleet observability plane: federation, burn-rate alerts, incidents.

Covers the scrape delta protocol (exactly-once via the idempotency
cache), the bounded federated series store (stale peers gap — never
interpolate), rollup math, the alert manager's hysteresis edges, the
incident correlator's ranked causes, the snapshot_delta contract, and
the render-vs-concurrent-inc thread-safety regression on the registry.
"""

import threading
from concurrent.futures import ThreadPoolExecutor

import pytest

from senweaver_ide_tpu import obs
from senweaver_ide_tpu.obs import (AlertManager, AlertRule, EventJournal,
                                   FleetMetricsStore, IncidentCorrelator,
                                   MetricsFederator, MetricsRegistry,
                                   MetricsScrapeMixin)


@pytest.fixture(autouse=True)
def fresh_obs():
    obs._reset_for_tests()
    yield
    obs._reset_for_tests()


class FakeClock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


# ---- snapshot_delta: the scrape wire format ----

def test_snapshot_delta_full_resync_when_no_baseline():
    reg = MetricsRegistry()
    reg.counter("c", "").inc(3)
    delta, snap = reg.snapshot_delta(None)
    assert delta == snap
    assert delta["c"]["values"][""] == 3.0


def test_snapshot_delta_ships_only_changed_cells():
    reg = MetricsRegistry()
    c = reg.counter("c", "", labelnames=("k",))
    g = reg.gauge("g", "")
    c.inc(k="a")
    c.inc(k="b")
    g.set(1.0)
    _, base = reg.snapshot_delta(None)
    c.inc(2, k="b")          # only cell "b" moves
    g.set(0.25)              # gauges always ship absolute
    delta, snap = reg.snapshot_delta(base)
    assert delta["c"]["values"] == {"b": 2.0}    # increment, not total
    assert snap["c"]["values"]["b"] == 3.0       # snapshot stays absolute
    assert delta["g"]["values"][""] == 0.25
    # An unchanged registry produces an EMPTY delta (nothing to ship).
    delta2, _ = reg.snapshot_delta(snap)
    assert "c" not in delta2


def test_snapshot_delta_histogram_cells_are_increments():
    reg = MetricsRegistry()
    h = reg.histogram("h", "")
    h.observe(10.0)
    _, base = reg.snapshot_delta(None)
    h.observe(30.0)
    delta, _ = reg.snapshot_delta(base)
    cell = delta["h"]["values"][""]
    assert cell["count"] == 1
    assert cell["sum"] == pytest.approx(30.0)


def test_snapshot_delta_new_metric_ships_whole():
    reg = MetricsRegistry()
    _, base = reg.snapshot_delta(None)
    reg.counter("late", "").inc(5)
    delta, _ = reg.snapshot_delta(base)
    assert delta["late"]["values"][""] == 5.0


# ---- registry thread-safety: render vs concurrent inc ----

def test_render_during_concurrent_labeled_incs_is_safe_and_exact():
    """Regression: Prometheus exposition while writer threads create
    NEW labeled cells must neither raise (dict-changed-size) nor lose
    increments."""
    reg = MetricsRegistry()
    c = reg.counter("c", "", labelnames=("k",))
    stop = threading.Event()
    errors = []

    def renderer():
        while not stop.is_set():
            try:
                reg.render()
                reg.snapshot()
                reg.snapshot_delta(None)
            except Exception as e:     # pragma: no cover - the bug
                errors.append(e)
                return

    def writer(base):
        for i in range(500):
            c.inc(k=f"{base}-{i % 50}")

    render_thread = threading.Thread(target=renderer)
    render_thread.start()
    with ThreadPoolExecutor(max_workers=4) as pool:
        list(pool.map(writer, range(4)))
    stop.set()
    render_thread.join(timeout=10)
    assert not errors
    assert sum(c.samples().values()) == 4 * 500


# ---- scrape mixin: cursors + exactly-once replay ----

class _Handler(MetricsScrapeMixin):
    """Bare mixin host (no rpc base needed for direct-call tests)."""


def _handler(reg, journal, clock, peer="p1"):
    h = _Handler()
    h.scrape_registry = reg
    h.scrape_journal = journal
    h.scrape_clock = clock
    h.scrape_peer = peer
    return h


def test_scrape_first_full_then_delta_per_scraper():
    clock = FakeClock()
    reg = MetricsRegistry()
    journal = EventJournal(clock=clock)
    c = reg.counter("c", "")
    c.inc(2)
    h = _handler(reg, journal, clock)
    first = h._m_scrape(scraper_id="fed")
    assert first["mode"] == "full"
    assert first["peer"] == "p1"
    assert first["metrics"]["c"]["values"][""] == 2.0
    c.inc(3)
    journal.emit("publish_begin", version=7)
    second = h._m_scrape(scraper_id="fed")
    assert second["mode"] == "delta"
    assert second["metrics"]["c"]["values"][""] == 3.0
    assert [e["kind"] for e in second["events"]] == ["publish_begin"]
    # A DIFFERENT scraper has its own cursor: still full.
    other = h._m_scrape(scraper_id="other")
    assert other["mode"] == "full"
    assert other["metrics"]["c"]["values"][""] == 5.0


def test_retried_scrape_replays_cached_delta_exactly_once():
    """The reason scrape is a MUTATING method: the retry must replay
    the same delta, not advance the cursor twice and skip a window."""
    from senweaver_ide_tpu.serve.remote_server import RpcHandlerBase

    class H(MetricsScrapeMixin, RpcHandlerBase):
        mutating_methods = frozenset({"scrape"})

    clock = FakeClock()
    reg = MetricsRegistry()
    h = H()
    h.scrape_registry = reg
    h.scrape_journal = EventJournal(clock=clock)
    h.scrape_clock = clock
    c = reg.counter("c", "")
    c.inc(1)
    h.handle("scrape", {"scraper_id": "fed"}, request_id="s1")
    c.inc(4)
    a = h.handle("scrape", {"scraper_id": "fed"}, request_id="s2")
    c.inc(100)  # movement AFTER the scrape being retried
    b = h.handle("scrape", {"scraper_id": "fed"}, request_id="s2")
    assert b == a                       # replay, not a fresh delta
    assert h.replays == 1
    nxt = h.handle("scrape", {"scraper_id": "fed"}, request_id="s3")
    assert nxt["metrics"]["c"]["values"][""] == 100.0   # nothing lost


# ---- FleetMetricsStore: rings, staleness, rollups ----

def _full_payload(metrics, events=(), t=0.0, peer=None):
    return {"peer": peer, "t": t, "mode": "full", "metrics": metrics,
            "events": list(events)}


def test_store_rollups_counter_sum_gauge_max_and_worst_peer():
    clock = FakeClock()
    store = FleetMetricsStore(clock=clock, registry=MetricsRegistry())
    store.ingest("a", _full_payload({
        "senweaver_kv_pressure": {"kind": "gauge", "labels": [],
                                  "values": {"": 0.4}},
        "senweaver_serve_shed_total": {"kind": "counter", "labels": [],
                                       "values": {"": 3.0}}}))
    store.ingest("b", _full_payload({
        "senweaver_kv_pressure": {"kind": "gauge", "labels": [],
                                  "values": {"": 0.9}},
        "senweaver_serve_shed_total": {"kind": "counter", "labels": [],
                                       "values": {"": 5.0}}}))
    assert store.rollup_value("senweaver_kv_pressure", "max") == 0.9
    assert store.rollup_value("senweaver_kv_pressure", "min") == 0.4
    assert store.rollup_value("senweaver_serve_shed_total", "sum") == 8.0
    assert store.worst_peer("senweaver_kv_pressure") == ("b", 0.9)
    assert store.rollup_value("nope", "max") is None


def test_stale_peer_rings_gap_and_leave_rollups():
    clock = FakeClock()
    store = FleetMetricsStore(clock=clock, registry=MetricsRegistry())
    g = {"senweaver_kv_pressure": {"kind": "gauge", "labels": [],
                                   "values": {"": 0.9}}}
    store.ingest("a", _full_payload(g), t=1.0)
    n_before = len(store.series("senweaver_kv_pressure", peer="a"))
    store.mark_stale("a", t=2.0)
    store.mark_stale("a", t=3.0)
    # The gap IS the record: no points fabricated while stale.
    assert len(store.series("senweaver_kv_pressure", peer="a")) == n_before
    assert store.is_stale("a")
    assert store.rollup_value("senweaver_kv_pressure", "max") is None
    assert store.rollup_value("senweaver_kv_pressure", "max",
                              include_stale=True) == 0.9
    # Recovery: a successful ingest un-stales and resumes the ring.
    store.ingest("a", _full_payload(g), t=4.0)
    assert not store.is_stale("a")
    assert len(store.series("senweaver_kv_pressure",
                            peer="a")) == n_before + 1


def test_window_delta_per_peer_and_zero_baseline():
    clock = FakeClock()
    store = FleetMetricsStore(clock=clock, registry=MetricsRegistry())
    cnt = lambda v: {"c": {"kind": "counter", "labels": [],  # noqa: E731
                           "values": {"": v}}}
    store.ingest("a", _full_payload(cnt(2.0)), t=1.0)
    store.ingest("a", {"peer": "a", "t": 5.0, "mode": "delta",
                       "metrics": cnt(4.0), "events": []}, t=5.0)
    clock.t = 6.0
    # No pre-window point at t<=‑54: baseline 0 → everything counts.
    assert store.window_delta("c", 60.0) == 6.0
    assert store.window_delta("c", 60.0, per_peer=True) == {"a": 6.0}
    # Tight window: only the t=5 point is inside; the t=1 point (2.0)
    # is the pre-window baseline.
    assert store.window_delta("c", 3.0) == 4.0


# ---- MetricsFederator over real loopback rpc + chaos ----

def _rpc_handler(reg, journal, clock, peer):
    from senweaver_ide_tpu.serve.remote_server import RpcHandlerBase

    class H(MetricsScrapeMixin, RpcHandlerBase):
        mutating_methods = frozenset({"scrape"})

    h = H()
    h.scrape_registry = reg
    h.scrape_journal = journal
    h.scrape_clock = clock
    h.scrape_peer = peer
    return h


def test_federator_partition_marks_stale_then_recovers_full():
    from senweaver_ide_tpu.resilience import NetworkFaultPlan
    from senweaver_ide_tpu.serve.rpc import LoopbackTransport

    clock = FakeClock()
    journal = EventJournal(clock=clock)
    store = FleetMetricsStore(clock=clock, registry=MetricsRegistry())
    reg = MetricsRegistry()
    c = reg.counter("c", "")
    plan = NetworkFaultPlan()
    fed = MetricsFederator(
        store,
        {"p1": LoopbackTransport(_rpc_handler(reg, journal, clock, "p1"),
                                 target="p1", fault_plan=plan)},
        clock=clock, journal=journal, interval_s=0.0, retries=0)
    c.inc(1)
    assert fed.scrape_once(clock.advance(1.0)) == {"p1": "ok"}
    plan.partition("p1")
    c.inc(10)  # movement the federation cannot see
    assert fed.scrape_once(clock.advance(1.0)) == {"p1": "stale"}
    assert fed.scrape_once(clock.advance(1.0)) == {"p1": "stale"}
    assert store.is_stale("p1")
    # journal: unreachable stamped ONCE per outage, not per sweep
    kinds = [e["kind"] for e in journal.recent()]
    assert kinds.count("peer_unreachable") == 1
    plan.heal("p1")
    assert fed.scrape_once(clock.advance(1.0)) == {"p1": "ok"}
    assert not store.is_stale("p1")
    kinds = [e["kind"] for e in journal.recent()]
    assert kinds.count("peer_recovered") == 1
    # Post-recovery resync is FULL: absolute value, nothing skipped.
    assert store.cells("c", "p1")[""] == 11.0


# ---- AlertManager hysteresis ----

def test_threshold_alert_sustain_fire_hold_clear_no_flap():
    clock = FakeClock()
    store = FleetMetricsStore(clock=clock, registry=MetricsRegistry())
    rule = AlertRule(name="kv", kind="threshold",
                     metric="senweaver_kv_pressure",
                     threshold=0.85, clear_threshold=0.75,
                     sustain_s=2.0, hold_s=10.0)
    mgr = AlertManager(store, [rule], clock=clock,
                       registry=MetricsRegistry(),
                       journal=EventJournal(clock=clock))
    gauge = lambda v: _full_payload(                     # noqa: E731
        {"senweaver_kv_pressure": {"kind": "gauge", "labels": [],
                                   "values": {"": v}}})

    store.ingest("a", gauge(0.95), t=0.0)
    assert mgr.evaluate(0.0) == []          # sustain clock just started
    assert mgr.evaluate(1.0) == []
    assert mgr.evaluate(2.5) == ["kv"]      # sustained past 2s → edge
    assert mgr.evaluate(3.0) == []          # level, not edge
    assert mgr.active() == ["kv"]
    # Dips below clear BEFORE hold_s elapses: still firing (hysteresis).
    store.ingest("a", gauge(0.5), t=4.0)
    mgr.evaluate(4.0)
    assert mgr.active() == ["kv"]
    # A bounce back up must NOT re-fire (no flap).
    store.ingest("a", gauge(0.95), t=6.0)
    mgr.evaluate(6.0)
    store.ingest("a", gauge(0.5), t=13.0)
    mgr.evaluate(13.0)                      # below clear AND past hold
    assert mgr.active() == []
    assert mgr.transitions("kv") == 2       # fired once, cleared once


def test_sustain_resets_on_dip_below_threshold():
    clock = FakeClock()
    store = FleetMetricsStore(clock=clock, registry=MetricsRegistry())
    rule = AlertRule(name="kv", kind="threshold", metric="m",
                     threshold=0.8, sustain_s=5.0, hold_s=1.0)
    mgr = AlertManager(store, [rule], clock=clock,
                       registry=MetricsRegistry(),
                       journal=EventJournal(clock=clock))
    m = lambda v: _full_payload(                         # noqa: E731
        {"m": {"kind": "gauge", "labels": [], "values": {"": v}}})
    store.ingest("a", m(0.9), t=0.0)
    mgr.evaluate(0.0)
    store.ingest("a", m(0.1), t=3.0)        # dip breaks the sustain run
    mgr.evaluate(3.0)
    store.ingest("a", m(0.9), t=4.0)
    mgr.evaluate(4.0)
    assert mgr.evaluate(6.0) == []          # only 2s of the NEW run
    assert mgr.evaluate(9.5) == ["kv"]


def test_stale_peers_rule_fires_on_marked_peer():
    clock = FakeClock()
    store = FleetMetricsStore(clock=clock, registry=MetricsRegistry())
    rule = AlertRule(name="stale", kind="stale_peers", threshold=1.0,
                     sustain_s=0.0, hold_s=1.0)
    mgr = AlertManager(store, [rule], clock=clock,
                       registry=MetricsRegistry(),
                       journal=EventJournal(clock=clock))
    store.ingest("a", _full_payload({}), t=0.0)
    assert mgr.evaluate(0.5) == []
    store.mark_stale("a", t=1.0)
    assert mgr.evaluate(1.0) == ["stale"]


# ---- IncidentCorrelator ----

def test_correlator_ranks_journal_cause_and_same_peer_bonus():
    clock = FakeClock(t=100.0)
    store = FleetMetricsStore(clock=clock, registry=MetricsRegistry())
    store.ingest("bad", _full_payload(
        {"senweaver_kv_pressure": {"kind": "gauge", "labels": [],
                                   "values": {"": 0.99}}},
        events=[{"kind": "publish_begin", "t": 95.0, "seq": 1,
                 "version": 3}]), t=99.0)
    corr = IncidentCorrelator(store, clock=clock, window_s=60.0,
                              registry=MetricsRegistry())
    rule = AlertRule(name="kv", kind="threshold",
                     metric="senweaver_kv_pressure", threshold=0.85,
                     causes=(("publish_begin", 1.0),))
    inc = corr.on_alert(rule, 0.99, now=100.0)
    assert inc.alert == "kv"
    assert inc.worst_peer == "bad"
    top = inc.top_cause
    assert top["cause"] == "publish_begin"
    assert top["event"]["peer"] == "bad"
    assert "publish_begin" in inc.summary
    assert corr.incidents(1)[0].incident_id == inc.incident_id


def test_correlator_synthesizes_causes_from_counter_movement():
    clock = FakeClock(t=10.0)
    store = FleetMetricsStore(clock=clock, registry=MetricsRegistry())
    evict = lambda v: {"senweaver_kv_evictions_total": {  # noqa: E731
        "kind": "counter", "labels": [], "values": {"": v}}}
    store.ingest("a", _full_payload(evict(0.0)), t=10.0)
    clock.t = 50.0
    store.ingest("a", {"peer": "a", "t": 50.0, "mode": "delta",
                       "metrics": evict(12.0), "events": []}, t=50.0)
    corr = IncidentCorrelator(store, clock=clock, window_s=60.0,
                              registry=MetricsRegistry())
    rule = AlertRule(name="kv", kind="threshold", metric="x",
                     causes=(("kv_evictions", 1.0),))
    inc = corr.on_alert(rule, 1.0, now=50.0)
    top = inc.top_cause
    assert top["cause"] == "kv_evictions"
    assert top["event"]["synthesized"] is True
    assert top["event"]["delta"] == 12.0


def test_correlator_recency_decay_prefers_newer_event():
    clock = FakeClock(t=100.0)
    store = FleetMetricsStore(clock=clock, registry=MetricsRegistry())
    store.ingest("a", _full_payload({}, events=[
        {"kind": "publish_begin", "t": 10.0, "seq": 1},
        {"kind": "publish_begin", "t": 99.0, "seq": 2}]), t=99.0)
    corr = IncidentCorrelator(store, clock=clock, window_s=120.0,
                              registry=MetricsRegistry())
    rule = AlertRule(name="r", kind="threshold", metric="x",
                     causes=(("publish_begin", 1.0),))
    inc = corr.on_alert(rule, 1.0, now=100.0)
    assert inc.top_cause["event"]["t"] == 99.0


# ---- peer stamping (timeline + SLO exemplars) ----

def test_timeline_recorder_stamps_peer_id():
    from senweaver_ide_tpu.obs.timeline import TimelineRecorder
    rec = TimelineRecorder(clock=FakeClock(), peer_id="serve-7")
    rec.begin(1, "interactive")
    tl = rec.finish_completed(1, tokens=1)
    assert tl.peer_id == "serve-7"


def test_slo_exemplars_carry_peer_id():
    from senweaver_ide_tpu.obs.slo import SLOConfig, SLOTracker
    from senweaver_ide_tpu.obs.timeline import TimelineRecorder
    clock = FakeClock()
    tracker = SLOTracker(SLOConfig(), registry=MetricsRegistry(),
                         peer_id="serve-7")
    rec = TimelineRecorder(clock=clock, slo=tracker, peer_id="serve-7")
    rec.begin(1, "interactive")
    clock.advance(1000.0)              # blow every target → exemplar
    rec.finish_completed(1, tokens=1)  # feeds tracker.observe
    exemplars = tracker.exemplars()
    assert exemplars and exemplars[0]["peer_id"] == "serve-7"


# ---- the whole plane under chaos: a loopback fleet (per-peer registries
# and journals behind real rpc handlers) under NetworkFaultPlan /
# MemoryPressurePlan chaos on a fake clock; each test has a KNOWN injected
# cause that the correlator must rank first ----

def _serving_plane(clock):
    """(store, journal, registry): the global registry and a fake-clock
    global journal, as a serving process has them."""
    journal = EventJournal(clock=clock)
    obs.set_event_journal(journal)
    return FleetMetricsStore(clock=clock), journal, obs.get_registry()


def _alerting(store, peers, clock, journal, *, correlator_journal=None):
    fed = MetricsFederator(store, peers, clock=clock, journal=journal,
                           interval_s=0.0)
    corr = IncidentCorrelator(store, journal=correlator_journal,
                              clock=clock)
    mgr = AlertManager(store, obs.default_alert_rules(), clock=clock,
                       journal=journal, correlator=corr)
    return fed, corr, mgr


def _tiny_engine(**kw):
    import jax

    from senweaver_ide_tpu.models import init_params, tiny_test
    from senweaver_ide_tpu.rollout import RolloutEngine
    from senweaver_ide_tpu.rollout.sampler import SampleParams

    config = tiny_test()
    params = init_params(config, jax.random.PRNGKey(0))
    return RolloutEngine(
        params, config, max_len=64,
        sample=SampleParams(temperature=0.0, top_k=0, top_p=1.0), **kw)


def test_injected_cause_top_ranked_partition():
    """One peer is partitioned mid-scrape: its series go STALE with a
    gap (never interpolated), ``fleet_peer_stale`` fires exactly once
    and clears exactly once after the heal, and the incident's top
    cause is ``peer_unreachable`` on that peer."""
    from senweaver_ide_tpu.resilience import NetworkFaultPlan
    from senweaver_ide_tpu.serve.rpc import LoopbackTransport

    clock = FakeClock()
    store, journal, reg = _serving_plane(clock)
    # the learner peer: its OWN registry + journal behind a real handler
    learner_reg = MetricsRegistry()
    learner_journal = EventJournal(clock=clock, registry=learner_reg)
    idle = learner_reg.gauge("senweaver_learner_idle_fraction", "")
    steps = learner_reg.counter("senweaver_learner_steps_total", "")
    netplan = NetworkFaultPlan()
    peers = {
        "serve-1": LoopbackTransport(
            _rpc_handler(reg, journal, clock, "serve-1"),
            target="serve-1", fault_plan=netplan),
        "learner-1": LoopbackTransport(
            _rpc_handler(learner_reg, learner_journal, clock,
                         "learner-1"),
            target="learner-1", fault_plan=netplan),
    }
    fed, corr, mgr = _alerting(store, peers, clock, journal,
                               correlator_journal=journal)

    def learner_steps():
        return store.series("senweaver_learner_steps_total",
                            peer="learner-1")

    for i in range(4):
        idle.set(0.2 + 0.01 * i)
        steps.inc()
        fed.scrape_once(clock.advance(1.0))
        mgr.evaluate(clock.t)
    assert not mgr.active()
    pre = learner_steps()
    assert len(pre) == 4

    # Its instruments KEEP MOVING — the store must see none of it.
    netplan.partition("learner-1")
    for _ in range(5):
        idle.set(0.4)
        steps.inc()
        fed.scrape_once(clock.advance(1.0))
        mgr.evaluate(clock.t)
    during = learner_steps()
    assert len(during) == len(pre), "points were fabricated"
    assert store.is_stale("learner-1")
    assert during[-1] == pre[-1], "a stale series was rewritten"
    assert mgr.active() == ["fleet_peer_stale"]
    assert mgr.transitions("fleet_peer_stale") == 1

    top = corr.incidents(1)[0].top_cause
    assert top is not None and top["cause"] == "peer_unreachable", top
    assert top["event"].get("peer") == "learner-1", top

    netplan.heal("learner-1")
    for _ in range(8):
        steps.inc()
        fed.scrape_once(clock.advance(1.0))
        mgr.evaluate(clock.t)
    assert len(learner_steps()) > len(pre), "series never resumed"
    assert not store.is_stale("learner-1")
    assert not mgr.active()
    assert mgr.transitions("fleet_peer_stale") == 2, "alert flapped"
    kinds = [e["kind"] for e in journal.recent(64)]
    assert "peer_unreachable" in kinds and "peer_recovered" in kinds


def test_injected_cause_top_ranked_kv_squat():
    """Chaos squats real blocks on the serving peer's pool under an
    over-capacity workload: ``kv_pressure_high`` fires once (hysteresis
    across the release — no flap) and the top cause is in the ``kv_*``
    reaction family, SYNTHESIZED from federated counter movement."""
    from senweaver_ide_tpu.resilience import (MemoryPressureFault,
                                              MemoryPressurePlan)
    from senweaver_ide_tpu.rollout import EngineConfig
    from senweaver_ide_tpu.serve import ServingFleet
    from senweaver_ide_tpu.serve.admission import AdmissionConfig
    from senweaver_ide_tpu.serve.rpc import LoopbackTransport

    clock = FakeClock()
    store, journal, reg = _serving_plane(clock)
    eng = _tiny_engine(num_slots=2, engine_config=EngineConfig(
        kv_layout="paged", block_size=4, num_blocks=10))
    # The squat fires on the FIRST engine step, while only the 1-block
    # warmup prompt occupies the pool, so it grabs 9 of 10 blocks
    # (``on_step`` clamps to free_blocks): the floor is 0.9 > 0.85 for
    # the whole hold and the sustain window is continuous. No
    # release_step: the schedule is indexed on engine steps, which
    # stall when nothing is placeable; the mitigation is release_all().
    plan = MemoryPressurePlan([MemoryPressureFault(at_step=0,
                                                   hold_blocks=9)])
    fleet = ServingFleet([plan.wrap_engine(eng)], clock=clock,
                         peer_id="serve-1",
                         admission=AdmissionConfig(kv_pressure_high=0.97,
                                                   kv_pressure_low=0.9))
    fed, corr, mgr = _alerting(
        store,
        {"serve-1": LoopbackTransport(
            _rpc_handler(reg, journal, clock, "serve-1"),
            target="serve-1")},
        clock, journal)
    fleet.attach_federation(fed, alert_manager=mgr)

    # One tiny request placed BEFORE the squat, so the first step both
    # fires the fault and leaves a live decode fighting the squeezed
    # pool (exhaustion → preemptions → the counter movement the
    # correlator synthesizes causes from).
    warmup = fleet.submit([5, 9, 2], max_new_tokens=6)
    clock.advance(0.5)
    fleet.step()
    hot = [5, 9, 2, 7, 4, 4, 8, 1]
    tickets = [warmup] + [fleet.submit(hot + [i + 1, 3],
                                       max_new_tokens=8)
                          for i in range(4)]
    for _ in range(30):
        clock.advance(0.5)
        fleet.step()           # pumps federation + alerts too

    assert mgr.transitions("kv_pressure_high") >= 1
    peak = max(v for (_t, v) in store.series(
        "senweaver_kv_pressure", peer="serve-1"))
    assert peak >= 0.85

    incidents = [i for i in corr.incidents(8)
                 if i.alert == "kv_pressure_high"]
    assert incidents
    top = incidents[-1].top_cause   # earliest firing = the onset
    assert top is not None and top["cause"] in {
        "kv_evictions", "kv_swaps_out", "kv_exhaustion",
        "kv_preemption_storm", "admission_sheds"}, top
    assert top["event"].get("synthesized")
    assert not str(top["event"].get("metric", "")).startswith(
        "senweaver_chaos_"), "correlator read the chaos plan's counters"

    plan.release_all(eng)
    for _ in range(300):
        if not fleet.pending():
            break
        clock.advance(0.5)
        fleet.step()
    assert not fleet.pending()
    for _ in range(14):
        clock.advance(5.0)
        fleet.step()
    assert "kv_pressure_high" not in mgr.active()
    assert mgr.transitions("kv_pressure_high") == 2, "alert flapped"
    assert all(fleet.outcome(t) is not None for t in tickets)


def test_injected_cause_top_ranked_eager_publish():
    """An eager weight publish lands during interactive traffic; TTFT
    blows the SLO, the multi-window burn alert fires, and the top
    cause names the publish event with its version."""
    import jax

    from senweaver_ide_tpu.models import init_params
    from senweaver_ide_tpu.serve import ServingFleet
    from senweaver_ide_tpu.serve.rpc import LoopbackTransport

    clock = FakeClock()
    store, journal, reg = _serving_plane(clock)
    eng = _tiny_engine(num_slots=4)
    fleet = ServingFleet([eng], clock=clock, peer_id="serve-1")
    fed, corr, mgr = _alerting(
        store,
        {"serve-1": LoopbackTransport(
            _rpc_handler(reg, journal, clock, "serve-1"),
            target="serve-1")},
        clock, journal)
    fleet.attach_federation(fed, alert_manager=mgr)

    tickets = [fleet.submit([5, 9, i + 2], max_new_tokens=6,
                            priority="interactive") for i in range(4)]
    # The EAGER publish lands right as the batch is admitted, and the
    # fake clock charges its stall to TTFT.
    fleet.begin_publish(init_params(eng.config, jax.random.PRNGKey(1)),
                        eager=True)
    clock.advance(1.2)          # > interactive ttft_s target (0.5)
    for _ in range(300):
        if not fleet.pending():
            break
        clock.advance(0.01)
        fleet.step()
    assert not fleet.pending()
    clock.advance(0.5)
    fleet.step()                # one more pump: scrape + evaluate

    assert mgr.transitions("slo_burn_fast") >= 1
    incidents = [i for i in corr.incidents(8)
                 if i.alert == "slo_burn_fast"]
    assert incidents
    top = incidents[0].top_cause
    assert top is not None and top["cause"] in (
        "publish_begin", "publish_end"), top
    assert top["event"].get("version") is not None
    out = fleet.run()
    assert all(t in out for t in tickets)
