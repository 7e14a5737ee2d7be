"""Live migration of in-flight decodes (ISSUE 17).

The acceptance invariants:

- **token-exactness**: a decode migrated at any step — including
  mid-group, under an active adapter, and with speculation enabled on
  either side — produces bitwise-identical output to the unmigrated
  reference (greedy decoding makes this scheduling-invariant);
- **exactly-once**: across every chaos race (target dies mid-install,
  source dies post-snapshot, partition during ack, weight publish
  between snapshot and restore) each admitted request finishes exactly
  once, and every replica's block allocator is leak-free at teardown;
- the three legacy degrade paths — truncate-finish at the preempt cap,
  eager-publish patience exhaustion, scale-down drain — become
  migrations when the fleet has somewhere to put the work.

Everything is hermetic on CPU: remote replicas speak to in-process
``EngineRpcHandler``s over ``LoopbackTransport``, chaos comes from a
deterministic :class:`NetworkFaultPlan`, and time is a fake clock.
"""

import jax
import numpy as np
import pytest

from senweaver_ide_tpu import obs
from senweaver_ide_tpu.models import init_params, tiny_test
from senweaver_ide_tpu.resilience import (NetworkFault, NetworkFaultPlan,
                                          RetryPolicy)
from senweaver_ide_tpu.rollout import EngineConfig, RolloutEngine
from senweaver_ide_tpu.rollout.adapter_pool import (AdapterPool,
                                                    AdapterPoolConfig)
from senweaver_ide_tpu.rollout.migration import (CHECKPOINT_FORMAT,
                                                 DecodeCheckpoint,
                                                 MigrationError)
from senweaver_ide_tpu.rollout.sampler import SampleParams
from senweaver_ide_tpu.serve import (Completed, DEAD, EngineRpcHandler,
                                     LoopbackTransport, RemoteReplica,
                                     ServingFleet)
from senweaver_ide_tpu.serve.admission import FleetRequest
from senweaver_ide_tpu.serve.replica import EngineReplica
from senweaver_ide_tpu.serve.router import Router
from senweaver_ide_tpu.serve.scheduler import (GlobalScheduler,
                                               MigrationCoordinator)
from senweaver_ide_tpu.training.lora import init_lora

GREEDY = SampleParams(temperature=0.0, top_k=0, top_p=1.0)
FAST = RetryPolicy(max_retries=2, base_delay_s=0.0, jitter=False)
PROMPT = [5, 9, 2, 7, 1, 3]


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


def make_engine(model, num_slots=2, max_len=64, **eng_kw):
    params, config = model
    return RolloutEngine(params, config, num_slots=num_slots,
                         max_len=max_len, sample=GREEDY, **eng_kw)


class FakeClock:
    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


def reference(model, prompt=PROMPT, max_new=12, **eng_kw):
    eng = make_engine(model, **eng_kw)
    rid = eng.submit(list(prompt), max_new_tokens=max_new)
    return eng.run()[rid]


def migrations_value(reason, outcome):
    m = obs.get_registry().get("senweaver_serve_migrations_total")
    return 0.0 if m is None else m.value(reason=reason, outcome=outcome)


# ---- engine level: token-exact checkpoint/restore ------------------------

@pytest.mark.parametrize("steps", [1, 3, 6, 10])
def test_migrated_decode_token_exact_at_any_step(model, steps):
    """Checkpoint after k engine steps, restore on a fresh peer, run
    both-sides-free: output is bitwise-identical to never migrating."""
    ref = reference(model)
    a = make_engine(model)
    b = make_engine(model)
    rid = a.submit(PROMPT, max_new_tokens=12)
    for _ in range(steps):
        a.step()
    ckpt = a.checkpoint_request(rid)
    assert ckpt.format_version == CHECKPOINT_FORMAT
    new_rid = b.restore_request(ckpt)
    assert a.release_request(rid)
    out = b.run()[new_rid]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert a.stats()["migrations_out"] == 1
    assert b.stats()["migrations_in"] == 1
    a._alloc.check_leaks()
    b._alloc.check_leaks()


def test_recompute_path_token_exact_without_kv_payload(model):
    """A checkpoint stripped of its KV payload restores through the
    preemption-resume replay — slower, still bit-exact."""
    ref = reference(model)
    a = make_engine(model)
    rid = a.submit(PROMPT, max_new_tokens=12)
    for _ in range(5):
        a.step()
    ckpt = a.checkpoint_request(rid)
    assert ckpt.kv_k is not None
    stripped = DecodeCheckpoint.from_wire(
        {**ckpt.to_wire(), "kv_k": None, "kv_v": None, "kv_len": 0})
    a.release_request(rid)
    b = make_engine(model)
    new_rid = b.restore_request(stripped)
    out = b.run()[new_rid]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    b._alloc.check_leaks()


def test_block_size_mismatch_falls_back_to_recompute(model):
    """A foreign block size cannot install-scatter; the restore must
    recompute (never a wrong-layout splice) and stay token-exact."""
    ref = reference(model)
    a = make_engine(model, engine_config=EngineConfig(
        kv_layout="paged", block_size=4))
    b = make_engine(model, engine_config=EngineConfig(
        kv_layout="paged", block_size=8))
    rid = a.submit(PROMPT, max_new_tokens=12)
    for _ in range(4):
        a.step()
    ckpt = a.checkpoint_request(rid)
    a.release_request(rid)
    new_rid = b.restore_request(ckpt)
    out = b.run()[new_rid]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    a._alloc.check_leaks()
    b._alloc.check_leaks()


def test_kv_dtype_fence_falls_back_to_recompute(model):
    """A checkpoint snapshotted on the int8 ladder must NEVER splice
    its payload into a different-flavor pool: restoring onto a bf16
    engine takes the recompute path (zero install copies) and still
    completes; restoring onto a matching int8 engine splices the
    quantized payload + scales and is token-exact within the rung."""
    cfg = EngineConfig(kv_layout="paged", block_size=4,
                       kv_dtype="int8")
    ref_eng = make_engine(model, engine_config=cfg)
    ref_rid = ref_eng.submit(PROMPT, max_new_tokens=12)
    ref = ref_eng.run()[ref_rid]

    a = make_engine(model, engine_config=cfg)
    rid = a.submit(PROMPT, max_new_tokens=12)
    for _ in range(4):
        a.step()
    ckpt = a.checkpoint_request(rid)
    assert ckpt.format_version == CHECKPOINT_FORMAT
    assert ckpt.kv_dtype == "int8"
    assert ckpt.kv_k_scale is not None and ckpt.kv_v_scale is not None
    assert ckpt.kv_k.dtype == np.int8
    ckpt = DecodeCheckpoint.from_wire(ckpt.to_wire())  # wire round-trip
    a.release_request(rid)

    # same ladder: quantized fast-path splice, token-exact in-rung
    b = make_engine(model, engine_config=cfg)
    b_rid = b.restore_request(ckpt)
    out = b.run()[b_rid]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert b.stats()["kv_install_copies"] == ckpt.kv_k.shape[1]

    # cross-ladder: the fence drops the payload and re-prefills — the
    # decode completes without ever installing foreign bytes
    c = make_engine(model, engine_config=EngineConfig(
        kv_layout="paged", block_size=4))
    c_rid = c.restore_request(ckpt)
    out_c = c.run()[c_rid]
    assert len(out_c) == 12
    assert out_c[:len(ckpt.tokens)] == list(ckpt.tokens)  # replayed
    assert c.stats()["kv_install_copies"] == 0
    assert c.stats()["migrations_in"] == 1
    for eng in (a, b, c):
        eng._alloc.check_leaks()


def test_v1_checkpoint_wire_still_decodes(model):
    """Format fencing, not format breakage: a pre-ladder (v1) wire
    payload — no kv_dtype, no scale tensors — must still decode with
    full-width semantics and restore through the fast path."""
    a = make_engine(model)
    rid = a.submit(PROMPT, max_new_tokens=12)
    for _ in range(3):
        a.step()
    ckpt = a.checkpoint_request(rid)
    wire = ckpt.to_wire()
    assert wire["format_version"] == 2
    v1 = {k: v for k, v in wire.items()
          if k not in ("kv_dtype", "hi_layers", "kv_k_scale",
                       "kv_v_scale", "kv_k_hi", "kv_v_hi")}
    v1["format_version"] = 1
    old = DecodeCheckpoint.from_wire(v1)
    assert old.kv_dtype == "bf16" and old.hi_layers == 0
    assert old.kv_k_scale is None
    a.release_request(rid)

    ref = reference(model)
    b = make_engine(model)
    b_rid = b.restore_request(old)
    out = b.run()[b_rid]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert b.stats()["kv_install_copies"] > 0   # fast path, not replay
    b._alloc.check_leaks()


def test_paused_request_is_frozen_until_resume(model):
    """Between snapshot and release the source row must not advance:
    freeze, step the engine, thaw — output still token-exact."""
    ref = reference(model)
    a = make_engine(model, num_slots=3)
    rid = a.submit(PROMPT, max_new_tokens=12)
    other = a.submit([4, 4, 8, 1], max_new_tokens=12)
    for _ in range(3):
        a.step()
    a.checkpoint_request(rid)           # pauses
    frozen_at = len(a.result(rid))
    for _ in range(4):                  # others decode; rid must not
        a.step()
    assert len(a.result(rid)) == frozen_at
    a.resume_request(rid)
    out = a.run()
    np.testing.assert_array_equal(np.asarray(out[rid]), np.asarray(ref))
    assert len(out[other]) == 12
    a._alloc.check_leaks()


def test_migrate_under_active_adapter(model):
    """A tenant decode migrates with its (tenant, version) binding and
    stays token-exact; a version drift on the target refuses."""
    params, config = model
    lora = init_lora(config, jax.random.PRNGKey(3), rank=4)
    for k in list(lora["layers"]):
        if k.endswith("_lora_b"):
            lora["layers"][k] = jax.random.normal(
                jax.random.PRNGKey(103), lora["layers"][k].shape,
                lora["layers"][k].dtype) * 0.05

    def adapter_engine():
        pool = AdapterPool(config, AdapterPoolConfig())
        eng = make_engine(model, adapter_pool=pool, engine_config=
                          EngineConfig(kv_layout="paged", block_size=4))
        eng.publish_adapter("t1", lora)
        return eng

    ref_eng = adapter_engine()
    ref_rid = ref_eng.submit(PROMPT, max_new_tokens=10,
                             adapter_id="t1")
    ref = ref_eng.run()[ref_rid]

    a, b = adapter_engine(), adapter_engine()
    rid = a.submit(PROMPT, max_new_tokens=10, adapter_id="t1")
    for _ in range(4):
        a.step()
    ckpt = a.checkpoint_request(rid)
    assert ckpt.adapter_id == "t1" and ckpt.adapter_version == 1
    new_rid = b.restore_request(ckpt)
    out = b.run()[new_rid]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert a.release_request(rid)
    a._alloc.check_leaks()
    b._alloc.check_leaks()

    # Version drift: the target republished t1 → no cross-version splice.
    c = adapter_engine()
    c.publish_adapter("t1", lora)       # now v2
    with pytest.raises(MigrationError):
        c.restore_request(ckpt)
    # The refused restore must not leak the transient acquire.
    occupants = [o for rung in c.adapter_pool.stats()["rungs"]
                 for o in rung["occupants"]]
    assert all(o["refs"] == 0 for o in occupants)


def test_migrate_with_speculation_on_either_side(model):
    """Draft state is dropped at snapshot and resynced by the target's
    catch-up replay — speculation on source, target, or both never
    changes the emitted tokens."""
    params, config = model
    ref = reference(model)
    for spec_source, spec_target in [(True, False), (False, True),
                                     (True, True)]:
        a = make_engine(model)
        b = make_engine(model)
        if spec_source:
            a.enable_speculation(params, config, depth=4)
        if spec_target:
            b.enable_speculation(params, config, depth=4)
        rid = a.submit(PROMPT, max_new_tokens=12)
        for _ in range(3):
            a.step()
        ckpt = a.checkpoint_request(rid)
        a.release_request(rid)
        new_rid = b.restore_request(ckpt)
        out = b.run()[new_rid]
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        a._alloc.check_leaks()
        b._alloc.check_leaks()


def test_checkpoint_and_wire_refusals(model):
    a = make_engine(model)
    with pytest.raises(MigrationError):
        a.checkpoint_request(999)               # unknown rid
    rid = a.submit(PROMPT, max_new_tokens=2)
    a.run()
    with pytest.raises(MigrationError):
        a.checkpoint_request(rid)               # already finished
    held = a.submit(PROMPT, max_new_tokens=2, hold_slot=True)
    with pytest.raises(MigrationError):
        a.checkpoint_request(held)              # held slots are pinned
    a.release_slot(held)

    b = make_engine(model)
    rid2 = b.submit(PROMPT, max_new_tokens=8)
    b.step()
    ckpt = b.checkpoint_request(rid2)
    with pytest.raises(MigrationError):
        DecodeCheckpoint.from_wire(
            {**ckpt.to_wire(), "format_version": 99})
    with pytest.raises(MigrationError):
        DecodeCheckpoint.from_wire(
            {**ckpt.to_wire(), "mystery_field": 1})
    # Sampler mismatch: token-exactness is meaningless across samplers.
    params, config = model
    hot = RolloutEngine(params, config, num_slots=2, max_len=64,
                        sample=SampleParams(temperature=0.8, top_k=0,
                                            top_p=1.0))
    with pytest.raises(MigrationError):
        hot.restore_request(ckpt)
    b.release_request(rid2)
    b._alloc.check_leaks()


def test_release_request_is_idempotent_and_leak_free(model):
    a = make_engine(model)
    rid = a.submit(PROMPT, max_new_tokens=12)
    for _ in range(3):
        a.step()
    a.checkpoint_request(rid)
    assert a.release_request(rid) is True
    assert a.release_request(rid) is False      # idempotent
    assert rid not in a._requests               # fully forgotten
    a._alloc.check_leaks()


# ---- satellite: the on-request-departure load-accounting hook ------------

def test_router_load_never_stale_after_departure(model):
    """Regression (ISSUE 17 satellite): remaining-decode-token load
    must drop the moment a request leaves a replica for ANY reason —
    migration-out included — not only on replica death."""
    rep = EngineReplica("r0", make_engine(model))
    router = Router([rep])
    req = FleetRequest(ticket=1, prompt=list(PROMPT),
                       max_new_tokens=32)
    rid = rep.submit(req)
    rep.step()
    assert rep.outstanding_decode_tokens > 0
    before = (req.emitted, req.first_token_at)
    # Migration-out: tokens survive, progress is kept, load drops NOW.
    router.on_request_departure(req, tokens_survive=True)
    gone = rep.detach(rid)
    assert gone is req
    assert rep.outstanding_decode_tokens == 0
    assert rep.outstanding == 0
    assert (req.emitted, req.first_token_at) == before
    assert req.attempts == 0                    # a migration is not a retry
    assert req.replica_id is None and req.engine_rid is None
    # Death-style departure: partial tokens died, attempt is spent.
    router.on_request_departure(req)
    assert req.emitted == 0 and req.first_token_at is None
    assert req.attempts == 1
    # detach is idempotent
    assert rep.detach(rid) is None


# ---- serve level: the coordinator two-phase handoff ----------------------

def make_local_fleet(model, n=2, *, clock=None, num_slots=4, **fleet_kw):
    clock = clock or FakeClock()
    engines = [make_engine(model, num_slots=num_slots)
               for _ in range(n)]
    fleet = ServingFleet(engines, clock=clock,
                         retry_base_delay_s=0.0, **fleet_kw)
    return fleet, clock


def test_fleet_migration_token_exact_and_acked(model):
    """Manual coordinator handoff mid-decode: the request finishes on
    the target, output token-exact, source copy released on the first
    post-migration token, allocators leak-free."""
    ref = reference(model)
    fleet, clock = make_local_fleet(model)
    mig = fleet.attach_migration()
    t = fleet.submit(PROMPT, max_new_tokens=12)
    for _ in range(4):
        fleet.step()
    req = fleet._requests[t]
    source = fleet._replica_by_id(req.replica_id)
    target = next(r for r in fleet.replicas if r is not source)
    assert mig.migrate(req, source, target, reason="test",
                       now=clock()) is True
    assert req.replica_id == target.replica_id
    assert source.outstanding == 0
    assert len(mig.pending) == 1
    fleet.run()
    out = fleet.outcome(t)
    assert isinstance(out, Completed)
    np.testing.assert_array_equal(np.asarray(out.tokens),
                                  np.asarray(ref))
    assert out.weight_version == out.weight_version_at_finish == 0
    assert len(mig.pending) == 0                # acked
    assert migrations_value("test", "completed") == 1
    for r in fleet.replicas:
        r.engine._alloc.check_leaks()


def test_fence_abort_on_publish_between_snapshot_and_restore(model):
    """Race 4: a weight publish lands between snapshot and install.
    The (epoch, version) fence must refuse the cross-version splice;
    the decode finishes locally on the source, still token-exact."""
    ref = reference(model)
    fleet, clock = make_local_fleet(model)
    mig = fleet.attach_migration()
    t = fleet.submit(PROMPT, max_new_tokens=12)
    for _ in range(3):
        fleet.step()
    req = fleet._requests[t]
    source = fleet._replica_by_id(req.replica_id)
    target = next(r for r in fleet.replicas if r is not source)
    # The publish "lands on the target" mid-handoff: its resident
    # version no longer matches the snapshot's fence.
    target.stamp_version(7)
    assert mig.migrate(req, source, target, reason="test",
                       now=clock()) is False
    assert migrations_value("test", "fence_abort") == 1
    assert req.replica_id == source.replica_id  # never left
    fleet.run()
    out = fleet.outcome(t)
    assert isinstance(out, Completed)
    np.testing.assert_array_equal(np.asarray(out.tokens),
                                  np.asarray(ref))
    for r in fleet.replicas:
        r.engine._alloc.check_leaks()


def test_global_scheduler_placement_signals(model):
    """pick_target must honor liveness, version fences, KV headroom,
    adapter residency, and federation staleness vetoes."""
    reps = [EngineReplica(f"r{i}", make_engine(model, num_slots=4))
            for i in range(3)]
    sched = GlobalScheduler(reps)
    assert sched.pick_target(reps[0]) in (reps[1], reps[2])
    # Version fence: only same-version peers qualify.
    reps[1].stamp_version(3)
    assert sched.pick_target(reps[0], require_version=0) is reps[2]
    assert sched.pick_target(reps[0], require_version=3) is reps[1]
    # Death disqualifies.
    reps[2].kill()
    assert sched.pick_target(reps[0], require_version=0) is None

    class StaleStore:
        def is_stale(self, peer):
            return peer == "r1"

    sched2 = GlobalScheduler(reps, fleet_store=StaleStore())
    assert sched2.pick_target(reps[0], require_version=3) is None


# ---- the three degrade call sites become migrations ----------------------

def test_kv_pressure_migrates_instead_of_truncating(model):
    """Call site 1: a request at the preempt cap on a starved pool is
    offered for migration and finishes FULL LENGTH on a roomy peer —
    the truncate-finish path never fires when the fleet has headroom."""
    params, config = model
    starved = RolloutEngine(
        params, config, num_slots=3, max_len=64, sample=GREEDY,
        engine_config=EngineConfig(kv_layout="paged", block_size=4,
                                   num_blocks=6, max_preempts=1))
    roomy = make_engine(model, num_slots=8)
    fleet = ServingFleet([starved, roomy], clock=FakeClock(),
                         retry_base_delay_s=0.0)
    fleet.attach_migration()
    assert starved.migrate_on_pressure is True
    tickets = [fleet.submit([i + 2, 9, 2, 7], max_new_tokens=12)
               for i in range(6)]
    fleet.run()
    for t in tickets:
        out = fleet.outcome(t)
        assert isinstance(out, Completed), out
        assert len(out.tokens) == 12            # nobody truncated
    assert migrations_value("kv_pressure", "completed") >= 1
    assert starved.stats()["migrations_out"] >= 1
    for r in fleet.replicas:
        r.engine._alloc.check_leaks()


def test_scale_down_evacuates_instead_of_draining(model):
    """Call site 3: retiring a replica migrates its in-flight decodes
    to survivors — the retirement completes without waiting out the
    decodes, and every request still finishes exactly once."""
    ref = reference(model)
    fleet, clock = make_local_fleet(model, n=2)
    mig = fleet.attach_migration()
    fleet.attach_autoscaler(lambda: make_engine(model))
    assert fleet.autoscaler.migrator is mig
    t = fleet.submit(PROMPT, max_new_tokens=12)
    for _ in range(3):
        fleet.step()
    req = fleet._requests[t]
    victim = fleet._replica_by_id(req.replica_id)
    # Simulate the controller's retirement decision on the busy victim.
    victim.drain()
    fleet.autoscaler._retiring = victim.replica_id
    while fleet.pending():
        clock.advance(0.3)
        fleet.step()
    out = fleet.outcome(t)
    assert isinstance(out, Completed)
    np.testing.assert_array_equal(np.asarray(out.tokens),
                                  np.asarray(ref))
    assert req.replica_id != victim.replica_id  # it moved
    assert migrations_value("scale_down", "completed") == 1
    # The retirement itself completed through the death path.
    clock.advance(0.3)
    fleet.step()
    assert victim.state == DEAD
    for r in fleet.replicas:
        r.engine._alloc.check_leaks()


def test_eager_publish_relief_consolidates_blockers(model):
    """Call site 2: an eager (no-drain) publish blocked on TWO busy
    replicas consolidates — the short decode migrates onto the
    long-decode replica, the vacated replica swaps immediately, and
    the roll stops burning patience without degrading to a drain."""
    params, config = model
    fleet, clock = make_local_fleet(model, n=2)
    fleet.attach_migration()
    t_long = fleet.submit(PROMPT, max_new_tokens=24)
    t_short = fleet.submit([4, 4, 8, 1], max_new_tokens=8)
    fleet.step()
    req_l, req_s = fleet._requests[t_long], fleet._requests[t_short]
    assert req_l.replica_id != req_s.replica_id     # two blockers
    fleet.begin_publish(params, eager=True)
    assert len(fleet.publisher.eager_pending()) == 2
    for _ in range(60):
        fleet.step()
        if not fleet.publisher.in_progress:
            break
    assert not fleet.publisher.in_progress      # roll converged
    # The publisher never degraded to a classic drain...
    assert obs.get_registry().get(
        "senweaver_serve_eager_degrades_total").value() == 0
    # ...because the short blocker moved onto the long one's replica.
    assert migrations_value("eager_publish", "completed") >= 1
    assert req_s.replica_id == req_l.replica_id
    fleet.run()
    out_l, out_s = fleet.outcome(t_long), fleet.outcome(t_short)
    assert isinstance(out_l, Completed) and isinstance(out_s, Completed)
    assert len(out_l.tokens) == 24 and len(out_s.tokens) == 8
    # No mixed versions anywhere: both finished on their dispatch
    # version (the old weights), exactly the fence's promise.
    for o in (out_l, out_s):
        assert o.weight_version == o.weight_version_at_finish
    for r in fleet.replicas:
        r.engine._alloc.check_leaks()


def test_eager_degrade_emits_incident_and_counter(model):
    """Satellite: patience exhaustion is no longer silent — the
    degrade increments its counter and lands in the incident journal."""
    params, config = model
    fleet, clock = make_local_fleet(model, n=1, num_slots=2)
    t = fleet.submit(PROMPT, max_new_tokens=48)
    fleet.step()
    fleet.begin_publish(params, eager=True)
    fleet.publisher._eager_wait_limit = 3       # exhaust fast
    for _ in range(10):
        fleet.step()
    assert obs.get_registry().get(
        "senweaver_serve_eager_degrades_total").value() == 1
    from senweaver_ide_tpu.obs.incidents import get_event_journal
    kinds = [e["kind"] for e in get_event_journal().recent(64)]
    assert "eager_degrade" in kinds
    fleet.run()
    assert isinstance(fleet.outcome(t), Completed)


# ---- chaos races over the wire -------------------------------------------

def make_remote_fleet(model, n, *, clock, plan=None, num_slots=4):
    handlers, transports, replicas = [], [], []
    for i in range(n):
        h = EngineRpcHandler(make_engine(model, num_slots=num_slots))
        tr = LoopbackTransport(h, target=f"replica-{i}",
                               fault_plan=plan, wire_codec=True)
        r = RemoteReplica(f"replica-{i}", tr, policy=FAST,
                          clock=clock, sleep=lambda s: None)
        handlers.append(h)
        transports.append(tr)
        replicas.append(r)
    # probe_interval_s > 0: a PARTITIONED replica answers has_work()
    # False (the client swallows transport errors there), so only the
    # hedged probes can escalate it to DEAD.
    fleet = ServingFleet(replicas, clock=clock, retry_base_delay_s=0.0,
                         probe_interval_s=0.5)
    return fleet, handlers, transports


def run_fleet(fleet, clock, max_steps=400):
    """fleet.run() with the fake clock advancing — probe intervals and
    retry backoff floors never elapse on a frozen clock."""
    for _ in range(max_steps):
        if not fleet.pending():
            return
        clock.advance(1.0)
        fleet.step()
    raise AssertionError(f"fleet did not converge in {max_steps} steps "
                         f"({fleet.pending()} still pending)")


def remote_migrate_setup(model, clock, plan=None):
    """Fleet of two remote replicas with one mid-decode request on
    replica-0; returns (fleet, handlers, mig, req, source, target)."""
    fleet, handlers, _ = make_remote_fleet(model, 2, clock=clock,
                                           plan=plan)
    mig = fleet.attach_migration()
    t = fleet.submit(PROMPT, max_new_tokens=12)
    for _ in range(4):
        fleet.step()
    req = fleet._requests[t]
    source = fleet._replica_by_id(req.replica_id)
    target = next(r for r in fleet.replicas if r is not source)
    return fleet, handlers, mig, t, req, source, target


def test_race_target_dies_mid_install(model):
    """Race 1: every install attempt is dropped on the wire. The
    handoff aborts, the source copy resumes, the request completes
    exactly once on the source — token-exact."""
    ref = reference(model)
    clock = FakeClock()
    plan = NetworkFaultPlan([
        NetworkFault(kind="drop", method="restore_checkpoint",
                     times=99)])
    fleet, handlers, mig, t, req, source, target = \
        remote_migrate_setup(model, clock, plan)
    assert mig.migrate(req, source, target, reason="test",
                       now=clock()) is False
    assert migrations_value("test", "install_abort") == 1
    assert req.replica_id == source.replica_id
    run_fleet(fleet, clock)
    out = fleet.outcome(t)
    assert isinstance(out, Completed)
    np.testing.assert_array_equal(np.asarray(out.tokens),
                                  np.asarray(ref))
    # Exactly-once on the wire: no handler double-executed an install.
    assert sum(h.executed.get("restore_checkpoint", 0)
               for h in handlers) == 0
    for h in handlers:
        h.engine._alloc.check_leaks()


def test_race_source_dies_after_handoff(model):
    """Race 2: the source dies post-snapshot (pre-ack). The request
    already lives on the target; the ack simply skips the release and
    the request completes exactly once."""
    ref = reference(model)
    clock = FakeClock()
    fleet, handlers, mig, t, req, source, target = \
        remote_migrate_setup(model, clock)
    assert mig.migrate(req, source, target, reason="test",
                       now=clock()) is True
    src_handler = handlers[int(source.replica_id.split("-")[1])]
    fleet.kill_replica(source.replica_id)
    assert source.state == DEAD
    run_fleet(fleet, clock)
    out = fleet.outcome(t)
    assert isinstance(out, Completed)
    np.testing.assert_array_equal(np.asarray(out.tokens),
                                  np.asarray(ref))
    assert len(mig.pending) == 0
    assert migrations_value("test", "completed") == 1
    # The dead source's engine still holds the frozen copy — its host
    # janitor (here: the test) releases it; leak-free after.
    frozen = [rid for rid in list(src_handler.engine._requests)
              if not src_handler.engine._requests[rid].done]
    for rid in frozen:
        src_handler.engine.release_request(rid)
    for h in handlers:
        h.engine._alloc.check_leaks()


def test_race_partition_during_ack(model):
    """Race 3: the target partitions AFTER the install but BEFORE its
    first post-migration token reaches the fleet. Death triage rescues
    the frozen source copy; the request completes exactly once, on the
    source, token-exact."""
    ref = reference(model)
    clock = FakeClock()
    plan = NetworkFaultPlan()
    fleet, handlers, mig, t, req, source, target = \
        remote_migrate_setup(model, clock, plan)
    assert mig.migrate(req, source, target, reason="test",
                       now=clock()) is True
    tgt_handler = handlers[int(target.replica_id.split("-")[1])]
    plan.partition(target.replica_id)   # silent before any ack token
    run_fleet(fleet, clock)
    out = fleet.outcome(t)
    assert isinstance(out, Completed)
    np.testing.assert_array_equal(np.asarray(out.tokens),
                                  np.asarray(ref))
    assert out.replica_id == source.replica_id
    assert target.state == DEAD
    assert migrations_value("test", "rescued") == 1
    assert len(mig.pending) == 0
    # Heal: the zombie target still holds the installed copy. Its own
    # fleet-side janitor would release it; simulate and audit blocks.
    plan.heal()
    for rid in [r for r in list(tgt_handler.engine._requests)
                if not tgt_handler.engine._requests[r].done]:
        tgt_handler.engine.release_request(rid)
    for h in handlers:
        h.engine._alloc.check_leaks()


def test_remote_checkpoint_retry_replays_snapshot(model):
    """A lost checkpoint_request response replays the SAME snapshot
    from the idempotency cache — the retried call must not cut a
    second, later checkpoint."""
    clock = FakeClock()
    plan = NetworkFaultPlan([
        NetworkFault(kind="drop_response", method="checkpoint_request",
                     call_idx=0)])
    fleet, handlers, mig, t, req, source, target = \
        remote_migrate_setup(model, clock, plan)
    ckpt = source.engine.checkpoint_request(req.engine_rid)
    src_handler = handlers[int(source.replica_id.split("-")[1])]
    assert src_handler.executed.get("checkpoint_request", 0) == 1
    assert src_handler.replays >= 1
    assert isinstance(ckpt, DecodeCheckpoint)
    source.engine.resume_request(req.engine_rid)
    run_fleet(fleet, clock)
    assert isinstance(fleet.outcome(t), Completed)


def test_chaos_evacuation_exactly_once(model):
    """Three remote replicas under mixed decode load: every in-flight
    decode is evacuated off replica-0 while the first install on the
    wire is dropped (the idempotency-keyed retry lands it, or the
    source finishes the decode), then one migration target is
    partitioned before its first post-handoff token can ack. Every
    admitted ticket completes EXACTLY once at its full length, no
    handoff stays unacked, and every allocator balances."""
    clock = FakeClock()
    plan = NetworkFaultPlan([
        NetworkFault(kind="drop", method="restore_checkpoint",
                     call_idx=0)])
    fleet, handlers, _ = make_remote_fleet(model, 3, clock=clock,
                                           plan=plan)
    mig = fleet.attach_migration()
    tickets = [fleet.submit([3 + i, 9, 2, 7, 1], max_new_tokens=8)
               for i in range(8)]
    for _ in range(2):
        clock.advance(1.0)
        fleet.step()
    source = fleet._replica_by_id("replica-0")
    moved = mig.evacuate(source, reason="test", now=clock())
    assert moved == len(mig.pending) == 3
    # death triage must rescue its decodes back onto their frozen sources
    plan.partition(next(iter(mig.pending.values())).target.replica_id)
    run_fleet(fleet, clock, max_steps=300)

    outcomes = [fleet.outcome(t) for t in tickets]
    assert all(isinstance(o, Completed) for o in outcomes)   # none lost
    assert all(len(o.tokens) == 8 for o in outcomes)    # none truncated
    assert len(fleet._outcomes) == len(fleet._requests) == len(tickets)
    assert all(o.weight_version == o.weight_version_at_finish
               for o in outcomes)
    assert not mig.pending                              # every handoff acked
    assert (migrations_value("test", "completed")
            + migrations_value("test", "rescued")) == moved
    assert migrations_value("test", "rescued") >= 1
    assert plan.injected_counts() == {"drop": 1, "partition": 1}

    # Heal, release what is stranded on the zombie (its janitor's job
    # in production), then balance every allocator.
    plan.heal()
    for h in handlers:
        for rid, r in list(h.engine._requests.items()):
            if not r.done:
                h.engine.release_request(rid)
        h.engine._alloc.check_leaks()


# ---- forked-row checkpoints (group-shared rollout, ISSUE 18) -------------

def test_forked_row_checkpoint_is_unshared_deep_copy(model):
    """Migrating one leaf of a KV-shared GRPO group: the checkpoint's
    payload must be an UNSHARED copy of the spine (gather materializes
    it), so the migrated leaf is token-exact on the target, the
    sibling keeps decoding untouched on the source, and the source
    release only drops refcounts on the shared blocks."""
    ref = reference(model)
    a = make_engine(model, num_slots=4,
                    engine_config=EngineConfig(kv_layout="paged",
                                               block_size=4))
    b = make_engine(model, engine_config=EngineConfig(kv_layout="paged",
                                                      block_size=4))
    donor, leaf = a.submit_group(PROMPT, 2, max_new_tokens=12)
    for _ in range(4):
        a.step()
    assert a.stats()["group_prefills"] == 1     # spine really shared
    ckpt = a.checkpoint_request(leaf)
    new_rid = b.restore_request(ckpt)
    assert a.release_request(leaf)              # refcount drop only
    # the sibling's decode on the source must be untouched by the
    # departure, and the migrated leaf exact on the target
    out_a = a.run()
    out_b = b.run()
    np.testing.assert_array_equal(np.asarray(out_a[donor]),
                                  np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(out_b[new_rid]),
                                  np.asarray(ref))
    a._alloc.check_leaks()
    b._alloc.check_leaks()


def test_forked_branch_child_checkpoint_midstream(model):
    """A tree-branch child (fork_request) checkpoints mid-decode like
    any row: restored output equals the unmigrated reference of its
    full stream, and the parent keeps its shared blocks."""
    a = make_engine(model, num_slots=4,
                    engine_config=EngineConfig(kv_layout="paged",
                                               block_size=4))
    root = a.submit(PROMPT, max_new_tokens=12)
    while len(a.result(root)) < 4:
        a.step()
    child = a.fork_request(root, token=7)
    for _ in range(3):
        a.step()
    ckpt = a.checkpoint_request(child)
    stream = list(a._requests[child].prompt)
    b = make_engine(model, engine_config=EngineConfig(kv_layout="paged",
                                                      block_size=4))
    new_rid = b.restore_request(ckpt)
    a.release_request(child)
    out_a = a.run()
    out_b = b.run()
    cref = reference(model, prompt=stream, max_new=len(out_b[new_rid]))
    np.testing.assert_array_equal(np.asarray(out_b[new_rid]),
                                  np.asarray(cref))
    np.testing.assert_array_equal(np.asarray(out_a[root]),
                                  np.asarray(reference(model)))
    a._alloc.check_leaks()
    b._alloc.check_leaks()
