"""Memory-pressure resilience for the paged KV pool (ISSUE 13):
prefix-aware eviction ordering, host-RAM tiering with token-exact
restore, torn-swap degradation, proactive admission backpressure ahead
of exhaustion, and the preemption-starvation cap."""

import jax
import numpy as np
import pytest

from senweaver_ide_tpu import obs
from senweaver_ide_tpu.models import init_params, tiny_test
from senweaver_ide_tpu.resilience import (ChaosError, MemoryPressureFault,
                                          MemoryPressurePlan)
from senweaver_ide_tpu.rollout import EngineConfig, RolloutEngine
from senweaver_ide_tpu.rollout.sampler import SampleParams
from senweaver_ide_tpu.serve import ServingFleet
from senweaver_ide_tpu.serve.admission import (AdmissionConfig,
                                               REJECT_KV_PRESSURE, Rejected)

GREEDY = SampleParams(temperature=0.0, top_k=0, top_p=1.0)

HOT = [5, 9, 2, 7, 4, 4, 8, 1]       # 8 tokens = 2 full blocks @ bs 4
COLD = [11, 3, 8, 1, 2, 6, 9, 5]


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


def make(model, num_slots=2, max_len=64, **cfg_kw):
    params, config = model
    cfg = EngineConfig(kv_layout="paged", block_size=4, **cfg_kw)
    return RolloutEngine(params, config, num_slots=num_slots,
                         max_len=max_len, sample=GREEDY,
                         engine_config=cfg)


def registry_value(name):
    m = obs.get_registry().get(name)
    return None if m is None else float(m.value())


# ---- rung 2: prefix-aware eviction ordering ------------------------------

def test_eviction_prefers_cold_unshared_prefix(model):
    """Under exhaustion, the scored evictor must drop the cold
    UNSHARED prefix and keep the hot one whose blocks an in-flight
    request has grafted — never recompute a hot shared prefix while
    cold blocks remain, and never fall through to preemption when one
    eviction suffices."""
    prompt = HOT + [1, 3, 2, 6]

    solo = make(model, num_slots=1)
    ref_rid = solo.submit(prompt, max_new_tokens=8)
    ref = solo.run()[ref_rid]

    eng = make(model, num_slots=1, num_blocks=6, host_tier=False)
    hot_pid = eng.register_prefix(HOT)       # 2 blocks, grafted below
    cold_pid = eng.register_prefix(COLD)     # 2 blocks, zero consumers
    rid = eng.submit(prompt, max_new_tokens=8, prefix_id=hot_pid)
    assert eng.run()[rid] == ref             # greedy invariance

    st = eng.stats()
    assert st["prefix_evictions"] == 1       # exactly one eviction
    assert st["kv_preemptions"] == 0         # ...and no preemption
    assert registry_value("senweaver_kv_evictions_total") == 1
    # the hot prefix stayed resident; the cold one is gone
    assert eng._prefixes[hot_pid][1] is not None
    with pytest.raises(KeyError):
        eng.submit(COLD + [1], max_new_tokens=2, prefix_id=cold_pid)
    eng.release_prefix(hot_pid)
    eng._alloc.check_leaks()


# ---- host tier: swap out -> restore is token-exact -----------------------

def test_swap_restore_decode_token_exact(model):
    prompt = HOT + [1, 3]

    ref_eng = make(model, num_slots=1)
    ref_pid = ref_eng.register_prefix(HOT)
    ref_rid = ref_eng.submit(prompt, max_new_tokens=10,
                             prefix_id=ref_pid)
    ref = ref_eng.run()[ref_rid]

    eng = make(model, num_slots=1)
    pid = eng.register_prefix(HOT)
    first_rid = eng.submit(prompt, max_new_tokens=10, prefix_id=pid)
    assert eng.run()[first_rid] == ref

    eng._swap_out_prefix(pid)
    assert eng.prefix_in_host_tier(pid)
    assert eng.stats()["prefix_swap_outs"] == 1
    assert registry_value("senweaver_kv_swapped_blocks") == 2
    assert registry_value("senweaver_kv_swaps_out_total") == 2

    # exports while tiered are served from host RAM (numpy, no device
    # traffic) and still satisfy the fleet broadcast contract
    toks, kv, _last = eng.export_prefix(pid)
    assert toks == HOT and isinstance(kv.k, np.ndarray)
    assert eng.stats()["prefix_host_exports"] == 1

    # next prefix-bearing request restores on demand, token-exact
    rid = eng.submit(prompt, max_new_tokens=10, prefix_id=pid)
    assert eng.run()[rid] == ref
    assert not eng.prefix_in_host_tier(pid)
    assert eng.stats()["prefix_swap_ins"] == 1
    assert registry_value("senweaver_kv_swaps_in_total") == 2
    assert registry_value("senweaver_kv_swapped_blocks") == 0
    eng.release_prefix(pid)
    eng._alloc.check_leaks()


# ---- torn swap: gather dies mid-flight -> clean fall-through -------------

def test_torn_swap_falls_back_to_eviction_leak_free(model, monkeypatch):
    """A chaos kill inside the swap-out readback must not strand pool
    blocks or host state: the evictor falls through to plain eviction,
    the pressured request still completes, and the pool drains clean."""
    eng = make(model, num_slots=1, num_blocks=6)
    pid = eng.register_prefix(COLD)
    for tail in (1, 2):                      # grafted twice: tier-worthy
        r0 = eng.submit(COLD + [tail], max_new_tokens=2, prefix_id=pid)
        assert len(eng.run()[r0]) == 2

    def boom(pool, ids):
        raise ChaosError("injected gather kill mid-swap")
    monkeypatch.setattr(
        "senweaver_ide_tpu.rollout.engine.gather_blocks_quant", boom)

    # 4+16 tokens = 5 blocks against 4 free: exhaustion tries to tier
    # the prefix, the gather dies, eviction reclaims instead
    rid = eng.submit([7, 7, 3, 2], max_new_tokens=16)
    assert len(eng.run()[rid]) == 16
    st = eng.stats()
    assert st["prefix_swap_outs"] == 0       # torn swap left no host copy
    assert st["prefix_evictions"] == 1
    assert not eng.prefix_in_host_tier(pid)
    assert pid not in eng._prefixes
    eng._alloc.check_leaks()


# ---- rung 4 gate: admission sheds BEFORE exhaustion ----------------------

def test_admission_sheds_on_kv_pressure_before_exhaustion(model):
    """Under a chaos pool squeeze, new sessions shed with a typed
    ``kv_pressure`` rejection while the engine records ZERO
    exhaustions — backpressure fires proactively, and the in-flight
    decode still runs to completion once the squeeze lifts."""
    eng = make(model, num_slots=2, num_blocks=12)
    plan = MemoryPressurePlan([MemoryPressureFault(at_step=1,
                                                   hold_blocks=9)])
    fleet = ServingFleet([plan.wrap_engine(eng)],
                         admission=AdmissionConfig(kv_pressure_high=0.8,
                                                   kv_pressure_low=0.5))
    t1 = fleet.submit([5, 9], max_new_tokens=10)
    for _ in range(3):
        fleet.step()                          # squat fires, gate engages
    assert fleet.admission.kv_gated
    assert registry_value("senweaver_kv_pressure") >= 0.8

    t2 = fleet.submit([7, 3], max_new_tokens=4)
    rej = fleet.outcome(t2)
    assert isinstance(rej, Rejected) and rej.reason == REJECT_KV_PRESSURE
    assert eng.stats()["kv_exhaustions"] == 0  # shed BEFORE exhaustion

    plan.release_all(eng)
    out = fleet.run()
    assert len(out[t1]) == 10                 # in-flight ran to completion
    assert not fleet.admission.kv_gated       # hysteresis released
    assert plan.injected_counts() == {"memory_pressure": 1}
    eng._alloc.check_leaks()


# ---- rung 3 cap: preemption storms latch, nothing is lost ----------------

def test_preemption_storm_cap_bounds_rework(model):
    """With max_preempts=1, no request is preempted twice: the storm
    counter latches and capped requests truncate-finish rather than
    livelock — every ticket gets an outcome."""
    eng = make(model, num_slots=3, num_blocks=6, max_preempts=1)
    rids = [eng.submit([i + 2, 9, 2, 7], max_new_tokens=12)
            for i in range(3)]
    out = eng.run()
    assert all(r in out for r in rids)        # zero lost
    assert all(len(out[r]) <= 12 for r in rids)
    assert any(len(out[r]) == 12 for r in rids)
    st = eng.stats()
    assert 1 <= st["kv_preemptions"] <= 3     # each at most once
    assert st["kv_preemption_storms"] >= 1
    assert registry_value("senweaver_kv_preemption_storms_total") \
        == st["kv_preemption_storms"]
    eng._alloc.check_leaks()


# ---- the whole ladder at 2x over capacity --------------------------------

def test_pressure_ladder_at_2x_over_capacity(model):
    """A prefix-sharing workload whose working set (~6 requests x ~5
    blocks + 2 prefixes) is ~2x a 10-block pool, squeezed by chaos
    mid-run. Every ticket completes with tokens that are an exact
    prefix of the unpressured run (swap/restore and preemption are
    invisible to outputs; a storm-capped request may truncate-finish
    short, a wrong token may never appear), the cold single-use prefix
    is evicted while the hot shared one survives through the host tier,
    and the pool drains leak-free."""
    prompts = [HOT + [i + 1, 3] for i in range(6)]

    ref_eng = make(model, num_blocks=64)
    ref_pid = ref_eng.register_prefix(HOT)
    ref_rids = [ref_eng.submit(p, max_new_tokens=8, prefix_id=ref_pid)
                for p in prompts]
    ref_raw = ref_eng.run()
    reference = [ref_raw[r] for r in ref_rids]

    eng = make(model, num_blocks=10)
    cold_pid = eng.register_prefix(COLD)     # decoy the evictor must take
    plan = MemoryPressurePlan([MemoryPressureFault(at_step=3,
                                                   hold_blocks=4,
                                                   release_step=60)])
    fleet = ServingFleet([plan.wrap_engine(eng)],
                         admission=AdmissionConfig(kv_pressure_high=0.95,
                                                   kv_pressure_low=0.7))
    pid = fleet.register_prefix(HOT)
    tickets = [fleet.submit(p, max_new_tokens=8, prefix_id=pid)
               for p in prompts]
    for _ in range(800):
        if not fleet.pending():
            break
        fleet.step()
    assert not fleet.pending()
    plan.release_all(eng)
    out = fleet.run()
    completed = [out.get(t) for t in tickets]
    assert all(c is not None for c in completed)   # zero lost
    for got, ref in zip(completed, reference):
        assert got == ref[:len(got)]
    st = eng.stats()
    full = sum(got == ref for got, ref in zip(completed, reference))
    assert full >= 4
    assert len(tickets) - full <= st["kv_preemption_storms"]
    assert st["prefix_evictions"] >= 1
    assert st["prefix_swap_outs"] >= 1 and st["prefix_swap_ins"] >= 1
    assert cold_pid not in eng._prefixes
    hot_pid = eng._prefix_by_tokens.get(tuple(HOT))
    assert hot_pid is not None and hot_pid in eng._prefixes
    eng.release_prefix(hot_pid)
    eng._alloc.check_leaks()
