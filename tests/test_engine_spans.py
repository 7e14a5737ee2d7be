"""Spans inside ``RolloutEngine.step()`` (paged path) and over a request's
life: names, nesting, the counts their attrs carry, the profiler-session
path into the trace's host plane, and what the off path costs."""

import glob
import os

import jax
import numpy as np
import pytest

from senweaver_ide_tpu import obs
from senweaver_ide_tpu.models import init_params, tiny_test
from senweaver_ide_tpu.models.config import (tiny_glm_moe_test,
                                             tiny_moe_test)
from senweaver_ide_tpu.rollout import EngineConfig, RolloutEngine
from senweaver_ide_tpu.rollout import engine as engine_mod
from senweaver_ide_tpu.rollout.sampler import SampleParams

SAMPLED = SampleParams(temperature=1.0, top_k=0, top_p=0.9)
# a step that launches and collects its own tokens (the serial order),
# and the pieces of one that runs ahead (tests/test_engine_run_ahead.py)
STEP_CHILDREN = ("engine.plan", "engine.launch", "engine.advance",
                 "engine.schedule", "engine.fetch", "engine.emit")
PLAN_CHILDREN = ("engine.schedule", "engine.assemble_plan", "engine.tables")
# the wrapped jit call alone: nothing under launch waits (the step's one
# wait is engine.fetch's own time, attr wait_ms)
LAUNCH_CHILDREN = ("engine.fused_step.dispatch",)
PROMPTS = ([5, 9, 2, 7, 1, 3, 8, 4, 6, 2, 9, 1, 7, 3, 5, 8, 2, 4, 6, 1,
            3, 5], [11, 3, 8, 1, 4], [2, 6, 4, 9, 9, 1, 2])


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs._reset_for_tests()
    yield
    obs._reset_for_tests()


@pytest.fixture(scope="module")
def model():
    config = tiny_test()
    return init_params(config, jax.random.PRNGKey(0)), config


def make_engine(model, num_slots=4, seed=3, **cfg):
    params, config = model
    cfg.setdefault("block_size", 4)
    cfg.setdefault("step_tokens", 16)
    return RolloutEngine(params, config, num_slots=num_slots, max_len=64,
                         sample=SAMPLED, seed=seed,
                         engine_config=EngineConfig(kv_layout="paged",
                                                    **cfg))


def drive(eng, group=True):
    """Three plain requests (one longer than a step's budget) and a group
    of three, driven until the engine is idle."""
    rids = [eng.submit(p, max_new_tokens=6) for p in PROMPTS]
    if group:
        rids += eng.submit_group([3, 4, 5, 6, 7, 8], 3, max_new_tokens=5)
    while eng.has_work:
        eng.step()
    return rids


def by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s.name, []).append(s)
    return out


def test_every_step_has_the_named_phases_nested_in_it(model):
    eng = make_engine(model)
    obs.enable()
    drive(eng)
    spans = obs.get_tracer().spans()
    byid = {s.span_id: s for s in spans}
    names = by_name(spans)
    steps = names["engine.step"]
    assert len(steps) == eng.stats()["decode_steps"]
    assert "engine.decode_step" not in names
    sub = lambda p: [s.name for s in spans if s.parent_id == p.span_id]
    serial = 0
    for step in steps:
        kids = [s for s in spans if s.parent_id == step.span_id]
        got = [k.name for k in kids]
        # plan, launch, advance; then the one schedule that decides whether
        # the step stays in flight, and a fetch + emit for each step that
        # comes home under this span: its own (serial), the one before it
        # (ahead, collected before the schedule), both, or none yet
        assert got[:3] == list(STEP_CHILDREN[:3])
        assert got.count("engine.schedule") == 1
        assert (got.count("engine.fetch") == got.count("engine.emit")
                == step.attrs["ahead"] + (got[-1] == "engine.emit"))
        for k, nxt in zip(kids, kids[1:]):
            assert (k.name == "engine.fetch") == (nxt.name == "engine.emit")
        if not step.attrs["ahead"] and got[-1] == "engine.emit":
            assert got == list(STEP_CHILDREN)
            serial += 1
        plan, launch, advance = kids[:3]
        assert [n for n in sub(plan) if n in PLAN_CHILDREN] == \
            list(PLAN_CHILDREN)
        assert sub(launch) == list(LAUNCH_CHILDREN)
        assert sub(advance) == []
        # two host arrays through the dispatch (the packed plan, the
        # table); the wait is inside the fetch
        assert launch.attrs["host_arrays"] == 2
        for k in kids[3:]:
            if k.name == "engine.fetch":
                assert sub(k) == []
                assert 0.0 <= k.attrs["wait_ms"] <= k.duration_ms
            elif k.name == "engine.emit":
                assert sub(k) == []         # scheduling is the step's
    assert serial and any(s.attrs["ahead"] for s in steps)
    assert "engine.fused_step.wait" not in names
    # every child lies inside its parent, on the perf_counter_ns clock
    for s in spans:
        if s.name.startswith("engine."):
            assert s.end_ns >= s.start_ns > 0
            assert s.duration_ms == pytest.approx(
                (s.end_ns - s.start_ns) / 1e6)
            if s.parent_id is not None:
                p = byid[s.parent_id]
                assert p.start_ns <= s.start_ns and s.end_ns <= p.end_ns
                assert p.trace_id == s.trace_id


def test_step_attrs_add_up_to_the_engines_own_counts(model):
    eng = make_engine(model)
    obs.enable()
    drive(eng)
    st = eng.stats()
    steps = by_name(obs.get_tracer().spans())["engine.step"]
    a = [s.attrs for s in steps]
    assert [x["step"] for x in a] == list(range(len(a)))
    assert sum(x["prefill_tokens"] for x in a) == st["prefill_tokens"]
    # every request's first token came from a prefill; the rest from
    # decode rows
    n_requests = len(PROMPTS) + 3
    assert sum(x["decode_rows"] for x in a) == \
        st["tokens_emitted"] - n_requests
    for x in a:
        assert x["entries"] in (eng.num_slots, 16)     # the compiled widths
        assert x["used"] == x["decode_rows"] + x["prefill_tokens"]
        assert 0 < x["used"] <= x["entries"]
        assert 0 < x["rows_active"] <= eng.num_slots
        assert x["table_width"] in (1, 2, 4, 8, 16)
    assert any(x["entries"] == 16 for x in a)
    assert any(x["entries"] == eng.num_slots for x in a)
    spans = by_name(obs.get_tracer().spans())
    # a request is placed by a step's leading or its trailing schedule
    assert sum(s.attrs.get("admitted", 0) for s in spans["engine.plan"]
               + spans["engine.schedule"]) == n_requests
    assert spans["engine.plan"][0].attrs["admitted"] == len(PROMPTS) + 1
    assert sum(s.attrs["emitted"] for s in spans["engine.emit"]) == \
        st["tokens_emitted"]
    assert sum(s.attrs["finished"] for s in spans["engine.emit"]) == \
        n_requests
    assert all(s.attrs["bytes"] > 0 for s in spans["engine.fetch"])


def test_kv_blocks_counts_each_runs_blocks_and_feeds_the_counter(model):
    """``kv_blocks``: for each run of one row's entries in a step, the
    blocks up to its last position; the always-on counter sums it."""
    eng = make_engine(model)
    obs.enable()
    rid = eng.submit(list(range(1, 23)), max_new_tokens=4)   # 22 tokens
    while eng.has_work:
        eng.step()
    a = [s.attrs for s in by_name(obs.get_tracer().spans())["engine.step"]]
    # block_size 4, step_tokens 16: a chunk of 16 (4 blocks), then 6 (up
    # to position 21: 6 blocks), then decode rows at positions 22, 23, 24
    # (6, 6, 7 blocks); each step has tail padding, one block more
    assert [x["kv_blocks"] for x in a] == [4, 7, 7, 7, 8]
    assert [x["entries"] for x in a] == [16, 16, 4, 4, 4]
    for x in a:
        assert x["kv_blocks"] <= x["entries"] * x["table_width"]
    assert (f"senweaver_engine_kv_blocks_read_total "
            f"{sum(x['kv_blocks'] for x in a)}"
            in obs.get_registry().render())
    assert eng.is_done(rid)


@pytest.mark.parametrize("block_size,resolved", [(4, 4), (8, 8),
                                                 (None, 16)])
def test_step_says_the_blocks_size_and_a_copys_bytes(model, block_size,
                                                     resolved):
    """``block_size`` and ``kv_copy_bytes`` ride every ``engine.step``
    beside ``kv_blocks``: the resolved block's tokens and the bytes of
    one payload leaf's block (what one DMA of the attention kernels
    carries), so a trace has copies a step and bytes a copy; the gauge
    ``senweaver_kv_block_size`` says the first with tracing off."""
    eng = make_engine(model, block_size=block_size)
    assert f"senweaver_kv_block_size {resolved}" in \
        obs.get_registry().render()
    obs.enable()
    eng.submit(list(range(1, 23)), max_new_tokens=4)         # 22 tokens
    while eng.has_work:
        eng.step()
    a = [s.attrs for s in by_name(obs.get_tracer().spans())["engine.step"]]
    config = model[1]
    row_bytes = (config.num_kv_heads * config.head_dim
                 * np.dtype(config.dtype).itemsize)
    assert {x["block_size"] for x in a} == {resolved}
    assert {x["kv_copy_bytes"] for x in a} == {resolved * row_bytes}
    # kv_blocks counts blocks of that size: a full chunk of 16 tokens,
    # then one of 6 and decode rows at 22, 23, 24, each of those steps
    # with tail padding, one block more
    assert [x["kv_blocks"] for x in a] == [15 // resolved + 1] + [
        (last // resolved + 1) + 1 for last in (21, 22, 23, 24)]


def test_a_requests_three_phases_share_an_id_and_abut(model):
    eng = make_engine(model)
    obs.enable()
    rids = drive(eng)
    spans = obs.get_tracer().spans()
    for rid in rids:
        mine = {s.name: s for s in spans if s.trace_id == f"req-{rid}"}
        assert set(mine) == {"request.queue", "request.prefill",
                             "request.decode"}
        q, p, d = (mine[n] for n in ("request.queue", "request.prefill",
                                     "request.decode"))
        assert q.end_ns == p.start_ns and p.end_ns == d.start_ns
        assert q.start_ns <= q.end_ns <= p.end_ns <= d.end_ns
        req = eng._requests[rid]
        assert (q.start_ns, q.end_ns, p.end_ns, d.end_ns) == (
            req.t_submit_ns, req.t_scheduled_ns, req.t_first_token_ns,
            req.t_done_ns)
        assert d.attrs["rid"] == rid and d.attrs["row"] == req.row
        assert d.attrs["output_tokens"] == len(req.tokens)
        assert d.attrs["prompt_tokens"] == len(req.prompt)
        assert d.attrs["preempts"] == 0 and not d.attrs["prefix_hit"]
    # the group's followers took the donor's spine; nobody else did
    grafted = {rid: next(s for s in spans if s.trace_id == f"req-{rid}"
                         and s.name == "request.prefill").attrs["grafted"]
               for rid in rids}
    assert [grafted[r] for r in rids] == [False] * 4 + [True, True]


def test_request_stamps_are_set_with_tracing_off(model):
    eng = make_engine(model)
    rids = drive(eng)
    assert obs.get_tracer().spans() == []
    for rid in rids:
        r = eng._requests[rid]
        assert r.t_submit_ns <= r.t_scheduled_ns <= r.t_first_token_ns \
            <= r.t_done_ns
        assert r.row is not None and r.slot is None


def test_a_preempted_request_keeps_its_first_scheduled_stamp(model):
    # 6 blocks cannot hold two 16-token rollouts: one is preempted
    eng = make_engine(model, num_slots=2, num_blocks=6)
    obs.enable()
    rids = [eng.submit(p, max_new_tokens=12)
            for p in ([5, 9, 2, 7], [11, 3, 8, 1])]
    first = {}
    while eng.has_work:
        eng.step()
        for rid in rids:
            t = eng._requests[rid].t_scheduled_ns
            if t is not None:
                assert first.setdefault(rid, t) == t
    assert eng.stats()["kv_preemptions"] >= 1
    hit = [r for r in rids if eng._requests[r].preempt_count]
    assert hit
    spans = obs.get_tracer().spans()
    for rid in hit:
        mine = [s for s in spans if s.trace_id == f"req-{rid}"]
        assert sorted(s.name for s in mine) == [
            "request.decode", "request.prefill", "request.queue"]
        dec = next(s for s in mine if s.name == "request.decode")
        assert dec.attrs["preempts"] == eng._requests[rid].preempt_count


def test_profiler_session_alone_turns_spans_on_and_lands_them_in_the_trace(
        model, tmp_path):
    """The profiler-session path, without a chip: the tracer stays
    disabled, a ``jax.profiler`` session runs, and the engine's spans are
    both in memory and events of the trace's host plane (on that plane's
    clock; the device's lines have their own,
    ``benchmark/readers/idle_ledger.py``)."""
    from jax.profiler import ProfileData
    eng = make_engine(model)
    eng.submit(PROMPTS[1], max_new_tokens=2)
    while eng.has_work:                 # compile outside the session
        eng.step()
    assert obs.get_tracer().spans() == [] and not obs.is_enabled()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.submit(PROMPTS[1], max_new_tokens=3)
        while eng.has_work:
            eng.step()
    finally:
        jax.profiler.stop_trace()
    names = by_name(obs.get_tracer().spans())
    assert len(names["engine.step"]) >= 3
    assert {"engine.plan", "engine.fused_step.dispatch",
            "request.decode"} <= set(names)
    eng.submit(PROMPTS[1], max_new_tokens=2)      # session over: off again
    n = len(obs.get_tracer().spans())
    while eng.has_work:
        eng.step()
    assert len(obs.get_tracer().spans()) == n
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("engine."):
                        found.setdefault(e.name.split("#")[0], []).append(e)
    assert len(found["engine.step"]) == len(names["engine.step"])
    assert len(found["engine.plan"]) == len(names["engine.plan"])
    step, plan = found["engine.step"][0], found["engine.plan"][0]
    assert step.start_ns <= plan.start_ns
    assert plan.start_ns + plan.duration_ns <= \
        step.start_ns + step.duration_ns


def test_off_path_asks_the_profiler_once_a_step_and_records_nothing(
        model, monkeypatch):
    from jax.profiler import TraceAnnotation
    eng = make_engine(model)
    calls = []
    monkeypatch.setattr(TraceAnnotation, "is_enabled",
                        staticmethod(lambda: calls.append(1) or False))
    rids = [eng.submit(p, max_new_tokens=4) for p in PROMPTS]
    rids += eng.submit_group([3, 4, 5, 6, 7, 8], 2, max_new_tokens=3)
    steps = 0
    while eng.has_work:
        before = len(calls)
        eng.step()
        steps += 1
        assert len(calls) - before <= 1
    assert steps > 3 and len(calls) == steps
    assert obs.get_tracer().spans() == []
    assert all(eng.is_done(r) for r in rids)
    # the unqueued counter needs no tracing: one reading between every
    # two fused steps (0 where the second was launched ahead), in the
    # ledger and on /metrics
    from senweaver_ide_tpu.obs.runtime_profile import get_profiler
    assert get_profiler().ledger()["engine.fused_step"][
        "unqueued_ms_sum"] > 0.0
    launched = eng.stats()["decode_steps"]
    assert steps - 1 <= launched <= steps
    assert (f'senweaver_runtime_unqueued_ms_count{{fn="engine.fused_step"}}'
            f' {launched - 1}' in obs.get_registry().render())


def test_same_seed_same_tokens_and_logps_with_tracing_on_and_off(model):
    outs = []
    for on in (False, True):
        obs._reset_for_tests()
        if on:
            obs.enable()
        eng = make_engine(model, seed=11)
        rids = drive(eng)
        outs.append([(eng.result(r), eng.result_logps(r)) for r in rids])
        # the group's followers split the block they share: the on run
        # went through ``engine.cow_copy``, the off run through no span
        names = by_name(obs.get_tracer().spans())
        assert ("engine.cow_copy" in names) == on
        assert eng._alloc.counters()["cow_copies"] == 2
        assert bool(names) == on
    assert outs[0] == outs[1]
    assert any(len(set(t)) > 1 for t, _ in outs[0])


def test_a_followers_first_write_is_a_cow_copy_span_under_the_plan(model):
    """A group's prompt ends inside a block: each follower's first write
    splits the block it shares (``_ensure_block``): one ``paged_kv.copy``
    that nothing waits for (the pool's donation orders it), inside
    ``engine.cow_copy`` under ``engine.assemble_plan``; the spans add up to the allocator's
    counter (a step's count of copies is its ``engine.cow_copy`` spans)."""
    eng = make_engine(model)
    obs.enable()
    drive(eng)
    spans = obs.get_tracer().spans()
    byid = {s.span_id: s for s in spans}
    names = by_name(spans)
    copies = names["engine.cow_copy"]
    n = eng._alloc.counters()["cow_copies"]
    assert len(copies) == n == 2
    for c in copies:
        assert byid[c.parent_id].name == "engine.assemble_plan"
        assert c.attrs == {}
        # the copy's own dispatch is inside it, not beside it
        kids = [s.name for s in spans if s.parent_id == c.span_id]
        assert kids == ["paged_kv.copy.dispatch"]
    for c in copies:
        (st,) = [s for s in names["engine.step"]
                 if s.start_ns <= c.start_ns and c.end_ns <= s.end_ns]
        assert "cow_copies" not in st.attrs
    assert (f"senweaver_kv_cow_copies_total {n}"
            in obs.get_registry().render())


def test_unqueued_ms_rides_the_step_and_is_the_profilers_reading(model):
    """From the second fused step on ``engine.step`` carries
    ``unqueued_ms``: the time from the last step's tokens on the host to
    this step's launch (emit, the caller, plan, copies), as
    ``RuntimeProfiler.end_step`` counted it. It lies between the spans
    that bound it, and is 0 where the step was launched ahead: the one
    before it was still in flight."""
    from senweaver_ide_tpu.obs.runtime_profile import get_profiler
    eng = make_engine(model)
    obs.enable()
    drive(eng)
    spans = obs.get_tracer().spans()
    names = by_name(spans)
    steps = [s for s in names["engine.step"] if "launches" in s.attrs]
    kid = lambda st, name: next(s for s in spans if s.name == name
                                and s.parent_id == st.span_id)
    assert "unqueued_ms" not in steps[0].attrs
    serial = 0
    for cur in steps[1:]:
        got = cur.attrs["unqueued_ms"]
        if cur.attrs["ahead"]:
            assert got == 0.0
            continue
        serial += 1
        launch = kid(cur, "engine.launch")
        fetch = max((f for f in names["engine.fetch"]
                     if f.end_ns <= launch.start_ns), key=lambda f: f.end_ns)
        emit = min((e for e in names["engine.emit"]
                    if e.start_ns >= fetch.end_ns), key=lambda e: e.start_ns)
        inner = (kid(cur, "engine.plan").end_ns - emit.start_ns) / 1e6
        outer = (launch.start_ns - fetch.end_ns) / 1e6
        assert 0.0 < inner <= got <= outer
    assert serial and serial < len(steps) - 1
    led = get_profiler().ledger()["engine.fused_step"]
    assert led["unqueued_ms_sum"] == pytest.approx(
        sum(s.attrs["unqueued_ms"] for s in steps[1:]), abs=0.01)
    hist = obs.get_registry().get("senweaver_runtime_unqueued_ms")
    assert hist.snapshot(fn="engine.fused_step")["count"] == len(steps) - 1


def test_two_engines_in_two_threads_each_read_their_own_unqueued_time(
        model):
    """``serve/replica.py`` steps several engines in one process, each in a
    thread of its own and all under ``engine.fused_step``: one's launch
    precedes another's fetch. Each engine keeps its own last fetch, so no
    ``unqueued_ms`` is negative, an engine's first step carries none
    (whatever the other did before; 0 where it ran ahead of itself), and
    the name's sum is the sum of the attrs."""
    import threading
    from senweaver_ide_tpu.obs.runtime_profile import get_profiler
    engines = [make_engine(model, seed=s) for s in (3, 4)]
    obs.enable()
    threads = [threading.Thread(target=drive, args=(e,)) for e in engines]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    steps = by_name(obs.get_tracer().spans())["engine.step"]
    assert len(steps) == sum(e.stats()["decode_steps"] for e in engines)
    firsts = [s for s in steps if s.attrs["step"] == 0]
    assert len(firsts) == 2
    assert all("unqueued_ms" not in s.attrs for s in firsts)
    got = [s.attrs["unqueued_ms"] for s in steps if s.attrs["step"] > 0]
    assert len(got) == len(steps) - 2 and min(got) >= 0.0 < max(got)
    led = get_profiler().ledger()["engine.fused_step"]
    assert led["unqueued_ms_sum"] == pytest.approx(sum(got), abs=0.01)
    hist = obs.get_registry().get("senweaver_runtime_unqueued_ms")
    snap = hist.snapshot(fn="engine.fused_step")
    assert snap["count"] == len(got)
    assert snap["sum"] == pytest.approx(sum(got), abs=0.01)


def test_engine_counters_are_on_metrics_with_span_tracing_off(model):
    eng = make_engine(model)
    drive(eng)
    assert not obs.is_enabled() and obs.get_tracer().spans() == []
    st = eng.stats()
    text = obs.get_registry().render()
    assert f"senweaver_engine_tokens_total {st['tokens_emitted']}" in text
    assert f"senweaver_engine_decode_steps_total {st['decode_steps']}" \
        in text


def test_slot_layout_keeps_its_decode_step_span(model):
    params, config = model
    eng = RolloutEngine(params, config, num_slots=2, max_len=64,
                        sample=SAMPLED,
                        engine_config=EngineConfig(kv_layout="slots"))
    obs.enable()
    rid = eng.submit(PROMPTS[1], max_new_tokens=3)
    eng.run()
    names = by_name(obs.get_tracer().spans())
    assert "engine.decode_step" in names and "engine.step" not in names
    assert {"request.queue", "request.prefill", "request.decode"} <= \
        set(names)
    assert names["request.decode"][0].trace_id == f"req-{rid}"
    text = obs.get_registry().render()
    assert "senweaver_engine_tokens_total 3" in text


def test_record_span_keeps_the_given_ends_and_ids():
    t = obs.get_tracer()
    t.record_span("request.queue", 1_000, 3_500_000, trace_id="req-7",
                  parent_id="abc", rid=7)
    (s,) = t.spans()
    assert (s.name, s.trace_id, s.parent_id) == ("request.queue", "req-7",
                                                 "abc")
    assert (s.start_ns, s.end_ns) == (1_000, 3_500_000)
    assert s.duration_ms == pytest.approx(3.499)
    assert s.attrs == {"rid": 7} and len(s.span_id) == 16
    assert "start_ns" in s.to_dict()


def test_child_span_follows_an_open_span_or_an_enabled_tracer(monkeypatch):
    """``child_span`` is for a callee of a loop that asked ``active()``
    once: it never asks the profiler, and is on only under a span that is
    open around it or an enabled tracer."""
    from jax.profiler import TraceAnnotation
    asked = []
    monkeypatch.setattr(TraceAnnotation, "is_enabled",
                        staticmethod(lambda: asked.append(1) or True))
    t = obs.get_tracer()
    with t.child_span("alone"):
        pass
    assert asked == [] and t.spans() == []
    with t.span("outer"):               # on: a session "runs"
        with t.child_span("inner"):
            pass
    inner, outer = t.spans()
    assert (inner.name, outer.name) == ("inner", "outer")
    assert inner.parent_id == outer.span_id and len(asked) == 1
    obs.enable()
    with t.child_span("top"):
        pass
    assert t.spans()[-1].name == "top" and len(asked) == 1


def _lowered_fused_step(config, seed=0):
    params = init_params(config, jax.random.PRNGKey(seed))
    eng = RolloutEngine(params, config, num_slots=4, max_len=64,
                        sample=SAMPLED,
                        engine_config=EngineConfig(kv_layout="paged",
                                                   block_size=4))
    return engine_mod._paged_fused_step.lower(
        params, config, np.zeros((6, 4), np.int32),
        np.zeros((4, 2), np.int32), eng.pool, jax.random.PRNGKey(0),
        eng._cur_tok_dev, SAMPLED, False).as_text(debug_info=True)


@pytest.mark.parametrize("make,scopes", [
    (tiny_test, ("embed", "attn.qkv", "attn.kv_write", "attn.kv_gather",
                 "attn.scores", "attn.out", "mlp", "lm_head", "sample")),
    (tiny_moe_test, ("mlp", "moe.router", "moe.sort", "moe.experts",
                     "moe.combine")),
    (tiny_glm_moe_test, ("embed", "attn.q_latent", "attn.kv_latent",
                         "attn.kv_write", "attn.absorb", "attn.kv_gather",
                         "attn.scores", "attn.out", "mlp", "moe.router",
                         "moe.sort", "moe.experts", "moe.shared",
                         "moe.combine", "lm_head", "sample"))],
    ids=["dense", "moe", "latent-moe"])
def test_fused_step_carries_the_stable_device_side_names(make, scopes):
    text = _lowered_fused_step(make())
    for scope in scopes:
        assert f'"{scope}/' in text or f"/{scope}/" in text, scope
