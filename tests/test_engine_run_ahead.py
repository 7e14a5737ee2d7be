"""A saturated paged engine runs one step ahead: ``step()`` launches step
k+1 before step k's tokens are home, the device feeds the rows their own
sampled tokens, and nothing a caller can see differs from the serial engine
but WHEN a token is returned. The serial engine is the same code with the
private predicate ``_saturated`` forced false (a seam, not a knob). Counted
and compared here on the CPU; what the overlap is worth in time only the chip
says (PERF.md §6, PR 36)."""

import jax
import numpy as np
import pytest

from senweaver_ide_tpu import obs
from senweaver_ide_tpu.models import init_params, tiny_test
from senweaver_ide_tpu.models.config import (tiny_falcon_h1_test,
                                             tiny_glm_moe_test)
from senweaver_ide_tpu.rollout import (AdapterPool, AdapterPoolConfig,
                                       EngineConfig, RolloutEngine)
from senweaver_ide_tpu.rollout import engine as engine_mod
from senweaver_ide_tpu.rollout.sampler import SampleParams
from senweaver_ide_tpu.training.lora import init_lora

GREEDY = SampleParams(temperature=0.0, top_k=0, top_p=1.0)
SAMPLED = SampleParams(temperature=1.0, top_k=0, top_p=1.0)
MODELS = {"dense": tiny_test, "latent-moe": tiny_glm_moe_test,
          "hybrid-ssm": tiny_falcon_h1_test}
PROMPTS = ([5, 9, 2, 7, 1, 3, 8, 4, 6, 2, 9, 1, 7, 3, 5, 8, 2, 4, 6, 1, 3, 5],
           [11, 3, 8, 1, 4], [2, 6, 4, 9, 9, 1, 2], [1, 2, 3],
           [4, 5, 6, 7, 8, 9, 10], [3, 3, 3, 3], [8, 1, 8, 1, 8])
GROUP = [3, 4, 5, 6, 7, 8]
COUNTER = "senweaver_engine_steps_run_ahead_total"


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs._reset_for_tests()
    yield
    obs._reset_for_tests()


@pytest.fixture(scope="module", params=list(MODELS))
def model(request):
    config = MODELS[request.param]()
    return init_params(config, jax.random.PRNGKey(0)), config


@pytest.fixture(scope="module")
def dense():
    config = tiny_test()
    return init_params(config, jax.random.PRNGKey(0)), config


@pytest.fixture
def serial(monkeypatch):
    """Call it to force the predicate false from here on."""
    def force():
        monkeypatch.setattr(RolloutEngine, "_saturated", lambda self: False)
    return force


def make_engine(model, sample=GREEDY, num_slots=3, max_len=64, **kw):
    params, config = model
    cfg = dict(kv_layout="paged", block_size=4, step_tokens=16)
    cfg.update(kw.pop("engine", {}))
    return RolloutEngine(params, config, num_slots=num_slots,
                         max_len=max_len, sample=sample, seed=7,
                         engine_config=EngineConfig(**cfg), **kw)


def run_ahead_steps() -> int:
    c = obs.get_registry().get(COUNTER)
    return int(c.value()) if c is not None else 0


def submit_all(eng, kind, **kw):
    if kind == "singles":
        return [eng.submit(p, max_new_tokens=8 + i, **kw)
                for i, p in enumerate(PROMPTS)]
    rids = eng.submit_group(GROUP, 5, max_new_tokens=9, **kw)
    return rids + eng.submit_group(PROMPTS[0], 4, max_new_tokens=7, **kw)


def drive(eng, rids):
    """Step to the end. Returns, a request, (tokens, log-probs, what the
    ``step()`` calls handed out), checked against each other."""
    seen = {r: [] for r in rids}
    while eng.has_work:
        for rid, toks in eng.step().items():
            assert (not eng.is_done(rid)
                    or eng.result(rid)[-len(toks):] == toks)
            seen[rid].extend(toks)
    out = []
    for r in rids:
        assert eng.is_done(r) and seen[r] == eng.result(r)
        assert len(eng.result_logps(r)) == len(eng.result(r))
        out.append((eng.result(r), eng.result_logps(r)))
    assert eng._flying is None
    return out


# ---- the same tokens, the same log-probs -----------------------------------

@pytest.mark.parametrize("kind", ["singles", "groups"])
@pytest.mark.parametrize("sample", [GREEDY, SAMPLED],
                         ids=["greedy", "sampled"])
def test_a_saturated_engine_emits_what_the_serial_one_does(
        model, kind, sample, serial):
    """More requests than rows: the engine runs ahead, and tokens AND
    log-probs of every request are the serial engine's, to the bit (the
    entry layout of every step is the same, so a seed's sampled tokens are
    too: a length-finished request leaves its row at launch, and the
    queue's next request enters the step it entered before)."""
    eng = make_engine(model, sample)
    ahead = drive(eng, submit_all(eng, kind))
    steps = eng.stats()["decode_steps"]
    assert run_ahead_steps() > steps // 2
    eng._alloc.check_leaks()
    assert eng._alloc.used_blocks == 0
    serial()
    ref = make_engine(model, sample)
    assert drive(ref, submit_all(ref, kind)) == ahead
    assert ref.stats()["decode_steps"] == steps
    assert run_ahead_steps() > steps // 2       # the counter stood still
    assert any(len(set(t)) > 1 for t, _ in ahead)


# ---- what ends a request ---------------------------------------------------

def test_an_eos_met_while_the_next_entry_is_in_flight(dense, monkeypatch):
    """EOS is a value: the row has one more entry in the step that was
    launched ahead. Its sample is dropped, nothing after the EOS token is in
    ``result`` / ``result_logps`` / what ``step()`` hands out, the row
    serves the queue again, and every block comes back."""
    probe = make_engine(dense)
    full = drive(probe, submit_all(probe, "singles"))
    # the first token a request repeats nowhere before: its EOS
    cut = {}
    for i, (toks, _) in enumerate(full):
        j = next(j for j in range(2, len(toks)) if toks[j] not in toks[:j])
        cut[i] = j
    in_flight_at_eos = []
    mark = RolloutEngine._mark_done

    def spy(self, req):
        if req.eos_id is not None and req.tokens[-1] == req.eos_id:
            in_flight_at_eos.append(req.inflight)
        return mark(self, req)

    monkeypatch.setattr(RolloutEngine, "_mark_done", spy)
    eng = make_engine(dense)
    rids = [eng.submit(p, max_new_tokens=8 + i, eos_id=full[i][0][cut[i]])
            for i, p in enumerate(PROMPTS)]
    out = drive(eng, rids)
    for i, (toks, logps) in enumerate(out):
        assert toks == full[i][0][:cut[i] + 1]
        assert logps == full[i][1][:cut[i] + 1]
    assert 1 in in_flight_at_eos            # an entry was in flight past one
    assert all(r is None for r in eng._slot_req)
    eng._alloc.check_leaks()
    assert eng._alloc.used_blocks == 0
    assert eng.stats()["tokens_emitted"] == sum(len(t) for t, _ in out)


def test_a_held_row_that_ends_on_eos_continues_as_the_serial_one(
        model, serial, monkeypatch):
    """A conversation that ends on EOS with its row held, on a saturated
    engine. Where the row holds k/v alone, the entry that was in flight
    wrote the EOS token's k/v one step early: the row's length excludes
    it, and the continuation's first entry writes it again. A recurrent
    state that consumed the token cannot step back, so a step in which a
    held request of such a model may sample its EOS comes home before the
    next is planned: no entry is ever in flight past it. Either way the
    continuation decodes what the serial engine's does."""
    probe = make_engine(model)
    full = drive(probe, submit_all(probe, "singles"))
    toks0 = full[0][0]
    j = next(j for j in range(2, len(toks0)) if toks0[j] not in toks0[:j])
    in_flight_at_eos = []
    mark = RolloutEngine._mark_done

    def spy(self, req):
        if req.hold_slot:
            in_flight_at_eos.append(req.inflight)
        return mark(self, req)

    monkeypatch.setattr(RolloutEngine, "_mark_done", spy)

    def conversation():
        eng = make_engine(model)
        rids = [eng.submit(p, max_new_tokens=8 + i,
                           **({"hold_slot": True, "eos_id": toks0[j]}
                              if i == 0 else {}))
                for i, p in enumerate(PROMPTS)]
        drive(eng, rids)
        first = eng.result(rids[0])
        assert first == toks0[:j + 1]
        held = eng._slot_held.index(rids[0])
        assert eng._row_len[held] == len(PROMPTS[0]) + j
        nxt = eng.submit(list(PROMPTS[0]) + first + [9, 9],
                         max_new_tokens=5, continue_from=rids[0])
        rest = [eng.submit(p, max_new_tokens=6) for p in PROMPTS[1:5]]
        return drive(eng, [nxt] + rest)

    ahead = conversation()
    assert run_ahead_steps() > 0
    assert in_flight_at_eos == [0 if model[1].ssm else 1]
    serial()
    assert conversation() == ahead


@pytest.mark.parametrize("bound", ["max_new_tokens", "context"])
def test_no_entry_is_planned_past_a_requests_last_token(dense, bound,
                                                         monkeypatch):
    """Budget and context bound are counts of LAUNCHED tokens: a request
    has exactly as many sampling entries as it has tokens, launched ahead
    or not, and no row's table outgrows the context."""
    sampled = {}
    advance = RolloutEngine._advance_paged

    def spy(self, decode_rows, job_rows):
        out = advance(self, decode_rows, job_rows)
        for _idx, req, _first in out:
            sampled[req.rid] = sampled.get(req.rid, 0) + 1
        for row, table in enumerate(self._tables):
            assert len(table) <= self._blocks_per_row
            assert self._row_len[row] <= self.context_bound - 1
        return out

    monkeypatch.setattr(RolloutEngine, "_advance_paged", spy)
    if bound == "context":
        eng = make_engine(dense, max_len=32)
        rids = [eng.submit(p, max_new_tokens=100) for p in PROMPTS]
    else:
        eng = make_engine(dense)
        rids = [eng.submit(p, max_new_tokens=1 + i)
                for i, p in enumerate(PROMPTS)]
    out = drive(eng, rids)
    assert run_ahead_steps() > 0
    for i, (rid, (toks, _)) in enumerate(zip(rids, out)):
        assert sampled[rid] == len(toks)
        if bound == "context":
            assert len(PROMPTS[i]) + len(toks) == eng.context_bound
        else:
            assert len(toks) == 1 + i
    assert eng._alloc.used_blocks == 0


def test_done_and_result_turn_with_the_last_delivered_token(dense):
    """``is_done`` is true exactly when the request's last token has been
    returned by a ``step()``; ``has_work`` holds while a step is in
    flight, also when no row and no queue is left."""
    eng = make_engine(dense, num_slots=2)
    want = {eng.submit(p, max_new_tokens=4 + i): 4 + i
            for i, p in enumerate(PROMPTS[:5])}
    got = {r: 0 for r in want}
    flying_seen = closed_not_done = 0
    while eng.has_work:
        out = eng.step()
        for rid, toks in out.items():
            got[rid] += len(toks)
        for rid, n in want.items():
            assert len(eng.result(rid)) == got[rid] <= n
            assert eng.is_done(rid) == (got[rid] == n)
        if eng._flying is not None:
            flying_seen += 1
            assert eng.has_work
            closed_not_done += sum(
                r.closing and not r.done for r in eng._requests.values())
    assert flying_seen and closed_not_done      # launched, not yet home
    assert all(eng.is_done(r) for r in want)
    assert eng._flying is None and not eng.has_work


# ---- where it stands down --------------------------------------------------

def _mid_flight(model):
    """A saturated engine with a step in flight and its requests."""
    eng = make_engine(model)
    rids = [eng.submit(p, max_new_tokens=12) for p in PROMPTS]
    for _ in range(6):
        eng.step()
    assert eng._flying is not None
    return eng, rids


def _decoding(eng, rids):
    return next(r for r in rids if eng._requests[r].slot is not None
                and r not in eng._prefill_jobs and eng.result(r))


def _checkpointed(eng, rids):
    """Take a request out by checkpoint, then get a step in flight again."""
    ckpt = eng.checkpoint_request(_decoding(eng, rids))
    eng.release_request(ckpt.rid)
    rids.remove(ckpt.rid)
    while eng._flying is None:
        eng.step()
    return ckpt


# name -> (what it needs first, the call that has to drain)
ENTRIES = {
    "update_params": (None, lambda eng, rids, _: eng.update_params(
        eng.params)),
    "fork_request": (None, lambda eng, rids, _: rids.append(
        eng.fork_request(_decoding(eng, rids)))),
    "checkpoint_request": (
        None, lambda eng, rids, _: eng.checkpoint_request(
            _decoding(eng, rids), pause=False)),
    "restore_request": (_checkpointed, lambda eng, rids, ckpt: rids.append(
        eng.restore_request(ckpt))),
    "pause_request": (None, lambda eng, rids, _: (
        eng.pause_request(rids[-1]), eng.resume_request(rids[-1]))),
    "release_request": (None, lambda eng, rids, _: eng.release_request(
        rids.pop())),
    "release_slot": (None, lambda eng, rids, _: eng.release_slot(rids[0])),
    "take_pressure_migrations": (
        lambda eng, rids: eng._pressure_migrations.append(rids[0]),
        lambda eng, rids, _: eng.take_pressure_migrations()),
    "enable_speculation": (None, lambda eng, rids, _: eng.enable_speculation(
        eng.params, eng.config, depth=2)),
}


@pytest.mark.parametrize("entry", list(ENTRIES))
def test_an_entry_that_reads_tokens_or_rows_sees_a_delivered_engine(
        dense, entry, monkeypatch):
    """Each such entry first brings the step in flight home (one
    ``_drain``): it sees no request with a token on its way, the drained
    tokens are handed out by the next ``step()``, and every request still
    decodes what it decodes alone."""
    alone = make_engine(dense, num_slots=8)
    want = {tuple(p): t for p, (t, _) in zip(
        PROMPTS, drive(alone, [alone.submit(p, max_new_tokens=12)
                               for p in PROMPTS]))}
    eng, rids = _mid_flight(dense)
    prepare, act = ENTRIES[entry]
    ctx = prepare(eng, rids) if prepare else None
    assert eng._flying is not None
    before = {r: list(eng.result(r)) for r in rids}
    inflight_seen = []
    drain = RolloutEngine._drain

    def spy(self):
        drain(self)
        inflight_seen.append(sum(r.inflight
                                 for r in self._requests.values()))
        assert self._flying is None

    monkeypatch.setattr(RolloutEngine, "_drain", spy)
    act(eng, rids, ctx)
    assert inflight_seen and set(inflight_seen) == {0}
    # what the drain delivered waits for the next step() to hand it out
    grown = {r: eng.result(r)[len(before[r]):] for r in before
             if r in eng._requests and len(eng.result(r)) > len(before[r])}
    assert grown
    out = eng.step()
    for r, toks in grown.items():
        assert out[r][:len(toks)] == toks
    seen = {r: list(eng.result(r)) for r in rids}
    while eng.has_work:
        for rid, toks in eng.step().items():
            seen.setdefault(rid, []).extend(toks)
    for r in rids:
        req = eng._requests[r]
        assert eng.is_done(r) and seen[r] == eng.result(r)
        if req.parent_rid is None and tuple(req.prompt) in want:
            assert eng.result(r) == want[tuple(req.prompt)]
    eng._alloc.check_leaks()


def test_a_fleets_tick_with_nothing_on_offer_leaves_the_step_in_flight(dense):
    """``serve/scheduler.py`` asks every replica for its pressure offers on
    every coordinator tick: with none to hand out nothing is read or moved,
    and the engine goes on running ahead."""
    eng, rids = _mid_flight(dense)
    assert eng.take_pressure_migrations() == []
    assert eng._flying is not None
    eng.run()
    assert all(eng.is_done(r) for r in rids)


def test_publish_adapter_and_export_prefix_drain_too(dense):
    params, config = dense
    pool = AdapterPool(config, AdapterPoolConfig())
    eng = make_engine(dense, adapter_pool=pool)
    pid = eng.register_prefix(PROMPTS[0][:8])
    rids = [eng.submit(p, max_new_tokens=12) for p in PROMPTS]
    for _ in range(6):
        eng.step()
    assert eng._flying is not None
    eng.publish_adapter("t1", init_lora(config, jax.random.PRNGKey(1),
                                        rank=2))
    assert eng._flying is None
    eng.step()
    eng.step()
    assert eng._flying is not None
    eng.export_prefix(pid)
    assert eng._flying is None
    eng.run()
    assert all(eng.is_done(r) and len(eng.result(r)) == 12 for r in rids)


def test_a_plan_that_has_to_reclaim_brings_the_step_home_first(dense,
                                                               serial):
    """A pool too small for its rows: the plan runs out of blocks with a
    step in flight, collects it, and only then preempts (a preempted
    request is rebuilt from its tokens). The tokens are the serial
    engine's."""
    small = {"engine": {"num_blocks": 14}}
    eng = make_engine(dense, **small)
    ahead = drive(eng, [eng.submit(p, max_new_tokens=24) for p in PROMPTS])
    assert eng.stats()["kv_preemptions"] > 0 and run_ahead_steps() > 0
    eng._alloc.check_leaks()
    serial()
    ref = make_engine(dense, **small)
    assert drive(ref, [ref.submit(p, max_new_tokens=24)
                       for p in PROMPTS]) == ahead


def test_speculation_on_never_runs_ahead(dense):
    """A verify window needs the values: with a draft attached every step
    is fetched before ``step()`` returns, saturated or not."""
    obs.enable()
    plain = make_engine(dense)
    want = drive(plain, submit_all(plain, "singles"))
    ahead = run_ahead_steps()
    assert ahead > 0
    obs.get_tracer().clear()
    eng = make_engine(dense)
    eng.enable_speculation(eng.params, eng.config, depth=3)
    rids = submit_all(eng, "singles")
    while eng.has_work:
        eng.step()
        assert eng._flying is None
    assert [(eng.result(r)) for r in rids] == [t for t, _ in want]
    assert run_ahead_steps() == ahead
    steps = [s for s in obs.get_tracer().spans() if s.name == "engine.step"]
    assert steps and all(s.attrs.get("ahead", 0) == 0 for s in steps)
    assert eng.stats()["spec_rounds"] > 0


# ---- when it engages, and what the spans say --------------------------------

def _step_spans():
    spans = obs.get_tracer().spans()
    steps = [s for s in spans if s.name == "engine.step"
             and "launches" in s.attrs]
    kids = {s.span_id: [k for k in spans if k.parent_id == s.span_id]
            for s in steps}
    return steps, kids


@pytest.mark.parametrize("load", ["lone", "under-loaded"])
def test_an_engine_with_a_free_row_and_no_queue_keeps_the_serial_order(
        dense, load):
    """The warm-up's lone request and the under-loaded server: every step
    is fetched by the call that launched it (``ahead`` 0 on every span,
    the counter untouched), so no arrival ever finds its step gone."""
    obs.enable()
    eng = make_engine(dense, num_slots=6)
    if load == "lone":
        rids = [eng.submit(PROMPTS[0], max_new_tokens=6)]
    else:
        rids = ([eng.submit(p, max_new_tokens=6) for p in PROMPTS[:2]]
                + eng.submit_group(GROUP, 2, max_new_tokens=6))
    while eng.has_work:
        eng.step()
        assert eng._flying is None
        if (load != "lone" and len(rids) < 7
                and len(eng._free_slots()) > 2):
            rids.append(eng.submit(PROMPTS[len(rids)], max_new_tokens=5))
    steps, kids = _step_spans()
    assert len(steps) == eng.stats()["decode_steps"] > 0
    assert run_ahead_steps() == 0
    for s in steps:
        assert s.attrs["ahead"] == 0 and s.attrs["launches"] == 1
        names = [k.name for k in sorted(kids[s.span_id],
                                        key=lambda k: k.start_ns)]
        assert names == ["engine.plan", "engine.launch", "engine.advance",
                         "engine.schedule", "engine.fetch", "engine.emit"]
        (fetch,) = [k for k in kids[s.span_id] if k.name == "engine.fetch"]
        assert fetch.attrs["of_step"] == s.attrs["step"]


def test_a_saturated_engine_says_so_on_its_spans(dense):
    """``ahead`` 1 and ``unqueued_ms`` 0 where the launch came before the
    last step's tokens were home; the fetch under such a span is the step
    BEFORE its own (``of_step``), and comes after the launch. Every step
    is fetched exactly once."""
    obs.enable()
    eng = make_engine(dense)
    drive(eng, submit_all(eng, "singles"))
    steps, kids = _step_spans()
    n = eng.stats()["decode_steps"]
    assert len(steps) == n
    ahead = [s for s in steps if s.attrs["ahead"]]
    assert len(ahead) == run_ahead_steps() > n // 2
    fetched = []
    for s in steps:
        mine = sorted(kids[s.span_id], key=lambda k: k.start_ns)
        names = [k.name for k in mine]
        assert names[:3] == ["engine.plan", "engine.launch",
                             "engine.advance"]
        fetched += [k.attrs["of_step"] for k in mine
                    if k.name == "engine.fetch"]
        if s.attrs["ahead"]:
            assert s.attrs["unqueued_ms"] == 0.0
            first = next(k for k in mine if k.name == "engine.fetch")
            assert first.attrs["of_step"] == s.attrs["step"] - 1
            assert names.index("engine.fetch") > names.index(
                "engine.launch")
    # steps the plan-less last call brought home have no launching span
    spans = obs.get_tracer().spans()
    assert sorted(k.attrs["of_step"] for k in spans
                  if k.name == "engine.fetch") == list(range(n))
    assert set(fetched) <= set(range(n))


def test_a_steps_values_sit_on_the_span_that_launched_it(serial):
    """``expert_*`` arrive with a step's tokens, one call after a run-ahead
    launch: they are set on the span that LAUNCHED the step, beside its
    ``used`` and ``entries``, so a reader that joins them by step (the
    expert roofline does) reads what it read from the serial engine, step
    by step."""
    config = tiny_glm_moe_test()
    moe = init_params(config, jax.random.PRNGKey(0)), config

    def steps_of():
        obs._reset_for_tests()
        obs.enable()
        eng = make_engine(moe)
        drive(eng, submit_all(eng, "singles"))
        steps, _ = _step_spans()
        assert len(steps) == eng.stats()["decode_steps"]
        return [(s.attrs["step"], s.attrs["used"],
                 s.attrs["expert_assignments"], s.attrs["experts_touched"],
                 s.attrs["expert_load_max"]) for s in steps]

    ahead = steps_of()
    assert run_ahead_steps() > len(ahead) // 2
    assert all(a == used * config.num_experts_per_tok
               for _, used, a, _, _ in ahead)
    serial()
    assert steps_of() == ahead
