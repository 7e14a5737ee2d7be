"""The head and the sampler run over the entries somebody reads.

A fused step wider than the rows (prefill chunks ride it) has at most one
sampler a row: the entry with ``FEED_PUT``. ``_paged_fused_step`` finds it
on the device and ``forward_paged`` gathers the stream's rows there BEFORE
the final norm and the head, so a wide step pays the vocabulary
``num_slots`` times, not ``T`` times. A step as wide as the rows gathers
nothing (the program it was), and a plan with verify entries keeps every
entry's head. A layer pattern whose trailing segments hold nothing (a
SambaY decoder's cross-decoder) runs those over the gathered entries too
(PR 46): the gather moves up behind the last layer that writes. Counted
and compared here on the CPU; what it is worth in time only the chip says
(PERF.md §6, PR 40 and 46)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from senweaver_ide_tpu import obs
from senweaver_ide_tpu.models import init_params, tiny_test
from senweaver_ide_tpu.models.config import (ModelConfig, sambay_layer_types,
                                             tiny_falcon_h1_test,
                                             tiny_phi4flash_test,
                                             tiny_solar_open2_test,
                                             tiny_xing_mhc_test)
from senweaver_ide_tpu.models.transformer import forward_paged
from senweaver_ide_tpu.obs.runtime_profile import get_profiler
from senweaver_ide_tpu.rollout import EngineConfig, RolloutEngine
from senweaver_ide_tpu.rollout import engine as engine_mod
from senweaver_ide_tpu.rollout.engine import FEED_PUT, _head_entries
from senweaver_ide_tpu.rollout.sampler import SampleParams

GREEDY = SampleParams(temperature=0.0, top_k=0, top_p=1.0)
SAMPLED = SampleParams(temperature=0.8, top_k=0, top_p=0.95)
ROWS, WIDE, BLOCK = 4, 16, 8

MODELS = {
    "dense-untied-head": tiny_test,
    "dense-tied-head": lambda: dataclasses.replace(
        tiny_test(), name="tiny-tied-test", tie_word_embeddings=True),
    "head-multiplier": tiny_falcon_h1_test,
    "mhc-stream": tiny_xing_mhc_test,
    "layer-pattern": tiny_phi4flash_test,
}


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs._reset_for_tests()
    yield
    obs._reset_for_tests()


@pytest.fixture(scope="module", params=list(MODELS))
def model(request):
    config = MODELS[request.param]()
    return init_params(config, jax.random.PRNGKey(0)), config


def make_engine(model):
    return RolloutEngine(
        model[0], model[1], num_slots=ROWS, max_len=32, sample=GREEDY,
        engine_config=EngineConfig(kv_layout="paged", block_size=BLOCK,
                                   step_tokens=WIDE))


def wide_plan(num_blocks, chunk=7):
    """A 16-entry plan over four rows by hand: two decode rows, a prefill
    that completes (its last entry puts), one of ``chunk`` entries that
    does not, and padding (none at ``chunk`` 10: the last entry is then a
    prompt token of row 3). Row r's table is blocks ``4 r .. 4 r + 3``.
    -> (plan (6, 16), tables (4, 4), the samplers' entries by row: 16 =
    none)."""
    entries = ([(7, 0, 5, FEED_PUT), (9, 1, 9, FEED_PUT)]
               + [(3 + j, 2, j, FEED_PUT if j == 3 else 0)
                  for j in range(4)]
               + [(11 + j, 3, j, 0) for j in range(chunk)])
    plan = np.zeros((6, WIDE), np.int32)
    plan[3] = num_blocks                    # padding: the write is dropped
    for i, (tok, row, pos, feed) in enumerate(entries):
        plan[:, i] = (tok, row, pos, 4 * row + pos // BLOCK, pos % BLOCK,
                      feed)
    tables = np.arange(ROWS * 4, dtype=np.int32).reshape(ROWS, 4)
    return plan, tables, np.asarray([0, 1, 5, WIDE], np.int32)


def paged_step(model, pool, plan, tables, entries, kernel=False):
    """``forward_paged`` on a ``(6, T)`` plan -> (logits, pool')."""
    params, config = model

    def run(params, pool, plan, tables, entries):
        tokens, seq_row, positions, write_block, write_off, _feed = plan
        return forward_paged(
            params, config, tokens, pool=pool, tables=tables,
            seq_row=seq_row, positions=positions, write_block=write_block,
            write_off=write_off, use_kernel=kernel, logit_entries=entries)

    return jax.jit(run)(params, pool, jnp.asarray(plan), jnp.asarray(tables),
                        entries)


def paged_logits(model, eng, plan, tables, entries):
    return np.asarray(paged_step(model, eng.pool, plan, tables, entries)[0])


# ---- (1) the gathered head is the every-entry head at the samplers --------

def test_gathered_logits_are_the_every_entry_heads_at_each_sampler(model):
    eng = make_engine(model)
    plan, tables, samplers = wide_plan(eng._alloc.num_blocks)
    every = paged_logits(model, eng, plan, tables, None)
    got = paged_logits(model, eng, plan, tables, jnp.asarray(samplers))
    assert every.shape == (WIDE, model[1].vocab_size)
    assert got.shape == (ROWS, model[1].vocab_size)
    assert got.dtype == every.dtype == np.float32
    # a row with no sampler reads the clamped (last) entry: nobody's
    at = np.minimum(samplers, WIDE - 1)
    np.testing.assert_allclose(got, every[at], rtol=1e-5, atol=1e-6)
    assert np.array_equal(got.argmax(-1), every[at].argmax(-1))
    assert len(set(every.argmax(-1).tolist())) > 1


def test_the_wide_step_samples_its_samplers_and_scatters_them_back(model):
    """The fused step on that plan against its every-entry form
    (``all_logits=True``: the program it was): greedy tokens equal and
    log-probs to rounding at the sampler entries, zeros elsewhere, the
    rows' current tokens the same, the extras behind them where they
    were."""
    params, config = model
    outs = []
    for every in (False, True):
        eng = make_engine(model)
        plan, tables, samplers = wide_plan(eng._alloc.num_blocks)
        cur = jnp.asarray([40, 41, 42, 43], jnp.int32)
        toks, logp, _pool, _key, cur = engine_mod._paged_fused_step(
            params, config, plan, tables, eng.pool, jax.random.PRNGKey(3),
            cur, GREEDY, False, all_logits=every)
        outs.append((np.asarray(toks), np.asarray(logp), np.asarray(cur)))
    (toks, logp, cur), (toks_all, logp_all, cur_all) = outs
    assert toks.shape == toks_all.shape and logp.shape == logp_all.shape
    put = samplers[samplers < WIDE]
    assert np.array_equal(toks[put], toks_all[put])
    np.testing.assert_allclose(logp[put], logp_all[put], rtol=1e-5,
                               atol=1e-6)
    rest = np.setdiff1d(np.arange(WIDE), put)
    assert not toks[rest].any() and not logp[rest].any()
    assert logp_all[rest].all()             # the parent paid for those
    # three rows put, the fourth keeps what it had
    assert np.array_equal(cur, cur_all)
    assert cur[3] == 43 and np.array_equal(cur[:3], toks[put])
    # MoEStats' counts, the attention plan's two, the Sinkhorn error
    assert np.array_equal(toks[WIDE:], toks_all[WIDE:])
    np.testing.assert_allclose(logp[WIDE:], logp_all[WIDE:], rtol=1e-5)


# ---- (1b) a pattern's trailing layers that write nothing -----------------

PATTERNS = {"sambay": tiny_phi4flash_test, "delta-rule": tiny_solar_open2_test}


def context_plan(num_blocks):
    """What the decode rows of ``wide_plan`` attend: rows 0 and 1 prefill
    positions 0..4 and 0..8 (14 entries, 2 of padding)."""
    plan = np.zeros((6, WIDE), np.int32)
    plan[3] = num_blocks
    runs = [(0, p) for p in range(5)] + [(1, p) for p in range(9)]
    for i, (row, pos) in enumerate(runs):
        plan[:, i] = (20 + i, row, pos, 4 * row + pos // BLOCK, pos % BLOCK,
                      0)
    return plan


@pytest.mark.parametrize("pattern, kernel, chunk", [
    ("sambay", False, 7), ("sambay", False, 10), ("sambay", True, 7),
    ("sambay", True, 10), ("delta-rule", False, 7), ("delta-rule", True, 10)])
def test_a_patterns_gathered_step_serves_and_writes_what_every_entry_does(
        pattern, kernel, chunk):
    """Through the gather and through the kernels (interpreted), on a pool
    that holds the decode rows' contexts: the gathered form's logits are
    the every-entry form's rows at each sampler, and every leaf of the
    pool — k, v, the mixers' states, the conv windows, the rings — is the
    every-entry form's, whether the pattern ends in layers that hold
    nothing (SambaY: they run over the 4 gathered entries) or in layers
    that write (the delta rule: nothing is cut). A row with no sampler is
    nobody's: at ``chunk`` 10 its index clamps onto a prompt token at
    position 9, and it must not read that token's context."""
    config = PATTERNS[pattern]()
    model = (init_params(config, jax.random.PRNGKey(0)), config)
    eng = make_engine(model)
    nb = eng._alloc.num_blocks
    plan, tables, samplers = wide_plan(nb, chunk)
    _, pool = paged_step(model, eng.pool, context_plan(nb), tables, None,
                           kernel)
    every, pool_every = paged_step(model, pool, plan, tables, None, kernel)
    got, pool_got = paged_step(model, pool, plan, tables,
                                 jnp.asarray(samplers), kernel)
    put = samplers < WIDE
    assert got.shape == (ROWS, config.vocab_size)
    np.testing.assert_allclose(np.asarray(got)[put],
                               np.asarray(every)[samplers[put]],
                               rtol=1e-5, atol=1e-6)
    assert np.isfinite(np.asarray(got)).all()
    leaves, leaves_every = (jax.tree_util.tree_leaves(p)
                            for p in (pool_got, pool_every))
    assert len(leaves) == len(leaves_every) >= 4
    for a, b in zip(leaves, leaves_every):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)
    # the step wrote something: the pool is not the one it was given
    assert any(float(np.abs(np.asarray(a) - np.asarray(b)).max()) > 0
               for a, b in zip(leaves, jax.tree_util.tree_leaves(pool)))


def test_a_row_with_no_sampler_runs_as_padding_does():
    """SambaY, ``chunk`` 10: row 3's index is out of range and clamps onto
    the plan's last entry, a prompt token of row 3 at position 9. The cut
    program hands the cross layers ``(row 0, position 0)`` for it, so its
    logits are NOT that entry's: the every-entry form's differ there and
    nowhere else."""
    config = tiny_phi4flash_test()
    model = (init_params(config, jax.random.PRNGKey(0)), config)
    eng = make_engine(model)
    nb = eng._alloc.num_blocks
    plan, tables, samplers = wide_plan(nb, 10)
    _, pool = paged_step(model, eng.pool, context_plan(nb), tables, None,
                           False)
    every, _ = paged_step(model, pool, plan, tables, None, False)
    got, _ = paged_step(model, pool, plan, tables, jnp.asarray(samplers),
                          False)
    gap = np.abs(np.asarray(got) - np.asarray(every)[
        np.minimum(samplers, WIDE - 1)]).max(-1)
    assert (gap[:3] < 1e-5).all() and gap[3] > 1e-3


SOLAR_PERIOD = tiny_solar_open2_test().layer_types


@pytest.mark.parametrize("layer_types, cut", [
    (sambay_layer_types(32), 2), (sambay_layer_types(8), 2),
    # a pattern that ends in a kind that writes is never cut
    (SOLAR_PERIOD, len(SOLAR_PERIOD)),
    (((("mamba", "full"), 1), (("mamba", "cross"), 3)), 2),
    (((("mamba", "full"), 1), (("gmu", "cross"), 2), (("mamba", "cross"), 1)),
     3),
    # segments that hold nothing count from the LAST one that writes
    (((("mamba", "full"), 1), (("gmu",), 2), (("cross", "gmu"), 1)), 1),
    ((), 0)])
def test_only_trailing_segments_that_hold_nothing_are_cut(layer_types, cut):
    c = dataclasses.replace(tiny_phi4flash_test(), layer_types=layer_types)
    assert c.readers_from == cut


def test_a_segment_that_writes_is_refused_over_gathered_entries(monkeypatch):
    """Were the rule ever to name a segment that writes (here: forced to
    cut at SambaY's middle pair, a mixer and the full layer), the forward
    raises while it traces: no program that skips a cache row exists."""
    config = tiny_phi4flash_test()
    model = (init_params(config, jax.random.PRNGKey(0)), config)
    eng = make_engine(model)
    plan, tables, samplers = wide_plan(eng._alloc.num_blocks)
    monkeypatch.setattr(ModelConfig, "readers_from",
                        property(lambda self: 1))
    with pytest.raises(ValueError, match="writes its cache"):
        paged_step(model, eng.pool, plan, tables, jnp.asarray(samplers),
                     False)


# ---- (2) the engine, greedy, through chunked prefill ----------------------

PROMPTS = ([5, 9, 2, 7, 1, 3, 8, 4, 6, 2, 9, 1, 7, 3, 5, 8, 2, 4, 6, 1, 3, 5],
           [11, 3, 8, 1, 4], [2, 6, 4, 9, 9, 1, 2], [1, 2, 3])


def serve(eng, new_tokens=6):
    rids = [eng.submit(p, max_new_tokens=new_tokens) for p in PROMPTS]
    while eng.has_work:
        eng.step()
    return [(eng.result(r), eng.result_logps(r)) for r in rids]


def every_entry(monkeypatch):
    """The parent's path: every step keeps every entry's head."""
    fn = engine_mod._paged_fused_step
    monkeypatch.setattr(
        engine_mod, "_paged_fused_step",
        lambda *a, **kw: fn(*a, **{**kw, "all_logits": True}))


def test_a_greedy_engine_serves_what_the_every_entry_path_serves(
        model, monkeypatch):
    got = serve(make_engine(model))
    every_entry(monkeypatch)
    want = serve(make_engine(model))
    assert [t for t, _ in got] == [t for t, _ in want]
    for (_, a), (_, b) in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    assert all(len(t) == 6 for t, _ in got)


def test_a_wide_steps_samples_are_draws_from_each_samplers_nucleus():
    """T 0.8, top-p 0.95, 24 keys on the hand-made plan: every sample at
    a sampler entry lies in the 128-candidate nucleus of THAT entry's
    every-entry logits (what the parent could have drawn there, a quarter
    of this vocabulary), its log-prob is the full-vocabulary log-softmax
    of those logits, and the keys do not all draw the same."""
    config = tiny_test()
    model = (init_params(config, jax.random.PRNGKey(0)), config)
    eng = make_engine(model)
    plan, tables, samplers = wide_plan(eng._alloc.num_blocks)
    put = samplers[samplers < WIDE]
    logits = paged_logits(model, eng, plan, tables, None)[put]
    logz = np.asarray(jax.nn.log_softmax(jnp.asarray(logits), axis=-1))
    probs = np.asarray(jax.nn.softmax(jnp.asarray(logits) / 0.8, axis=-1))
    # the sampler's nucleus: of the 128 likeliest, those before 0.95
    order = np.argsort(-probs, axis=-1)[:, :128]
    ranked = np.take_along_axis(probs, order, axis=-1)
    inside = (np.cumsum(ranked, axis=-1) - ranked) < 0.95
    nucleus = [set(order[i][inside[i]].tolist()) for i in range(len(put))]
    assert all(1 < len(n) <= 128 for n in nucleus)
    pool, cur, drawn = eng.pool, jnp.zeros((ROWS,), jnp.int32), set()
    for k in range(24):
        toks, logp, pool, _key, cur = engine_mod._paged_fused_step(
            model[0], config, plan, tables, pool, jax.random.PRNGKey(k),
            cur, SAMPLED, False)
        toks, logp = np.asarray(toks), np.asarray(logp)
        for i, e in enumerate(put):
            assert int(toks[e]) in nucleus[i]
            np.testing.assert_allclose(logp[e], logz[i, toks[e]],
                                       rtol=1e-5, atol=1e-6)
        drawn.add(tuple(toks[put].tolist()))
    assert len(drawn) > 12


# ---- (3) what the program is ---------------------------------------------

def step_closed_jaxpr(model, width, all_logits):
    params, config = model
    eng = make_engine(model)
    plan = jnp.zeros((6, width), jnp.int32)
    tables = jnp.zeros((ROWS, 4), jnp.int32)
    return jax.make_jaxpr(
        lambda p, pool, key, cur: engine_mod._paged_fused_step._fn(
            p, config, plan, tables, pool, key, cur, SAMPLED, False,
            all_logits=all_logits))(
                params, eng.pool, jax.random.PRNGKey(1),
                jnp.zeros((ROWS,), jnp.int32))


def step_jaxpr(model, width, all_logits):
    return str(step_closed_jaxpr(model, width, all_logits))


def test_a_narrow_step_takes_no_gather_and_a_wide_one_pays_rows(model):
    """At ``T == num_slots`` the step is its every-entry form, operation
    for operation; at ``T > num_slots`` the vocabulary-wide values are
    ``(num_slots, V)`` and none is ``(T, V)``."""
    assert step_jaxpr(model, ROWS, False) == step_jaxpr(model, ROWS, True)
    vocab = model[1].vocab_size
    wide, every = (step_jaxpr(model, WIDE, a) for a in (False, True))
    assert f"[{WIDE},{vocab}]" in every and f"[{ROWS},{vocab}]" not in every
    assert f"[{ROWS},{vocab}]" in wide and f"[{WIDE},{vocab}]" not in wide
    assert f"[{WIDE},1,{vocab}]" not in wide


def stream_rows(config, width, all_logits):
    """The leading axis of the stream ``(rows, 1, hidden)`` in the carry of
    each of the step's layer scans, in program order."""
    jaxpr = step_closed_jaxpr(
        (init_params(config, jax.random.PRNGKey(0)), config), width,
        all_logits)
    found = []

    def walk(jpr):
        for eqn in jpr.eqns:
            if eqn.primitive.name == "scan":
                found.extend(
                    v.aval.shape[0] for v in eqn.invars
                    if getattr(v.aval, "shape", ())[1:] == (
                        1, config.hidden_size))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return found


def test_a_sambay_wide_step_holds_its_cross_decoder_at_the_rows():
    """The tiny SambaY preset's three scans (window pairs, the middle pair,
    the cross pair): in a wide step the last one carries the stream of the
    4 rows' samplers, the two that write all 16 entries; the every-entry
    program and the narrow step carry one width throughout. A pattern that
    ends in a kind that writes (the delta rule's one scan) keeps all 16."""
    c = tiny_phi4flash_test()
    assert stream_rows(c, WIDE, False) == [WIDE, WIDE, ROWS]
    assert stream_rows(c, WIDE, True) == [WIDE] * 3
    assert stream_rows(c, ROWS, False) == [ROWS] * 3
    solar = tiny_solar_open2_test()
    assert set(stream_rows(solar, WIDE, False)) == {WIDE}


@pytest.mark.parametrize("entries, rows, all_logits, paid", [
    (48, 48, False, 48), (192, 48, False, 48), (192, 48, True, 192),
    (96, 48, True, 96), (16, 48, False, 16), (4, 4, True, 4)])
def test_head_entries_by_width_and_verify(entries, rows, all_logits, paid):
    assert _head_entries(entries, rows, all_logits) == paid


# ---- (4) the span, the counter, the ladder --------------------------------

def mixed_run(vocab_size):
    """``serve`` on a model with a vocabulary of its own (a cold jit
    cache) under tracing -> (the engine, its ``engine.step`` attrs, the
    fused step's compiles)."""
    config = dataclasses.replace(tiny_test(), vocab_size=vocab_size)
    obs.enable()
    eng = make_engine((init_params(config, jax.random.PRNGKey(0)), config))
    serve(eng)
    steps = [s.attrs for s in obs.get_tracer().spans()
             if s.name == "engine.step" and "entries" in s.attrs]
    return eng, steps, fused_compiles()


def fused_compiles():
    return int(get_profiler().ledger()["engine.fused_step"]["compiles"])


def test_the_span_and_the_counter_say_what_the_head_ran_over():
    _eng, steps, _ = mixed_run(103)
    assert {a["entries"] for a in steps} == {ROWS, WIDE}
    assert all(a["head_entries"] == ROWS for a in steps)
    total = obs.get_registry().get("senweaver_engine_head_entries_total")
    assert total.value() == ROWS * len(steps)
    assert sum(a["entries"] for a in steps) > total.value()


def test_the_wide_program_replaced_the_one_it_was(monkeypatch):
    """The fused step's ledger after a mixed run (chunked prefill, decode
    rows, both widths) has as many compiled signatures as the every-entry
    path's after the same run, and a second pass adds none."""
    eng, steps, compiles = mixed_run(107)
    assert {a["entries"] for a in steps} == {ROWS, WIDE}
    serve(eng)
    assert fused_compiles() == compiles
    obs._reset_for_tests()
    every_entry(monkeypatch)
    _eng, steps_every, compiles_every = mixed_run(109)
    assert len(steps_every) == len(steps)
    assert compiles == compiles_every


def test_a_plan_with_verify_entries_keeps_every_entrys_head(monkeypatch):
    config = tiny_test()
    params = init_params(config, jax.random.PRNGKey(0))
    draft_cfg = dataclasses.replace(config, name="tiny-draft")
    draft = init_params(draft_cfg, jax.random.PRNGKey(1))
    calls = []
    fn = engine_mod._paged_fused_step

    def spy(*args, **kwargs):
        calls.append((args[2].shape[1], kwargs["all_logits"]))
        return fn(*args, **kwargs)

    monkeypatch.setattr(engine_mod, "_paged_fused_step", spy)
    obs.enable()
    eng = RolloutEngine(params, config, num_slots=2, max_len=96,
                        sample=GREEDY, engine_config=EngineConfig(
                            kv_layout="paged", block_size=4))
    eng.enable_speculation(draft, draft_cfg, depth=4)
    rids = [eng.submit(p, max_new_tokens=10) for p in PROMPTS[1:3]]
    eng.run()
    assert eng.spec_stats()["rounds"] > 0
    steps = [s.attrs for s in obs.get_tracer().spans()
             if s.name == "engine.step" and "entries" in s.attrs]
    verify = [a for a, (_w, every) in zip(steps, calls) if every]
    assert verify and all(a["head_entries"] == a["entries"] > 2
                          for a in verify)
    assert all(a["head_entries"] == 2 for a, (_w, every)
               in zip(steps, calls) if not every)
    assert all(len(eng.result(r)) == 10 for r in rids)
