"""The paged step's launch path: between two fused steps the host enqueues
ONE program and waits on ONE transfer. The key is split inside the jit
(the parent's stream, to the bit), the wrapper neither blocks nor scans the
pool, the tokens' copy is asked for at launch, the plan's six vectors
enter as one array, and a copy-on-write waits for nothing either. Counted here on the CPU; what it is worth in time only
the chip says (PERF.md §6, PR 31)."""

import dataclasses

import jax
import numpy as np
import pytest

from senweaver_ide_tpu import obs
from senweaver_ide_tpu.models import init_params, tiny_test
from senweaver_ide_tpu.models.config import tiny_glm_moe_test
from senweaver_ide_tpu.obs.runtime_profile import get_profiler
from senweaver_ide_tpu.rollout import EngineConfig, RolloutEngine
from senweaver_ide_tpu.rollout import engine as engine_mod
from senweaver_ide_tpu.rollout.sampler import SampleParams

SAMPLED = SampleParams(temperature=1.0, top_k=0, top_p=1.0)
SEED = 31
STEPS = 20
PROMPTS = ([5, 9, 2, 7, 1, 3, 8, 4, 6, 2, 9, 1, 7, 3, 5, 8, 2, 4, 6, 1,
            3, 5], [11, 3, 8, 1, 4], [2, 6, 4, 9, 9, 1, 2])
MODELS = {"dense": tiny_test, "latent-moe": tiny_glm_moe_test}


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs._reset_for_tests()
    yield
    obs._reset_for_tests()


@pytest.fixture(scope="module", params=list(MODELS))
def model(request):
    config = MODELS[request.param]()
    return init_params(config, jax.random.PRNGKey(0)), config


def make_engine(model, seed=SEED):
    params, config = model
    return RolloutEngine(
        params, config, num_slots=4, max_len=64, sample=SAMPLED, seed=seed,
        engine_config=EngineConfig(kv_layout="paged", block_size=4,
                                   step_tokens=16))


def drive(eng, steps=STEPS):
    """Three requests and a group of three, ``steps`` paged steps: chunked
    prefill (one prompt is longer than a step's budget), decode rows and a
    group fork all pass. Returns the rids."""
    rids = [eng.submit(p, max_new_tokens=24) for p in PROMPTS]
    rids += eng.submit_group([3, 4, 5, 6, 7, 8], 3, max_new_tokens=24)
    for _ in range(steps):
        assert eng.has_work
        eng.step()
    assert eng.stats()["decode_steps"] == steps
    return rids


def test_a_step_splits_no_key_on_the_host_and_blocks_once(model,
                                                          monkeypatch):
    """Over 20 steps: no ``jax.random.split`` of a concrete key (the one
    inside the jit runs on tracers, while a shape compiles), no
    ``jax.block_until_ready``, and one ``device_get`` a step, counted by
    ``senweaver_engine_step_host_syncs_total`` too. Three requests on four
    rows: an under-loaded engine fetches every step in the call that
    launched it (tests/test_engine_run_ahead.py has the saturated one)."""
    host_splits, blocks, gets = [], [], []
    split, ready, get = (jax.random.split, jax.block_until_ready,
                         jax.device_get)

    def counted_split(key, *a, **kw):
        if not isinstance(key, jax.core.Tracer):
            host_splits.append(key)
        return split(key, *a, **kw)

    eng = make_engine(model)
    monkeypatch.setattr(jax.random, "split", counted_split)
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: blocks.append(1) or ready(x))
    monkeypatch.setattr(jax, "device_get",
                        lambda x: gets.append(1) or get(x))
    rids = [eng.submit(p, max_new_tokens=24) for p in PROMPTS]
    for _ in range(STEPS):
        before = len(gets)
        eng.step()
        assert len(gets) - before == 1
    assert host_splits == [] and blocks == []
    assert len(gets) == STEPS == eng.stats()["decode_steps"]
    assert (f"senweaver_engine_step_host_syncs_total {STEPS}"
            in obs.get_registry().render())
    assert all(eng.result(r) for r in rids)


def test_the_key_after_n_steps_is_the_nth_host_side_split(model):
    """The stream is the parent's: it split ``self._key`` on the host
    before every step and kept the first half."""
    eng = make_engine(model)
    key = jax.random.PRNGKey(SEED)
    assert np.array_equal(np.asarray(eng._key), np.asarray(key))
    drive(eng)
    for _ in range(STEPS):
        key, _step_key = jax.random.split(key)
    assert isinstance(eng._key, jax.Array)
    assert np.array_equal(np.asarray(eng._key), np.asarray(key))


def test_a_steps_tokens_are_sampled_with_the_second_half_of_the_split(
        model, monkeypatch):
    """One step by hand, in the parent's form: the host splits, the step
    key samples. The engine's first step gives the same tokens and
    log-probs. That step is a WIDE one (a 5-token prompt in 16 entries on
    4 rows), so since PR 40 the hand's sampler runs over the rows'
    sampler entries as the step's does (one a row, the clamped last entry
    for a row with none): the noise is drawn at ``(num_slots, V)``, and
    the pin moved with it (``tests/test_head_entries.py`` holds the
    logits to the every-entry head's)."""
    eng, ref = make_engine(model), make_engine(model)
    for e in (eng, ref):
        e.submit(PROMPTS[1], max_new_tokens=4)
    seen = {}
    launch = engine_mod.RolloutEngine._launch_paged

    def spy(self, span, vectors, tables, *rest):
        seen["plan"], seen["tables"] = vectors, tables
        out = launch(self, span, vectors, tables, *rest)
        seen["out"] = [np.asarray(a) for a in out]
        return out

    monkeypatch.setattr(engine_mod.RolloutEngine, "_launch_paged", spy)
    eng.step()
    params, config = model
    next_key, step_key = jax.random.split(jax.random.PRNGKey(SEED))
    tokens, seq_row, positions, write_block, write_off, feed = seen["plan"]
    assert not (feed & engine_mod.FEED_TAKE).any()      # host-known tokens
    n = tokens.shape[0]
    assert n > eng.num_slots
    (put,) = np.flatnonzero(feed & engine_mod.FEED_PUT)
    samplers = np.full((eng.num_slots,), n, np.int32)
    samplers[seq_row[put]] = put
    logits, _pool, *_ = engine_mod.forward_paged(
        params, config, tokens, pool=ref.pool, tables=seen["tables"],
        seq_row=seq_row, positions=positions, write_block=write_block,
        write_off=write_off, use_kernel=ref._use_paged_kernel)
    logits = logits[np.minimum(samplers, n - 1)]
    tok = engine_mod.sample_token(logits, step_key, temperature=1.0,
                                  top_k=0, top_p=1.0)
    logp = engine_mod.sampled_logprob(logits, tok)
    row = seq_row[put]
    assert seen["out"][0][put] == int(tok[row])
    np.testing.assert_allclose(seen["out"][1][put], float(logp[row]),
                               rtol=1e-5, atol=1e-6)
    rest = np.delete(np.arange(n), put)
    assert not seen["out"][0][rest].any() and not seen["out"][1][rest].any()
    assert eng.result(0) == [int(tok[row])]
    assert np.array_equal(np.asarray(eng._key), np.asarray(next_key))


def test_two_engines_of_one_seed_emit_equal_tokens_and_logps(model):
    outs = []
    for _ in range(2):
        eng = make_engine(model)
        rids = drive(eng)
        outs.append([(eng.result(r), eng.result_logps(r)) for r in rids])
    assert outs[0] == outs[1]
    assert any(len(set(t)) > 1 for t, _ in outs[0])
    other = make_engine(model, seed=SEED + 1)
    rids = drive(other)
    assert [other.result(r) for r in rids] != [t for t, _ in outs[0]]


def test_launch_says_its_host_arrays_and_fetch_its_wait(model):
    eng = make_engine(model)
    obs.enable()
    drive(eng)
    spans = obs.get_tracer().spans()
    launches = [s for s in spans if s.name == "engine.launch"]
    fetches = [s for s in spans if s.name == "engine.fetch"]
    # six requests on four rows: the last step launched is still in flight
    assert eng._flying is not None
    assert len(launches) == len(fetches) + 1 == STEPS
    assert [s.attrs["of_step"] for s in fetches] == list(range(STEPS - 1))
    assert all(s.attrs["host_arrays"] == 2 for s in launches)
    for s in fetches:
        assert 0.0 <= s.attrs["wait_ms"] <= s.duration_ms
        assert s.attrs["bytes"] > 0
    # the wrapped call is launch's one child; nothing waits under it
    kids = {s.name for s in spans
            if s.parent_id in {p.span_id for p in launches}}
    assert kids == {"engine.fused_step.dispatch"}
    assert not any(s.name == "engine.fused_step.wait" for s in spans)


def test_the_plan_enters_as_one_array_beside_the_table(model, monkeypatch):
    """What the jit is handed: two host arrays (the ``(6, T)`` plan, the
    table); params, pool, key and the rows' current tokens are the
    device's."""
    calls = []
    fn = engine_mod._paged_fused_step

    def spy(*args, **kwargs):
        calls.append((args, kwargs))
        return fn(*args, **kwargs)

    monkeypatch.setattr(engine_mod, "_paged_fused_step", spy)
    eng = make_engine(model)
    drive(eng, steps=6)
    widths = set()
    for args, kwargs in calls:
        host = [a for a in jax.tree_util.tree_leaves((args, kwargs))
                if isinstance(a, np.ndarray)]
        assert len(host) == 2
        plan, tables = args[2], args[3]
        assert plan.dtype == np.int32 and plan.shape[0] == 6
        assert tables.shape[0] == eng.num_slots
        assert isinstance(args[5], jax.Array)           # the key
        assert isinstance(args[6], jax.Array)           # the rows' tokens
        assert args[6].shape == (eng.num_slots,)
        widths.add(plan.shape[1])
    assert widths == {eng.num_slots, 16}                 # the same two


def test_the_ledger_counts_one_compile_a_new_shape_without_blocking():
    """``window_compiles`` reads ``compiles`` of the ``engine.fused_step``
    ledger: with ``block=False`` and the pool out of the scan it still
    counts exactly one a new shape (through the jit's cache size), none on
    a repeat, and ``step_ms`` is the engine's own launch-to-fetch time. A
    vocabulary of its own keeps this test's jit cache cold."""
    config = dataclasses.replace(tiny_test(), vocab_size=101)
    model = (init_params(config, jax.random.PRNGKey(0)), config)
    assert engine_mod._paged_fused_step.block is False
    assert set(engine_mod._paged_fused_step.skip_args) == {0, 1, 4, 5, 6}
    prof = get_profiler()
    obs.enable()
    eng = make_engine(model)
    drive(eng)
    snap = prof.ledger()["engine.fused_step"]
    shapes = {(s.attrs["entries"], s.attrs["table_width"])
              for s in obs.get_tracer().spans() if s.name == "engine.step"}
    assert len(shapes) >= 3
    assert snap["blocking"] is False
    assert snap["calls"] == STEPS
    assert snap["compiles"] == len(snap["signatures"]) == len(shapes)
    assert all(s["compiles"] == 1 for s in snap["signatures"])
    # the step as the host saw it: the engine's own launch-to-fetch time,
    # one observation a fetched step, nothing of the wrapper's dispatch
    # beside it. A saturated engine has up to two steps in flight at a
    # time, the one that runs and the one queued behind it.
    spans = obs.get_tracer().spans()
    lo = sum(s.duration_ms for s in spans
             if s.name in ("engine.launch", "engine.fetch"))
    steps = [s for s in spans if s.name == "engine.step"]
    hi = 2 * (steps[-1].end_ns - steps[0].start_ns) / 1e6
    assert lo <= snap["step_ms_sum"] <= hi
    hist = obs.get_registry().get("senweaver_runtime_step_ms").snapshot(
        fn="engine.fused_step")
    assert eng._flying is not None
    assert hist["count"] == STEPS - 1
    assert hist["sum"] == pytest.approx(snap["step_ms_sum"], abs=1e-2)
    drive(make_engine(model))                   # the same shapes again
    again = prof.ledger()["engine.fused_step"]
    assert again["compiles"] == snap["compiles"]
    assert again["calls"] == 2 * STEPS


def test_a_copy_on_write_waits_for_nothing_and_hands_host_ids(model,
                                                              monkeypatch):
    """A follower's first write splits the block it shares
    (``_ensure_block``): ``paged_kv.copy`` is wrapped ``block=False`` with
    the pool out of the signature scan, as ``copy_state_rows`` is, and its
    two block ids enter as numpy, not as two dispatches of their own. A
    blocking copy on the pool would wait for the step in flight and turn
    every forking step back into a serial one."""
    assert engine_mod.copy_blocks.block is False
    assert engine_mod.copy_blocks.skip_args == (0,)
    calls, blocks, made = [], [], []
    copy, ready, asarray = (engine_mod.copy_blocks, jax.block_until_ready,
                            engine_mod.jnp.asarray)
    monkeypatch.setattr(engine_mod, "copy_blocks",
                        lambda *a: calls.append(a[1:]) or copy(*a))
    eng = make_engine(model)
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: blocks.append(1) or ready(x))
    monkeypatch.setattr(engine_mod.jnp, "asarray",
                        lambda *a, **kw: made.append(a) or asarray(*a, **kw))
    drive(eng)
    assert len(calls) == eng._alloc.counters()["cow_copies"] > 0
    for src, dst in calls:
        for ids in (src, dst):
            assert isinstance(ids, np.ndarray)
            assert ids.dtype == np.int32 and ids.shape == (1,)
    assert blocks == [] and made == []


def test_the_hot_frames_keep_the_size_set_up_was_measured_at():
    """CPython 3.12 keeps frames in 16 KiB chunks, and JAX's lowering slows
    by a quarter to a third when the frames from ``step()`` down to the
    jitted call leave a hot call astride a chunk's edge: at 60 slots for
    these two frames the warm-up took 2.75 s in the qwen cells and 4.77 s
    in glm, at the 67 they had before PR 31 it takes 2.23 and 3.88 (my
    chip runs, PERF.md §6, PR 31). PR 36 rewrote both frames (the step
    runs ahead: ``_step_paged`` 30 locals + 12 of stack, ``_launch_paged``
    11 + 14 with two unused locals) and kept the sum: warm, 25 engine
    steps of the qwen grpo cell took 2.36 / 2.38 / 2.54 s against the
    parent's 2.23 / 2.37 / 2.28 / 2.27 / 2.29, chat-open's 13 steps 2.34
    / 2.49 against 2.28 / 2.38, glm's 55 steps 3.51 against 3.59 / 3.67
    (my chip runs, one call a cell, PERF.md §6, PR 36: the qwen cells
    +0.1 s, inside the parent's own range of PR 35, 2.26-2.40; glm
    none). The first run of every shape compiles anew (18-19.7 s at 65
    to 73 slots, the least at 67).
    A change here is a new draw: measure warm ``setup_s`` on the chip,
    parent against change, and move this number with it."""
    slots = sum(f.__code__.co_nlocals + f.__code__.co_stacksize
                for f in (RolloutEngine._step_paged,
                          RolloutEngine._launch_paged))
    assert slots == 67
