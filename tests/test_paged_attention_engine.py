"""The paged-attention kernels through ``forward_paged`` and the engine
(interpreted here): the kernel path against the gather path and against
teacher forcing, for a dense GQA pool and a latent one, and the table's
width where the kernel reads the pool. The kernels alone:
``tests/test_paged_attention.py`` (one file a worker: split for time)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from senweaver_ide_tpu.models import transformer as tf
from senweaver_ide_tpu.models.config import get_config
from senweaver_ide_tpu.rollout.engine import EngineConfig, RolloutEngine
from senweaver_ide_tpu.rollout.paged_kv import init_paged_pool
from senweaver_ide_tpu.rollout.sampler import SampleParams

# the dense GQA model and the latent-attention one (GLM-4.7-Flash's layer
# at test size: its pool has one payload leaf), both f32 at ``highest``
MODELS = ["tiny-test", "tiny-glm-moe-test"]


@pytest.mark.parametrize("model", MODELS)
def test_forward_paged_kernel_matches_gather_prefill_then_decode(model):
    """Chunked prefill of two rows, then a decode step at every position
    up to the table's end (with a dropped write riding each step): the
    kernel path's logits and pool equal the gather path's to f32
    rounding."""
    c = get_config(model)
    params = tf.init_params(c, jax.random.PRNGKey(0))
    bs, mb = 4, 6
    tables = jnp.asarray([[3, 8, 1, 10, 5, 7], [2, 9, 4, 11, 0, 6]],
                         jnp.int32)
    nb = 12
    run = jax.jit(tf.forward_paged,
                  static_argnames=("config", "use_kernel"))
    pools = {uk: init_paged_pool(c, nb, bs) for uk in (False, True)}
    toks = np.random.default_rng(1).integers(1, c.vocab_size, (2, bs * mb))

    def step(seq_row, positions, drop):
        seq_row, positions = np.asarray(seq_row), np.asarray(positions)
        block = np.asarray(tables)[seq_row, positions // bs]
        batch = dict(
            tokens=jnp.asarray(toks[seq_row, positions], jnp.int32),
            tables=tables, seq_row=jnp.asarray(seq_row, jnp.int32),
            positions=jnp.asarray(positions, jnp.int32),
            write_block=jnp.asarray(np.where(drop, nb, block), jnp.int32),
            write_off=jnp.asarray(positions % bs, jnp.int32))
        out = {}
        for uk in (False, True):
            out[uk], pools[uk] = run(params, config=c, pool=pools[uk],
                                     use_kernel=uk, **batch)
        live = ~np.asarray(drop)
        np.testing.assert_allclose(np.asarray(out[True])[live],
                                   np.asarray(out[False])[live],
                                   atol=1e-5, rtol=1e-5)
        for a, b in zip(pools[True], pools[False]):
            if a is not None:
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-5, rtol=1e-5)

    # prefill: row 0 takes 7 then 4 tokens, row 1 takes 5 beside them
    step([0] * 7 + [1] * 2, list(range(7)) + [0, 1], [False] * 9)
    step([0] * 4 + [1] * 3, list(range(7, 11)) + [2, 3, 4], [False] * 7)
    # decode: both rows a step, and a padding entry on the drop sentinel
    for i in range(bs * mb - 11):
        step([0, 1, 0], [11 + i, 5 + i, 0], [False, False, True])


@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("sample", ["greedy", "sampled"])
def test_engine_logps_with_the_kernel_equal_teacher_forcing(sample, model):
    """``paged_kernel=True`` through the engine (interpreted here):
    chunked prefill, decode rows and a forked group, each served token's
    log p against the teacher-forced ``forward``."""
    c = get_config(model)
    params = tf.init_params(c, jax.random.PRNGKey(2))
    eng = RolloutEngine(
        params, c, num_slots=4, max_len=64, seed=3,
        sample=SampleParams(temperature=0.0 if sample == "greedy" else 1.0),
        engine_config=EngineConfig(paged_kernel=True, step_tokens=8))
    assert eng.kv_layout == "paged"
    prompts = [list(range(1, 14)), [7, 7, 7]]
    rids = [eng.submit(p, max_new_tokens=9) for p in prompts]
    group_prompt = list(range(30, 51))
    rids += eng.submit_group(group_prompt, 2, max_new_tokens=6)
    prompts += [group_prompt] * 2
    eng.run()
    for p, rid in zip(prompts, rids):
        out = eng.result(rid)
        seq = jnp.asarray([p + out], jnp.int32)
        logits = tf.forward(params, c, seq)[0]
        logp = jax.nn.log_softmax(logits[0].astype(jnp.float32), axis=-1)
        want = [float(logp[len(p) - 1 + i, tok])
                for i, tok in enumerate(out)]
        np.testing.assert_allclose(eng.result_logps(rid), want, atol=2e-4)
    eng._alloc.check_leaks()


@pytest.mark.parametrize("model,paged_kernel,widths", [
    ("tiny-test", True, {8}), ("tiny-test", None, {1, 2, 4, 8}),
    ("tiny-glm-moe-test", True, {8}),
    ("tiny-glm-moe-test", False, {1, 2, 4, 8})])
def test_table_keeps_one_width_where_the_kernel_reads_the_pool(
        model, paged_kernel, widths):
    """The gather's cost follows the table's width, so the table is cut
    to a ladder of widths, a compiled program each; the kernel reads a
    row's live blocks whatever the width, so there the table stays
    ``blocks_per_row`` wide and the step has one shape a batch width: a
    dense pool's and a latent pool's alike. ``engine.step``'s
    ``table_width`` is the width handed over and ``kv_blocks`` what the
    step's attention had to cover, the same on either path: the share of
    ``entries x table_width`` that the gather would copy for nothing."""
    from senweaver_ide_tpu import obs
    c = get_config(model)
    params = tf.init_params(c, jax.random.PRNGKey(2))
    eng = RolloutEngine(
        params, c, num_slots=2, max_len=32, seed=3,
        engine_config=EngineConfig(paged_kernel=paged_kernel, block_size=4,
                                   step_tokens=8))
    obs._reset_for_tests()
    obs.enable()
    try:
        eng.submit(list(range(1, 6)), max_new_tokens=24)
        seen = set()
        while eng.has_work:
            seen.add(eng._tables_device().shape[1])
            eng.step()
        steps = [s.attrs for s in obs.get_tracer().spans()
                 if s.name == "engine.step"]
    finally:
        obs._reset_for_tests()
    # (before the first step no block is held yet: a width of 1 that no
    # step runs at)
    assert seen == widths
    assert {x["table_width"] for x in steps} == widths - {1}
    # a chunk of 5 tokens (2 blocks), then a decode row at positions 5..27;
    # every step has tail padding, one block more
    assert [x["kv_blocks"] for x in steps] == [3] + [
        p // 4 + 2 for p in range(5, 28)]
    assert all(x["kv_blocks"] <= x["entries"] * x["table_width"]
               for x in steps)


# ---- the block's size changes where a token's KV lies, not one value -----

@functools.lru_cache(maxsize=None)
def _served_at(model, block_size):
    """What a greedy engine serves at one block size: two lone prompts
    through 8-entry prefill chunks, a group of three whose 22-token prompt
    ends inside a block at every size (5 blocks and 2 tokens at 4, 1 and 6
    at 16, 22 into the first at 64; a state-space model forks a token
    earlier, inside a block too), so each follower's first write copies
    the boundary block, then a follower and a lone request preempted in
    mid-decode and recomputed. ``(tokens, log-probs)`` a request."""
    c = get_config(model)
    params = tf.init_params(c, jax.random.PRNGKey(2))
    eng = RolloutEngine(
        params, c, num_slots=6, max_len=128, seed=3,
        sample=SampleParams(temperature=0.0),
        engine_config=EngineConfig(block_size=block_size, step_tokens=8))
    assert eng.kv_layout == "paged"
    assert eng.engine_config.block_size == eng.pool.block_size == block_size
    rids = [eng.submit(p, max_new_tokens=9)
            for p in (list(range(1, 14)), [7, 7, 7])]
    rids += eng.submit_group(list(range(30, 52)), 3, max_new_tokens=8)
    while len(eng._requests[rids[-1]].tokens) < 3:
        eng.step()
    with eng._lock:
        eng._preempt_row(eng._requests[rids[-1]].slot)
        eng._preempt_row(eng._requests[rids[0]].slot)
    eng.run()
    st = eng.stats()
    assert st["kv_preemptions"] == 2 and st["group_forks"] >= 1
    assert st["kv_cow_copies"] >= 2
    eng._alloc.check_leaks()
    return [(eng.result(r), eng.result_logps(r)) for r in rids]


@pytest.mark.parametrize("block_size", [16, 64])
@pytest.mark.parametrize("model", MODELS + ["tiny-falcon-h1-test"])
def test_a_larger_block_serves_the_same_tokens(model, block_size):
    """The dense, the latent and the hybrid model at blocks of 16 and 64
    against blocks of 4 (off the TPU: the gather path): the same greedy
    tokens, the same log-probs to rounding, for the lone requests, the
    forked group and the preempted rows."""
    want, got = _served_at(model, 4), _served_at(model, block_size)
    assert [t for t, _ in got] == [t for t, _ in want]
    for (_, a), (_, b) in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5)


# ---- a group's shared blocks are attended once (ops/paged_attention) -----

def _group_of_8(model, paged_kernel):
    """A ``submit_group`` of 8 over a 22-token prompt (5 full blocks of 4
    and 2 tokens: every member's first write copies the boundary block) on
    an engine of 8 rows, so it is saturated and runs ahead once the
    followers hold their rows. Greedy. ``(tokens, log-probs)`` a member,
    the ``engine.step`` spans' attrs and the counter."""
    from senweaver_ide_tpu import obs
    c = get_config(model)
    params = tf.init_params(c, jax.random.PRNGKey(2))
    obs._reset_for_tests()
    obs.enable()
    try:
        eng = RolloutEngine(
            params, c, num_slots=8, max_len=64, seed=3,
            sample=SampleParams(temperature=0.0),
            engine_config=EngineConfig(paged_kernel=paged_kernel,
                                       block_size=4, step_tokens=16))
        rids = eng.submit_group(list(range(30, 52)), 8, max_new_tokens=7)
        eng.run()
        steps = [dict(s.attrs) for s in obs.get_tracer().spans()
                 if s.name == "engine.step" and "entries" in s.attrs]
        counter = obs.get_registry().get(
            "senweaver_engine_kv_blocks_shared_total").value()
    finally:
        obs._reset_for_tests()
    assert eng.stats()["group_forks"] == 7
    eng._alloc.check_leaks()
    return ([(eng.result(r), eng.result_logps(r)) for r in rids], steps,
            counter)


@pytest.mark.parametrize("model", MODELS + ["tiny-falcon-h1-test"])
def test_a_group_of_8_attends_its_prompt_once(model):
    """The dense, the latent and the hybrid model: with the kernel on, the
    group's eight decode rows attend the prompt's five shared blocks in
    ONE group item (35 block reads a step not made) and serve the tokens
    and log-probs of the gather path; the two counts are on the span that
    LAUNCHED the step, under run-ahead too, and feed the counter."""
    got, steps, counter = _group_of_8(model, True)
    want, plain, none = _group_of_8(model, False)
    assert [t for t, _ in got] == [t for t, _ in want]
    for (_, a), (_, b) in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-4)
    # the gather path plans nothing and reports zeros
    assert none == 0 and all(
        (x["kv_blocks_saved"], x["attn_group_items"]) == (0, 0)
        for x in plain)
    assert len(steps) == len(plain)
    shared = [x for x in steps if x["attn_group_items"]]
    # all eight decoding: one group item over the prompt's five full
    # blocks; never more saved than covered
    assert {(x["attn_group_items"], x["kv_blocks_saved"])
            for x in shared if x["decode_rows"] == 8} == {(1, 35)}
    assert all(x["kv_blocks_saved"] < x["kv_blocks"] for x in steps)
    assert any(x["ahead"] for x in shared)
    assert counter == sum(x["kv_blocks_saved"] for x in steps) > 0
