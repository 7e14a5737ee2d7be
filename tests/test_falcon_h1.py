"""Falcon-H1's block (``falcon_h1``) through the program at test size: a
Mamba-2 state-space mixer beside GQA attention on one normed input
(``models.transformer._mixers``; ``ops/ssm.py`` itself is held to its
invariants in ``tests/test_ssm_ops.py``), the pool's row-addressed
state beside its KV blocks (``rollout.paged_kv.StateRows``), the engine's
group fork by state copy, and the twelve multipliers — against the plain
reference the benchmark's output check uses
(``benchmark/reference/falcon_h1.py``: float32, the mixer one token at a
time, nothing of the program imported).

The tiny preset is the architecture map of a published-key dict, all
multipliers away from 1. Everything float32 at ``highest``. The weights are
``init_params``' (Mamba-2's own start: a token decays a head's state by
exp(dt A) in [0.2, 0.999], so the state carries over the whole sequence;
the benchmark's seeded weights decay by about half a token, PERF.md §7).
"""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.archs.falcon_h1 import model_config
from benchmark.reference import falcon_h1 as ref
from senweaver_ide_tpu import obs
from senweaver_ide_tpu.models import forward, init_params
from senweaver_ide_tpu.models import transformer as tf
from senweaver_ide_tpu.models.config import (RecurrentStateUnsupported,
                                             get_config,
                                             tiny_falcon_h1_test)
from senweaver_ide_tpu.models.load import export_hf_params, load_hf_params
from senweaver_ide_tpu.models.transformer import (forward_paged,
                                                  init_kv_cache)
from senweaver_ide_tpu.obs.tracing import noop_span
from senweaver_ide_tpu.rollout import (AdapterPool, EngineConfig,
                                       RolloutEngine)
from senweaver_ide_tpu.rollout.paged_kv import (copy_blocks, copy_state_rows,
                                                init_paged_pool,
                                                pool_bytes_per_block)
from senweaver_ide_tpu.rollout.sampler import SampleParams
from senweaver_ide_tpu.training.lora import init_lora

TINY = {
    "name": "tiny-falcon-h1-test", "model_type": "falcon_h1",
    "attention_bias": False, "attention_in_multiplier": 0.9,
    "attention_out_multiplier": 0.5, "attn_layer_indices": None,
    "embedding_multiplier": 5.0, "head_dim": 16, "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128, "key_multiplier": 0.7,
    "lm_head_multiplier": 0.25, "mamba_chunk_size": 128,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 8,
    "mamba_d_ssm": 32, "mamba_d_state": 16, "mamba_expand": 2,
    "mamba_n_groups": 2, "mamba_n_heads": 4,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 128, "mlp_bias": False,
    "mlp_expansion_factor": 8, "mlp_multipliers": [0.75, 0.4],
    "num_attention_heads": 4, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "projectors_bias": False, "rms_norm_eps": 1e-5, "rope_scaling": None,
    "rope_theta": 100000000000, "ssm_in_multiplier": 0.8,
    "ssm_multipliers": [0.9, 0.8, 0.7, 1.2, 1.1],
    "ssm_out_multiplier": 0.6, "tie_word_embeddings": False,
    "vocab_size": 512, "torch_dtype": "float32",
    "matmul_precision": "highest"}
GREEDY = SampleParams(temperature=0.0, top_k=0, top_p=1.0)
# float32 at ``highest`` on both sides, logits of magnitude ~1: the program
# (the duality form in chunks, the state carried between them) and the
# reference (one token at a time) differ by summation order alone, measured
# 5e-7 forward and 1e-6 paged. A state rounded to bfloat16 ONCE, between
# two chunks, moves the logits by 6e-5 (small: the head's multiplier is
# 1/4 here), a zeroed carry by 1e-2 and more, a dropped multiplier by 2e-4
# to 1 (the three tests below that have to FAIL it).
TOL = 5e-6
FORWARD = jax.jit(forward, static_argnames=("config",))
BLOCK, BLOCKS, ROWS = 8, 32, 6


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs._reset_for_tests()
    yield
    obs._reset_for_tests()


@pytest.fixture(scope="module")
def model():
    config = model_config(TINY)
    return init_params(config, jax.random.PRNGKey(0)), config


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.PRNGKey(1), (3, 48), 0,
                                         512))


@pytest.fixture(scope="module")
def want(model, tokens):
    return np.asarray(ref.logits(model[0], TINY, tokens))


def test_tiny_preset_is_the_arch_map_of_its_published_keys(model):
    params, config = model
    assert config == tiny_falcon_h1_test()
    lp = params["layers"]
    assert lp["ssm_in"].shape == (2, 64, 32 + (32 + 2 * 2 * 16) + 4)
    assert lp["ssm_conv_w"].shape == (2, 4, 96)
    assert lp["ssm_A_log"].dtype == lp["ssm_dt_bias"].dtype == jnp.float32
    # Mamba-2's start: a token keeps 20% to 99.9% of a head's state
    keep = jnp.exp(-jax.nn.softplus(lp["ssm_dt_bias"])
                   * jnp.exp(lp["ssm_A_log"]))
    assert 0.2 < float(keep.min()) and float(keep.max()) < 0.9991


@pytest.mark.parametrize("key", [
    "attn_layer_indices", "rope_scaling", "attention_bias", "mlp_bias",
    "projectors_bias", "mamba_proj_bias", "mamba_conv_bias",
    "mamba_rms_norm", "mamba_norm_before_gate", "mamba_use_mlp",
    "hidden_act", "mamba_d_ssm"])
def test_arch_map_refuses_what_it_does_not_model(key):
    other = {"attn_layer_indices": [0], "rope_scaling": {"factor": 2},
             "hidden_act": "gelu", "mamba_d_ssm": 40}
    bad = dict(TINY, **{key: other.get(key, not TINY[key])})
    with pytest.raises(SystemExit, match=key):
        model_config(bad)


# ---- (1) forward ---------------------------------------------------------

def test_forward_logits_equal_the_reference(model, tokens, want):
    logits, _ = FORWARD(model[0], model[1], jnp.asarray(tokens))
    assert float(np.abs(np.asarray(logits) - want).max()) < TOL
    assert float(np.abs(want).max()) > 0.5


MULTIPLIERS = (["embedding_multiplier", "lm_head_multiplier",
                "attention_in_multiplier", "attention_out_multiplier",
                "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier"]
               + [f"ssm_multipliers.{i}" for i in range(5)]
               + [f"mlp_multipliers.{i}" for i in range(2)])


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_a_dropped_multiplier_fails_the_tolerance(model, tokens, want, name):
    """Each of the twelve published multipliers (the five of the mixer's
    projection and the MLP's two one by one: fourteen numbers) set to 1 in
    the program alone misses the reference by 40 x TOL or more."""
    params, config = model
    field, _, i = name.partition(".")
    value = 1.0
    if i:
        value = list(getattr(config, field))
        value[int(i)] = 1.0
        value = tuple(value)
    logits, _ = FORWARD(params, dataclasses.replace(config, **{field: value}),
                        jnp.asarray(tokens[:1]))
    assert float(np.abs(np.asarray(logits) - want[:1]).max()) > 40 * TOL


# ---- (2) chunked prefill, then decoding, through forward_paged -----------

PAGED = jax.jit(
    lambda params, config, toks, pool, tables, rows, pos, wb, wo:
    forward_paged(params, config, toks, pool=pool, tables=tables,
                  seq_row=rows, positions=pos, write_block=wb, write_off=wo),
    static_argnums=(1,))
TABLES = np.zeros((ROWS - 2, 8), np.int32)
for _r in range(ROWS - 2):
    TABLES[_r, :6] = 1 + 6 * _r + np.arange(6)


def feed(model, pool, runs, width=24, tables=TABLES):
    """One call of ``forward_paged``: ``runs`` = [(row, tokens, first
    position)], laid out one after the other, padded to ``width`` entries
    with dropped writes on row 0. -> (logits of each run, pool')."""
    toks, rows, pos, wb, wo = [], [], [], [], []
    for row, t, start in runs:
        p = start + np.arange(len(t))
        toks += list(t)
        rows += [row] * len(t)
        pos += list(p)
        wb += list(tables[row][p // BLOCK])
        wo += list(p % BLOCK)
    pad = width - len(toks)
    vec = lambda v, fill: jnp.asarray(list(v) + [fill] * pad, jnp.int32)
    logits, pool = PAGED(model[0], model[1], vec(toks, 0), pool,
                         jnp.asarray(tables), vec(rows, 0), vec(pos, 0),
                         vec(wb, BLOCKS), vec(wo, 0))
    out, at = [], 0
    for _row, t, _start in runs:
        out.append(np.asarray(logits[at:at + len(t)]))
        at += len(t)
    return out, pool


def fresh_pool(model, **kw):
    return init_paged_pool(model[1], BLOCKS, BLOCK, state_rows=ROWS, **kw)


def test_chunked_prefill_then_decode_equals_the_reference(model, tokens,
                                                          want):
    """Row 1 prefills sequence 0 in chunks of 10, 17 and 5 and decodes the
    rest a token a call, while row 2 prefills sequence 1 in chunks of 3, 20
    and 9 beside it and decodes too: every logit equals the reference's
    full forward."""
    pool = fresh_pool(model)
    got = {1: [], 2: []}
    plan = [[(1, 0, 10), (2, 0, 3)], [(1, 10, 17)], [(2, 3, 20)],
            [(1, 27, 5), (2, 23, 9)]]
    plan += [[(1, p, 1), (2, p, 1)] for p in range(32, 48)]
    for call in plan:
        runs = [(row, tokens[row - 1][s:s + n], s) for row, s, n in call]
        out, pool = feed(model, pool, runs)
        for (row, _s, _n), lg in zip(call, out):
            got[row].append(lg)
    for row in (1, 2):
        assert float(np.abs(np.concatenate(got[row])
                            - want[row - 1]).max()) < TOL


def _two_chunks(model, tokens, between):
    pool = fresh_pool(model)
    (a,), pool = feed(model, pool, [(1, tokens[0][:20], 0)])
    pool = between(pool)
    (b,), _ = feed(model, pool, [(1, tokens[0][20:40], 20)])
    return np.concatenate([a, b])


def test_a_bfloat16_state_fails_the_tolerance(model, tokens, want):
    """The state rounded to bfloat16 once, between two chunks: 5 x TOL
    and more away, so the comparison tells a state held in a lower
    precision than float32."""
    exact = _two_chunks(model, tokens, lambda pool: pool)
    assert float(np.abs(exact - want[0][:40]).max()) < TOL
    low = _two_chunks(model, tokens, lambda pool: pool._replace(
        rows=pool.rows._replace(ssm=pool.rows.ssm.astype(
            jnp.bfloat16).astype(jnp.float32))))
    assert float(np.abs(low - want[0][:40]).max()) > 5 * TOL


@pytest.mark.parametrize("leaf", ["ssm", "conv"])
def test_a_zeroed_carry_fails_the_tolerance(model, tokens, want, leaf):
    lost = _two_chunks(model, tokens, lambda pool: pool._replace(
        rows=pool.rows._replace(**{leaf: jnp.zeros_like(
            getattr(pool.rows, leaf))})))
    assert float(np.abs(lost - want[0][:40]).max()) > 100 * TOL


def test_padding_and_dropped_writes_advance_no_row(model):
    """A call of pure padding (row 0, position 0, write dropped), and
    padding beside another row's run, leave row 0's state and window
    bit-equal: padding is addressed to row 0 and is no entry of it."""
    pool = fresh_pool(model)
    _, pool = feed(model, pool, [(0, [5, 6, 7], 0)])
    before = jax.tree_util.tree_map(np.asarray, pool.rows)
    _, pool = feed(model, pool, [])                       # padding alone
    _, pool = feed(model, pool, [(1, [9], 0)])            # and beside a row
    for was, now in zip(before, pool.rows):
        assert np.array_equal(was[:, 0], np.asarray(now)[:, 0])
    assert float(np.abs(before.ssm[:, 0]).max()) > 0


def test_a_quantized_kv_ladder_runs_beside_the_float32_state(model, tokens,
                                                             want):
    """``kv_dtype`` int8 quantizes the attention's blocks and nothing of
    the state: the rows stay float32 and advance, and the logits move off
    the reference by the KV's rounding alone (far more than TOL, far less
    than a lost state). A full-width prefix layer (``kv_dtype_per_layer``)
    indexes the state by the block's absolute number."""
    for kw in ({"kv_dtype": "int8"},
               {"kv_dtype": "int8",
                "kv_dtype_per_layer": ("bf16", "int8")}):
        pool = fresh_pool(model, **kw)
        (a,), pool = feed(model, pool, [(1, tokens[0][:20], 0)])
        (b,), pool = feed(model, pool, [(1, tokens[0][20:40], 20)])
        gap = float(np.abs(np.concatenate([a, b]) - want[0][:40]).max())
        assert 20 * TOL < gap < 0.05, (kw, gap)
        assert pool.rows.ssm.dtype == jnp.float32
        assert float(jnp.abs(pool.rows.ssm[1, 1]).max()) > 0


# ---- (3) through RolloutEngine --------------------------------------------

PROMPT = [int(t) for t in np.random.RandomState(0).randint(1, 500, size=21)]
OTHER = [int(t) for t in np.random.RandomState(1).randint(1, 500, size=13)]


def make_engine(model, **kw):
    ec = dict(block_size=8, step_tokens=16)
    ec.update(kw.pop("engine", {}))
    args = dict(num_slots=6, max_len=64, sample=GREEDY)
    args.update(kw)
    return RolloutEngine(model[0], model[1],
                         engine_config=EngineConfig(**ec), **args)


@pytest.fixture(scope="module")
def solo(model):
    """Four independent submits of one prompt, greedy: what every shared
    form has to reproduce token for token."""
    e = make_engine(model)
    rids = [e.submit(PROMPT, max_new_tokens=12) for _ in range(4)]
    out = e.run()
    assert all(out[r] == out[rids[0]] for r in rids)
    # and the no-cache forward agrees
    seq = list(PROMPT)
    for _ in range(12):
        lg, _ = FORWARD(model[0], model[1], jnp.asarray([seq]))
        seq.append(int(jnp.argmax(lg[0, -1])))
    assert seq[len(PROMPT):] == out[rids[0]]
    return out[rids[0]]


def test_group_of_four_equals_four_submits_with_one_prefill(model, solo):
    e = make_engine(model)
    rids = e.submit_group(PROMPT, 4, max_new_tokens=12)
    out = e.run()
    assert [out[r] for r in rids] == [solo] * 4
    st = e.stats()
    assert (st["prefills"], st["group_prefills"], st["group_forks"],
            st["group_degrades"]) == (1, 1, 3, 0)
    # the prompt once, and its last token once more a follower
    assert st["prefill_tokens"] == len(PROMPT) + 3
    e._alloc.check_leaks()
    assert sorted(e._state_snap_free) == [6, 7]


def test_followers_start_from_the_donors_state_to_the_bit(model, solo):
    """At the fork the snapshot row and every follower's rows hold the
    donor's state and window bit for bit, all layers: checked between the
    copies and the step that feeds the prompt's last token."""
    e = make_engine(model)
    rids = e.submit_group(PROMPT, 4, max_new_tokens=12)
    while not e._state_copies:
        e.step()
    copies = list(e._state_copies)
    with e._lock:
        assert e._flush_state_copies(noop_span) == 4
    donor_row = e._requests[rids[0]].slot
    assert copies[0][0] == donor_row and copies[0][1] >= e.num_slots
    rows = jax.tree_util.tree_map(np.asarray, e.pool.rows)
    for leaf in rows:
        assert np.abs(leaf[:, donor_row]).max() > 0
        for _src, dst in copies:
            assert np.array_equal(leaf[:, dst], leaf[:, donor_row])
    assert {dst for _s, dst in copies[1:]} == {
        e._requests[r].slot for r in rids[1:]}
    out = e.run()
    assert [out[r] for r in rids] == [solo] * 4


def test_no_free_snapshot_row_degrades_and_stays_exact(model, solo):
    e = make_engine(model)
    e._state_snap_free.clear()      # every snapshot row is another group's
    rids = e.submit_group(PROMPT, 4, max_new_tokens=12)
    out = e.run()
    assert [out[r] for r in rids] == [solo] * 4
    st = e.stats()
    assert (st["group_degrades"], st["group_forks"], st["prefills"]) == (
        1, 0, 4)
    e._alloc.check_leaks()


def test_a_group_of_a_one_token_prompt_has_nothing_to_share(model):
    e = make_engine(model)
    rids = e.submit_group([7], 3, max_new_tokens=4)
    out = e.run()
    lone = make_engine(model)
    rid = lone.submit([7], max_new_tokens=4)
    assert [out[r] for r in rids] == [lone.run()[rid]] * 3
    assert e.stats()["group_degrades"] == 1


def test_a_preempted_row_resumes_to_the_same_tokens(model, solo):
    """Preemption by recompute: the row's blocks go, its request is fed
    again from position 0, which clears the state, and decodes on to the
    same tokens — a lone request, and a group's follower and donor."""
    e = make_engine(model)
    rid = e.submit(PROMPT, max_new_tokens=12)
    while len(e._requests[rid].tokens) < 5:
        e.step()
    with e._lock:
        e._preempt_row(e._requests[rid].slot)
    assert e.run()[rid] == solo and e.stats()["kv_preemptions"] == 1
    e = make_engine(model)
    rids = e.submit_group(PROMPT, 4, max_new_tokens=12)
    while len(e._requests[rids[2]].tokens) < 3:
        e.step()
    with e._lock:
        e._preempt_row(e._requests[rids[2]].slot)
        e._preempt_row(e._requests[rids[0]].slot)
    out = e.run()
    assert [out[r] for r in rids] == [solo] * 4
    e._alloc.check_leaks()


def test_a_reused_row_does_not_see_the_last_tenants_state(model, solo):
    e = make_engine(model, num_slots=1)
    first = e.submit(OTHER, max_new_tokens=9)
    e.run()
    assert float(jnp.abs(e.pool.rows.ssm[:, 0]).max()) > 0
    again = e.submit(PROMPT, max_new_tokens=12)
    assert e.run()[again] == solo
    held = make_engine(model, num_slots=1)
    # and a held conversation goes on from its stored state
    a = held.submit(PROMPT, max_new_tokens=6, hold_slot=True)
    head = held.run()[a]
    assert head == solo[:6]
    b = held.submit(PROMPT + head + [3, 1], max_new_tokens=4,
                    continue_from=a)
    got = held.run()[b]
    lone = make_engine(model, num_slots=1)
    c = lone.submit(PROMPT + head + [3, 1], max_new_tokens=4)
    assert got == lone.run()[c]
    assert first != again


def test_the_step_reports_its_state_rows_and_copies(model):
    """``engine.step`` attrs ``ssm_rows`` (rows whose state the step read
    and wrote) and ``ssm_state_copies`` (row copies dispatched before it),
    an ``engine.state_copy`` span under ``engine.plan`` for each, and the
    registry's counter and gauge."""
    obs.enable()
    e = make_engine(model)
    e.submit_group(PROMPT, 4, max_new_tokens=4)
    e.run()
    spans = obs.get_tracer().spans()
    steps = [s.attrs for s in spans if s.name == "engine.step"
             and "entries" in s.attrs]
    assert max(a["ssm_state_copies"] for a in steps) == 4
    assert sum(a["ssm_state_copies"] for a in steps) == 4
    assert max(a["ssm_rows"] for a in steps) == 4
    assert all(1 <= a["ssm_rows"] <= a["rows_active"] for a in steps)
    plans = {s.span_id for s in spans if s.name == "engine.plan"}
    copies = [s for s in spans if s.name == "engine.state_copy"]
    assert len(copies) == 4 and all(c.parent_id in plans for c in copies)
    reg = obs.get_registry()
    assert reg.counter("senweaver_ssm_state_copies_total").value() == 4
    rows = e.pool.rows
    assert reg.gauge("senweaver_ssm_state_bytes").value() == sum(
        int(a.size) * a.dtype.itemsize for a in rows) == rows.nbytes
    assert e.stats()["state_bytes_device"] == rows.nbytes


def test_block_movers_leave_the_state_rows_alone(model):
    pool = fresh_pool(model)
    _, pool = feed(model, pool, [(1, [5, 6, 7], 0)])
    rows = jax.tree_util.tree_map(np.asarray, pool.rows)
    per_block = pool_bytes_per_block(pool)
    assert per_block == pool_bytes_per_block(pool._replace(rows=None))
    pool = copy_blocks(pool, jnp.asarray([7], jnp.int32),
                       jnp.asarray([1], jnp.int32))
    for was, now in zip(rows, pool.rows):
        assert np.array_equal(was, np.asarray(now))
    pool = copy_state_rows(pool, jnp.asarray([1], jnp.int32),
                           jnp.asarray([5], jnp.int32))
    for was, now in zip(rows, pool.rows):
        assert np.array_equal(was[:, 1], np.asarray(now)[:, 5])
        assert np.array_equal(was[:, :5], np.asarray(now)[:, :5])


# ---- (4) what is refused, by name -----------------------------------------

def _engine_refusals(model):
    params, config = model
    ring = dataclasses.replace(config, sliding_window=16)
    quant = dataclasses.replace(config, kv_quant=True)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("tp",))
    return {
        "slots": lambda: RolloutEngine(
            params, config, engine_config=EngineConfig(kv_layout="slots")),
        "kv_quant": lambda: RolloutEngine(params, quant),
        "ring": lambda: RolloutEngine(params, ring, max_len=64),
        "mesh": lambda: RolloutEngine(params, config, mesh=mesh),
        "adapter_pool": lambda: AdapterPool(config),
        "adapter_pool_engine": lambda: RolloutEngine(
            params, config, adapter_pool=object()),
        "init_lora": lambda: init_lora(config, jax.random.PRNGKey(0)),
        "register_prefix": lambda: make_engine(model).register_prefix(
            PROMPT),
        "import_prefix": lambda: make_engine(model).import_prefix(
            PROMPT, None),
        "export_prefix": lambda: make_engine(model).export_prefix(0),
        "enable_speculation": lambda: make_engine(model).enable_speculation(
            params, config),
        "fork_request": lambda: make_engine(model).fork_request(0),
        "checkpoint_request": lambda: make_engine(model).checkpoint_request(
            0),
        "restore_request": lambda: make_engine(model).restore_request(None),
        "load_hf_params": lambda: load_hf_params("/nonexistent", config),
        "export_hf_params": lambda: export_hf_params(params, config,
                                                     "/nonexistent"),
        "init_kv_cache": lambda: init_kv_cache(config, 1, 16),
        "forward_cache": lambda: forward(
            params, config, jnp.zeros((1, 4), jnp.int32),
            cache=init_kv_cache(get_config("tiny-test"), 1, 16)),
        "forward_mesh": lambda: forward(
            params, config, jnp.zeros((1, 4), jnp.int32), mesh=mesh),
    }


@pytest.mark.parametrize("what", [
    "slots", "kv_quant", "ring", "mesh", "adapter_pool",
    "adapter_pool_engine", "init_lora", "register_prefix", "import_prefix",
    "export_prefix", "enable_speculation", "fork_request",
    "checkpoint_request", "restore_request", "load_hf_params",
    "export_hf_params", "init_kv_cache", "forward_cache", "forward_mesh"])
def test_what_has_no_state_snapshot_is_refused_by_name(model, what):
    with pytest.raises(RecurrentStateUnsupported) as err:
        _engine_refusals(model)[what]()
    assert err.value.mechanism and "tiny-falcon-h1-test" in str(err.value)


# ---- (5) every other model is what it was ---------------------------------

# The jaxprs of ``init_params``, ``forward`` and ``forward_paged`` for the
# three tiny presets of the benchmark's other configurations, as the PARENT
# of PR 32 (commit 0411e8a) prints them, by sha256: with no ``mamba_*`` key
# and every multiplier 1 the same operations run in the same order, so the
# parameter trees, outputs and pools are bit-equal (compared array by array
# against a clone of the parent when this was written: CHANGES.md, PR 32).
# A PR that changes these models' programs on purpose prints the new ones
# with ``_digests`` below and says so.
PARENT = {
    "tiny-test": ("2e03fb3af943d80b", "b3a9710d444a503d",
                  "5a61d6ff7261c710"),
    "tiny-glm-moe-test": ("5ccafe1e4d46c3fa", "f7db031f3b5ba6b5",
                          "54ea6ad022c50a8b"),
    "tiny-xing-mhc-test": ("57794ae7ad387f5f", "4de6d1df403ed2ec",
                           "bd0d7a04992a9a27"),
}


def _digests(name):
    c = get_config(name)
    key = jax.random.PRNGKey(0)
    params = init_params(c, key)
    pool = init_paged_pool(c, 16, 4)
    toks = jnp.arange(12, dtype=jnp.int32) * 7 % c.vocab_size
    tables = jnp.asarray(np.arange(3 * 6).reshape(3, 6) % 16, jnp.int32)
    rows = jnp.asarray([0, 1, 1, 1, 1, 1, 2, 2, 2, 0, 0, 0], jnp.int32)
    pos = jnp.asarray([5, 0, 1, 2, 3, 4, 0, 1, 2, 0, 0, 0], jnp.int32)
    wb = jnp.asarray([1, 6, 6, 6, 6, 7, 12, 12, 12, 16, 16, 16], jnp.int32)
    wo = pos % 4
    paged = lambda p, pool: forward_paged(
        p, c, toks, pool=pool, tables=tables, seq_row=rows, positions=pos,
        write_block=wb, write_off=wo)
    texts = (jax.make_jaxpr(lambda k: init_params(c, k))(key),
             jax.make_jaxpr(lambda p: forward(p, c, toks[None]))(params),
             jax.make_jaxpr(paged)(params, pool))
    return tuple(hashlib.sha256(str(t).encode()).hexdigest()[:16]
                 for t in texts)


@pytest.mark.parametrize("name", sorted(PARENT))
def test_the_other_models_programs_are_the_parents(name):
    assert _digests(name) == PARENT[name]
    assert init_paged_pool(get_config(name), 4, 4).rows is None
