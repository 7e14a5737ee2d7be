"""LongCat-Flash's layer (``longcat_flash``) through the program, at test
size, against the plain reference the benchmark's output check uses
(``benchmark/reference/longcat_flash.py``: expanded attention, float32, no
cache, every token through every held expert, nothing of the program
imported).

The tiny preset is the architecture map of a published-key dict: two
shortcut blocks (two latent-attention sublayers and two dense FFNs each, the
expert branch beside them), 16 real + 8 identity experts under one softmax
router top-4 with the 6.0 scaling and no renormalisation, both latent
scales, and a share of the experts: 8 of the 16, from the 4th. Everything
float32 at ``highest``; the tests draw their own correction bias (the
benchmark's is a constant).
"""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.archs.longcat_flash import model_config
from benchmark.reference import longcat_flash as ref
from senweaver_ide_tpu import obs
from senweaver_ide_tpu.models import (export_hf_params, forward, init_params,
                                      load_hf_params, moe)
from senweaver_ide_tpu.models import transformer as tf
from senweaver_ide_tpu.models.config import (ExpertShareUnsupported,
                                             LatentCacheUnsupported,
                                             get_config,
                                             longcat_flash_config,
                                             tiny_longcat_flash_test)
from senweaver_ide_tpu.models.transformer import forward_paged
from senweaver_ide_tpu.rollout import EngineConfig, RolloutEngine
from senweaver_ide_tpu.rollout import engine as engine_mod
from senweaver_ide_tpu.rollout.paged_kv import (init_paged_pool,
                                                pool_bytes_per_block)
from senweaver_ide_tpu.rollout.sampler import SampleParams
from senweaver_ide_tpu.training.lora import init_lora

TINY = {
    "name": "tiny-longcat-flash-test", "model_type": "longcat_flash",
    "attention_bias": False, "vocab_size": 512, "hidden_size": 64,
    "ffn_hidden_size": 96, "expert_ffn_hidden_size": 32, "num_layers": 2,
    "num_attention_heads": 4, "kv_lora_rank": 24, "q_lora_rank": 16,
    "qk_rope_head_dim": 4, "v_head_dim": 16, "qk_nope_head_dim": 8,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "routed_scaling_factor": 6, "n_routed_experts": 8,
    "max_position_embeddings": 128, "rms_norm_eps": 1e-5,
    "rope_theta": 10000000, "attention_method": "MLA", "zero_expert_num": 8,
    "zero_expert_type": "identity", "moe_topk": 4,
    "torch_dtype": "float32", "matmul_precision": "highest",
    "held_experts": {"first": 4, "count": 8, "of": 16},
    "published": {"n_routed_experts": 16}}
# the same model with every one of its 16 experts held: the uncut layer
WHOLE = dict(TINY, n_routed_experts=16,
             held_experts={"first": 0, "count": 16, "of": 16})
SAMPLED = SampleParams(temperature=1.0, top_k=0, top_p=1.0)
# float32 at ``highest`` on both sides: the two differ by summation order
# (absorbed against expanded attention, sorted against dense experts).
# bfloat16 where float32 is stated moves a logit by ~1e-2: 500 times this.
TOL = 2e-5


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs._reset_for_tests()
    yield
    obs._reset_for_tests()


def _with_bias(params, key=5):
    # a correction bias of the size of the scores' own spread (1 / 24): it
    # changes many choices, so a bias that leaked into the weights shows
    lead = params["layers"]["router_bias_norm"].shape
    params["layers"]["router_bias_norm"] = 0.03 * jax.random.normal(
        jax.random.PRNGKey(key), lead)
    return params


@pytest.fixture(scope="module")
def model():
    config = model_config(TINY)
    return _with_bias(init_params(config, jax.random.PRNGKey(0))), config


@pytest.fixture(scope="module")
def whole():
    config = model_config(WHOLE)
    return _with_bias(init_params(config, jax.random.PRNGKey(0))), config


def test_tiny_preset_is_the_arch_map_of_its_published_keys():
    c = model_config(TINY)
    assert c == tiny_longcat_flash_test()
    assert (c.num_experts, c.routed_experts, c.router_width) == (8, 16, 24)
    assert c.attn_layers == 4 and c.expert_share
    s_q, s_kv = c.mla_scales
    assert s_q == 2.0 and abs(s_kv - (64 / 24) ** 0.5) < 1e-12
    # the configurations before it: every expert held, one pool layer each
    glm = get_config("tiny-glm-moe-test")
    assert not glm.expert_share and glm.attn_layers == glm.num_layers
    assert glm.mla_scales == (1.0, 1.0)


# ---- (1) forward, expanded form ------------------------------------------

def test_forward_logits_equal_the_reference(model):
    params, config = model
    toks = jax.random.randint(jax.random.PRNGKey(1), (3, 40), 0, 512)
    logits, _, aux = forward(params, config, toks, with_aux=True)
    want = ref.logits(params, TINY, toks)
    assert float(jnp.abs(logits - want).max()) < TOL
    assert float(jnp.abs(want).max()) > 1.0
    assert float(aux) == 0.0        # a bias router has no aux loss


def _leave_out(monkeypatch, what):
    """Break ONE mechanism of the program; -> the config to run."""
    c = model_config(TINY)
    if what == "s_q":
        return dataclasses.replace(c, mla_scale_q_lora=False)
    if what == "s_kv":
        return dataclasses.replace(c, mla_scale_kv_lora=False)
    if what == "the zero term":
        # the identity experts become real experts held elsewhere
        return dataclasses.replace(c, moe_zero_experts=0,
                                   moe_routed_experts=24)
    real = moe.route if what == "no renormalisation" else tf.expert_ffn
    if what == "no renormalisation":
        def route(c, lp, h):
            idx, w, aux = real(c, lp, h)
            return idx, (w / w.sum(-1, keepdims=True)
                         * c.routed_scaling_factor), aux
        monkeypatch.setattr(moe, "route", route)
    else:
        assert what == "the shortcut's m"
        def no_branch(*a, **kw):
            y, aux, stats = real(*a, **kw)
            return 0.0 * y, aux, stats
        monkeypatch.setattr(tf, "expert_ffn", no_branch)
    return c


@pytest.mark.parametrize("what", ["the zero term", "s_q", "s_kv",
                                  "the shortcut's m", "no renormalisation"])
def test_the_comparison_sees_every_mechanism(model, monkeypatch, what):
    """Take any one of them out of the program and the comparison with
    the reference fails, by a hundred times its tolerance or more."""
    params, _ = model
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 512)
    want = ref.logits(params, TINY, toks)
    broken, _ = forward(params, _leave_out(monkeypatch, what), toks)
    assert float(jnp.abs(broken - want).max()) > 100 * TOL
    monkeypatch.undo()
    sound, _ = forward(params, model_config(TINY), toks)
    assert float(jnp.abs(sound - want).max()) < TOL


# ---- (2) chunked prefill, then decode, through the latent pool -----------

def _paged_run(model, toks, spans, pool=None):
    """Feed ``toks`` (S,) through ``forward_paged`` in the given position
    spans, one call each, over a fresh pool; every call's logits."""
    params, config = model
    bs = 4
    pool = init_paged_pool(config, 24, bs) if pool is None else pool
    tables = (jnp.arange(10, dtype=jnp.int32)[None, :] * 2 + 1)  # scattered
    out, counts = [], []
    for lo, hi in spans:
        pos = jnp.arange(lo, hi, dtype=jnp.int32)
        logits, pool, st = forward_paged(
            params, config, toks[lo:hi], pool=pool, tables=tables,
            seq_row=jnp.zeros((hi - lo,), jnp.int32), positions=pos,
            write_block=tables[0, pos // bs], write_off=pos % bs,
            with_moe_stats=True)
        out.append(logits)
        counts.append(st)
    return jnp.concatenate(out, 0), pool, counts


@pytest.mark.parametrize("chunks", [(13,), (5, 8), (1, 4, 4, 4)],
                         ids=["one-chunk", "two-chunks", "ragged-chunks"])
def test_chunked_prefill_then_paged_decode_equals_the_reference(model,
                                                                chunks):
    """Absorbed attention over the latent cache, twice a layer, against the
    reference's expanded full forward, at EVERY position: the prompt in
    chunks, then token by token."""
    s = 24
    toks = jax.random.randint(jax.random.PRNGKey(3), (s,), 0, 512)
    edges = np.concatenate([[0], np.cumsum(chunks)])
    spans = [(int(a), int(b)) for a, b in zip(edges[:-1], edges[1:])]
    spans += [(i, i + 1) for i in range(int(edges[-1]), s)]
    got, pool, counts = _paged_run(model, toks, spans)
    want = ref.logits(model[0], TINY, toks[None])[0]
    assert float(jnp.abs(got - want).max()) < TOL
    # two pool layers a layer, one latent row a token each, no values
    config = model[1]
    assert pool.k.shape == (4, 24, 4, 1, config.latent_row_dim)
    assert pool.v.shape[-1] == 0 and pool.k_scale is None
    assert pool_bytes_per_block(pool) == 4 * 4 * config.latent_row_dim * 4
    # every pick is a held, an absent or an identity expert's
    for (lo, hi), st in zip(spans, counts):
        pairs = (hi - lo) * 4 * config.num_layers
        assert 0 <= int(st.zero_picks) + int(st.local_pairs) <= pairs
        assert int(st.experts_touched) <= min(16, int(st.local_pairs))
    assert sum(int(st.zero_picks) for st in counts) > 0
    assert sum(int(st.local_pairs) for st in counts) > 0


def test_sublayer_i_of_layer_l_owns_pool_layer_2l_plus_i(model):
    """Each of the four pool layers is written by one sublayer and read by
    the same: swap two of them after the prefill and the next token's
    logits are no longer the reference's."""
    toks = jax.random.randint(jax.random.PRNGKey(4), (14,), 0, 512)
    want = ref.logits(model[0], TINY, toks[None])[0, 13]
    _, pool, _ = _paged_run(model, toks, [(0, 13)])
    rows = np.asarray(pool.k[:, jnp.asarray([1, 3, 5, 7])])
    assert all(np.abs(rows[i]).max() > 0 for i in range(4))
    assert all(np.abs(rows[i] - rows[j]).max() > 1e-3
               for i in range(4) for j in range(i))
    got, _, _ = _paged_run(model, toks, [(13, 14)], pool=pool)
    assert float(jnp.abs(got[0] - want).max()) < TOL
    for a, b in [(0, 1), (2, 3), (1, 2)]:
        order = list(range(4))
        order[a], order[b] = b, a
        swapped = pool._replace(k=pool.k[jnp.asarray(order)])
        got, _, _ = _paged_run(model, toks, [(13, 14)], pool=swapped)
        assert float(jnp.abs(got[0] - want).max()) > 100 * TOL


# ---- (3) the expert layer alone: shares, identity experts, the bias ------

def _layer0(params, bias=None):
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    if bias is not None:
        lp["router_bias_norm"] = jnp.asarray(bias, jnp.float32)
    return lp


def _share(lp, lo, held):
    """Layer leaves that hold the experts [lo, lo + held) of ``lp``'s."""
    return dict(lp, **{k: lp[k][lo:lo + held] for k in
                       ("w_gate", "w_up", "w_down")})


@pytest.mark.parametrize("side", ["program", "reference"])
@pytest.mark.parametrize("held", [8, 4], ids=["2-shares-of-8",
                                              "4-shares-of-4"])
def test_the_shares_routed_parts_and_the_zero_term_once_add_up(whole, held,
                                                               side):
    """The routed parts of all shares, plus what every chip computes alike
    (the identity experts' term) counted once, are the uncut reference's
    whole MoE(u), to float32 rounding."""
    params, config = whole
    lp = _layer0(params)
    u = jax.random.normal(jax.random.PRNGKey(7), (24, 64))
    cfg = ref.settings(WHOLE)
    real, zero = ref.moe_parts(cfg, None, u, lp)
    assert float(jnp.abs(real).max()) > 0.1 < float(jnp.abs(zero).max())
    total = zero
    for lo in range(0, 16, held):
        if side == "program":
            c = dataclasses.replace(config, num_experts=held,
                                    moe_first_expert=lo)
            y, _, stats = moe.expert_ffn(c, _share(lp, lo, held), u)
            part = y - zero
            assert int(stats.experts_touched) <= held
        else:
            part, again = ref.moe_parts(dict(cfg, first_expert=lo), None, u,
                                        _share(lp, lo, held))
            assert float(jnp.abs(again - zero).max()) == 0.0
        total = total + part
    assert float(jnp.abs(total - (real + zero)).max()) < 1e-5


def _bias(**at):
    b = np.zeros(24)
    for name, v in at.items():
        b[{"absent": slice(0, 4), "held": slice(4, 8),
           "zero": slice(16, 20)}[name]] = v
    return b


def test_picks_of_identity_experts_alone_return_the_token_scaled(model):
    """Four identity picks: gamma x (sum of their p) x the token, exactly
    what one multiply gives; no bank runs."""
    params, config = model
    lp = _layer0(params, _bias(zero=9.0))
    u = jax.random.normal(jax.random.PRNGKey(8), (10, 64))
    y, aux, stats = moe.expert_ffn(config, lp, u)
    idx, w, _ = moe.route(config, lp, u)
    assert np.array_equal(np.sort(np.asarray(idx), -1),
                          np.tile(np.arange(16, 20), (10, 1)))
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(w.sum(-1)[:, None] * u))
    assert (int(stats.zero_picks), int(stats.local_pairs),
            int(stats.experts_touched), float(aux)) == (40, 0, 0, 0.0)


def test_picks_of_absent_experts_alone_add_nothing_here(model):
    params, config = model
    lp = _layer0(params, _bias(absent=9.0))
    u = jax.random.normal(jax.random.PRNGKey(8), (10, 64))
    y, _, stats = moe.expert_ffn(config, lp, u)
    assert float(jnp.abs(y).max()) == 0.0
    assert (int(stats.zero_picks), int(stats.local_pairs),
            int(stats.experts_touched)) == (0, 0, 0)
    # and the same picks on the chip that holds them are its whole part
    there = dataclasses.replace(config, moe_first_expert=0)
    y, _, stats = moe.expert_ffn(there, lp, u)
    assert float(jnp.abs(y).max()) > 0.01
    assert (int(stats.local_pairs), int(stats.experts_touched),
            int(stats.expert_load_max)) == (40, 4, 10)


def test_bias_enters_the_choice_and_never_the_weights(model):
    params, config = model
    lp = _layer0(params, _bias(held=9.0))
    u = jax.random.normal(jax.random.PRNGKey(9), (12, 64))
    idx, w, aux = moe.route(config, lp, u)
    assert np.array_equal(np.sort(np.asarray(idx), -1),
                          np.tile(np.arange(4, 8), (12, 1)))
    p = jax.nn.softmax(jnp.dot(u, lp["router"],
                               precision=jax.lax.Precision.HIGHEST), -1)
    want = 6.0 * jnp.take_along_axis(p, idx, -1)
    assert float(jnp.abs(w - want).max()) < 1e-6
    # the weights are the scores as they are: four of 24 sum far under 1
    assert float(w.sum(-1).max()) < 6.0 * 0.9 and float(aux) == 0.0
    lp0 = dict(lp, router_bias_norm=jnp.zeros(24))
    assert not np.array_equal(np.sort(np.asarray(moe.route(
        config, lp0, u)[0]), -1), np.tile(np.arange(4, 8), (12, 1)))


def test_a_tokens_result_does_not_depend_on_its_batch(model):
    params, config = model
    lp = _layer0(params)
    u = jax.random.normal(jax.random.PRNGKey(10), (24, 64))
    together, _, _ = moe.expert_ffn(config, lp, u)
    for i in (0, 7, 23):
        alone, _, _ = moe.expert_ffn(config, lp, u[i:i + 1])
        assert float(jnp.abs(alone[0] - together[i]).max()) < 1e-5
    # counted entries only: padding is routed, and is not work
    some = jnp.arange(24) < 3
    _, _, stats = moe.expert_ffn(config, lp, u, some)
    assert int(stats.zero_picks) + int(stats.local_pairs) <= 12
    assert int(stats.expert_load_max) <= 3


# ---- (4) the engine: group fork, chunked prefill, run-ahead --------------

def make_engine(model, *, num_slots=4, max_len=64, sample=SAMPLED, **cfg_kw):
    params, config = model
    return RolloutEngine(
        params, config, num_slots=num_slots, max_len=max_len, sample=sample,
        engine_config=EngineConfig(kv_layout="paged", block_size=4,
                                   **cfg_kw))


def test_engine_logps_equal_the_reference_under_fork_and_run_ahead(model):
    """What the output check compares on the chip: log p of each served
    token, sampled at temperature 1, against the teacher-forced reference;
    two groups of four on four rows (the second queued: the engine is
    saturated and runs a step ahead), prompts prefilled in chunks of 8,
    followers forking their donor's latent blocks in all four pool layers.
    """
    obs.enable()
    eng = make_engine(model, step_tokens=8)
    prompts = [list(range(1, 22)), [7, 9, 11, 7, 9, 2]]
    groups = [eng.submit_group(p, 4, max_new_tokens=9) for p in prompts]
    eng.run()
    assert eng.kv_layout == "paged" and eng.kv_layout_fallback is None
    for p, rids in zip(prompts, groups):
        for rid in rids:
            seq = np.asarray([p + eng.result(rid)], np.int32)
            want = ref.served_logps(model[0], TINY, seq, [len(p) - 1], 9)
            got = np.asarray(eng.result_logps(rid))
            assert np.abs(got - np.asarray(want)[0]).max() < TOL
        assert len({tuple(eng.result(r)) for r in rids}) > 1
    s = eng.stats()
    assert (s["group_prefills"], s["group_forks"]) == (2, 6)
    steps = [sp.attrs for sp in obs.get_tracer().spans()
             if sp.name == "engine.step" and "entries" in sp.attrs]
    assert sum(a.get("ahead", 0) for a in steps) > 0
    eng._alloc.check_leaks()


def test_step_reports_its_share_of_the_routing_in_the_one_fetch(model):
    """``zero_picks`` and ``local_pairs`` ride behind the step's tokens
    with the two older counts; attrs of ``engine.step`` and
    ``senweaver_moe_*`` counters; the banks are those held."""
    obs.enable()
    eng = make_engine(model)
    rid = eng.submit([5, 9, 2, 7, 1, 3], max_new_tokens=4)
    eng.run()
    steps = [s.attrs for s in obs.get_tracer().spans()
             if s.name == "engine.step" and "entries" in s.attrs]
    assert steps and all(a["expert_assignments"] == 4 * a["used"]
                         and a["expert_banks"] == 2 * 8 for a in steps)
    for a in steps:
        # over the two expert layers: every counted pick is one of three
        assert a["expert_picks"] == 2 * a["expert_assignments"]
        assert 0 <= a["zero_picks"] + a["local_pairs"] <= a["expert_picks"]
        assert a["experts_touched"] <= min(16, a["local_pairs"])
    assert sum(a["zero_picks"] for a in steps) > 0
    reg = obs.get_registry()
    assert reg.get("senweaver_moe_zero_picks_total").value() == sum(
        a["zero_picks"] for a in steps)
    assert reg.get("senweaver_moe_local_pairs_total").value() == sum(
        a["local_pairs"] for a in steps)
    assert reg.get("senweaver_moe_expert_banks_total").value() == (
        16 * len(steps))
    assert eng.is_done(rid)


# ---- (5) the configurations before it run the programs they ran ----------

# sha256 of ``_paged_fused_step``'s jaxpr at the tiny presets: held = all
# and no identity expert give the same operations in the same order, two
# counts behind the tokens, one pool layer a layer, no latent scale. As the
# parent of PR 37 (commit ebe0dcc) printed them, and since PR 38, on
# purpose, with ONE more operation each: the attention plan's two counts
# (zeros on this gather path) joined on behind the step's tokens
# (9bc362fd790508c7, d7ca6bc275e8c59f, ae41dbfc9a84c662, cef23a44eb88e94f
# before; ``forward_paged``'s own jaxprs, tests/test_falcon_h1.py, are
# what they were). Since PR 40 a step wider than its rows (12 entries on 3
# here) pays the head for its samplers alone: the pin is its every-entry
# form, ``all_logits=True``, which is the program the parent ran
# (tests/test_head_entries.py: a narrow step is that form too).
PARENT_STEP = {"tiny-test": "8c64e81a4b54a550",
               "tiny-glm-moe-test": "55e468c6bcc5911d",
               "tiny-xing-mhc-test": "2affdb048e6dc77d",
               "tiny-falcon-h1-test": "a62eea48df2b0c27"}


def _step_digest(name):
    c = get_config(name)
    params = init_params(c, jax.random.PRNGKey(0))
    pool = init_paged_pool(c, 16, 4, **({"state_rows": 4} if c.ssm else {}))
    plan = jnp.asarray(np.arange(6 * 12).reshape(6, 12) % 5, jnp.int32)
    tables = jnp.asarray(np.arange(3 * 6).reshape(3, 6) % 16, jnp.int32)
    text = jax.make_jaxpr(
        lambda p, pool, key, cur: engine_mod._paged_fused_step._fn(
            p, c, plan, tables, pool, key, cur, SAMPLED, False,
            all_logits=True))(
                params, pool, jax.random.PRNGKey(1),
                jnp.zeros((3,), jnp.int32))
    return hashlib.sha256(str(text).encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(PARENT_STEP))
def test_the_other_models_fused_steps_are_the_parents(name):
    assert _step_digest(name) == PARENT_STEP[name]


# ---- (6) what has no form here raises, by name ---------------------------

def _mapped(**change):
    keys = {k: v for k, v in TINY.items() if k not in (
        "name", "model_type", "torch_dtype", "matmul_precision",
        "held_experts", "published")}
    return longcat_flash_config({**keys, **change}, name="x")


UNMAPPED = {
    "zero_expert_type": dict(zero_expert_type="copy"),
    "attention_bias": dict(attention_bias=True),
    "attention_method": dict(attention_method="MHA"),
    "rope_scaling": dict(rope_scaling={"rope_type": "yarn", "factor": 10}),
    "index_topk": dict(index_topk=2048),
}


@pytest.mark.parametrize("key", list(UNMAPPED))
def test_a_published_key_with_no_form_here_is_refused_by_name(key):
    with pytest.raises(ValueError, match=key):
        _mapped(**UNMAPPED[key])
    with pytest.raises(SystemExit, match=key):
        model_config({**TINY, **UNMAPPED[key]})


SHARE_UNSUPPORTED = {
    "the ep mesh": (lambda m: forward(m[0], m[1],
                                      jnp.ones((1, 4), jnp.int32),
                                      mesh=object()), "mesh"),
    "the HF loader": (lambda m: load_hf_params("/nonexistent", m[1]),
                      "HF loader"),
    "the HF exporter": (lambda m: export_hf_params(m[0], m[1],
                                                   "/nonexistent"),
                        "HF exporter"),
}


@pytest.mark.parametrize("case", list(SHARE_UNSUPPORTED))
def test_what_has_no_share_of_the_experts_raises_its_typed_error(model,
                                                                 case):
    call, names = SHARE_UNSUPPORTED[case]
    with pytest.raises(ExpertShareUnsupported) as err:
        call(model)
    assert names in err.value.mechanism
    assert model[1].name in str(err.value)


LATENT_UNSUPPORTED = {
    "kv_dtype fp8": (lambda m: make_engine(m, kv_dtype="fp8"),
                     "quantized KV"),
    "per-layer ladder": (lambda m: make_engine(
        m, kv_dtype_per_layer=("bf16", "int8", "int8", "int8")),
        "quantized KV"),
    "lora": (lambda m: init_lora(m[1], jax.random.PRNGKey(0), rank=4),
             "LoRA"),
}


@pytest.mark.parametrize("case", list(LATENT_UNSUPPORTED))
def test_lora_banks_and_a_quantized_latent_pool_stay_refused(model, case):
    call, names = LATENT_UNSUPPORTED[case]
    with pytest.raises(LatentCacheUnsupported) as err:
        call(model)
    assert names in err.value.mechanism


@pytest.mark.parametrize("change", [
    dict(moe_first_expert=12), dict(hc_mult=4), dict(kv_lora_rank=0),
    dict(num_shared_experts=1)],
    ids=["share-past-the-routed", "multi-stream", "no-latent", "shared"])
def test_a_block_the_program_cannot_build_is_refused_at_init(change):
    c = dataclasses.replace(tiny_longcat_flash_test(), **change)
    with pytest.raises(ValueError, match=c.name):
        init_params(c, jax.random.PRNGKey(0))
