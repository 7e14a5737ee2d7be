"""Xing4.0's layer (``xing4_0``) through the program at test size: the
multi-stream residual path (manifold-constrained hyper-connections, mHC:
``models.transformer._residual``) and YaRN rotary, against the plain
reference the benchmark's output check uses
(``benchmark/reference/xing4_0.py``: float32, no cache, nothing of the
program imported).

The tiny preset is the architecture map of a published-key dict: two dense
layers + two expert layers, latent attention, 4 streams, YaRN over 4 rotary
pairs, and 4 Sinkhorn rounds where the model has 20 (unrolled, 20 take the
CPU backend half a minute a program to compile; the maps alone are tested
at 20). Everything float32 at ``highest``. The
weights are the benchmark's own seeded ones (``benchmark/weights.py``:
gates 1, biases and phi drawn), so every map depends on the token.
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.archs.xing4_0 import model_config
from benchmark.reference import xing4_0 as ref
from benchmark.weights import make_weights
from senweaver_ide_tpu import obs
from senweaver_ide_tpu.models import forward, init_params
from senweaver_ide_tpu.models import transformer as tf
from senweaver_ide_tpu.models.config import (ResidualStreamUnsupported,
                                             YarnScaling, tiny_glm_moe_test,
                                             tiny_test, tiny_xing_mhc_test)
from senweaver_ide_tpu.models.load import export_hf_params, load_hf_params
from senweaver_ide_tpu.models.transformer import (forward_paged,
                                                  init_kv_cache, sinkhorn)
from senweaver_ide_tpu.ops.rotary import (rope_cos_sin, rope_frequencies,
                                          scale_frequencies_yarn,
                                          yarn_ramp_edges)
from senweaver_ide_tpu.rollout import (AdapterPool, EngineConfig,
                                       RolloutEngine)
from senweaver_ide_tpu.rollout import engine as engine_mod
from senweaver_ide_tpu.rollout.paged_kv import init_paged_pool
from senweaver_ide_tpu.rollout.sampler import SampleParams
from senweaver_ide_tpu.training.lora import init_lora

TINY = {
    "name": "tiny-xing-mhc-test", "model_type": "xing4_0",
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 160,
    "kv_lora_rank": 24, "max_position_embeddings": 128,
    "moe_intermediate_size": 48, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 8, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts_per_tok": 2,
    "num_hidden_layers": 4, "num_key_value_heads": 4,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 4,
    "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 32, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 2, "beta_slow": 0.25, "factor": 8,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 16, "vocab_size": 512, "torch_dtype": "float32",
    "matmul_precision": "highest"}
GREEDY = SampleParams(temperature=0.0, top_k=0, top_p=1.0)
SAMPLED = SampleParams(temperature=1.0, top_k=0, top_p=1.0)
# float32 at ``highest`` on both sides, logits of magnitude ~4: the two
# differ by summation order (measured 7e-6 forward, 8e-6 paged). Maps
# computed in bfloat16 move the logits by 1e-2 and more
# (``test_maps_in_bfloat16_fail_the_tolerance``).
TOL = 3e-5
FORWARD = jax.jit(forward, static_argnames=("config",))


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs._reset_for_tests()
    yield
    obs._reset_for_tests()


@pytest.fixture(scope="module")
def model():
    config = model_config(TINY)
    return make_weights(config, 2600000123), config


def test_tiny_preset_is_the_arch_map_of_its_published_keys(model):
    assert model[1] == tiny_xing_mhc_test()
    lp = model[0]["layers"]
    # what the seed gives: gates on, every bias drawn
    assert float(jnp.abs(lp["attn_hc_gate_norm"] - 1.0).max()) == 0.0
    assert float(jnp.abs(lp["mlp_hc_bias"]).mean()) > 0.3
    assert lp["attn_hc_phi"].shape == (2, 4 * 64, 24)
    assert lp["attn_hc_phi"].dtype == jnp.float32
    assert set(model[0]["dense_layers"]) >= {"attn_hc_phi", "mlp_hc_phi"}


# ---- (1) forward ---------------------------------------------------------

def test_forward_logits_equal_the_reference(model):
    params, config = model
    toks = jax.random.randint(jax.random.PRNGKey(1), (3, 40), 0, 512)
    logits, _ = FORWARD(params, config, toks)
    want = ref.logits(params, TINY, toks)
    assert float(jnp.abs(logits - want).max()) < TOL
    assert float(jnp.abs(want).max()) > 1.0


def test_maps_in_bfloat16_fail_the_tolerance(model, monkeypatch):
    """The comparison is tight enough to see the maps' precision: with the
    stream and phi rounded to bfloat16 before the projection, nothing else
    changed, the same logits miss the reference by far more than TOL."""
    params, config = model
    exact = tf._stream_maps

    def rounded(c, lp, x, sub):
        lp = dict(lp)
        lp[f"{sub}_hc_phi"] = lp[f"{sub}_hc_phi"].astype(
            jnp.bfloat16).astype(jnp.float32)
        return exact(c, lp, x.astype(jnp.bfloat16), sub)

    monkeypatch.setattr(tf, "_stream_maps", rounded)
    toks = jax.random.randint(jax.random.PRNGKey(1), (3, 40), 0, 512)
    logits, _ = forward(params, config, toks)
    assert float(jnp.abs(logits - ref.logits(params, TINY, toks)).max()
                 ) > 100 * TOL


# ---- (2) chunked prefill, then decode, through the latent pool -----------

@functools.partial(jax.jit, static_argnames=("config",))
def _paged_step(params, config, toks, pool, tables, pos):
    """One ``forward_paged`` call of row 0 at ``pos``; jitted, so that a
    chunk width compiles once for all the cases."""
    bs = pool.k.shape[2]
    return forward_paged(
        params, config, toks, pool=pool, tables=tables,
        seq_row=jnp.zeros(pos.shape, jnp.int32), positions=pos,
        write_block=tables[0, pos // bs], write_off=pos % bs,
        with_mhc_stats=True)


def _paged_run(model, toks, spans):
    params, config = model
    pool = init_paged_pool(config, 24, 4)
    tables = (jnp.arange(10, dtype=jnp.int32)[None, :] * 2 + 1)  # scattered
    out, errs = [], []
    for lo, hi in spans:
        logits, pool, err = _paged_step(
            params, config, toks[lo:hi], pool, tables,
            jnp.arange(lo, hi, dtype=jnp.int32))
        out.append(logits)
        errs.append(float(err))
    return jnp.concatenate(out, 0), errs


@pytest.mark.parametrize("chunks", [(12,), (8, 4), (1, 4, 4, 4)],
                         ids=["one-chunk", "two-chunks", "ragged-chunks"])
def test_chunked_prefill_then_paged_decode_equals_the_reference(model,
                                                                chunks):
    """The stream (T, 1, 4, D) through the layer scans and the latent pool
    against the reference's full forward, logits at EVERY position: the
    prompt in chunks, then token by token. Past position 16 the stretched
    YaRN pairs have turned measurably less than plain RoPE's."""
    s = 20
    toks = jax.random.randint(jax.random.PRNGKey(3), (s,), 0, 512)
    edges = np.concatenate([[0], np.cumsum(chunks)])
    spans = [(int(a), int(b)) for a, b in zip(edges[:-1], edges[1:])]
    spans += [(i, i + 1) for i in range(int(edges[-1]), s)]
    got, errs = _paged_run(model, toks, spans)
    want = ref.logits(model[0], TINY, toks[None])[0]
    assert float(jnp.abs(got - want).max()) < TOL
    # every call reports how far its worst H_res is from doubly stochastic
    # (4 rounds at test size: the columns are still a few percent off)
    assert all(1e-6 < e < 0.5 for e in errs)


def make_engine(model, *, num_slots=2, max_len=64, sample=GREEDY, **cfg_kw):
    params, config = model
    return RolloutEngine(
        params, config, num_slots=num_slots, max_len=max_len, sample=sample,
        engine_config=EngineConfig(kv_layout="paged", block_size=4,
                                   **cfg_kw))


@pytest.fixture(scope="module")
def served(model):
    """One engine run, spans on: a group of three and a lone request at
    temperature 1. What was served, the ``engine.step`` attrs and the
    gauge's last value (the tracer is reset around every test)."""
    obs._reset_for_tests()
    obs.enable()
    eng = make_engine(model, num_slots=4, sample=SAMPLED, step_tokens=8)
    group = list(range(1, 14))
    rids = eng.submit_group(group, 3, max_new_tokens=9)
    lone = eng.submit([7, 7, 7], max_new_tokens=9)
    eng.run()
    assert eng.kv_layout == "paged" and eng.kv_layout_fallback is None
    assert eng.stats()["group_forks"] == 2
    eng._alloc.check_leaks()
    # four requests fill the four rows: the engine runs a step ahead, and
    # a step's values are set on the span that LAUNCHED it when they come
    # home, so every step has its own
    steps = [s.attrs for s in obs.get_tracer().spans()
             if s.name == "engine.step" and "entries" in s.attrs]
    assert all("mhc_ds_err" in a for a in steps)
    gauge = obs.get_registry().get("senweaver_mhc_sinkhorn_err").value()
    obs._reset_for_tests()
    return {"requests": [(p, eng.result(r), eng.result_logps(r))
                         for p, r in [(group, r) for r in rids]
                         + [([7, 7, 7], lone)]],
            "steps": steps, "gauge": gauge, "pool": eng.pool}


def test_engine_logps_equal_the_reference(model, served):
    """What the output check compares on the chip: log p of each served
    token against the teacher-forced reference."""
    for prompt, tokens, logps in served["requests"]:
        seq = np.asarray([prompt + tokens], np.int32)
        want = ref.served_logps(model[0], TINY, seq, [len(prompt) - 1], 9)
        assert np.abs(np.asarray(logps) - np.asarray(want)[0]).max() < TOL


# ---- (3) the Sinkhorn maps -----------------------------------------------

def _sinkhorn64(logits, iters, eps=1e-6, lo=-30.0, hi=30.0):
    m = np.exp(np.clip(np.asarray(logits, np.float64), lo, hi))
    for _ in range(iters):
        m = m / (m.sum(-2, keepdims=True) + eps)
        m = m / (m.sum(-1, keepdims=True) + eps)
    return m


def _ds_err(m):
    m = np.asarray(m, np.float64)
    return max(np.abs(m.sum(-1) - 1).max(), np.abs(m.sum(-2) - 1).max())


def test_sinkhorn_equals_numpy_float64_and_needs_its_rounds():
    c = dataclasses.replace(tiny_xing_mhc_test(), hc_sinkhorn_iters=20)
    logits = jax.random.normal(jax.random.PRNGKey(4), (256, 4, 4))
    got = sinkhorn(logits, c)
    # float32 against float64: 20 rounds of two divisions
    assert np.abs(np.asarray(got) - _sinkhorn64(logits, 20)).max() < 1e-5
    assert _ds_err(got) < 1e-3
    five = sinkhorn(logits, dataclasses.replace(c, hc_sinkhorn_iters=5))
    assert _ds_err(five) > 10 * _ds_err(got)
    assert np.abs(np.asarray(five) - _sinkhorn64(logits, 5)).max() < 1e-5


def test_clamp_holds_for_logits_of_a_thousand():
    c = dataclasses.replace(tiny_xing_mhc_test(), hc_sinkhorn_iters=20)
    logits = 1e3 * jnp.sign(jax.random.normal(jax.random.PRNGKey(5),
                                              (64, 4, 4)))
    got = np.asarray(sinkhorn(logits, c))
    assert np.isfinite(got).all()
    assert np.abs(got - _sinkhorn64(logits, 20)).max() < 1e-5
    # unclamped, exp(1000) is inf and the first division NaN
    wide = dataclasses.replace(c, mhc_h_res_clamp_min=-2e3,
                               mhc_h_res_clamp_max=2e3)
    assert not np.isfinite(np.asarray(sinkhorn(logits, wide))).all()


def test_maps_are_bounded_and_doubly_stochastic(model):
    params, config = model
    config = dataclasses.replace(config, hc_sinkhorn_iters=20)
    lp = {k: v[0] for k, v in params["layers"].items()}
    x = jax.random.normal(jax.random.PRNGKey(6), (50, 4, 64))
    h_pre, h_post, h_res, err = tf._stream_maps(config, lp, x, "mlp")
    want = ref.maps(dict(TINY, hc_sinkhorn_iters=20), x, lp, "mlp")
    for a, b in zip((h_pre, h_post, h_res), want):
        assert float(jnp.abs(a - b).max()) < 1e-5
    assert 0 < float(h_pre.min()) and float(h_pre.max()) < 1
    assert 0 < float(h_post.min()) and float(h_post.max()) < 2
    assert float(h_res.min()) > 0 and float(err) == pytest.approx(
        _ds_err(h_res), abs=1e-6)
    # the maps are the token's own: they differ from token to token
    assert float(jnp.abs(h_res - h_res[:1]).max()) > 0.05


# ---- (4) the operator's plain forms --------------------------------------

def test_fresh_model_computes_the_plain_models_function():
    """``init_params``: gates 0, sum H_pre = 1, H_post = 1, so equal rows
    stay equal and the final norm forgets the factor 4. Not to the last
    bit: each of the 4 x 2 divisions adds ``hc_eps`` 1e-6 to a sum of 1,
    so a row keeps 1 - 4e-6 of itself a sublayer, 8 sublayers."""
    c = tiny_xing_mhc_test()
    plain = dataclasses.replace(c, hc_mult=0)
    key = jax.random.PRNGKey(0)
    p, p0 = init_params(c, key), init_params(plain, key)
    # the stream's leaves are extra; every other leaf is bit-equal
    for stack in ("layers", "dense_layers"):
        assert {k for k in p[stack] if k not in p0[stack]} == {
            f"{s}_hc_{n}" for s in ("attn", "mlp")
            for n in ("phi", "gate_norm", "bias")}
        for k, v in p0[stack].items():
            assert np.array_equal(np.asarray(v), np.asarray(p[stack][k]))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 20), 0, 512)
    got, _ = FORWARD(p, c, toks)
    want, _ = FORWARD(p0, plain, toks)
    assert float(jnp.abs(got - want).max()) < 1e-3
    assert float(jnp.abs(want).max()) > 1.0


def test_one_stream_with_open_maps_is_the_plain_residual():
    """n = 1, alpha = 0, b_pre = 30, b_post = 0: H_pre = sigmoid(30) = 1 in
    float32, H_post = 1, H_res = 1 - 4e-5 (``hc_eps``, 20 rounds): x + f(x) to
    rounding, through ``forward`` and through ``forward_paged``."""
    plain = dataclasses.replace(tiny_test(), qkv_bias=False)
    c = dataclasses.replace(plain, hc_mult=1)
    key = jax.random.PRNGKey(0)
    p, p0 = init_params(c, key), init_params(plain, key)
    assert float(p["layers"]["attn_hc_bias"][0, 0, 0]) == 30.0
    assert float(jnp.abs(p["layers"]["mlp_hc_gate_norm"]).max()) == 0.0
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 512)
    got, _ = FORWARD(p, c, toks)
    want, _ = FORWARD(p0, plain, toks)
    assert float(jnp.abs(got - want).max()) < 1e-3
    pos = jnp.arange(16, dtype=jnp.int32)
    kw = dict(tables=jnp.arange(1, 5, dtype=jnp.int32)[None],
              seq_row=jnp.zeros((16,), jnp.int32), positions=pos,
              write_block=1 + pos // 4, write_off=pos % 4)
    paged, _ = jax.jit(functools.partial(forward_paged, config=c, **kw))(
        p, tokens=toks[0], pool=init_paged_pool(c, 8, 4))
    assert float(jnp.abs(paged - want[0]).max()) < 1e-3


def _literal_parent_residual(c, lp, x, sub, f):
    """The five sites as the parent commit wrote them out: x = x + f(x)."""
    y, extra = f(x)
    return x + y, extra, None


PARENT_LEAVES = {
    "tiny-test": {
        "layers": ["attn_norm", "bk", "bq", "bv", "mlp_norm", "w_down",
                   "w_gate", "w_up", "wk", "wo", "wq", "wv"]},
    "tiny-glm-moe-test": {
        "dense_layers": ["attn_norm", "kv_a_norm", "mlp_norm", "q_a_norm",
                         "w_down", "w_gate", "w_up", "wkv_a", "wkv_b", "wo",
                         "wq_a", "wq_b"],
        "layers": ["attn_norm", "kv_a_norm", "mlp_norm", "q_a_norm",
                   "router", "router_bias_norm", "w_down", "w_gate", "w_up",
                   "wkv_a", "wkv_b", "wo", "wq_a", "wq_b", "ws_down",
                   "ws_gate", "ws_up"]}}


@pytest.mark.parametrize("preset", [tiny_test, tiny_glm_moe_test],
                         ids=["qwen", "glm"])
def test_without_streams_nothing_changes(preset, monkeypatch):
    """``hc_mult == 0``: the parameter tree has the parent's leaves and no
    other, and ``forward_paged`` gives, bit for bit, what it gives with the
    residual operator replaced by the parent's literal ``x + f(x)``. (The
    parent's own outputs were compared once, on this machine, when the
    operator was written: CHANGES.md, PR 30.)"""
    c = preset()
    params = init_params(c, jax.random.PRNGKey(0))
    assert {k: sorted(v) for k, v in params.items()
            if isinstance(v, dict)} == PARENT_LEAVES[c.name]
    toks = jax.random.randint(jax.random.PRNGKey(2), (12,), 0, 512)
    pos = jnp.arange(12, dtype=jnp.int32)
    kw = dict(tables=jnp.arange(1, 5, dtype=jnp.int32)[None],
              seq_row=jnp.zeros((12,), jnp.int32), positions=pos,
              write_block=1 + pos // 4, write_off=pos % 4)
    got, pool = forward_paged(params, c, toks,
                              pool=init_paged_pool(c, 8, 4), **kw)
    monkeypatch.setattr(tf, "_residual", _literal_parent_residual)
    want, want_pool = forward_paged(params, c, toks,
                                    pool=init_paged_pool(c, 8, 4), **kw)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.array_equal(np.asarray(pool.k), np.asarray(want_pool.k))


# ---- (5) YaRN -------------------------------------------------------------

def test_yarn_frequencies_by_hand():
    """d = 64, base 10000, L0 = 4096, beta 32 and 1 (the published keys):
    64 ln(4096 / (2 pi 32)) / (2 ln 10000) = 10.47 -> lo 10;
    64 ln(4096 / (2 pi)) / (2 ln 10000) = 22.51 -> hi 23."""
    kw = dict(beta_fast=32.0, beta_slow=1.0, original_max_position=4096)
    assert yarn_ramp_edges(64, 10000.0, **kw) == (10, 23)
    f = np.asarray(rope_frequencies(64, 10000.0), np.float64)
    got = np.asarray(scale_frequencies_yarn(
        rope_frequencies(64, 10000.0), head_dim=64, theta=10000.0,
        factor=64.0, **kw), np.float64)
    ramp = np.clip((np.arange(32) - 10) / 13.0, 0, 1)
    np.testing.assert_allclose(got, f / 64 * ramp + f * (1 - ramp),
                               rtol=1e-6)
    assert np.array_equal(got[:11], f[:11].astype(np.float32))   # kept
    np.testing.assert_allclose(got[23:], f[23:] / 64, rtol=1e-6)
    assert f[16] / 64 < got[16] < f[16]                          # the ramp
    # the reference's own arithmetic agrees, and so do the magnitudes
    cfg = {"qk_rope_head_dim": 64, "rope_theta": 10000, "rope_scaling": {
        "factor": 64, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}}
    inv, magnitude, scale_by, lo, hi = ref.yarn(cfg)
    np.testing.assert_allclose(inv, got, rtol=1e-6)
    m = 0.1 * math.log(64) + 1
    assert (lo, hi, magnitude) == (10, 23, 1.0)
    assert scale_by == pytest.approx(m * m) == pytest.approx(1.41589 ** 2,
                                                             rel=1e-5)


def test_yarn_scale_enters_the_configs_attention_scale():
    c = tiny_xing_mhc_test()
    m = 0.1 * math.log(8.0) + 1.0
    assert c.attn_scale == pytest.approx(m * m / 4.0)       # head_dim 16
    assert tiny_glm_moe_test().attn_scale == 1.0 / 12 ** 0.5
    ys = YarnScaling(factor=8.0, original_max_position=16, mscale=1.0,
                     mscale_all_dim=0.0)
    assert dataclasses.replace(c, rope_scaling=ys).attn_scale == 0.25
    cos, _ = rope_cos_sin(jnp.arange(4), 8, 10000.0, scaling=ys)
    assert float(cos[0, 0]) == pytest.approx(m)     # m(1) / m(0) on cos/sin


def test_yarn_of_factor_one_is_plain_rope():
    pos = jnp.arange(40)
    one = YarnScaling(factor=1.0, original_max_position=16, beta_fast=2.0,
                      beta_slow=0.25, mscale=1.0, mscale_all_dim=1.0)
    for got, want in zip(rope_cos_sin(pos, 8, 10000.0, scaling=one),
                         rope_cos_sin(pos, 8, 10000.0)):
        assert np.array_equal(np.asarray(got), np.asarray(want))
    eight = dataclasses.replace(one, factor=8.0)
    cos8, _ = rope_cos_sin(pos, 8, 10000.0, scaling=eight)
    cos1, _ = rope_cos_sin(pos, 8, 10000.0)
    assert np.array_equal(np.asarray(cos8[:, 0]), np.asarray(cos1[:, 0]))
    assert float(jnp.abs(cos8[:, 1:] - cos1[:, 1:]).max()) > 0.1


def test_yarn_for_a_non_latent_model_raises():
    c = dataclasses.replace(tiny_test(), rope_scaling=YarnScaling(
        factor=8.0, original_max_position=16))
    params = init_params(c, jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="YaRN"):
        forward(params, c, jnp.ones((1, 4), jnp.int32))


# ---- (6) what has no multi-stream form raises, by name -------------------

def _construct(model, config=None, engine_config=None, **kw):
    params, c = model
    return RolloutEngine(params, config or c, num_slots=2, max_len=64,
                         sample=GREEDY, engine_config=engine_config, **kw)


@functools.lru_cache(maxsize=None)
def _dense_streams():
    """A non-latent model with streams: what latent attention's own
    refusals would otherwise answer first."""
    c = dataclasses.replace(tiny_test(), name="tiny-dense-mhc", hc_mult=4)
    return init_params(c, jax.random.PRNGKey(0)), c


UNSUPPORTED = {
    "slot layout": (lambda m: _construct(
        _dense_streams(), engine_config=EngineConfig(kv_layout="slots")),
        "slot KVCache"),
    "slot fallback (ring)": (lambda m: _construct(
        (_dense_streams()[0], dataclasses.replace(
            _dense_streams()[1], sliding_window=8))), "slot KVCache"),
    "forward with a cache": (lambda m: forward(
        *_dense_streams(), jnp.ones((1, 4), jnp.int32),
        cache=init_kv_cache(tiny_test(), 1, 32)), "slot KVCache"),
    "engine mesh": (lambda m: _construct(_dense_streams(), mesh=object()),
                    "mesh"),
    "forward mesh": (lambda m: forward(
        *_dense_streams(), jnp.ones((1, 4), jnp.int32), mesh=object()),
        "mesh"),
    "adapter pool": (lambda m: _construct(_dense_streams(),
                                          adapter_pool=object()),
                     "adapter pool"),
    "adapter pool itself": (lambda m: AdapterPool(_dense_streams()[1]),
                            "adapter pool"),
    "adapter banks": (lambda m: forward_paged(
        *_dense_streams(), jnp.zeros((2,), jnp.int32),
        pool=init_paged_pool(_dense_streams()[1], 4, 4),
        tables=jnp.zeros((1, 2), jnp.int32),
        seq_row=jnp.zeros((2,), jnp.int32),
        positions=jnp.arange(2, dtype=jnp.int32),
        write_block=jnp.zeros((2,), jnp.int32),
        write_off=jnp.arange(2, dtype=jnp.int32), adapters=({},),
        adapter_ids=(jnp.zeros((2,), jnp.int32),)), "adapter banks"),
    "lora": (lambda m: init_lora(_dense_streams()[1], jax.random.PRNGKey(0),
                                 rank=4), "LoRA"),
    "hf loader": (lambda m: load_hf_params("/nonexistent", m[1]),
                  "HF loader"),
    "hf exporter": (lambda m: export_hf_params(m[0], m[1], "/nonexistent"),
                    "HF exporter"),
}


@pytest.mark.parametrize("case", list(UNSUPPORTED))
def test_unsupported_mechanisms_raise_their_typed_error(model, case):
    call, names = UNSUPPORTED[case]
    with pytest.raises(ResidualStreamUnsupported) as err:
        call(model)
    assert names in err.value.mechanism
    assert "hc_mult" in str(err.value)


# ---- tracing --------------------------------------------------------------

def test_step_reports_its_worst_map_in_the_one_fetch(model, served):
    """``mhc_ds_err`` rides behind the step's log-probs: an attr of
    ``engine.step`` and the gauge ``senweaver_mhc_sinkhorn_err``."""
    steps = served["steps"]
    assert steps and all(1e-6 < a["mhc_ds_err"] < 0.5 for a in steps)
    assert all("experts_touched" in a for a in steps)
    assert served["gauge"] == steps[-1]["mhc_ds_err"]
    assert all(len(logps) == 9 for _, _, logps in served["requests"])
    # the step itself: T log-probs and one float, T tokens, the experts'
    # two counts and the attention plan's two
    plan = np.zeros((6, 4), np.int32)
    plan[3] = served["pool"].num_blocks
    toks, logp, _, _, _ = engine_mod._paged_fused_step(
        model[0], model[1], plan, np.zeros((4, 16), np.int32),
        served["pool"], jax.random.PRNGKey(0), np.zeros((4,), np.int32),
        SAMPLED, None)
    assert toks.shape == (8,) and logp.shape == (5,)
    assert 1e-6 < float(logp[-1]) < 0.5


def test_a_plain_models_engine_has_no_stream_instrument():
    obs.enable()
    c = tiny_glm_moe_test()
    eng = RolloutEngine(init_params(c, jax.random.PRNGKey(0)), c,
                        num_slots=2, max_len=32, sample=GREEDY,
                        engine_config=EngineConfig(block_size=4))
    eng.submit([1, 2, 3], max_new_tokens=2)
    eng.run()
    assert eng._mhc_gauge is None
    assert obs.get_registry().get("senweaver_mhc_sinkhorn_err") is None
    assert not any("mhc_ds_err" in s.attrs
                   for s in obs.get_tracer().spans())
