"""GLM-4.7-Flash's layer (``glm4_moe_lite``) through the program, at test
size, against the plain reference the benchmark's output check uses
(``benchmark/reference/glm4_moe_lite.py``: expanded attention, float32, no
cache, nothing of the program imported).

The tiny preset is the architecture map of a published-key dict: one dense
layer + two expert layers, 8 routed experts top-2 + 1 shared, sigmoid router
with a correction bias and the 1.8 scaling, latent attention with q/k width
8 + 4 and value width 16. Everything float32 at ``highest``; the tests draw
their own correction bias (the benchmark's is a constant).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.archs.glm4_moe_lite import model_config
from benchmark.reference import glm4_moe_lite as ref
from senweaver_ide_tpu import obs
from senweaver_ide_tpu.models import forward, init_params
from senweaver_ide_tpu.models import moe
from senweaver_ide_tpu.models.config import (LatentCacheUnsupported,
                                             tiny_glm_moe_test,
                                             tiny_moe_test, tiny_test)
from senweaver_ide_tpu.models.quantize import quantize_weights_int8
from senweaver_ide_tpu.models.transformer import (_mlp, forward_paged,
                                                  init_kv_cache)
from senweaver_ide_tpu.rollout import (AdapterPool, EngineConfig,
                                       RolloutEngine)
from senweaver_ide_tpu.rollout import engine as engine_mod
from senweaver_ide_tpu.rollout.migration import DecodeCheckpoint
from senweaver_ide_tpu.rollout.paged_kv import (copy_blocks,
                                                gather_blocks_quant,
                                                init_paged_pool,
                                                install_blocks_quant,
                                                pool_bytes_per_block)
from senweaver_ide_tpu.rollout.sampler import SampleParams
from senweaver_ide_tpu.training.lora import init_lora

TINY = {
    "name": "tiny-glm-moe-test", "model_type": "glm4_moe_lite",
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 160, "max_position_embeddings": 128,
    "moe_intermediate_size": 48, "topk_method": "noaux_tc",
    "norm_topk_prob": True, "num_attention_heads": 4, "n_group": 1,
    "topk_group": 1, "n_routed_experts": 8, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 2,
    "first_k_dense_replace": 1, "num_hidden_layers": 3,
    "num_key_value_heads": 4, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-5, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 32,
    "kv_lora_rank": 24, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
    "v_head_dim": 16, "vocab_size": 512, "torch_dtype": "float32",
    "matmul_precision": "highest"}
GREEDY = SampleParams(temperature=0.0, top_k=0, top_p=1.0)
SAMPLED = SampleParams(temperature=1.0, top_k=0, top_p=1.0)
PROMPT = [5, 9, 2, 7, 1, 3]
# float32 at ``highest`` on both sides: the two differ by summation order
TOL = 2e-5


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs._reset_for_tests()
    yield
    obs._reset_for_tests()


@pytest.fixture(scope="module")
def model():
    config = model_config(TINY)
    params = init_params(config, jax.random.PRNGKey(0))
    # a correction bias of the size of the scores' own spread: it changes
    # many choices, so a bias that leaked into the weights would show
    params["layers"]["router_bias_norm"] = 0.2 * jax.random.normal(
        jax.random.PRNGKey(5), (2, 8))
    return params, config


def make_engine(model, *, num_slots=2, max_len=64, sample=GREEDY, **cfg_kw):
    params, config = model
    return RolloutEngine(
        params, config, num_slots=num_slots, max_len=max_len, sample=sample,
        engine_config=EngineConfig(kv_layout="paged", block_size=4,
                                   **cfg_kw))


def independent(model, prompt=PROMPT, max_new=12):
    eng = make_engine(model)
    rid = eng.submit(list(prompt), max_new_tokens=max_new)
    out = eng.run()[rid]
    return out, eng.result_logps(rid)


def test_tiny_preset_is_the_arch_map_of_its_published_keys():
    assert model_config(TINY) == tiny_glm_moe_test()


# ---- (1) forward, expanded form ------------------------------------------

def test_forward_logits_equal_the_reference(model):
    params, config = model
    toks = jax.random.randint(jax.random.PRNGKey(1), (3, 40), 0, 512)
    logits, _, aux = forward(params, config, toks, with_aux=True)
    want = ref.logits(params, TINY, toks)
    assert float(jnp.abs(logits - want).max()) < TOL
    assert float(jnp.abs(want).max()) > 1.0
    assert float(aux) == 0.0        # a sigmoid_bias router has no aux loss


def test_reference_rounds_of_any_size_leave_no_pair_out(model, monkeypatch):
    params, _ = model
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 24), 0, 512)
    want = ref.logits(params, TINY, toks)
    monkeypatch.setattr(ref, "CAP", 4)      # many rounds an expert
    assert float(jnp.abs(ref.logits(params, TINY, toks) - want).max()) < TOL


# ---- (2) chunked prefill, then decode, through the latent pool -----------

def _paged_run(model, toks, spans):
    """Feed ``toks`` (S,) through ``forward_paged`` in the given position
    spans, one call each, over a fresh pool; every call's logits."""
    params, config = model
    bs = 4
    pool = init_paged_pool(config, 24, bs)
    tables = (jnp.arange(10, dtype=jnp.int32)[None, :] * 2 + 1)  # scattered
    out = []
    for lo, hi in spans:
        pos = jnp.arange(lo, hi, dtype=jnp.int32)
        logits, pool = forward_paged(
            params, config, toks[lo:hi], pool=pool, tables=tables,
            seq_row=jnp.zeros((hi - lo,), jnp.int32), positions=pos,
            write_block=tables[0, pos // bs], write_off=pos % bs)
        out.append(logits)
    return jnp.concatenate(out, 0), pool


@pytest.mark.parametrize("chunks", [(13,), (5, 8), (1, 4, 4, 4)],
                         ids=["one-chunk", "two-chunks", "ragged-chunks"])
def test_chunked_prefill_then_paged_decode_equals_the_reference(model,
                                                                chunks):
    """Absorbed attention over the latent cache against the reference's
    expanded full forward, at EVERY position: the prompt in chunks, then
    token by token."""
    s = 24
    toks = jax.random.randint(jax.random.PRNGKey(3), (s,), 0, 512)
    edges = np.concatenate([[0], np.cumsum(chunks)])
    spans = [(int(a), int(b)) for a, b in zip(edges[:-1], edges[1:])]
    spans += [(i, i + 1) for i in range(int(edges[-1]), s)]
    got, pool = _paged_run(model, toks, spans)
    want = ref.logits(model[0], TINY, toks[None])[0]
    assert float(jnp.abs(got - want).max()) < TOL
    # the pool holds one latent row a token and no values
    config = model[1]
    assert pool.k.shape == (3, 24, 4, 1, config.latent_row_dim)
    assert pool.v.shape[-1] == 0 and pool.k_scale is None
    assert pool_bytes_per_block(pool) == (
        3 * 4 * config.latent_row_dim * 4)


def test_flat_batch_mixes_a_decode_row_and_a_prefill_chunk(model):
    """Two sequences in one flat batch, one decoding and one prefilling:
    each entry's logits are those of its own sequence alone."""
    params, config = model
    a = jax.random.randint(jax.random.PRNGKey(4), (10,), 0, 512)
    b = jax.random.randint(jax.random.PRNGKey(5), (7,), 0, 512)
    bs, pool = 4, init_paged_pool(config, 16, 4)
    tables = jnp.asarray([[1, 3, 5, 7], [2, 4, 6, 8]], jnp.int32)

    def step(pool, toks, rows, pos):
        rows, pos = jnp.asarray(rows, jnp.int32), jnp.asarray(pos, jnp.int32)
        return forward_paged(
            params, config, jnp.asarray(toks, jnp.int32), pool=pool,
            tables=tables, seq_row=rows, positions=pos,
            write_block=tables[rows, pos // bs], write_off=pos % bs)

    _, pool = step(pool, a[:9], [0] * 9, range(9))
    got, pool = step(pool, jnp.concatenate([a[9:], b]), [0] + [1] * 7,
                     [9] + list(range(7)))
    want_a = ref.logits(params, TINY, a[None])[0, 9]
    want_b = ref.logits(params, TINY, b[None])[0]
    assert float(jnp.abs(got[0] - want_a).max()) < TOL
    assert float(jnp.abs(got[1:] - want_b).max()) < TOL


# ---- (3) the expert layer alone ------------------------------------------

def _expert_layer_inputs(model, bias):
    params, config = model
    lp = {k: v[0] for k, v in params["layers"].items()}
    lp["router_bias_norm"] = jnp.asarray(bias, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 24, 64))
    return config, lp, x


BIASES = {
    "random-bias": 0.2 * np.random.default_rng(0).standard_normal(8),
    "every-token-on-the-same-two": np.array([0, 0, 9, 0, 0, 9, 0, 0.]),
    "an-expert-with-no-token": np.array([0, 0, 0, -9, 0, 0, 0, 0.]),
    "no-bias": np.zeros(8)}


@pytest.mark.parametrize("case", list(BIASES))
def test_expert_layer_equals_the_reference(model, case):
    """Routed experts, weights, the 1.8 and the shared expert, whatever the
    load: every token on one pair of experts, an expert with none."""
    config, lp, x = _expert_layer_inputs(model, BIASES[case])
    got, _, stats, _ = _mlp(config, lp, x)
    h = ref._rms_norm(x[0], lp["mlp_norm"], config.rms_norm_eps)
    want, _ = ref.expert_layer(TINY, None, h, lp)
    assert float(jnp.abs(got[0] - x[0] - want).max()) < TOL
    idx, _, _ = moe.route(config, lp, h)
    load = np.bincount(np.asarray(idx).ravel(), minlength=8)
    assert int(stats.expert_load_max) == load.max()
    assert int(stats.experts_touched) == (load > 0).sum()
    if case == "every-token-on-the-same-two":
        assert load.tolist() == [0, 0, 24, 0, 0, 24, 0, 0]
    if case == "an-expert-with-no-token":
        assert load[3] == 0


def test_bias_enters_the_choice_and_never_the_weights(model):
    config, lp, x = _expert_layer_inputs(model,
                                         [0, 0, 0, 0, 0, 0, 9.0, 9.0])
    h = ref._rms_norm(x[0], lp["mlp_norm"], config.rms_norm_eps)
    idx, w, aux = moe.route(config, lp, h)
    s = jax.nn.sigmoid(jnp.dot(h, lp["router"],
                               precision=jax.lax.Precision.HIGHEST))
    assert np.array_equal(np.sort(np.asarray(idx), -1),
                          np.tile([6, 7], (24, 1)))
    chosen = jnp.take_along_axis(s, idx, -1)
    want = 1.8 * chosen / chosen.sum(-1, keepdims=True)
    assert float(jnp.abs(w - want).max()) < 1e-6
    assert abs(float(w.sum(-1).mean()) - 1.8) < 1e-5 and float(aux) == 0.0
    # without the bias other experts are chosen: the bias did change it
    lp0 = dict(lp, router_bias_norm=jnp.zeros(8))
    assert not np.array_equal(np.sort(np.asarray(moe.route(
        config, lp0, h)[0]), -1), np.tile([6, 7], (24, 1)))


def test_a_tokens_result_does_not_depend_on_its_batch(model):
    config, lp, x = _expert_layer_inputs(model, BIASES["random-bias"])
    together, _, _ = moe.expert_ffn(config, lp, x[0])
    for i in (0, 7, 23):
        alone, _, _ = moe.expert_ffn(config, lp, x[0, i:i + 1])
        assert float(jnp.abs(alone[0] - together[i]).max()) < 1e-6
    # counted entries only: padding is routed, and is not work
    some = jnp.arange(24) < 3
    _, _, stats = moe.expert_ffn(config, lp, x[0], some)
    assert int(stats.expert_load_max) <= 3
    assert int(stats.experts_touched) <= 6


def test_whole_stack_banks_equal_the_layers_own_slice(model):
    """The paged scan hands the grouped products the whole (L, E, ...)
    bank and the layer's index instead of a copy of its slice."""
    params, config = model
    x = jax.random.normal(jax.random.PRNGKey(8), (17, 64))
    for layer in (0, 1):
        lp = {k: v[layer] for k, v in params["layers"].items()}
        own, _, _ = moe.expert_ffn(config, lp, x)
        whole = dict(lp, **{k: params["layers"][k] for k in moe.BANKS
                            if k in params["layers"]})
        got, _, _ = moe.expert_ffn(config, whole, x,
                                   stack_layer=jnp.asarray(layer))
        assert float(jnp.abs(got - own).max()) < 1e-6


def _plain_softmax_moe(config, lp, h):
    """Dropless top-k softmax routing, token by token, in numpy."""
    h = np.asarray(h, np.float64)
    logits = h @ np.asarray(lp["router"], np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    out = np.zeros_like(h)
    for t in range(h.shape[0]):
        top = np.argsort(-p[t])[:config.num_experts_per_tok]
        for e in top:
            g = h[t] @ np.asarray(lp["w_gate"][e], np.float64)
            u = h[t] @ np.asarray(lp["w_up"][e], np.float64)
            act = g / (1 + np.exp(-g)) * u
            out[t] += (p[t, e] / p[t, top].sum()) * (
                act @ np.asarray(lp["w_down"][e], np.float64))
    return out


@pytest.mark.parametrize("int8", [False, True], ids=["float", "int8-banks"])
def test_softmax_router_layer_drops_nothing(int8):
    """tiny-moe-test (Mixtral form) through the same layer: every token
    gets both its experts whatever the load, where the capacity-bounded
    path dropped the overflow. int8 banks: the scale is applied per row's
    expert and output channel."""
    config = tiny_moe_test()
    params = init_params(config, jax.random.PRNGKey(0))
    if int8:
        params = quantize_weights_int8(params)
    lp = {k: v[0] for k, v in params["layers"].items()}
    # a router that sends nearly every token to experts 0 and 1
    lp["router"] = lp["router"].at[:, :2].add(
        4.0 * jnp.sign(lp["router"][:, :1]))
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(9), (32, 64)))
    got, aux, stats = moe.expert_ffn(config, lp, h)
    plain = dict(lp)
    if int8:
        for n in ("w_gate", "w_up", "w_down"):
            plain[n] = (lp[n].astype(jnp.float32)
                        * lp[n + "_scale"][:, None, :])
    want = _plain_softmax_moe(config, plain, h)
    assert float(np.abs(np.asarray(got) - want).max()) < 1e-4
    assert int(stats.expert_load_max) > 32 * 2 / 4 * 1.25   # over capacity
    assert float(aux) > 0.0


# ---- (4) the engine ------------------------------------------------------

def test_engine_logps_equal_the_reference(model):
    """What the output check compares on the chip: log p of each served
    token, sampled at temperature 1, against the teacher-forced reference."""
    eng = make_engine(model, num_slots=4, sample=SAMPLED, step_tokens=8)
    prompts = [list(range(1, 14)), [7, 7, 7], list(range(30, 51))]
    rids = [eng.submit(p, max_new_tokens=9) for p in prompts]
    eng.run()
    assert eng.kv_layout == "paged" and eng.kv_layout_fallback is None
    for p, rid in zip(prompts, rids):
        seq = np.asarray([p + eng.result(rid)], np.int32)
        want = ref.served_logps(model[0], TINY, seq, [len(p) - 1], 9)
        got = np.asarray(eng.result_logps(rid))
        assert np.abs(got - np.asarray(want)[0]).max() < TOL
    eng._alloc.check_leaks()


def test_request_is_served_the_same_alone_and_beside_other_traffic(model):
    """Nothing is dropped and no capacity is shared, so which prefill
    chunk or decode row shares a step cannot move a request's tokens."""
    want, want_lp = independent(model)
    eng = make_engine(model, num_slots=4, step_tokens=8)
    others = [eng.submit(list(range(40, 40 + n)), max_new_tokens=6)
              for n in (17, 9)]
    rid = eng.submit(PROMPT, max_new_tokens=12)
    late = None
    while eng.has_work:
        eng.step()
        if late is None and len(eng._requests[rid].tokens) >= 3:
            late = eng.submit(list(range(60, 83)), max_new_tokens=4)
    assert eng.result(rid) == want
    np.testing.assert_allclose(eng.result_logps(rid), want_lp, atol=1e-5)
    assert all(eng.is_done(r) for r in others + [late])
    eng._alloc.check_leaks()


def test_group_followers_fork_the_donors_latent_blocks(model):
    """One prefill a group; the followers graft the donor's table (a fork:
    refcounts, no bytes) and their first write into the shared boundary
    block copies it (COW) — a latent block moves like any other."""
    want, _ = independent(model)
    eng = make_engine(model, num_slots=8, max_len=96)
    rids = eng.submit_group(PROMPT, 8, max_new_tokens=12)   # 6 = 1.5 blocks
    out = eng.run()
    for r in rids:
        np.testing.assert_array_equal(np.asarray(out[r]), np.asarray(want))
    s = eng.stats()
    assert (s["prefills"], s["group_prefills"], s["group_forks"],
            s["group_degrades"]) == (1, 1, 7, 0)
    assert eng._alloc.counters()["cow_copies"] >= 7
    eng._alloc.check_leaks()


def test_group_followers_sample_from_the_donors_logits(model):
    """At temperature 1 every member's first log p is under the same
    distribution: the reference's at the prompt's last position."""
    eng = make_engine(model, num_slots=4, max_len=96, sample=SAMPLED)
    rids = eng.submit_group(PROMPT, 4, max_new_tokens=5)
    eng.run()
    logp = jax.nn.log_softmax(
        ref.logits(model[0], TINY, jnp.asarray([PROMPT]))[0, -1])
    for r in rids:
        assert abs(eng.result_logps(r)[0]
                   - float(logp[eng.result(r)[0]])) < TOL
    assert len({tuple(eng.result(r)) for r in rids}) > 1
    eng._alloc.check_leaks()


# ---- (5) the movers, on latent blocks ------------------------------------

@pytest.mark.parametrize("steps", [1, 4, 9])
def test_decode_checkpoint_round_trips_latent_blocks(model, steps):
    want, _ = independent(model)
    a, b = make_engine(model), make_engine(model)
    rid = a.submit(PROMPT, max_new_tokens=12)
    for _ in range(steps):
        a.step()
    ckpt = DecodeCheckpoint.from_wire(a.checkpoint_request(rid).to_wire())
    assert ckpt.kv_k.shape[-2:] == (1, model[1].latent_row_dim)
    assert ckpt.kv_v.shape[-1] == 0
    new_rid = b.restore_request(ckpt)
    assert a.release_request(rid)
    np.testing.assert_array_equal(np.asarray(b.run()[new_rid]),
                                  np.asarray(want))
    assert b.stats()["migrations_in"] == 1
    a._alloc.check_leaks()
    b._alloc.check_leaks()


def test_swap_out_and_restore_move_a_latent_block_whole(model):
    """The host tier's movers (gather to host numpy, install into other
    blocks) and the COW copy, on a latent pool: the restored sequence
    decodes as if it had never left."""
    params, config = model
    toks = jax.random.randint(jax.random.PRNGKey(11), (14,), 0, 512)
    want = ref.logits(params, TINY, toks[None])[0]
    _, pool = _paged_run(model, toks, [(0, 12)])       # blocks 1, 3, 5
    host = jax.device_get(gather_blocks_quant(
        pool, jnp.asarray([1, 3, 5], jnp.int32)))
    assert isinstance(host.k, np.ndarray) and host.k.shape[1] == 3
    pool = init_paged_pool(config, 24, 4)              # everything gone
    pool = install_blocks_quant(pool, host, jnp.asarray([20, 2, 9], jnp.int32))
    pool = copy_blocks(pool, jnp.asarray([9], jnp.int32),
                       jnp.asarray([11], jnp.int32))   # COW of the last
    tables = jnp.asarray([[20, 2, 11, 13]], jnp.int32)
    out = []
    for i in (12, 13):
        logits, pool = forward_paged(
            params, config, toks[i:i + 1], pool=pool, tables=tables,
            seq_row=jnp.zeros((1,), jnp.int32),
            positions=jnp.asarray([i], jnp.int32),
            write_block=tables[0, i // 4][None],
            write_off=jnp.asarray([i % 4], jnp.int32))
        out.append(logits[0])
    assert float(jnp.abs(jnp.stack(out) - want[12:]).max()) < TOL


# ---- (6) what has no latent form raises, by name -------------------------

def _construct(model, config=None, engine_config=None, **kw):
    params, c = model
    return RolloutEngine(params, config or c, num_slots=2, max_len=64,
                         sample=GREEDY, engine_config=engine_config, **kw)


UNSUPPORTED = {
    "slot layout": (lambda m: _construct(
        m, engine_config=EngineConfig(kv_layout="slots")), "slot KVCache"),
    "kv_dtype fp8": (lambda m: _construct(
        m, engine_config=EngineConfig(kv_dtype="fp8")), "quantized KV"),
    "kv_dtype int8": (lambda m: _construct(
        m, engine_config=EngineConfig(kv_dtype="int8")), "quantized KV"),
    "per-layer ladder": (lambda m: _construct(
        m, engine_config=EngineConfig(
            kv_dtype_per_layer=("bf16", "int8", "int8"))), "quantized KV"),
    "adapter pool": (lambda m: _construct(m, adapter_pool=object()),
                     "adapter pool"),
    "tensor parallel": (lambda m: _construct(m, mesh=object()),
                        "tensor-parallel"),
    "sliding window": (lambda m: _construct(m, config=dataclasses.replace(
        m[1], sliding_window=8)), "ring cache"),
    "kv_quant": (lambda m: _construct(m, config=dataclasses.replace(
        m[1], kv_quant=True)), "kv_quant"),
    "speculation": (lambda m: _construct(m).enable_speculation(
        m[0], m[1], depth=2), "speculation"),
    "registered prefix": (lambda m: _construct(m).register_prefix(
        [1, 2, 3, 4, 5]), "slot KVCache"),
    "slot cache": (lambda m: init_kv_cache(m[1], 1, 32), "slot KVCache"),
    "forward with a cache": (lambda m: forward(
        m[0], m[1], jnp.ones((1, 4), jnp.int32),
        cache=init_kv_cache(tiny_test(), 1, 32)), "slot KVCache"),
    "lora": (lambda m: init_lora(m[1], jax.random.PRNGKey(0), rank=4),
             "LoRA"),
    "adapter pool itself": (lambda m: AdapterPool(m[1]), "adapter pool"),
    "flash prefill": (lambda m: forward(
        m[0], dataclasses.replace(m[1], attn_impl="flash"),
        jnp.ones((1, 4), jnp.int32)), "attn_impl"),
}


@pytest.mark.parametrize("case", list(UNSUPPORTED))
def test_unsupported_mechanisms_raise_their_typed_error(model, case):
    call, names = UNSUPPORTED[case]
    with pytest.raises(LatentCacheUnsupported) as err:
        call(model)
    assert names in err.value.mechanism
    assert model[1].name in str(err.value)


# ---- tracing --------------------------------------------------------------

def test_step_reports_its_routing_in_the_one_fetch(model):
    """``experts_touched`` and ``expert_load_max`` ride behind the step's
    tokens; attrs of ``engine.step`` and ``senweaver_moe_*`` counters;
    padding entries are not counted."""
    obs.enable()
    eng = make_engine(model, num_slots=4)
    rid = eng.submit(PROMPT, max_new_tokens=4)
    eng.run()
    steps = [s.attrs for s in obs.get_tracer().spans()
             if s.name == "engine.step" and "entries" in s.attrs]
    assert steps and all(a["expert_assignments"] == 2 * a["used"]
                         and a["expert_banks"] == 2 * 8 for a in steps)
    decode = [a for a in steps if a["decode_rows"] == 1]
    # one row of four decodes: three padding entries, never counted
    assert decode and all(a["entries"] == 4 and a["expert_load_max"] == 1
                          and a["experts_touched"] == 4 for a in decode)
    prefill = steps[0]
    assert prefill["used"] == len(PROMPT)
    assert 2 <= prefill["experts_touched"] <= 2 * min(8, 2 * len(PROMPT))
    reg = obs.get_registry()
    assert reg.get("senweaver_moe_assignments_total").value() == sum(
        a["expert_assignments"] for a in steps)
    assert reg.get("senweaver_moe_experts_touched_total").value() == sum(
        a["experts_touched"] for a in steps)
    assert reg.get("senweaver_moe_expert_banks_total").value() == (
        16 * len(steps))
    assert reg.get("senweaver_moe_expert_load_max").value() == (
        steps[-1]["expert_load_max"])
    assert eng.is_done(rid)


def test_a_dense_models_step_fetches_what_it_did():
    """Nothing is added for a dense model: T tokens out, no routing attrs,
    no ``senweaver_moe_*`` instrument."""
    obs.enable()
    config = tiny_test()
    params = init_params(config, jax.random.PRNGKey(0))
    eng = RolloutEngine(params, config, num_slots=4, max_len=64,
                        sample=GREEDY,
                        engine_config=EngineConfig(block_size=4))
    toks, logp, _pool, _, _ = engine_mod._paged_fused_step(
        params, config, np.zeros((6, 4), np.int32),
        np.zeros((4, 2), np.int32), eng.pool, jax.random.PRNGKey(0),
        eng._cur_tok_dev, GREEDY, False)
    # T tokens and the attention plan's two counts (every model's)
    assert toks.shape == (6,) and logp.shape == (4,)
    eng.pool = _pool
    eng.submit(PROMPT, max_new_tokens=3)
    eng.run()
    assert eng._moe_counters is None
    assert not any(k.startswith("expert") for s in obs.get_tracer().spans()
                   for k in s.attrs)
    assert obs.get_registry().get("senweaver_moe_assignments_total") is None


def test_an_expert_models_step_appends_two_counts(model):
    params, config = model
    eng = make_engine(model, num_slots=4)
    drop = eng.pool.num_blocks          # the dropped-write sentinel
    plan = np.zeros((6, 4), np.int32)
    plan[3] = [0, 0, drop, drop]
    toks, logp, _, _, _ = engine_mod._paged_fused_step(
        params, config, plan, np.zeros((4, 2), np.int32), eng.pool,
        jax.random.PRNGKey(0), eng._cur_tok_dev, GREEDY, False)
    assert toks.shape == (8,) and logp.shape == (4,)
    # two identical entries write, two are dropped: 2 experts x 2 layers
    # touched, 2 pairs on each; behind them the attention plan's two
    assert (int(toks[4]), int(toks[5])) == (4, 2)
    assert toks[6:].tolist() == [0, 0]
