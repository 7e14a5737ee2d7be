"""Solar-Open2's layers (``solar_open2``) through the program at test size:
periods of one gated NoPE GQA layer and three gated delta-rule (KDA) layers
in one scan (``models.transformer._pattern_scan``: a kind more than once a
period, experts as every layer's second sublayer, an untied head), the
matrix state a row beside the KV blocks (``rollout.paged_kv.StateRows``
under a pattern), the chip's share of a 16-wide router, the engine's group
fork by state copy, the step's new attrs, and the typed refusals — against
the plain reference the benchmark's output check uses
(``benchmark/reference/solar_open2.py``: float32, the delta rule one token
at a time, a plain mask, every token through every held expert, nothing of
the program imported).

The tiny preset is the architecture map of a published-key dict
(``models.config.TINY_SOLAR_OPEN2_KEYS``): two periods, 4/2 attention heads
x 16, a mixer of 4 heads x 8, experts 8 of 16 from the 4th, top-4, one
shared. Everything float32 at ``highest``. The weights are ``init_params``'
(steps in [1e-3, 1e-1], A in [1, 16]: a state value keeps 20% to 99.9% a
token, so a state carries over the whole sequence) with every gain, the
gate's bias and the correction bias drawn at random, so that a dropped one
shows.
"""

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.archs.solar_open2 import model_config
from benchmark.manifest import HERE, load_json
from benchmark.reference import solar_open2 as ref
from senweaver_ide_tpu import obs
from senweaver_ide_tpu.models import forward, init_params
from senweaver_ide_tpu.models import transformer as tf
from senweaver_ide_tpu.models.config import (TINY_SOLAR_OPEN2_KEYS,
                                             LayerPatternUnsupported,
                                             get_config, pattern_keys,
                                             tiny_solar_open2_test)
from senweaver_ide_tpu.models.load import export_hf_params, load_hf_params
from senweaver_ide_tpu.models.transformer import (forward_paged,
                                                  init_kv_cache)
from senweaver_ide_tpu.rollout import (AdapterPool, EngineConfig,
                                       RolloutEngine)
from senweaver_ide_tpu.rollout import paged_kv
from senweaver_ide_tpu.rollout.engine import _paged_fused_step
from senweaver_ide_tpu.rollout.paged_kv import (StateRows, cache_kinds,
                                                init_paged_pool)
from senweaver_ide_tpu.rollout.sampler import SampleParams
from senweaver_ide_tpu.training.lora import init_lora

TINY = {**TINY_SOLAR_OPEN2_KEYS, "name": "tiny-solar-open2-test",
        "model_type": "solar_open2", "torch_dtype": "float32",
        "hidden_act": "silu", "matmul_precision": "highest",
        "published": {"n_routed_experts": 16},
        "held_experts": {"first": 4, "count": 8, "of": 16}}
GREEDY = SampleParams(temperature=0.0, top_k=0, top_p=1.0)
# float32 at ``highest`` on both sides, logits of magnitude ~5: program and
# reference differ by summation order alone (a chunk's triangular solve and
# products against the reference's sum a token; the grouped expert products
# against every token through every expert), measured 4e-5 forward and
# paged. A state rounded to bfloat16 ONCE between two chunks and each
# dropped term of the mathematics move the logits by 10 x that and more
# (the tests below that have to FAIL it).
TOL = 1e-4
FORWARD = jax.jit(forward, static_argnames=("config",))
BLOCK, BLOCKS, ROWS, STEP = 8, 32, 6, 24


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs._reset_for_tests()
    yield
    obs._reset_for_tests()


def _shaken(params, key):
    """``init_params``' tree with the leaves it starts at a constant drawn
    at random: gains 1 + 0.1 n, the gate's bias 0.1 n, the correction bias
    0.3 n (it then decides some picks, as a trained one does)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, leaf) in enumerate(flat):
        name = str(getattr(path[-1], "key", path[-1]))
        k = jax.random.fold_in(key, i)
        if name == "router_bias_norm":
            leaf = 0.3 * jax.random.normal(k, leaf.shape, leaf.dtype)
        elif name.endswith("norm"):
            leaf = 1.0 + 0.1 * jax.random.normal(k, leaf.shape, leaf.dtype)
        elif name == "kda_g_bias":
            leaf = leaf + 0.1 * jax.random.normal(k, leaf.shape, leaf.dtype)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


@pytest.fixture(scope="module")
def model():
    config = model_config(TINY)
    params = _shaken(init_params(config, jax.random.PRNGKey(0)),
                     jax.random.PRNGKey(7))
    return params, config


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.PRNGKey(1), (3, 48), 0,
                                         512))


@pytest.fixture(scope="module")
def want(model, tokens):
    return np.asarray(ref.logits(model[0], TINY, tokens))


def test_tiny_preset_is_the_arch_map_of_its_published_keys(model):
    params, config = model
    assert config == tiny_solar_open2_test() == get_config(
        "tiny-solar-open2-test")
    period = ("full", "kda", "kda", "kda")
    assert config.layer_types == ((period, 2),)
    assert pattern_keys(period) == ("full", "kda1", "kda2", "kda3")
    assert pattern_keys(("mamba", "window")) == ("mamba", "window")
    assert [config.kind_layers(k) for k in ("full", "kda")] == [2, 6]
    assert config.ssm and config.pattern and config.expert_share
    assert (config.attn_layers, config.num_expert_layers) == (2, 8)
    seg = params["layers"]["seg0"]
    assert list(seg) == ["full", "kda1", "kda2", "kda3"]
    moe = {"attn_norm", "mlp_norm", "router", "router_bias_norm", "w_gate",
           "w_up", "w_down", "ws_gate", "ws_up", "ws_down"}
    assert set(seg["full"]) == moe | {"wq", "wk", "wv", "wo", "w_attn_gate"}
    assert set(seg["kda2"]) == moe | {
        "kda_in", "kda_conv_w", "kda_f_down", "kda_f_up", "kda_dt_bias",
        "kda_A_log", "kda_beta", "kda_g_down", "kda_g_up", "kda_g_bias",
        "kda_o_norm", "kda_out"}
    mix = seg["kda1"]
    assert mix["kda_in"].shape == (2, 64, 3 * 32)
    assert mix["kda_conv_w"].shape == (2, 4, 3 * 32)
    assert mix["kda_f_down"].shape == (2, 64, 8)        # rank = head_dim
    assert mix["kda_A_log"].shape == (2, 1, 4)
    assert mix["kda_A_log"].dtype == mix["kda_dt_bias"].dtype == jnp.float32
    assert seg["full"]["router"].shape == (2, 64, 16)   # as wide as routed
    assert seg["full"]["w_gate"].shape == (2, 8, 64, 32)       # 8 held
    assert params["lm_head"].shape == (64, 512)                # untied
    keep = jnp.exp(-jax.nn.softplus(mix["kda_dt_bias"])
                   * jnp.exp(jnp.repeat(mix["kda_A_log"], 8, axis=-1)))
    assert 0.19 < float(keep.min()) and float(keep.max()) < 0.9991


@pytest.mark.parametrize("key", [
    "use_rope", "first_k_dense_replace", "kda_use_full_proj",
    "tie_word_embeddings", "norm_topk_prob", "num_hidden_layers",
    "gqa_layers", "partial_rotary_factor", "hidden_act", "rope_scaling",
    "linear_attn_config.num_kv_heads", "linear_attn_config.gate_rank"])
def test_arch_map_refuses_what_it_does_not_model(key):
    other = {"first_k_dense_replace": 1, "num_hidden_layers": 6,
             "gqa_layers": [0, 3], "partial_rotary_factor": 0.5,
             "hidden_act": "gelu", "rope_scaling": {"type": "yarn"}}
    bad = dict(TINY)
    if key.startswith("linear_attn_config."):
        bad["linear_attn_config"] = dict(TINY["linear_attn_config"],
                                         **{key.split(".")[1]: 2})
    else:
        bad[key] = other[key] if key in other else not TINY[key]
    with pytest.raises(SystemExit, match=key.replace(".", r"\.")):
        model_config(bad)


# ---- (1) forward: the whole model, each kind of layer, the shares ----------

def test_forward_logits_equal_the_reference(model, tokens, want):
    logits, _ = FORWARD(model[0], model[1], jnp.asarray(tokens))
    assert float(np.abs(np.asarray(logits) - want).max()) < TOL
    assert float(np.abs(want).max()) > 1.0


def _one(stack, i=0):
    return jax.tree_util.tree_map(lambda a: a[i], stack)


@pytest.mark.parametrize("kind", ["full", "kda"])
def test_each_kind_of_layer_equals_the_reference(model, kind):
    """A layer's first sublayer alone on a random normed input, one
    sequence: the program's dense mixer against the reference's."""
    params, c = model
    lp = _one(params["layers"]["seg0"]["full" if kind == "full" else "kda2"],
              1)
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 40, 64))
    st = ref.settings(TINY)
    with jax.default_matmul_precision("highest"):
        got, _, _ = tf._dense_mixer(c, None, kind, lp, h, None, None, {})
        mixer = ref.gqa if kind == "full" else ref.kda
        expect = mixer(st, None, h[0], lp)
    assert float(jnp.abs(got[0] - expect).max()) < TOL / 10
    assert float(jnp.abs(expect).max()) > 0.1


def _routed_and_shared(c, lp, u):
    """The program's expert layer on u (N, D) -> (its routed part, the
    shared expert's)."""
    with jax.default_matmul_precision("highest"):
        both, _ = tf._ffn(c, lp, u[None])
        routed, _, _ = tf.expert_ffn(c, lp, u)
    return routed, both[0] - routed


def test_the_eight_shares_and_the_shared_expert_once_add_up_to_the_layer(
        model):
    """The 16 routed experts over 8 chips, 2 a chip: the routed parts that
    the eight shares give, with the shared expert counted once, add up to
    what the uncut layer gives — in the program and in the reference, and
    the two agree. A pick of an absent expert adds nothing on a chip."""
    params, c = model
    whole = dataclasses.replace(c, num_experts=16, moe_first_expert=0)
    lp8 = _one(params["layers"]["seg0"]["kda1"])
    k = jax.random.split(jax.random.PRNGKey(5), 4)
    draw = lambda key, a: jax.random.normal(
        key, (16,) + a.shape[1:]) / float(a.shape[-2]) ** 0.5
    lp = dict(lp8, w_gate=draw(k[0], lp8["w_gate"]),
              w_up=draw(k[1], lp8["w_up"]), w_down=draw(k[2], lp8["w_down"]))
    u = jax.random.normal(k[3], (24, 64))
    uncut_routed, uncut_shared = _routed_and_shared(whole, lp, u)
    st = ref.settings(TINY)
    with jax.default_matmul_precision("highest"):
        ref_routed, ref_shared = ref.moe_parts(
            dict(st, first_expert=0), None, u, lp)
    assert float(jnp.abs(uncut_routed - ref_routed).max()) < TOL / 10
    assert float(jnp.abs(uncut_shared - ref_shared).max()) < TOL / 10
    total, ref_total, touched = 0.0, 0.0, 0
    for chip in range(8):
        share = dataclasses.replace(c, num_experts=2,
                                    moe_first_expert=2 * chip)
        lp_s = dict(lp, **{n: lp[n][2 * chip:2 * chip + 2]
                           for n in ("w_gate", "w_up", "w_down")})
        routed, shared = _routed_and_shared(share, lp_s, u)
        with jax.default_matmul_precision("highest"):
            r_routed, _ = ref.moe_parts(dict(st, first_expert=2 * chip),
                                        None, u, lp_s)
        assert float(jnp.abs(shared - uncut_shared).max()) < 1e-6
        total, ref_total = total + routed, ref_total + r_routed
        touched += int(jnp.abs(routed).max() > 0)
    assert touched >= 5          # the choice leaves a few chips idle
    whole_layer = uncut_routed + uncut_shared
    assert float(jnp.abs(total + uncut_shared - whole_layer).max()) < 1e-5
    assert float(jnp.abs(ref_total + ref_shared - whole_layer).max()) < (
        TOL / 10)
    assert float(jnp.abs(uncut_routed).max()) > 0.1


@pytest.mark.parametrize("what", ["decay", "beta_factor", "conv", "gqa_gate",
                                  "kda_gate", "qk_norm", "shared_expert",
                                  "correction_bias"])
def test_a_dropped_term_fails_the_tolerance(model, tokens, want, what,
                                            monkeypatch):
    """The comparison is tight enough to tell a part of the mathematics
    left out: the decay (alpha = 1), the factor 2 on beta, the short conv
    (its last tap alone), either output gate, q's and k's normalisation,
    the shared expert, the correction bias in the choice."""
    params, c = model

    def edit(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        if what == "decay" and name == "kda_A_log":
            return jnp.full_like(leaf, -40.0)              # g = -e^-40 x .
        if what == "conv" and name == "kda_conv_w":
            return jnp.zeros_like(leaf).at[:, -1].set(1.0)
        if what == "kda_gate" and name == "kda_g_bias":
            return jnp.full_like(leaf, 40.0)               # sigmoid = 1
        if what == "kda_gate" and name == "kda_g_up":
            return jnp.zeros_like(leaf)
        if what == "shared_expert" and name == "ws_down":
            return jnp.zeros_like(leaf)
        if what == "correction_bias" and name == "router_bias_norm":
            return jnp.zeros_like(leaf)
        return leaf

    edited = jax.tree_util.tree_map_with_path(edit, params)
    run = FORWARD
    if what == "beta_factor":
        c = dataclasses.replace(c, kda_neg_eigval=False)
    if what == "gqa_gate":
        c = dataclasses.replace(c, attn_out_gate=False)
    if what == "qk_norm":
        def plain(c, conv_out):
            b, s, _ = conv_out.shape
            a = jax.nn.silu(conv_out).reshape(b, s, 3, c.kda_num_heads,
                                              c.kda_head_dim)
            return a[:, :, 0], a[:, :, 1], a[:, :, 2]
        monkeypatch.setattr(tf, "_kda_qkv", plain)
        run = forward                                      # traced anew
    logits, _ = run(edited, c, jnp.asarray(tokens[:1]))
    assert float(np.abs(np.asarray(logits) - want[:1]).max()) > 10 * TOL


# ---- (2) chunked prefill, then decoding, through forward_paged -------------

PAGED = jax.jit(
    lambda params, config, toks, pool, tables, rows, pos, wb, wo, kernel:
    forward_paged(params, config, toks, pool=pool, tables=tables,
                  seq_row=rows, positions=pos, write_block=wb, write_off=wo,
                  use_kernel=kernel, with_moe_stats=True,
                  with_kda_stats=True),
    static_argnums=(1, 9))
TABLES = np.zeros((ROWS - 2, 8), np.int32)
for _r in range(ROWS - 2):
    TABLES[_r, :6] = 1 + 6 * _r + np.arange(6)


def feed(model, pool, runs, width=STEP, tables=TABLES, kernel=None):
    """One call of ``forward_paged``: ``runs`` = [(row, tokens, first
    position)], laid out one after the other, padded to ``width`` entries
    with dropped writes on row 0. -> (logits of each run, pool', the
    call's ``MoEStats``, its (chunk entries, readout absmax))."""
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
    logits, pool, moe, kda = PAGED(
        model[0], model[1], vec(toks, 0), pool, jnp.asarray(tables),
        vec(rows, 0), vec(pos, 0), vec(wb, BLOCKS), vec(wo, 0), kernel)
    out, at = [], 0
    for _row, t, _start in runs:
        out.append(np.asarray(logits[at:at + len(t)]))
        at += len(t)
    return out, pool, moe, kda


def fresh_pool(model):
    return init_paged_pool(model[1], BLOCKS, BLOCK, state_rows=ROWS,
                           step_tokens=STEP)


def test_chunked_prefill_then_decode_equals_the_reference(model, tokens,
                                                          want):
    """Row 1 prefills sequence 0 in chunks of 10, 17 and 4 (the third ends
    at ``prompt[:-1]`` of a 32-token prompt), feeds the prompt's last token
    alone and decodes the rest a token a call, while row 2 prefills
    sequence 1 in chunks of 3, 20 and 9 beside it and decodes too: matrix
    state and conv window cross every call's edge, and every logit equals
    the reference's full forward. The calls count their chunk entries and
    their expert pairs over kept entries alone."""
    pool = fresh_pool(model)
    assert isinstance(pool.rows, StateRows)
    assert pool.rows.ssm.shape == (6, ROWS, 4, 8, 8)
    assert pool.rows.conv.shape == (6, ROWS, 3, 96)
    assert pool.k.shape == (2, BLOCKS, BLOCK, 2, 16)
    got = {1: [], 2: []}
    plan = [[(1, 0, 10), (2, 0, 3)], [(1, 10, 17)], [(2, 3, 20)],
            [(1, 27, 4), (2, 23, 9)], [(1, 31, 1)]]
    plan += [[(1, p, 1), (2, p, 1)] for p in range(32, 48)]
    chunked = []
    for call in plan:
        runs = [(row, tokens[row - 1][s:s + n], s) for row, s, n in call]
        outs, pool, moe, kda = feed(model, pool, runs)
        for (row, _s, _n), o in zip(call, outs):
            got[row].append(o)
        chunked.append(int(kda[0]))
        used = sum(n for _r, _s, n in call)
        assert 0 <= int(moe.local_pairs) <= used * 4 * 8
        assert float(kda[1]) > 0
    assert chunked[:6] == [13, 17, 20, 13, 0, 0]
    assert float(np.abs(np.concatenate(got[1]) - want[0]).max()) < TOL
    assert float(np.abs(np.concatenate(got[2]) - want[1]).max()) < TOL


def _two_chunks(model, tokens, between):
    pool = fresh_pool(model)
    (a,), pool, _, _ = feed(model, pool, [(1, tokens[0][:20], 0)])
    pool = between(pool)
    (b,), _, _, _ = feed(model, pool, [(1, tokens[0][20:40], 20)])
    return np.concatenate([a, b])


def test_a_bfloat16_state_fails_the_tolerance(model, tokens, want):
    """The state rounded to bfloat16 once, between two chunks: 10 x TOL
    and more away, so the comparison tells a state held in a lower
    precision than float32."""
    exact = _two_chunks(model, tokens, lambda pool: pool)
    assert float(np.abs(exact - want[0][:40]).max()) < TOL
    low = _two_chunks(model, tokens, lambda pool: pool._replace(
        rows=pool.rows._replace(ssm=pool.rows.ssm.astype(
            jnp.bfloat16).astype(jnp.float32))))
    assert float(np.abs(low - want[0][:40]).max()) > 10 * TOL


@pytest.mark.parametrize("leaf", ["ssm", "conv", "k"])
def test_a_zeroed_carry_fails_the_tolerance(model, tokens, want, leaf):
    """Each kind of cache carries what the second chunk reads: the matrix
    state, the conv's window, the GQA layers' blocks."""
    def lose(pool):
        if leaf == "k":
            return pool._replace(k=jnp.zeros_like(pool.k))
        return pool._replace(rows=pool.rows._replace(**{
            leaf: jnp.zeros_like(getattr(pool.rows, leaf))}))

    lost = _two_chunks(model, tokens, lose)
    assert float(np.abs(lost - want[0][:40]).max()) > 10 * TOL


def test_padding_and_dropped_writes_advance_no_row(model):
    pool = fresh_pool(model)
    _, pool, _, _ = feed(model, pool, [(0, [5, 6, 7], 0)])
    before = jax.tree_util.tree_map(np.asarray, pool.rows)
    _, pool, moe, kda = feed(model, pool, [])             # padding alone
    assert (int(moe.local_pairs), int(moe.experts_touched)) == (0, 0)
    assert (int(kda[0]), float(kda[1])) == (0, 0.0)
    _, pool, _, _ = feed(model, pool, [(1, [9], 0)])      # and beside a row
    for was, now in zip(before, pool.rows):
        assert np.array_equal(was[:, 0], np.asarray(now)[:, 0])
    assert float(np.abs(before.ssm[:, 0]).max()) > 0


def test_a_row_reused_at_position_0_sees_nothing_of_its_last_tenant(
        model, tokens, want):
    pool = fresh_pool(model)
    for s in (0, 24):
        _, pool, _, _ = feed(model, pool, [(1, tokens[0][s:s + 24], s)])
    (a,), pool, _, _ = feed(model, pool, [(1, tokens[1][:20], 0)])
    (b,), pool, _, _ = feed(model, pool, [(1, tokens[1][20:40], 20)])
    assert float(np.abs(np.concatenate([a, b]) - want[1][:40]).max()) < TOL


def test_the_pools_bytes_by_descriptor_equal_the_configuration_files():
    """At the published sizes, from ``eval_shape`` alone: the weights and
    each kind of cache are what ``benchmark/configs``' ``bytes`` says, a
    kind's descriptor is its leaves' bytes, and the state's bytes do not
    grow with ``max_len``."""
    cfg = load_json(HERE, "configs", "solar-open2-250b.json")
    c = model_config(cfg)
    b = cfg["bytes"]
    assert (c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim) == (
        4096, 64, 8, 128)
    assert (c.kda_num_heads, c.kda_head_dim, c.kda_conv, c.kda_rank) == (
        64, 128, 4, 128)
    assert (c.num_experts, c.routed_experts, c.num_experts_per_tok,
            c.expert_size, c.num_shared_experts) == (40, 320, 8, 1280, 1)
    assert c.layer_types == ((("full", "kda", "kda", "kda"), 1),)
    tree = jax.eval_shape(functools.partial(init_params, c),
                          jax.random.PRNGKey(0))
    count = lambda t: sum(x.size for x in jax.tree_util.tree_leaves(t))
    seg = tree["layers"]["seg0"]
    banks = lambda lp: sum(lp[n].size for n in ("w_gate", "w_up", "w_down"))
    assert banks(seg["full"]) == b["held_expert_params_per_layer"] == (
        40 * b["expert_params"])
    assert count(seg["full"]) == b["gqa_layer_params"]
    assert {count(seg[k]) for k in ("kda1", "kda2", "kda3")} == {
        b["kda_layer_params"]}
    mixer = lambda lp, prefix: sum(
        v.size for k, v in lp.items() if k.startswith(prefix))
    assert mixer(seg["kda1"], "kda_") == b["kda_mixer_params"]
    assert sum(seg["full"][k].size for k in (
        "wq", "wk", "wv", "wo", "w_attn_gate")) == b["gqa_mixer_params"]
    assert count(seg) == b["period_params"]
    assert tree["embed"].size + tree["lm_head"].size == b[
        "embedding_and_head_params"]
    assert count(tree) == b["params"]
    assert sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree)) == b["weights_bytes"]
    mix = load_json(HERE, "traffic", "grpo-rollout-ctx4k.json")["engine"]
    slots, max_len = mix["num_slots"], mix["max_len"]
    bs = paged_kv.resolve_block_size(paged_kv.kv_row_bytes(c), max_len)
    rows = slots + max(2, slots // 6)
    assert (bs, rows) == (b["block_size"], 56)

    def pool_bytes(max_len):
        nb = (slots + 4) * (max_len // bs)
        pool = jax.eval_shape(lambda: init_paged_pool(
            c, nb, bs, state_rows=rows, step_tokens=192))
        assert isinstance(pool.rows, StateRows)
        size = lambda a: a.size * a.dtype.itemsize
        got = {"kv": size(pool.k) + size(pool.v),
               "ssm": size(pool.rows.ssm), "conv": size(pool.rows.conv)}
        assert got == {k.kind: k.nbytes(nb, bs, rows)
                       for k in cache_kinds(c, bs, 192)}
        return got, pool

    got, pool = pool_bytes(max_len)
    assert pool.rows.ssm.shape == (3, 56, 64, 128, 128)
    assert pool.rows.ssm.dtype == jnp.float32
    assert pool.rows.conv.shape == (3, 56, 3, 3 * 8192)
    assert pool.k.shape == (1, 52 * 128, 32, 8, 128)
    assert got == {"kv": b["kv_cache_bytes"], "ssm": b["state_bytes"],
                   "conv": b["conv_bytes"]}
    twice, _ = pool_bytes(2 * max_len)
    assert twice["kv"] == 2 * got["kv"] and twice["ssm"] == got["ssm"]
    share = (b["weights_bytes"] + sum(got.values())) / 17.18e9
    assert 0.47 < share < 0.49


# ---- (3) through RolloutEngine ----------------------------------------------

PROMPT = [int(t) for t in np.random.RandomState(0).randint(1, 500, size=29)]


def make_engine(model, **kw):
    ec = dict(block_size=8, step_tokens=16)
    ec.update(kw.pop("engine", {}))
    args = dict(num_slots=8, max_len=64, sample=GREEDY)
    args.update(kw)
    return RolloutEngine(model[0], model[1],
                         engine_config=EngineConfig(**ec), **args)


def drain(eng, rids):
    while eng.has_work:
        eng.step()
    return [eng.result(r) for r in rids]


@pytest.fixture(scope="module")
def solo(model):
    """One request alone: 29 prompt tokens in chunks of 16 and 13, 24 new
    tokens."""
    eng = make_engine(model)
    (out,) = drain(eng, [eng.submit(PROMPT, max_new_tokens=24)])
    assert eng.kv_layout == "paged" and eng.kv_layout_fallback is None
    return out


def test_the_engine_decodes_the_references_argmax(model, solo):
    seq = np.asarray([PROMPT + solo])
    lg = np.asarray(ref.logits(model[0], TINY, seq))[0]
    assert solo == [int(lg[len(PROMPT) - 1 + i].argmax())
                    for i in range(len(solo))]


def test_group_of_eight_equals_eight_submits_with_one_prefill(model, solo):
    """``submit_group`` of 8 at T 0: one prefill cut at ``prompt[:-1]``,
    seven followers that fork both kinds of state — block refcounts for
    the GQA layers, and a copy of the donor's snapshot row: six layers'
    matrix state and conv windows — and every one of the eight equals the
    lone request token for token."""
    eng = make_engine(model)
    outs = drain(eng, eng.submit_group(PROMPT, 8, max_new_tokens=24))
    assert all(o == solo for o in outs)
    st = eng.stats()
    assert (st["group_prefills"], st["group_forks"]) == (1, 7)
    assert st["group_prefill_tokens_avoided"] == 7 * (len(PROMPT) - 1)
    eng._alloc.check_leaks()
    indep = make_engine(model)
    outs = drain(indep, [indep.submit(PROMPT, max_new_tokens=24)
                         for _ in range(8)])
    assert all(o == solo for o in outs)


def test_run_ahead_on_and_off_agree(model, solo, monkeypatch):
    def serve(ahead):
        eng = make_engine(model)
        if not ahead:
            monkeypatch.setattr(eng, "_saturated", lambda: False)
        before = int(eng._run_ahead_total.value())
        rids = [eng.submit(PROMPT, max_new_tokens=24) for _ in range(11)]
        outs = drain(eng, rids)
        return outs, int(eng._run_ahead_total.value()) - before

    on, n_on = serve(True)
    off, n_off = serve(False)
    assert n_on > 0 and n_off == 0
    assert on == off and all(o == solo for o in on)


def test_the_step_reports_the_new_attrs_in_the_one_fetch(model):
    """A group of 4: the spans carry the state's attrs, the delta rule's
    and the expert share's; the counters move; and a step makes ONE
    device-to-host transfer."""
    obs.enable()
    eng = make_engine(model)
    syncs = int(eng._host_syncs_total.value())
    drain(eng, eng.submit_group(PROMPT, 4, max_new_tokens=4))
    steps = [s.attrs for s in obs.get_tracer().spans()
             if s.name == "engine.step" and "kda_chunk_entries" in s.attrs]
    assert steps
    assert int(eng._host_syncs_total.value()) - syncs == len(steps)
    # the donor's prefill of prompt[:-1]: 16 entries, then 12
    assert [s["kda_chunk_entries"] for s in steps[:2]] == [16, 12]
    assert all(s["kda_chunk_entries"] == 0 for s in steps[2:])
    assert all(s["kda_readout_absmax"] > 0 for s in steps)
    assert [s["ssm_rows"] for s in steps[:2]] == [1, 1]
    assert max(s["ssm_rows"] for s in steps) == 4
    assert max(s["ssm_state_copies"] for s in steps) >= 1
    for s in steps:
        assert s["expert_picks"] == s["used"] * 4 * 8
        assert s["expert_assignments"] == s["used"] * 4
        assert 0 <= s["local_pairs"] <= s["expert_picks"]
        assert s["expert_banks"] == 8 * 8
        assert 0 < s["experts_touched"] <= 64
        assert s["expert_load_max"] >= 1
    assert eng._kda_meters[0].value() == 28
    assert eng._kda_meters[1].value() == steps[-1]["kda_readout_absmax"]
    assert eng.cache_kind_bytes == {
        k.kind: k.nbytes(eng._alloc.num_blocks, 8, 8 + 2)
        for k in cache_kinds(model[1], 8, 16)}
    assert set(eng.cache_kind_bytes) == {"kv", "ssm", "conv"}


# ---- (4) what has no form yet is refused by name ---------------------------

def _engine_with_request(model):
    eng = make_engine(model)
    rid = eng.submit(PROMPT, max_new_tokens=8)
    eng.step()
    return eng, rid


REFUSED = {
    "the slot KVCache layout": lambda m: make_engine(
        m, engine={"kv_layout": "slots"}),
    "the slot int8 cache": lambda m: RolloutEngine(
        m[0], dataclasses.replace(m[1], kv_quant=True), num_slots=2,
        max_len=64),
    "the quantized KV ladder": lambda m: make_engine(
        m, engine={"kv_dtype": "int8"}),
    "a mesh": lambda m: make_engine(m, mesh=jax.sharding.Mesh(
        np.asarray(jax.devices()[:1]), ("tp",))),
    "the multi-LoRA adapter pool": lambda m: AdapterPool(m[1]),
    "LoRA adapters": lambda m: init_lora(m[1], jax.random.PRNGKey(0)),
    "fused draft/verify speculation": lambda m: make_engine(
        m).enable_speculation(m[0], m[1]),
    "registered prefixes": lambda m: make_engine(m).register_prefix(
        PROMPT[:16]),
    "prefix export": lambda m: make_engine(m).export_prefix(0),
    "prefix import": lambda m: make_engine(m).import_prefix(
        PROMPT[:16], None),
    "request checkpoints and migration": lambda m: (
        lambda e: e[0].checkpoint_request(e[1]))(_engine_with_request(m)),
    "fork_request": lambda m: (
        lambda e: e[0].fork_request(e[1]))(_engine_with_request(m)),
    "the HF loader": lambda m: load_hf_params("/nonexistent", m[1]),
    "the HF exporter": lambda m: export_hf_params(m[0], m[1],
                                                  "/nonexistent"),
    "the slot KVCache layout (init_kv_cache)": lambda m: init_kv_cache(
        m[1], 2, 32),
    "forward(cache=...)": lambda m: forward(
        m[0], m[1], jnp.zeros((1, 4), jnp.int32), cache=object()),
    "forward(mesh=...)": lambda m: forward(
        m[0], m[1], jnp.zeros((1, 4), jnp.int32), mesh=object()),
    "a quantized pool": lambda m: forward_paged(
        m[0], m[1], jnp.zeros((4,), jnp.int32),
        pool=fresh_pool(m)._replace(k_scale=jnp.zeros((1,))),
        tables=jnp.asarray(TABLES), seq_row=jnp.zeros((4,), jnp.int32),
        positions=jnp.arange(4), write_block=jnp.zeros((4,), jnp.int32),
        write_off=jnp.arange(4)),
}


@pytest.mark.parametrize("mechanism", sorted(REFUSED))
def test_what_the_three_forms_lack_is_refused_by_name(model, mechanism):
    """The configuration is a layer pattern with recurrent state and a
    share of the experts at once; each mechanism raises the typed error of
    the first of its forms the table asks (the pattern's), with the
    mechanism and the model in its message; none reaches the ``slots``
    fallback."""
    with pytest.raises(LayerPatternUnsupported) as err:
        REFUSED[mechanism](model)
    said = mechanism.split(" (init_kv_cache)")[0]
    assert said in str(err.value) and said in err.value.mechanism
    assert model[1].name in str(err.value)


def test_a_pattern_refuses_the_forms_it_has_no_equations_for():
    c = tiny_solar_open2_test()
    key = jax.random.PRNGKey(0)
    for bad, said in (
            (dict(router_type="softmax"), "bias router"),
            (dict(layer_types=((("full", "window"), 4),), layer_window=8),
             "diff_attn"),
            (dict(kda_rank=0), "kda_rank"),
            (dict(layer_types=((("mamba", "kda"), 4),)), "one state leaf"),
            (dict(layer_types=((("full", "conv"), 4),)), "each one of")):
        with pytest.raises(ValueError, match=said):
            init_params(dataclasses.replace(c, **bad), key)


# ---- (5) the other patterns' and state models' steps are the parent's ------

# sha256 of ``_paged_fused_step``'s lowered text at the tiny presets (6
# rows; 6 and 24 entries), printed by the PARENT of PR 43 (commit dfe1db6):
# the pattern scan, the state pool and the expert layer changed around
# these models, and their programs did not. PR 46 changed ONE on purpose,
# SambaY's wide step (its cross-decoder runs over the rows' samplers:
# "3b6de128f064f399" at the parent, 02f1966), and added this file's own
# preset as its parent printed it: a pattern that ends in a kind that writes
# is cut nowhere, so the seam in ``_pattern_scan`` lowers what was lowered.
PARENT_STEPS = {
    ("tiny-phi4flash-test", 6): "6c3e4be0ad33fcfe",
    ("tiny-phi4flash-test", 24): "f4f7359f2d50f3ed",
    ("tiny-solar-open2-test", 6): "8d057e45ef7b8344",
    ("tiny-solar-open2-test", 24): "3678e35f54cace5d",
    ("tiny-falcon-h1-test", 6): "44284bac802734dd",
    ("tiny-falcon-h1-test", 24): "a304c513da631233",
    ("tiny-longcat-flash-test", 6): "c7bb384666103916",
    ("tiny-longcat-flash-test", 24): "ccceb6752af9a11e",
}


def _lowered_step(name, entries, rows=6):
    c = get_config(name)
    s = jax.ShapeDtypeStruct
    params = jax.eval_shape(functools.partial(init_params, c),
                            jax.random.PRNGKey(0))
    pool = jax.eval_shape(
        lambda: init_paged_pool(c, 32, 8, state_rows=rows + 2,
                                step_tokens=24) if c.ssm
        else init_paged_pool(c, 32, 8))
    return _paged_fused_step.lower(
        params, c, s((6, entries), jnp.int32), s((rows, 8), jnp.int32), pool,
        s((2,), jnp.uint32), s((rows,), jnp.int32),
        SampleParams(temperature=1.0, top_p=1.0), None).as_text()


@pytest.mark.parametrize("name,entries", sorted(PARENT_STEPS))
def test_the_older_models_lowered_steps_are_the_parents(name, entries):
    text = _lowered_step(name, entries)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENT_STEPS[
        (name, entries)]
