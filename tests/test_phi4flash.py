"""Phi-4-mini-flash-reasoning's pattern (``phi4flash``) through the program
at test size: layers of five kinds in one scan (``models.transformer.
_pattern_scan``) — Mamba-1 mixers, window and full differential attention,
gated memory units, cross attention over the full layer's KV — the pool's
descriptor a kind (block-addressed KV for the one full layer, rings and
state by row, ``rollout.paged_kv.cache_kinds``), the engine's group fork of
all three, and the typed refusals — against the plain reference the
benchmark's output check uses (``benchmark/reference/phi4flash.py``:
float32, the mixer one token at a time, a plain mask, nothing of the
program imported).

The tiny preset is the architecture map of a published-key dict: 8 layers,
a window of 8 (a 48-token sequence wraps its ring five times), 6 cache rows
a token stored folded 3 x 2. Everything float32 at ``highest``. The weights
are ``init_params``' (Mamba-1's own start: A = -(1..N), a step size in
[1e-3, 1e-1], so a state carries over the whole sequence) with every norm's
gain and bias, the conv's bias and D drawn at random, so that a dropped
bias shows.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.archs.phi4flash import model_config
from benchmark.manifest import HERE, load_json
from benchmark.reference import phi4flash as ref
from senweaver_ide_tpu import obs
from senweaver_ide_tpu.models import forward, init_params
from senweaver_ide_tpu.models import transformer as tf
from senweaver_ide_tpu.models.config import (LayerPatternUnsupported,
                                             get_config, sambay_layer_types,
                                             tiny_phi4flash_test)
from senweaver_ide_tpu.models.load import export_hf_params, load_hf_params
from senweaver_ide_tpu.models.transformer import (forward_paged,
                                                  init_kv_cache)
from senweaver_ide_tpu.ops import paged_attention as pa
from senweaver_ide_tpu.ops import ssm as ssm_ops
from senweaver_ide_tpu.rollout import (AdapterPool, EngineConfig,
                                       RolloutEngine)
from senweaver_ide_tpu.rollout import paged_kv
from senweaver_ide_tpu.rollout.engine import FEED_PUT
from senweaver_ide_tpu.rollout.paged_kv import (cache_kinds, init_paged_pool,
                                                stored_kv_heads,
                                                window_capacity)
from senweaver_ide_tpu.rollout.sampler import SampleParams
from senweaver_ide_tpu.training.lora import init_lora

TINY = {
    "name": "tiny-phi4flash-test", "model_type": "phi4flash",
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 96,
    "intermediate_size": 128, "layer_norm_eps": 1e-5,
    "max_position_embeddings": 128, "mb_per_layer": 2,
    "num_attention_heads": 24, "num_hidden_layers": 8,
    "num_key_value_heads": 12, "resid_pdrop": 0, "sliding_window": 8,
    "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
    "vocab_size": 512, "mamba_d_state": 8, "mamba_d_conv": 4,
    "mamba_expand": 2, "mamba_dt_rank": 6, "torch_dtype": "float32",
    "matmul_precision": "highest"}
GREEDY = SampleParams(temperature=0.0, top_k=0, top_p=1.0)
# float32 at ``highest`` on both sides, logits of magnitude ~1: program and
# reference differ by summation order alone (the program's zero-padded
# one-call form of the two softmax maps against the reference's two maps;
# a run's scan in blocks of 8 against one token at a time), measured 3e-6
# forward and paged. A state rounded to bfloat16 ONCE between two chunks, a
# dropped lambda term, a dropped LayerNorm bias, a zeroed ring each move
# the logits by 20 x that and more (the tests below that have to FAIL it).
TOL = 1e-5
FORWARD = jax.jit(forward, static_argnames=("config",))
BLOCK, BLOCKS, ROWS, STEP = 8, 32, 6, 24


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs._reset_for_tests()
    yield
    obs._reset_for_tests()


def _shaken(params, key):
    """``init_params``' tree with the leaves it starts at a constant drawn
    at random: gains 1 + 0.1 n, biases and D 0.1 n."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, leaf) in enumerate(flat):
        name = str(getattr(path[-1], "key", path[-1]))
        k = jax.random.fold_in(key, i)
        if name.endswith("norm"):
            leaf = 1.0 + 0.1 * jax.random.normal(k, leaf.shape, leaf.dtype)
        elif name.endswith("_bias") and name != "ssm_dt_bias" or (
                name == "ssm_D"):
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
    assert config == tiny_phi4flash_test()
    assert config.layer_types == sambay_layer_types(8) == (
        (("mamba", "window"), 2), (("mamba", "full"), 1),
        (("gmu", "cross"), 1))
    big = get_config("phi-4-mini-flash-reasoning")
    assert big.layer_types == sambay_layer_types(32) == (
        (("mamba", "window"), 8), (("mamba", "full"), 1),
        (("gmu", "cross"), 7))
    assert [big.kind_layers(k) for k in ("mamba", "window", "full", "gmu",
                                         "cross")] == [9, 8, 1, 7, 7]
    lp = params["layers"]
    # each kind has only its own leaves
    mlp = {"attn_norm", "attn_norm_bias", "mlp_norm", "mlp_norm_bias",
           "w_gate", "w_up", "w_down"}
    assert set(lp["seg2"]["gmu"]) == mlp | {"gmu_in", "gmu_out"}
    assert set(lp["seg2"]["cross"]) == mlp | {"wq", "wo", "attn_lambda",
                                             "attn_sub_norm"}
    assert set(lp["seg1"]["full"]) == set(lp["seg0"]["window"]) == mlp | {
        "wq", "wk", "wv", "wo", "attn_lambda", "attn_sub_norm"}
    mix = lp["seg0"]["mamba"]
    assert mix["ssm_in"].shape == (2, 96, 2 * 192)
    assert mix["ssm_x"].shape == (2, 192, 6 + 2 * 8)
    assert mix["ssm_A_log"].shape == (2, 192, 8)
    assert mix["ssm_A_log"].dtype == mix["ssm_dt_bias"].dtype == jnp.float32
    # Mamba-1's start: a token keeps 45% to 99.9% of a state value
    keep = jnp.exp(-jax.nn.softplus(mix["ssm_dt_bias"])[..., None]
                   * jnp.exp(mix["ssm_A_log"]))
    assert 0.4 < float(keep.min()) and float(keep.max()) < 0.9991


@pytest.mark.parametrize("key", [
    "hidden_act", "mb_per_layer", "num_hidden_layers", "tie_word_embeddings",
    "mlp_bias", "lm_head_bias", "embd_pdrop", "resid_pdrop",
    "num_key_value_heads", "hidden_size"])
def test_arch_map_refuses_what_it_does_not_model(key):
    other = {"hidden_act": "gelu", "mb_per_layer": 4, "num_hidden_layers": 6,
             "embd_pdrop": 0.1, "resid_pdrop": 0.1, "num_key_value_heads": 9,
             "hidden_size": 100}
    bad = dict(TINY, **{key: other.get(key, not TINY[key])})
    with pytest.raises(SystemExit, match=key):
        model_config(bad)


# ---- (1) forward: the whole model, and each kind of layer ------------------

def test_forward_logits_equal_the_reference(model, tokens, want):
    logits, _ = FORWARD(model[0], model[1], jnp.asarray(tokens))
    assert float(np.abs(np.asarray(logits) - want).max()) < TOL


def _one(stack, i=0):
    return jax.tree_util.tree_map(lambda a: a[i], stack)


@pytest.mark.parametrize("kind", ["mamba", "window", "full", "gmu", "cross"])
def test_each_kind_of_layer_equals_the_reference(model, kind):
    """The first sublayer of one layer of ``kind`` on a random normed
    input, the program's (``_dense_mixer``, batch of 2) against the
    reference's own function for it, a sequence at a time."""
    params, c = model
    cfg = dict(ref._items(TINY))
    key = jax.random.PRNGKey(3)
    h = jax.random.normal(key, (2, 40, 96), jnp.float32)
    m = jax.random.normal(jax.random.fold_in(key, 1), (2, 40, 192))
    shape = (2, 40, c.cache_kv_heads, c.cache_head_dim)
    kv = (jax.random.normal(jax.random.fold_in(key, 2), shape),
          jax.random.normal(jax.random.fold_in(key, 3), shape))
    seg = {"mamba": "seg0", "window": "seg0", "full": "seg1", "gmu": "seg2",
           "cross": "seg2"}[kind]
    lp = _one(params["layers"][seg][kind])
    at = {"layer": jnp.int32(5), "index": jnp.int32(0), "full": 0}
    with jax.default_matmul_precision("highest"):
        got, got_m, got_kv = tf._dense_mixer(c, None, kind, lp, h, m, kv, at)
        for r in range(2):
            if kind == "mamba":
                out, y = ref._mamba(cfg, None, h[r], lp)
                assert float(jnp.abs(got_m[r] - y).max()) < TOL
            elif kind == "gmu":
                out = (jax.nn.silu(h[r] @ lp["gmu_in"]) * m[r]
                       ) @ lp["gmu_out"]
            else:
                pairs = (kv[0][r].reshape(40, -1, 2, 4),
                         kv[1][r].reshape(40, -1, 2, 4))
                out, kv_r = ref._attention(
                    cfg, None, h[r], lp, jnp.int32(5),
                    8 if kind == "window" else None,
                    pairs if kind == "cross" else None)
                if kind == "full":      # and it hands its k, v on
                    assert float(jnp.abs(
                        got_kv[0][r] - kv_r[0].reshape(40, -1, 8)).max()
                    ) < TOL
            assert float(jnp.abs(got[r] - out).max()) < TOL, kind


@pytest.mark.parametrize("what", ["lambda", "norm_bias", "sub_norm",
                                  "memory", "window"])
def test_a_dropped_term_fails_the_tolerance(model, tokens, want, what):
    """The comparison is tight enough to tell a part of the mathematics
    left out: the lambda term of the second softmax map, a LayerNorm's
    bias, the sub-norm's gain, the memory handed to the gated units, the
    window's bound."""
    params, c = model

    def edit(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        if what == "lambda" and name == "attn_lambda":
            # exp(0) - exp(0): lam = lam0 alone
            return jnp.zeros_like(leaf)
        if what == "norm_bias" and name == "mlp_norm_bias":
            return jnp.zeros_like(leaf)
        if what == "sub_norm" and name == "attn_sub_norm":
            return jnp.ones_like(leaf)
        if what == "memory" and name == "gmu_in":
            return jnp.zeros_like(leaf)
        return leaf

    edited = jax.tree_util.tree_map_with_path(edit, params)
    if what == "window":
        c = dataclasses.replace(c, layer_window=9)
    logits, _ = FORWARD(edited, c, jnp.asarray(tokens[:1]))
    assert float(np.abs(np.asarray(logits) - want[:1]).max()) > 20 * TOL


# ---- (2) chunked prefill, then decoding, through forward_paged -------------

PAGED = jax.jit(
    lambda params, config, toks, pool, tables, rows, pos, wb, wo, kernel:
    forward_paged(params, config, toks, pool=pool, tables=tables,
                  seq_row=rows, positions=pos, write_block=wb, write_off=wo,
                  use_kernel=kernel),
    static_argnums=(1, 9))
TABLES = np.zeros((ROWS - 2, 8), np.int32)
for _r in range(ROWS - 2):
    TABLES[_r, :6] = 1 + 6 * _r + np.arange(6)


def feed(model, pool, runs, width=STEP, tables=TABLES, kernel=None):
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
                         vec(wb, BLOCKS), vec(wo, 0), kernel)
    out, at = [], 0
    for _row, t, _start in runs:
        out.append(np.asarray(logits[at:at + len(t)]))
        at += len(t)
    return out, pool


def fresh_pool(model):
    return init_paged_pool(model[1], BLOCKS, BLOCK, state_rows=ROWS,
                           step_tokens=STEP)


def test_chunked_prefill_then_decode_equals_the_reference(model, tokens,
                                                          want):
    """Row 1 prefills sequence 0 in chunks of 10, 17 and 4 — the second
    crosses the window's edge twice over, the third ends at
    ``prompt[:-1]`` of a 32-token prompt — feeds the prompt's last token
    alone and decodes the rest a token a call, past five windows, while
    row 2 prefills sequence 1 in chunks of 3, 20 and 9 beside it and
    decodes too: every logit equals the reference's full forward."""
    pool = fresh_pool(model)
    got = {1: [], 2: []}
    plan = [[(1, 0, 10), (2, 0, 3)], [(1, 10, 17)], [(2, 3, 20)],
            [(1, 27, 4), (2, 23, 9)], [(1, 31, 1)]]
    plan += [[(1, p, 1), (2, p, 1)] for p in range(32, 48)]
    for call in plan:
        runs = [(row, tokens[row - 1][s:s + n], s) for row, s, n in call]
        out, pool = feed(model, pool, runs)
        for (row, _s, _n), lg in zip(call, out):
            got[row].append(lg)
    for row in (1, 2):
        assert float(np.abs(np.concatenate(got[row])
                            - want[row - 1]).max()) < TOL


def test_the_kernels_read_what_the_gather_reads(model, tokens, want):
    """``use_kernel=True`` (``paged_attention_rows`` interpreted off the
    TPU): the block plan for the full and cross layers, the window plan
    over the rings' constant table, the folded head axis — the same logits
    as the reference, prefill chunks and decode rows in one call."""
    pool = fresh_pool(model)
    (a,), pool = feed(model, pool, [(1, tokens[0][:20], 0)], kernel=True)
    (b, c), pool = feed(model, pool, [(1, tokens[0][20:30], 20),
                                      (2, tokens[1][:12], 0)], kernel=True)
    (d, e), pool = feed(model, pool, [(1, tokens[0][30:31], 30),
                                      (2, tokens[1][12:13], 12)],
                        kernel=True)
    assert float(np.abs(np.concatenate([a, b, d])
                        - want[0][:31]).max()) < TOL
    assert float(np.abs(np.concatenate([c, e]) - want[1][:13]).max()) < TOL


def _two_chunks(model, tokens, between):
    pool = fresh_pool(model)
    (a,), pool = feed(model, pool, [(1, tokens[0][:20], 0)])
    pool = between(pool)
    (b,), _ = feed(model, pool, [(1, tokens[0][20:40], 20)])
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


@pytest.mark.parametrize("leaf", ["ssm", "conv", "win_k", "win_v", "k"])
def test_a_zeroed_carry_fails_the_tolerance(model, tokens, want, leaf):
    """Each kind of cache carries what the second chunk reads: the state,
    the conv's window, the rings (the chunk's first queries read 7
    positions of the first chunk), the full layer's blocks."""
    def lose(pool):
        if leaf == "k":
            return pool._replace(k=jnp.zeros_like(pool.k))
        return pool._replace(rows=pool.rows._replace(**{
            leaf: jnp.zeros_like(getattr(pool.rows, leaf))}))

    lost = _two_chunks(model, tokens, lose)
    assert float(np.abs(lost - want[0][:40]).max()) > 100 * TOL


def test_padding_and_dropped_writes_advance_no_row(model):
    """A call of pure padding (row 0, position 0, write dropped), and
    padding beside another row's run, leave row 0's state, conv window and
    rings bit-equal: padding is addressed to row 0 and is no entry of
    it."""
    pool = fresh_pool(model)
    _, pool = feed(model, pool, [(0, [5, 6, 7], 0)])
    before = jax.tree_util.tree_map(np.asarray, pool.rows)
    _, pool = feed(model, pool, [])                       # padding alone
    _, pool = feed(model, pool, [(1, [9], 0)])            # and beside a row
    for was, now in zip(before, pool.rows):
        assert np.array_equal(was[:, 0], np.asarray(now)[:, 0])
    assert float(np.abs(before.ssm[:, 0]).max()) > 0
    assert float(np.abs(before.win_k[:, 0]).max()) > 0


def test_a_row_reused_at_position_0_sees_nothing_of_its_last_tenant(
        model, tokens, want):
    """Row 1 holds sequence 0 to its end (state, rings wrapped five
    times); sequence 1 then starts in the same row at position 0 with no
    program between: its logits are the reference's."""
    pool = fresh_pool(model)
    for s in (0, 24):
        _, pool = feed(model, pool, [(1, tokens[0][s:s + 24], s)])
    (a,), pool = feed(model, pool, [(1, tokens[1][:20], 0)])
    (b,), pool = feed(model, pool, [(1, tokens[1][20:40], 20)])
    assert float(np.abs(np.concatenate([a, b]) - want[1][:40]).max()) < TOL


def test_a_step_wider_than_the_rings_slack_is_refused(model):
    pool = init_paged_pool(model[1], BLOCKS, BLOCK, state_rows=ROWS,
                           step_tokens=8)
    with pytest.raises(ValueError, match="step_tokens"):
        feed(model, pool, [(1, list(range(20)), 0)])


# ---- (3) the ops: Mamba-1's scans, the window plan -------------------------

def _scan_case(key, t, i=16, n=4):
    ks = jax.random.split(key, 6)
    u = jax.random.normal(ks[0], (t, i))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (t, i)))
    a = -jnp.exp(jax.random.normal(ks[2], (i, n)))
    b, c = jax.random.normal(ks[3], (t, n)), jax.random.normal(ks[4], (t, n))
    d = jax.random.normal(ks[5], (i,))
    return u, dt, a, b, c, d


def test_scan1_flat_in_runs_equals_scan1_dense():
    """A 29-entry sequence fed as runs of 11 (a block of 8 + 3), 1, 1 and
    16 entries through rows' stored states, another row's run beside it
    and padding that is not kept behind: the dense scan's outputs."""
    u, dt, a, b, c, d = _scan_case(jax.random.PRNGKey(0), 29)
    want = ssm_ops.scan1_dense(u[None], dt[None], a, b[None], c[None],
                               d)[0]
    other, width = 5, 24

    @jax.jit
    def call(ssm, rows, pos, keep, u, dt, b, c):
        plan = ssm_ops.plan_runs(rows, pos, keep, 3)
        return ssm_ops.scan1_flat(u, dt, a, b, c, d, ssm, jnp.int32(1), rows,
                                  plan)

    ssm = jnp.ones((2, 3, 4, 16), jnp.float32)            # stale states
    got, at = [], 0
    for n in (11, 1, 1, 16):
        pad = width - other - n
        rows = jnp.asarray([2] * other + [1] * n + [0] * pad, jnp.int32)
        pos = jnp.asarray(list(range(other)) + list(range(at, at + n))
                          + [0] * pad, jnp.int32)
        keep = jnp.arange(width) < other + n
        cat = lambda v: jnp.concatenate(
            [v[:other], v[at:at + n], jnp.ones((pad,) + v.shape[1:])])
        y, ssm = call(ssm, rows, pos, keep, cat(u), cat(dt), cat(b), cat(c))
        got.append(y[other:other + n])
        at += n
    assert float(jnp.abs(jnp.concatenate(got) - want).max()) < 1e-5
    assert float(jnp.abs(ssm[0] - 1.0).max()) == 0       # the other layer
    assert float(jnp.abs(ssm[1, 0] - 1.0).max()) == 0     # a row with no run


def test_scan1_flat_entries_not_kept_advance_nothing():
    u, dt, a, b, c, d = _scan_case(jax.random.PRNGKey(1), 6)
    ssm = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 4, 16))
    rows = jnp.asarray([0, 0, 0, 1, 1, 1], jnp.int32)
    pos = jnp.asarray([3, 4, 5, 7, 8, 9], jnp.int32)
    keep = jnp.asarray([True, True, True, False, False, False])
    plan = ssm_ops.plan_runs(rows, pos, keep, 2)
    _, out = ssm_ops.scan1_flat(u, dt, a, b, c, d, ssm, jnp.int32(0), rows,
                                plan)
    assert np.array_equal(np.asarray(out[0, 1]), np.asarray(ssm[0, 1]))
    assert not np.array_equal(np.asarray(out[0, 0]), np.asarray(ssm[0, 0]))


def test_window_plan_starts_an_item_at_its_first_querys_window():
    rows = jnp.asarray([0, 1, 1, 1, 2], jnp.int32)
    pos = jnp.asarray([40, 17, 18, 19, 3], jnp.int32)
    plan = pa.plan_rows(rows, pos, block_size=8, table_width=8, q_tile=4,
                        window=8)
    assert plan.window == 8 and int(plan.num_items[0]) == 3
    # positions 33..40 -> blocks 4..5; 10..19 -> 1..2; 0..3 -> 0
    assert [int(x) for x in plan.first[:3]] == [4, 1, 0]
    assert [int(x) for x in plan.blocks[:3]] == [6, 3, 1]
    with pytest.raises(ValueError, match="rings"):
        pa.plan_rows(rows, pos, block_size=8, table_width=8, q_tile=4,
                     window=8, tables=jnp.zeros((3, 8), jnp.int32))


@pytest.mark.parametrize("heads,stored", [(1, 1), (2, 2), (4, 4), (8, 8),
                                          (16, 16), (32, 32), (10, 2),
                                          (6, 2), (12, 4), (3, 1)])
def test_stored_kv_heads_folds_only_what_would_be_padded(heads, stored):
    assert stored_kv_heads(heads) == stored


# ---- (4) the pool's bytes by descriptor ------------------------------------

def test_the_pools_bytes_by_descriptor_equal_the_configuration_files():
    """At the published sizes, from ``eval_shape`` alone: the weights and
    each kind of cache are what ``benchmark/configs``' ``bytes`` says, a
    kind's descriptor is its leaves' bytes, and the window layers' bytes
    do not grow with ``max_len``."""
    cfg = load_json(HERE, "configs", "phi-4-mini-flash-reasoning.json")
    c = model_config(cfg)
    b = cfg["bytes"]
    tree = jax.eval_shape(functools.partial(init_params, c),
                          jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(tree)
    assert sum(x.size for x in leaves) == b["params"] == 3_852_457_984
    assert sum(x.size * x.dtype.itemsize for x in leaves) == b[
        "weights_bf16_bytes"]
    mix = load_json(HERE, "traffic", "grpo-rollout-ctx4k.json")["engine"]
    slots, max_len = mix["num_slots"], mix["max_len"]
    bs = paged_kv.resolve_block_size(paged_kv.kv_row_bytes(c), max_len)
    step = max(4 * slots, 64)
    rows = slots + max(2, slots // 6)
    assert (bs, step, rows) == (b["block_size"], 192, b["state_rows"])
    assert window_capacity(c, bs, step) == b["window_capacity"] == 704

    def pool_bytes(max_len):
        nb = (slots + 4) * (max_len // bs)
        pool = jax.eval_shape(lambda: init_paged_pool(
            c, nb, bs, state_rows=rows, step_tokens=step))
        size = lambda a: a.size * a.dtype.itemsize
        got = {"kv": size(pool.k) + size(pool.v),
               "window": size(pool.rows.win_k) + size(pool.rows.win_v),
               "ssm": size(pool.rows.ssm), "conv": size(pool.rows.conv)}
        kinds = {k.kind: k.nbytes(nb, bs, rows)
                 for k in cache_kinds(c, bs, step)}
        assert got == kinds
        return got

    got = pool_bytes(max_len)
    assert got == {"kv": b["kv_pool_bytes"],
                   "window": b["window_pool_bytes"],
                   "ssm": b["state_pool_bytes"],
                   "conv": b["conv_pool_bytes"]}
    twice = pool_bytes(2 * max_len)
    assert twice["kv"] == 2 * got["kv"]
    assert twice["window"] == got["window"]       # a window, not the context
    assert got["window"] == 8 * rows * (512 + 192) * b[
        "kv_bytes_per_token_per_layer"]
    # 9 of 32 layers own a cache... and 14 hold nothing
    assert [k.layers for k in cache_kinds(c, bs, step)] == [1, 8, 9, 9]
    share = (b["weights_bf16_bytes"] + sum(got.values())) / 17.18e9
    assert 0.60 < share < 0.63


# ---- (5) through RolloutEngine ----------------------------------------------

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
    """One request alone: 29 prompt tokens in chunks of 16 and 13 (the
    first crosses the window's edge), 24 new tokens: past 6 windows."""
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
    seven followers that fork all three kinds of state — block refcounts
    for the full layer, and a copy of the donor's snapshot row: the
    mixers' state, the conv windows and the window layers' rings — and
    every one of the eight equals the lone request token for token."""
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
    """A saturated engine launches step k+1 before step k's tokens are
    home (``_saturated``); held serial it serves the same tokens."""
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


def test_the_step_reports_its_columns_and_copies(model):
    obs.enable()
    eng = make_engine(model)
    rids = eng.submit_group(PROMPT, 4, max_new_tokens=4)
    drain(eng, rids)
    steps = [s.attrs for s in obs.get_tracer().spans()
             if s.name == "engine.step" and "kv_columns" in s.attrs]
    assert steps
    first = steps[0]            # 16 prompt tokens at positions 0..15
    assert first["kv_columns_full"] == sum(range(1, 17))
    assert first["kv_columns_window"] == 2 * sum(min(p, 8)
                                                 for p in range(1, 17))
    # a wide step with no sampler: nothing reaches the cross layer, which
    # runs over the 8 rows' (empty) places all the same (PR 46)
    assert (first["entries"], first["head_entries"]) == (16, 8)
    assert first["kv_columns_cross"] == 0 and first["cross_entries"] == 8
    assert all(s["kv_columns"] == (s["kv_columns_full"]
                                   + s["kv_columns_window"]
                                   + s["kv_columns_cross"]) for s in steps)
    # a step as wide as the rows gathers nothing: every entry's context
    narrow = [s for s in steps if s["entries"] == 8]
    assert narrow and all(
        s["kv_columns_cross"] == s["kv_columns_full"] > 0
        and s["cross_entries"] == 8 for s in narrow)
    # a fork copies a row of every window layer's ring with the state
    assert max(s["ssm_state_copies"] for s in steps) >= 1
    assert all(s["window_row_copies"] == 2 * s["ssm_state_copies"]
               for s in steps)
    assert eng.cache_kind_bytes == {
        k.kind: k.nbytes(eng._alloc.num_blocks, 8, 8 + 2)
        for k in cache_kinds(model[1], 8, 16)}


def test_the_cross_columns_count_the_entries_that_reach_the_cross_layers(
        model, monkeypatch):
    """Every step of a run in which prefill chunks ride beside decode rows,
    against the plan the step was launched with: in a step that gathers
    its samplers (wider than the rows) ``kv_columns_cross`` is the contexts
    of the entries that put — decode rows, a completing prefill's last —
    times the cross layers, in any other step every used entry's; and
    ``cross_entries`` is the entries the head ran over."""
    obs.enable()
    eng = make_engine(model)
    plans = []
    note = type(eng)._note_pattern_step

    def spy(self, st, plan, used, n_copies):
        plans.append((plan[:, :used].copy(), plan.shape[1]))
        return note(self, st, plan, used, n_copies)

    monkeypatch.setattr(type(eng), "_note_pattern_step", spy)
    rids = eng.submit_group(PROMPT, 4, max_new_tokens=8)
    for _ in range(4):
        eng.step()
    rids.append(eng.submit(PROMPT[:21], max_new_tokens=4))
    drain(eng, rids)
    steps = [s.attrs for s in obs.get_tracer().spans()
             if s.name == "engine.step" and "kv_columns" in s.attrs]
    assert len(steps) == len(plans)
    kinds = set()
    for attrs, (plan, entries) in zip(steps, plans):
        ctx = plan[2].astype(np.int64) + 1
        puts = (plan[5] & FEED_PUT) > 0
        wide = entries > eng.num_slots
        want = int(ctx[puts].sum()) if wide else int(ctx.sum())
        assert attrs["kv_columns_cross"] == want       # one cross layer
        assert attrs["kv_columns_full"] == int(ctx.sum())
        assert attrs["cross_entries"] == attrs["head_entries"] == (
            eng.num_slots if wide else entries)
        kinds.add((wide, bool(puts.any()), bool((~puts).any())))
    # wide steps with and without samplers, chunks beside decode rows
    assert {(True, False, True), (True, True, True),
            (False, True, False)} <= kinds


def test_a_pattern_that_ends_in_a_kind_that_writes_pays_every_entry():
    """The delta-rule pattern cuts nothing: its ``cross_entries`` is the
    step's ``entries`` at both widths."""
    from senweaver_ide_tpu.models.config import tiny_solar_open2_test
    config = tiny_solar_open2_test()
    obs.enable()
    eng = make_engine((init_params(config, jax.random.PRNGKey(0)), config))
    drain(eng, [eng.submit(PROMPT, max_new_tokens=3)])
    steps = [s.attrs for s in obs.get_tracer().spans()
             if s.name == "engine.step" and "cross_entries" in s.attrs]
    assert {s["entries"] for s in steps} == {8, 16}
    assert all(s["cross_entries"] == s["entries"] for s in steps)
    assert all(s["kv_columns_cross"] == 0 for s in steps)


# ---- (6) what has no form yet is refused by name ---------------------------

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
    "the sliding-window ring cache of the slot layout":
        lambda m: RolloutEngine(
            m[0], dataclasses.replace(m[1], sliding_window=16), num_slots=2,
            max_len=64),
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
    "a quantized pool": lambda m: forward_paged(
        m[0], m[1], jnp.zeros((4,), jnp.int32),
        pool=fresh_pool(m)._replace(k_scale=jnp.zeros((1,))),
        tables=jnp.asarray(TABLES), seq_row=jnp.zeros((4,), jnp.int32),
        positions=jnp.arange(4), write_block=jnp.zeros((4,), jnp.int32),
        write_off=jnp.arange(4)),
}


@pytest.mark.parametrize("mechanism", sorted(REFUSED))
def test_what_has_no_form_for_the_pattern_is_refused_by_name(model,
                                                             mechanism):
    """Each mechanism raises ``LayerPatternUnsupported`` with the mechanism
    and the model in its message; none reaches the ``slots`` fallback."""
    with pytest.raises(LayerPatternUnsupported) as err:
        REFUSED[mechanism](model)
    said = mechanism.split(" (init_kv_cache)")[0]
    assert said in str(err.value) and said in err.value.mechanism
    assert model[1].name in str(err.value)


def test_a_pattern_model_never_reaches_the_slots_fallback(model):
    """The engine's silent fallbacks (``kv_quant``, the one-window ring, a
    mesh) are refusals for a ``layer_types`` model; what it serves from is
    the paged pool."""
    eng = make_engine(model)
    assert (eng.kv_layout, eng.kv_layout_fallback) == ("paged", None)
    assert eng.cache is None and eng.pool.rows.win_k is not None
    assert not eng._ring
