"""Model runtime tests: forward, KV-cache parity, sampling, sharded mesh."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from senweaver_ide_tpu.models import (count_params, forward, get_config,
                                      init_kv_cache, init_params, tiny_test)
from senweaver_ide_tpu.ops import (apply_rope, apply_top_k, apply_top_p,
                                   rope_cos_sin, sample_token, sampling)
from senweaver_ide_tpu.parallel import (MeshConfig, data_sharding, make_mesh,
                                        param_specs, shard_params)
from senweaver_ide_tpu.rollout import SampleParams, generate


@pytest.fixture(scope="module")
def model():
    cfg = tiny_test()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_forward_shapes_and_dtype(model):
    cfg, params = model
    toks = jnp.arange(12, dtype=jnp.int32).reshape(2, 6) % cfg.vocab_size
    logits, cache = forward(params, cfg, toks)
    assert logits.shape == (2, 6, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert cache is None


def test_prefill_matches_full_forward(model):
    cfg, params = model
    toks = jnp.array([[5, 9, 2, 7, 1, 3]], dtype=jnp.int32)
    full, _ = forward(params, cfg, toks)
    cache = init_kv_cache(cfg, 1, 16)
    cached, cache = forward(params, cfg, toks, cache=cache)
    np.testing.assert_allclose(np.asarray(full), np.asarray(cached), atol=2e-4)
    assert int(cache.length) == 6


def test_incremental_decode_matches_full(model):
    """Feeding tokens one at a time through the cache must equal the full
    causal forward — the core KV-cache correctness property."""
    cfg, params = model
    toks = jnp.array([[5, 9, 2, 7, 1, 3, 8, 4]], dtype=jnp.int32)
    full, _ = forward(params, cfg, toks)
    cache = init_kv_cache(cfg, 1, 16)
    step_logits = []
    for i in range(toks.shape[1]):
        lg, cache = forward(params, cfg, toks[:, i:i + 1], cache=cache)
        step_logits.append(lg[:, 0])
    inc = jnp.stack(step_logits, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(inc), atol=5e-4)


def test_generate_greedy_deterministic(model):
    cfg, params = model
    toks = jnp.array([[1, 2, 3]], dtype=jnp.int32)
    a = generate(params, cfg, toks, max_new_tokens=6,
                 sample=SampleParams(temperature=0.0))
    b = generate(params, cfg, toks, max_new_tokens=6,
                 sample=SampleParams(temperature=0.0))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert a.shape == (1, 6)


def test_eos_early_stop(model):
    cfg, params = model
    toks = jnp.array([[1, 2]], dtype=jnp.int32)
    greedy = generate(params, cfg, toks, max_new_tokens=4,
                      sample=SampleParams(temperature=0.0))
    eos = int(greedy[0, 1])  # force the 2nd generated token to be "eos"
    out = generate(params, cfg, toks, max_new_tokens=8, eos_id=eos,
                   sample=SampleParams(temperature=0.0))
    got = np.asarray(out)[0]
    idx = int(np.argmax(got == eos))
    assert (got[idx:] == eos).all()  # everything after stop is eos-padded


def test_rope_rotation_properties():
    cos, sin = rope_cos_sin(jnp.arange(4), 8, theta=10000.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 4, 2, 8))
    rot = apply_rope(x, cos[None], sin[None])
    # norm-preserving per (pair) rotation
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(rot), axis=-1), rtol=1e-5)
    # position 0 is identity
    np.testing.assert_allclose(np.asarray(x[:, 0]), np.asarray(rot[:, 0]),
                               rtol=1e-6)


def test_top_k_top_p_masks():
    logits = jnp.array([1.0, 2.0, 3.0, 4.0])
    k2 = apply_top_k(logits, 2)
    assert (np.asarray(k2)[:2] < -1e29).all() and (np.asarray(k2)[2:] > 0).all()
    p = apply_top_p(logits, 0.5)
    kept = np.asarray(p) > -1e29
    assert kept[3] and not kept[0]  # top token always kept, tail dropped
    # temperature 0 → greedy
    tok = sample_token(logits, jax.random.PRNGKey(0), temperature=0.0)
    assert int(tok) == 3


def test_top_p_cutoff_matches_exact():
    """Bounded-candidate nucleus mask == full-sort mask whenever the
    nucleus fits inside the cutoff."""
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((4, 1000)) * 3, jnp.float32)
    for p, cutoff in ((0.3, 128), (0.8, 128), (0.95, 600)):
        exact = np.asarray(apply_top_p(logits, p)) > -1e29
        fast = np.asarray(apply_top_p(logits, p, cutoff=cutoff)) > -1e29
        np.testing.assert_array_equal(fast, exact)
    # Nucleus wider than the cutoff clips to exactly the cutoff.
    clipped = np.asarray(apply_top_p(logits, 0.95, cutoff=64)) > -1e29
    assert (clipped.sum(axis=-1) == 64).all()


def _top_k_widths(fn, *args):
    """Last-axis widths of every ``top_k`` / ``sort`` operand in fn's
    jaxpr, nested jaxprs included."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in ("top_k", "sort"):
                found.append((eqn.primitive.name,
                              eqn.invars[0].aval.shape[-1]))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def _top_p_rows(kind, vocab):
    rng = np.random.default_rng(vocab)
    rows = rng.standard_normal((3, vocab)) * 3
    if kind == "bf16":          # thousands of equal neighbours
        return jnp.asarray(rows, jnp.bfloat16).astype(jnp.float32) / 0.8
    if kind == "one_hot":
        return jnp.zeros((3, vocab), jnp.float32).at[:, vocab // 3].set(40.0)
    if kind == "flat":          # every probability equal
        return jnp.full((3, vocab), 0.25, jnp.float32)
    assert kind == "top_k_masked"
    return apply_top_k(jnp.asarray(rows, jnp.float32), 40)


# (vocabulary, cutoff, groups, the widths ``lax.top_k`` sees): the default
# groups at real vocabularies (50,257 is divided by no group: the padding;
# 32,000 skips the cut by 128 and takes the one by 16; 1,000 keeps the
# single ``lax.top_k``), and the helper forced to cut at 1,000: once, once
# with padding, and twice.
_TOP_P_SHAPES = [(151936, 128, None, [1187, 1024, 2048]),
                 (50257, 128, None, [393, 1024, 2048]),
                 (32000, 128, None, [2000, 2048]),
                 (1000, 128, None, [1000]),
                 (1000, 16, (8,), [125, 128]),
                 (1000, 16, (7,), [143, 112]),
                 (1000, 4, (50, 5), [20, 40, 20])]


@pytest.mark.parametrize("p", [0.3, 0.8, 0.95])
@pytest.mark.parametrize("kind", ["bf16", "one_hot", "flat", "top_k_masked"])
@pytest.mark.parametrize("vocab,cutoff,groups,widths", _TOP_P_SHAPES)
def test_top_p_two_stage_matches_single_top_k(monkeypatch, vocab, cutoff,
                                              groups, widths, kind, p):
    """The staged selection returns ``lax.top_k``'s values bit for bit,
    so the nucleus mask and a key's tokens are those of the nucleus cut
    with ONE ``lax.top_k`` over the whole vocabulary."""
    if groups is not None:
        monkeypatch.setattr(
            sampling, "_top_values",
            functools.partial(sampling._top_values, groups=groups))
    logits = _top_p_rows(kind, vocab)
    probs = jax.nn.softmax(logits, axis=-1)
    key = jax.random.PRNGKey(vocab)

    def cut():
        return (sampling._top_values(probs, cutoff),
                apply_top_p(logits, p, cutoff=cutoff),
                sample_token(logits, key, top_p=p, top_p_cutoff=cutoff))

    assert _top_k_widths(lambda x: sampling._top_values(x, cutoff),
                         probs) == [("top_k", w) for w in widths]
    staged = cut()
    monkeypatch.setattr(sampling, "_top_values",
                        lambda x, k: jax.lax.top_k(x, k)[0])
    for got, want in zip(staged, cut()):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("top_p", [0.95, 1.0])
def test_sampler_program_sorts_no_vocabulary(top_p):
    """The program a chat step samples with (48 rows of Qwen's 151,936
    columns, top-p 0.95) holds no ``top_k`` or ``sort`` over the
    vocabulary; without top-p (the rollout cells) it holds no ``top_k``
    at all."""
    logits = jax.ShapeDtypeStruct((48, 151936), jnp.float32)
    found = _top_k_widths(
        lambda x, key: sample_token(x, key, temperature=0.8, top_p=top_p),
        logits, jax.random.PRNGKey(0))
    if top_p < 1.0:
        assert found and all(width < 151936 // 8 for _, width in found)
    else:
        assert found == []


def test_top_p_zero_is_disabled():
    """top_p=0 means DISABLED: sampling follows the temperature
    distribution instead of collapsing to uniform (r1 bug: p=0 masked
    every token and paid a full-vocab sort per decode step)."""
    logits = jnp.asarray([[10.0, 0.0, 0.0, 0.0]] * 64, jnp.float32)
    toks = sample_token(logits, jax.random.PRNGKey(0), temperature=1.0,
                        top_p=0.0)
    # Token 0 holds ~99.99% of the mass; uniform sampling would pick it
    # ~25% of the time — 64/64 hits is decisive.
    assert (np.asarray(toks) == 0).all()


def test_sharded_forward_on_8_device_mesh(model):
    """Multi-chip path: fsdp=2 × tp=4 mesh on the virtual CPU devices;
    sharded forward must equal single-device forward."""
    cfg, params = model
    assert len(jax.devices()) == 8, "conftest must force 8 CPU devices"
    mesh = make_mesh(MeshConfig(fsdp=2, tp=4))
    sharded = shard_params(params, mesh)
    toks = jnp.arange(8, dtype=jnp.int32).reshape(2, 4)
    toks_sharded = jax.device_put(toks, data_sharding(mesh))
    ref, _ = forward(params, cfg, toks)
    with mesh:
        out, _ = forward(sharded, cfg, toks_sharded)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-4)


def test_param_specs_cover_tree(model):
    cfg, params = model
    specs = param_specs(params)  # raises KeyError on any uncovered path
    assert jax.tree_util.tree_structure(specs) == \
        jax.tree_util.tree_structure(params)


def test_real_config_param_counts():
    cfg = get_config("qwen2.5-coder-1.5b")
    # embed 151936*1536 ≈ 233M; total ≈ 1.54B params for the full model.
    assert cfg.q_dim == 1536 and cfg.kv_dim == 256
    cfg7 = get_config("deepseek-coder-6.7b")
    assert cfg7.num_kv_heads == cfg7.num_heads  # MHA


def test_moe_model_forward_and_grads():
    """MoE policy variant: forward parity of shapes, KV-cache decode path,
    gradients through router + experts."""
    import jax
    import jax.numpy as jnp

    from senweaver_ide_tpu.models import forward, get_config, init_params
    from senweaver_ide_tpu.models.transformer import init_kv_cache

    config = get_config("tiny-moe-test")
    params = init_params(config, jax.random.PRNGKey(0))
    assert params["layers"]["router"].shape == (2, 64, 4)
    assert params["layers"]["w_gate"].shape == (2, 4, 64, 128)

    tokens = jnp.ones((2, 16), jnp.int32)
    logits, _ = forward(params, config, tokens)
    assert logits.shape == (2, 16, config.vocab_size)

    cache = init_kv_cache(config, 2, 64)
    logits_c, cache = forward(params, config, tokens, cache=cache)
    assert cache.length == 16

    def loss(p):
        out, _ = forward(p, config, tokens)
        return out.mean()

    g = jax.grad(loss)(params)
    router_g = float(jnp.abs(g["layers"]["router"]).sum())
    expert_g = float(jnp.abs(g["layers"]["w_gate"]).sum())
    assert router_g > 0 and expert_g > 0


def test_moe_model_sharded_train_step():
    """MoE params shard (ep axis) and the train step runs on a mesh."""
    import jax
    import jax.numpy as jnp

    from senweaver_ide_tpu.models import get_config
    from senweaver_ide_tpu.parallel import make_named_mesh
    from senweaver_ide_tpu.training import make_train_state, train_step

    config = get_config("tiny-moe-test")
    mesh = make_named_mesh({"ep": 2, "tp": 2},
                           devices=jax.devices()[:4])
    state = make_train_state(config, jax.random.PRNGKey(0), mesh,
                             learning_rate=1e-4)
    b, s = 4, 16
    state, metrics = train_step(
        state, config, mesh, jnp.ones((b, s), jnp.int32),
        jnp.ones((b, s), bool), jnp.linspace(-1, 1, b),
        jnp.zeros((b,), jnp.int32))
    assert jnp.isfinite(metrics["loss"])
