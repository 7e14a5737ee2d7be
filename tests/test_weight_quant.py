"""Weight-only int8 quantization: accuracy, decode-path transparency,
serving integration, and the publish re-quantization bridge."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from senweaver_ide_tpu.models import (get_config, init_kv_cache, init_params,
                                      is_quantized, quantize_weights_int8,
                                      quantized_bytes)
from senweaver_ide_tpu.models.transformer import forward


def _setup(name="tiny-test"):
    c = get_config(name)
    params = init_params(c, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                              c.vocab_size, dtype=jnp.int32)
    return c, params, toks


def test_quantized_forward_close_to_fp():
    c, params, toks = _setup()
    ref, _ = forward(params, c, toks)
    qp = quantize_weights_int8(params)
    assert is_quantized(qp) and not is_quantized(params)
    got, _ = forward(qp, c, toks)
    ref, got = np.asarray(ref), np.asarray(got)
    # int8 per-channel error compounds over layers; demand the logits
    # stay close in relative norm and agree on nearly all argmaxes
    rel = np.linalg.norm(got - ref) / np.linalg.norm(ref)
    assert rel < 0.05, rel
    agree = np.mean(got.argmax(-1) == ref.argmax(-1))
    assert agree > 0.9, agree


def test_quantized_cache_decode_matches_full():
    """The property serving relies on: prefill+decode through the KV
    cache equals the no-cache forward — with int8 weights in play."""
    c, params, toks = _setup()
    qp = quantize_weights_int8(params)
    full, _ = forward(qp, c, toks)
    cache = init_kv_cache(c, 2, 32)
    logits, cache = forward(qp, c, toks[:, :16], cache=cache,
                            fresh_cache=True)
    outs = [logits[:, -1]]
    for i in range(16, 24):
        step, cache = forward(qp, c, toks[:, i:i + 1], cache=cache)
        outs.append(step[:, -1])
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)),
                               np.asarray(full[:, 15:24]),
                               atol=2e-4, rtol=2e-4)


def test_idempotent_and_smaller():
    _, params, _ = _setup()
    qp = quantize_weights_int8(params)
    assert quantized_bytes(qp) < 0.62 * quantized_bytes(params)
    qp2 = quantize_weights_int8(qp)
    assert qp2["layers"]["wq"].dtype == jnp.int8


def test_untied_head_quantized():
    c, params, toks = _setup()
    c = dataclasses.replace(c, tie_word_embeddings=False)
    params = init_params(c, jax.random.PRNGKey(0))
    qp = quantize_weights_int8(params)
    assert qp["lm_head"].dtype == jnp.int8
    ref, _ = forward(params, c, toks)
    got, _ = forward(qp, c, toks)
    rel = (np.linalg.norm(np.asarray(got) - np.asarray(ref))
           / np.linalg.norm(np.asarray(ref)))
    assert rel < 0.05, rel


def test_moe_banks_quantized_router_fp():
    """Expert banks quantize (per-expert per-channel scales); the tiny
    precision-sensitive router stays fp; the routed forward stays close
    to full precision."""
    c = get_config("tiny-moe-test")
    params = init_params(c, jax.random.PRNGKey(0))
    qp = quantize_weights_int8(params)
    assert qp["layers"]["wq"].dtype == jnp.int8
    assert qp["layers"]["w_gate"].dtype == jnp.int8
    assert qp["layers"]["w_gate_scale"].shape == qp["layers"][
        "w_gate"].shape[:2] + qp["layers"]["w_gate"].shape[-1:]
    assert qp["layers"]["router"].dtype == c.dtype
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0,
                              c.vocab_size, dtype=jnp.int32)
    ref, _ = forward(params, c, toks)
    got, _ = forward(qp, c, toks)
    # top-k routing is DISCONTINUOUS: the int8 perturbation flips expert
    # assignment for borderline tokens, so the norm metric is dominated
    # by a few rerouted positions (observed rel ≈ 0.13 on this random
    # tiny model). The serving-relevant metric is argmax agreement.
    rel = (np.linalg.norm(np.asarray(got) - np.asarray(ref))
           / np.linalg.norm(np.asarray(ref)))
    assert rel < 0.25, rel
    agree = np.mean(np.asarray(got).argmax(-1)
                    == np.asarray(ref).argmax(-1))
    assert agree > 0.85, agree


def test_engine_republish_requantizes():
    from senweaver_ide_tpu.rollout import RolloutEngine
    c, params, _ = _setup()
    engine = RolloutEngine(quantize_weights_int8(params), c, num_slots=2,
                           max_len=64, eos_id=None, seed=0)
    assert is_quantized(engine.params)
    # trainer publishes full-precision weights; the bridge re-quantizes
    engine.update_params(init_params(c, jax.random.PRNGKey(7)))
    assert is_quantized(engine.params)
    rid = engine.submit([1, 2, 3], max_new_tokens=4)
    out = engine.run()
    assert len(out[rid]) == 4


def test_train_and_pipeline_reject_int8():
    import optax
    import pytest

    from senweaver_ide_tpu.parallel.pipeline import split_layers_for_stages
    from senweaver_ide_tpu.training.trainer import TrainState, train_step
    c, params, toks = _setup()
    qp = quantize_weights_int8(params)
    with pytest.raises(TypeError, match="serving"):
        split_layers_for_stages(qp, 2)
    opt = optax.sgd(0.1)
    state = TrainState(params=qp, opt_state=None, step=jnp.zeros((),
                       jnp.int32), opt=opt)
    with pytest.raises(TypeError, match="SERVING"):
        train_step(state, c, None, toks,
                   jnp.ones_like(toks, jnp.bool_),
                   jnp.ones((2,), jnp.float32),
                   jnp.arange(2, dtype=jnp.int32))


def test_mesh_backed_quantized_engine():
    """Scale leaves must have sharding rules: a mesh-backed engine with
    int8 params places every leaf through param_specs."""
    from senweaver_ide_tpu.parallel import MeshConfig, make_mesh
    from senweaver_ide_tpu.rollout import RolloutEngine
    c, params, _ = _setup()
    mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    engine = RolloutEngine(quantize_weights_int8(params), c, num_slots=4,
                           max_len=64, eos_id=None, seed=0, mesh=mesh)
    rid = engine.submit([1, 2, 3], max_new_tokens=4)
    assert len(engine.run()[rid]) == 4
    # publish path re-places re-quantized params through the same specs
    engine.update_params(init_params(c, jax.random.PRNGKey(3)))
    assert is_quantized(engine.params)


def test_export_hf_rejects_int8(tmp_path):
    from senweaver_ide_tpu.models.load import export_hf_params
    c, params, _ = _setup()
    with pytest.raises(TypeError, match="serving"):
        export_hf_params(quantize_weights_int8(params), c, str(tmp_path))


def test_all_serving_levers_compose():
    """The max-memory-efficiency serving config: sliding-window RING
    cache + int8 KV quantization + int8 weights, through the engine (the
    one-16GB-chip 7B posture, every lever at once)."""
    from senweaver_ide_tpu.rollout import RolloutEngine
    c = dataclasses.replace(get_config("tiny-test"), sliding_window=128,
                            kv_quant=True, max_seq_len=512)
    params = quantize_weights_int8(init_params(c, jax.random.PRNGKey(0)))
    engine = RolloutEngine(params, c, num_slots=2, max_len=128,
                           eos_id=None, seed=0)
    rid = engine.submit(list(range(1, 40)), max_new_tokens=110)
    out = engine.run()
    # decode proceeds PAST the ring capacity (modular writes) and stays
    # finite/int-valued the whole way
    assert len(out[rid]) == 110
    st = engine.stats()
    assert st["weight_quant"] == 1


def test_tied_head_int8_shadow():
    """Tied-embedding models get an int8 shadow for the head matmul
    (the ~15% of flagship decode bytes the dense pass left bf16); the
    gather keeps the bf16 embed, logits stay close, and a mesh-backed
    engine places the new leaves."""
    c = dataclasses.replace(get_config("tiny-test"),
                            tie_word_embeddings=True)
    params = init_params(c, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              c.vocab_size, dtype=jnp.int32)
    qp = quantize_weights_int8(params)
    assert qp["tied_head_q8"].dtype == jnp.int8
    assert qp["embed"].dtype == c.dtype          # gather stays fp
    ref, _ = forward(params, c, toks)
    got, _ = forward(qp, c, toks)
    rel = (np.linalg.norm(np.asarray(got) - np.asarray(ref))
           / np.linalg.norm(np.asarray(ref)))
    assert rel < 0.05, rel
    # idempotent: a second pass must not add a shadow of the shadow
    qp2 = quantize_weights_int8(qp)
    assert qp2["tied_head_q8"] is qp["tied_head_q8"]

    from senweaver_ide_tpu.parallel import MeshConfig, make_mesh
    from senweaver_ide_tpu.rollout import RolloutEngine
    mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    engine = RolloutEngine(qp, c, num_slots=4, max_len=64, eos_id=None,
                           seed=0, mesh=mesh)
    rid = engine.submit([1, 2, 3], max_new_tokens=4)
    assert len(engine.run()[rid]) == 4
