"""Pretrained-weight loader: HF-layout safetensors ↔ stacked pytree.

The decisive test here is parity against the HF *implementation*: a
randomly-initialized transformers Qwen2/LLaMA model is saved with
``save_pretrained`` and reloaded through ``load_hf_params``; our forward
must match the torch forward logits. That pins the weight transposes, the
RoPE convention (rotate_half), RMSNorm eps placement, and SwiGLU wiring all
at once — no egress needed."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from senweaver_ide_tpu.models import ModelConfig, forward, get_config, \
    init_params
from senweaver_ide_tpu.models.load import (available_hf_keys,
                                           export_hf_params, load_hf_params)


@pytest.fixture(scope="module")
def cfg():
    return get_config("tiny-test")


def test_export_load_roundtrip(tmp_path, cfg):
    params = init_params(cfg, jax.random.PRNGKey(0))
    export_hf_params(params, cfg, str(tmp_path))
    loaded = load_hf_params(str(tmp_path), cfg)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        params, loaded)


def test_roundtrip_forward_identical(tmp_path, cfg):
    params = init_params(cfg, jax.random.PRNGKey(1))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 16), 0, 512)
    ref, _ = forward(params, cfg, tokens)
    export_hf_params(params, cfg, str(tmp_path))
    out, _ = forward(load_hf_params(str(tmp_path), cfg), cfg, tokens)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_strict_rejects_leftover(tmp_path, cfg):
    params = init_params(cfg, jax.random.PRNGKey(0))
    export_hf_params(params, cfg, str(tmp_path))
    # Append an extra tensor the config doesn't know about.
    from safetensors.numpy import load_file, save_file
    path = tmp_path / "model.safetensors"
    tensors = load_file(str(path))
    tensors["model.layers.0.self_attn.unknown.weight"] = np.zeros(
        (2, 2), np.float32)
    save_file(tensors, str(path))
    with pytest.raises(ValueError, match="unconsumed"):
        load_hf_params(str(tmp_path), cfg)
    assert load_hf_params(str(tmp_path), cfg, strict=False) is not None


def test_shape_mismatch_reported(tmp_path, cfg):
    params = init_params(cfg, jax.random.PRNGKey(0))
    export_hf_params(params, cfg, str(tmp_path))
    wrong = dataclasses.replace(cfg, intermediate_size=64)
    with pytest.raises(ValueError, match="shape"):
        load_hf_params(str(tmp_path), wrong)


def test_missing_key_reported(tmp_path, cfg):
    tied = dataclasses.replace(cfg, tie_word_embeddings=True)
    params = init_params(tied, jax.random.PRNGKey(0))   # no lm_head saved
    export_hf_params(params, tied, str(tmp_path))
    with pytest.raises(KeyError, match="lm_head"):
        load_hf_params(str(tmp_path), cfg)              # untied cfg wants it


def test_sharded_index_checkpoint(tmp_path, cfg):
    """Multi-file checkpoints with model.safetensors.index.json load too."""
    import json

    from safetensors.numpy import load_file, save_file

    params = init_params(cfg, jax.random.PRNGKey(3))
    export_hf_params(params, cfg, str(tmp_path))
    tensors = load_file(str(tmp_path / "model.safetensors"))
    keys = sorted(tensors)
    half = len(keys) // 2
    shards = {"model-00001-of-00002.safetensors": keys[:half],
              "model-00002-of-00002.safetensors": keys[half:]}
    weight_map = {}
    for fname, ks in shards.items():
        save_file({k: tensors[k] for k in ks}, str(tmp_path / fname))
        weight_map.update({k: fname for k in ks})
    (tmp_path / "model.safetensors").unlink()
    (tmp_path / "model.safetensors.index.json").write_text(
        json.dumps({"weight_map": weight_map}))
    loaded = load_hf_params(str(tmp_path), cfg)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        params, loaded)
    assert "model.embed_tokens.weight" in available_hf_keys(str(tmp_path))


def _hf_parity(tmp_path, torch_model, our_cfg, vocab):
    import torch

    torch_model = torch_model.eval().to(torch.float32)
    torch_model.save_pretrained(str(tmp_path), safe_serialization=True)
    params = load_hf_params(str(tmp_path), our_cfg)
    ids = np.asarray([[1, 5, 9, 42, 7, 3, 100, 2]]) % vocab
    with torch.no_grad():
        ref = torch_model(torch.tensor(ids)).logits.numpy()
    ours, _ = forward(params, our_cfg, jnp.asarray(ids, jnp.int32))
    np.testing.assert_allclose(np.asarray(ours), ref, atol=2e-4, rtol=2e-4)


def test_parity_vs_transformers_qwen2(tmp_path):
    """Our forward on loaded weights == HF Qwen2 torch forward (fp32)."""
    transformers = pytest.importorskip("transformers")

    hf_cfg = transformers.Qwen2Config(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rope_theta=10000.0, rms_norm_eps=1e-6,
        tie_word_embeddings=False)
    model = transformers.Qwen2ForCausalLM(hf_cfg)
    our_cfg = ModelConfig(
        name="qwen2-parity", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, max_seq_len=128, rope_theta=10000.0, qkv_bias=True,
        dtype=jnp.float32, matmul_precision="highest")
    _hf_parity(tmp_path, model, our_cfg, 512)


def test_parity_vs_transformers_llama(tmp_path):
    """DeepSeek-Coder is LLaMA-architecture; parity vs HF LlamaForCausalLM."""
    transformers = pytest.importorskip("transformers")

    hf_cfg = transformers.LlamaConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=128, rope_theta=100000.0, rms_norm_eps=1e-6,
        attention_bias=False, tie_word_embeddings=False)
    model = transformers.LlamaForCausalLM(hf_cfg)
    our_cfg = ModelConfig(
        name="llama-parity", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=4,
        head_dim=16, max_seq_len=128, rope_theta=100000.0, qkv_bias=False,
        dtype=jnp.float32, matmul_precision="highest")
    _hf_parity(tmp_path, model, our_cfg, 512)


def test_parity_vs_transformers_qwen3(tmp_path):
    """Qwen3's QK-norm (per-head RMSNorm before RoPE) wired exactly as
    HF does it — parity vs Qwen3ForCausalLM at fp32 tolerance."""
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "Qwen3ForCausalLM"):
        pytest.skip("transformers too old for Qwen3")

    hf_cfg = transformers.Qwen3Config(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=128, rope_theta=1_000_000.0,
        rms_norm_eps=1e-6, tie_word_embeddings=False,
        attention_bias=False)
    model = transformers.Qwen3ForCausalLM(hf_cfg)
    our_cfg = ModelConfig(
        name="qwen3-parity", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, max_seq_len=128, rope_theta=1_000_000.0,
        qkv_bias=False, qk_norm=True,
        dtype=jnp.float32, matmul_precision="highest")
    _hf_parity(tmp_path, model, our_cfg, 512)


def test_qk_norm_roundtrip_and_cache_parity(tmp_path):
    """Export/load round-trip carries q_norm/k_norm; prefill+decode
    through the KV cache equals the full forward with QK-norm on."""
    cfg = dataclasses.replace(get_config("tiny-test"), name="tiny-qk",
                              qkv_bias=False, qk_norm=True)
    params = init_params(cfg, jax.random.PRNGKey(3))
    # break the all-ones init with DISTINCT values per tensor so a
    # q/k mapping swap in load/export cannot round-trip undetected
    import jax as _jax
    params["layers"]["q_norm"] = _jax.random.uniform(
        _jax.random.PRNGKey(4), params["layers"]["q_norm"].shape,
        minval=0.5, maxval=1.5)
    params["layers"]["k_norm"] = _jax.random.uniform(
        _jax.random.PRNGKey(5), params["layers"]["k_norm"].shape,
        minval=0.5, maxval=1.5)
    export_hf_params(params, cfg, str(tmp_path))
    loaded = load_hf_params(str(tmp_path), cfg)
    for name in ("q_norm", "k_norm"):
        np.testing.assert_allclose(np.asarray(loaded["layers"][name]),
                                   np.asarray(params["layers"][name]),
                                   rtol=1e-6)

    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 24), 0, 512)
    full, _ = forward(params, cfg, toks)
    from senweaver_ide_tpu.models import init_kv_cache
    cache = init_kv_cache(cfg, 2, 32)
    logits, cache = forward(params, cfg, toks[:, :16], cache=cache,
                            fresh_cache=True)
    outs = [logits[:, -1]]
    for i in range(16, 24):
        step, cache = forward(params, cfg, toks[:, i:i + 1], cache=cache)
        outs.append(step[:, -1])
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)),
                               np.asarray(full[:, 15:24]),
                               atol=2e-4, rtol=2e-4)


def test_parity_vs_transformers_llama3_rope_scaling(tmp_path):
    """Llama-3.1-style checkpoints: our RopeScaling (NTK-by-parts) must
    match transformers' llama3 rope_type bit-for-bit at fp32 tolerance —
    this pins the frequency-band formula, not just the plain RoPE path."""
    transformers = pytest.importorskip("transformers")
    from senweaver_ide_tpu.models import RopeScaling

    hf_cfg = transformers.LlamaConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rope_theta=100000.0, rms_norm_eps=1e-5,
        attention_bias=False, tie_word_embeddings=False,
        rope_scaling={"rope_type": "llama3", "factor": 8.0,
                      "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                      "original_max_position_embeddings": 32})
    model = transformers.LlamaForCausalLM(hf_cfg)
    our_cfg = ModelConfig(
        name="llama3-scaled-parity", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, max_seq_len=128, rope_theta=100000.0,
        rope_scaling=RopeScaling(factor=8.0, low_freq_factor=1.0,
                                 high_freq_factor=4.0,
                                 original_max_position=32),
        rms_norm_eps=1e-5, qkv_bias=False,
        dtype=jnp.float32, matmul_precision="highest")
    _hf_parity(tmp_path, model, our_cfg, 512)


def test_moe_roundtrip_mixtral_layout(tmp_path, rng):
    """Export a tiny MoE model to the Mixtral block-sparse HF layout and
    load it back: forward must match the original exactly."""
    import jax.numpy as jnp

    from senweaver_ide_tpu.models import (export_hf_params, forward,
                                          get_config, init_params,
                                          load_hf_params)

    cfg = get_config("tiny-moe-test")
    params = init_params(cfg, jax.random.PRNGKey(5))
    export_hf_params(params, cfg, str(tmp_path))
    loaded = load_hf_params(str(tmp_path), cfg, dtype=jnp.float32)

    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 8)), jnp.int32)
    a, _ = forward(params, cfg, toks)
    b, _ = forward(loaded, cfg, toks)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_parity_vs_transformers_qwen3_moe(tmp_path):
    """Qwen3-MoE: QK-norm + softmax-top-k-renormalized routing + the
    qwen3 expert layout, parity vs Qwen3MoeForCausalLM. The expert layer
    drops nothing, as HF's has no capacity limit; routing weights must
    then match exactly."""
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "Qwen3MoeForCausalLM"):
        pytest.skip("transformers too old for Qwen3-MoE")

    hf_cfg = transformers.Qwen3MoeConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=48, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_experts=4, num_experts_per_tok=2, decoder_sparse_step=1,
        norm_topk_prob=True, max_position_embeddings=128,
        rope_theta=1_000_000.0, rms_norm_eps=1e-6,
        tie_word_embeddings=False, attention_bias=False,
        mlp_only_layers=[])
    model = transformers.Qwen3MoeForCausalLM(hf_cfg)
    our_cfg = ModelConfig(
        name="qwen3-moe-parity", vocab_size=512, hidden_size=64,
        intermediate_size=48, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, max_seq_len=128, rope_theta=1_000_000.0,
        qkv_bias=False, qk_norm=True, num_experts=4,
        num_experts_per_tok=2, moe_layout="qwen3",
        dtype=jnp.float32, matmul_precision="highest")
    _hf_parity(tmp_path, model, our_cfg, 512)


def test_qwen3_moe_export_roundtrip(tmp_path):
    """Export in the qwen3 layout → autodetected load → identical."""
    cfg = dataclasses.replace(get_config("tiny-moe-test"),
                              moe_layout="qwen3", qkv_bias=False)
    params = init_params(cfg, jax.random.PRNGKey(6))
    export_hf_params(params, cfg, str(tmp_path))
    keys = available_hf_keys(str(tmp_path))
    assert any("mlp.experts.0.gate_proj" in k for k in keys)
    loaded = load_hf_params(str(tmp_path), cfg)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        params, loaded)


def test_unknown_moe_layout_rejected(tmp_path):
    cfg = dataclasses.replace(get_config("tiny-moe-test"),
                              moe_layout="qwen3-moe")   # typo'd value
    params = init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="unknown moe_layout"):
        export_hf_params(params, cfg, str(tmp_path))
