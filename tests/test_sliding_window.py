"""Sliding-window attention (Mistral-family; ModelConfig.sliding_window).

Window semantics: each query attends to kv positions in (q - W, q] — the
trailing W tokens including itself. Covers the op (vs a numpy oracle), the
model cache/no-cache parity, and the preset/guard surface.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from senweaver_ide_tpu.models import (forward, get_config, init_kv_cache,
                                      init_params, tiny_test)
from senweaver_ide_tpu.ops.attention import attention, causal_mask


def _oracle(q, k, v, window, q_offset=0):
    """Dense numpy attention with an explicit (q, kv) loop mask."""
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    rep = hq // hkv
    out = np.zeros_like(np.asarray(q, dtype=np.float64))
    qn = np.asarray(q, np.float64)
    kn = np.asarray(k, np.float64)
    vn = np.asarray(v, np.float64)
    for bi in range(b):
        for h in range(hq):
            kv_h = h // rep
            for qi in range(sq):
                qpos = q_offset + qi
                lo = max(0, qpos - window + 1) if window else 0
                hi = min(qpos + 1, k.shape[1])
                scores = kn[bi, lo:hi, kv_h] @ qn[bi, qi, h] / np.sqrt(d)
                p = np.exp(scores - scores.max())
                p /= p.sum()
                out[bi, qi, h] = p @ vn[bi, lo:hi, kv_h]
    return out


def test_window_mask_shape_and_bounds():
    m = causal_mask(4, 8, 4, window=2)            # queries at pos 4..7
    assert m.shape == (4, 8)
    # query 0 (abs pos 4) sees kv 3..4 only
    assert list(np.where(np.asarray(m[0]))[0]) == [3, 4]
    # per-slot offsets broadcast to (B, q, kv)
    mb = causal_mask(1, 8, jnp.array([2, 5]), window=3)
    assert mb.shape == (2, 1, 8)
    assert list(np.where(np.asarray(mb[1, 0]))[0]) == [3, 4, 5]


@pytest.mark.parametrize("window", [1, 3, 16])
def test_attention_window_matches_oracle(rng, window):
    b, s, hq, hkv, d = 2, 12, 4, 2, 8
    q = jnp.asarray(rng.normal(size=(b, s, hq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    got = attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got),
                               _oracle(q, k, v, window), atol=1e-5)


def test_window_geq_len_equals_full_causal(rng):
    b, s, h, d = 1, 10, 2, 8
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    full = attention(q, k, v, causal=True)
    win = attention(q, k, v, causal=True, window=s + 5)
    np.testing.assert_allclose(np.asarray(full), np.asarray(win), atol=1e-6)


def test_swa_model_cache_matches_full_forward(rng):
    """Incremental decode through the KV cache must equal the no-cache
    forward under a window smaller than the sequence — the decode path's
    q_offset-based window mask and the training path's must agree."""
    cfg = dataclasses.replace(tiny_test(), sliding_window=4)
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 9)), jnp.int32)
    full, _ = forward(params, cfg, toks)

    cache = init_kv_cache(cfg, 2, 16)
    outs = []
    for i in range(toks.shape[1]):
        lg, cache = forward(params, cfg, toks[:, i:i + 1], cache=cache)
        outs.append(lg)
    np.testing.assert_allclose(np.asarray(full),
                               np.asarray(jnp.concatenate(outs, axis=1)),
                               atol=2e-4)


def test_swa_prefill_then_decode(rng):
    """Chunked prefill (s>1 with cache) + single-token decode under SWA."""
    cfg = dataclasses.replace(tiny_test(), sliding_window=3)
    params = init_params(cfg, jax.random.PRNGKey(1))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 8)), jnp.int32)
    full, _ = forward(params, cfg, toks)

    cache = init_kv_cache(cfg, 1, 16)
    pre, cache = forward(params, cfg, toks[:, :5], cache=cache)
    np.testing.assert_allclose(np.asarray(full[:, :5]), np.asarray(pre),
                               atol=2e-4)
    for i in range(5, 8):
        lg, cache = forward(params, cfg, toks[:, i:i + 1], cache=cache)
        np.testing.assert_allclose(np.asarray(full[:, i:i + 1]),
                                   np.asarray(lg), atol=2e-4)


def test_swa_actually_limits_attention(rng):
    """Changing a token OUTSIDE the window must not change the last-token
    logits; changing one INSIDE must."""
    cfg = dataclasses.replace(tiny_test(), sliding_window=3)
    params = init_params(cfg, jax.random.PRNGKey(2))
    toks = np.asarray(rng.integers(1, cfg.vocab_size, (1, 10)), np.int32)
    base, _ = forward(params, cfg, jnp.asarray(toks))
    last = np.asarray(base[:, -1])

    far = toks.copy()
    far[0, 2] = (far[0, 2] + 7) % cfg.vocab_size     # outside last window
    far_lg, _ = forward(params, cfg, jnp.asarray(far))
    np.testing.assert_allclose(last, np.asarray(far_lg[:, -1]), atol=1e-5)

    near = toks.copy()
    near[0, 8] = (near[0, 8] + 7) % cfg.vocab_size   # inside last window
    near_lg, _ = forward(params, cfg, jnp.asarray(near))
    assert np.abs(last - np.asarray(near_lg[:, -1])).max() > 1e-4


def test_mistral_preset_and_guards():
    cfg = get_config("mistral-7b")
    assert cfg.sliding_window == 4096
    assert cfg.num_kv_heads == 8 and cfg.vocab_size == 32_000
    # flash + SWA is now a real in-kernel band mask (r3 continuation;
    # parity in tests/test_flash_attention.py) — only the ring/ulysses
    # kernels still refuse windows, and must keep refusing LOUDLY
    # (they would silently attend outside the window).
    swa_flash = dataclasses.replace(tiny_test(), sliding_window=4,
                                    attn_impl="flash")
    params = init_params(swa_flash, jax.random.PRNGKey(0))
    out, _ = forward(params, swa_flash, jnp.ones((1, 8), jnp.int32))
    assert np.isfinite(np.asarray(out)).all()
    bad = dataclasses.replace(tiny_test(), sliding_window=4,
                              attn_impl="ring")
    with pytest.raises(NotImplementedError, match="sliding_window"):
        forward(init_params(bad, jax.random.PRNGKey(0)), bad,
                jnp.ones((1, 8), jnp.int32))


# ---- ring-buffer KV cache (the memory benefit of SWA) ----

def test_ring_cache_capacity_bounded():
    from senweaver_ide_tpu.models.transformer import ring_capacity
    cfg = dataclasses.replace(tiny_test(), sliding_window=4)
    cache = init_kv_cache(cfg, 2, 100)
    assert cache.k.shape[2] == 8           # window rounded to lane multiple
    assert ring_capacity(cfg, 100) == 8
    assert ring_capacity(cfg, 6) == 6      # never larger than requested
    assert ring_capacity(tiny_test(), 100) == 100


def test_ring_decode_long_sequence_matches_full(rng):
    """Incremental decode through a WRAPPING ring cache (20 tokens, cap 8)
    must equal the no-cache SWA forward at every step."""
    cfg = dataclasses.replace(tiny_test(), sliding_window=4)
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 20)), jnp.int32)
    full, _ = forward(params, cfg, toks)

    cache = init_kv_cache(cfg, 2, 64)      # cap = 8 regardless
    assert cache.k.shape[2] == 8
    for i in range(20):
        lg, cache = forward(params, cfg, toks[:, i:i + 1], cache=cache)
        np.testing.assert_allclose(np.asarray(full[:, i:i + 1]),
                                   np.asarray(lg), atol=3e-4,
                                   err_msg=f"step {i}")


def test_ring_chunked_prefill_with_wrap(rng):
    """Chunked prefill whose chunks wrap the ring (5+4+3 tokens, cap 8)."""
    cfg = dataclasses.replace(tiny_test(), sliding_window=4)
    params = init_params(cfg, jax.random.PRNGKey(1))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 12)), jnp.int32)
    full, _ = forward(params, cfg, toks)

    cache = init_kv_cache(cfg, 1, 32)
    got = []
    for lo, hi in [(0, 5), (5, 9), (9, 12)]:
        lg, cache = forward(params, cfg, toks[:, lo:hi], cache=cache)
        got.append(lg)
    np.testing.assert_allclose(np.asarray(full),
                               np.asarray(jnp.concatenate(got, axis=1)),
                               atol=3e-4)


def test_ring_chunk_larger_than_capacity_raises():
    cfg = dataclasses.replace(tiny_test(), sliding_window=4)
    params = init_params(cfg, jax.random.PRNGKey(0))
    cache = init_kv_cache(cfg, 1, 32)
    with pytest.raises(ValueError, match="ring capacity"):
        forward(params, cfg, jnp.ones((1, 9), jnp.int32), cache=cache)


def test_ring_per_slot_lengths_match_scalar(rng):
    """The per-slot (continuous batching) ring path must agree with the
    scalar-length path at equal fill."""
    cfg = dataclasses.replace(tiny_test(), sliding_window=4)
    params = init_params(cfg, jax.random.PRNGKey(2))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 11)), jnp.int32)

    scalar_cache = init_kv_cache(cfg, 2, 32)
    for i in range(10):
        lg_s, scalar_cache = forward(params, cfg, toks[:, i:i + 1],
                                     cache=scalar_cache)

    vec_cache = init_kv_cache(cfg, 2, 32)
    vec_cache = vec_cache._replace(length=jnp.zeros((2,), jnp.int32))
    for i in range(10):
        lg_v, vec_cache = forward(params, cfg, toks[:, i:i + 1],
                                  cache=vec_cache)
    np.testing.assert_allclose(np.asarray(lg_s), np.asarray(lg_v),
                               atol=2e-4)


def test_ring_int8_cache_parity(rng):
    """Quantized ring writes (values AND scales at modular indices)."""
    cfg = dataclasses.replace(tiny_test(), sliding_window=4, kv_quant=True)
    ref = dataclasses.replace(tiny_test(), sliding_window=4)
    params = init_params(ref, jax.random.PRNGKey(4))
    toks = jnp.asarray(rng.integers(0, ref.vocab_size, (1, 14)), jnp.int32)

    qc = init_kv_cache(cfg, 1, 32)
    fc = init_kv_cache(ref, 1, 32)
    assert qc.quantized and qc.k.dtype == jnp.int8
    for i in range(14):
        lq, qc = forward(params, cfg, toks[:, i:i + 1], cache=qc)
        lf, fc = forward(params, ref, toks[:, i:i + 1], cache=fc)
        # int8 cache is lossy; logits must stay close, not identical
        assert float(jnp.max(jnp.abs(lq - lf))) < 0.15, f"step {i}"


def test_ring_wrapping_chunks_cap_equals_window(rng):
    """cap == window (the mistral-7b shape): EVERY wrapping chunk used to
    overwrite keys still inside earlier queries' windows before attention
    ran. Window-sized chunks across 3 wraps must match the full forward."""
    cfg = dataclasses.replace(tiny_test(), sliding_window=8)
    params = init_params(cfg, jax.random.PRNGKey(5))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 24)), jnp.int32)
    full, _ = forward(params, cfg, toks)

    cache = init_kv_cache(cfg, 2, 64)
    assert cache.k.shape[2] == 8                     # cap == window
    got = []
    for lo in range(0, 24, 8):                       # window-sized chunks
        lg, cache = forward(params, cfg, toks[:, lo:lo + 8], cache=cache)
        got.append(lg)
    np.testing.assert_allclose(np.asarray(full),
                               np.asarray(jnp.concatenate(got, axis=1)),
                               atol=3e-4)


def test_ring_wrapping_chunks_mixed_sizes(rng):
    """Chunk sizes straddling the cap−window slack (window 4, cap 8,
    chunks of 6: s−1 > cap−window) across several wraps."""
    cfg = dataclasses.replace(tiny_test(), sliding_window=4)
    params = init_params(cfg, jax.random.PRNGKey(6))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 18)), jnp.int32)
    full, _ = forward(params, cfg, toks)

    cache = init_kv_cache(cfg, 1, 64)
    got = []
    for lo, hi in [(0, 6), (6, 12), (12, 18)]:
        lg, cache = forward(params, cfg, toks[:, lo:hi], cache=cache)
        got.append(lg)
    np.testing.assert_allclose(np.asarray(full),
                               np.asarray(jnp.concatenate(got, axis=1)),
                               atol=3e-4)


def test_speculative_rejects_ring_configs():
    from senweaver_ide_tpu.rollout.speculative import SpeculativeDecoder
    cfg = dataclasses.replace(tiny_test(), sliding_window=4)
    plain = tiny_test()
    p1 = init_params(cfg, jax.random.PRNGKey(0))
    p2 = init_params(plain, jax.random.PRNGKey(1))
    with pytest.raises(ValueError, match="ring-cache"):
        SpeculativeDecoder(p1, cfg, p2, plain)
    with pytest.raises(ValueError, match="ring-cache"):
        SpeculativeDecoder(p2, plain, p1, cfg)


def test_engine_serves_sliding_window_config(rng):
    """RolloutEngine on an SWA config: ring-sized pool, prefill through
    the padding mask, decode past the window — tokens must match the
    plain sampler.generate greedy path."""
    from senweaver_ide_tpu.rollout.engine import RolloutEngine
    from senweaver_ide_tpu.rollout.sampler import SampleParams, generate

    cfg = dataclasses.replace(tiny_test(), sliding_window=8)
    params = init_params(cfg, jax.random.PRNGKey(7))
    prompt = [int(x) for x in rng.integers(1, 500, 5)]

    eng = RolloutEngine(params, cfg, num_slots=2, max_len=64,
                        sample=SampleParams(temperature=0.0))
    assert eng.cache.k.shape[2] == 8                 # ring-sized pool
    rid = eng.submit(prompt, max_new_tokens=12)      # decodes past window
    out = eng.run()[rid]

    ref = generate(params, cfg,
                   jnp.asarray([prompt], jnp.int32), max_new_tokens=12,
                   sample=SampleParams(temperature=0.0),
                   key=jax.random.PRNGKey(0), max_len=64)
    assert out == [int(t) for t in np.asarray(ref[0])]


def test_generate_long_prompt_chunks_through_ring(rng):
    """A prompt LONGER than the ring capacity must stream through in
    chunks (the mistral-7b 32k-prompt-on-a-4096-ring path) and continue
    into greedy decode matching a teacher-forced no-cache oracle."""
    from senweaver_ide_tpu.rollout.sampler import SampleParams, generate

    cfg = dataclasses.replace(tiny_test(), sliding_window=8)
    params = init_params(cfg, jax.random.PRNGKey(8))
    prompt = jnp.asarray(rng.integers(1, 500, (1, 20)), jnp.int32)

    got = generate(params, cfg, prompt, max_new_tokens=6,
                   sample=SampleParams(temperature=0.0),
                   key=jax.random.PRNGKey(0), max_len=64)

    seq = [int(t) for t in np.asarray(prompt[0])]
    want = []
    for _ in range(6):                       # teacher-forced argmax oracle
        logits, _ = forward(params, cfg, jnp.asarray([seq], jnp.int32))
        tok = int(jnp.argmax(logits[0, -1]))
        want.append(tok)
        seq.append(tok)
    assert [int(t) for t in np.asarray(got[0])] == want


def test_short_swa_cache_uses_absolute_mode(rng):
    """cap < window: no wrap can ever occur, writes are contiguous, and
    the positional window mask applies — decode parity with the full
    forward, plus the decode bound stops at capacity (engine semantics)."""
    from senweaver_ide_tpu.models.transformer import _is_ring

    cfg = dataclasses.replace(tiny_test(), sliding_window=8)
    cache = init_kv_cache(cfg, 1, 6)              # 6 < aligned window 8
    assert cache.k.shape[2] == 6
    assert not _is_ring(cfg, 6)

    params = init_params(cfg, jax.random.PRNGKey(10))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 6)), jnp.int32)
    full, _ = forward(params, cfg, toks)
    for i in range(6):
        lg, cache = forward(params, cfg, toks[:, i:i + 1], cache=cache)
        np.testing.assert_allclose(np.asarray(full[:, i:i + 1]),
                                   np.asarray(lg), atol=2e-4)


def test_engine_short_swa_pool_stops_at_capacity(rng):
    """An engine pool smaller than the window must behave as a bounded
    absolute cache: decode STOPS at capacity instead of silently
    shrinking the window by wrapping."""
    from senweaver_ide_tpu.rollout.engine import RolloutEngine
    from senweaver_ide_tpu.rollout.sampler import SampleParams

    cfg = dataclasses.replace(tiny_test(), sliding_window=64)
    params = init_params(cfg, jax.random.PRNGKey(11))
    eng = RolloutEngine(params, cfg, num_slots=1, max_len=16,
                        sample=SampleParams(temperature=0.0))
    assert eng.max_len == 16                      # absolute, not ring
    rid = eng.submit([5, 6, 7], max_new_tokens=100)
    out = eng.run()[rid]
    assert len(out) <= 16 - 3                     # bounded by capacity


def test_fresh_cache_hint_changes_nothing(rng):
    """fresh_cache=True on an actually-fresh ring cache is purely an
    optimization: logits identical to the default path."""
    cfg = dataclasses.replace(tiny_test(), sliding_window=4)
    params = init_params(cfg, jax.random.PRNGKey(12))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 7)), jnp.int32)

    lg_a, _ = forward(params, cfg, toks, cache=init_kv_cache(cfg, 1, 32),
                      fresh_cache=True)
    lg_b, _ = forward(params, cfg, toks, cache=init_kv_cache(cfg, 1, 32))
    np.testing.assert_allclose(np.asarray(lg_a), np.asarray(lg_b),
                               atol=1e-5)


def test_engine_long_prompt_chunked_prefill(rng):
    """A prompt LONGER than the ring pool (21 tokens on an 8-slot ring)
    must serve via exact-size chunked prefill and match generate()."""
    from senweaver_ide_tpu.rollout.engine import RolloutEngine, _chunk_sizes
    from senweaver_ide_tpu.rollout.sampler import SampleParams, generate

    assert _chunk_sizes(21, 8) == [8, 8, 4, 1]
    assert _chunk_sizes(8, 8) == [8]
    assert _chunk_sizes(3, 8) == [2, 1]

    cfg = dataclasses.replace(tiny_test(), sliding_window=8)
    params = init_params(cfg, jax.random.PRNGKey(13))
    prompt = [int(x) for x in rng.integers(1, 500, 21)]

    eng = RolloutEngine(params, cfg, num_slots=2, max_len=64,
                        sample=SampleParams(temperature=0.0))
    rid = eng.submit(prompt, max_new_tokens=8)
    out = eng.run()[rid]

    ref = generate(params, cfg, jnp.asarray([prompt], jnp.int32),
                   max_new_tokens=8, sample=SampleParams(temperature=0.0),
                   key=jax.random.PRNGKey(0), max_len=64)
    assert out == [int(t) for t in np.asarray(ref[0])]


def test_swa_composes_with_moe(rng):
    """Mixtral shape: sliding window + routed experts in one model —
    ring-cache decode must match the no-cache forward."""
    cfg = dataclasses.replace(get_config("tiny-moe-test"), sliding_window=4)
    params = init_params(cfg, jax.random.PRNGKey(14))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 12)), jnp.int32)
    full, _ = forward(params, cfg, toks)

    cache = init_kv_cache(cfg, 1, 64)
    assert cache.k.shape[2] == 8
    outs = []
    for i in range(12):
        lg, cache = forward(params, cfg, toks[:, i:i + 1], cache=cache)
        outs.append(lg)
    np.testing.assert_allclose(np.asarray(full),
                               np.asarray(jnp.concatenate(outs, axis=1)),
                               atol=3e-4)


def test_mixtral_preset_registered():
    cfg = get_config("mixtral-8x7b")
    # Released Mixtral-8x7B uses full dense attention (HF config.json
    # sliding_window: null) — the preset must match real checkpoints.
    assert cfg.sliding_window is None and cfg.num_experts == 8
    assert cfg.num_experts_per_tok == 2 and cfg.num_kv_heads == 8
