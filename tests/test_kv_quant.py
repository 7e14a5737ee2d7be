"""int8 KV cache: quantization round-trip, decode parity vs the bf16
cache, generate() end-to-end, and per-slot scatter writes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from senweaver_ide_tpu.models import get_config, init_params
from senweaver_ide_tpu.models.transformer import (KVCache, _dequantize_kv,
                                                  _quantize_kv, forward,
                                                  init_kv_cache)


@pytest.fixture(scope="module")
def setup():
    config = get_config("tiny-test")
    params = init_params(config, jax.random.PRNGKey(0))
    return config, params


def test_quantize_roundtrip_error_small():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 3, 16),
                          jnp.float32)
    q, scale = _quantize_kv(x)
    assert q.dtype == jnp.int8 and scale.shape == (2, 5, 3)
    back = _dequantize_kv(q, scale, jnp.float32)
    # int8 absmax quantization: ≤ absmax/254 per-element error
    err = jnp.max(jnp.abs(back - x))
    bound = jnp.max(jnp.abs(x)) / 254 * 1.01
    assert float(err) <= float(bound)


def test_init_quantized_cache_dtypes(setup):
    config, _ = setup
    cache = init_kv_cache(config, 2, 32, quantized=True)
    assert cache.quantized
    assert cache.k.dtype == jnp.int8 and cache.v.dtype == jnp.int8
    assert cache.k_scale.dtype == jnp.float32
    assert cache.k_scale.shape == cache.k.shape[:-1]
    assert not init_kv_cache(config, 2, 32).quantized


def test_decode_parity_quantized_vs_full(setup):
    """Prefill + 4 decode steps: logits with the int8 cache track the
    full-precision cache closely (same top-1 on a tiny model)."""
    config, params = setup
    prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 12), 0,
                                config.vocab_size)
    # Teacher-forced continuation: BOTH runs must see identical inputs,
    # or one flipped near-tie makes the sequences (and logits) diverge
    # for reasons unrelated to cache precision.
    forced = jax.random.randint(jax.random.PRNGKey(3), (4, 2, 1), 0,
                                config.vocab_size)
    caches = {
        "full": init_kv_cache(config, 2, 20),
        "int8": init_kv_cache(config, 2, 20, quantized=True),
    }
    logits = {}
    for name, cache in caches.items():
        lg, cache = forward(params, config, prompt, cache=cache)
        steps = [lg[:, -1]]
        for i in range(4):
            lg, cache = forward(params, config, forced[i], cache=cache)
            steps.append(lg[:, -1])
        logits[name] = jnp.stack(steps)
    a, b = logits["full"], logits["int8"]
    # Random-init logits are near-uniform, so top-1 equality is noise —
    # the meaningful parity metrics are elementwise error and direction.
    rel = jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(a)) + 1e-9)
    assert float(rel) < 0.05, float(rel)
    cos = jnp.sum(a * b) / (jnp.linalg.norm(a) * jnp.linalg.norm(b))
    assert float(cos) > 0.995, float(cos)


def test_per_slot_scatter_writes_scales(setup):
    """Continuous-batching path: (B,) lengths scatter values + scales at
    per-slot offsets."""
    config, params = setup
    cache = init_kv_cache(config, 3, 16, quantized=True)
    lengths = jnp.array([0, 4, 9], jnp.int32)
    cache = KVCache(k=cache.k, v=cache.v, length=lengths,
                    k_scale=cache.k_scale, v_scale=cache.v_scale)
    tok = jnp.ones((3, 1), jnp.int32)
    _lg, new_cache = forward(params, config, tok, cache=cache)
    scales = np.asarray(new_cache.k_scale)  # (L, B, S, H)
    for slot, ln in enumerate([0, 4, 9]):
        assert (scales[:, slot, ln] > 0).all(), f"slot {slot} not written"
        # untouched positions stay zero
        assert (scales[:, slot, ln + 1:] == 0).all()


# ======================================================================
# Paged quantized KV ladder (ISSUE 19): pool round-trip units, ladder
# resolution, the golden-decode parity gate with an explicit divergence
# budget, and the acceptance suites (speculative verify, group fork,
# COW donor death, preempt-by-recompute) under ``kv_dtype="int8"``.
# ======================================================================

import dataclasses

from senweaver_ide_tpu.models.transformer import (dequantize_pool_kv,
                                                  quantize_pool_kv)
from senweaver_ide_tpu.rollout import (EngineConfig, RolloutEngine,
                                       resolve_kv_dtypes)
from senweaver_ide_tpu.rollout.paged_kv import (_FP8_DTYPE,
                                                gather_blocks,
                                                init_paged_pool,
                                                pool_bytes_per_block)
from senweaver_ide_tpu.rollout.sampler import SampleParams
from senweaver_ide_tpu.rollout.speculative import SpeculativeDecoder

GREEDY = SampleParams(temperature=0.0, top_k=0, top_p=1.0)
PROMPT = [5, 9, 2, 7, 1, 3]

# The parity budget for the tiny random-init model: its logits are
# near-uniform, so single near-ties can flip greedy tokens for reasons
# unrelated to cache precision — the gate bounds divergence instead of
# demanding bitwise equality across precision rungs.
MATCH_BUDGET = 0.6


def _mk(model, kv_dtype="bf16", per_layer=None, num_slots=2, **cfg_kw):
    params, config = model
    cfg = EngineConfig(kv_layout="paged", block_size=4,
                       kv_dtype=kv_dtype, kv_dtype_per_layer=per_layer,
                       **cfg_kw)
    return RolloutEngine(params, config, num_slots=num_slots,
                         max_len=64, sample=GREEDY, engine_config=cfg)


@pytest.fixture(scope="module")
def paged_model():
    config = get_config("tiny-test")
    params = init_params(config, jax.random.PRNGKey(0))
    return params, config


# ---- quantize/dequantize round-trip units --------------------------------

def test_pool_quantize_roundtrip_int8():
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 3, 4, 2, 16),
                          jnp.float32)
    q, scale = quantize_pool_kv(x, jnp.int8)
    assert q.dtype == jnp.int8 and scale.shape == x.shape[:-1]
    back = dequantize_pool_kv(q, scale, jnp.float32)
    # absmax int8: per-vector error ≤ absmax/254
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    err = jnp.abs(back - x)
    assert float(jnp.max(err - absmax / 254 * 1.01)) <= 0.0


def test_pool_quantize_roundtrip_fp8():
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 3, 4, 2, 16),
                          jnp.float32)
    q, scale = quantize_pool_kv(x, _FP8_DTYPE)
    assert q.dtype == _FP8_DTYPE
    back = dequantize_pool_kv(q, scale, jnp.float32)
    # e4m3 keeps ~3 mantissa bits: elementwise relative error ≤ 2^-3.5,
    # with an absmax-scaled floor for the denormal tail
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    bound = 0.09 * jnp.abs(x) + 2e-3 * absmax
    assert bool(jnp.all(jnp.abs(back - x) <= bound))


# ---- ladder resolution ----------------------------------------------------

def test_resolve_kv_dtypes_ladder():
    assert resolve_kv_dtypes(4, "bf16") == (None, 0)
    assert resolve_kv_dtypes(4, "int8") == (jnp.int8, 0)
    assert resolve_kv_dtypes(
        4, "int8", ("bf16", "bf16", "int8", "int8")) == (jnp.int8, 2)
    # an all-bf16 override is just a full-width pool
    assert resolve_kv_dtypes(2, "bf16", ("bf16", "bf16")) == (None, 0)

    with pytest.raises(ValueError):
        resolve_kv_dtypes(4, "int4")                  # unknown rung
    with pytest.raises(ValueError):
        resolve_kv_dtypes(4, "int8", ("int8",))       # wrong length
    with pytest.raises(ValueError):                   # not a prefix
        resolve_kv_dtypes(4, "int8", ("int8", "bf16", "int8", "int8"))
    with pytest.raises(ValueError):                   # contradictory tail
        resolve_kv_dtypes(2, "int8", ("bf16", "fp8"))


def test_pool_bytes_ladder_ordering(paged_model):
    _, config = paged_model
    full = init_paged_pool(config, 8, 4)
    q8 = init_paged_pool(config, 8, 4, kv_dtype="int8")
    mixed = init_paged_pool(config, 8, 4, kv_dtype="int8",
                            kv_dtype_per_layer=("bf16", "int8"))
    b_full = pool_bytes_per_block(full)
    b_mix = pool_bytes_per_block(mixed)
    b_q8 = pool_bytes_per_block(q8)
    assert b_q8 < b_mix < b_full
    assert q8.quantized and q8.k.dtype == jnp.int8
    assert q8.k_scale.shape == q8.k.shape[:-1]
    assert mixed.hi_layers == 1 and mixed.k_hi is not None
    assert not full.quantized


def test_quantized_ladder_requires_paged_layout(paged_model):
    params, config = paged_model
    with pytest.raises(ValueError):
        RolloutEngine(params, config, num_slots=1, max_len=32,
                      engine_config=EngineConfig(kv_layout="slots",
                                                 kv_dtype="int8"))


# ---- golden-decode parity gate -------------------------------------------

@pytest.mark.parametrize("ladder", [
    {"kv_dtype": "int8"},
    {"kv_dtype": "int8", "per_layer": ("bf16", "int8")},
])
def test_quantized_golden_decode_budget(paged_model, ladder):
    """The quantized rungs must track the full-width golden stream
    within the declared budget: greedy token-match rate ≥ MATCH_BUDGET
    over mixed-length prompts, and the layer-0 KV content of a shared
    prefix must round-trip with tiny per-layer MSE (layer 0 sees
    un-compounded quantization error only)."""
    prompts = [[5, 9, 2, 7, 1, 3], [11, 3], [4, 4, 8, 1, 2, 6, 9, 5]]
    prefix = [5, 9, 2, 7]

    def run(eng):
        pid = eng.register_prefix(prefix)
        rids = [eng.submit(p, max_new_tokens=10) for p in prompts]
        out = eng.run()
        return [out[r] for r in rids], pid

    golden = _mk(paged_model)
    ref, g_pid = run(golden)
    quant = _mk(paged_model, **ladder)
    got, q_pid = run(quant)

    total = sum(len(s) for s in ref)
    match = sum(int(a == b) for s1, s2 in zip(ref, got)
                for a, b in zip(s1, s2))
    assert match / total >= MATCH_BUDGET, (match, total)

    # per-layer KV divergence of the shared prefix: gather both pools
    # full-width and bound the relative MSE (layer 0 is pure
    # quantization noise; deeper layers compound through attention)
    g_idx = np.asarray(golden._prefixes[g_pid][1], np.int32)
    q_idx = np.asarray(quant._prefixes[q_pid][1], np.int32)
    gk, _gv = gather_blocks(golden.pool, g_idx, dtype=jnp.float32)
    qk, _qv = gather_blocks(quant.pool, q_idx, dtype=jnp.float32)
    gk, qk = np.asarray(gk), np.asarray(qk)
    for layer in range(gk.shape[0]):
        denom = float(np.mean(gk[layer] ** 2)) + 1e-9
        mse = float(np.mean((gk[layer] - qk[layer]) ** 2))
        assert mse / denom < 5e-2, (layer, mse / denom)
    # layer 0 of a mixed ladder is full-width: bitwise identical
    if ladder.get("per_layer"):
        np.testing.assert_array_equal(gk[0], qk[0])

    assert quant.stats()["kv_bytes_per_block"] \
        < golden.stats()["kv_bytes_per_block"]
    golden.release_prefix(g_pid)
    quant.release_prefix(q_pid)
    golden._alloc.check_leaks()
    quant._alloc.check_leaks()


# ---- acceptance: exactness invariants WITHIN the int8 rung ---------------

def test_preempt_by_recompute_exact_under_int8(paged_model):
    """Exhaustion-preempt + recompute must be invisible inside the int8
    rung: the preempted request's stream equals its solo int8 run
    (quantize-at-write is deterministic per position, so recompute
    rebuilds bit-identical blocks)."""
    prompts = [[5, 9, 2, 7], [11, 3, 8, 1]]
    solo = []
    for p in prompts:
        e = _mk(paged_model, "int8", num_slots=1)
        r = e.submit(p, max_new_tokens=12)
        solo.append(e.run()[r])

    eng = _mk(paged_model, "int8", num_slots=2, num_blocks=6)
    rids = [eng.submit(p, max_new_tokens=12) for p in prompts]
    out = eng.run()
    for rid, ref in zip(rids, solo):
        assert out[rid] == ref
    st = eng.stats()
    assert st["kv_preemptions"] >= 1 and st["kv_exhaustions"] >= 1
    assert st["kv_dtype"] == "int8"
    eng._alloc.check_leaks()


def test_cow_donor_release_exact_under_int8(paged_model):
    """Boundary-block COW + donor death mid-flight under int8: the
    grafted request still matches its unshared int8 reference, and the
    copied block carries payload AND scales (a scale-less copy would
    silently rescale the shared tail)."""
    prefix = [5, 9, 2, 7, 4, 4]          # partial boundary block
    suffix = [1, 3]

    ref_eng = _mk(paged_model, "int8")
    ref_rid = ref_eng.submit(prefix + suffix, max_new_tokens=10)
    ref = ref_eng.run()[ref_rid]

    eng = _mk(paged_model, "int8")
    pid = eng.register_prefix(prefix)
    rid = eng.submit(prefix + suffix, max_new_tokens=10, prefix_id=pid)
    for _ in range(3):
        eng.step()
    eng.release_prefix(pid)
    assert eng.run()[rid] == ref
    c = eng._alloc.counters()
    assert c["grafts"] == 1 and c["cow_copies"] >= 1
    eng._alloc.check_leaks()


def test_group_fork_exact_under_int8(paged_model):
    """A GRPO group under int8 pays one prefill and every follower
    matches the unshared int8 decode bitwise — fork refcounts and the
    dropped-write sentinel commute with quantize-at-write."""
    solo = _mk(paged_model, "int8", num_slots=1)
    solo_rid = solo.submit(PROMPT, max_new_tokens=12)
    ref = solo.run()[solo_rid]

    eng = _mk(paged_model, "int8", num_slots=4)
    rids = eng.submit_group(PROMPT, 4, max_new_tokens=12)
    out = eng.run()
    for r in rids:
        assert out[r] == ref
    s = eng.stats()
    assert s["group_prefills"] == 1 and s["group_forks"] == 3
    eng._alloc.check_leaks()


def test_speculative_verify_under_int8(paged_model):
    """Draft-independence under a quantized verify pool: whatever the
    draft proposes, the accepted stream is the target's own greedy
    continuation over its int8 paged KV — a distinct draft and a
    self-draft must emit identical tokens, leak-free."""
    params, config = paged_model
    dc = dataclasses.replace(config, num_layers=2, name="tiny-draft")
    draft = init_params(dc, jax.random.PRNGKey(7))

    dec_a = SpeculativeDecoder(params, config, draft, dc, k=3,
                               kv_layout="paged", block_size=4,
                               kv_dtype="int8")
    dec_b = SpeculativeDecoder(params, config, params, config, k=4,
                               kv_layout="paged", block_size=4,
                               kv_dtype="int8")
    out_a = dec_a.generate(PROMPT, max_new_tokens=12, max_len=64)
    out_b = dec_b.generate(PROMPT, max_new_tokens=12, max_len=64)
    assert out_a == out_b
    assert len(out_a) == 12
    t_kv, d_kv = dec_a._last_paged_kv
    assert t_kv.pool.quantized          # verify ran over int8 blocks
    assert not d_kv.pool.quantized      # draft stays full-width
    for kv in (t_kv, d_kv):
        assert kv.allocator.used_blocks == len(kv.table)
        kv.free()
        kv.allocator.check_leaks()


def test_speculative_slot_layout_rejects_kv_dtype(paged_model):
    params, config = paged_model
    with pytest.raises(ValueError):
        SpeculativeDecoder(params, config, params, config, k=2,
                           kv_dtype="int8")


# ---- fleet prefix store: payloads stay quantized end to end ---------------

def test_prefix_store_ships_quantized_payloads(paged_model):
    """The fleet prefix store holds the donor's export verbatim: an
    int8 fleet's shared-prefix entry carries int8 payload + scales (no
    silent dequant on the broadcast path), every replica installs it,
    and prefix decodes complete."""
    from senweaver_ide_tpu.serve import ServingFleet

    params, config = paged_model
    fleet = ServingFleet([_mk(paged_model, kv_dtype="int8")
                          for _ in range(3)])
    hot = [(j * 7) % 200 + 2 for j in range(8)]
    pid = fleet.register_prefix(hot)
    tickets = [fleet.submit(hot + [i + 1], max_new_tokens=6,
                            prefix_id=pid) for i in range(6)]
    out = fleet.run()
    assert all(t in out and len(out[t]) == 6 for t in tickets)

    entry = fleet.prefix_store._entries[pid]
    assert entry.kv is not None and entry.kv.quantized
    assert np.asarray(entry.kv.k).dtype == np.int8
    assert entry.kv.k_scale is not None
    assert len(entry.installed) == 3    # donor + 2 broadcast installs


def test_prefix_store_cross_ladder_import(paged_model):
    """A heterogeneous fleet (int8 donor, bf16 receiver) still shares
    prefixes: the receiver dequantizes the broadcast payload at the
    door instead of refusing the import, and every stream stays inside
    the declared divergence budget vs the full-width golden."""
    from senweaver_ide_tpu.serve import ServingFleet

    donor = _mk(paged_model, kv_dtype="int8")
    receiver = _mk(paged_model)                    # bf16 rung
    fleet = ServingFleet([donor, receiver])
    hot = [(j * 7) % 200 + 2 for j in range(8)]
    pid = fleet.register_prefix(hot)
    tickets = [fleet.submit(hot + [i + 1], max_new_tokens=6,
                            prefix_id=pid) for i in range(4)]
    out = fleet.run()
    assert all(t in out and len(out[t]) == 6 for t in tickets)
    assert fleet.stats()["replicas"] and all(
        r["engine"]["prefix_prefills"] + r["engine"]["prefix_imports"]
        >= 1 for r in fleet.stats()["replicas"].values()
        if isinstance(r["engine"], dict))

    golden = _mk(paged_model)
    total = match = 0
    for i, t in enumerate(tickets):
        spid = golden.register_prefix(hot)
        rid = golden.submit(hot + [i + 1], max_new_tokens=6,
                            prefix_id=spid)
        ref = golden.run()[rid]
        total += len(ref)
        match += sum(int(a == b) for a, b in zip(out[t], ref))
    assert match / max(1, total) >= MATCH_BUDGET


# ---- what the int8 rung buys at one device byte budget -------------------

def test_int8_at_one_byte_budget(paged_model):
    """In the bytes of a 10-block bf16 pool the int8 pool holds >= 2x
    the blocks (f32 scales included), and the 2x-over-capacity
    shared-prefix workload records strictly fewer evictions +
    preemptions there and finishes no fewer requests in full. A
    swapped-out int8 prefix stays quantized in host RAM, exports
    quantized, and restores token-exact within the rung."""
    _, config = paged_model
    bpb_full = pool_bytes_per_block(init_paged_pool(config, 8, 4))
    bpb_q8 = pool_bytes_per_block(
        init_paged_pool(config, 8, 4, kv_dtype="int8"))
    blocks_full = 10
    blocks_q8 = blocks_full * bpb_full // bpb_q8
    assert blocks_q8 >= 2 * blocks_full

    hot = [(j * 11) % 200 + 2 for j in range(16)]   # 4 blocks @ bs 4
    prompts = [hot + [i + 1, 3] for i in range(6)]

    def pressured(kv_dtype, num_blocks):
        eng = _mk(paged_model, kv_dtype, num_blocks=int(num_blocks),
                  host_tier=False)
        pid = eng.register_prefix(hot)
        rids = [eng.submit(p, max_new_tokens=12, prefix_id=pid)
                for p in prompts]
        out = eng.run()
        st = eng.stats()
        if pid in eng._prefixes:
            eng.release_prefix(pid)
        eng._alloc.check_leaks()
        # the storm cap may truncate-finish a ticket; none may be LOST
        assert all(r in out for r in rids)
        return (st["prefix_evictions"] + st["kv_preemptions"],
                sum(len(out[r]) == 12 for r in rids))

    press_full, done_full = pressured("bf16", blocks_full)
    press_q8, done_q8 = pressured("int8", blocks_q8)
    assert press_full >= 1          # the workload IS over capacity
    assert press_q8 < press_full
    assert done_q8 >= done_full

    eng = _mk(paged_model, "int8")
    pid = eng.register_prefix(hot)
    r0 = eng.submit(hot + [1, 3], max_new_tokens=8, prefix_id=pid)
    ref = eng.run()[r0]
    eng._swap_out_prefix(pid)
    hp = eng._prefix_host[pid]
    assert hp.quantized and hp.k.dtype == np.int8
    _toks, kv, _ = eng.export_prefix(pid)
    assert kv.quantized and isinstance(kv.k, np.ndarray)
    r1 = eng.submit(hot + [1, 3], max_new_tokens=8, prefix_id=pid)
    assert eng.run()[r1] == ref
    eng.release_prefix(pid)
    eng._alloc.check_leaks()
