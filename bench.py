"""Headline benchmark: policy decode throughput (tokens/sec/chip).

Measures KV-cache autoregressive DECODE on the flagship policy
(Qwen2.5-Coder-1.5B architecture, bf16, randomly initialised — throughput
is weight-value independent) via the fully-jitted ``generate_scan`` path,
on whatever accelerator JAX exposes (one TPU v5e chip under the driver).

Timing method: SLOPE — the decode rate is computed from two
prefill+decode runs that differ only in decode length (n_lo vs n_hi
tokens); rate = extra_tokens / (t_hi − t_lo). Identical prefill work
cancels exactly. The r1 bench mistakenly timed 3 8×512-token prefills
inside the decode loop; the r2 interim used (prefill+decode) −
(prefill-only), which goes singular when prefill dominates — at b32 the
subtraction landed within timing noise and reported 1e10 tok/s.

Baseline semantics: the reference (senweaver/senweaver-ide) publishes no
quantitative numbers (BASELINE.json ``published: {}``); its policy tokens
come from remote provider APIs / local Ollama over the streaming IPC path
(``electron-main/llmMessage/sendLLMMessage.impl.ts``), where per-stream
decode throughput for a 1.5B-class model is ~60 tok/s. ``vs_baseline``
anchors to that documented reference-path figure unless BASELINE.json
``published`` ever provides ``tokens_per_sec_per_chip``.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}
— ``extra`` carries secondary points (larger batch; the 7B-class
deepseek-coder-6.7b) and the device stamp. No accelerator, or any case
that raises: non-zero exit and no line. ``BENCH_FORCE_CPU=1`` is the
explicit CPU route (tiny-test, reported as ``cpu_smoke_*``).
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Optional

REFERENCE_PATH_TOKS_PER_SEC = 60.0

BATCH = 8
PROMPT_LEN = 512
DECODE_TOKENS = 128
TIMED_ITERS = 3

# Per-case warmup/steady split, stamped into the artifact as
# extra["timing"]: compile_s is the warmup wall (trace + XLA compile +
# first execution), step_s the steady-state wall per timed unit (one
# run / one dispatch / one train step). Every reported throughput
# number comes from the steady-state side only — the split makes that
# auditable and gives scripts/perf_gate.py its baseline axes.
TIMINGS: dict = {}


def _stamp_timing(key: Optional[str], compile_s: float,
                  step_s: float) -> None:
    if key:
        TIMINGS[key] = {"compile_s": round(compile_s, 3),
                        "step_s": round(step_s, 4)}


def _log(msg: str) -> None:
    """Progress to stderr (stdout carries ONLY the one JSON line)."""
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}",
          file=sys.stderr, flush=True)


def _baseline() -> float:
    try:
        with open("BASELINE.json") as f:
            published = json.load(f).get("published", {})
        return float(published.get("tokens_per_sec_per_chip",
                                   REFERENCE_PATH_TOKS_PER_SEC))
    except Exception:
        return REFERENCE_PATH_TOKS_PER_SEC


def _artifact_summaries() -> dict:
    """Headline numbers from the committed eval artifacts (best-effort —
    a missing/unparsable file contributes nothing)."""
    root = os.path.dirname(os.path.abspath(__file__))
    out = {}

    def read(name):
        try:
            with open(os.path.join(root, name)) as f:
                payload = json.load(f)
            # shape guard: valid-JSON-but-not-object must not crash a
            # best-effort summary (and with it the judged perf line)
            return payload if isinstance(payload, dict) else None
        except Exception:
            return None

    learn = read("LEARNING_r03.json")
    if learn and "uplift" in learn:
        out["grpo_learning_uplift"] = learn["uplift"]
        out["grpo_learning_final"] = learn.get("reward_final")
    up = next((d for d in (read("UPLIFT_r04.json"), read("UPLIFT_r03.json"))
               if d and "uplift_ratio_shifted" in d), None)
    if up:
        out["apo_uplift_ratio_shifted"] = up["uplift_ratio_shifted"]
        out["apo_uplift_searched"] = up.get("searched")
    spec = read("SPEC_r03.json")
    if spec and "gain" in spec:
        out["speculative_acceptance_gain"] = spec["gain"]
    ctx = next((c for c in (read("LEARNING_CONTEXTUAL_ANCHORED_r03.json"),
                            read("LEARNING_CONTEXTUAL_SHORT_r03.json"))
                if c and "peak_window_mean" in c), None)
    if ctx:
        out["contextual_peak_window_mean"] = ctx["peak_window_mean"]
        out["contextual_conditioned"] = ctx.get("conditioned")
        out["contextual_final"] = ctx.get("reward_final")
    lora = next((d for d in (read("LEARNING_LORA_r04.json"),
                             read("LEARNING_LORA_r03.json"))
                 if d and "uplift" in d), None)
    if lora:
        out["lora_learning_uplift"] = lora["uplift"]
        out["lora_learning_final"] = lora.get("reward_final")
    qlora = read("LEARNING_QLORA_r04.json")
    if qlora and "uplift" in qlora:
        out["qlora_learning_uplift"] = qlora["uplift"]
    # round-4 headline artifacts: the north star on REAL weights
    real = read("UPLIFT_REALPOLICY_r04.json")
    if real and "uplift_ratio_shifted" in real:
        out["apo_uplift_realpolicy_ratio"] = real["uplift_ratio_shifted"]
        out["realpolicy_conditioning_delta"] = real.get(
            "conditioning_delta")
    online = read("ONLINE_r04.json")
    if online and "curve" in online and online["curve"]:
        out["online_loop_reward_first"] = online["curve"][0]
        out["online_loop_reward_final"] = online["curve"][-1]
    sevenb = next((d for d in (read("SEVENB_r05.json"),
                               read("SEVENB_r04.json"))
                   if d and isinstance(d.get("sizing"), dict)), None)
    if sevenb:
        plans = sevenb["sizing"].get("plans_gb")
        if isinstance(plans, dict):
            out["sevenb_qlora_plan_gb"] = plans.get("qlora_int8_base")
        upd = sevenb.get("qlora_update")
        if isinstance(upd, dict):
            out["sevenb_qlora_update_step_wall_s"] = upd.get("step_wall_s")
    # round-5 headline artifacts: capacity/curriculum conditioning, the
    # generative optimizer, the task-shift online loop, scale steps
    cap = read("CAPACITY_r05.json")
    if cap and "conditioning_delta" in cap:
        out["capacity_curriculum_delta"] = cap["conditioning_delta"]
        out["capacity_curriculum_prefix_bytes"] = cap.get(
            "target_prefix_bytes")
        out["capacity_curriculum_conditioned"] = cap.get("conditioned")
    gen = read("UPLIFT_GENERATIVE_r05.json")
    if gen and "uplift_ratio_shifted" in gen:
        out["generative_uplift_ratio"] = gen["uplift_ratio_shifted"]
        out["generative_searched"] = gen.get("searched")
    online5 = read("ONLINE_r05.json")
    if online5 and online5.get("beam_invocations") is not None:
        out["online_shift_beam_invocations"] = online5["beam_invocations"]
        out["online_shift_recovered"] = online5.get("post_shift_recovered")
    b15 = read("ONEPOINTFIVEB_r05.json")
    if b15 and isinstance(b15.get("phases"), dict):
        tr = b15["phases"].get("train")
        if isinstance(tr, dict):
            out["onepointfiveb_step_walls_s"] = tr.get("step_walls_s")
    hf = read("HF_ROUNDTRIP_r05.json")
    if hf and "ok" in hf:
        out["hf_roundtrip_ok"] = hf["ok"]
    robust = read("SEED_ROBUSTNESS_r05.json")
    if robust and isinstance(robust.get("by_config"), dict):
        out["seed_robustness_best"] = robust.get("best_config")
    return out


def _measure(model_name: str, batch: int, prompt_len: int,
             decode_tokens: int, *, weight_quant: bool = False,
             decode_attn_impl: Optional[str] = None,
             timing_key: Optional[str] = None) -> float:
    """Decode tokens/sec via the slope between two decode lengths.

    ``weight_quant``: serve int8 weight-only quantized params
    (models/quantize.py) — halves the weight bytes each decode step
    streams from HBM, the binding resource at these shapes.
    ``decode_attn_impl``: override the cache-attention kernel (the
    "flash" entry is the real-chip lowering revalidation).
    """
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from senweaver_ide_tpu.models import get_config, init_params
    from senweaver_ide_tpu.models.quantize import quantize_weights_int8
    from senweaver_ide_tpu.models.transformer import init_kv_cache
    from senweaver_ide_tpu.rollout.sampler import (SampleParams,
                                                   generate_scan)

    config = get_config(model_name)
    if decode_attn_impl is not None:
        config = dataclasses.replace(config,
                                     decode_attn_impl=decode_attn_impl)
    params = jax.block_until_ready(init_params(config, jax.random.PRNGKey(0)))
    if weight_quant:
        params = jax.block_until_ready(quantize_weights_int8(params))
    prompt = jnp.ones((batch, prompt_len), dtype=jnp.int32)
    n_lo, n_hi = 16, 16 + decode_tokens
    max_len = prompt_len + n_hi
    if decode_attn_impl == "flash":
        # flash decode engages only on a 128-aligned cache
        max_len = -(-max_len // 128) * 128
    sample = SampleParams(temperature=0.8, top_k=0, top_p=0.0)

    def run(key, n):
        # Same max_len cache for both lengths: per-step attention cost
        # must match so the slope isolates pure per-token decode time.
        cache = init_kv_cache(config, batch, max_len)
        toks, _ = generate_scan(params, config, prompt, cache, key,
                                max_new_tokens=n, sample=sample)
        return np.asarray(toks)     # host copy: waits for the device

    # Warmup/compile as plain statements: inside `assert` they would be
    # stripped under python -O, moving compilation into the timed loops.
    t_warm = time.perf_counter()
    warm_lo = run(jax.random.PRNGKey(1), n_lo)
    warm_hi = run(jax.random.PRNGKey(1), n_hi)
    compile_s = time.perf_counter() - t_warm
    if warm_lo.shape != (batch, n_lo) or warm_hi.shape != (batch, n_hi):
        raise RuntimeError("generate_scan returned unexpected shapes")

    def timed_pair():
        t0 = time.perf_counter()
        for i in range(TIMED_ITERS):
            run(jax.random.PRNGKey(2 + i), n_lo)
        t_lo = time.perf_counter() - t0
        t0 = time.perf_counter()
        for i in range(TIMED_ITERS):
            run(jax.random.PRNGKey(2 + i), n_hi)
        return t_lo, time.perf_counter() - t0

    t_lo, t_hi = timed_pair()
    if t_hi <= t_lo * 1.02:
        # A GC pause or dispatch hiccup in the n_lo loop makes the slope
        # non-positive; silently clamping would report an absurd rate
        # (the 1e10-tok/s failure this method replaced). Retry once,
        # then fail loudly.
        t_lo, t_hi = timed_pair()
    if t_hi <= t_lo * 1.02:   # same margin as the retry trigger: a
        # marginal slope would divide by near-noise and inflate the rate
        raise RuntimeError(
            f"decode slope not positive (t_lo={t_lo:.3f}s "
            f"t_hi={t_hi:.3f}s); timing too noisy to report")
    _stamp_timing(timing_key, compile_s, t_hi / TIMED_ITERS)
    return batch * decode_tokens * TIMED_ITERS / (t_hi - t_lo)


def _init_int8_params(config, key):
    """Random int8 serving params built DIRECTLY in int8 on device.

    The honest route (bf16 init → models/quantize) needs the 13.4 GB
    bf16 tree plus a 5.8 GB fp32 transient for w_gate's absmax pass —
    past one 16 GB chip at 6.7B. Decode throughput is weight-HBM-bound,
    so random int8 values with constant per-channel scales stream
    exactly the same bytes through the same ``transformer._dense`` int8
    epilogue; only the sampled text is meaningless (fine for a bench).
    """
    import jax
    import jax.numpy as jnp

    from senweaver_ide_tpu.models.quantize import dense_family_shapes

    c = config
    L, D, V = c.num_layers, c.hidden_size, c.vocab_size
    q_dim, kv_dim = c.q_dim, c.kv_dim
    shapes = dense_family_shapes(config)   # raises on MoE configs
    keys = jax.random.split(key, len(shapes) + 2)
    layers = {"attn_norm": jnp.ones((L, D), c.dtype),
              "mlp_norm": jnp.ones((L, D), c.dtype)}
    for k, (name, (fan_in, out)) in zip(keys, shapes.items()):
        layers[name] = jax.random.randint(k, (L, fan_in, out), -127, 128,
                                          jnp.int8)
        layers[name + "_scale"] = jnp.full(
            (L, out), 1.0 / (127.0 * fan_in ** 0.5), jnp.float32)
    if c.qkv_bias:
        layers["bq"] = jnp.zeros((L, q_dim), c.dtype)
        layers["bk"] = jnp.zeros((L, kv_dim), c.dtype)
        layers["bv"] = jnp.zeros((L, kv_dim), c.dtype)
    if c.qk_norm:
        layers["q_norm"] = jnp.ones((L, c.head_dim), c.dtype)
        layers["k_norm"] = jnp.ones((L, c.head_dim), c.dtype)
    params = {
        "embed": jax.random.normal(keys[-2], (V, D), c.dtype) * 0.02,
        "layers": layers,
        "final_norm": jnp.ones((D,), c.dtype),
    }
    if not c.tie_word_embeddings:
        params["lm_head"] = jax.random.randint(keys[-1], (D, V), -127, 128,
                                               jnp.int8)
        params["lm_head_scale"] = jnp.full(
            (V,), 1.0 / (127.0 * D ** 0.5), jnp.float32)
    return params


def _measure_steps(model_name: str, batch: int, prompt_len: int,
                   decode_tokens: int, *, quantized: bool = False,
                   weight_quant: bool = False,
                   timing_key: Optional[str] = None) -> float:
    """Decode tokens/sec via pipelined per-step dispatch (the `generate`
    / rollout-engine serving path): prefill once, then ``decode_tokens``
    back-to-back ``decode_step`` dispatches, blocking only at the end.

    Fallback for models whose prefill+scan graph the AOT compile helper
    rejects (observed: deepseek-coder-6.7b); per-step dispatches overlap
    device execution, so this still measures device decode throughput,
    with dispatch overhead making it an UNDER-estimate.
    """
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from senweaver_ide_tpu.models import get_config, init_params
    from senweaver_ide_tpu.models.transformer import init_kv_cache
    from senweaver_ide_tpu.rollout.sampler import (SampleParams, decode_step,
                                                   prefill)

    config = get_config(model_name)
    params = jax.block_until_ready(
        _init_int8_params(config, jax.random.PRNGKey(0)) if weight_quant
        else init_params(config, jax.random.PRNGKey(0)))
    sample = SampleParams(temperature=0.8, top_k=0, top_p=0.0)
    cache = init_kv_cache(config, batch, prompt_len + decode_tokens + 1,
                          quantized=quantized)
    logits, cache = prefill(params, config,
                            jnp.ones((batch, prompt_len), jnp.int32), cache)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    key = jax.random.PRNGKey(1)
    # warmup: compiles decode_step and fills the dispatch pipeline
    t_warm = _time.perf_counter()
    tok, _, cache = decode_step(params, config, tok[:, None], cache, key,
                                sample)
    np.asarray(tok)    # host copy: waits for the device
    compile_s = _time.perf_counter() - t_warm

    t0 = _time.perf_counter()
    for i in range(decode_tokens):
        tok, _, cache = decode_step(params, config, tok[:, None], cache,
                                    jax.random.fold_in(key, i), sample)
    np.asarray(tok)    # forces the whole dependent chain to execute
    dt = _time.perf_counter() - t0
    _stamp_timing(timing_key, compile_s, dt / decode_tokens)
    return batch * decode_tokens / dt


# bf16 peak FLOP/s per chip by device kind; the MFU denominator.
_PEAK_FLOPS = {
    "TPU v5e": 197e12, "TPU v5 lite": 197e12, "TPU v5litepod": 197e12,
    "TPU v4": 275e12, "TPU v6e": 918e12,
}


def _measure_train(model_name: str, batch: int, seq: int, *,
                   accum_steps: int = 1, iters: int = 3,
                   timing_key: Optional[str] = None) -> dict:
    """GRPO train-step throughput: tokens/sec and MFU.

    Times the full clipped-objective update (forward + backward + adamw)
    on random data via training.trainer.train_step — the exact workload
    of grpo_round's update phase. MFU uses the 6·N·tokens/s dense-matmul
    approximation over the device's bf16 peak (the north-star rows in
    BASELINE.md name training tokens/sec/chip at 1.5-7B). Memory fitting on one 16 GB chip:
    remat="full" (recompute activations) + bf16 first moment
    (mu_dtype) — params 3.1 GB + mu 3.1 + nu 6.2 for 1.5B.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from senweaver_ide_tpu.models import get_config, init_params
    from senweaver_ide_tpu.training.trainer import TrainState, train_step

    config = dataclasses.replace(get_config(model_name), remat="full")
    params = jax.block_until_ready(init_params(config, jax.random.PRNGKey(0)))
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params))
    opt = optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(1e-5, b1=0.9, b2=0.95, eps=1e-8,
                    mu_dtype=jnp.bfloat16))
    state = TrainState(params=params, opt_state=jax.jit(opt.init)(params),
                       step=jnp.zeros((), jnp.int32), opt=opt)

    key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(key, (batch, seq), 0,
                                config.vocab_size, dtype=jnp.int32)
    mask = jnp.ones((batch, seq), jnp.bool_).at[:, : seq // 4].set(False)
    rewards = jax.random.normal(key, (batch,), jnp.float32)
    group_ids = jnp.arange(batch, dtype=jnp.int32) // 2

    def step(st):
        st, metrics = train_step(st, config, None, tokens, mask, rewards,
                                 group_ids, optimizer=opt,
                                 accum_steps=accum_steps)
        return st, metrics

    t_warm = time.perf_counter()
    state, metrics = step(state)             # compile + warmup
    jax.block_until_ready(state.params)
    compile_s = time.perf_counter() - t_warm
    t0 = time.perf_counter()
    for _ in range(iters):
        state, metrics = step(state)
    jax.block_until_ready(state.params)
    dt = time.perf_counter() - t0

    _stamp_timing(timing_key, compile_s, dt / iters)
    toks_per_sec = batch * seq * iters / dt
    dev = jax.devices()[0]
    peak = _PEAK_FLOPS.get(getattr(dev, "device_kind", ""), None)
    out = {"tokens_per_sec": round(toks_per_sec, 2),
           "step_ms": round(dt / iters * 1000.0, 1),
           "compile_s": round(compile_s, 3),
           "n_params": n_params}
    if peak is not None and dev.platform != "cpu":
        # 6·N FLOPs/token covers fwd (2N) + bwd (4N) dense matmuls; the
        # remat="full" forward recompute adds ~2N more → report both.
        out["mfu"] = round(6.0 * n_params * toks_per_sec / peak, 4)
        out["mfu_with_remat"] = round(8.0 * n_params * toks_per_sec / peak,
                                      4)
    return out


def _measure_prefix_fleet(*, n_replicas: int = 4, prefix_len: int = 48,
                          n_requests: int = 8) -> dict:
    """Fleet-shared prefix economics: one-prefill broadcast vs lazy
    per-replica prefill on an N-replica fleet (serve/prefix_store.py).

    Protocol-level numbers, so the tiny model demonstrates them on any
    backend: prefix prefills actually computed per mode, prefill FLOPs
    avoided by installing the donor's KV instead of recomputing
    (≈ 2·N_params per prefix token per avoided prefill), and the
    prefix-bearing TTFT mean per mode — the acceptance signal is
    broadcast TTFT < lazy TTFT."""
    import time as _time

    import jax
    import numpy as np

    from senweaver_ide_tpu import obs
    from senweaver_ide_tpu.models import init_params, tiny_test
    from senweaver_ide_tpu.rollout import RolloutEngine
    from senweaver_ide_tpu.rollout.sampler import SampleParams
    from senweaver_ide_tpu.serve import ServingFleet

    config = tiny_test()
    params = jax.block_until_ready(
        init_params(config, jax.random.PRNGKey(0)))
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params))
    greedy = SampleParams(temperature=0.0, top_k=0, top_p=1.0)
    prefix = [(i % 200) + 2 for i in range(prefix_len)]

    def run(shared: bool) -> dict:
        obs._reset_for_tests()
        engines = [RolloutEngine(params, config, num_slots=2,
                                 max_len=128, sample=greedy)
                   for _ in range(n_replicas)]
        fleet = ServingFleet(engines,
                             shared_prefix_broadcast=shared)
        pid = fleet.register_prefix(prefix)
        tickets = [fleet.submit(prefix + [7 + i], max_new_tokens=4,
                                prefix_id=pid)
                   for i in range(n_requests)]
        fleet.run()
        ttfts = [fleet.outcome(t).ttft_ms for t in tickets
                 if fleet.outcome(t).ttft_ms is not None]
        snap = fleet.snapshot_event()
        return {
            "prefix_prefills": sum(e.stats()["prefix_prefills"]
                                   for e in engines),
            "prefills_avoided": snap["prefix_prefills_avoided"],
            "ttft_ms_mean": sum(ttfts) / max(1, len(ttfts)),
        }

    t_warm = _time.perf_counter()
    run(shared=True)        # warm the jit caches so neither mode pays
    compile_s = _time.perf_counter() - t_warm
    lazy = run(shared=False)
    t0 = _time.perf_counter()
    bcast = run(shared=True)
    _stamp_timing("prefix_fleet", compile_s, _time.perf_counter() - t0)
    obs._reset_for_tests()
    avoided = bcast["prefills_avoided"]
    return {
        "replicas": n_replicas,
        "prefix_len": prefix_len,
        "prefix_prefills_lazy": lazy["prefix_prefills"],
        "prefix_prefills_broadcast": bcast["prefix_prefills"],
        "prefills_avoided": avoided,
        "prefill_flops_avoided": int(
            2.0 * n_params * prefix_len * avoided),
        "ttft_ms_lazy": round(lazy["ttft_ms_mean"], 2),
        "ttft_ms_broadcast": round(bcast["ttft_ms_mean"], 2),
        "ttft_speedup": round(
            lazy["ttft_ms_mean"] / max(1e-9, bcast["ttft_ms_mean"]), 3),
    }


def _measure_paged_vs_slots(*, num_slots: int = 4, prompt_len: int = 16,
                            decode_tokens: int = 48) -> dict:
    """Paged (block-table) decode vs the contiguous slot cache at equal
    batch (EngineConfig.kv_layout). Greedy, identical prompts; both
    layouts warm their jit caches first, then one timed run() each. The
    acceptance signal is paged_over_slots >= 1.0 — the indirection must
    not tax steady-state decode — plus the allocator counters proving
    the paged run stayed graft/alloc-exact."""
    import time as _time

    import jax

    from senweaver_ide_tpu import obs
    from senweaver_ide_tpu.models import init_params, tiny_test
    from senweaver_ide_tpu.rollout import EngineConfig, RolloutEngine
    from senweaver_ide_tpu.rollout.sampler import SampleParams

    config = tiny_test()
    params = jax.block_until_ready(
        init_params(config, jax.random.PRNGKey(0)))
    greedy = SampleParams(temperature=0.0, top_k=0, top_p=1.0)
    prompts = [[(i * 7 + j) % 200 + 2 for j in range(prompt_len)]
               for i in range(num_slots)]

    def run(layout: str) -> dict:
        obs._reset_for_tests()
        eng = RolloutEngine(
            params, config, num_slots=num_slots, max_len=128,
            sample=greedy, engine_config=EngineConfig(kv_layout=layout))
        rids = [eng.submit(p, max_new_tokens=decode_tokens)
                for p in prompts]
        t0 = _time.perf_counter()
        out = eng.run()
        dt = _time.perf_counter() - t0
        return {"tok_s": sum(len(out[r]) for r in rids) / dt,
                "tokens": [out[r] for r in rids],
                "stats": eng.stats()}

    t_warm = _time.perf_counter()
    run("slots")            # compile warmup, both layouts
    run("paged")
    compile_s = _time.perf_counter() - t_warm
    slots = run("slots")
    t0 = _time.perf_counter()
    paged = run("paged")
    _stamp_timing("paged_vs_slots", compile_s, _time.perf_counter() - t0)
    obs._reset_for_tests()
    exact = paged["tokens"] == slots["tokens"]
    return {
        "num_slots": num_slots,
        "decode_tokens": decode_tokens,
        "slots_tok_s": round(slots["tok_s"], 1),
        "paged_tok_s": round(paged["tok_s"], 1),
        "paged_over_slots": round(
            paged["tok_s"] / max(1e-9, slots["tok_s"]), 3),
        "outputs_exact": exact,
        "kv_preemptions": paged["stats"].get("kv_preemptions", 0),
        "kv_blocks_total": paged["stats"].get("kv_blocks_total", 0),
    }


def _measure_kv_pressure(*, num_requests: int = 6, prefix_len: int = 16,
                         decode_tokens: int = 12) -> dict:
    """Host-RAM tiering vs evict-and-recompute when a prefix-sharing
    workload runs ~2x over pool capacity (rollout/kv_pressure.py). Same
    pool, same prompts; the only knob is EngineConfig.host_tier. The
    acceptance signal is prefill_tokens strictly lower with the tier on
    — restores from host replace re-prefills of the shared prefix — at
    comparable tok/s, with the swap counters proving the tier (not
    luck) supplied the savings."""
    import time as _time

    import jax

    from senweaver_ide_tpu import obs
    from senweaver_ide_tpu.models import init_params, tiny_test
    from senweaver_ide_tpu.rollout import EngineConfig, RolloutEngine
    from senweaver_ide_tpu.rollout.sampler import SampleParams

    config = tiny_test()
    params = jax.block_until_ready(
        init_params(config, jax.random.PRNGKey(0)))
    greedy = SampleParams(temperature=0.0, top_k=0, top_p=1.0)
    prefix = [(j * 11) % 200 + 2 for j in range(prefix_len)]
    prompts = [prefix + [(i * 7 + j) % 200 + 2 for j in range(4)]
               for i in range(num_requests)]
    # working set: 2 concurrent x ~8 blocks + 4 prefix blocks against
    # a 10-block pool — sustained pressure, the ladder fires every run
    num_blocks = 10

    def run(host_tier: bool) -> dict:
        obs._reset_for_tests()
        eng = RolloutEngine(
            params, config, num_slots=2, max_len=128, sample=greedy,
            engine_config=EngineConfig(
                kv_layout="paged", block_size=4, num_blocks=num_blocks,
                host_tier=host_tier, tier_min_uses=1))
        pid = eng.register_prefix(prefix)
        rids = [eng.submit(p, max_new_tokens=decode_tokens,
                           prefix_id=pid) for p in prompts]
        t0 = _time.perf_counter()
        out = eng.run()
        dt = _time.perf_counter() - t0
        return {"tok_s": sum(len(out[r]) for r in rids) / dt,
                "tokens": [out[r] for r in rids],
                "stats": eng.stats()}

    t_warm = _time.perf_counter()
    run(True)               # compile warmup, both modes
    run(False)
    compile_s = _time.perf_counter() - t_warm
    evict = run(False)
    t0 = _time.perf_counter()
    tier = run(True)
    _stamp_timing("kv_pressure", compile_s, _time.perf_counter() - t0)
    obs._reset_for_tests()
    # the minimum prefill work any run must do: the prefix once plus
    # each request's non-prefix suffix
    ideal = prefix_len + sum(len(p) - prefix_len for p in prompts)
    return {
        "num_requests": num_requests,
        "kv_blocks_total": num_blocks,
        "tier_tok_s": round(tier["tok_s"], 1),
        "evict_tok_s": round(evict["tok_s"], 1),
        "tier_over_evict": round(
            tier["tok_s"] / max(1e-9, evict["tok_s"]), 3),
        "prefill_tokens_ideal": ideal,
        "prefill_tokens_tier": tier["stats"]["prefill_tokens"],
        "prefill_tokens_evict": evict["stats"]["prefill_tokens"],
        "recompute_ratio_tier": round(
            tier["stats"]["prefill_tokens"] / max(1, ideal), 3),
        "recompute_ratio_evict": round(
            evict["stats"]["prefill_tokens"] / max(1, ideal), 3),
        "swap_outs": tier["stats"].get("prefix_swap_outs", 0),
        "swap_ins": tier["stats"].get("prefix_swap_ins", 0),
        "evictions_evict": evict["stats"].get("prefix_evictions", 0),
        "preemptions_tier": tier["stats"].get("kv_preemptions", 0),
        "preemptions_evict": evict["stats"].get("kv_preemptions", 0),
        "outputs_equal": tier["tokens"] == evict["tokens"],
    }


def _measure_kv_quant(*, num_requests: int = 8, prefix_len: int = 16,
                      decode_tokens: int = 12) -> dict:
    """The quantized KV ladder's capacity payoff (ISSUE 19): the same
    2x-over-capacity shared-prefix workload against the same DEVICE
    BYTE budget, bf16 vs int8. Quantized blocks are ~3x smaller, so the
    int8 pool holds ~3x the blocks in the same bytes — the pressure
    ladder (evictions, preemption-recompute) fires less, and aggregate
    tok/s rises. The acceptance gate: int8 strictly fewer
    evictions + preemptions, higher tok/s, token streams within the
    declared divergence budget, and a leak-free drain on both rungs."""
    import time as _time

    import jax

    from senweaver_ide_tpu import obs
    from senweaver_ide_tpu.models import init_params, tiny_test
    from senweaver_ide_tpu.rollout import EngineConfig, RolloutEngine
    from senweaver_ide_tpu.rollout.paged_kv import (init_paged_pool,
                                                    pool_bytes_per_block)
    from senweaver_ide_tpu.rollout.sampler import SampleParams

    config = tiny_test()
    params = jax.block_until_ready(
        init_params(config, jax.random.PRNGKey(0)))
    greedy = SampleParams(temperature=0.0, top_k=0, top_p=1.0)
    prefix = [(j * 11) % 200 + 2 for j in range(prefix_len)]
    prompts = [prefix + [(i * 7 + j) % 200 + 2 for j in range(4)]
               for i in range(num_requests)]

    # Equalize the DEVICE BYTE budget, not the block count: a bf16 pool
    # of 10 blocks sets the budget; the int8 pool gets however many
    # blocks fit in the same bytes (scales included — the ratio is
    # honest about the f32 scale overhead).
    block_size = 4
    bf16_blocks = 10
    budget = pool_bytes_per_block(
        init_paged_pool(config, bf16_blocks, block_size)) * bf16_blocks
    int8_blocks = budget // pool_bytes_per_block(
        init_paged_pool(config, bf16_blocks, block_size,
                        kv_dtype="int8"))

    def run(kv_dtype: str, num_blocks: int) -> dict:
        obs._reset_for_tests()
        eng = RolloutEngine(
            params, config, num_slots=2, max_len=128, sample=greedy,
            engine_config=EngineConfig(
                kv_layout="paged", block_size=block_size,
                num_blocks=num_blocks, kv_dtype=kv_dtype,
                host_tier=False))
        pid = eng.register_prefix(prefix)
        rids = [eng.submit(p, max_new_tokens=decode_tokens,
                           prefix_id=pid) for p in prompts]
        t0 = _time.perf_counter()
        out = eng.run()
        dt = _time.perf_counter() - t0
        st = eng.stats()
        if pid in eng._prefixes:
            eng.release_prefix(pid)
        eng._alloc.check_leaks()    # leak-free drain or the case errors
        return {"tok_s": sum(len(out[r]) for r in rids) / dt,
                "tokens": [out[r] for r in rids], "stats": st}

    t_warm = _time.perf_counter()
    run("bf16", bf16_blocks)        # compile warmup, both rungs
    run("int8", int8_blocks)
    compile_s = _time.perf_counter() - t_warm
    bf16 = run("bf16", bf16_blocks)
    t0 = _time.perf_counter()
    q8 = run("int8", int8_blocks)
    _stamp_timing("kv_quant", compile_s, _time.perf_counter() - t0)
    obs._reset_for_tests()

    total = sum(len(s) for s in bf16["tokens"])
    match = sum(int(a == b)
                for s1, s2 in zip(bf16["tokens"], q8["tokens"])
                for a, b in zip(s1, s2))
    press = lambda st: (st.get("prefix_evictions", 0)
                        + st.get("kv_preemptions", 0))
    return {
        "num_requests": num_requests,
        "kv_bytes_budget": int(budget),
        "bf16_blocks": bf16_blocks,
        "int8_blocks": int(int8_blocks),
        "bf16_tok_s": round(bf16["tok_s"], 1),
        "int8_tok_s": round(q8["tok_s"], 1),
        "int8_over_bf16": round(
            q8["tok_s"] / max(1e-9, bf16["tok_s"]), 3),
        "evictions_bf16": bf16["stats"].get("prefix_evictions", 0),
        "evictions_int8": q8["stats"].get("prefix_evictions", 0),
        "preemptions_bf16": bf16["stats"].get("kv_preemptions", 0),
        "preemptions_int8": q8["stats"].get("kv_preemptions", 0),
        "pressure_events_bf16": press(bf16["stats"]),
        "pressure_events_int8": press(q8["stats"]),
        "token_match_rate": round(match / max(1, total), 3),
        "bytes_per_block_bf16": bf16["stats"]["kv_bytes_per_block"],
        "bytes_per_block_int8": q8["stats"]["kv_bytes_per_block"],
    }


def _measure_fleet_remote(*, n_replicas: int = 4,
                          n_requests: int = 8) -> dict:
    """Cross-host dispatch economics: a loopback remote fleet
    (serve/remote.py — full RPC framing, idempotency keys, breaker
    bookkeeping; no sockets) vs the in-process fleet on the same
    engines, plus the cost of a held-slot continuation replay after
    the holder dies. Protocol-level numbers on the tiny model: the
    acceptance signal is dispatch overhead small relative to decode
    e2e, and replay latency ≈ one extra full prefill."""
    import time as _time

    import jax

    from senweaver_ide_tpu import obs
    from senweaver_ide_tpu.models import init_params, tiny_test
    from senweaver_ide_tpu.resilience import RetryPolicy
    from senweaver_ide_tpu.rollout import RolloutEngine
    from senweaver_ide_tpu.rollout.sampler import SampleParams
    from senweaver_ide_tpu.serve import (EngineRpcHandler,
                                         LoopbackTransport,
                                         RemoteReplica, ServingFleet)

    config = tiny_test()
    params = jax.block_until_ready(
        init_params(config, jax.random.PRNGKey(0)))
    greedy = SampleParams(temperature=0.0, top_k=0, top_p=1.0)
    policy = RetryPolicy(max_retries=1, base_delay_s=0.0, jitter=False)

    def engines():
        return [RolloutEngine(params, config, num_slots=2, max_len=64,
                              sample=greedy) for _ in range(n_replicas)]

    def drive(fleet) -> dict:
        t0 = _time.perf_counter()
        tickets = [fleet.submit([11 + i, 22 + i, 33 + i],
                                max_new_tokens=8)
                   for i in range(n_requests)]
        fleet.run()
        wall = _time.perf_counter() - t0
        e2es = [fleet.outcome(t).e2e_ms for t in tickets]
        return {"wall_s": wall,
                "e2e_ms_mean": sum(e2es) / max(1, len(e2es))}

    def build_remote():
        return ServingFleet(
            [RemoteReplica(f"replica-{i}",
                           LoopbackTransport(
                               EngineRpcHandler(e),
                               target=f"replica-{i}"),
                           policy=policy, sleep=lambda s: None)
             for i, e in enumerate(engines())],
            retry_base_delay_s=0.0)

    obs._reset_for_tests()
    t_warm = _time.perf_counter()
    drive(ServingFleet(engines()))          # warm the jit caches
    drive(build_remote())
    compile_s = _time.perf_counter() - t_warm
    # Interleave repetitions and keep the best of each mode: at the
    # tiny model's ~50 ms scale, scheduler noise swamps a single run.
    local = min((drive(ServingFleet(engines())) for _ in range(3)),
                key=lambda r: r["e2e_ms_mean"])
    remote_fleet = build_remote()
    remote = min([drive(remote_fleet)] +
                 [drive(build_remote()) for _ in range(2)],
                 key=lambda r: r["e2e_ms_mean"])

    # Held-slot continuation replay latency: holder dies, the full
    # transcript re-prefills on a survivor.
    held = remote_fleet.submit([5, 9, 2, 7], max_new_tokens=4,
                               hold_slot=True)
    remote_fleet.run()
    out1 = list(remote_fleet.outcome(held).tokens)
    remote_fleet.kill_replica(remote_fleet._requests[held].replica_id)
    t0 = _time.perf_counter()
    t2 = remote_fleet.submit([5, 9, 2, 7] + out1 + [6, 1],
                             max_new_tokens=4, continue_from=held)
    remote_fleet.run()
    replay_ms = (_time.perf_counter() - t0) * 1000.0
    assert remote_fleet.outcome(t2) is not None
    _stamp_timing("fleet_remote", compile_s, remote["wall_s"])
    obs._reset_for_tests()
    return {
        "replicas": n_replicas,
        "requests": n_requests,
        "e2e_ms_local": round(local["e2e_ms_mean"], 2),
        "e2e_ms_remote": round(remote["e2e_ms_mean"], 2),
        "dispatch_overhead_ms": round(
            remote["e2e_ms_mean"] - local["e2e_ms_mean"], 2),
        "dispatch_overhead_pct": round(
            100.0 * (remote["e2e_ms_mean"] - local["e2e_ms_mean"])
            / max(1e-9, local["e2e_ms_mean"]), 1),
        "continuation_replay_ms": round(replay_ms, 2),
    }


def _measure_learner_publish(*, n_replicas: int = 3,
                             n_publishes: int = 4) -> dict:
    """Disaggregated-learner publish economics: a fenced publish staged
    over the loopback rpc gateway and polled to convergence
    (serve/learner.py saga) vs the same fleet's in-process
    ``update_params``, plus the recovery time for the crash path — a
    learner killed mid-roll, its successor re-acquiring the lease at a
    higher epoch and republishing the durable version until every live
    replica reconverges. Protocol-level numbers on the tiny model: the
    acceptance signal is gateway overhead small relative to the roll
    itself, and recovery ≈ one extra full roll."""
    import time as _time

    import jax

    from senweaver_ide_tpu import obs
    from senweaver_ide_tpu.models import init_params, tiny_test
    from senweaver_ide_tpu.resilience import RetryPolicy
    from senweaver_ide_tpu.rollout import RolloutEngine
    from senweaver_ide_tpu.rollout.sampler import SampleParams
    from senweaver_ide_tpu.serve import (FleetPublishClient,
                                         FleetRpcHandler, LearnerConfig,
                                         LearnerService,
                                         LoopbackTransport, ServingFleet)

    config = tiny_test()
    params = jax.block_until_ready(
        init_params(config, jax.random.PRNGKey(0)))
    greedy = SampleParams(temperature=0.0, top_k=0, top_p=1.0)
    policy = RetryPolicy(max_retries=1, base_delay_s=0.0, jitter=False)

    class Trainer:
        class _State:
            def __init__(self, p):
                self.params = p

        def __init__(self, p):
            self.state = self._State(p)

        def run_round(self):
            pass                        # isolate publish cost from train

    def build():
        fleet = ServingFleet(
            [RolloutEngine(params, config, num_slots=2, max_len=64,
                           sample=greedy) for _ in range(n_replicas)],
            retry_base_delay_s=0.0, probe_interval_s=0.0)
        handler = FleetRpcHandler(fleet)
        client = FleetPublishClient(
            LoopbackTransport(handler, target="fleet-gw"),
            name="bench-learner", policy=policy, sleep=lambda s: None)
        learner = LearnerService(
            Trainer(params), client,
            config=LearnerConfig(holder="bench-learner"))
        return fleet, handler, client, learner

    obs._reset_for_tests()
    # In-process baseline: the trainer-side blocking publish.
    fleet_local, _, _, _ = build()
    t_warm = _time.perf_counter()
    fleet_local.update_params(params)   # warm
    compile_s = _time.perf_counter() - t_warm
    t0 = _time.perf_counter()
    for _ in range(n_publishes):
        fleet_local.update_params(params)
    inproc_ms = (_time.perf_counter() - t0) * 1000.0 / n_publishes

    # Learner saga over the loopback gateway (stage + poll-to-converge).
    fleet, handler, client, learner = build()
    learner.start()
    t_warm = _time.perf_counter()
    learner.run_round()                 # warm
    compile_s += _time.perf_counter() - t_warm
    t0 = _time.perf_counter()
    for _ in range(n_publishes):
        learner.run_round()
    learner_ms = (_time.perf_counter() - t0) * 1000.0 / n_publishes

    # Crash recovery: stage the next version, tear the roll after one
    # pump, then time the successor's start() — lease re-acquire at a
    # higher epoch + durable republish — until full reconvergence.
    torn = learner.version + 1
    client.publish(params, epoch=learner.epoch, version=torn)
    fleet.step()                        # one replica swaps — torn roll
    assert fleet.publisher.in_progress
    successor = LearnerService(
        Trainer(params),
        FleetPublishClient(
            LoopbackTransport(handler, target="fleet-gw"),
            name="bench-learner-2", policy=policy, sleep=lambda s: None),
        config=LearnerConfig(holder="bench-learner"))
    successor.version = learner.version  # the durable state a restart reads
    t0 = _time.perf_counter()
    epoch2 = successor.client.acquire_lease("bench-learner")["epoch"]
    successor.epoch = int(epoch2)
    successor._publish(params, successor.version)
    recovery_ms = (_time.perf_counter() - t0) * 1000.0
    versions = {r.weight_version for r in fleet.replicas}
    assert versions == {successor.version}, "reconvergence failed"
    _stamp_timing("learner_publish", compile_s, learner_ms / 1000.0)
    obs._reset_for_tests()
    return {
        "replicas": n_replicas,
        "publishes": n_publishes,
        "publish_ms_inprocess": round(inproc_ms, 2),
        "publish_ms_learner": round(learner_ms, 2),
        "gateway_overhead_ms": round(learner_ms - inproc_ms, 2),
        "gateway_overhead_pct": round(
            100.0 * (learner_ms - inproc_ms) / max(1e-9, inproc_ms), 1),
        "recovery_reconverge_ms": round(recovery_ms, 2),
    }


def _measure_streaming_grpo(*, n_replicas: int = 2, group_size: int = 8,
                            n_rounds: int = 8, decode_tokens: int = 4,
                            prompt_len: int = 8,
                            remote_rtt_s: float = 0.016) -> dict:
    """Continuous-flow GRPO vs lockstep rounds at EQUAL episode budget
    (ISSUE 15). Both arms run the full real pipeline on the tiny model —
    threaded fleet decode for collection, token-exact streamed episodes
    (recorded behavior logps), real ``train_step`` via the
    StreamingTrainerAdapter, fenced publishes over the loopback rpc
    gateway. Lockstep serializes collect -> train -> BLOCKING publish
    per round; streaming runs the collector in its own thread against
    the staleness-bounded queue while the learner trains and stages
    eager no-drain publishes.

    ``remote_rtt_s`` models the one piece a single-host bench cannot
    produce: in the disaggregated topology the replicas live on OTHER
    hosts, so each finished group spends a network+queuing hop in
    flight before the learner can see it. Both arms pay the identical
    hop per round; the difference is structural. Lockstep waits it out
    on the critical path (collect -> hop -> train -> blocking publish).
    Streaming treats it as delivery latency: the collector fires the
    group into the pipe and immediately starts the next decode, so the
    hop (a GIL-releasing wait) overlaps real compute even on a 1-core
    host, where compute can never overlap compute (cpu count is
    stamped in the output). Everything else — decode, train, rpc
    framing, queue dedup, fenced publishes — is real and measured.
    Headline: rounds/sec speedup and the learner idle fraction
    collapsing, with zero episodes lost or double-trained
    (asserted)."""
    import threading as _threading
    import time as _time

    import jax
    import jax.numpy as jnp

    from senweaver_ide_tpu import obs
    from senweaver_ide_tpu.models import init_params, tiny_test
    from senweaver_ide_tpu.resilience import RetryPolicy
    from senweaver_ide_tpu.rollout import RolloutEngine
    from senweaver_ide_tpu.rollout.sampler import SampleParams
    from senweaver_ide_tpu.serve import (EpisodeStreamer, ExperienceClient,
                                         ExperienceRpcHandler,
                                         FleetPublishClient,
                                         FleetRpcHandler, LearnerConfig,
                                         LoopbackTransport, ServingFleet,
                                         StreamingLearnerConfig,
                                         StreamingLearnerService)
    from senweaver_ide_tpu.training.experience import (
        StreamedEpisode, StreamingTrainerAdapter)
    from senweaver_ide_tpu.training.trainer import (TrainState,
                                                    make_optimizer)

    config = tiny_test()
    params = jax.block_until_ready(
        init_params(config, jax.random.PRNGKey(0)))
    greedy = SampleParams(temperature=0.0, top_k=0, top_p=1.0)
    policy = RetryPolicy(max_retries=1, base_delay_s=0.0, jitter=False)
    opt = make_optimizer()
    prompts = [[(i * 7 + j) % 200 + 2 for j in range(prompt_len)]
               for i in range(group_size)]

    class ForceLockstep:
        """Pin the service to its lockstep fallback path (the veto
        permanently active) — the baseline arm."""

        def lockstep_fallback_active(self):
            return True

        def apply(self, grpo_config, triggers):
            return grpo_config, []

    def run_arm(streaming: bool) -> dict:
        obs._reset_for_tests()
        fleet = ServingFleet(
            [RolloutEngine(params, config, num_slots=group_size,
                           max_len=64, sample=greedy)
             for _ in range(n_replicas)],
            retry_base_delay_s=0.0, probe_interval_s=0.0)
        handler = FleetRpcHandler(fleet)
        client = FleetPublishClient(
            LoopbackTransport(handler, target="fleet-gw"),
            name="bench-stream", policy=policy)
        state = TrainState(params=params,
                           opt_state=jax.jit(opt.init)(params),
                           step=jnp.zeros((), jnp.int32), opt=opt)
        adapter = StreamingTrainerAdapter(state, config, None,
                                          optimizer=opt)
        svc = StreamingLearnerService(
            adapter, client,
            stream_config=StreamingLearnerConfig(
                group_size=group_size, min_groups=1, max_staleness=64),
            config=LearnerConfig(holder="bench-stream",
                                 publish_poll_interval_s=0.0005),
            mitigator=None if streaming else ForceLockstep())
        streamer = EpisodeStreamer(ExperienceClient(
            LoopbackTransport(ExperienceRpcHandler(svc), target="exp"),
            name="bench-collector", policy=policy))
        fleet.start(dispatch_interval_s=0.0005)
        try:
            svc.start()

            deliver_lock = _threading.Lock()

            def deliver(group):
                """The modeled remote hop: the group is in flight for
                ``remote_rtt_s`` before the learner's intake sees it."""
                _time.sleep(remote_rtt_s)
                with deliver_lock:
                    streamer.offer(group)
                    streamer.flush()

            def collect(round_idx: int):
                tickets = [fleet.submit(p, max_new_tokens=decode_tokens)
                           for p in prompts]
                while not all(fleet.is_done(t) for t in tickets):
                    _time.sleep(0.0002)
                version = fleet.publisher.version
                return [StreamedEpisode(
                    episode_id=f"b/r{round_idx}/i{i}",
                    group_key=f"b/r{round_idx}",
                    prompt_ids=prompts[i],
                    completion_ids=fleet.result(t),
                    reward=float(i % 3) - 1.0, epoch=svc.epoch,
                    version=version,
                    behavior_logp=fleet.result_logps(t))
                    for i, t in enumerate(tickets)]

            def train_next() -> dict:
                while True:
                    res = svc.run_step()
                    if res is not None:
                        return res
                    svc.note_idle(0.0005)
                    _time.sleep(0.0005)

            # Warmup round: decode + train + publish compiles land here,
            # OUTSIDE the timed window (honest steady-state numbers).
            t_warm = _time.perf_counter()
            deliver(collect(0))
            train_next()
            svc.pump_publish(block=True)
            compile_s = _time.perf_counter() - t_warm
            svc.reset_utilization()

            t0 = _time.perf_counter()
            if streaming:
                def collector():
                    hops = []
                    for r in range(1, n_rounds + 1):
                        group = collect(r)
                        hop = _threading.Thread(
                            target=deliver, args=(group,), daemon=True)
                        hop.start()
                        hops.append(hop)
                    for hop in hops:
                        hop.join()
                ct = _threading.Thread(target=collector, daemon=True)
                ct.start()
                for _ in range(n_rounds):
                    train_next()
                ct.join()
                svc.pump_publish(block=True)
            else:
                for r in range(1, n_rounds + 1):
                    tc = _time.perf_counter()
                    group = collect(r)
                    deliver(group)   # the hop sits on the critical path
                    svc.note_idle(_time.perf_counter() - tc)
                    res = train_next()
                    assert res["mode"] == "lockstep"
            wall = _time.perf_counter() - t0

            # Zero lost / double-trained at equal budget, both arms.
            qstats = svc.queue.stats()
            episodes = (n_rounds + 1) * group_size
            assert qstats["accepted"] == episodes, qstats
            assert svc.rounds == n_rounds + 1
            assert streamer.pending == 0
            stall = obs.get_registry().get(
                "senweaver_collector_stall_fraction")
            return {
                "wall_s": wall,
                "rounds_per_sec": n_rounds / wall,
                "learner_idle_fraction": round(svc.idle_fraction(), 4),
                "collector_stall_fraction": round(
                    float(stall.value() or 0.0), 4),
                "compile_s": compile_s,
                "staleness_mean_last": None,
            }
        finally:
            fleet.stop()

    lockstep = run_arm(streaming=False)
    streaming = run_arm(streaming=True)
    _stamp_timing("streaming_grpo", streaming.pop("compile_s"),
                  streaming["wall_s"] / n_rounds)
    lockstep.pop("compile_s")
    lockstep.pop("staleness_mean_last")
    streaming.pop("staleness_mean_last")
    speedup = (streaming["rounds_per_sec"]
               / max(1e-9, lockstep["rounds_per_sec"]))
    import os as _os
    return {
        "replicas": n_replicas,
        "group_size": group_size,
        "rounds": n_rounds,
        "modeled_remote_rtt_ms": round(remote_rtt_s * 1000.0, 1),
        "host_cpu_count": _os.cpu_count(),
        "episode_budget_per_arm": (n_rounds + 1) * group_size,
        "lockstep": {k: round(v, 4) if isinstance(v, float) else v
                     for k, v in lockstep.items()},
        "streaming": {k: round(v, 4) if isinstance(v, float) else v
                      for k, v in streaming.items()},
        "rounds_per_sec_speedup": round(speedup, 3),
    }


def _measure_spec_adaptive(*, num_slots: int = 4, n_requests: int = 12,
                           decode_tokens: int = 24) -> dict:
    """Concurrency-adaptive speculation economics (ISSUE 12): the same
    overloaded workload served with a FIXED depth-8 draft vs the
    adaptive controller. The acceptance signal is
    ``wasted_ratio_adaptive < wasted_ratio_fixed`` — under a saturated
    fleet the controller throttles speculation so rejected draft
    tokens stop stealing verify compute — plus an idle-engine arm
    showing the controller sitting at the deepest rung where
    speculation is near-free. Greedy outputs are asserted identical
    across all arms (speculation only ever moves throughput)."""
    import dataclasses as _dc
    import time as _time

    import jax

    from senweaver_ide_tpu import obs
    from senweaver_ide_tpu.models import init_params, tiny_test
    from senweaver_ide_tpu.rollout import EngineConfig, RolloutEngine
    from senweaver_ide_tpu.rollout.sampler import SampleParams
    from senweaver_ide_tpu.rollout.spec_controller import (
        SpecController, SpecControllerConfig)

    config = tiny_test()
    params = jax.block_until_ready(
        init_params(config, jax.random.PRNGKey(0)))
    draft_cfg = _dc.replace(config, num_layers=2, name="tiny-draft")
    draft = jax.block_until_ready(
        init_params(draft_cfg, jax.random.PRNGKey(1)))
    greedy = SampleParams(temperature=0.0, top_k=0, top_p=1.0)
    prompts = [[(i * 7 + j) % 200 + 2 for j in range(8)]
               for i in range(n_requests)]

    def run(mode: str) -> dict:
        obs._reset_for_tests()
        eng = RolloutEngine(
            params, config, num_slots=num_slots, max_len=128,
            sample=greedy,
            engine_config=EngineConfig(kv_layout="paged"))
        if mode == "fixed":
            eng.enable_speculation(draft, draft_cfg, depth=8)
        elif mode == "adaptive":
            eng.enable_speculation(
                draft, draft_cfg, controller=SpecController(
                    SpecControllerConfig(hysteresis_steps=2)))
        rids = [eng.submit(p, max_new_tokens=decode_tokens)
                for p in prompts]
        # The router's backlog signal for a saturated replica.
        eng.note_decode_load(float(n_requests * decode_tokens))
        t0 = _time.perf_counter()
        out = eng.run()
        dt = _time.perf_counter() - t0
        s = eng.spec_stats() if mode != "off" else {}
        return {"tok_s": sum(len(out[r]) for r in rids) / dt,
                "tokens": [out[r] for r in rids],
                "proposed": s.get("proposed", 0),
                "wasted": s.get("wasted_draft_tokens", 0)}

    t_warm = _time.perf_counter()
    for m in ("off", "fixed", "adaptive"):
        run(m)              # compile warmup, all arms
    compile_s = _time.perf_counter() - t_warm
    off = run("off")
    fixed = run("fixed")
    t0 = _time.perf_counter()
    adaptive = run("adaptive")
    _stamp_timing("spec_adaptive", compile_s,
                  _time.perf_counter() - t0)

    # Idle arm: one light request; the controller should sit deep.
    obs._reset_for_tests()
    eng = RolloutEngine(
        params, config, num_slots=num_slots, max_len=128, sample=greedy,
        engine_config=EngineConfig(kv_layout="paged"))
    eng.enable_speculation(
        draft, draft_cfg,
        controller=SpecController(SpecControllerConfig(hysteresis_steps=2)))
    rid = eng.submit(prompts[0], max_new_tokens=decode_tokens)
    idle_tokens = eng.run()[rid]
    idle_depth = eng.spec_stats()["depth"]
    obs._reset_for_tests()

    emitted = sum(len(t) for t in off["tokens"])
    exact = (fixed["tokens"] == off["tokens"]
             == adaptive["tokens"])
    return {
        "num_slots": num_slots,
        "n_requests": n_requests,
        "outputs_exact": exact and idle_tokens == off["tokens"][0],
        "off_tok_s": round(off["tok_s"], 1),
        "fixed8_tok_s": round(fixed["tok_s"], 1),
        "adaptive_tok_s": round(adaptive["tok_s"], 1),
        "fixed8_wasted_draft_tokens": fixed["wasted"],
        "adaptive_wasted_draft_tokens": adaptive["wasted"],
        "fixed8_wasted_per_token": round(fixed["wasted"] / emitted, 3),
        "adaptive_wasted_per_token": round(
            adaptive["wasted"] / emitted, 3),
        "idle_controller_depth": idle_depth,
    }


def _measure_multi_lora(*, n_tenants: int = 6, reqs_per_tenant: int = 2,
                        decode_tokens: int = 16) -> dict:
    """Multi-tenant adapter economics (ISSUE 14): the same N-tenant
    request mix served (a) batched through ONE pool engine — every
    tenant's rows share each fused step via the gathered adapter
    banks — vs (b) sequentially with a swap-per-tenant engine
    (update_params(merge_lora(...)) then that tenant's requests alone,
    the pre-pool serving story). Outputs are asserted token-exact
    across arms; the acceptance signal is aggregate tok/s >= 1.5x,
    plus per-tenant mean TTFT for both arms and the gathered-step
    overhead vs a base-only batch of the same shape."""
    import time as _time

    import jax

    from senweaver_ide_tpu import obs
    from senweaver_ide_tpu.models import init_params, tiny_test
    from senweaver_ide_tpu.rollout import (AdapterPool, AdapterPoolConfig,
                                           EngineConfig, RolloutEngine)
    from senweaver_ide_tpu.rollout.sampler import SampleParams
    from senweaver_ide_tpu.training.lora import init_lora, merge_lora

    config = tiny_test()
    params = jax.block_until_ready(
        init_params(config, jax.random.PRNGKey(0)))
    greedy = SampleParams(temperature=0.0, top_k=0, top_p=1.0)
    num_slots = n_tenants * reqs_per_tenant
    tenants = [f"tenant-{i}" for i in range(n_tenants)]
    loras = {}
    for i, name in enumerate(tenants):
        lora = init_lora(config, jax.random.PRNGKey(10 + i),
                         rank=8 if i % 2 else 16)
        for k in list(lora["layers"]):
            if k.endswith("_lora_b"):
                lora["layers"][k] = jax.random.normal(
                    jax.random.PRNGKey(50 + i), lora["layers"][k].shape,
                    lora["layers"][k].dtype) * 0.05
        loras[name] = lora
    mix = [(name, [(i * 13 + t * 7 + j) % 200 + 2 for j in range(8)])
           for t, name in enumerate(tenants)
           for i in range(reqs_per_tenant)]

    def drain_with_ttft(eng, rids, t0):
        first, out = {}, {r: [] for r in rids}
        while eng.has_work:
            emitted = eng.step()
            now = _time.perf_counter()
            for r, toks in emitted.items():
                if toks and r not in first:
                    first[r] = now - t0
                out[r].extend(toks)
        return out, first

    def run_batched():
        pool = AdapterPool(config, AdapterPoolConfig())
        eng = RolloutEngine(
            params, config, num_slots=num_slots, max_len=128,
            sample=greedy, adapter_pool=pool,
            engine_config=EngineConfig(kv_layout="paged"))
        for name, lora in loras.items():
            eng.publish_adapter(name, lora)
        t0 = _time.perf_counter()
        rids = [eng.submit(p, max_new_tokens=decode_tokens,
                           adapter_id=name) for name, p in mix]
        out, first = drain_with_ttft(eng, rids, t0)
        dt = _time.perf_counter() - t0
        return ([out[r] for r in rids], dt,
                sum(first.values()) / len(first), pool)

    def run_sequential():
        eng = RolloutEngine(
            params, config, num_slots=num_slots, max_len=128,
            sample=greedy,
            engine_config=EngineConfig(kv_layout="paged"))
        outs, ttfts = [], []
        # TTFT from the ARM start: the whole mix arrives together, so a
        # later tenant's first token honestly includes waiting for every
        # earlier tenant's swap + decode (the queue the pool removes).
        t0 = _time.perf_counter()
        for name in tenants:
            eng.update_params(merge_lora(params, loras[name]))
            rids = [eng.submit(p, max_new_tokens=decode_tokens)
                    for n2, p in mix if n2 == name]
            out, first = drain_with_ttft(eng, rids, t0)
            outs.extend(out[r] for r in rids)
            ttfts.extend(first.values())
        dt = _time.perf_counter() - t0
        return outs, dt, sum(ttfts) / len(ttfts)

    def run_base_only():
        eng = RolloutEngine(
            params, config, num_slots=num_slots, max_len=128,
            sample=greedy,
            engine_config=EngineConfig(kv_layout="paged"))
        t0 = _time.perf_counter()
        for _, p in mix:
            eng.submit(p, max_new_tokens=decode_tokens)
        eng.run()
        return _time.perf_counter() - t0

    t_warm = _time.perf_counter()
    run_batched(); run_sequential(); run_base_only()   # compile warmup
    compile_s = _time.perf_counter() - t_warm
    obs._reset_for_tests()
    base_dt = run_base_only()
    seq_out, seq_dt, seq_ttft = run_sequential()
    t0 = _time.perf_counter()
    bat_out, bat_dt, bat_ttft, pool = run_batched()
    _stamp_timing("multi_lora", compile_s, _time.perf_counter() - t0)

    # The batched arm must be reordered back to the sequential arm's
    # tenant-major order before comparing (same mix, same order here).
    exact = bat_out == seq_out
    tokens = sum(len(t) for t in bat_out)
    overhead = bat_dt / base_dt if base_dt > 0 else 1.0
    pool.note_gather_overhead(overhead)
    out = {
        "n_tenants": n_tenants,
        "requests": len(mix),
        "outputs_exact": exact,
        "batched_tok_s": round(tokens / bat_dt, 1),
        "sequential_swap_tok_s": round(tokens / seq_dt, 1),
        "aggregate_speedup": round(seq_dt / bat_dt, 2),
        "batched_mean_ttft_s": round(bat_ttft, 4),
        "sequential_mean_ttft_s": round(seq_ttft, 4),
        "gather_overhead_vs_base": round(overhead, 3),
        "pool": {k: v for k, v in pool.stats().items()
                 if k in ("publishes", "installs", "evictions")},
    }
    obs._reset_for_tests()
    return out


def _measure_group_rollout(*, group_size: int = 8, prompt_len: int = 48,
                           decode_tokens: int = 24) -> dict:
    """Group-shared rollout economics (ISSUE 18): one GRPO group of G
    completions over the same prompt decoded (a) via submit_group —
    the donor prefills once and every follower grafts the forked KV
    spine, paying a single-token rescore — vs (b) G independent
    submits that each prefill the full prompt. Outputs are asserted
    bitwise-exact across arms; the acceptance signals are prefill
    tokens avoided (counter-backed) and aggregate tok/s uplift."""
    import time as _time

    import jax

    from senweaver_ide_tpu import obs
    from senweaver_ide_tpu.models import init_params, tiny_test
    from senweaver_ide_tpu.rollout import EngineConfig, RolloutEngine
    from senweaver_ide_tpu.rollout.sampler import SampleParams

    config = tiny_test()
    params = jax.block_until_ready(
        init_params(config, jax.random.PRNGKey(0)))
    greedy = SampleParams(temperature=0.0, top_k=0, top_p=1.0)
    prompt = [(i * 31 + 7) % 200 + 2 for i in range(prompt_len)]
    max_len = prompt_len + decode_tokens + 8

    def engine():
        return RolloutEngine(
            params, config, num_slots=group_size, max_len=max_len,
            sample=greedy,
            engine_config=EngineConfig(kv_layout="paged", block_size=4))

    def run_shared():
        eng = engine()
        t0 = _time.perf_counter()
        rids = eng.submit_group(prompt, group_size,
                                max_new_tokens=decode_tokens)
        out = eng.run()
        dt = _time.perf_counter() - t0
        return [out[r] for r in rids], dt, eng.stats()

    def run_independent():
        eng = engine()
        t0 = _time.perf_counter()
        rids = [eng.submit(list(prompt), max_new_tokens=decode_tokens)
                for _ in range(group_size)]
        out = eng.run()
        dt = _time.perf_counter() - t0
        return [out[r] for r in rids], dt, eng.stats()

    t_warm = _time.perf_counter()
    run_shared(); run_independent()            # compile warmup
    compile_s = _time.perf_counter() - t_warm
    obs._reset_for_tests()
    ind_out, ind_dt, ind_st = run_independent()
    t0 = _time.perf_counter()
    sh_out, sh_dt, sh_st = run_shared()
    _stamp_timing("group_rollout", compile_s, _time.perf_counter() - t0)

    exact = sh_out == ind_out
    tokens = sum(len(t) for t in sh_out)
    out = {
        "group_size": group_size,
        "prompt_len": prompt_len,
        "outputs_exact": exact,
        "shared_prefills": sh_st["prefills"],
        "independent_prefills": ind_st["prefills"],
        "prefill_tokens_avoided": sh_st["group_prefill_tokens_avoided"],
        "cow_copies": sh_st["kv_cow_copies"],
        "shared_tok_s": round(tokens / sh_dt, 1),
        "independent_tok_s": round(tokens / ind_dt, 1),
        "aggregate_speedup": round(ind_dt / sh_dt, 2),
    }
    obs._reset_for_tests()
    return out


def main() -> None:
    import jax

    from senweaver_ide_tpu.runtime.compile_cache import enable_compile_cache

    force_cpu = bool(os.environ.get("BENCH_FORCE_CPU"))
    if force_cpu:
        jax.config.update("jax_platforms", "cpu")
    enable_compile_cache()

    dev = jax.devices()[0]
    on_accel = dev.platform != "cpu"
    if not on_accel and not force_cpu:
        # No chip, no measurement: a CPU number under the device
        # metric's name is worse than none.
        sys.exit("bench: JAX found no accelerator (platform cpu). "
                 "BENCH_FORCE_CPU=1 is the explicit CPU route (tiny-test, "
                 "reported as a CPU smoke, never as a device metric).")
    _log(f"backend up: {dev}")
    model_name = "qwen2.5-coder-1.5b" if on_accel else "tiny-test"

    _log(f"primary decode measure: {model_name}")
    primary = _measure(model_name, BATCH, PROMPT_LEN, DECODE_TOKENS,
                       timing_key="primary")
    _log(f"primary done: {primary:.1f} tok/s")

    # Every case below raises on failure and the process exits non-zero:
    # an "error: ..." string riding a healthy-looking line hid failures.
    extra = {}
    if on_accel:
        for name, b, p, n, key, quant, wq, mode in (
                ("qwen2.5-coder-1.5b", 32, 512, 128, "qwen1.5b_b32",
                 False, False, "scan"),
                # int8 KV cache + donated cache buffers are what fit b16
                # next to 13.4 GB of bf16 weights; measured via the
                # per-step serving path.
                ("deepseek-coder-6.7b", 16, 128, 96,
                 "deepseek6.7b_b16_int8kv", True, False, "steps"),
                # int8 weights (6.4 GB, built directly in int8 —
                # _init_int8_params) + int8 KV: half the bytes of the
                # bf16 row.
                ("deepseek-coder-6.7b", 16, 128, 96,
                 "deepseek6.7b_b16_int8w_int8kv", True, True, "steps"),
                # The SWA family (mistral-7b). At this shape the cache
                # (193 < window) runs the absolute short-cache SWA path;
                # a full 4096-slot ring at b4 would be 4.3 GB of cache
                # next to 14.5 GB of bf16 weights — past one 16 GB chip.
                ("mistral-7b", 4, 128, 64, "mistral7b_b4_swa",
                 False, False, "steps"),
        ):
            _log(f"extra measure: {key}")
            if mode == "scan":
                extra[key] = round(_measure(name, b, p, n, timing_key=key), 2)
            else:
                extra[key] = round(
                    _measure_steps(name, b, p, n, quantized=quant,
                                   weight_quant=wq, timing_key=key), 2)

        # int8 weight-only serving (models/quantize.py) and the
        # flash-decode kernel inside generate_scan.
        for key, kw in (("qwen1.5b_b8_int8w", {"weight_quant": True}),
                        ("qwen1.5b_b8_flash",
                         {"decode_attn_impl": "flash"})):
            _log(f"extra measure: {key}")
            extra[key] = round(_measure("qwen2.5-coder-1.5b", BATCH,
                                        PROMPT_LEN, DECODE_TOKENS,
                                        timing_key=key, **kw), 2)

    # Train-step throughput + MFU (north-star training rows).
    train_shapes = ([("qwen2.5-coder-1.5b", 4, 1024, 1, "train_1.5b")]
                    if on_accel else [("tiny-test", 4, 128, 1,
                                       "train_tiny")])
    for name, b, s, acc, key in train_shapes:
        _log(f"train measure: {key}")
        extra[key] = _measure_train(name, b, s, accum_steps=acc,
                                    timing_key=key)

    # Protocol- and layout-level cases: tiny-test on every backend.
    for key, measure in (
            ("prefix_fleet", _measure_prefix_fleet),
            ("paged_vs_slots", _measure_paged_vs_slots),
            ("kv_pressure", _measure_kv_pressure),
            ("kv_quant", _measure_kv_quant),
            ("spec_adaptive", _measure_spec_adaptive),
            ("multi_lora", _measure_multi_lora),
            ("group_rollout", _measure_group_rollout),
            ("fleet_remote", _measure_fleet_remote),
            ("learner_publish", _measure_learner_publish),
            ("streaming_grpo", _measure_streaming_grpo)):
        _log(f"feature measure: {key}")
        extra[key] = measure()

    # Warmup/steady split for every case that ran (satellite of the
    # runtime observatory: compile_s vs step_s, see TIMINGS).
    extra["timing"] = dict(sorted(TIMINGS.items()))
    extra["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())}
    # Surface the round's committed eval artifacts alongside the perf
    # number (the north star is reward uplift + tokens/sec — one line
    # should carry both stories).
    extra["artifacts"] = _artifact_summaries()
    if on_accel:
        baseline = _baseline()
        line = {"metric": (f"decode_tokens_per_sec_per_chip[{model_name}"
                           f",b{BATCH},p{PROMPT_LEN}]"),
                "value": round(primary, 2), "unit": "tokens/sec/chip",
                "vs_baseline": round(primary / baseline, 3)}
    else:
        # a CPU number never carries the device metric's name
        line = {"metric": (f"cpu_smoke_decode_tokens_per_sec[{model_name}"
                           f",b{BATCH},p{PROMPT_LEN}]"),
                "value": round(primary, 2), "unit": "tokens/sec (cpu)",
                "vs_baseline": None}
    print(json.dumps({**line, "extra": extra}))


if __name__ == "__main__":
    main()
