#!/usr/bin/env python3
"""Chip smoke: the main path once, on the device JAX gives us.

rollout engine -> GRPO update -> weight publish -> rollout again, at the
published widths of qwen2.5-coder-1.5b (random weights from a seed),
through the entry points a user calls: ``RolloutEngine``, ``train_step``,
``materialize_lora``, ``engine.update_params`` and, on a host with four
chips, ``make_train_state`` over an fsdp mesh and a ``ServingFleet`` of
one-chip replicas. Every Pallas kernel a config option can reach is
compiled by Mosaic (``interpret=False``) and compared with its XLA
sibling.

    python chip_smoke.py          # needs a TPU; anything else exits non-zero
    python chip_smoke.py --tiny   # CPU rehearsal: tiny-test, kernels interpreted

One process: whoever touches JAX first owns the chip. The first failed
check raises and the process exits non-zero with no result line; nothing
is caught and carried on. Stdout is two lines of JSON: first the report
(versions, per-phase wall and compile seconds, peak device memory), and
last the result, ``{"ok": true, "device": {"platform", "kind",
"count"}}`` with exactly those keys, the device as JAX reports it. It
reports no rate and no utilisation: this is a bring-up check, not a
benchmark.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import gc
import importlib.metadata
import json
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from senweaver_ide_tpu.models import (forward, get_config, init_params)
from senweaver_ide_tpu.models.transformer import (count_params,
                                                  dequantize_pool_kv,
                                                  quantize_pool_kv)
from senweaver_ide_tpu.obs.runtime_profile import get_profiler
from senweaver_ide_tpu.ops.attention import attention
from senweaver_ide_tpu.ops.flash_attention import flash_attention
from senweaver_ide_tpu.ops.paged_attention import (
    paged_attention_rows, paged_flash_decode, paged_latent_attention_rows,
    plan_rows, query_tile)
from senweaver_ide_tpu.parallel import MeshConfig, make_mesh
from senweaver_ide_tpu.rollout import RolloutEngine
from senweaver_ide_tpu.rollout import engine as engine_mod
from senweaver_ide_tpu.rollout.engine import EngineConfig
from senweaver_ide_tpu.rollout.paged_kv import (kv_payload_dtype,
                                              resolve_block_size)
from senweaver_ide_tpu.rollout.sampler import SampleParams
from senweaver_ide_tpu.runtime.compile_cache import enable_compile_cache
from senweaver_ide_tpu.serve import ServingFleet
from senweaver_ide_tpu.training import (make_lora_train_state,
                                        make_train_state, materialize_lora,
                                        train_step)
from senweaver_ide_tpu.training.data import (Trajectory, make_batch,
                                             make_batch_logps,
                                             place_batch_for_mesh)
from senweaver_ide_tpu.training.grpo import token_logprobs


@dataclasses.dataclass(frozen=True)
class Sizes:
    model: str
    num_slots: int
    max_len: int
    groups_per_wave: int      # distinct prompts per wave
    group_size: int           # samples per prompt (one GRPO group)
    prompt_lo: int
    prompt_hi: int
    new_tokens: int
    score_rows: int           # requests teacher-forced per agreement check
    score_prompt_hi: int      # their prompts are drawn from [prompt_lo, this]
    seq_len: int              # padded length: teacher forcing and training
    accum_steps: int
    lora_rank: int
    train_steps: int
    logp_tol: float
    kernel_seq: int           # S / Smax of the standalone kernel checks
    fleet_requests: int       # four-chip phase: requests over 4 replicas
    fsdp_batch: int           # four-chip phase: rows of the fsdp train step


# Published widths of qwen2.5-coder-1.5b, full depth. Requests outnumber
# slots (queueing), prompts outrun step_tokens=64 (chunked prefill) and
# decode rows ride the same fused steps.
FULL = Sizes(
    model="qwen2.5-coder-1.5b", num_slots=8, max_len=2048,
    groups_per_wave=8, group_size=2, prompt_lo=32, prompt_hi=512,
    new_tokens=64, score_rows=4, score_prompt_hi=192, seq_len=640,
    accum_steps=8, lora_rank=16, train_steps=3,
    # bf16 keeps 8 mantissa bits: each of 28 layers' residual adds and
    # the 151,936-wide logit row round at ~0.4% relative, and the engine
    # (token at a time over a gathered bf16 cache) and the full forward
    # accumulate in different orders. On a v5e the largest difference
    # over 256 tokens was 0.054, the mean 0.012 (chip run, PR 21).
    # Random-weight logits have a spread of ~0.8, so a wrong cache entry
    # or position moves a logp by ~1: 0.15 is three times the noise and
    # a sixth of a fault.
    logp_tol=0.15,
    kernel_seq=1024,
    fleet_requests=16, fsdp_batch=8)

# CPU rehearsal of the same control flow. fp32 with highest matmul
# precision, so engine and full forward agree far tighter than bf16.
TINY = Sizes(
    model="tiny-test", num_slots=4, max_len=128,
    groups_per_wave=3, group_size=2, prompt_lo=8, prompt_hi=40,
    new_tokens=8, score_rows=2, score_prompt_hi=24, seq_len=64,
    accum_steps=2, lora_rank=4, train_steps=3, logp_tol=1e-3,
    kernel_seq=128,
    fleet_requests=8, fsdp_batch=4)

# bf16 outputs of O(1) attention rows against an f32-accumulating
# sibling: one bf16 ulp at 1.0 is 2^-7 ~ 0.008; a few of them. Largest
# seen on a v5e: 0.016 (flash_attention forward; chip run, PR 21).
KERNEL_TOL = 3e-2


def check(cond: bool, msg: str) -> None:
    # not `assert`: python -O strips those
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


def log(msg: str) -> None:
    print(f"[smoke {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


class Report:
    """Per-phase wall / compile seconds and device memory."""

    def __init__(self):
        self.phases = {}
        self._compile = {"backend_compile_s": 0.0, "trace_lower_s": 0.0,
                         "cache_hits": 0}
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self._compile["backend_compile_s"] += duration
        elif event.startswith("/jax/core/compile/"):
            self._compile["trace_lower_s"] += duration

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self._compile["cache_hits"] += 1

    def memory(self):
        # one entry per device; the CPU backend reports no stats
        stats = [d.memory_stats() or {} for d in jax.devices()]
        return {k: [s.get(k) for s in stats]
                for k in ("bytes_in_use", "peak_bytes_in_use")}

    @contextlib.contextmanager
    def phase(self, name: str):
        log(f"phase {name} ...")
        before = dict(self._compile)
        rec = {}
        t0 = time.monotonic()
        yield rec
        rec["wall_s"] = round(time.monotonic() - t0, 2)
        for k, v in self._compile.items():
            rec[k] = round(v - before[k], 2)
        rec["memory"] = self.memory()
        self.phases[name] = rec
        log(f"phase {name} ok: {json.dumps(rec)}")


def ledger_of(name: str) -> dict:
    led = get_profiler().ledger().get(name, {})     # no call yet: no entry
    return {"calls": led.get("calls", 0), "compiles": led.get("compiles", 0),
            "compile_s": led.get("compile_ms", 0.0) / 1e3,
            "step_s": led.get("step_ms_sum", 0.0) / 1e3}


def ledger_delta(name: str, before: dict) -> dict:
    now = ledger_of(name)
    return {k: round(now[k] - before[k], 2) for k in now}


def draw_prompts(rng, n: int, lo: int, hi: int, vocab: int) -> list:
    return [[int(t) for t in rng.integers(1, vocab,
                                          size=int(rng.integers(lo, hi + 1)))]
            for _ in range(n)]


def run_wave(engine, prompts, group_size: int, new_tokens: int,
             vocab: int) -> tuple:
    """Submit every prompt ``group_size`` times, drive the engine dry,
    validate every result. Returns (rows, stats) with rows =
    [(group, prompt, tokens, logps)]."""
    before = ledger_of("engine.fused_step")
    stats0 = engine.stats()
    t0 = time.monotonic()
    rids = [(g, engine.submit(p, max_new_tokens=new_tokens))
            for g, p in enumerate(prompts) for _ in range(group_size)]
    engine.run()
    wall = time.monotonic() - t0
    rows = []
    for g, rid in rids:
        toks, lps = engine.result(rid), engine.result_logps(rid)
        check(len(toks) == new_tokens and len(lps) == new_tokens,
              f"request {rid}: {len(toks)} tokens / {len(lps)} logps, "
              f"wanted {new_tokens}")
        check(all(0 <= t < vocab for t in toks),
              f"request {rid}: token outside the vocabulary")
        check(all(math.isfinite(x) and x <= 0.0 for x in lps),
              f"request {rid}: logp not finite or positive")
        rows.append((g, prompts[g], toks, lps))
    stats1 = engine.stats()
    stats = {"requests": len(rids), "wall_s": round(wall, 2),
             "fused_step": ledger_delta("engine.fused_step", before)}
    for k in ("prefills", "prefill_tokens", "batched_prefills",
              "decode_steps", "tokens_emitted", "kv_preemptions"):
        stats[k] = stats1[k] - stats0[k]
    return rows, stats


def make_scorer(config, seq_len: int, n_rows: int):
    """Teacher-forced reference: the full no-cache forward over
    prompt+output, log p of each next token and the argmax beside it.
    One padded shape, so every agreement check shares one compile."""
    @jax.jit
    def score(params, tokens):
        logits, _ = forward(params, config, tokens[:, :-1])
        return (token_logprobs(logits, tokens[:, 1:]),
                jnp.argmax(logits, axis=-1))

    def run(params, seqs):
        check(len(seqs) <= n_rows and max(map(len, seqs)) <= seq_len,
              "teacher-forcing batch larger than its padded shape")
        toks = np.zeros((n_rows, seq_len), np.int32)
        for i, s in enumerate(seqs):
            toks[i, :len(s)] = s          # causal: right padding is inert
        logp, top = jax.device_get(score(params, jnp.asarray(toks)))
        return logp, top

    return run


def agreement(scorer, params, rows, tol: float) -> dict:
    """Engine logps (prefill-then-decode through the paged cache) against
    the teacher-forced full forward, per emitted token."""
    logp, top = scorer(params, [p + t for _, p, t, _ in rows])
    diffs, same = [], []
    for i, (_, prompt, toks, lps) in enumerate(rows):
        at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(toks))
        diffs.append(np.abs(logp[i, at] - np.asarray(lps, np.float32)))
        same.append(top[i, at] == np.asarray(toks))
    diffs, same = np.concatenate(diffs), np.concatenate(same)
    out = {"tokens": int(diffs.size),
           "max_abs_logp_diff": float(diffs.max()),
           "mean_abs_logp_diff": float(diffs.mean()),
           "tolerance": tol,
           # meaningful at temperature 0 only; near-ties may flip in bf16
           "token_is_reference_argmax": float(same.mean())}
    check(out["max_abs_logp_diff"] <= tol,
          f"engine logps disagree with the teacher-forced forward: {out}")
    return out


def greedy_agreement(engine, scorer, params, prompts, sz: Sizes,
                     vocab: int) -> dict:
    rows, wave = run_wave(engine, prompts, 1, sz.new_tokens, vocab)
    out = agreement(scorer, params, rows, sz.logp_tol)
    out["wave"] = wave
    return out


def trajectories(rows) -> list:
    # Outcome judge for random weights: the share of even token ids —
    # half of any vocabulary qualifies, so samples of one prompt differ
    # and group advantages are not all zero.
    return [Trajectory(prompt_ids=p, completion_ids=t,
                       reward=2.0 * sum(x % 2 == 0 for x in t) / len(t) - 1.0,
                       group_id=g, behavior_logp=lps)
            for g, p, t, lps in rows]


def grpo_batch(trajs, sz: Sizes, mesh, accum_steps: int):
    tokens, mask, rewards, gids = make_batch(trajs, pad_id=0,
                                             max_len=sz.seq_len)
    old = make_batch_logps(trajs, tokens, mask)
    check(old is not None, "engine logps missing from the trajectories")
    return place_batch_for_mesh(mesh, tokens, mask, rewards, gids, old,
                                accum_steps=accum_steps)


def take_steps(state, config, mesh, batch, n_groups: int, steps: int,
               accum_steps: int, lora_base=None) -> tuple:
    tokens, mask, rewards, gids, old = batch
    before = ledger_of("trainer.grpo_step")
    out = []
    for i in range(steps):
        t0 = time.monotonic()
        state, metrics = train_step(
            state, config, mesh, tokens, mask, rewards, gids, old_logp=old,
            num_groups=n_groups, accum_steps=accum_steps,
            lora_base=lora_base)
        m = {k: float(v) for k, v in jax.device_get(metrics).items()}
        check(math.isfinite(m["loss"]), f"step {i}: loss {m['loss']}")
        check(math.isfinite(m["grad_norm"]) and m["grad_norm"] > 0.0,
              f"step {i}: grad_norm {m['grad_norm']}")
        out.append({"wall_s": round(time.monotonic() - t0, 2),
                    "loss": m["loss"], "grad_norm": m["grad_norm"],
                    "ratio_mean": m["ratio_mean"]})
    led = ledger_delta("trainer.grpo_step", before)
    check(led["compiles"] == 1,
          f"{steps} identical train steps compiled {led['compiles']} times")
    return state, {"steps": out, "grpo_step": led}


def lowered_with_kernel(lowered, kernel_name: str) -> None:
    """A Mosaic kernel shows in the lowered module as a tpu_custom_call
    carrying its function's name; an interpreted or replaced one does
    not."""
    check(f'kernel_name = "{kernel_name}"' in lowered.as_text(),
          f"{kernel_name} is not in the lowered program: the kernel was "
          f"interpreted or replaced")


def max_err(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float32)
                               - np.asarray(b, np.float32))))


def kernel_checks(config, s: int, interpret: bool, seed: int) -> dict:
    """Each Pallas kernel, compiled by Mosaic (``interpret=False`` on the
    chip), against its XLA sibling at the model's head geometry, over
    ``s`` positions."""
    hq, hkv, d = config.num_heads, config.num_kv_heads, config.head_dim
    dtype = jnp.float32 if interpret else jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    out = {}

    def timed(name, fn, *args):
        t0 = time.monotonic()
        res = jax.block_until_ready(fn(*args))
        out[name + "_first_call_s"] = round(time.monotonic() - t0, 2)
        return res

    # flash_attention: forward, and the blockwise backward via jax.grad
    b = 2
    q = jax.random.normal(ks[0], (b, s, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    w = jax.random.normal(ks[3], (b, s, hq, d), jnp.float32)
    flash = functools.partial(flash_attention, causal=True,
                              interpret=interpret)
    plain = functools.partial(attention, causal=True)
    fa_jit = jax.jit(flash)
    if not interpret:
        lowered_with_kernel(fa_jit.lower(q, k, v), "_fa_kernel")
    err = max_err(timed("flash_attention", fa_jit, q, k, v),
                  jax.jit(plain)(q, k, v))
    out["flash_attention_max_err"] = err
    check(err <= KERNEL_TOL, f"flash_attention forward off by {err}")

    # sliding-window variant (the in-kernel band mask a
    # ``sliding_window`` config selects)
    win = s // 4
    err = max_err(
        timed("flash_attention_window",
              jax.jit(functools.partial(flash, window=win)), q, k, v),
        jax.jit(functools.partial(plain, window=win))(q, k, v))
    out["flash_attention_window_max_err"] = err
    check(err <= KERNEL_TOL, f"flash_attention window={win} off by {err}")

    def grads(fn):
        return jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * w),
            argnums=(0, 1, 2)))

    g_flash = timed("flash_attention_grad", grads(flash), q, k, v)
    g_plain = grads(plain)(q, k, v)
    for name, gf, gp in zip("qkv", g_flash, g_plain):
        scale = float(np.max(np.abs(np.asarray(gp, np.float32))))
        rel = max_err(gf, gp) / scale
        out[f"flash_attention_d{name}_rel_err"] = rel
        check(rel <= KERNEL_TOL, f"flash_attention d{name} off by {rel} "
                                 f"of its largest entry")

    # paged_flash_decode at blocks of 16: one query a row through
    # paged_attention_rows, and the quantized pools with fused dequant
    bs = 16
    t, mb = 16, s // bs
    nb = 2 * mb
    rng = np.random.default_rng(seed)
    tables = jnp.asarray(np.stack([rng.permutation(nb)[:mb]
                                   for _ in range(t)]).astype(np.int32))
    plens = jnp.asarray(np.linspace(1, s, t).round().astype(np.int32))
    qp = jax.random.normal(ks[4], (t, hq, d), dtype)
    k_pool = jax.random.normal(ks[5], (nb, bs, hkv, d), dtype)
    v_pool = jax.random.normal(ks[6], (nb, bs, hkv, d), dtype)
    pvalid = jnp.arange(s)[None, :] < plens[:, None]

    def gathered(kp, vp):
        return attention(qp[:, None], kp[tables].reshape(t, s, hkv, d),
                         vp[tables].reshape(t, s, hkv, d),
                         kv_mask=pvalid, causal=False)[:, 0]

    pfd_jit = jax.jit(functools.partial(paged_flash_decode,
                                        interpret=interpret))
    if not interpret:
        lowered_with_kernel(pfd_jit.lower(qp, k_pool, v_pool, tables, plens),
                            "paged_attention_rows")
    err = max_err(timed("paged_flash_decode", pfd_jit, qp, k_pool, v_pool,
                        tables, plens), gathered(k_pool, v_pool))
    out["paged_flash_decode_max_err"] = err
    check(err <= KERNEL_TOL, f"paged_flash_decode off by {err}")

    # the quantized ladder's fused-dequant variant, both payload dtypes
    pfdq_jit = jax.jit(
        lambda q, k, v, tb, ln, ksc, vsc: paged_flash_decode(
            q, k, v, tb, ln, k_scale=ksc, v_scale=vsc, interpret=interpret))
    for name in ("int8", "fp8"):
        kq, kscale = quantize_pool_kv(k_pool, kv_payload_dtype(name))
        vq, vscale = quantize_pool_kv(v_pool, kv_payload_dtype(name))
        if not interpret:
            lowered_with_kernel(
                pfdq_jit.lower(qp, kq, vq, tables, plens, kscale, vscale),
                "_pfd_kernel")
        err = max_err(
            timed(f"paged_flash_decode_{name}", pfdq_jit, qp, kq, vq,
                  tables, plens, kscale, vscale),
            gathered(dequantize_pool_kv(kq, kscale, dtype),
                     dequantize_pool_kv(vq, vscale, dtype)))
        out[f"paged_flash_decode_{name}_max_err"] = err
        check(err <= KERNEL_TOL, f"paged_flash_decode {name} off by {err}")
    d = config.head_dim
    out.update(paged_rows_checks(config.num_heads, config.num_kv_heads, d,
                                 "", interpret, seed))
    # a second head shape (mistral-7b's, llama-3.1-8b's, qwen3-8b's):
    # four times the bytes a block, a quarter of the blocks a chunk
    out.update(paged_rows_checks(32, 8, d, "_32x8", interpret, seed))
    # Falcon-H1-34B's heads over its cell's contexts (a row of 4096)
    out.update(paged_rows_checks(20, 4, d, "_20x4", interpret, seed,
                                 long_rows=True))
    # the latent pool's one-leaf form at GLM-4.7-Flash's shapes: 20 heads
    # over rows of 576 values stored 640 wide, the value their first 512
    out.update(paged_rows_checks(20, 1, 640, "_latent", interpret, seed,
                                 latent_rank=512, long_rows=True))
    return out


def paged_rows_checks(hq: int, hkv: int, d: int, tag: str, interpret: bool,
                      seed: int, latent_rank: int = 0,
                      long_rows: bool = False) -> dict:
    """``paged_attention_rows`` against the XLA gather it replaces in the
    fused step, over a stacked pool at the benchmark cells' shapes: 48
    rows of 1024 tokens, contexts 128-900 (``long_rows``: the ctx4k cells'
    rows of 4096, contexts 1024-3840), as a narrow step (48 decode
    entries) and a wide one (47 decode rows, a prefill chunk of 137 tokens
    on the last row, 8 entries of padding). With a ``latent_rank`` it is
    ``paged_latent_attention_rows`` over a latent pool's one leaf of
    ``d``-wide rows, the value their first ``latent_rank`` columns, at the
    scale of 256-wide heads. Twice: at blocks of 16 (``..._bs16_...``) and
    at the block the engine resolves for this row (``..._resolved_...``,
    ``paged_kv.resolve_block_size``; ``paged_rows{tag}_block`` says which),
    the same tokens in a table a fraction as wide: the kernel pays for each
    block's copy, so the pair is what a copy costs (PERF.md §6, PR 33).
    Reports each path's largest error against the other
    (``paged_rows{tag}_{entries}_{size}_max_err``), the kernel's time a
    layer with 16 layers looped inside ONE program
    (``..._{size}_ms``: a call's dispatch, ~0.6 ms, would hide a kernel of
    0.1) and the gather's time a call (``paged_gather{tag}_{entries}_ms``,
    blocks of 16, the median of 20 after the first): a bring-up reading of
    one layer's attention, not the benchmark's."""
    looped = 2 if interpret else 16
    if interpret:
        dtype, rows, max_len, layers, lo, hi, chunk, pad, reps = (
            jnp.float32, 6, 128, 2, 20, 100, 21, 3, 1)
    elif long_rows:
        dtype, rows, max_len, layers, lo, hi, chunk, pad, reps = (
            jnp.bfloat16, 48, 4096, 2, 1024, 3840, 137, 8, 20)
    else:
        dtype, rows, max_len, layers, lo, hi, chunk, pad, reps = (
            jnp.bfloat16, 48, 1024, 2, 128, 900, 137, 8, 20)
    rng = np.random.default_rng(seed)
    ctx = rng.integers(lo, hi + 1, rows).astype(np.int32)
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    layer = jnp.asarray(layers - 1, jnp.int32)
    scale = 1.0 / 16.0
    name = ("paged_latent_attention_rows" if latent_rank
            else "paged_attention_rows")
    resolved = resolve_block_size(
        hkv * d * jnp.dtype(dtype).itemsize, max_len)

    def ms_a_call(fn, *args):
        jax.block_until_ready(fn(*args))
        times = []
        for _ in range(reps):
            t0 = time.monotonic()
            jax.block_until_ready(fn(*args))
            times.append(time.monotonic() - t0)
        return round(1e3 * float(np.median(times)), 4)

    narrow = (np.arange(rows, dtype=np.int32), ctx - 1)
    wide = (np.concatenate([np.arange(rows - 1), np.full(chunk, rows - 1),
                            np.zeros(pad)]).astype(np.int32),
            np.concatenate([ctx[:-1] - 1, ctx[-1] + np.arange(chunk),
                            np.zeros(pad)]).astype(np.int32))
    out = {f"paged_rows{tag}_block": resolved}
    # (a row whose block of 16 is large enough already is timed once)
    sizes = {"bs16": 16, "resolved": resolved}
    if resolved == 16:
        del sizes["resolved"]
    for size, bs in sizes.items():
        mb = max_len // bs
        nb = rows * mb + 4
        # the pool's payload leaves: k and v, or the one latent leaf
        leaves = tuple(jax.random.normal(k, (layers, nb, bs, hkv, d), dtype)
                       for k in ks[:1 if latent_rank else 2])
        tables = jnp.asarray(rng.permutation(nb)[:rows * mb].reshape(
            rows, mb).astype(np.int32))

        # the pool and the tables are arguments: closed over, they would
        # be constants of the program
        def gather(leaves, tables, q, seq_row, positions):
            tbl = tables[seq_row]
            valid = jnp.arange(mb * bs)[None, :] < positions[:, None] + 1
            if latent_rank:
                # _paged_mla_attend's gather path
                seq = leaves[0][layer, tbl].reshape(-1, mb * bs, d)
                scores = jnp.einsum(
                    "thc,tsc->ths", q, seq,
                    preferred_element_type=jnp.float32) * scale
                probs = jax.nn.softmax(
                    jnp.where(valid[:, None, :], scores, -1e30), axis=-1)
                return jnp.einsum(
                    "ths,tsc->thc", probs.astype(dtype), seq,
                    preferred_element_type=jnp.float32)[..., :latent_rank]
            k_seq, v_seq = (leaf[layer, tbl].reshape(-1, mb * bs, hkv, d)
                            for leaf in leaves)
            return attention(q[:, None], k_seq, v_seq, q_offset=positions,
                             kv_mask=valid, causal=True)[:, 0]

        def kernel(leaves, tables, q, seq_row, positions, trips=0):
            plan = plan_rows(seq_row, positions, block_size=bs,
                             table_width=mb, q_tile=query_tile(hq))

            def one(at):
                if latent_rank:
                    return paged_latent_attention_rows(
                        q, *leaves, at, tables, positions, plan,
                        scale=scale, value_dim=latent_rank,
                        interpret=interpret)
                return paged_attention_rows(q, *leaves, at, tables,
                                            positions, plan,
                                            interpret=interpret)

            if not trips:
                return one(layer)
            # the layers in turn, as the fused step's scan runs them
            return jax.lax.fori_loop(
                0, trips, lambda i, acc: acc + one(i % layers)[..., :1],
                jnp.zeros(q.shape[:2] + (1,), dtype))

        gather_jit, kernel_jit = jax.jit(gather), jax.jit(kernel)
        looped_jit = jax.jit(functools.partial(kernel, trips=looped))
        for seq_row, positions in (narrow, wide):
            t = len(seq_row)
            q = jax.random.normal(jax.random.fold_in(ks[2], t), (t, hq, d),
                                  dtype)
            args = (leaves, tables, q, jnp.asarray(seq_row),
                    jnp.asarray(positions))
            if not interpret:
                lowered_with_kernel(kernel_jit.lower(*args), name)
            err = max_err(kernel_jit(*args), gather_jit(*args))
            out[f"paged_rows{tag}_{t}_{size}_max_err"] = err
            check(err <= KERNEL_TOL,
                  f"{name}{tag} over {t} entries, blocks of {bs}, off by "
                  f"{err}")
            out[f"paged_rows{tag}_{t}_{size}_ms"] = round(
                ms_a_call(looped_jit, *args) / looped, 4)
            if size == "bs16":
                out[f"paged_gather{tag}_{t}_ms"] = ms_a_call(gather_jit,
                                                             *args)
    if resolved == 16:
        out.update({k.replace("_bs16_", "_resolved_"): v
                    for k, v in out.items() if "_bs16_" in k})
    return out


def fused_step_lowering(engine):
    """The engine's own jitted step, lowered with the engine's own state
    and flags (nothing is run or donated)."""
    n = engine.num_slots
    return engine_mod._paged_fused_step.lower(
        engine.params, engine.config, np.zeros((6, n), np.int32),
        np.zeros((n, 1), np.int32), engine.pool, jax.random.PRNGKey(0),
        engine._cur_tok_dev, engine.sample, engine._use_paged_kernel)


def four_chips(report: Report, sz: Sizes, config, train_config, seed: int,
               trajs, vocab: int) -> None:
    devs = jax.devices()[:4]
    own = [[d.id] for d in devs]

    def in_use():
        return report.memory()["bytes_in_use"][:4]

    with report.phase("fsdp4_train") as rec:
        mesh = make_mesh(MeshConfig(fsdp=4), devices=devs)
        # same key as the serving params: the trajectories stay on-policy
        state = make_train_state(train_config, jax.random.PRNGKey(seed),
                                 mesh, learning_rate=1e-5)
        jax.block_until_ready(state)
        big = [(x.shape, x.dtype, x.sharding) for x in
               jax.tree_util.tree_leaves((state.params, state.opt_state))
               if x.size >= 1 << 16]
        for shape, dtype, sharding in big:
            check(len(sharding.device_set) == 4
                  and not sharding.is_fully_replicated,
                  f"leaf {shape} {dtype} is not spread over four "
                  f"devices: {sharding}")
        rec["sharded_leaves"] = len(big)
        gc.collect()
        rec["bytes_in_use_after_init"] = used = in_use()
        if None not in used:
            check(max(used) <= 2 * min(used),
                  f"train state is not of one order across devices: {used}")
        batch = grpo_batch(trajs[:sz.fsdp_batch], sz, mesh, 1)
        n_groups = 1 + max(t.group_id for t in trajs)
        state, steps = take_steps(state, train_config, mesh, batch,
                                  n_groups, sz.train_steps, 1)
        rec.update(steps)
        del state, batch
        gc.collect()

    with report.phase("fleet4_serve") as rec:
        params = init_params(config, jax.random.PRNGKey(seed))
        engines = [RolloutEngine(jax.device_put(params, d), config,
                                 num_slots=sz.num_slots, max_len=sz.max_len,
                                 seed=seed + i)
                   for i, d in enumerate(devs)]
        del params

        def homes():
            return [sorted({dev.id for x in jax.tree_util.tree_leaves(
                (e.params, e.pool, e._key)) for dev in x.devices()})
                for e in engines]

        rec["replica_devices_at_init"] = at = homes()
        check(at == own, f"replica arrays are not each on their own chip: "
                         f"{at}")
        fleet = ServingFleet(engines)
        rng = np.random.default_rng(seed + 4)
        # short prompts: every replica compiles its own ladder (the
        # executable is per device), so keep the ladder short
        prompts = draw_prompts(rng, sz.fleet_requests, sz.prompt_lo,
                               sz.score_prompt_hi, vocab)
        before = ledger_of("engine.fused_step")
        tickets = [fleet.submit(p, max_new_tokens=sz.new_tokens)
                   for p in prompts]
        fleet.run()
        for t in tickets:
            toks, lps = fleet.result(t), fleet.result_logps(t)
            check(len(toks) == sz.new_tokens
                  and all(0 <= x < vocab for x in toks)
                  and all(math.isfinite(x) for x in lps),
                  f"fleet ticket {t}: bad result")
        rec["fused_step"] = ledger_delta("engine.fused_step", before)
        rec["replica_devices_after_run"] = at = homes()
        check(at == own, f"replica arrays moved: {at}")
        served = [e.stats()["tokens_emitted"] for e in engines]
        rec["tokens_by_replica"] = served
        check(all(n > 0 for n in served),
              f"a replica served nothing: {served}")
        rec["bytes_in_use_by_device"] = in_use()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal: tiny-test config, Pallas kernels "
                         "in interpret mode, prints platform cpu")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    sz = TINY if args.tiny else FULL

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.tiny:
        sys.exit(f"chip_smoke: JAX found platform {dev.platform!r}, not a "
                 f"TPU. The only CPU route is the explicit rehearsal: "
                 f"python chip_smoke.py --tiny")
    interpret = dev.platform != "tpu"

    cache_dir = enable_compile_cache()
    report = Report()
    config = get_config(sz.model)
    vocab = config.vocab_size
    rng = np.random.default_rng(args.seed)
    t_all = time.monotonic()

    with report.phase("init") as rec:
        params = jax.block_until_ready(
            init_params(config, jax.random.PRNGKey(args.seed)))
        rec["params"] = count_params(params)
        scorer = make_scorer(config, sz.seq_len, sz.score_rows)

    with report.phase("serve") as rec:
        engine = RolloutEngine(params, config, num_slots=sz.num_slots,
                               max_len=sz.max_len, seed=args.seed)
        check(engine.kv_layout == "paged"
              and engine.kv_layout_fallback is None,
              f"engine left the paged layout: {engine.kv_layout} "
              f"({engine.kv_layout_fallback})")
        check(sz.groups_per_wave * sz.group_size > sz.num_slots,
              "a wave must queue: more requests than slots")
        rows = []
        for wave in ("wave1", "wave2"):
            prompts = draw_prompts(rng, sz.groups_per_wave, sz.prompt_lo,
                                   sz.prompt_hi, vocab)
            got, rec[wave] = run_wave(engine, prompts, sz.group_size,
                                      sz.new_tokens, vocab)
            # group ids stay distinct across waves
            rows += [(g + len(rows) // sz.group_size, p, t, lps)
                     for g, p, t, lps in got]
            check(rec[wave]["decode_steps"] < rec[wave]["tokens_emitted"],
                  "no fused step served more than one row")
        trajs = trajectories(rows)

    with report.phase("agree") as rec:
        # On the chip the engine must choose the kernel by itself
        # (paged_kernel=None: an unquantized dense pool on a TPU is read
        # by paged_attention_rows, as in every engine here). The
        # rehearsal forces it, so the interpreted kernel rides the fused
        # step too.
        greedy = RolloutEngine(
            params, config, num_slots=sz.num_slots, max_len=sz.max_len,
            seed=args.seed, sample=SampleParams(temperature=0.0),
            engine_config=EngineConfig(
                paged_kernel=True if interpret else None))
        if not interpret:
            lowered_with_kernel(fused_step_lowering(greedy),
                                "paged_attention_rows")
        rec.update(greedy_agreement(
            greedy, scorer, params,
            draw_prompts(rng, sz.score_rows, sz.prompt_lo,
                         sz.score_prompt_hi, vocab), sz, vocab))
        del greedy

    # LoRA on one chip: full fine-tuning of 1.5B (params + float32
    # gradient accumulator + Adam moments, nothing donated) does not fit
    # 16 GB; frozen base + adapters is the repo's one-chip recipe, with
    # per-layer remat and microbatches for the 151,936-wide logits.
    train_config = dataclasses.replace(config, remat=True)
    with report.phase("train") as rec:
        state = make_lora_train_state(
            config, params, jax.random.PRNGKey(args.seed + 1),
            rank=sz.lora_rank)
        lora0 = state.params
        batch = grpo_batch(trajs, sz, None, sz.accum_steps)
        rec["batch"] = list(batch[0].shape)
        state, steps = take_steps(
            state, train_config, None, batch,
            1 + max(t.group_id for t in trajs), sz.train_steps,
            sz.accum_steps, lora_base=params)
        rec.update(steps)
        moved = max(max_err(a, b) for a, b in zip(
            jax.tree_util.tree_leaves(state.params),
            jax.tree_util.tree_leaves(lora0)))
        rec["adapter_max_change"] = moved
        check(moved > 0.0, "three updates left the adapters unchanged")
        del batch, lora0

    with report.phase("publish") as rec:
        served = materialize_lora(params, state.params, config)
        rec["wq_max_change"] = max_err(served["layers"]["wq"],
                                       params["layers"]["wq"])
        check(rec["wq_max_change"] > 0.0, "published weights equal the base")
        engine.update_params(served)
        prompts = draw_prompts(rng, sz.groups_per_wave, sz.prompt_lo,
                               sz.prompt_hi, vocab)
        got, rec["wave3"] = run_wave(engine, prompts, sz.group_size,
                                     sz.new_tokens, vocab)
        # the engine now answers from the PUBLISHED weights
        rec["agreement"] = agreement(scorer, served, got[:sz.score_rows],
                                     sz.logp_tol)
        del engine, state, served, params

    with report.phase("kernels") as rec:
        rec.update(kernel_checks(config, sz.kernel_seq, interpret,
                                 args.seed))

    if jax.device_count() >= 4:
        gc.collect()
        four_chips(report, sz, config, train_config, args.seed, trajs, vocab)

    print(json.dumps({"report": {
        "versions": {pkg: importlib.metadata.version(pkg)
                     for pkg in ("jax", "jaxlib", "libtpu")},
        "model": sz.model,
        "compile_cache_dir": cache_dir,
        "wall_s": round(time.monotonic() - t_all, 1),
        "phases": report.phases,
    }}), flush=True)
    # the result line: these keys and no others, last on stdout
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
    }), flush=True)


if __name__ == "__main__":
    main()
