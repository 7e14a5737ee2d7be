"""6.7B feasibility: execute the QLoRA/int8 serving memory plan on CPU.

Round-3 review: the deepseek-coder-6.7b preset, QLoRA, int8 and
kv-quant paths all existed but nothing ever SIZED or RAN the 6.7B shape.
This eval executes the plan as far as a CPU host allows:

1. **Sizing table** (exact, from the config): weights (bf16/int8), LoRA
   adapters + AdamW moments (full-FT vs adapter-only), KV cache per
   4k-token slot (bf16 vs int8 kv_quant), against the 16 GB v5e HBM —
   the arithmetic behind BASELINE's "1.5B-7B ladder" claim.
2. **Layer-streamed int8 init**: the full 6.7B parameter set is built
   layer-by-layer in numpy (one layer's fp32 transient at a time — the
   loading posture a 16 GB host needs) directly into the
   ``models/quantize.py`` int8 format. Peak RSS is recorded.
3. **Real decode step**: a RolloutEngine serves the quantized 6.7B on
   CPU — prefill + a few decode tokens through the actual int8 matmul
   epilogue and int8 KV cache. Slow on one core, but it is the REAL
   serving path at the real shape (dtype plumbing, scale epilogues,
   cache layout all executed, not argued).
4. **Sharding validation**: every leaf of the (quantized and LoRA)
   6.7B tree resolves a PartitionSpec (parallel/sharding.py) and the
   fsdp=8 per-device byte split fits a v5e chip.

Device time for this shape: not measured (no benchmark cell has it).

    python eval_sevenb.py [--skip-decode]

Prints ONE JSON line (the SEVENB_r04 artifact).
"""

from __future__ import annotations

import argparse
import json
import resource
import sys
import time
from typing import Dict

GB = 1024 ** 3


def sizing_table(config, *, lora_rank: int = 16,
                 kv_slot_tokens: int = 4096) -> Dict:
    """Exact byte accounting for the 6.7B memory plan."""
    from senweaver_ide_tpu.models.quantize import dense_family_shapes

    c = config
    L, D, V = c.num_layers, c.hidden_size, c.vocab_size
    kv_dim = c.kv_dim
    shapes = dense_family_shapes(config)
    dense_in = {k: v[0] for k, v in shapes.items()}
    dense_out = {k: v[1] for k, v in shapes.items()}
    dense_params = sum(L * dense_in[k] * dense_out[k] for k in dense_out)
    norm_params = L * 2 * D + D
    embed_params = V * D
    head_params = 0 if c.tie_word_embeddings else D * V
    total_params = dense_params + norm_params + embed_params + head_params

    int8_dense = dense_params + 4 * sum(L * dense_out[k]
                                        for k in dense_out)   # +fp32 scales
    int8_head = (0 if c.tie_word_embeddings
                 else D * V + 4 * V)
    weights_int8 = int8_dense + int8_head + 2 * (norm_params + embed_params)
    weights_bf16 = 2 * total_params

    # LoRA rank-r on the seven dense families: A (in, r) + B (r, out).
    lora_params = sum(L * lora_rank * (dense_in[k] + dense_out[k])
                      for k in dense_out)
    # AdamW: fp32 m+v (+fp32 master is not kept; grads bf16 transient).
    moments_full = 8 * total_params
    moments_lora = 8 * lora_params

    kv_bytes_per_tok = L * 2 * kv_dim * 2                 # bf16 k+v
    kv_bytes_per_tok_q8 = L * 2 * (kv_dim + 4 * c.num_kv_heads)
    hbm = 16 * GB
    plans = {
        "full_ft_bf16": weights_bf16 + moments_full + 2 * total_params,
        "lora_bf16_base": weights_bf16 + 2 * lora_params + moments_lora,
        "qlora_int8_base": weights_int8 + 2 * lora_params + moments_lora,
        "serve_int8": weights_int8,
    }
    slot = kv_bytes_per_tok * kv_slot_tokens
    slot_q8 = kv_bytes_per_tok_q8 * kv_slot_tokens
    return {
        "params_total": total_params,
        "weights_bf16_gb": round(weights_bf16 / GB, 2),
        "weights_int8_gb": round(weights_int8 / GB, 2),
        "lora_params_r16": lora_params,
        "adamw_moments_full_gb": round(moments_full / GB, 2),
        "adamw_moments_lora_mb": round(moments_lora / GB * 1024, 1),
        "kv_per_4k_slot_bf16_mb": round(slot / GB * 1024, 1),
        "kv_per_4k_slot_int8_mb": round(slot_q8 / GB * 1024, 1),
        "plans_gb": {k: round(v / GB, 2) for k, v in plans.items()},
        "fits_16gb": {k: bool(v < hbm) for k, v in plans.items()},
        "decode_slots_at_4k": {
            "qlora_int8_base_int8kv": int(
                (hbm - plans["qlora_int8_base"]) // slot_q8),
            "serve_int8_int8kv": int((hbm - plans["serve_int8"]) // slot_q8),
            "full_ft_bf16": max(0, int(
                (hbm - plans["full_ft_bf16"]) // slot)),
        },
    }


def streamed_int8_init(config, seed: int = 0):
    """Full 6.7B int8 params, built layer-by-layer in numpy.

    Only ONE layer of ONE family is ever held in fp32 (~180 MB for
    w_gate), so peak memory ≈ the int8 result itself — the posture that
    loads 6.7B on a 16 GB host. Matches ``models/quantize.py`` exactly:
    int8 values + fp32 per-output-channel scales (absmax over the
    contraction axis), norms/embed kept bf16, tied-head shadow unused
    (deepseek-6.7b has an untied head, itself int8-quantized)."""
    import numpy as np

    import jax.numpy as jnp

    from senweaver_ide_tpu.models.quantize import dense_family_shapes

    c = config
    L, D, V = c.num_layers, c.hidden_size, c.vocab_size
    shapes = dense_family_shapes(config)
    rng = np.random.default_rng(seed)
    layers: Dict[str, object] = {}
    for name, (fan_in, out) in shapes.items():
        q = np.empty((L, fan_in, out), np.int8)
        scales = np.empty((L, out), np.float32)
        for li in range(L):
            w = rng.standard_normal((fan_in, out), dtype=np.float32)
            w *= 1.0 / fan_in ** 0.5
            absmax = np.maximum(np.abs(w).max(axis=0), 1e-8)
            s = absmax / 127.0
            np.clip(np.round(w / s[None, :]), -127, 127, out=w)
            q[li] = w.astype(np.int8)
            scales[li] = s
            del w
        layers[name] = jnp.asarray(q)
        layers[name + "_scale"] = jnp.asarray(scales)
        del q, scales
    layers["attn_norm"] = jnp.ones((L, D), c.dtype)
    layers["mlp_norm"] = jnp.ones((L, D), c.dtype)
    embed = rng.standard_normal((V, D), dtype=np.float32) * 0.02
    params = {"embed": jnp.asarray(embed, c.dtype),
              "layers": layers,
              "final_norm": jnp.ones((D,), c.dtype)}
    del embed
    head = rng.standard_normal((D, V), dtype=np.float32) / D ** 0.5
    absmax = np.maximum(np.abs(head).max(axis=0), 1e-8)
    s = absmax / 127.0
    params["lm_head"] = jnp.asarray(
        np.clip(np.round(head / s[None, :]), -127, 127).astype(np.int8))
    params["lm_head_scale"] = jnp.asarray(s)
    del head
    return params


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-decode", action="store_true",
                    help="sizing + init + sharding only (no CPU forward)")
    ap.add_argument("--decode-tokens", type=int, default=4)
    ap.add_argument("--engine-max-len", type=int, default=256)
    ap.add_argument("--update-step", action="store_true",
                    help="run ONE QLoRA GRPO update on the int8 6.7B "
                         "tree (round-4 review: feasibility stopped "
                         "short of a training step)")
    ap.add_argument("--update-seq", type=int, default=128,
                    help="token budget per update trajectory")
    args = ap.parse_args()

    import jax
    jax.config.update("jax_platforms", "cpu")
    from senweaver_ide_tpu.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    import dataclasses

    import jax.numpy as jnp

    from senweaver_ide_tpu.models import get_config
    from senweaver_ide_tpu.models.quantize import is_quantized
    from senweaver_ide_tpu.parallel.sharding import param_specs

    report: Dict = {"metric": "sevenb_feasibility",
                    "config": "deepseek-coder-6.7b"}
    config = get_config("deepseek-coder-6.7b")
    config = dataclasses.replace(config, kv_quant=True)
    report["sizing"] = sizing_table(config)

    t0 = time.monotonic()
    params = streamed_int8_init(config)
    report["int8_init"] = {
        "wall_s": round(time.monotonic() - t0, 1),
        "is_quantized": bool(is_quantized(params)),
        "bytes_gb": round(sum(
            x.size * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves(params)) / GB, 2),
        "peak_rss_gb": round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024**2, 2),
    }

    # Sharding: every leaf (int8 weights, fp32 scales, LoRA adapters)
    # resolves a spec; fsdp=8 split of the QLoRA plan fits one chip.
    from senweaver_ide_tpu.training.lora import init_lora
    lora = init_lora(config, jax.random.PRNGKey(1), rank=16)
    specs = param_specs(params)           # raises KeyError on any gap
    lora_specs = param_specs(lora)
    n_leaves = len(jax.tree_util.tree_leaves(specs)) + \
        len(jax.tree_util.tree_leaves(lora_specs))
    shard_bytes = sizing_table(config)["plans_gb"]["qlora_int8_base"]
    report["sharding"] = {
        "leaves_with_specs": n_leaves,
        "fsdp8_per_device_gb": round(shard_bytes / 8, 2),
        # int8 weights replicate scales/norms; call it ~weights/8 + slack
        "note": "param_specs resolved every quantized + LoRA leaf; "
                "fsdp=8 splits the 8.1 GB QLoRA plan to ~1 GB/chip "
                "before KV",
    }

    if not args.skip_decode:
        from senweaver_ide_tpu.models.tokenizer import ByteTokenizer
        from senweaver_ide_tpu.rollout import RolloutEngine

        tok = ByteTokenizer()
        t0 = time.monotonic()
        engine = RolloutEngine(params, config, num_slots=1,
                               max_len=args.engine_max_len, eos_id=None,
                               seed=0)
        rid = engine.submit(tok.encode("def main():", add_bos=True),
                            max_new_tokens=args.decode_tokens)
        while not engine.is_done(rid):
            engine.step()
        out = engine.result(rid)
        decode_wall = time.monotonic() - t0
        report["cpu_decode"] = {
            "tokens_out": len(out),
            "wall_s": round(decode_wall, 1),
            "engine_stats": {k: v for k, v in engine.stats().items()
                             if isinstance(v, (int, float))},
            "note": "real int8 serving path at the 6.7B shape (1 CPU "
                    "core; throughput is the chip queue's job)",
        }
        report["peak_rss_gb"] = round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024**2, 2)

    if args.update_step:
        # The QLoRA *update* at shape (round-5 review): adapters
        # train against the frozen int8 base through train_step's
        # lora_base path — the exact posture the 16 GB-chip plan
        # serves-and-trains with. Two same-group trajectories with a
        # low-byte outcome judge keep the group advantage
        # non-degenerate; loss + wall + RSS are the artifact.
        import numpy as np

        from senweaver_ide_tpu.models.tokenizer import ByteTokenizer
        from senweaver_ide_tpu.training.data import Trajectory, make_batch
        from senweaver_ide_tpu.training.grpo import GRPOConfig
        from senweaver_ide_tpu.training.trainer import (
            make_lora_train_state, train_step)

        tok = ByteTokenizer()
        t0 = time.monotonic()
        state = make_lora_train_state(config, params,
                                      jax.random.PRNGKey(2), rank=16,
                                      learning_rate=1e-4)
        state_wall = time.monotonic() - t0
        rng = np.random.default_rng(0)
        trajs = []
        prompt = tok.encode("def main():", add_bos=True)
        budget = max(args.update_seq - len(prompt) - 1, 8)
        # Contrastive BY CONSTRUCTION: one low-byte and one high-byte
        # completion → rewards +1/−1, so the group advantage (and the
        # gradient) cannot degenerate (two same-distribution random
        # draws can tie on the judge — observed: grad_norm exactly 0).
        for g, (lo, hi) in enumerate(((0, 128), (128, 256))):
            comp = rng.integers(lo, hi, size=budget).tolist()
            low = sum(1 for t in comp if t < 128) / len(comp)
            trajs.append(Trajectory(prompt_ids=list(prompt),
                                    completion_ids=comp,
                                    reward=2.0 * low - 1.0, group_id=0))
        tokens, mask, rewards, group_ids = make_batch(
            trajs, pad_id=tok.pad_id, max_len=args.update_seq)
        t0 = time.monotonic()
        state, metrics = train_step(
            state, config, None, jnp.asarray(tokens), jnp.asarray(mask),
            jnp.asarray(rewards), jnp.asarray(group_ids),
            grpo_config=GRPOConfig(), num_groups=1, lora_base=params)
        jax.block_until_ready(state.params)
        report["qlora_update"] = {
            "batch_shape": list(tokens.shape),
            "lora_state_wall_s": round(state_wall, 1),
            "step_wall_s": round(time.monotonic() - t0, 1),
            "includes_compile": True,
            "loss": round(float(metrics["loss"]), 6),
            "grad_norm": (round(float(metrics["grad_norm"]), 6)
                          if "grad_norm" in metrics else None),
            "peak_rss_gb": round(
                resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
                / 1024 ** 2, 2),
            "note": "adapters differentiate through the int8 dequant "
                    "epilogue (training/lora.py QLoRA path) at the real "
                    "6.7B shape",
        }
    print(json.dumps(report))


if __name__ == "__main__":
    try:
        main()
    except Exception as e:   # always leave a JSON line for the driver
        print(json.dumps({"error": f"{type(e).__name__}: {e}"}))
        sys.exit(1)
