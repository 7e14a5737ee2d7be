"""One REAL GRPO round at the 1.5B flagship shape, executed on CPU.

Round-4 review: "no training step has ever executed at
1.5B shapes anywhere" — the flagship-scale train path was extrapolation.
This eval executes it end to end at the ``qwen2.5-coder-1.5b`` config
(BASELINE.json config 4): real RolloutEngine sampling at shape → GRPO
trajectories → ``train_step`` (the same jit step the tiny evals and the
chip MFU bench use) → a SECOND step so the loss can move. Wall-time per
phase, peak RSS, and losses are recorded; throughput/MFU on silicon
stays the chip's job (a training cell of ``benchmark/``) — this artifact
proves the path is executed code at the real shape, with real memory.

Modes:
  --mode full   : full-precision full-FT step (fits the 125 GB host)
  --mode qlora  : int8-quantized base + LoRA adapters (the 16 GB-chip
                  training posture: train_step(lora_base=int8_base))

    python eval_onepointfiveb.py --mode full

Prints ONE JSON line (the ONEPOINTFIVEB_r05 artifact).
"""

from __future__ import annotations

import argparse
import json
import resource
import sys
import time

GB = 1024 ** 3


def rss_gb() -> float:
    return round(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
                 / 1024 ** 2, 2)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("full", "qlora"), default="full")
    ap.add_argument("--model", default="qwen2.5-coder-1.5b")
    ap.add_argument("--group-size", type=int, default=2)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--steps", type=int, default=2,
                    help="train steps on the collected batch (>=2 shows "
                         "the loss moving)")
    ap.add_argument("--lora-rank", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    jax.config.update("jax_platforms", "cpu")
    from senweaver_ide_tpu.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    import jax.numpy as jnp

    from senweaver_ide_tpu.models import get_config
    from senweaver_ide_tpu.models.tokenizer import ByteTokenizer
    from senweaver_ide_tpu.models.transformer import count_params, init_params
    from senweaver_ide_tpu.rollout import RolloutEngine
    from senweaver_ide_tpu.training.data import Trajectory, make_batch
    from senweaver_ide_tpu.training.grpo import GRPOConfig
    from senweaver_ide_tpu.training.trainer import (make_lora_train_state,
                                                    make_train_state,
                                                    train_step)

    report = {"metric": f"grpo_round_at_shape[{args.model}]",
              "mode": args.mode, "phases": {}}
    config = get_config(args.model)
    tok = ByteTokenizer()
    t_all = time.monotonic()

    # ---- params at shape -------------------------------------------------
    t0 = time.monotonic()
    params = init_params(config, jax.random.PRNGKey(args.seed))
    n_params = count_params(params)
    report["params_b"] = round(n_params / 1e9, 3)
    report["phases"]["init"] = {"wall_s": round(time.monotonic() - t0, 1),
                                "rss_gb": rss_gb()}

    serve_params = params
    lora_base = None
    if args.mode == "qlora":
        from senweaver_ide_tpu.models.quantize import quantize_weights_int8
        t0 = time.monotonic()
        lora_base = quantize_weights_int8(params)
        del params            # the fp32 tree is not part of this posture
        serve_params = lora_base
        report["phases"]["quantize"] = {
            "wall_s": round(time.monotonic() - t0, 1), "rss_gb": rss_gb()}
        state = make_lora_train_state(config, lora_base,
                                      jax.random.PRNGKey(args.seed + 1),
                                      rank=args.lora_rank,
                                      learning_rate=1e-4)
    else:
        state = make_train_state(config, jax.random.PRNGKey(args.seed),
                                 None, learning_rate=1e-5, params=params)
    report["phases"]["train_state"] = {"rss_gb": rss_gb()}

    # ---- real engine rollouts at shape ----------------------------------
    t0 = time.monotonic()
    engine = RolloutEngine(serve_params, config, num_slots=4, max_len=256,
                           eos_id=None, seed=args.seed)
    tasks = ["write the log line", "emit the payload"]
    rids = []
    for ti, task in enumerate(tasks):
        prompt = tok.encode(f"User: {task}\nAssistant:", add_bos=True)
        for g in range(args.group_size):
            rids.append((ti, engine.submit(
                prompt, max_new_tokens=args.max_new_tokens)))
    engine.run()
    trajs = []
    for ti, rid in rids:
        out = engine.result(rid)
        prompt = tok.encode(f"User: {tasks[ti]}\nAssistant:", add_bos=True)
        # Outcome judge at shape: token-id parity — exactly half of ANY
        # vocab qualifies, so a random-init policy's samples vary and
        # group advantages are non-degenerate (a byte-class judge
        # collapses on a 151k-entry vocab: every reward -1, advantage 0,
        # loss identically 0 — observed on the first 1.5B run).
        even = sum(1 for t in out if t % 2 == 0) / max(len(out), 1)
        trajs.append(Trajectory(prompt_ids=prompt, completion_ids=out,
                                reward=2.0 * even - 1.0, group_id=ti))
    report["phases"]["rollout"] = {
        "wall_s": round(time.monotonic() - t0, 1),
        "episodes": len(trajs),
        "tokens_sampled": sum(len(t.completion_ids) for t in trajs),
        "rewards": [round(t.reward, 3) for t in trajs],
        "rss_gb": rss_gb(),
        "engine_stats": {k: v for k, v in engine.stats().items()},
    }
    del engine

    # ---- the GRPO update(s) ---------------------------------------------
    tokens, mask, rewards, group_ids = make_batch(
        trajs, pad_id=tok.pad_id, max_len=256)
    # NB: with no recorded behavior logps, each step's surrogate sits at
    # ratio 1 where mean group advantage is 0 by construction — the
    # LOSS value is ~0 regardless of signal. grad_norm is the honest
    # per-step evidence that the update carries gradient.
    losses, grad_norms, step_walls = [], [], []
    for s in range(args.steps):
        t0 = time.monotonic()
        state, metrics = train_step(
            state, config, None, jnp.asarray(tokens),
            jnp.asarray(mask), jnp.asarray(rewards),
            jnp.asarray(group_ids), grpo_config=GRPOConfig(),
            num_groups=len(tasks), lora_base=lora_base)
        losses.append(round(float(metrics["loss"]), 6))
        grad_norms.append(round(float(metrics["grad_norm"]), 6))
        step_walls.append(round(time.monotonic() - t0, 1))
    report["phases"]["train"] = {
        "batch_shape": list(tokens.shape),
        "step_walls_s": step_walls,
        "first_step_includes_compile": True,
        "losses": losses,
        "grad_norms": grad_norms,
        "update_signal": bool(grad_norms and
                              all(g > 0 for g in grad_norms)),
        "rss_gb": rss_gb(),
    }
    report["peak_rss_gb"] = rss_gb()
    report["total_wall_s"] = round(time.monotonic() - t_all, 1)
    report["config"] = {"group_size": args.group_size,
                        "max_new_tokens": args.max_new_tokens,
                        "steps": args.steps, "mode": args.mode,
                        "lora_rank": (args.lora_rank
                                      if args.mode == "qlora" else None),
                        "seed": args.seed}
    print(json.dumps(report))


if __name__ == "__main__":
    try:
        main()
    except Exception as e:   # always leave a JSON line for the driver
        print(json.dumps({"error": f"{type(e).__name__}: {e}"}))
        sys.exit(1)
