"""Generative APO uplift: a real LM writes the candidate rules.

Round-4 review: the critique/apply-edit prompts existed
(``apo/gradient.py``, mirroring ``apoService.ts:992-1215``) but a
deterministic bank answered them — no artifact had a model *producing*
the edits. Here the optimizer role is a purpose-trained tiny byte-LM
(``apo/proposer.py``): the beam's critique and apply-edit calls both
return REAL sampled model text, ``parse_rules`` extracts the '- '
lines, and the scorer (real rollouts through the jit reward head)
selects. There is NO hand-built candidate bank anywhere in the loop,
and the proposer's training corpus holds out chosen (frame, subject)
compositions — sampling one is text the model composed, present in no
training document.

Pipeline:
  1. frozen rule-following policy (load the uplift checkpoint or
     GRPO-pretrain with retries — same recipe as eval_uplift_real)
  2. train the proposer LM (causal cross-entropy on the compositional
     corpus; holdout includes (0,0) = the exact steering sentence)
  3. proposer diagnostics: N direct samples → well-formed / novel /
     train-corpus rates (published; if nothing parses, the artifact
     says so instead of a vacuous beam)
  4. full APO cycle (run_real_uplift) with the LMProposer in the
     optimizer seat; generation audit from its log

    python eval_uplift_generative.py [--load-dir /tmp/uplift_ckpt]

Prints ONE JSON line (the UPLIFT_GENERATIVE_r05 artifact).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from eval_uplift_real import (DEFAULT_MAX_ATTEMPTS, RULE_LOW, RULE_HIGH,
                              pretrain_with_retries, run_real_uplift)


def proposer_diagnostics(proposer, corpus, n: int = 24) -> dict:
    samples = proposer.sample_rules(n)
    flat = [r for s in samples for r in s]
    train = set(corpus.train_sentences)
    holdout = set(corpus.holdout_sentences)
    return {
        "samples": n,
        "parsed_rule_lines": len(flat),
        "well_formed_rate": round(sum(1 for s in samples if s) / n, 3),
        "train_corpus_rate": round(
            sum(1 for r in flat if r in train) / max(len(flat), 1), 3),
        "novel_composition_rate": round(
            sum(1 for r in flat if r in holdout) / max(len(flat), 1), 3),
        "free_text_rate": round(
            sum(1 for r in flat if r not in train and r not in holdout)
            / max(len(flat), 1), 3),
        "distinct_rules": len(set(flat)),
        "example_samples": [s for s in samples[:6]],
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=80)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--beam-rounds", type=int, default=4)
    ap.add_argument("--proposer-steps", type=int, default=600)
    ap.add_argument("--proposer-temperature", type=float, default=0.9)
    ap.add_argument("--max-attempts", type=int, default=DEFAULT_MAX_ATTEMPTS)
    ap.add_argument("--load-dir", default=None,
                    help="frozen-policy checkpoint (skip pretraining)")
    ap.add_argument("--pretrain-attempts", type=int, default=3)
    args = ap.parse_args()

    import jax
    jax.config.update("jax_platforms", "cpu")   # CPU-sized models
    from senweaver_ide_tpu.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    from senweaver_ide_tpu.apo.proposer import (LMProposer, ProposerCorpus,
                                                train_rule_proposer)

    t0 = time.monotonic()
    # ---- frozen policy --------------------------------------------------
    if args.load_dir:
        from eval_uplift_real import load_policy
        state, engine, tok, _config = load_policy(args.load_dir,
                                                  seed=args.seed)
        pretrain_info = {"loaded_from": args.load_dir}
    else:
        state, engine, tok, _cfg, curve, seed_used, tried = \
            pretrain_with_retries(max_attempts=args.pretrain_attempts,
                                  seed=args.seed, seed_stride=7,
                                  rounds=args.rounds, group_size=16)
        pretrain_info = {"rounds_run": len(curve), "seed_used": seed_used,
                         "attempts": tried, "curve_tail": curve[-4:]}
    pretrain_wall = time.monotonic() - t0

    # ---- proposer LM ----------------------------------------------------
    t1 = time.monotonic()
    # Holdout (0,0): "Respond using plain ascii text only." — the exact
    # steering sentence is ABSENT from proposer training; emitting it is
    # compositional generalization (frame 0 and subject 0 each appear in
    # training, never together).
    holdout_pairs = ((0, 0),)
    p_params, p_cfg, p_tok, corpus, p_curve = train_rule_proposer(
        steps=args.proposer_steps, seed=args.seed,
        holdout_pairs=holdout_pairs)
    proposer = LMProposer(p_params, p_cfg, p_tok, corpus,
                          temperature=args.proposer_temperature,
                          seed=args.seed)
    diag = proposer_diagnostics(proposer, corpus)
    proposer_wall = time.monotonic() - t1
    print(f"[generative] proposer diag {json.dumps(diag)}",
          file=sys.stderr, flush=True)

    # ---- APO cycle with the LM in the optimizer seat --------------------
    report = run_real_uplift(engine, tok, beam_rounds=args.beam_rounds,
                             proposer_seed=args.seed,
                             max_attempts=args.max_attempts,
                             proposer=proposer)

    # Generation audit: every apply-edit response the beam consumed.
    gen_log = proposer.generation_log
    all_gen_rules = [r for g in gen_log for r in g["rules"]]
    winner = report.get("optimized_rules", [])
    train_set = set(corpus.train_sentences)
    holdout_set = set(corpus.holdout_sentences)
    report.update({
        "metric": "uplift_generative",
        "optimizer": "trained byte-LM proposer (apo/proposer.py); no "
                     "candidate bank anywhere",
        "proposer": {
            "steps": args.proposer_steps,
            "loss_curve": p_curve,
            "temperature": args.proposer_temperature,
            "holdout_sentences": sorted(holdout_set),
            "diagnostics": diag,
            "train_wall_s": round(proposer_wall, 1),
        },
        "generation_audit": {
            "apply_edit_calls": len(gen_log),
            "rules_generated": len(all_gen_rules),
            "distinct_rules_generated": len(set(all_gen_rules)),
            "novel_compositions_generated": sorted(
                {r for r in all_gen_rules if r in holdout_set}),
            "free_text_generated": sorted(
                {r for r in all_gen_rules
                 if r not in holdout_set and r not in train_set})[:10],
        },
        "winner_audit": {
            "rules": winner,
            "novel_composition": [r in holdout_set for r in winner],
            "in_proposer_train_corpus": [r in train_set for r in winner],
            "is_trained_steering_sentence": [r in (RULE_LOW, RULE_HIGH)
                                             for r in winner],
        },
        "pretrain": {**pretrain_info,
                     "wall_s": round(pretrain_wall, 1)},
        "total_wall_s": round(time.monotonic() - t0, 1),
    })
    print(json.dumps(report))


if __name__ == "__main__":
    try:
        main()
    except Exception as e:   # always leave a JSON line for the driver
        print(json.dumps({"error": f"{type(e).__name__}: {e}"}))
        sys.exit(1)
