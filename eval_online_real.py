"""OnlineImprovementLoop end to end on REAL weights (no scripted policy).

Round-3 review: weight-learning (LEARNING_r03) and
prompt-conditioning (LEARNING_CONTEXTUAL_*) each existed in isolation;
this eval runs them TOGETHER through ``training/online.py`` — the
reference's coupled cycle (``apoService.ts:435-472`` auto-analysis timer
feeding ``chatThreadService.ts:1172``'s agent loop) with the TPU build's
weight-update upgrade — against a real transformer only:

- The policy starts from the rule-following checkpoint the uplift eval
  pretrains (eval_uplift_real.py): an instruction-follower, the stand-in
  for the pretrained LLM the reference drives. Its unconditioned prior
  FAILS the task suite.
- Each round: real engine rollouts (multi-attempt conversations — a
  judge-failed output draws a user follow-up in the same trace, the
  reference's P4/P5 retry shape), symmetric outcome feedback recorded on
  every trace, a GRPO step on the episodes' real sampled tokens trained
  on the 9-dim reward head's finalReward, weight publish to the engine,
  then the APO tick: auto-analysis when the corpus gates open and beam
  search when goodRate is low.
- Expected dynamics (the artifact's claim): rounds before the beam fires
  are flat-low (the judge fails everything; group advantages are ~zero,
  so weights alone cannot move — the optimizers NEED each other); the
  beam-found rule conditions the policy onto the target class (step
  jump); subsequent GRPO rounds consolidate first-attempt success
  (mean attempts falls, reward_mean keeps rising toward 1.0).

    python eval_online_real.py [--rounds 12] [--ckpt /tmp/uplift_ckpt]

Prints ONE JSON line (the ONLINE_r04 artifact).
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import tempfile
import time
from typing import List, Optional, Sequence

from eval_uplift_real import (BankProposer, RULE_BANK, RETRY_FOLLOWUP,
                              RULE_HIGH, RULE_LOW, frac_low,
                              make_rule_scorer, minimal_sysmsg,
                              pretrain_rule_policy, probe_frac_low)

ONLINE_TASKS = ["write the status line", "emit the reply text",
                "produce the summary"]


def run_online_eval(*, rounds: int = 12, ckpt: Optional[str] = None,
                    seed: int = 0, group_size: int = 4,
                    max_attempts: int = 4, good_threshold: float = 0.75,
                    lr: float = 0.02, pretrain_rounds: int = 60,
                    shift_round: Optional[int] = None,
                    analyze_interval_ms: Optional[float] = None,
                    analyze_every: Optional[int] = None) -> dict:
    import jax

    from senweaver_ide_tpu.apo.local import make_local_apo
    from senweaver_ide_tpu.apo.types import APOConfig
    from senweaver_ide_tpu.models import get_config
    from senweaver_ide_tpu.models.tokenizer import ByteTokenizer
    from senweaver_ide_tpu.rollout import EnginePolicyClient, RolloutSession
    from senweaver_ide_tpu.training.grpo import GRPOConfig
    from senweaver_ide_tpu.training.online import OnlineImprovementLoop
    from senweaver_ide_tpu.traces.collector import TraceCollector

    t0 = time.monotonic()
    config = get_config("tiny-test")
    tok = ByteTokenizer()
    if ckpt and os.path.isdir(ckpt):
        from eval_uplift_real import load_policy
        state, engine, tok, config = load_policy(ckpt, seed=seed, lr=lr)
        pretrained = {"loaded_from": ckpt}
    else:
        # Explicit recipe kwargs (the proven 2-group x 16 regime) so a
        # default change upstream cannot silently alter this eval.
        state, engine, _tok, _cfg, curve = pretrain_rule_policy(
            rounds=pretrain_rounds, lr=lr, seed=seed, group_size=16,
            tasks_per_class=1)
        pretrained = {"rounds": pretrain_rounds, "curve_tail": curve[-5:]}

    # Target the class the instruction-follower does NOT emit unprompted:
    # the suite must fail until an optimizer moves something. A mutable
    # holder, not a bool: --shift-round flips the demanded class mid-run
    # (the task-shift that re-opens the APO gates — the reference's
    # analysis timer is RECURRING, apoService.ts:435-472, so one-shot
    # gate-opening was the r4 evidence gap).
    prior = probe_frac_low(engine, tok, [])
    target = {"low": prior < 0.5}

    workdir = tempfile.mkdtemp(prefix="online_real_")
    collector = TraceCollector()

    def agreement_of(session) -> float:
        ids = (session.client.call_log[-1][1]
               if session.client.call_log else [])
        f = frac_low(ids)
        return f if target["low"] else 1.0 - f

    # Judge with the episode's sampled tokens (2-arg feedback_fn form):
    # good = on-target output within 2 attempts — same contract as the
    # frozen uplift eval's scorer.
    episode_log: List[dict] = []

    def judge(trace, session) -> str:
        ok = agreement_of(session) >= good_threshold
        attempts = len(session.client.call_log)
        fb = "good" if ok and attempts <= 2 else "bad"
        episode_log.append({"ok": ok, "attempts": attempts, "fb": fb})
        return fb

    ws = itertools.count()

    class RetrySession(RolloutSession):
        """run_turn = a multi-attempt conversation: failed attempts draw
        user follow-ups inside ONE trace (P4/P5 retry shape)."""

        def run_turn(self, user_message: str):
            def follow_up(_res, _turn):
                if agreement_of(self) >= good_threshold:
                    return None
                return RETRY_FOLLOWUP
            return self.run_conversation(user_message,
                                         next_message=follow_up,
                                         max_turns=max_attempts)

    def make_session(*, rules: List[str], thread_id: str):
        client = EnginePolicyClient(engine, tok,
                                    default_max_new_tokens=16,
                                    record_calls=True, auto_prefix=True)
        return RetrySession(client, f"{workdir}/ws{next(ws)}",
                            thread_id=thread_id, collector=collector,
                            include_tool_definitions=False,
                            system_message_override=minimal_sysmsg(rules))

    # The APO half: bank-proposer optimizer + the real-rollout scorer
    # (memoize=False — the engine's weights move between beam passes;
    # target_low as a callable — the scorer must judge candidates
    # against the CURRENT demanded class after a task shift).
    apo_cfg = (APOConfig(beam_rounds=2)
               if analyze_interval_ms is None
               else APOConfig(beam_rounds=2,
                              auto_analyze_interval_ms=analyze_interval_ms))
    apo = make_local_apo(
        collector, BankProposer(RULE_BANK, seed=seed),
        config=apo_cfg,
        score_fn=make_rule_scorer(engine, tok, workdir,
                                  target_low=lambda: target["low"],
                                  good_threshold=good_threshold,
                                  max_attempts=max_attempts,
                                  memoize=False))

    loop = OnlineImprovementLoop(
        state, config, None, make_session, ONLINE_TASKS,
        apo=apo, collector=collector, engine=engine,
        group_size=group_size, pad_id=tok.pad_id, max_len=1024,
        grpo_config=GRPOConfig(kl_coef=0.02, entropy_coef=0.02),
        ppo_epochs=2, max_parallel=8, feedback_fn=judge, anchor_every=5,
        analyze_every=analyze_every)

    per_round: List[dict] = []
    shift_probes = None
    ep_per_round = len(ONLINE_TASKS) * group_size
    for r in range(rounds):
        if shift_round is not None and r == shift_round:
            # TASK SHIFT: the demanded byte class flips. The judge and
            # the beam scorer read the holder, so from this round on
            # the installed rules are WRONG for the task — good rate
            # collapses, the cumulative corpus good-rate decays below
            # the gradient threshold, and the gates re-open (beam #2
            # must install the opposite rule for reward to recover).
            target["low"] = not target["low"]
            shift_probes = {
                "frac_low_rule_low": round(
                    probe_frac_low(engine, tok, [RULE_LOW]), 4),
                "frac_low_rule_high": round(
                    probe_frac_low(engine, tok, [RULE_HIGH]), 4),
                "frac_low_no_rules": round(
                    probe_frac_low(engine, tok, []), 4),
            }
        res = loop.run_round()
        round_eps = episode_log[r * ep_per_round:(r + 1) * ep_per_round]
        per_round.append({
            "round": r,
            "target_class": "low" if target["low"] else "high",
            "reward_mean": round(res.reward_mean, 4),
            "rules_active": list(res.rules),
            "analyzed": res.analyzed,
            "beam_ran": res.beam_ran,
            "good_rate": round(sum(e["fb"] == "good" for e in round_eps)
                               / max(len(round_eps), 1), 3),
            "mean_attempts": round(sum(e["attempts"] for e in round_eps)
                                   / max(len(round_eps), 1), 2),
            "loss": res.train_metrics.get("loss"),
        })
        print(f"[online] {json.dumps(per_round[-1])}",
              file=sys.stderr, flush=True)

    curve = [p["reward_mean"] for p in per_round]
    first_beam = next((p["round"] for p in per_round if p["beam_ran"]),
                      None)
    post_beam = ([p for p in per_round
                  if first_beam is not None and p["round"] > first_beam]
                 or [])

    def w2(vals):
        """2-round window mean for the endpoint fields: dampens (does
        not eliminate) single-round noise, same posture as
        eval_learning's windows. The `improved` margin is +0.4 over
        round 0 — a solid post-beam jump — chosen WITH the window so a
        sustained-1.0 run ending on one ~0.85 round still passes."""
        tail = vals[-2:] if len(vals) >= 2 else vals
        return sum(tail) / max(len(tail), 1)
    final_no_rule_prior = probe_frac_low(engine, tok, [])
    beam_rounds_ran = [p["round"] for p in per_round if p["beam_ran"]]
    rule_sets = []
    for p in per_round:
        if not rule_sets or rule_sets[-1][1] != p["rules_active"]:
            rule_sets.append((p["round"], p["rules_active"]))
    post_shift = ([p for p in per_round if p["round"] >= shift_round]
                  if shift_round is not None else [])
    report = {
        "metric": "online_improvement_realpolicy",
        "rounds": rounds,
        "curve": curve,
        "per_round": per_round,
        "shift_round": shift_round,
        "shift_probes_frac_low": shift_probes,
        "beam_rounds_ran": beam_rounds_ran,
        "beam_invocations": len(beam_rounds_ran),
        "rules_timeline": [{"from_round": r, "rules": rs}
                           for r, rs in rule_sets],
        "rules_changed_after_shift": bool(
            shift_round is not None
            and any(r > shift_round for r, _ in rule_sets[1:])),
        "post_shift_recovered": bool(
            post_shift and len(post_shift) >= 3
            and w2([p["reward_mean"] for p in post_shift])
            > post_shift[0]["reward_mean"] + 0.4),
        "reward_initial": curve[0] if curve else None,
        "reward_final": round(w2(curve), 4) if curve else None,
        "first_beam_round": first_beam,
        "rules_final": per_round[-1]["rules_active"] if per_round else [],
        "improved": bool(curve and w2(curve) > curve[0] + 0.4),
        "weights_refined_post_beam": bool(
            len(post_beam) >= 3
            and w2([p["reward_mean"] for p in post_beam])
            > post_beam[0]["reward_mean"] + 1e-9),
        "prior_frac_low_initial": round(prior, 4),
        "prior_frac_low_final": round(final_no_rule_prior, 4),
        "target_class_initial": per_round[0]["target_class"]
        if per_round else None,
        "target_class_final": per_round[-1]["target_class"]
        if per_round else None,
        "pretrained": pretrained,
        "policy": "real transformer (tiny-test); no scripted policy "
                  "anywhere in the loop",
        "reward_source": "9-dim reward head finalReward (no override)",
        "config": {"group_size": group_size, "tasks": len(ONLINE_TASKS),
                   "max_attempts": max_attempts,
                   "good_threshold": good_threshold, "lr": lr,
                   "seed": seed, "shift_round": shift_round,
                   "analyze_interval_ms": analyze_interval_ms,
                   "analyze_every": analyze_every},
        "wall_s": round(time.monotonic() - t0, 1),
    }
    return report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=12)
    ap.add_argument("--ckpt", default="/tmp/uplift_ckpt",
                    help="rule-following checkpoint dir (missing → "
                         "pretrain from scratch)")
    ap.add_argument("--pretrain-rounds", type=int, default=60)
    ap.add_argument("--group-size", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shift-round", type=int, default=None,
                    help="flip the demanded byte class at this round "
                         "(task shift → APO gates re-open → beam #2)")
    ap.add_argument("--analyze-interval-ms", type=float, default=None,
                    help="override the 1h analysis interval — the "
                         "reference's timer is hourly-RECURRING; an "
                         "eval compressing hours into minutes scales "
                         "the interval with it")
    ap.add_argument("--analyze-every", type=int, default=None,
                    help="consult the APO gates every N rounds (round-"
                         "based translation of the recurring timer; "
                         "use with --analyze-interval-ms 0)")
    args = ap.parse_args()

    import jax
    jax.config.update("jax_platforms", "cpu")   # tiny-model CPU work
    from senweaver_ide_tpu.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    report = run_online_eval(rounds=args.rounds, ckpt=args.ckpt,
                             seed=args.seed, group_size=args.group_size,
                             pretrain_rounds=args.pretrain_rounds,
                             shift_round=args.shift_round,
                             analyze_interval_ms=args.analyze_interval_ms,
                             analyze_every=args.analyze_every)
    print(json.dumps(report))


if __name__ == "__main__":
    try:
        main()
    except Exception as e:   # always leave a JSON line for the driver
        print(json.dumps({"error": f"{type(e).__name__}: {e}"}))
        sys.exit(1)
