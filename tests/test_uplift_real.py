"""Real-policy APO uplift harness (eval_uplift_real.py / round-3 review).

Unit coverage for the pieces (multi-turn single-trace conversations, the
bank proposer, prompt rendering) plus a shrunken end-to-end cycle on a
REAL (random-init) engine — asserting plumbing and report structure, not
the ≥2× headline (that is UPLIFT_REALPOLICY_r04.json's job, produced by
the full pretrained run)."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from eval_uplift_real import (BankProposer, DECOY_RULE, RULE_BANK, RULE_LOW,
                              frac_low, make_rule_scorer, minimal_sysmsg,
                              run_real_uplift)
from senweaver_ide_tpu.agents.llm import ChatMessage, LLMResponse, LLMUsage
from senweaver_ide_tpu.apo.gradient import (build_apply_edit_prompt,
                                            build_textual_gradient_prompt,
                                            parse_rules)
from senweaver_ide_tpu.rollout.session import RolloutSession


class EchoClient:
    """Minimal PolicyClient: fixed text, no tools."""

    def __init__(self, text="ok then"):
        self.text = text
        self.calls = 0

    def chat(self, messages, *, temperature=None, max_tokens=None,
             on_text=None):
        self.calls += 1
        return LLMResponse(text=self.text, usage=LLMUsage(10, 5),
                           model="echo")


def test_run_conversation_keeps_one_trace(tmp_path):
    """Follow-up turns land in the SAME trace — the P4/P5 retry shapes
    (apoService.ts:712-750) count llm calls / user messages per trace."""
    sess = RolloutSession(EchoClient(), str(tmp_path / "ws"),
                          include_tool_definitions=False,
                          system_message_override="sys")
    try:
        turns = []

        def follow_up(res, turn):
            turns.append(turn)
            return "again" if turn < 2 else None

        out = sess.run_conversation("first", next_message=follow_up,
                                    max_turns=5)
        assert out.trace is not None
        s = out.trace.summary
        assert s.total_llm_calls == 3           # first + 2 follow-ups
        assert out.trace.user_message_count == 3   # all in ONE trace
        assert len(sess.collector.get_all_traces()) == 1
        # history carries the whole conversation for the next turn
        roles = [m.role for m in sess.history]
        assert roles == ["user", "assistant"] * 3
    finally:
        sess.close()


def test_run_turn_unchanged_single_turn(tmp_path):
    sess = RolloutSession(EchoClient(), str(tmp_path / "ws"),
                          include_tool_definitions=False,
                          system_message_override="sys")
    try:
        out = sess.run_turn("hello")
        assert out.trace.summary.total_llm_calls == 1
        assert out.trace.user_message_count == 1
    finally:
        sess.close()


def test_bank_proposer_distinguishes_prompt_kinds():
    p = BankProposer(RULE_BANK, seed=3)
    grad = build_textual_gradient_prompt([""], [])
    edit = build_apply_edit_prompt([""], "some critique")
    critique = p.chat([ChatMessage("user", grad)]).text
    assert "rule" in critique.lower()
    rules = parse_rules(p.chat([ChatMessage("user", edit)]).text)
    assert rules and all(r in RULE_BANK for r in rules)
    # seeded determinism
    p2 = BankProposer(RULE_BANK, seed=3)
    p2.chat([ChatMessage("user", grad)])
    assert parse_rules(p2.chat([ChatMessage("user", edit)]).text) == rules


def test_minimal_sysmsg_renders_apo_section():
    assert "# APO Optimized Rules" not in minimal_sysmsg([])
    msg = minimal_sysmsg([RULE_LOW])
    assert msg.startswith("You are a byte emitter.")
    assert f"- {RULE_LOW}" in msg


def test_frac_low_ignores_specials():
    assert frac_low([65, 66, 200, 256, 258]) == pytest.approx(2 / 3)
    assert frac_low([]) == 0.0


@pytest.fixture(scope="module")
def tiny_engine():
    import jax

    from senweaver_ide_tpu.models import get_config, init_params
    from senweaver_ide_tpu.models.tokenizer import ByteTokenizer
    from senweaver_ide_tpu.rollout import RolloutEngine

    config = get_config("tiny-test")
    params = init_params(config, jax.random.PRNGKey(0))
    engine = RolloutEngine(params, config, num_slots=8, max_len=2048,
                           eos_id=None, seed=0)
    return engine, ByteTokenizer()


def test_rule_scorer_scores_and_logs(tiny_engine, tmp_path):
    engine, tok = tiny_engine
    log = []
    score = make_rule_scorer(engine, tok, str(tmp_path),
                             target_low=True, eval_tasks=("emit bytes",),
                             max_attempts=2, score_log=log)
    s1 = score([DECOY_RULE])
    assert -1.0 <= s1 <= 1.0
    assert log[0]["rules"] == [DECOY_RULE]
    assert 1.0 <= log[0]["mean_attempts"] <= 2.0
    # memoized: same rules → cached score, no new log entry
    assert score([DECOY_RULE]) == s1
    assert len(log) == 1


def test_full_cycle_structure_random_policy(tiny_engine, tmp_path):
    """Shrunken APO cycle on a random-init REAL policy: the report must
    carry probes, baseline/optimized scores, per-round bests, and a
    score log — structure only (a random policy need not show uplift)."""
    engine, tok = tiny_engine
    report = run_real_uplift(engine, tok, beam_rounds=1,
                             eval_tasks=("emit bytes", "write data"),
                             max_attempts=2, probe_episodes=2)
    for key in ("probes_frac_low", "conditioning_delta", "target_class",
                "baseline_final_reward", "optimized_final_reward",
                "uplift_ratio_shifted", "beam_round_best_scores",
                "optimized_rules", "score_log"):
        assert key in report, key
    assert report["target_class"] in ("low", "high")
    assert len(report["beam_round_best_scores"]) == 1
    assert report["candidates_scored"] >= 1
    # every scored candidate came from the bank (plus the empty seed)
    for entry in report["score_log"]:
        assert all(r in RULE_BANK or r == "" for r in entry["rules"])
