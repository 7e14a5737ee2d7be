"""Prompt-conditioned candidate scoring + the north-star uplift eval.

The round-1 review's core APO gap: beam candidates were scored by a
prompt-INDEPENDENT corpus baseline, so the search could never rank them.
This module supplies the real scorer the reference keeps on its backend
(``POST /api/apo/optimize`` scores candidates against rollouts,
``apoService.ts:1102-1215``): each candidate rule-set is rendered into the
system prompt of fresh RolloutSessions, the eval task suite is re-rolled
under it, and the traces are batch-scored by the jit reward head
(mean finalReward = the candidate's score).

Two policy backends drive the same harness:
- the REAL policy via ``rollout.EnginePolicyClient`` (weights loaded with
  ``models/load.py``) — the north-star configuration;
- :class:`RuleSensitivePolicy`, a deterministic scripted stand-in for
  hermetic tests and the offline ``eval_uplift.py`` script (this
  environment has no pretrained weights on disk and zero egress). It
  misbehaves exactly like the 6 problem patterns unless the injected APO
  rules demand careful tool use — giving the eval a ground-truth "better
  prompt exists" structure without any network or checkpoint.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence

from ..agents.llm import ChatMessage, LLMResponse, LLMUsage, ToolCallRequest
from ..rewards.head import reward_head_batch
from ..traces.features import batch_features
from ..traces.schema import Trace

# An evaluation task per problem pattern (apoService.ts:643-770): the
# prompts nudge a real policy toward the failure the pattern describes;
# the scripted policy reproduces it deterministically.
SIX_PATTERN_TASKS: List[str] = [
    "Fix the crash in app.py (pattern: errors)",                     # P1
    "Run the build and report failures (pattern: tool failures)",    # P2
    "Summarize every file in the workspace (pattern: token blowup)", # P3
    "Refactor app.py; retry until it works (pattern: retries)",      # P4
    "Here is my fourth follow-up: still broken (pattern: churn)",    # P5
    "Search the web for the API docs (pattern: slow tools)",         # P6
]

# The behavior contract between rules and the scripted policy, GRADED
# over two rule classes (the 6 problem patterns split the same way:
# failure-type patterns P1/P2 respond to VERIFICATION, waste-type
# P3-P6 to EFFICIENCY): a verification rule alone fixes the failures
# but leaves churn; an efficiency rule alone trims calls but leaves
# them unverified; only BOTH yield fully careful behavior. A real
# policy has the same structure statistically; the markers make it
# exact for tests — and graded, so beam search must COMPOSE the right
# pair, not merely hit any one marker (round-3 review).
VERIFY_MARKERS = ("verify", "read the file before")
EFFICIENCY_MARKERS = ("minimal tool", "minimum number of tool calls",
                      "never retry")
CAREFUL_MARKERS = VERIFY_MARKERS + EFFICIENCY_MARKERS

GOOD_RULESET = [
    "Verify inputs and read the target file before any other tool call.",
    "Use the minimum number of tool calls needed; never retry blindly.",
]

# Hold-out proposal bank (round-3 review): rule phrasings the
# OPTIMIZER can propose, of which only SOME satisfy the policy's behavior
# contract (CAREFUL_MARKERS) — and nothing in the proposer encodes which.
# With this bank the beam must discover the steering subset by scored
# search instead of being handed GOOD_RULESET in one shot; near-miss
# paraphrases ("check your work", "act deliberately") read equally
# plausible to a human but do NOT match the contract, exactly like rules
# a real policy happens not to respond to.
HOLDOUT_RULE_BANK = [
    GOOD_RULESET[0],                                        # steers
    GOOD_RULESET[1],                                        # steers
    "Always verify inputs before taking any action.",       # steers
    "Re-read the task description before editing.",         # near-miss
    "Check your work carefully at every step.",             # near-miss
    "Act deliberately; avoid unnecessary repetition.",      # near-miss
    "Plan before acting and summarize after.",              # decoy
    "Prefer small, reviewable changes.",                    # decoy
    "Keep responses short and direct.",                     # decoy
    "Escalate to the user when uncertain.",                 # decoy
]


def evaluate_rules(
    rules: Sequence[str],
    make_session: Callable[[Sequence[str]], "RolloutSession"],
    tasks: Sequence[str] = tuple(SIX_PATTERN_TASKS),
    *,
    feedback_fn: Optional[Callable[[int, object], Optional[str]]] = None,
) -> float:
    """Mean finalReward of ``tasks`` re-rolled under ``rules``.

    ``make_session(rules)`` must return a FRESH session (own workspace +
    collector) whose system prompt injects the rules (RolloutSession
    ``apo_rules=``). ``feedback_fn(task_idx, turn_result)`` may return
    'good'/'bad' to add the top-weight feedback dim (evaluator-in-the-loop).
    Scoring is one vmapped reward-head pass over all collected traces.
    """
    traces: List[Trace] = []
    for i, task in enumerate(tasks):
        session = make_session(list(rules))
        try:
            out = session.run_turn(task)
            if feedback_fn is not None:
                fb = feedback_fn(i, out)
                if fb:
                    session.record_feedback(fb)
            trace = (session.collector.get_trace(out.trace.id)
                     if out.trace is not None else None)
            if trace is not None:
                traces.append(trace)
        finally:
            session.close()
    if not traces:
        return 0.0
    import jax.numpy as jnp

    feats = jnp.asarray(batch_features(traces))
    return float(jnp.mean(reward_head_batch(feats).final_reward))


def make_rollout_score_fn(
    make_session: Callable[[Sequence[str]], "RolloutSession"],
    tasks: Sequence[str] = tuple(SIX_PATTERN_TASKS),
    *,
    feedback_fn=None,
) -> Callable[[Sequence[str]], float]:
    """The default prompt-conditioned ScoreFn for ``make_local_apo``."""
    def score(rules: Sequence[str]) -> float:
        return evaluate_rules(rules, make_session, tasks,
                              feedback_fn=feedback_fn)
    return score


def task_pattern(messages: Sequence[ChatMessage]) -> str:
    """Extract the '(pattern: X)' tag from the episode's user message.

    The 6-pattern task suite tags each task with the failure mode it
    probes (apoService.ts:643-770's problem taxonomy); the scripted
    policy keys its sloppy behavior off the tag so every pattern
    produces ITS OWN failure signature instead of one generic shape."""
    for m in messages:
        if m.role == "user" and "(pattern: " in m.content:
            return m.content.rsplit("(pattern: ", 1)[1].split(")")[0]
    return ""


@dataclasses.dataclass
class RuleSensitivePolicy:
    """Deterministic scripted PolicyClient for the hermetic APO eval.

    Agent-loop calls (a system message is present): reads the
    '# APO Optimized Rules' section; with a careful rule-set it performs
    one successful read of ``good_file`` then answers. Without, it
    reproduces the task's tagged problem pattern with the SEVERITY the
    reference's reward thresholds define for agent mode
    (traceCollectorService.ts:701-762 — fail severe≥5, call count
    fair>25, tokens poor>30k, LLM-call threshold 3):

    - errors        → 2 failed reads, then the stream crashes (the agent
                      loop exhausts retries → record_error → hasErrors)
    - tool failures → 5 failed tool calls (severe band)
    - token blowup  → 3 calls at 16k tokens each (>30k total)
    - retries       → 26 blind retries of the same failing read (>25)
    - churn         → 9 successful re-reads of the same file (pure
                      repetition: llm_calls ≫ threshold 3, call count
                      past the agent 'excellent' band — no failures)
    - slow tools    → 5 failed external lookups
    - (untagged)    → the generic ``sloppy_calls`` failing-read shape

    Optimizer calls (no system message): recognizes the textual-gradient
    and apply-edit prompt shapes (apo/gradient.py) and returns a critique /
    the improved rule-set — the scripted counterpart of the reference's
    backend optimizer LLM.
    """
    good_file: str = "app.py"
    sloppy_calls: int = 3
    improved_rules: Sequence[str] = tuple(GOOD_RULESET)
    # Hold-out mode: apply-edit calls SAMPLE 2-rule subsets from this
    # bank (seeded) instead of returning improved_rules outright — the
    # optimizer no longer knows the answer, so the beam has to find the
    # steering subset by scoring (round-3 review).
    proposal_bank: Optional[Sequence[str]] = None
    proposal_seed: int = 0

    def __post_init__(self):
        import random
        self._rng = random.Random(self.proposal_seed)

    def chat(self, messages: List[ChatMessage], *, temperature=None,
             max_tokens=None, on_text=None) -> LLMResponse:
        sysmsg = messages[0] if messages and messages[0].role == "system" \
            else None
        if sysmsg is None:
            return self._optimizer_call(messages[-1].content if messages
                                        else "")
        rules_text = self._apo_rules_text(sysmsg.content).lower()
        has_verify = any(m in rules_text for m in VERIFY_MARKERS)
        has_eff = any(m in rules_text for m in EFFICIENCY_MARKERS)
        tool_msgs = sum(1 for m in messages if m.role == "tool")
        if has_verify and has_eff:         # fully careful: 1 good read
            if tool_msgs == 0:
                return LLMResponse(
                    text="Checking the file first.",
                    tool_call=ToolCallRequest("read_file",
                                              {"uri": self.good_file}),
                    usage=LLMUsage(300, 40), model="scripted")
            return LLMResponse(text="Done: verified and fixed.",
                               usage=LLMUsage(300, 40), model="scripted")
        if has_verify:                     # verified but churny: no
            if tool_msgs < 4:              # failures, 4 re-reads → the
                return LLMResponse(        # efficiency dims still drag
                    text="Verifying the file again.",
                    tool_call=ToolCallRequest("read_file",
                                              {"uri": self.good_file}),
                    usage=LLMUsage(600, 80), model="scripted")
            return LLMResponse(text="Done after double-checking.",
                               usage=LLMUsage(600, 80), model="scripted")
        if has_eff:                        # minimal but unverified: one
            if tool_msgs == 0:             # failed read, then answers —
                return LLMResponse(        # the failure dims drag
                    text="Acting without checking.",
                    tool_call=ToolCallRequest(
                        "read_file", {"uri": "missing_guess.py"}),
                    usage=LLMUsage(300, 40), model="scripted")
            return LLMResponse(text="Done, hopefully.",
                               usage=LLMUsage(300, 40), model="scripted")
        return self._sloppy_call(task_pattern(messages), tool_msgs)

    def _sloppy_call(self, pattern: str, tool_msgs: int) -> LLMResponse:
        def fail_read(usage=LLMUsage(1500, 400)):
            return LLMResponse(
                text="Trying something.",
                tool_call=ToolCallRequest(
                    "read_file", {"uri": f"missing_{tool_msgs}.py"}),
                usage=usage, model="scripted")

        def done(usage=LLMUsage(1500, 400)):
            return LLMResponse(text="It might be fixed now, not sure.",
                               usage=usage, model="scripted")

        if pattern == "errors":
            if tool_msgs < 2:
                return fail_read()
            raise RuntimeError("model stream crashed mid-response")
        if pattern in ("tool failures", "slow tools"):
            return fail_read() if tool_msgs < 5 else done()
        if pattern == "token blowup":
            heavy = LLMUsage(12_000, 4_000)
            return fail_read(heavy) if tool_msgs < 3 else done(heavy)
        if pattern == "retries":
            return (LLMResponse(
                text="Retrying the same thing.",
                tool_call=ToolCallRequest("read_file",
                                          {"uri": "missing_0.py"}),
                usage=LLMUsage(1500, 400), model="scripted")
                if tool_msgs < 26 else done())
        if pattern == "churn":
            # Back-and-forth: re-reading the SAME (existing) file over
            # and over — every call succeeds, so churn's signature is
            # pure repetition (llm_calls ≫ threshold 3, call count past
            # the 'excellent' band), distinct from the tool-failure
            # patterns. (The loop only continues on tool calls, so churn
            # manifests as repeated successful lookups.)
            if tool_msgs < 9:
                return LLMResponse(
                    text="Let me reconsider the approach.",
                    tool_call=ToolCallRequest("read_file",
                                              {"uri": self.good_file}),
                    usage=LLMUsage(1500, 400), model="scripted")
            return done()
        return fail_read() if tool_msgs < self.sloppy_calls else done()

    # -- optimizer-side scripted responses --------------------------------
    @staticmethod
    def _parent_rules(prompt: str) -> List[str]:
        """Current rules from the apply-edit prompt's own section
        (gradient.build_apply_edit_prompt) — what a real optimizer LLM
        would read and revise."""
        from .gradient import NO_RULES_PLACEHOLDER
        if "## Current Prompt Rules" not in prompt:
            return []
        section = prompt.split("## Current Prompt Rules", 1)[1]
        section = section.split("## Critique", 1)[0]
        return [ln.strip().lstrip("- ").strip()
                for ln in section.splitlines()
                if ln.strip()
                and NO_RULES_PLACEHOLDER.lower() not in ln.lower()]

    def _holdout_proposal(self, prompt: str) -> List[str]:
        """Hold-out mode: MUTATE the parent rule-set — keep one rule,
        swap in a bank draw. The proposer encodes no knowledge of which
        rules steer; composition quality emerges only through scored
        selection across rounds (the graded contract needs a
        verify+efficiency PAIR, so single-class parents improve
        incrementally)."""
        bank = list(self.proposal_bank)
        parent = [r for r in self._parent_rules(prompt) if r]
        keep = [self._rng.choice(parent)] if parent else []
        draw = self._rng.choice([r for r in bank if r not in keep])
        return keep + [draw] if keep else [draw,
                                           self._rng.choice(bank)]

    def _optimizer_call(self, prompt: str) -> LLMResponse:
        if "## Critique" in prompt:      # apply-edit prompt
            rules = (self._holdout_proposal(prompt)
                     if self.proposal_bank else self.improved_rules)
            text = "\n".join(f"- {r}" for r in rules)
        else:                            # textual-gradient critique prompt
            text = ("- Tool calls fail because inputs are never verified; "
                    "require reading the target file before acting.\n"
                    "- Cap tool-call count; retries without new information "
                    "waste tokens.")
        return LLMResponse(text=text, usage=LLMUsage(800, 120),
                           model="scripted")

    @staticmethod
    def _apo_rules_text(system_message: str) -> str:
        marker = "# APO Optimized Rules"
        idx = system_message.find(marker)
        if idx < 0:
            return ""
        section = system_message[idx + len(marker):]
        nxt = section.find("\n# ")
        return section[:nxt] if nxt >= 0 else section


def outcome_feedback(turn_result) -> Optional[str]:
    """Deterministic evaluator-in-the-loop: judge an episode good/bad
    from its OUTCOME (the automatic analogue of the reference's
    user-feedback signal, the highest-weight reward dim).

    Good = the agent acted (≥1 successful tool call) with zero failures,
    no stream errors, and no churning (LLM calls within 2x the agent
    response threshold of 3 — catches the repetition pattern, whose
    tool calls all succeed); bad otherwise. Applied SYMMETRICALLY to
    baseline and optimized rollouts (r2's harness fed 'bad' only to the
    baseline pass, which understated the baseline and left the optimized
    score without its feedback dim)."""
    trace = getattr(turn_result, "trace", None) or turn_result
    s = trace.summary
    if (s.has_errors or s.tool_calls_failed > 0
            or s.tool_calls_succeeded == 0 or s.total_llm_calls > 6):
        return "bad"
    return "good"


def run_uplift_eval(workdir: str, *, client=None,
                    tasks: Sequence[str] = tuple(SIX_PATTERN_TASKS),
                    beam_rounds: int = 3,
                    holdout: bool = False,
                    proposal_seed: int = 0) -> dict:
    """Baseline-vs-optimized finalReward on the pattern task suite (the
    north-star ≥2× comparison, BASELINE configs 2-3), fully offline.

    Flow (= the reference cycle, SURVEY.md §3.3, with the backend in-tree):
    roll the tasks with NO rules (baseline; traces + 'bad' feedback feed
    the gradient corpus) → run local beam search with the
    prompt-conditioned scorer → re-roll under the winning rules → report.
    """
    import os

    from ..rollout.session import RolloutSession
    from ..traces.collector import TraceCollector
    from .local import make_local_apo
    from .types import APOConfig

    # holdout: the scripted optimizer proposes sampled subsets from the
    # hold-out bank instead of handing over GOOD_RULESET — beam search
    # must FIND the steering rules by score (round-3 review). The
    # bank only wires into the SCRIPTED client; a caller-supplied client
    # (real policy) keeps its own optimizer behavior, and the report's
    # holdout flag must say what actually ran.
    holdout_wired = holdout and client is None
    client = client or RuleSensitivePolicy(
        proposal_bank=HOLDOUT_RULE_BANK if holdout else None,
        proposal_seed=proposal_seed)
    ws_counter = [0]

    def make_session(rules, collector=None):
        ws_counter[0] += 1
        root = os.path.join(workdir, f"ws{ws_counter[0]}")
        # loop_sleep no-op: the 'errors' pattern exhausts the agent
        # loop's retry ladder by design; hermetic scoring must not serve
        # its real exponential backoffs.
        s = RolloutSession(client, root, apo_rules=list(rules),
                          collector=collector,
                          include_tool_definitions=False,
                          loop_sleep=lambda _s: None)
        s.workspace.write_file("app.py", "def run():\n    return 1\n")
        return s

    # The same outcome evaluator feeds BOTH passes (and the beam's
    # candidate scoring below) — symmetric feedback, judged from each
    # episode's own outcome.
    feedback_fn = lambda _i, out: outcome_feedback(out)

    # Baseline pass also populates the APO corpus (with the reference's
    # feedback gate satisfied: gradient needs feedback'd traces).
    corpus = TraceCollector()
    baseline = evaluate_rules([], lambda rules: make_session(rules, corpus),
                              tasks, feedback_fn=feedback_fn)

    apo = make_local_apo(
        corpus, client,
        config=APOConfig(beam_rounds=1),
        score_fn=make_rollout_score_fn(make_session, tasks,
                                       feedback_fn=feedback_fn))
    # One visible round at a time: the per-round best progression is the
    # "search matters" record — in holdout mode round 1 need not contain
    # the winner, so later rounds must beat it for the ratio to appear.
    round_best: List[float] = []
    state = None
    for _ in range(beam_rounds):
        state = apo.run_beam_search(seed_prompt="")
        round_best.append(round(state.history_best_score, 4))
    optimized_rules = apo.get_optimized_rules()
    optimized = evaluate_rules(optimized_rules, make_session, tasks,
                               feedback_fn=feedback_fn)

    delta = optimized - baseline
    return {
        "baseline_final_reward": round(baseline, 4),
        "optimized_final_reward": round(optimized, 4),
        "uplift_delta": round(delta, 4),
        # Ratio vs the positive-shifted scale [-1, 1] → [0, 2]: finalReward
        # can be ≤ 0, which would make a raw ratio meaningless.
        "uplift_ratio_shifted": round((optimized + 1.0)
                                      / max(baseline + 1.0, 1e-6), 4),
        "optimized_rules": list(optimized_rules),
        "beam_rounds": state.current_round,
        "beam_round_best_scores": round_best,
        "searched": bool(round_best
                         and round_best[0] < round_best[-1] - 1e-9),
        "holdout_bank": holdout_wired,
        "tasks": len(tasks),
        "evaluator": "outcome_feedback (symmetric)",
    }
