"""A real LM in the APO optimizer role (generative textual gradient).

The reference keeps the optimizer on a backend LLM: ``apoService.ts``
builds the critique prompt (:992-1056) and the apply-edit prompt
(:1268-1343) and a *model* writes the critique text and the revised
'- ' rule lines. Round-4 review: our beam had the prompts but a
deterministic bank answered them — the generative half was unexercised.

This module closes it with a purpose-trained tiny byte-LM proposer:

- **Corpus**: rule sentences are COMPOSITIONAL — frame x subject
  (``RULE_FRAMES`` x ``RULE_SUBJECTS``), so the LM learns the template
  structure, not a lookup table. A configurable holdout keeps chosen
  (frame, subject) pairs OUT of training: sampling one of those is a
  novel composition — text the model generated, present in no training
  document and no hand-built bank.
- **Training**: plain causal-LM cross-entropy (Adam) over marker-tagged
  docs (``RULES:`` docs teach the '- ' line contract; ``CRITIQUE:``
  docs teach critique-flavored prose), on the same transformer stack
  the policies use (models/transformer.py forward).
- **Serving**: ``LMProposer`` is a PolicyClient-shaped ``chat()`` —
  the beam's critique call samples from the ``CRITIQUE:`` marker and
  the apply-edit call samples rule lines from ``RULES:\\n- `` through a
  RolloutEngine, with `parse_rules` (gradient.py) downstream, exactly
  where the reference's HTTPS response lands.

Candidate SELECTION stays in the scorer (real rollouts through the jit
reward head) — generation proposes, measurement disposes, the same
division of labor as the reference.
"""

from __future__ import annotations

import dataclasses
import itertools
import random
from typing import List, Optional, Sequence, Set, Tuple

RULE_FRAMES = [
    "Respond using {x} only.",
    "Use {x} in replies.",
    "Emit {x} for every answer.",
    "Write all output as {x}.",
    "Keep every reply to {x}.",
    "Answer with {x} each time.",
]
RULE_SUBJECTS = [
    "plain ascii text",
    "binary high bytes",
    "lowercase ascii letters",
    "uppercase ascii words",
    "ascii digits",
    "short ascii symbols",
]

CRITIQUE_LINES = [
    "The responses use the wrong byte style for what the tasks demand.",
    "Failed runs retry many times; a clear response-style rule is missing.",
    "Outputs drift between styles; pin the output style explicitly.",
    "The rules never say which character class replies must use.",
    "Low reward traces show style mismatches, not tool failures.",
    "State the required output style as a single testable rule.",
]

RULES_MARKER = "RULES:\n"
CRITIQUE_MARKER = "CRITIQUE:\n"


def rule_sentence(frame_idx: int, subject_idx: int) -> str:
    return RULE_FRAMES[frame_idx].format(x=RULE_SUBJECTS[subject_idx])


def all_rule_pairs() -> List[Tuple[int, int]]:
    return list(itertools.product(range(len(RULE_FRAMES)),
                                  range(len(RULE_SUBJECTS))))


@dataclasses.dataclass
class ProposerCorpus:
    """Train/holdout split over the compositional rule grid."""
    train_sentences: List[str]
    holdout_sentences: List[str]
    critiques: List[str]

    @classmethod
    def build(cls, holdout_pairs: Sequence[Tuple[int, int]] = ((0, 0),)
              ) -> "ProposerCorpus":
        held = set(holdout_pairs)
        train, holdout = [], []
        for f, s in all_rule_pairs():
            (holdout if (f, s) in held else train).append(rule_sentence(f, s))
        return cls(train_sentences=train, holdout_sentences=holdout,
                   critiques=list(CRITIQUE_LINES))

    def docs(self, *, rng: random.Random, n: int) -> List[str]:
        """Marker-tagged training documents: rule docs carry 1-2 '- '
        lines (the apply-edit output contract), critique docs one prose
        line. ~5:1 rules:critique mix (rules are the load-bearing
        output)."""
        out = []
        for _ in range(n):
            if rng.random() < 0.2:
                out.append(CRITIQUE_MARKER + rng.choice(self.critiques)
                           + "\n")
            else:
                k = rng.choice([1, 2])
                lines = rng.sample(self.train_sentences, k)
                out.append(RULES_MARKER
                           + "".join(f"- {ln}\n" for ln in lines))
        return out


def train_rule_proposer(*, model: str = "tiny-test", steps: int = 500,
                        batch_size: int = 16, lr: float = 1e-3,
                        seed: int = 0,
                        holdout_pairs: Sequence[Tuple[int, int]] = ((0, 0),),
                        log_every: int = 100):
    """Causal-LM-train a proposer on the compositional corpus.

    Returns (params, config, tokenizer, corpus, loss_curve). Runs on
    whatever platform jax is configured for (the eval scripts force CPU
    for these tiny models).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ..models import get_config
    from ..models.tokenizer import ByteTokenizer
    from ..models.transformer import forward, init_params

    config = get_config(model)
    tok = ByteTokenizer()
    corpus = ProposerCorpus.build(holdout_pairs)
    rng = random.Random(seed)
    docs = corpus.docs(rng=rng, n=4096)
    encoded = [tok.encode(d, add_eos=True) for d in docs]
    max_len = max(len(e) for e in encoded)
    # power-of-two bucket, one compilation
    S = 32
    while S < max_len:
        S *= 2

    def batch_arrays(idx: Sequence[int]):
        toks = np.full((len(idx), S), tok.pad_id, np.int32)
        msk = np.zeros((len(idx), S), np.float32)
        for i, j in enumerate(idx):
            e = encoded[j][:S]
            toks[i, :len(e)] = e
            msk[i, 1:len(e)] = 1.0    # predict every token after the first
        return jnp.asarray(toks), jnp.asarray(msk)

    params = init_params(config, jax.random.PRNGKey(seed))
    opt = optax.adam(lr)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, toks, msk):
        def loss_fn(p):
            logits, _ = forward(p, config, toks)
            logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
            tgt = toks[:, 1:]
            nll = -jnp.take_along_axis(logp, tgt[..., None],
                                       axis=-1)[..., 0]
            m = msk[:, 1:]
            return (nll * m).sum() / jnp.maximum(m.sum(), 1.0)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    curve = []
    for s in range(steps):
        idx = [rng.randrange(len(encoded)) for _ in range(batch_size)]
        toks, msk = batch_arrays(idx)
        params, opt_state, loss = step(params, opt_state, toks, msk)
        if (s + 1) % log_every == 0 or s == steps - 1:
            curve.append(round(float(loss), 4))
    return params, config, tok, corpus, curve


class LMProposer:
    """PolicyClient-shaped optimizer backed by the trained proposer LM.

    ``propose_candidates`` (apo/beam.py) calls ``chat()`` twice per
    candidate: a critique call (free prose) and an apply-edit call
    (whose response feeds ``parse_rules``). Both responses here are
    REAL sampled model text — the tiny proposer's conditioning is the
    marker prefix (its capacity does not absorb the full critique
    prompt; noted in the artifact), the reference-shaped prompts are
    still built and threaded by the beam.

    Tracks every apply-edit generation for the novelty audit:
    ``generation_log`` entries say whether each parsed rule is a
    training sentence, a held-out composition, or free text.
    """

    def __init__(self, params, config, tok, corpus: ProposerCorpus, *,
                 temperature: float = 0.9, seed: int = 0,
                 max_new_tokens: int = 96):
        from ..rollout.engine import RolloutEngine
        from ..rollout.sampler import SampleParams

        self.engine = RolloutEngine(
            params, config, num_slots=4, max_len=512,
            sample=SampleParams(temperature=temperature, top_p=0.98),
            eos_id=tok.eos_id, seed=seed)
        self.tok = tok
        self.corpus = corpus
        self.max_new_tokens = max_new_tokens
        self.generation_log: List[dict] = []
        self._train_set: Set[str] = set(corpus.train_sentences)
        self._holdout_set: Set[str] = set(corpus.holdout_sentences)

    def _sample(self, marker: str) -> str:
        rid = self.engine.submit(self.tok.encode(marker),
                                 max_new_tokens=self.max_new_tokens)
        self.engine.run()
        return self.tok.decode(self.engine.result(rid))

    def chat(self, messages, *, temperature=None, max_tokens=None,
             on_text=None):
        from ..agents.llm import LLMResponse, LLMUsage

        prompt = messages[-1].content if messages else ""
        if "## Critique" in prompt:           # apply-edit call
            text = self._sample(RULES_MARKER)
            from .gradient import parse_rules
            parsed = parse_rules(text)
            self.generation_log.append({
                "raw": text,
                "rules": parsed,
                "novel": [r in self._holdout_set for r in parsed],
                "in_train_corpus": [r in self._train_set for r in parsed],
            })
        else:                                  # critique call
            text = self._sample(CRITIQUE_MARKER)
        return LLMResponse(text=text, usage=LLMUsage(0, 0),
                           model="lm-proposer")

    def sample_rules(self, n: int = 1) -> List[List[str]]:
        """Direct rule sampling (diagnostics / tests)."""
        from .gradient import parse_rules
        return [parse_rules(self._sample(RULES_MARKER)) for _ in range(n)]
