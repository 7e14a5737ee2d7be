"""Generative APO proposer (apo/proposer.py): corpus, training, serving.

The optimizer-role LM that closes a round-4 review gap — the beam's
critique and apply-edit calls answered by REAL sampled model text
(ref ``apoService.ts:992-1215``: the reference keeps this role on a
backend LLM; SURVEY.md §3.3 in-trees it)."""

import pytest

from senweaver_ide_tpu.apo.gradient import parse_rules
from senweaver_ide_tpu.apo.proposer import (CRITIQUE_MARKER, LMProposer,
                                            ProposerCorpus, RULE_FRAMES,
                                            RULE_SUBJECTS, RULES_MARKER,
                                            all_rule_pairs, rule_sentence,
                                            train_rule_proposer)


def test_corpus_holdout_split():
    corpus = ProposerCorpus.build(holdout_pairs=((0, 0), (2, 3)))
    n = len(RULE_FRAMES) * len(RULE_SUBJECTS)
    assert len(corpus.train_sentences) == n - 2
    assert len(corpus.holdout_sentences) == 2
    assert rule_sentence(0, 0) in corpus.holdout_sentences
    assert rule_sentence(2, 3) in corpus.holdout_sentences
    assert rule_sentence(0, 0) not in corpus.train_sentences
    # compositional coverage: frame 0 and subject 0 each still appear
    # in training (in OTHER combinations) — that is what makes sampling
    # the held-out sentence a novel composition, not an impossibility
    assert any(s.startswith("Respond using ")
               for s in corpus.train_sentences)
    assert any("plain ascii text" in s for s in corpus.train_sentences)


def test_corpus_docs_follow_output_contracts():
    import random
    corpus = ProposerCorpus.build()
    docs = corpus.docs(rng=random.Random(0), n=200)
    rule_docs = [d for d in docs if d.startswith(RULES_MARKER)]
    crit_docs = [d for d in docs if d.startswith(CRITIQUE_MARKER)]
    assert rule_docs and crit_docs
    assert len(rule_docs) + len(crit_docs) == len(docs)
    for d in rule_docs:
        rules = parse_rules(d[len(RULES_MARKER):])
        assert 1 <= len(rules) <= 2
        for r in rules:
            assert r in corpus.train_sentences   # holdout never trains


def test_rule_sentence_grid_is_unique():
    sentences = {rule_sentence(f, s) for f, s in all_rule_pairs()}
    assert len(sentences) == len(RULE_FRAMES) * len(RULE_SUBJECTS)


def test_train_and_serve_contract():
    """Few-step training smoke + the PolicyClient chat contract: the
    apply-edit path returns sampled text and logs a novelty audit
    entry; the critique path returns text without logging."""
    from senweaver_ide_tpu.agents.llm import ChatMessage, LLMResponse

    params, cfg, tok, corpus, curve = train_rule_proposer(
        steps=3, batch_size=4, log_every=1)
    assert len(curve) == 3
    assert all(c > 0 for c in curve)
    prop = LMProposer(params, cfg, tok, corpus, seed=0, max_new_tokens=24)
    crit = prop.chat([ChatMessage("user", "critique this prompt")])
    assert isinstance(crit, LLMResponse)
    assert prop.generation_log == []          # critique calls not audited
    edit = prop.chat([ChatMessage("user", "x\n## Critique\ny")])
    assert isinstance(edit, LLMResponse)
    assert len(prop.generation_log) == 1
    entry = prop.generation_log[0]
    assert set(entry) == {"raw", "rules", "novel", "in_train_corpus"}
    assert entry["rules"] == parse_rules(entry["raw"])
