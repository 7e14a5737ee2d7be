"""Every forward ends in one head (``models.transformer._lm_head``): the
stream closed, the final norm, the tied / int8-shadowed / separate head
matrix, the multiplier. ``forward`` and ``forward_paged`` (every entry's
logits, and the entries asked for) give the same logits for a prompt."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from senweaver_ide_tpu.models import (forward, get_config, init_params,
                                      quantize_weights_int8)
from senweaver_ide_tpu.models.transformer import forward_paged
from senweaver_ide_tpu.rollout.paged_kv import init_paged_pool

BLOCK, TOKENS = 4, 8


def _tied_q8():
    c = dataclasses.replace(get_config("tiny-test"),
                            tie_word_embeddings=True)
    params = quantize_weights_int8(init_params(c, jax.random.PRNGKey(0)))
    assert "tied_head_q8" in params and "lm_head" not in params
    return c, params


def _preset(name, **changes):
    c = dataclasses.replace(get_config(name), **changes)
    return c, init_params(c, jax.random.PRNGKey(0))


HEADS = {
    "tied": lambda: _preset("tiny-test", tie_word_embeddings=True),
    "untied": lambda: _preset("tiny-test"),
    "tied_q8": _tied_q8,
    # lm_head_multiplier 0.25, embedding_multiplier 5
    "multiplier": lambda: _preset("tiny-falcon-h1-test"),
    # LayerNorm with a bias, the embedding tied, a layer pattern
    "layernorm": lambda: _preset("tiny-phi4flash-test"),
}


@pytest.mark.parametrize("head", sorted(HEADS))
def test_every_forward_ends_in_the_same_head(head):
    c, params = HEADS[head]()
    assert ("lm_head" in params) == (not c.tie_word_embeddings)
    tokens = (jnp.arange(TOKENS, dtype=jnp.int32) * 37 + 11) % c.vocab_size
    want = forward(params, c, tokens[None])[0][0]
    assert want.shape == (TOKENS, c.vocab_size) and want.dtype == jnp.float32

    pos = jnp.arange(TOKENS, dtype=jnp.int32)
    tables = jnp.asarray([[1, 2, 3]], jnp.int32)
    paged = jax.jit(lambda entries: forward_paged(
        params, c, tokens, pool=init_paged_pool(
            c, 4, BLOCK, state_rows=2, step_tokens=TOKENS),
        tables=tables, seq_row=jnp.zeros((TOKENS,), jnp.int32),
        positions=pos, write_block=tables[0, pos // BLOCK],
        write_off=pos % BLOCK, logit_entries=entries)[0])
    tol = 2e-2 if head == "tied_q8" else 2e-4
    np.testing.assert_allclose(paged(None), want, atol=tol, rtol=tol)
    # the entries asked for, one of them past the last: nobody's (clamped
    # to the last where every layer runs over every entry; run as padding
    # is by a pattern whose trailing layers hold nothing, PR 46)
    entries = jnp.asarray([TOKENS - 1, 2, TOKENS + 5], jnp.int32)
    got = np.asarray(paged(entries))
    np.testing.assert_allclose(
        got[:2], want[jnp.asarray([TOKENS - 1, 2])], atol=tol, rtol=tol)
    if c.readers_from == len(c.layer_types):
        np.testing.assert_allclose(got[2], want[TOKENS - 1], atol=tol,
                                   rtol=tol)
    assert got.shape == (3, c.vocab_size) and np.isfinite(got).all()
