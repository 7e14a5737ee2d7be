"""Speculative decoding: draft-model proposals, one-forward verification.

A TPU-serving feature beyond the reference's remote-API path (which
streams one token per round trip): a small draft model proposes ``k``
tokens autoregressively, the big target model scores all of them in a
SINGLE forward, and the standard rejection rule (Leviathan et al. 2023)
keeps the longest valid prefix — so the target's cost per emitted token
drops toward 1/k of a per-token loop while the output distribution is
exactly the target's. On this repo's dispatch-bound serving path (each
host→TPU step costs fixed overhead) the verify-k-at-once shape is also
what amortizes dispatches.

Greedy (temperature 0) acceptance is ``proposal == target argmax``,
which makes the output IDENTICAL to vanilla greedy decoding of the
target — the property the tests pin. (Identical up to numerics: a
(1, k) verify forward and a (1, 1) decode step may tile matmuls
differently, so a last-ulp difference can flip a near-tie argmax on
low-precision configs. The tests pin it on the fp32
matmul_precision="highest" test config, where the shapes agree
bitwise.) Stochastic sampling uses the exact
accept-with-prob(min(1, p/q)) rule with residual resampling on
rejection, which preserves the target distribution.

Cache bookkeeping: both models keep a "pending" token (emitted but not
yet written to cache). Each round feeds ``[pending, d_1..d_{k-1}]`` so
position i's logits are the target distribution FOR proposal d_{i+1};
on acceptance of m ≤ k proposals both caches truncate to the valid
prefix by resetting ``length`` (stale positions beyond ``length`` are
never attended — models/transformer.py kv validity mask).

Single-sequence (B=1): per-sequence acceptance lengths make batched
caches ragged; latency-oriented speculation is the B=1 regime.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.config import ModelConfig
from ..models.transformer import (KVCache, Params, forward, forward_paged,
                                  init_kv_cache)
from ..obs.runtime_profile import ProfiledFunction
from .paged_kv import PagedKVPool, PagedSeqKV


@functools.partial(jax.jit, static_argnames=("config",),
                   donate_argnames=("cache",))
def _verify_forward(params: Params, config: ModelConfig, tokens: jax.Array,
                    cache: KVCache) -> Tuple[jax.Array, KVCache]:
    """Feed (1, k) tokens; return fp32 logits (k, V) + updated cache."""
    logits, cache = forward(params, config, tokens, cache=cache)
    return logits[0], cache


@functools.partial(jax.jit, static_argnames=("config", "last_only"),
                   donate_argnames=("pool",))
def _verify_forward_paged(params: Params, config: ModelConfig,
                          tokens: jax.Array, tables: jax.Array,
                          positions: jax.Array, write_block: jax.Array,
                          write_off: jax.Array, pool: PagedKVPool,
                          last_only: bool):
    """Paged verify: feed (k,) tokens through the block-table forward.
    ``last_only`` slices the final row in-jit (prefill — avoids
    materializing (n_prompt, V) fp32 on host just to keep one row).
    The pool rides through as the whole pytree, so quantized ladders
    (scales + optional full-width prefix) verify through the same jit."""
    logits, pool = forward_paged(
        params, config, tokens, pool=pool,
        tables=tables, seq_row=jnp.zeros_like(tokens),
        positions=positions, write_block=write_block, write_off=write_off)
    if last_only:
        logits = logits[-1:]
    return logits, pool


# Runtime observatory wiring (obs/runtime_profile.py): the verify
# forwards are the speculative hot path — their ledger shows whether
# draft-length variation induces retraces (the k-ladder should bound
# the compile set) and what each verify window costs on device.
_verify_forward = ProfiledFunction(
    _verify_forward, "speculative.verify", skip_args=(0, 1))
_verify_forward_paged = ProfiledFunction(
    _verify_forward_paged, "speculative.verify_paged", skip_args=(0, 1),
    storm_threshold=32)


def _truncate(cache: KVCache, length: int) -> KVCache:
    """Roll the cache back to ``length`` valid tokens (pure metadata —
    stale entries past length are masked out of attention)."""
    return cache._replace(length=jnp.asarray(length, jnp.int32))


def _softmax(logits: np.ndarray, temperature: float) -> np.ndarray:
    x = logits.astype(np.float64) / max(temperature, 1e-6)
    x = x - x.max()
    e = np.exp(x)
    return e / e.sum()


class SpeculativeDecoder:
    """Draft/target pair with independent KV caches."""

    def __init__(self, target_params: Params, target_config: ModelConfig,
                 draft_params: Params, draft_config: ModelConfig, *,
                 k: int = 4, kv_layout: str = "slots",
                 block_size: int = 16, kv_dtype: str = "bf16"):
        if target_config.vocab_size != draft_config.vocab_size:
            raise ValueError(
                "draft and target must share a vocabulary "
                f"({draft_config.vocab_size} vs {target_config.vocab_size})")
        if (target_config.sliding_window is not None
                or draft_config.sliding_window is not None):
            # Rollback (_truncate) relies on stale entries past `length`
            # being masked, but a ring cache physically OVERWRITES slot
            # pos % cap: rejected draft writes destroy in-window keys and
            # cannot be undone by resetting length.
            raise ValueError(
                "speculative decoding does not support sliding-window "
                "(ring-cache) configs: draft rejection cannot roll back "
                "overwritten ring slots — use sampler.generate instead")
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        if kv_layout not in ("slots", "paged"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}")
        self.tp, self.tc = target_params, target_config
        self.dp, self.dc = draft_params, draft_config
        self.k = k
        # "paged" verifies through block tables (rollout/paged_kv.py):
        # rejection releases the rejected drafts' blocks instead of
        # only resetting a length — _last_paged_kv exposes the
        # (target, draft) caches so tests can assert no block leaks.
        self.kv_layout = kv_layout
        self.block_size = block_size
        # Quantized KV ladder on the TARGET cache only (paged layout):
        # acceptance compares the target's argmax against proposals, so
        # the exactness budget is the target's; the draft cache stays
        # full-width — it is small and its quality only moves the
        # acceptance RATE, never the output distribution.
        if kv_dtype != "bf16" and kv_layout != "paged":
            raise ValueError("kv_dtype quantized ladder needs "
                             "kv_layout='paged'")
        self.kv_dtype = kv_dtype
        self._last_paged_kv: Optional[Tuple[PagedSeqKV, PagedSeqKV]] = None
        self.rounds = 0          # verify forwards issued (observability)
        self.accepted = 0        # proposals accepted across rounds
        self.proposed = 0

    def generate(self, prompt: List[int], *, max_new_tokens: int,
                 temperature: float = 0.0,
                 eos_id: Optional[int] = None,
                 key: Optional[jax.Array] = None,
                 max_len: Optional[int] = None) -> List[int]:
        """Decode ``max_new_tokens`` tokens (stops early at ``eos_id``)."""
        k = self.k
        seed = int(jax.random.randint(key, (), 0, 2**31 - 1)) \
            if key is not None else 0
        rng = np.random.default_rng(seed)
        n_prompt = len(prompt)
        # Each verify round writes up to k tokens past the accepted prefix
        # before truncation; a cache sized for vanilla decoding clamps
        # those writes onto VALID positions (silent corruption, not an
        # error) — so enforce the speculative headroom on top of any
        # caller-supplied max_len.
        max_len = max(max_len or 0, n_prompt + max_new_tokens + k + 1)
        paged = self.kv_layout == "paged"
        if paged:
            t_kv = PagedSeqKV(self.tc, max_len=max_len,
                              block_size=self.block_size,
                              kv_dtype=self.kv_dtype)
            d_kv = PagedSeqKV(self.dc, max_len=max_len,
                              block_size=self.block_size)
            self._last_paged_kv = (t_kv, d_kv)
            t_cache = d_cache = None
            t_last = self._paged_feed(t_kv, self.tp, self.tc, prompt,
                                      last_only=True)
            self._paged_feed(d_kv, self.dp, self.dc, prompt,
                             last_only=True)
        else:
            t_cache = init_kv_cache(self.tc, 1, max_len)
            d_cache = init_kv_cache(self.dc, 1, max_len)
            toks = jnp.asarray([prompt], jnp.int32)

            # sampler.prefill slices the last-token logits INSIDE the
            # jit — verify-shaped prefill would materialize
            # (n_prompt, V) fp32 per model only to discard all but one
            # row. (_paged_feed's last_only flag does the same in-jit.)
            from .sampler import prefill
            t_last, t_cache = prefill(self.tp, self.tc, toks, t_cache)
            _d_last, d_cache = prefill(self.dp, self.dc, toks, d_cache)
        # pending = emitted-but-uncached; its target dist is in hand
        pending = int(jnp.argmax(t_last[0])) if temperature <= 0.0 \
            else self._pick(np.asarray(t_last[0]), temperature, rng)
        out = [pending]
        n_cached = n_prompt

        while len(out) < max_new_tokens and \
                (eos_id is None or out[-1] != eos_id):
            greedy = temperature <= 0.0
            # -- draft k proposals ----------------------------------------
            # Feed pending, then each sampled proposal; the k-th proposal
            # is sampled from the final dist but never fed, keeping draft
            # and target caches in lockstep at [pending, d_1..d_{k-1}].
            # Greedy mode argmaxes ON DEVICE and transfers one int per
            # step; a full fp32 (V,) row per step would move ~600 kB per
            # proposal at a 152k vocab, rivaling the dispatch overhead
            # speculation exists to amortize. Stochastic mode still needs
            # the q-rows host-side for the accept/residual math.
            q_logits: List[np.ndarray] = []
            proposals: List[int] = []
            tok = pending
            for _ in range(k):
                if paged:
                    dl = self._paged_feed(d_kv, self.dp, self.dc, [tok])
                else:
                    dl, d_cache = _verify_forward(
                        self.dp, self.dc, jnp.asarray([[tok]], jnp.int32),
                        d_cache)
                if greedy:
                    tok = int(jnp.argmax(dl[-1]))
                else:
                    q_logits.append(np.asarray(dl[-1]))
                    tok = self._pick(q_logits[-1], temperature, rng)
                proposals.append(tok)

            # -- verify in ONE target forward ------------------------------
            if paged:
                p_dev = self._paged_feed(t_kv, self.tp, self.tc,
                                         [pending] + proposals[:-1])
            else:
                verify_in = jnp.asarray([[pending] + proposals[:-1]],
                                        jnp.int32)
                p_dev, t_cache = _verify_forward(self.tp, self.tc,
                                                 verify_in, t_cache)
            self.rounds += 1
            self.proposed += k

            # -- acceptance --------------------------------------------------
            m = 0
            correction: Optional[int] = None
            if greedy:
                t_arg = np.asarray(jnp.argmax(p_dev, axis=-1))  # (k,) ints
                for i, d_i in enumerate(proposals):
                    if int(t_arg[i]) != d_i:
                        correction = int(t_arg[i])
                        break
                    m += 1
            else:
                p_logits = np.asarray(p_dev)     # (k, V): row i maps prop i
                for i, d_i in enumerate(proposals):
                    p = _softmax(p_logits[i], temperature)
                    q = _softmax(q_logits[i], temperature)
                    if rng.random() >= min(1.0,
                                           p[d_i] / max(q[d_i], 1e-12)):
                        residual = np.maximum(p - q, 0.0)
                        total = residual.sum()
                        if total <= 0:
                            correction = int(rng.choice(len(p), p=p))
                        else:
                            correction = int(rng.choice(
                                len(residual), p=residual / total))
                        break
                    m += 1
            self.accepted += m

            if m == k:
                emitted = proposals
                new_pending = proposals[-1]
                # caches hold pending + proposals[:-1] = 1 + (k-1) tokens
                n_cached += k
            else:
                emitted = proposals[:m] + [correction]
                new_pending = correction
                n_cached += 1 + m            # pending + accepted prefix
                if paged:
                    # Paged rollback returns the rejected drafts' blocks
                    # to the pool (refcount-exact), not just a length
                    # reset — the leak assertion in tests rides on this.
                    t_kv.truncate(n_cached)
                    d_kv.truncate(n_cached)
                else:
                    t_cache = _truncate(t_cache, n_cached)
                    d_cache = _truncate(d_cache, n_cached)

            for tok in emitted:
                out.append(int(tok))
                if eos_id is not None and tok == eos_id:
                    break
                if len(out) >= max_new_tokens:
                    break
            pending = new_pending

        return out[:max_new_tokens]

    def _paged_feed(self, kv: PagedSeqKV, params: Params,
                    config: ModelConfig, toks: List[int], *,
                    last_only: bool = False) -> jax.Array:
        """Feed host tokens at the cache tip through the block-table
        forward; returns fp32 logits rows ((1, V) when ``last_only``,
        else (len(toks), V)). Grows the block table first so every
        write lands in an owned block."""
        start = kv.length
        kv.ensure(start + len(toks))
        bs = kv.allocator.block_size
        poss = list(range(start, start + len(toks)))
        logits, kv.pool = _verify_forward_paged(
            params, config, jnp.asarray(toks, jnp.int32),
            kv.tables_array(), jnp.asarray(poss, jnp.int32),
            jnp.asarray([kv.table[p // bs] for p in poss], jnp.int32),
            jnp.asarray([p % bs for p in poss], jnp.int32),
            kv.pool, last_only)
        kv.length = start + len(toks)
        return logits

    @property
    def acceptance_rate(self) -> float:
        return self.accepted / self.proposed if self.proposed else 0.0

    @staticmethod
    def _pick(logits: np.ndarray, temperature: float,
              rng: np.random.Generator) -> int:
        if temperature <= 0.0:
            return int(np.argmax(logits))
        p = _softmax(logits, temperature)
        return int(rng.choice(len(p), p=p))


# ---- online draft learning (FastGRPO, PAPERS.md) ------------------------

@functools.partial(jax.jit, static_argnames=("config", "optimizer"))
def _distill_step(params: Params, opt_state, config: ModelConfig,
                  optimizer, tokens: jax.Array, mask: jax.Array):
    """One cross-entropy step teaching the draft to imitate sequences the
    TARGET emitted. tokens: (B, S); mask True on positions whose
    next-token prediction should be trained (the emitted continuation)."""
    import optax

    from ..training.grpo import token_logprobs

    def loss_fn(p):
        logits, _ = forward(p, config, tokens[:, :-1])
        logp = token_logprobs(logits, tokens[:, 1:])
        m = mask[:, 1:].astype(jnp.float32)
        return -(logp * m).sum() / jnp.maximum(m.sum(), 1.0)

    loss, grads = jax.value_and_grad(loss_fn)(params)
    updates, opt_state = optimizer.update(grads, opt_state, params)
    params = optax.apply_updates(params, updates)
    return params, opt_state, loss


class OnlineDraftLearner:
    """Distill the draft toward the target ONLINE from served outputs.

    FastGRPO's observation (PAPERS.md): during RL the target policy
    drifts, so a frozen draft's acceptance rate — and with it the
    speculative speedup — decays. The fix is continual distillation on
    exactly the sequences the target emits while serving: call
    :meth:`observe` with each finished (prompt, output) pair and
    :meth:`step` between serving bursts; the decoder's draft params are
    swapped in place, so the next ``generate`` proposes with the
    improved draft. Output distributions are untouched — speculative
    decoding is exact regardless of draft quality; only the ACCEPTANCE
    RATE (throughput) moves.
    """

    def __init__(self, decoder: SpeculativeDecoder, *,
                 learning_rate: float = 1e-3, buffer_size: int = 256,
                 max_len: int = 512, pad_id: int = 0, seed: int = 0):
        import optax
        self.decoder = decoder
        self.optimizer = optax.adam(learning_rate)
        self.opt_state = jax.jit(self.optimizer.init)(decoder.dp)
        self.buffer: List[Tuple[List[int], List[int]]] = []
        self.buffer_size = buffer_size
        self.max_len = max_len
        self.pad_id = pad_id
        self.steps = 0
        self._rng = np.random.default_rng(seed)

    def observe(self, prompt: List[int], output: List[int]) -> None:
        """Record a served sequence (drop-oldest ring buffer)."""
        self.buffer.append((list(prompt), list(output)))
        if len(self.buffer) > self.buffer_size:
            del self.buffer[:len(self.buffer) - self.buffer_size]

    def step(self, batch_size: int = 8) -> float:
        """One distillation update over the newest ``batch_size`` pairs.
        Returns the cross-entropy loss (0.0 when the buffer is empty)."""
        if not self.buffer:
            return 0.0
        # Sample uniformly from the whole buffer (newest-only would
        # overfit the last burst and waste everything else retained).
        idx = self._rng.choice(len(self.buffer),
                               size=min(batch_size, len(self.buffer)),
                               replace=False)
        pairs = [self.buffer[i] for i in idx]
        # Bucket the batch width (powers of two) AND pad the batch rows
        # to a constant batch_size (all-False mask rows): both axes must
        # be shape-stable or every distinct (B, width) recompiles the
        # jitted step.
        width = 16
        need = min(self.max_len,
                   max(len(p) + len(o) for p, o in pairs))
        while width < need:
            width *= 2
        toks = np.full((batch_size, width), self.pad_id, np.int32)
        mask = np.zeros((batch_size, width), bool)
        for i, (p, o) in enumerate(pairs):
            seq = (p + o)[-width:]
            n_out = min(len(o), width)
            toks[i, :len(seq)] = seq
            mask[i, len(seq) - n_out:len(seq)] = True
        self.decoder.dp, self.opt_state, loss = _distill_step(
            self.decoder.dp, self.opt_state, self.decoder.dc,
            self.optimizer, jnp.asarray(toks), jnp.asarray(mask))
        self.steps += 1
        return float(loss)
