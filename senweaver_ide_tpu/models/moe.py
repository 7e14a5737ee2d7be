"""One chip's expert layer: top-k routing that drops nothing.

The router scores every expert it addresses and picks k of them a token.
Every (token, choice) pair of an expert HELD here is computed: the step's
pairs are sorted by held expert, each projection is ONE grouped matrix
product over the sorted rows (``jax.lax.ragged_dot``: on TPU a Mosaic
grouped matmul that streams each touched expert's weights once per row
tile, on CPU a masked dense product), the rows are put back in token order
and summed with their weights. There is no capacity and no (T, k, E, C)
one-hot: a token's result depends on its own hidden state alone, never on
which other tokens share the batch — the property every token-exactness
guarantee of the paged engine rests on (group fork, migration, speculative
verify, chunked prefill against block prefill). The capacity-bounded path
with dropped tokens stays in ``parallel/expert.py`` for ``moe_ffn_sharded``
over the ``ep`` axis.

The chip's share (``ModelConfig.expert_share``). The banks ``w_gate /
w_up / w_down`` hold ``num_experts`` experts, those numbered
[``moe_first_expert``, ``moe_first_expert + num_experts``) of the
``routed_experts`` the router addresses; where that is all of them (every
configuration before LongCat-Flash) every pair is computed. A pair of an
ABSENT real expert adds nothing here, as it would be computed on the chip
that holds it: it sorts behind the last held expert's group, where the
grouped product has no group, and its rows are zeroed. A pair of an
IDENTITY expert (index >= ``routed_experts``; ``moe_zero_experts`` of them)
adds ``weight x the token itself``: no bank, no matmul, one multiply a token
for the sum of its identity weights, computed here in full for every token.
Nothing is dropped for capacity in either case.

Three router forms (``ModelConfig.router_type``):

``softmax``       probs = softmax(h W_r); top-k of probs; weights = chosen
                  probs renormalised (Mixtral, Qwen3-MoE). Aux = the Switch
                  load-balancing loss E * sum_e f_e p_e.
``sigmoid_bias``  s = sigmoid(h W_r); CHOICE = top-k of s + b (b a per-expert
                  correction bias, used for the choice only); weights =
                  s_j / (sum of the chosen s + 1e-20) * routed_scaling_factor
                  (DeepSeek-V3 ``noaux_tc`` with one group; GLM-4.7-Flash).
                  Aux = 0: the bias, not a loss, evens the load.
``softmax_bias``  p = softmax(h W_r) over ALL router outputs (real and
                  identity experts alike); CHOICE = top-k of p + b; weights =
                  p_j * routed_scaling_factor, NOT renormalised over the
                  chosen (LongCat-Flash). Aux = 0.

Router logits, scores and the choice are float32 at ``HIGHEST`` whatever the
model dtype: a near-tie between the k-th and (k+1)-th score decides which
expert runs.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig


class MoEStats(NamedTuple):
    """What one expert layer did, counted over the entries of ``count``
    (all entries where it is None): how many of the E expert banks HELD
    got at least one (token, choice) pair, and the largest number of pairs
    on one of them; where the layer holds a share of the router's experts
    (``ModelConfig.expert_share``; None otherwise) also how many picks
    fell on identity experts and how many on held experts. int32 scalars.
    """
    experts_touched: jax.Array
    expert_load_max: jax.Array
    zero_picks: Optional[jax.Array] = None
    local_pairs: Optional[jax.Array] = None

    def merge(self, other: "MoEStats") -> "MoEStats":
        """Two layers' stats as one: sums, and the larger peak."""
        add = lambda a, b: None if a is None else a + b
        return MoEStats(
            self.experts_touched + other.experts_touched,
            jnp.maximum(self.expert_load_max, other.expert_load_max),
            add(self.zero_picks, other.zero_picks),
            add(self.local_pairs, other.local_pairs))

    @classmethod
    def zeros(cls, c: ModelConfig) -> "MoEStats":
        z = lambda: jnp.zeros((), jnp.int32)
        return cls(z(), z(), *((z(), z()) if c.expert_share else ()))


def route(c: ModelConfig, lp: Dict[str, jax.Array],
          h: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """h (T, D) -> (expert index (T, k) int32, weight (T, k) f32, aux)."""
    logits = jnp.einsum("td,de->te", h.astype(jnp.float32),
                        lp["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    k = c.num_experts_per_tok
    if c.router_type == "sigmoid_bias":
        s = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(
            s + lp["router_bias_norm"].astype(jnp.float32), k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        w = w / (w.sum(-1, keepdims=True) + 1e-20) * c.routed_scaling_factor
        return idx, w, jnp.zeros((), jnp.float32)
    if c.router_type == "softmax_bias":
        probs = jax.nn.softmax(logits, axis=-1)
        _, idx = jax.lax.top_k(
            probs + lp["router_bias_norm"].astype(jnp.float32), k)
        w = jnp.take_along_axis(probs, idx, axis=-1)
        return idx, w * c.routed_scaling_factor, jnp.zeros((), jnp.float32)
    if c.router_type != "softmax":
        raise ValueError(f"unknown router_type {c.router_type!r}; expected "
                         f"softmax|sigmoid_bias|softmax_bias")
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    frac = jax.nn.one_hot(idx, c.num_experts, dtype=jnp.float32).sum(1).mean(0)
    aux = (frac * probs.mean(0)).sum() * c.num_experts
    return idx, w, aux


def _grouped(x: jax.Array, lp: Dict[str, jax.Array], name: str,
             sizes: jax.Array, expert_of_row: jax.Array,
             stack_layer: Optional[jax.Array]) -> jax.Array:
    """Rows of ``x`` sorted by expert times that expert's ``lp[name]``
    (E, in, out); int8 banks (models/quantize.py) upcast at use with the
    per-(expert, output channel) scale applied to the output rows.

    With ``stack_layer`` the bank is the WHOLE stack's (L, E, in, out) and
    this layer is ``stack_layer`` of it: the product runs over L * E groups
    of which only this layer's E hold rows. A grouped product reads the
    weights of the groups that have rows and no others, so nothing of the
    bank is sliced out first — a layer's slice handed to the kernel is a
    copy of 3 x 403 MB a layer at GLM-4.7-Flash's sizes."""
    w, scale = lp[name], lp.get(name + "_scale")
    if stack_layer is not None:
        n_l, e = w.shape[:2]
        w = w.reshape((n_l * e,) + w.shape[2:])
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n_l * e,), sizes.dtype), sizes, (stack_layer * e,))
        if scale is not None:
            scale = scale[stack_layer]
    if w.dtype == jnp.int8:
        out = jax.lax.ragged_dot(x, w.astype(x.dtype), sizes)
        return (out.astype(jnp.float32)
                * scale[expert_of_row]).astype(x.dtype)
    return jax.lax.ragged_dot(x, w, sizes)


BANKS = ("w_gate", "w_up", "w_down",
         "w_gate_scale", "w_up_scale", "w_down_scale")


def expert_ffn(c: ModelConfig, lp: Dict[str, jax.Array], h: jax.Array,
               count: Optional[jax.Array] = None,
               stack_layer: Optional[jax.Array] = None):
    """The routed experts of one layer for a flat batch.

    h (T, D), already normed -> (y (T, D) f32, aux, MoEStats). ``count``
    (T,) bool marks the entries the stats count (the paged step's padding
    is routed like any token, and is not work). ``stack_layer``: the
    ``BANKS`` leaves of ``lp`` are the whole stack's, see ``_grouped``."""
    t, d = h.shape
    e, k = c.num_experts, c.num_experts_per_tok
    with jax.named_scope("moe.router"):
        idx, weight, aux = route(c, lp, h)
    with jax.named_scope("moe.sort"):
        flat = idx.reshape(t * k)
        held = zero = None
        if c.expert_share:
            # held pairs first, by held expert; an absent or identity
            # expert's pair goes behind the last group (bank ``e``: out of
            # range for the counts below, which drop it)
            zero = flat >= c.routed_experts
            local = flat - c.moe_first_expert
            held = (local >= 0) & (local < e)
            flat = jnp.where(held, local, e)
        order = jnp.argsort(flat, stable=True)            # pair -> sorted row
        inverse = jnp.zeros((t * k,), jnp.int32).at[order].set(
            jnp.arange(t * k, dtype=jnp.int32))
        expert_of_row = flat[order]
        sizes = jnp.zeros((e,), jnp.int32).at[flat].add(1)
        rows = h[order // k]                              # (T*k, D)
        counted = sizes if count is None else jnp.zeros((e,), jnp.int32).at[
            flat].add(jnp.repeat(count.astype(jnp.int32), k))
        stats = MoEStats((counted > 0).sum().astype(jnp.int32),
                         counted.max().astype(jnp.int32))
        if held is not None:
            ones = 1 if count is None else jnp.repeat(
                count.astype(jnp.int32), k)
            stats = stats._replace(
                zero_picks=(zero * ones).sum().astype(jnp.int32),
                local_pairs=(held * ones).sum().astype(jnp.int32))
    with jax.named_scope("moe.experts"):
        gate = _grouped(rows, lp, "w_gate", sizes, expert_of_row, stack_layer)
        up = _grouped(rows, lp, "w_up", sizes, expert_of_row, stack_layer)
        act = jax.nn.silu(gate.astype(jnp.float32)).astype(h.dtype) * up
        out = _grouped(act, lp, "w_down", sizes, expert_of_row, stack_layer)
        if held is not None:
            # a row behind the last group belongs to no product: whatever
            # the kernel left there is not a result
            out = jnp.where((expert_of_row < e)[:, None], out, 0)
    with jax.named_scope("moe.combine"):
        # back to (token, choice) order; the k terms of a token are summed
        # in the order of its own top-k, in float32
        y = (out[inverse].reshape(t, k, d).astype(jnp.float32)
             * weight[..., None]).sum(1)
    if c.moe_zero_experts:
        with jax.named_scope("moe.zero"):
            # the identity experts' term: (sum of a token's identity
            # weights) x the token, in float32
            w_zero = jnp.where(zero.reshape(t, k), weight, 0.0).sum(-1)
            y = y + w_zero[:, None] * h.astype(jnp.float32)
    return y, aux, stats
