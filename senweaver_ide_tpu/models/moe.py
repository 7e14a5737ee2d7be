"""The single-device expert layer: top-k routing that drops nothing.

Every (token, choice) pair is computed. The step's pairs are sorted by
expert, each projection is ONE grouped matrix product over the sorted rows
(``jax.lax.ragged_dot``: on TPU a Mosaic grouped matmul that streams each
touched expert's weights once per row tile, on CPU a masked dense product),
the rows are put back in token order and summed with their weights. There
is no capacity and no (T, k, E, C) one-hot: a token's result depends on its
own hidden state alone, never on which other tokens share the batch — the
property every token-exactness guarantee of the paged engine rests on
(group fork, migration, speculative verify, chunked prefill against block
prefill). The capacity-bounded path with dropped tokens stays in
``parallel/expert.py`` for ``moe_ffn_sharded`` over the ``ep`` axis.

Two router forms (``ModelConfig.router_type``):

``softmax``       probs = softmax(h W_r); top-k of probs; weights = chosen
                  probs renormalised (Mixtral, Qwen3-MoE). Aux = the Switch
                  load-balancing loss E * sum_e f_e p_e.
``sigmoid_bias``  s = sigmoid(h W_r); CHOICE = top-k of s + b (b a per-expert
                  correction bias, used for the choice only); weights =
                  s_j / (sum of the chosen s + 1e-20) * routed_scaling_factor
                  (DeepSeek-V3 ``noaux_tc`` with one group; GLM-4.7-Flash).
                  Aux = 0: the bias, not a loss, evens the load.

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
    (all entries where it is None): how many of the E expert banks got at
    least one (token, choice) pair, and the largest number of pairs on one
    expert. int32 scalars."""
    experts_touched: jax.Array
    expert_load_max: jax.Array


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
    if c.router_type != "softmax":
        raise ValueError(f"unknown router_type {c.router_type!r}; expected "
                         f"softmax|sigmoid_bias")
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
    with jax.named_scope("moe.experts"):
        gate = _grouped(rows, lp, "w_gate", sizes, expert_of_row, stack_layer)
        up = _grouped(rows, lp, "w_up", sizes, expert_of_row, stack_layer)
        act = jax.nn.silu(gate.astype(jnp.float32)).astype(h.dtype) * up
        out = _grouped(act, lp, "w_down", sizes, expert_of_row, stack_layer)
    with jax.named_scope("moe.combine"):
        # back to (token, choice) order; the k terms of a token are summed
        # in the order of its own top-k, in float32
        y = (out[inverse].reshape(t, k, d).astype(jnp.float32)
             * weight[..., None]).sum(1)
    return y, aux, stats
