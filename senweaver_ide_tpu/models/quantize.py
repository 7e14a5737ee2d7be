"""Weight-only int8 quantization for serving.

Small-batch decode streams every weight byte once per step: 3.1 GB of
bf16 at a v5e chip's 819 GB/s bounds the 1.5B at ~264 steps/s (device
time on today's code: not measured). Storing
the dense matmul weights as int8 with one fp32 scale per OUTPUT channel
(absmax over the contraction axis) halves the bytes every decode step
must stream, raising the bandwidth ceiling ~2× at <1% relative logit
error; the MXU still computes in the activation dtype (the int8→bf16
upcast happens at tile load, the scale is a fused output epilogue — see
``transformer._dense``).

Scope: the seven stacked per-layer dense matrices + ``lm_head`` +
the 4-D MoE expert banks (per-expert per-output-channel scales — with
expert parallelism this is what fits Mixtral-class weights on a small
pod slice). Excluded on purpose:
  - norms/biases (tiny, precision-critical),
  - the MoE router (routing decisions are precision-sensitive and the
    matrix is tiny),
  - ``embed`` (a gather, not a matmul; tied-head quality is sensitive).

This is a SERVING transform: quantized params are not differentiable
and must never enter ``train_step``. The actor/learner bridge
(``RolloutEngine.update_params``) re-applies it on publish when the
engine was built with quantized weights.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

# Stacked (L, in, out) layer matrices + the 2-D head; in all of them the
# contraction axis is -2, so per-output-channel absmax is over axis=-2.
QUANTIZABLE = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def dense_family_shapes(config) -> Dict[str, tuple]:
    """(fan_in, out) per dense family for a NON-MoE config — the one
    source of truth for sizing tables and direct-int8 initializers
    (bench/eval scripts otherwise each restate this table and drift)."""
    c = config
    if c.num_experts > 0:
        raise ValueError("dense_family_shapes: MoE configs carry (L, E, "
                         "in, out) expert banks — size those explicitly")
    D, F = c.hidden_size, c.intermediate_size
    q_dim, kv_dim = c.q_dim, c.kv_dim
    return {"wq": (D, q_dim), "wk": (D, kv_dim), "wv": (D, kv_dim),
            "wo": (q_dim, D), "w_gate": (D, F), "w_up": (D, F),
            "w_down": (F, D)}


def _quantize_matrix(w: jax.Array):
    """(…, in, out) → int8 values + fp32 (…, out) per-channel scales."""
    wf = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(wf), axis=-2)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(wf / scale[..., None, :]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def quantize_weights_int8(params: Dict) -> Dict:
    """Return a new param pytree with dense weights int8-quantized.

    Idempotent (already-int8 tensors pass through); anything outside
    QUANTIZABLE (router, norms, biases, embed) is left untouched."""
    out = dict(params)
    layers = dict(params["layers"])
    for name in QUANTIZABLE:
        w = layers.get(name)
        # 3-D: stacked dense (L, in, out); 4-D: stacked MoE expert banks
        # (L, E, in, out) — _quantize_matrix is rank-generic (absmax
        # over the contraction axis -2, scales (..., out)).
        if w is None or w.dtype == jnp.int8 or w.ndim not in (3, 4):
            continue
        layers[name], layers[name + "_scale"] = _quantize_matrix(w)
    out["layers"] = layers
    head = params.get("lm_head")
    if head is not None and head.dtype != jnp.int8:
        out["lm_head"], out["lm_head_scale"] = _quantize_matrix(head)
    elif head is None and "tied_head_q8" not in params:
        # Tied embeddings: the head matmul streams the FULL (V, D) table
        # every decode step (the largest single tensor of the 1.5B
        # flagship, ~15% of its weight bytes). Keep the bf16 embed for
        # the GATHER (quality-sensitive, reads only B rows) and store an
        # int8 SHADOW with per-vocab-row scales for the head matmul —
        # +50% of embed's footprint, −50% of its per-step traffic.
        emb = params["embed"].astype(jnp.float32)          # (V, D)
        absmax = jnp.max(jnp.abs(emb), axis=-1)            # (V,)
        scale = jnp.maximum(absmax, 1e-8) / 127.0
        out["tied_head_q8"] = jnp.clip(
            jnp.round(emb / scale[:, None]), -127, 127).astype(jnp.int8)
        # _scale suffix on the weight's own key: transformer._dense's
        # shared int8 epilogue resolves it by name
        out["tied_head_q8_scale"] = scale
    return out


def is_quantized(params: Dict) -> bool:
    w = params.get("layers", {}).get("wq")
    return w is not None and w.dtype == jnp.int8


def quantized_bytes(params: Dict) -> int:
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(params))
