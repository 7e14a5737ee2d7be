"""Parameter and activation sharding rules (Megatron/FSDP layout).

One place defines how every param in the transformer pytree maps onto the
(dp, fsdp, tp, sp) mesh:

- column-parallel projections (wq/wk/wv, w_gate/w_up): output dim on ``tp``,
  input dim on ``fsdp``
- row-parallel projections (wo, w_down): input dim on ``tp``, output dim on
  ``fsdp`` (XLA inserts the tp all-reduce after the matmul)
- embedding: vocab on ``tp``, hidden on ``fsdp``; lm_head hidden on ``fsdp``,
  vocab on ``tp``
- norms: replicated
- the leading layer axis of scanned params is unsharded (reserved for
  pipeline stages later)

This is ZeRO-3-style: fsdp-sharded params are all-gathered per layer by XLA
during the scan, and gradients reduce-scattered back.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# PartitionSpecs per param-tree path (leading axis of layer-stacked params
# is the scan/pipeline axis).
PARAM_SPECS: Dict[str, P] = {
    "embed": P("tp", "fsdp"),
    "final_norm": P(None),
    "lm_head": P("fsdp", "tp"),
    "layers/attn_norm": P(None, None),
    "layers/wq": P(None, "fsdp", "tp"),
    "layers/wk": P(None, "fsdp", "tp"),
    "layers/wv": P(None, "fsdp", "tp"),
    "layers/bq": P(None, "tp"),
    "layers/bk": P(None, "tp"),
    "layers/bv": P(None, "tp"),
    "layers/wo": P(None, "tp", "fsdp"),
    "layers/mlp_norm": P(None, None),
    "layers/q_norm": P(None, None),     # (L, head_dim) — replicated
    "layers/k_norm": P(None, None),
    "layers/w_gate": P(None, "fsdp", "tp"),
    "layers/w_up": P(None, "fsdp", "tp"),
    "layers/w_down": P(None, "tp", "fsdp"),
    "layers/router": P(None, "fsdp", None),
    # int8 weight-only serving (models/quantize.py): per-output-channel
    # scales shard like their weight's OUTPUT axis, so the epilogue
    # multiply stays local to the shard that produced the output tile.
    "lm_head_scale": P("tp"),
    # int8 shadow of the tied-embedding head (models/quantize.py):
    # shards like embed; per-vocab-row scales follow the vocab axis
    "tied_head_q8": P("tp", "fsdp"),
    "tied_head_q8_scale": P("tp"),
    "layers/wq_scale": P(None, "tp"),
    "layers/wk_scale": P(None, "tp"),
    "layers/wv_scale": P(None, "tp"),
    "layers/wo_scale": P(None, "fsdp"),
    "layers/w_gate_scale": P(None, "tp"),
    "layers/w_up_scale": P(None, "tp"),
    "layers/w_down_scale": P(None, "fsdp"),
}

# LoRA adapter leaves (training/lora.py): replicated — rank-r factors
# are tiny and the (h@A)@B epilogue is cheapest with local factors.
for _t in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
    PARAM_SPECS[f"layers/{_t}_lora_a"] = P(None, None, None)
    PARAM_SPECS[f"layers/{_t}_lora_b"] = P(None, None, None)

# MoE variants: expert banks carry an extra (E,) axis after the layer
# axis, sharded over 'ep' (models/config.py num_experts > 0).
MOE_PARAM_SPECS: Dict[str, P] = {
    "layers/w_gate": P(None, "ep", "fsdp", "tp"),
    "layers/w_up": P(None, "ep", "fsdp", "tp"),
    "layers/w_down": P(None, "ep", "tp", "fsdp"),
}

# int8 MoE banks: (L, E, out) scales follow the bank's expert + OUTPUT
# axes (distinguished from the 2-axis dense scales by ndim).
MOE_SCALE_SPECS: Dict[str, P] = {
    "layers/w_gate_scale": P(None, "ep", "tp"),
    "layers/w_up_scale": P(None, "ep", "tp"),
    "layers/w_down_scale": P(None, "ep", "fsdp"),
}

# Activation specs.
ACT_SPEC = P(("dp", "fsdp"), "sp", None)          # (B, S, D)
LOGITS_SPEC = P(("dp", "fsdp"), "sp", "tp")       # (B, S, V)
# KV cache (L, B, S, Hkv, D): batch on data axes, heads on tp.
KV_CACHE_SPEC = P(None, ("dp", "fsdp"), None, "tp", None)


def restrict_spec(spec: P, mesh: Mesh) -> P:
    """Drop axes the mesh does not have (a tp-only serving mesh must not
    reject the canonical specs that also name dp/fsdp/sp) or has at size
    1, and trailing Nones — the form jit hands back for its outputs, so
    a state placed with this spec and the state a step returns are one
    jit cache entry, not two."""
    names = {a for a, n in mesh.shape.items() if n > 1}

    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, tuple):
            kept = tuple(a for a in entry if a in names)
            return kept if len(kept) > 1 else (kept[0] if kept else None)
        return entry if entry in names else None

    kept = [keep(e) for e in spec]
    while kept and kept[-1] is None:
        kept.pop()
    return P(*kept)


def spec_for_path(path: str, ndim: int = -1) -> P:
    if path in MOE_PARAM_SPECS and ndim == 4:
        return MOE_PARAM_SPECS[path]
    if path in MOE_SCALE_SPECS and ndim == 3:
        return MOE_SCALE_SPECS[path]
    if path in PARAM_SPECS:
        return PARAM_SPECS[path]
    raise KeyError(f"no sharding rule for param path {path!r}")


def param_specs(params: Any) -> Any:
    """Pytree of PartitionSpecs matching a transformer param tree."""
    def walk(tree: Any, prefix: str) -> Any:
        if isinstance(tree, dict):
            return {k: walk(v, f"{prefix}/{k}" if prefix else k)
                    for k, v in tree.items()}
        return spec_for_path(prefix, getattr(tree, "ndim", -1))

    return walk(params, "")


def shard_params(params: Any, mesh: Mesh) -> Any:
    """Place a param pytree onto the mesh per PARAM_SPECS (restricted to
    the mesh's axes)."""
    specs = param_specs(params)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(
            x, NamedSharding(mesh, restrict_spec(s, mesh))), params, specs)


def param_shardings(params: Any, mesh: Mesh) -> Any:
    """NamedSharding pytree (for jit in_shardings/out_shardings)."""
    specs = param_specs(params)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, restrict_spec(s, mesh)), specs,
        is_leaf=lambda x: isinstance(x, P))
