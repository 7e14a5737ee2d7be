"""LoRA adapters: low-rank GRPO fine-tuning that fits one chip.

Full-precision GRPO on a 6.7B policy needs ~13.4 GB bf16 weights +
~27 GB fp32-equivalent Adam moments + a full-size gradient tree — far
past one 16 GB v5e. LoRA freezes the base and trains rank-r factors on
the attention (and optionally MLP) matmuls: the gradient tree and
optimizer state shrink to the adapters (tens of MB at r=16), and with
an int8-quantized base (models/quantize.py) the whole setup — weights,
adapters, moments, activations — fits a single chip (QLoRA recipe,
TPU-first: the dequant epilogue lives inside ``transformer._dense``,
so the merged forward is one code path for full/int8/LoRA serving).

Mechanics:
  - ``init_lora(config, key, rank, targets)`` → adapter pytree shaped
    like the layer stack: ``{"layers": {"wq_lora_a": (L, in, r),
    "wq_lora_b": (L, r, out), ...}}``; B starts at zero so the adapted
    model EQUALS the base at init (the LoRA invariant).
  - ``merge_lora(base_params, lora)`` → params whose layers dict also
    carries the adapter leaves; ``transformer._dense`` applies
    ``y += (h @ A) @ B`` wherever they are present. The merge is a dict
    union — no weight materialization, scan-compatible (leading L).
  - ``train_step(..., lora_base=base)`` (training/trainer.py) treats
    ``state.params`` as the adapter tree: gradients and optimizer state
    exist ONLY for the adapters; the base is a closed-over constant.
  - ``materialize_lora(base, lora, config)`` folds A·B into the dense
    weights for publish/export (re-quantizing if the base was int8).

The alpha/rank scale is baked into A at init (A ~ N(0, 1/in)·alpha/r,
B = 0): the adapted function class is identical and no extra scale leaf
has to ride the scanned layer dict.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..models.config import ModelConfig, refuse
from ..models.quantize import _quantize_matrix, is_quantized

# (in_dim, out_dim) resolvers per supported target matrix.
_TARGET_DIMS = {
    "wq": lambda c: (c.hidden_size, c.q_dim),
    "wk": lambda c: (c.hidden_size, c.kv_dim),
    "wv": lambda c: (c.hidden_size, c.kv_dim),
    "wo": lambda c: (c.q_dim, c.hidden_size),
    "w_gate": lambda c: (c.hidden_size, c.intermediate_size),
    "w_up": lambda c: (c.hidden_size, c.intermediate_size),
    "w_down": lambda c: (c.intermediate_size, c.hidden_size),
}

DEFAULT_TARGETS: Tuple[str, ...] = ("wq", "wk", "wv", "wo")


def init_lora(config: ModelConfig, key: jax.Array, *, rank: int = 16,
              alpha: float = None, targets: Sequence[str] = DEFAULT_TARGETS,
              ) -> Dict:
    """Adapter pytree; zero function delta at init (B = 0)."""
    refuse(config, "init_lora")
    if config.num_experts > 0:
        bad = {"w_gate", "w_up", "w_down"} & set(targets)
        if bad:
            raise ValueError(f"MoE expert banks are not LoRA targets "
                             f"(got {sorted(bad)}); use attention targets")
    alpha = 2.0 * rank if alpha is None else alpha
    L = config.num_layers
    layers: Dict[str, jax.Array] = {}
    keys = jax.random.split(key, len(targets))
    for t, k in zip(targets, keys):
        if t not in _TARGET_DIMS:
            raise ValueError(f"unknown LoRA target {t!r}; "
                             f"available: {sorted(_TARGET_DIMS)}")
        d_in, d_out = _TARGET_DIMS[t](config)
        scale = (alpha / rank) / float(d_in) ** 0.5
        layers[t + "_lora_a"] = (
            jax.random.normal(k, (L, d_in, rank), config.dtype)
            * jnp.asarray(scale, config.dtype))
        layers[t + "_lora_b"] = jnp.zeros((L, rank, d_out), config.dtype)
    return {"layers": layers}


def merge_lora(base_params: Dict, lora: Dict) -> Dict:
    """Params view with adapter leaves alongside the base layer stack —
    what ``forward`` consumes. Pure dict union (no array math)."""
    out = dict(base_params)
    out["layers"] = {**base_params["layers"], **lora["layers"]}
    return out


def split_lora(params: Dict) -> Tuple[Dict, Dict]:
    """Inverse of merge_lora: (base_params, lora)."""
    base, adapters = {}, {}
    for name, leaf in params["layers"].items():
        (adapters if "_lora_" in name else base)[name] = leaf
    out = {k: v for k, v in params.items() if k != "layers"}
    out["layers"] = base
    return out, {"layers": adapters}


def materialize_lora(base_params: Dict, lora: Dict,
                     config: ModelConfig) -> Dict:
    """Fold A·B into the dense weights → a plain param tree (publish /
    export path). An int8 base is dequantized per matrix, folded, and
    re-quantized, so a QLoRA-served engine keeps its representation."""
    out = dict(base_params)
    layers = dict(base_params["layers"])
    for name in list(lora["layers"]):
        if not name.endswith("_lora_a"):
            continue
        target = name[: -len("_lora_a")]
        a = lora["layers"][name]
        b = lora["layers"][target + "_lora_b"]
        delta = jnp.einsum("lir,lro->lio", a.astype(jnp.float32),
                           b.astype(jnp.float32))
        w = layers[target]
        if w.dtype == jnp.int8:
            scale = layers[target + "_scale"]          # (L, out)
            wf = w.astype(jnp.float32) * scale[:, None, :]
            layers[target], layers[target + "_scale"] = _quantize_matrix(
                (wf + delta).astype(config.dtype))
        else:
            layers[target] = (w.astype(jnp.float32) + delta).astype(w.dtype)
    out["layers"] = layers
    return out


def lora_param_count(lora: Dict) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(lora))


# HF module names for each target (PEFT adapter layout).
_PEFT_MODULES = {
    "wq": "self_attn.q_proj", "wk": "self_attn.k_proj",
    "wv": "self_attn.v_proj", "wo": "self_attn.o_proj",
    "w_gate": "mlp.gate_proj", "w_up": "mlp.up_proj",
    "w_down": "mlp.down_proj",
}

# Hub repo ids for the local presets — what a PEFT runtime needs in
# adapter_config.json to resolve the base checkpoint.
_HF_REPO_IDS = {
    "qwen2.5-coder-0.5b": "Qwen/Qwen2.5-Coder-0.5B",
    "qwen2.5-coder-1.5b": "Qwen/Qwen2.5-Coder-1.5B",
    "qwen2.5-coder-7b": "Qwen/Qwen2.5-Coder-7B",
    "qwen3-1.7b": "Qwen/Qwen3-1.7B",
    "qwen3-8b": "Qwen/Qwen3-8B",
    "deepseek-coder-1.3b": "deepseek-ai/deepseek-coder-1.3b-base",
    "deepseek-coder-6.7b": "deepseek-ai/deepseek-coder-6.7b-base",
    "mistral-7b": "mistralai/Mistral-7B-v0.1",
    "mixtral-8x7b": "mistralai/Mixtral-8x7B-v0.1",
    "llama-3.2-1b": "meta-llama/Llama-3.2-1B",
    "llama-3.1-8b": "meta-llama/Llama-3.1-8B",
}


def export_peft_adapter(lora: Dict, config: ModelConfig,
                        out_dir: str, *,
                        base_model: str = None) -> str:
    """Write adapters in the HF-PEFT layout (adapter_model.safetensors +
    adapter_config.json) so a GRPO-trained adapter drops into any
    PEFT-ecosystem runtime over the matching base checkpoint.

    The alpha/rank scale is baked into our A at init, so the exported
    config pins ``lora_alpha == r`` (scaling 1.0) — the folded product
    A·B is identical either way. PEFT stores lora_A as (r, in) and
    lora_B as (out, r) (torch Linear layout); ours are (in, r)/(r, out).
    """
    import json
    import os

    import numpy as np
    from safetensors.numpy import save_file

    os.makedirs(out_dir, exist_ok=True)
    tensors: Dict[str, "np.ndarray"] = {}
    rank = None
    targets = []
    for name, leaf in lora["layers"].items():
        if not name.endswith("_lora_a"):
            continue
        target = name[: -len("_lora_a")]
        targets.append(_PEFT_MODULES[target].rsplit(".", 1)[-1])
        # one device→host transfer per stacked tensor, sliced host-side
        a = np.asarray(lora["layers"][name], dtype=np.float32)
        b = np.asarray(lora["layers"][target + "_lora_b"],
                       dtype=np.float32)
        rank = int(a.shape[-1])
        for i in range(a.shape[0]):
            prefix = (f"base_model.model.model.layers.{i}."
                      f"{_PEFT_MODULES[target]}")
            tensors[prefix + ".lora_A.weight"] = np.ascontiguousarray(
                a[i].T)                                    # (r, in)
            tensors[prefix + ".lora_B.weight"] = np.ascontiguousarray(
                b[i].T)                                    # (out, r)
    if not tensors:
        # An adapter tree with no *_lora_a leaves would otherwise export
        # an empty safetensors + a config with r=null — unusable in any
        # PEFT runtime and silent until load time (ADVICE r3).
        raise ValueError("export_peft_adapter: no LoRA adapter leaves "
                         "found in lora['layers'] (expected *_lora_a/"
                         "*_lora_b pairs)")
    path = os.path.join(out_dir, "adapter_model.safetensors")
    save_file(tensors, path)
    with open(os.path.join(out_dir, "adapter_config.json"), "w") as f:
        json.dump({"peft_type": "LORA", "r": rank, "lora_alpha": rank,
                   "lora_dropout": 0.0, "bias": "none",
                   "base_model_name_or_path": (
                       base_model or _HF_REPO_IDS.get(config.name,
                                                      config.name)),
                   "target_modules": sorted(set(targets)),
                   "task_type": "CAUSAL_LM"}, f, indent=1)
    return path


def load_peft_adapter(adapter_dir: str, config: ModelConfig) -> Dict:
    """Read a PEFT-layout adapter dir back into our stacked tree.

    Scaling: PEFT applies ``lora_alpha / r`` at runtime; we bake it into
    A, so A is multiplied by that factor on load (round-trips exports
    from :func:`export_peft_adapter`, whose config pins the factor to 1).
    """
    import json
    import os

    import numpy as np
    from safetensors.numpy import load_file

    with open(os.path.join(adapter_dir, "adapter_config.json")) as f:
        meta = json.load(f)
    r = float(meta["r"])
    alpha = float(meta.get("lora_alpha", r))
    # PEFT's rsLoRA option scales by alpha/sqrt(r) instead of alpha/r
    scaling = alpha / (r ** 0.5) if meta.get("use_rslora") else alpha / r
    raw = load_file(os.path.join(adapter_dir, "adapter_model.safetensors"))
    module_to_target = {v: k for k, v in _PEFT_MODULES.items()}

    per_target: Dict[str, Dict[int, Dict[str, "np.ndarray"]]] = {}
    skipped = []
    for key, tensor in raw.items():
        # base_model.model.model.layers.{i}.<module>.lora_{A,B}.weight;
        # keys outside that pattern (modules_to_save tensors, adapters
        # on modules this architecture doesn't have) are skipped — a
        # partial load is reported, a fully-unusable one is an error.
        parts = key.split(".")
        if "layers" not in parts or parts[-2] not in ("lora_A", "lora_B"):
            skipped.append(key)
            continue
        li = parts.index("layers")
        module = ".".join(parts[li + 2:-2])
        target = module_to_target.get(module)
        if target is None:
            skipped.append(key)
            continue
        i = int(parts[li + 1])
        per_target.setdefault(target, {}).setdefault(i, {})[parts[-2]] = \
            tensor
    if not per_target:
        raise ValueError(
            f"no loadable LoRA tensors in {adapter_dir!r} (skipped "
            f"{len(skipped)} keys, e.g. {skipped[:3]}); supported "
            f"modules: {sorted(module_to_target)}")

    layers: Dict[str, jax.Array] = {}
    for target, rows in per_target.items():
        L = config.num_layers
        if sorted(rows) != list(range(L)):
            raise ValueError(f"adapter covers layers {sorted(rows)} but "
                             f"config {config.name!r} has {L}")
        d_in, d_out = _TARGET_DIMS[target](config)
        got = rows[0]["lora_A"].T.shape
        if got != (d_in, int(r)):
            # fail HERE with the offending module, not deep inside a
            # jitted einsum (models/load.py _take precedent)
            raise ValueError(
                f"adapter {target} lora_A shape {got} does not match "
                f"config {config.name!r} expectation ({d_in}, {int(r)})")
        a = jnp.stack([jnp.asarray(rows[i]["lora_A"].T) for i in range(L)])
        b = jnp.stack([jnp.asarray(rows[i]["lora_B"].T) for i in range(L)])
        layers[target + "_lora_a"] = (a * scaling).astype(config.dtype)
        layers[target + "_lora_b"] = b.astype(config.dtype)
    return {"layers": layers}


__all__ = ["DEFAULT_TARGETS", "export_peft_adapter", "init_lora",
           "load_peft_adapter", "lora_param_count", "materialize_lora",
           "merge_lora", "split_lora", "is_quantized"]
