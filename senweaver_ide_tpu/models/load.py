"""Pretrained-weight loading: local HF-layout safetensors → stacked pytree.

The north star fine-tunes real checkpoints (Qwen2.5-Coder-1.5B …
DeepSeek-Coder-6.7B, BASELINE configs 3-5; the reference's policy models
live behind provider APIs — ``common/modelCapabilities.ts:300+``). This
module converts a locally-downloaded HuggingFace model directory (zero
egress: files must already be on disk) into the layer-STACKED param pytree
``models/transformer.py`` consumes, and can export back.

Conventions bridged:
- torch ``nn.Linear`` stores (out_features, in_features); our einsum
  weights are (in, out) → every projection transposes.
- Per-layer HF tensors (``model.layers.{i}.…``) stack on a new leading L
  axis (the ``lax.scan``/pipeline axis).
- RoPE: both sides use the half-rotation (rotate_half) layout, so q/k
  projections need NO row permutation (ops/rotary.py matches HF Qwen2/LLaMA).

Supported families: Qwen2/Qwen2.5 (GQA + QKV bias, optionally tied
embeddings), Qwen3 (QK-norm) incl. Qwen3-MoE (``mlp.experts`` layout),
LLaMA-architecture DeepSeek-Coder (MHA, no biases), Llama-3.x (rope
scaling), Mistral (GQA + sliding window), and Mixtral (block-sparse
MoE: ``block_sparse_moe.gate`` router + per-expert w1/w3/w2) — the
same coverage as models/config.py PRESETS.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional

import numpy as np

from .config import ModelConfig, refuse
from .transformer import Params

__all__ = ["load_hf_params", "export_hf_params", "available_hf_keys"]


def _safetensor_files(model_dir: str) -> List[str]:
    index = os.path.join(model_dir, "model.safetensors.index.json")
    if os.path.exists(index):
        with open(index) as f:
            weight_map = json.load(f)["weight_map"]
        return sorted({os.path.join(model_dir, v)
                       for v in weight_map.values()})
    files = sorted(
        os.path.join(model_dir, f) for f in os.listdir(model_dir)
        if f.endswith(".safetensors"))
    if not files:
        raise FileNotFoundError(
            f"no .safetensors files under {model_dir!r} (expected an "
            f"HF-layout checkpoint directory)")
    return files


def _load_raw(model_dir: str) -> Dict[str, np.ndarray]:
    from safetensors.numpy import load_file

    tensors: Dict[str, np.ndarray] = {}
    for path in _safetensor_files(model_dir):
        tensors.update(load_file(path))
    return tensors


def available_hf_keys(model_dir: str) -> List[str]:
    """Tensor names present in the checkpoint (debugging aid)."""
    return sorted(_load_raw(model_dir))


def _take(raw: Dict[str, np.ndarray], key: str, shape) -> np.ndarray:
    if key not in raw:
        close = [k for k in raw if key.rsplit(".", 2)[-2] in k][:5]
        raise KeyError(f"checkpoint is missing {key!r}; nearby keys: {close}")
    t = raw.pop(key)
    if tuple(t.shape) != tuple(shape):
        raise ValueError(f"{key}: checkpoint shape {tuple(t.shape)} != "
                         f"expected {tuple(shape)} for this ModelConfig")
    return t


# Expert-bank key wiring per HF MoE family: (module base, gate, up, down).
_MOE_LAYOUTS = {
    "mixtral": ("block_sparse_moe", "w1", "w3", "w2"),
    "qwen3": ("mlp", "gate_proj", "up_proj", "down_proj"),
}


def load_hf_params(model_dir: str, config: ModelConfig, *,
                   dtype=None, strict: bool = True) -> Params:
    """Read an HF-layout safetensors dir into the stacked param pytree.

    ``strict`` rejects leftover (unconsumed) checkpoint tensors, which
    catches silently-ignored weights from an architecture mismatch.
    """
    import jax.numpy as jnp

    c = config
    refuse(c, "load_hf_params")
    dtype = dtype or c.dtype
    raw = _load_raw(model_dir)
    D, F, L, V = c.hidden_size, c.intermediate_size, c.num_layers, c.vocab_size

    def stacked(fmt: str, shape, transpose: bool) -> np.ndarray:
        per_layer = []
        for i in range(L):
            t = _take(raw, fmt.format(i=i), shape)
            per_layer.append(t.T if transpose else t)
        return np.stack(per_layer)

    p = "model.layers.{i}."
    layers: Dict[str, Any] = {
        "attn_norm": stacked(p + "input_layernorm.weight", (D,), False),
        "wq": stacked(p + "self_attn.q_proj.weight", (c.q_dim, D), True),
        "wk": stacked(p + "self_attn.k_proj.weight", (c.kv_dim, D), True),
        "wv": stacked(p + "self_attn.v_proj.weight", (c.kv_dim, D), True),
        "wo": stacked(p + "self_attn.o_proj.weight", (D, c.q_dim), True),
        "mlp_norm": stacked(p + "post_attention_layernorm.weight", (D,),
                            False),
    }
    if c.num_experts > 0:
        # Two HF MoE layouts, autodetected from the checkpoint keys:
        #   mixtral: block_sparse_moe.gate + experts.N.{w1,w3,w2}
        #   qwen3-moe: mlp.gate + experts.N.{gate,up,down}_proj
        # Router is (E, D) in both; expert matrices (F, D)/(D, F).
        E = c.num_experts
        qwen3_moe = "model.layers.0.mlp.gate.weight" in raw
        base, g_key, u_key, d_key = _MOE_LAYOUTS[
            "qwen3" if qwen3_moe else "mixtral"]
        layers["router"] = stacked(p + base + ".gate.weight", (E, D), True)

        def experts(sub: str, shape) -> np.ndarray:
            per_layer = []
            for i in range(L):
                per_layer.append(np.stack([
                    _take(raw, f"model.layers.{i}.{base}."
                               f"experts.{e}.{sub}.weight", shape).T
                    for e in range(E)]))
            return np.stack(per_layer)          # (L, E, in, out)

        layers["w_gate"] = experts(g_key, (F, D))
        layers["w_up"] = experts(u_key, (F, D))
        layers["w_down"] = experts(d_key, (D, F))
    else:
        layers["w_gate"] = stacked(p + "mlp.gate_proj.weight", (F, D), True)
        layers["w_up"] = stacked(p + "mlp.up_proj.weight", (F, D), True)
        layers["w_down"] = stacked(p + "mlp.down_proj.weight", (D, F), True)
    if c.qkv_bias:
        layers["bq"] = stacked(p + "self_attn.q_proj.bias", (c.q_dim,), False)
        layers["bk"] = stacked(p + "self_attn.k_proj.bias", (c.kv_dim,),
                               False)
        layers["bv"] = stacked(p + "self_attn.v_proj.bias", (c.kv_dim,),
                               False)
    if c.qk_norm:
        layers["q_norm"] = stacked(p + "self_attn.q_norm.weight",
                                   (c.head_dim,), False)
        layers["k_norm"] = stacked(p + "self_attn.k_norm.weight",
                                   (c.head_dim,), False)

    params: Params = {
        "embed": _take(raw, "model.embed_tokens.weight", (V, D)),
        "layers": layers,
        "final_norm": _take(raw, "model.norm.weight", (D,)),
    }
    if not c.tie_word_embeddings:
        # Some tied-embedding exports still materialize lm_head; only
        # consume it when the config expects a separate head.
        params["lm_head"] = _take(raw, "lm_head.weight", (V, D)).T
    else:
        raw.pop("lm_head.weight", None)

    # RoPE inv_freq buffers etc. are derived, not parameters.
    leftover = [k for k in raw if not k.endswith("rotary_emb.inv_freq")]
    if leftover and strict:
        raise ValueError(
            f"{len(leftover)} unconsumed checkpoint tensors (architecture "
            f"mismatch?): {leftover[:8]}")

    import jax

    return jax.tree_util.tree_map(lambda x: jnp.asarray(x, dtype), params)


def export_hf_params(params: Params, config: ModelConfig,
                     out_dir: str) -> str:
    """Write the stacked pytree back to an HF-layout safetensors file —
    round-trip partner of :func:`load_hf_params` (lets a GRPO-tuned policy
    be served by any HF-ecosystem runtime)."""
    from safetensors.numpy import save_file

    from .quantize import is_quantized

    refuse(config, "export_hf_params")
    if is_quantized(params):
        # transposing the +/-127 codes without their scales would write a
        # garbage checkpoint that loads cleanly elsewhere
        raise TypeError("export_hf_params received int8-quantized params "
                        "(models/quantize.py is a serving transform); "
                        "export the full-precision train-state params")
    c = config
    if c.num_experts > 0 and c.moe_layout not in _MOE_LAYOUTS:
        raise ValueError(f"unknown moe_layout {c.moe_layout!r}; "
                         f"available: {sorted(_MOE_LAYOUTS)}")
    os.makedirs(out_dir, exist_ok=True)
    lp = params["layers"]

    def t(x):
        # safetensors serializes the raw buffer IGNORING strides, and
        # device_get on TPU can return non-C-contiguous arrays — every
        # tensor must be materialized contiguously before save.
        return np.ascontiguousarray(np.asarray(x))

    def tt(x):  # back to torch's (out, in) layout
        return np.ascontiguousarray(np.asarray(x).T)

    out: Dict[str, np.ndarray] = {
        "model.embed_tokens.weight": t(params["embed"]),
        "model.norm.weight": t(params["final_norm"]),
    }
    if "lm_head" in params:
        out["lm_head.weight"] = tt(params["lm_head"])
    for i in range(c.num_layers):
        p = f"model.layers.{i}."
        out[p + "input_layernorm.weight"] = t(lp["attn_norm"][i])
        out[p + "self_attn.q_proj.weight"] = tt(lp["wq"][i])
        out[p + "self_attn.k_proj.weight"] = tt(lp["wk"][i])
        out[p + "self_attn.v_proj.weight"] = tt(lp["wv"][i])
        out[p + "self_attn.o_proj.weight"] = tt(lp["wo"][i])
        out[p + "post_attention_layernorm.weight"] = t(lp["mlp_norm"][i])
        if c.num_experts > 0:
            # layout mirrors the loader's autodetected families
            # (validated once, before the per-layer loop — see below)
            base, g_key, u_key, d_key = _MOE_LAYOUTS[c.moe_layout]
            out[p + base + ".gate.weight"] = tt(lp["router"][i])
            for e in range(c.num_experts):
                ep = p + f"{base}.experts.{e}."
                out[ep + g_key + ".weight"] = tt(lp["w_gate"][i, e])
                out[ep + u_key + ".weight"] = tt(lp["w_up"][i, e])
                out[ep + d_key + ".weight"] = tt(lp["w_down"][i, e])
        else:
            out[p + "mlp.gate_proj.weight"] = tt(lp["w_gate"][i])
            out[p + "mlp.up_proj.weight"] = tt(lp["w_up"][i])
            out[p + "mlp.down_proj.weight"] = tt(lp["w_down"][i])
        if c.qkv_bias:
            out[p + "self_attn.q_proj.bias"] = t(lp["bq"][i])
            out[p + "self_attn.k_proj.bias"] = t(lp["bk"][i])
            out[p + "self_attn.v_proj.bias"] = t(lp["bv"][i])
        if c.qk_norm:
            out[p + "self_attn.q_norm.weight"] = t(lp["q_norm"][i])
            out[p + "self_attn.k_norm.weight"] = t(lp["k_norm"][i])
    path = os.path.join(out_dir, "model.safetensors")
    save_file(out, path)
    return path
