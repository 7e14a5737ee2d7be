"""LongCat-Flash-Chat (``model_type: longcat_flash``): 28 double layers, each
two multi-head latent attention sublayers (both latent scales) and two dense
FFNs of ``ffn_hidden_size`` with the expert layer on a shortcut beside them;
``n_routed_experts`` real experts of ``expert_ffn_hidden_size`` and
``zero_expert_num`` identity experts under one softmax router, ``moe_topk`` a
token, weights not renormalised, times ``routed_scaling_factor``. The file's
``n_routed_experts`` is the chip's share (``held_experts``: which of how
many). The mapping of the published keys is the program's
(``models.config.longcat_flash_config``), which raises on a key it does not
map; the file's own bookkeeping keys are taken out here, by name. A program
from before it had that mapping fails here, at once."""

from __future__ import annotations

# keys of the configuration file that are the benchmark's, not the model's
BOOKKEEPING = ("name", "source", "architectures", "model_type", "reference",
               "torch_dtype", "matmul_precision", "reduced", "published",
               "held_experts", "kept", "bytes", "assumed", "deployment")


def model_config(cfg: dict):
    import jax.numpy as jnp
    from senweaver_ide_tpu.models.config import longcat_flash_config
    held = cfg["held_experts"]
    if held["count"] != cfg["n_routed_experts"] or (
            held["of"] != cfg["published"]["n_routed_experts"]):
        raise SystemExit(f"benchmark: {cfg['name']}: held_experts {held} "
                         f"against n_routed_experts and its published count")
    try:
        return longcat_flash_config(
            {k: v for k, v in cfg.items() if k not in BOOKKEEPING},
            name=cfg["name"], first_expert=held["first"],
            routed_experts=held["of"],
            dtype={"bfloat16": jnp.bfloat16,
                   "float32": jnp.float32}[cfg["torch_dtype"]],
            matmul_precision=cfg.get("matmul_precision"))
    except ValueError as e:
        raise SystemExit(f"benchmark: {e}")
