"""Solar-Open2-250B (``model_type: solar_open2``): periods of one gated NoPE
GQA layer and ``gqa_interval`` gated delta-rule (KDA) layers — q, k and v
each through a causal depthwise conv of ``short_conv_kernel_size`` taps, a
decay a key channel, ``beta`` in (0, 2) under ``kda_allow_neg_eigval``, a
float32 matrix state a head — every layer's second sublayer
``n_routed_experts`` routed + ``n_shared_experts`` shared SwiGLU experts of
``moe_intermediate_size`` under a sigmoid router with a correction bias,
``num_experts_per_tok`` a token, the chosen scores normalised; no positional
term, an untied head. The file's ``n_routed_experts`` is the chip's share
(``held_experts``: which of how many). The mapping of the published keys is
the program's (``models.config.solar_open2_config``), which raises on a key
it does not map, by name; the file's own bookkeeping keys are taken out
here, by name. A program from before it had that mapping fails here, at
once."""

from __future__ import annotations

# keys of the configuration file that are the benchmark's, not the model's
BOOKKEEPING = ("name", "source", "architectures", "model_type", "reference",
               "torch_dtype", "hidden_act", "matmul_precision", "reduced",
               "published", "held_experts", "kept", "bytes", "assumed",
               "deployment")


def model_config(cfg: dict):
    import jax.numpy as jnp
    from senweaver_ide_tpu.models.config import solar_open2_config
    held = cfg["held_experts"]
    if held["count"] != cfg["n_routed_experts"] or (
            held["of"] != cfg["published"]["n_routed_experts"]):
        raise SystemExit(f"benchmark: {cfg['name']}: held_experts {held} "
                         f"against n_routed_experts and its published count")
    if cfg.get("hidden_act", "silu") != "silu":
        raise SystemExit(f"benchmark: {cfg['name']}: ['hidden_act'] as set "
                         f"is not mapped by archs/solar_open2.py")
    try:
        return solar_open2_config(
            {k: v for k, v in cfg.items() if k not in BOOKKEEPING},
            name=cfg["name"], first_expert=held["first"],
            routed_experts=held["of"],
            dtype={"bfloat16": jnp.bfloat16,
                   "float32": jnp.float32}[cfg["torch_dtype"]],
            matmul_precision=cfg.get("matmul_precision"))
    except ValueError as e:
        raise SystemExit(f"benchmark: {e}")
