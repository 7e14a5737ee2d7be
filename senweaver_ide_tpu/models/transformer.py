"""Decoder-only transformer — functional JAX, layer-stacked, scan-compiled.

TPU-first design decisions (vs a PyTorch-style module port):
- Params are a plain pytree of layer-STACKED arrays (leading axis L) and the
  forward pass is one ``lax.scan`` over layers: the layer body is traced once,
  giving O(1) compile time in depth and a natural pipeline-parallel axis.
- All matmuls are einsums in bf16 with fp32 softmax/norm accumulation — the
  shapes XLA tiles directly onto the MXU.
- KV is pre-allocated at static shapes. The paged pool
  (``forward_paged``; what the engine serves from) is stacked
  (L, num_blocks, block_size, Hkv, Dh) and rides the layer scan's CARRY:
  each layer scatters into and gathers from the stacked arrays at its own
  index, so a donated pool is updated in place — nothing of the pool's or
  of a layer's size is copied, sliced out or written back in a step. The
  legacy slot cache (``forward(..., cache=...)``, (L, B, Smax, Hkv, Dh),
  ``dynamic_update_slice``) still scans its layers as xs/ys, which XLA
  cannot alias: it moves the whole cache every step.
- Sharding lives entirely in ``parallel/sharding.py`` PartitionSpecs; the
  model code is sharding-agnostic (GSPMD propagates).

Architectures covered: Qwen2.5-Coder (GQA + QKV bias, tied embeddings at
0.5B/1.5B) and DeepSeek-Coder/LLaMA (MHA, untied) — see models/config.py.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import NEG_INF, attention
from ..ops.norms import layer_norm, rms_norm
from ..ops.rotary import apply_rope, rope_cos_sin
from ..ops import delta_rule, ssm as ssm_ops
from .config import (LAYER_KINDS, STATELESS_KINDS, ModelConfig, YarnScaling,
                     pattern_keys, refuse)
from .moe import BANKS, MoEStats, expert_ffn

Params = Dict[str, Any]


class KVCache(NamedTuple):
    k: jax.Array  # (L, B, Smax, Hkv, Dh) — bf16, or int8 when quantized
    v: jax.Array  # (L, B, Smax, Hkv, Dh)
    # () int32 — tokens currently in cache; or (B,) int32 for per-slot
    # lengths (continuous batching, rollout/engine.py).
    length: jax.Array
    # Per-(layer, slot, position, head) dequantization scales, present
    # only for the int8 cache (absmax/127 over head_dim). Halving cache
    # bytes is a CAPACITY lever: a 16 GB chip serving deepseek-6.7b
    # (13.4 GB bf16 weights) fits 2× the decode batch.
    k_scale: Optional[jax.Array] = None  # (L, B, Smax, Hkv) f32
    v_scale: Optional[jax.Array] = None

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


def ring_capacity(config: ModelConfig, max_len: int) -> int:
    """KV capacity actually allocated for ``max_len`` requested positions.

    Sliding-window configs keep only the trailing ``sliding_window``
    positions (ring buffer, written at pos % capacity) — THE memory
    benefit of SWA: a mistral-7b 32k-context decode holds 4096 cache
    slots, not 32768. Rounded up to a multiple of 8 for TPU lane
    tiling."""
    if config.sliding_window is None:
        return max_len
    return min(max_len, -(-config.sliding_window // 8) * 8)


def _is_ring(c: ModelConfig, cap: int) -> bool:
    """Ring (modular-write) semantics apply only when the cache can hold
    the whole window: cap < window would overwrite keys still inside the
    window on every wrap (write-then-attend is only safe because the slot
    being overwritten, pos − cap, lies outside the window when
    cap ≥ window). Short SWA caches (cap < aligned window) therefore use
    ABSOLUTE positions — plain bounded cache with the positional window
    mask, never wrapping."""
    return (c.sliding_window is not None
            and cap >= -(-c.sliding_window // 8) * 8)


def init_kv_cache(config: ModelConfig, batch: int, max_len: int,
                  dtype=None, *, quantized: Optional[bool] = None) -> KVCache:
    refuse(config, "init_kv_cache")
    quantized = config.kv_quant if quantized is None else quantized
    max_len = ring_capacity(config, max_len)
    shape = (config.num_layers, batch, max_len, config.num_kv_heads,
             config.head_dim)
    if quantized:
        sshape = shape[:-1]
        return KVCache(k=jnp.zeros(shape, jnp.int8),
                       v=jnp.zeros(shape, jnp.int8),
                       length=jnp.zeros((), jnp.int32),
                       k_scale=jnp.zeros(sshape, jnp.float32),
                       v_scale=jnp.zeros(sshape, jnp.float32))
    dtype = dtype or config.dtype
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   length=jnp.zeros((), jnp.int32))


def _quantize_kv(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(B, S, H, D) → int8 values + (B, S, H) f32 absmax/127 scales."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray,
                   dtype) -> jnp.ndarray:
    """int8 (B, S, H, D) + (B, S, H) scales → ``dtype`` values."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def pool_qmax(dtype) -> float:
    """Clip magnitude of a quantized paged-KV payload dtype (the scale
    denominator: scale = absmax / qmax)."""
    if np.dtype(dtype) == np.int8:
        return 127.0
    return 448.0  # float8_e4m3fn


def quantize_pool_kv(x: jnp.ndarray, dtype) -> Tuple[jnp.ndarray,
                                                     jnp.ndarray]:
    """Per-vector absmax quantization over the trailing head_dim axis:
    ``(..., D)`` full-width → (payload in ``dtype``, ``(...)`` f32
    scales). Used both inside the fused step (quantize-at-write) and by
    :func:`rollout.paged_kv.install_blocks` (quantize-at-install), so a
    block written token-by-token and a block installed wholesale hold
    bit-identical payloads."""
    qmax = pool_qmax(dtype)
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(absmax, 1e-8) / qmax
    y = xf / scale[..., None]
    if np.dtype(dtype) == np.int8:
        q = jnp.clip(jnp.round(y), -qmax, qmax).astype(jnp.int8)
    else:
        q = jnp.clip(y, -qmax, qmax).astype(dtype)
    return q, scale


def dequantize_pool_kv(q: jnp.ndarray, scale: jnp.ndarray,
                       dtype) -> jnp.ndarray:
    """Inverse of :func:`quantize_pool_kv`: ``(..., D)`` payload +
    ``(...)`` scales → ``dtype`` values."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _init_expert_leaves(c: ModelConfig, L: int, dense: Callable,
                        ks: jax.Array, at: Tuple[int, ...]
                        ) -> Dict[str, jax.Array]:
    """An expert layer's leaves, stacked ``L`` deep: the router as wide as
    it addresses, the banks of the experts held, a bias router's correction
    bias, the shared expert. ``dense(key, shape, fan_in)`` draws a matrix;
    ``at``: which of the keys ``ks`` draws the router, the three banks and
    the shared expert."""
    D, E, Fe = c.hidden_size, c.num_experts, c.expert_size
    if c.moe_first_expert + E > c.routed_experts:
        raise ValueError(
            f"{c.name}: experts [{c.moe_first_expert}, "
            f"{c.moe_first_expert + E}) held of {c.routed_experts} routed")
    out = {"router": dense(ks[at[0]], (L, D, c.router_width), D),
           "w_gate": dense(ks[at[1]], (L, E, D, Fe), D),
           "w_up": dense(ks[at[2]], (L, E, D, Fe), D),
           "w_down": dense(ks[at[3]], (L, E, Fe, D), Fe)}
    if c.router_type in ("sigmoid_bias", "softmax_bias"):
        # The per-expert correction bias added to the scores for the
        # CHOICE only. A trained model's bias evens the load; training
        # starts it at 0. Named ``*_norm`` because, like a norm's gain,
        # a seeded-weights filler has to leave it a constant: drawn at
        # random it would decide the choice in place of the scores.
        out["router_bias_norm"] = jnp.zeros((L, c.router_width),
                                            jnp.float32)
    if c.num_shared_experts:
        Fs = c.num_shared_experts * Fe
        kk = jax.random.split(ks[at[4]], 3)
        out["ws_gate"] = dense(kk[0], (L, D, Fs), D)
        out["ws_up"] = dense(kk[1], (L, D, Fs), D)
        out["ws_down"] = dense(kk[2], (L, Fs, D), Fs)
    return out


def _init_layer_stack(c: ModelConfig, key: jax.Array, L: int,
                      expert: bool) -> Dict[str, jax.Array]:
    """One stack of ``L`` layers of the same structure, every leaf with a
    leading L axis. ``expert``: routed experts (+ shared expert) in place
    of the dense SwiGLU. Every matrix is stored ``(..., fan_in, fan_out)``.

    A shortcut block (``c.shortcut_moe``) has two attention sublayers and
    two dense FFNs a layer: ``sub0`` and ``sub1`` are each a dense layer's
    leaves (norms, attention, ``w_gate / w_up / w_down`` of
    ``intermediate_size``), and beside them sit the expert layer's
    (``router`` as wide as the router, ``router_bias_norm``, the banks of
    the experts held) (``_shortcut_block``). Leaves of their own, not one
    leaf with a sublayer axis: a layer's slice of such a leaf has two
    readers, and XLA:TPU then copies the slice out every layer (PERF.md
    section 6, PR 37)."""
    def dense(key, shape, fan_in):
        # Generate directly in the target dtype: the fp32-then-cast
        # pattern materializes an fp32 transient of every stacked tensor
        # (5.8 GB for deepseek-6.7b's w_gate alone), OOMing a 16 GB chip
        # whose bf16 weights otherwise fit.
        scale = jnp.asarray(1.0 / float(fan_in) ** 0.5, c.dtype)
        return jax.random.normal(key, shape, c.dtype) * scale

    D, F = c.hidden_size, c.intermediate_size
    ks = jax.random.split(key, 8)
    if c.shortcut_moe and not (c.mla and expert and not c.hc_mult
                               and not c.ssm and not c.num_shared_experts):
        raise ValueError(
            f"{c.name}: a shortcut block is two latent-attention sublayers, "
            f"two dense FFNs and routed experts, on the plain residual")

    def experts():
        return _init_expert_leaves(c, L, dense, ks, (7, 4, 5, 6, 1))

    if c.shortcut_moe:
        plain = dataclasses.replace(c, shortcut_moe=False)
        return {**experts(), **{
            f"sub{i}": _init_layer_stack(
                plain, jax.random.fold_in(key, 300 + i), L, expert=False)
            for i in range(2)}}
    layers = {"attn_norm": jnp.ones((L, D), c.dtype),
              "mlp_norm": jnp.ones((L, D), c.dtype)}
    if c.mla:
        if c.q_lora_rank <= 0 or c.head_dim != (c.qk_nope_head_dim
                                                + c.qk_rope_head_dim):
            raise ValueError(
                f"{c.name}: latent attention needs q_lora_rank > 0 and "
                f"head_dim == qk_nope_head_dim + qk_rope_head_dim")
        ka = jax.random.split(ks[0], 4)
        H, rq, rkv = c.num_heads, c.q_lora_rank, c.kv_lora_rank
        layers.update(
            wq_a=dense(ka[0], (L, D, rq), D),
            q_a_norm=jnp.ones((L, rq), c.dtype),
            wq_b=dense(ka[1], (L, rq, H * c.head_dim), rq),
            wkv_a=dense(ka[2], (L, D, c.latent_dim), D),
            kv_a_norm=jnp.ones((L, rkv), c.dtype),
            wkv_b=dense(ka[3], (L, rkv, H * (c.qk_nope_head_dim
                                             + c.v_head_dim)), rkv),
            wo=dense(ks[3], (L, H * c.v_head_dim, D), H * c.v_head_dim))
    else:
        layers.update(
            wq=dense(ks[0], (L, D, c.q_dim), D),
            wk=dense(ks[1], (L, D, c.kv_dim), D),
            wv=dense(ks[2], (L, D, c.kv_dim), D),
            wo=dense(ks[3], (L, c.q_dim, D), c.q_dim))
    if expert:
        layers.update(experts())
    else:
        layers["w_gate"] = dense(ks[4], (L, D, F), D)
        layers["w_up"] = dense(ks[5], (L, D, F), D)
        layers["w_down"] = dense(ks[6], (L, F, D), F)
    if c.qkv_bias and not c.mla:
        layers["bq"] = jnp.zeros((L, c.q_dim), c.dtype)
        layers["bk"] = jnp.zeros((L, c.kv_dim), c.dtype)
        layers["bv"] = jnp.zeros((L, c.kv_dim), c.dtype)
    if c.qk_norm and not c.mla:
        layers["q_norm"] = jnp.ones((L, c.head_dim), c.dtype)
        layers["k_norm"] = jnp.ones((L, c.head_dim), c.dtype)
    if c.ssm:
        # The state-space mixer's leaves (``_ssm_project`` .. ``_ssm_out``),
        # Mamba-2's own start: a step size dt log-uniform on [1e-3, 1e-1]
        # behind the softplus, A = -exp(A_log) uniform on [-16, -1], so a
        # token decays a head's state by exp(dt A) in [0.2, 0.999]; the
        # skip D 1; the conv as a depthwise Conv1d starts. dt_bias, A_log
        # and D are float32 whatever the serving dtype: they set decays.
        if c.mamba_d_ssm != c.mamba_n_heads * c.mamba_d_head or (
                c.mamba_n_heads % c.mamba_n_groups):
            raise ValueError(
                f"{c.name}: mamba_d_ssm {c.mamba_d_ssm} != mamba_n_heads x "
                f"mamba_d_head, or heads not divisible by mamba_n_groups")
        km = [jax.random.fold_in(key, 200 + i) for i in range(6)]
        I, H, K = c.mamba_d_ssm, c.mamba_n_heads, c.mamba_d_conv
        bound = 1.0 / float(K) ** 0.5
        dt = jnp.exp(jax.random.uniform(
            km[4], (L, H), jnp.float32, math.log(1e-3), math.log(1e-1)))
        layers.update(
            ssm_in=dense(km[0], (L, D, c.ssm_proj_dim), D),
            ssm_out=dense(km[1], (L, I, D), I),
            ssm_conv_w=jax.random.uniform(
                km[2], (L, K, c.ssm_conv_dim), c.dtype, -bound, bound),
            ssm_conv_b=jax.random.uniform(
                km[3], (L, c.ssm_conv_dim), c.dtype, -bound, bound),
            ssm_dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
            ssm_A_log=jnp.log(jax.random.uniform(
                km[5], (L, H), jnp.float32, 1.0, 16.0)),
            ssm_D=jnp.ones((L, H), jnp.float32),
            ssm_norm=jnp.ones((L, I), c.dtype))
    if c.hc_mult:
        # The residual operator's leaves (``_residual``), one set a
        # sublayer, all float32: the projection phi like any matrix; the
        # three gates alpha 0, so that the maps are their biases alone;
        # biases that read the mean of the rows (sum H_pre = 1), write the
        # sublayer's output to every row (H_post = 1) and mix the rows
        # evenly. Rows that start equal then stay equal and a fresh model
        # computes the plain model's function. The gates' name ends in
        # ``norm`` for the reason ``router_bias_norm``'s does: a
        # seeded-weights filler leaves them a constant.
        n, m = c.hc_mult, c.hc_maps
        b_pre = -math.log(n - 1.0) if n > 1 else 30.0
        bias = jnp.concatenate([jnp.full((n,), b_pre, jnp.float32),
                                jnp.zeros((m - n,), jnp.float32)])
        for i, sub in enumerate(("attn", "mlp")):
            kp = jax.random.fold_in(key, 100 + i)
            layers[f"{sub}_hc_phi"] = (
                jax.random.normal(kp, (L, n * D, m), jnp.float32)
                / float(n * D) ** 0.5)
            layers[f"{sub}_hc_gate_norm"] = jnp.zeros((L, 3), jnp.float32)
            layers[f"{sub}_hc_bias"] = jnp.broadcast_to(bias, (L, 1, m))
    return layers


def init_params(config: ModelConfig, key: jax.Array) -> Params:
    """Random init (normal / sqrt(fan_in)); layer params stacked on axis 0.

    ``params["layers"]`` is the model's main stack. A configuration with
    ``first_dense_layers`` leading dense-FFN layers before its expert
    layers has those in a second stack, ``params["dense_layers"]``, of the
    same attention structure; the forward scans it first."""
    c = config
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    D = c.hidden_size
    if c.pattern:
        params = _init_pattern_params(c, k_embed, k_layers)
    else:
        n_dense = c.first_dense_layers if c.num_experts > 0 else 0
        if not 0 <= n_dense < c.num_layers:
            raise ValueError(f"{c.name}: first_dense_layers {n_dense} of "
                             f"{c.num_layers} layers")
        params = {
            "embed": (jax.random.normal(k_embed, (c.vocab_size, D), c.dtype)
                      * jnp.asarray(0.02, c.dtype)),
            "layers": _init_layer_stack(c, k_layers, c.num_layers - n_dense,
                                        expert=c.num_experts > 0),
            "final_norm": jnp.ones((D,), c.dtype),
        }
        if n_dense:
            params["dense_layers"] = _init_layer_stack(
                c, jax.random.fold_in(k_layers, 1), n_dense, expert=False)
    if not c.tie_word_embeddings:
        params["lm_head"] = (
            jax.random.normal(k_head, (D, c.vocab_size), c.dtype)
            * jnp.asarray(1.0 / float(D) ** 0.5, c.dtype))
    return params


def _init_pattern_params(c: ModelConfig, k_embed: jax.Array,
                         k_layers: jax.Array) -> Params:
    """``init_params`` for a configuration of unlike layers
    (``c.layer_types``): ``params["layers"]["seg<i>"][key]`` holds the
    leaves of one layer of segment i's period (``pattern_keys``: the kind,
    numbered where the period names it more than once), stacked
    ``repeats`` deep, and each kind has only its own: every kind the two
    norms (with biases under LayerNorm) and the second sublayer, a SwiGLU
    MLP or where the configuration has experts the expert layer
    (``_init_expert_leaves``); "mamba" the mixer's seven (``_mamba1_mix``),
    at Mamba-1's own start (A = -(1..N), a step size log-uniform on [1e-3,
    1e-1] behind the softplus, D 1); "window" and "full" the q/k/v/o
    projections and, under differential attention, the four lambda vectors
    as the columns of one ``(head_dim, 4)`` leaf (normal * 0.1) and the
    sub-norm's gain, else under ``attn_out_gate`` the gate's projection;
    "cross" a query and an output projection with its own lambda and
    sub-norm; "gmu" two matrices; "kda" the delta-rule mixer's
    (``_kda_project`` .. ``_kda_out``): ``kda_in`` = [W_q | W_k | W_v], the
    conv's taps over those channels (no bias), the decay's and the output
    gate's bottlenecks, ``kda_beta``, the output norm's gain a head, and in
    float32 whatever the serving dtype ``kda_A_log`` (A uniform on [1, 16])
    and ``kda_dt_bias`` (a step log-uniform on [1e-3, 1e-1] behind the
    softplus). ``final_norm_bias`` is ``(1, D)``, as the decay's two
    vectors are ``(L, 1, .)``: a seeded-weights filler that reads a leaf's
    fan-in off its second-to-last axis (``benchmark/weights.py``) then has
    one to read."""
    D, F, I = c.hidden_size, c.intermediate_size, c.mamba_d_ssm
    kinds = {kind for period, _ in c.layer_types for kind in period}
    if (sum(len(p) * n for p, n in c.layer_types) != c.num_layers
            or kinds - set(LAYER_KINDS)):
        raise ValueError(f"{c.name}: layer_types {c.layer_types} is not "
                         f"{c.num_layers} layers, each one of {LAYER_KINDS}")
    if c.hc_mult or c.mla or c.shortcut_moe or c.first_dense_layers or (
            c.num_experts and c.router_type == "softmax"):
        raise ValueError(
            f"{c.name}: a layer pattern runs on the plain residual, without "
            f"latent attention, a shortcut block or leading dense layers, "
            f"and its experts under a bias router (no aux loss is carried)")
    if not c.diff_attn and kinds & {"window", "cross"}:
        raise ValueError(
            f"{c.name}: \"window\" and \"cross\" layers are differential "
            f"attention (diff_attn); plain attention is a \"full\" layer")
    if "kda" in kinds and ("mamba" in kinds or not (
            c.kda_num_heads and c.kda_head_dim and c.kda_rank
            and c.kda_conv > 1)):
        raise ValueError(f"{c.name}: a \"kda\" layer needs kda_num_heads, "
                         f"kda_head_dim, kda_rank and kda_conv > 1, and no "
                         f"\"mamba\" layer beside it (the pool has one "
                         f"state leaf)")

    def dense(key, shape, fan_in):
        scale = jnp.asarray(1.0 / float(fan_in) ** 0.5, c.dtype)
        return jax.random.normal(key, shape, c.dtype) * scale

    def norm(lp, name, L):
        lp[name] = jnp.ones((L, D), c.dtype)
        if c.norm == "layer":
            lp[name + "_bias"] = jnp.zeros((L, D), c.dtype)

    def diff(lp, ks, L):
        lp["attn_lambda"] = 0.1 * jax.random.normal(
            ks, (L, c.head_dim, 4), jnp.float32)
        lp["attn_sub_norm"] = jnp.ones((L, 2 * c.head_dim), c.dtype)

    def one_kind(kind, key, L):
        ks = jax.random.split(key, 12)
        if c.num_experts:
            lp = _init_expert_leaves(c, L, dense, ks, (10, 0, 1, 2, 11))
        else:
            lp = {"w_gate": dense(ks[0], (L, D, F), D),
                  "w_up": dense(ks[1], (L, D, F), D),
                  "w_down": dense(ks[2], (L, F, D), F)}
        norm(lp, "attn_norm", L)
        norm(lp, "mlp_norm", L)
        if kind == "mamba":
            N, K, R = c.mamba_d_state, c.mamba_d_conv, c.mamba_dt_rank
            bound = 1.0 / float(K) ** 0.5
            dt = jnp.exp(jax.random.uniform(
                ks[7], (L, I), jnp.float32, math.log(1e-3), math.log(1e-1)))
            lp.update(
                ssm_in=dense(ks[3], (L, D, 2 * I), D),
                ssm_conv_w=jax.random.uniform(ks[4], (L, K, I), c.dtype,
                                              -bound, bound),
                ssm_conv_b=jax.random.uniform(ks[5], (L, I), c.dtype,
                                              -bound, bound),
                ssm_x=dense(ks[6], (L, I, R + 2 * N), I),
                ssm_dt=dense(ks[8], (L, R, I), R),
                ssm_dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                ssm_A_log=jnp.broadcast_to(
                    jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)),
                    (L, I, N)),
                ssm_D=jnp.ones((L, I), jnp.float32),
                ssm_out=dense(ks[9], (L, I, D), I))
        elif kind in ("window", "full"):
            lp.update(wq=dense(ks[3], (L, D, c.q_dim), D),
                      wk=dense(ks[4], (L, D, c.kv_dim), D),
                      wv=dense(ks[5], (L, D, c.kv_dim), D),
                      wo=dense(ks[6], (L, c.q_dim, D), c.q_dim))
            if c.diff_attn:
                diff(lp, ks[7], L)
            elif c.attn_out_gate:
                lp["w_attn_gate"] = dense(ks[7], (L, D, c.q_dim), D)
        elif kind == "cross":
            lp.update(wq=dense(ks[3], (L, D, c.q_dim), D),
                      wo=dense(ks[6], (L, c.q_dim, D), c.q_dim))
            diff(lp, ks[7], L)
        elif kind == "kda":
            H, W, R, K = c.kda_num_heads, c.kda_dim, c.kda_rank, c.kda_conv
            kk = jax.random.split(ks[3], 6)
            bound = 1.0 / float(K) ** 0.5
            dt = jnp.exp(jax.random.uniform(
                ks[7], (L, 1, W), jnp.float32, math.log(1e-3),
                math.log(1e-1)))
            lp.update(
                kda_in=dense(kk[0], (L, D, 3 * W), D),
                kda_conv_w=jax.random.uniform(ks[4], (L, K, 3 * W), c.dtype,
                                              -bound, bound),
                kda_f_down=dense(kk[1], (L, D, R), D),
                kda_f_up=dense(kk[2], (L, R, W), R),
                kda_dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                kda_A_log=jnp.log(jax.random.uniform(
                    ks[5], (L, 1, H), jnp.float32, 1.0, 16.0)),
                kda_beta=dense(kk[3], (L, D, H), D),
                kda_g_down=dense(kk[4], (L, D, R), D),
                kda_g_up=dense(kk[5], (L, R, W), R),
                kda_g_bias=jnp.zeros((L, 1, W), c.dtype),
                kda_o_norm=jnp.ones((L, c.kda_head_dim), c.dtype),
                kda_out=dense(ks[6], (L, W, D), W))
        else:       # "gmu"
            lp.update(gmu_in=dense(ks[3], (L, D, I), D),
                      gmu_out=dense(ks[4], (L, I, D), I))
        return lp

    params: Params = {
        "embed": (jax.random.normal(k_embed, (c.vocab_size, D), c.dtype)
                  * jnp.asarray(0.02, c.dtype)),
        "layers": {
            f"seg{i}": {name: one_kind(
                kind, jax.random.fold_in(k_layers, 16 * i + j), n)
                for j, (name, kind) in enumerate(zip(pattern_keys(period),
                                                     period))}
            for i, (period, n) in enumerate(c.layer_types)},
        "final_norm": jnp.ones((D,), c.dtype)}
    if c.norm == "layer":
        params["final_norm_bias"] = jnp.zeros((1, D), c.dtype)
    return params


def _norm(c: ModelConfig, x: jax.Array, lp: Dict[str, jax.Array],
          name: str) -> jax.Array:
    """The configuration's norm with leaf ``name`` (and, under LayerNorm,
    ``name_bias``)."""
    if c.norm == "layer":
        return layer_norm(x, lp[name], lp[name + "_bias"], c.rms_norm_eps)
    return rms_norm(x, lp[name], c.rms_norm_eps)


def _dense(h: jax.Array, lp: Dict[str, jax.Array], name: str,
           spec: str) -> jax.Array:
    """``einsum(spec, h, lp[name])`` with transparent weight-only int8.

    When the stored weight is int8 (see ``models.quantize``), the matmul
    upcasts it in-compute and applies the per-output-channel scale to the
    (much smaller) output. Small-batch decode streams every weight byte
    once per step, so halving those bytes halves that bound (see
    ``models.quantize``); the scale multiply is an elementwise epilogue
    XLA fuses into the dot."""
    w = lp[name]
    if w.dtype == jnp.int8:
        out = jnp.einsum(spec, h, w.astype(h.dtype))
        out = (out.astype(jnp.float32)
               * lp[name + "_scale"]).astype(h.dtype)
    else:
        out = jnp.einsum(spec, h, w)
    la = lp.get(name + "_lora_a")
    if la is not None:
        # Low-rank adapter (training/lora.py): y += (h @ A) @ B, with
        # the alpha/rank scaling baked into A at merge time. Factored
        # order keeps the FLOPs O(r·(in+out)) instead of materializing
        # the (in, out) delta; works over an int8 base (QLoRA-style).
        lb = lp[name + "_lora_b"]
        out = out + jnp.einsum("bsr,ro->bso",
                               jnp.einsum("bsi,ir->bsr", h, la), lb)
    return out


def _adapter_delta(h: jax.Array, adapters, adapter_ids, name: str):
    """Gathered multi-LoRA delta for a flat token batch: each row t
    applies ITS adapter's factors, ``B[ids[t]] @ (A[ids[t]] @ h[t])``.

    ``adapters`` is the pool's rank ladder (rollout/adapter_pool.py) —
    one bank dict per rung, each leaf ``(slots+1, d_in, r)`` /
    ``(slots+1, r, d_out)`` after the layer scan consumes the leading
    L axis — and ``adapter_ids`` the matching per-rung ``(T,)`` slot
    vectors. Slot 0 of every rung is the permanent null adapter
    (A = B = 0), so base-only rows contribute exact zeros and the sum
    over rungs needs no masking: a row is non-null in at most one
    rung. Returns None when no bank carries this target."""
    out = None
    for bank, ids in zip(adapters, adapter_ids):
        a = bank.get(name + "_lora_a")
        if a is None:
            continue
        b = bank[name + "_lora_b"]
        d = jnp.einsum("tsr,tro->tso",
                       jnp.einsum("tsi,tir->tsr", h, a[ids]), b[ids])
        out = d if out is None else out + d
    return out


def _with_adapter(out: jax.Array, h: jax.Array, adapters, adapter_ids,
                  name: str) -> jax.Array:
    if adapters is None:
        return out
    d = _adapter_delta(h, adapters, adapter_ids, name)
    return out if d is None else out + d


def _qkv(c: ModelConfig, lp: Dict[str, jax.Array], h: jax.Array,
         cos: jax.Array, sin: jax.Array, adapters=None, adapter_ids=None):
    """Project + rotate. h: (B, S, D) → q (B,S,Hq,Dh), k/v (B,S,Hkv,Dh)."""
    b, s, _ = h.shape
    q = _dense(h, lp, "wq", "bsd,de->bse")
    k = _dense(h, lp, "wk", "bsd,de->bse")
    v = _dense(h, lp, "wv", "bsd,de->bse")
    # Per-row adapter deltas land where the merged-LoRA ``_dense`` hook
    # would: after the base matmul, before bias/reshape/norm/rope.
    q = _with_adapter(q, h, adapters, adapter_ids, "wq")
    k = _with_adapter(k, h, adapters, adapter_ids, "wk")
    v = _with_adapter(v, h, adapters, adapter_ids, "wv")
    k = _times(k, c.key_multiplier)
    if c.qkv_bias:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = q.reshape(b, s, c.num_heads, c.head_dim)
    k = k.reshape(b, s, c.num_kv_heads, c.head_dim)
    if c.qk_norm:
        # Qwen3: per-head RMSNorm over head_dim BEFORE RoPE
        q = rms_norm(q, lp["q_norm"], c.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], c.rms_norm_eps)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    v = v.reshape(b, s, c.num_kv_heads, c.head_dim)
    return q, k, v


def _mla_project(c: ModelConfig, lp: Dict[str, jax.Array], h: jax.Array,
                 cos: jax.Array, sin: jax.Array):
    """Latent-attention projections. h (B, S, D) -> q_nope (B, S, H, nope),
    q_rope (B, S, H, rope) rotated, and the row the cache holds for each
    token, ``latent`` (B, S, kv_lora_rank + rope) = [RMSNorm(c) | RoPE(k_r)]:
    one compressed vector for all heads' keys and values, and one rotary
    key shared by all heads. Where the configuration sets them
    (``ModelConfig.mla_scales``) the queries are multiplied by s_q and the
    normed latent by s_kv; the rotary key is not scaled."""
    b, s, _ = h.shape
    nope, r = c.qk_nope_head_dim, c.kv_lora_rank
    s_q, s_kv = c.mla_scales
    with jax.named_scope("attn.q_latent"):
        cq = rms_norm(_dense(h, lp, "wq_a", "bsd,dr->bsr"), lp["q_a_norm"],
                      c.rms_norm_eps)
        q = _times(_dense(cq, lp, "wq_b", "bsr,re->bse"), s_q).reshape(
            b, s, c.num_heads, c.head_dim)
        q_nope = q[..., :nope]
        q_rope = apply_rope(q[..., nope:], cos, sin)
    with jax.named_scope("attn.kv_latent"):
        ckr = _dense(h, lp, "wkv_a", "bsd,dr->bsr")
        c_kv = _times(rms_norm(ckr[..., :r], lp["kv_a_norm"],
                               c.rms_norm_eps), s_kv)
        k_rope = apply_rope(ckr[..., None, r:], cos, sin)[..., 0, :]
        latent = jnp.concatenate([c_kv, k_rope], axis=-1)
    return q_nope, q_rope, latent


def _mla_up_weights(c: ModelConfig, lp: Dict[str, jax.Array]):
    """``wkv_b`` (r, H * (nope + v)) by head: W_kb (r, H, nope), W_vb
    (r, H, v)."""
    w = lp["wkv_b"].reshape(c.kv_lora_rank, c.num_heads,
                            c.qk_nope_head_dim + c.v_head_dim)
    return w[..., :c.qk_nope_head_dim], w[..., c.qk_nope_head_dim:]


def _mla_self_attention(c: ModelConfig, lp: Dict[str, jax.Array],
                        h: jax.Array, cos, sin, kv_mask):
    """No-cache latent attention in the EXPANDED form: every position's
    per-head keys and values are made from its latent, then plain causal
    attention with q/k width nope + rope and value width ``v_head_dim``.
    h (B, S, D) -> (B, S, H * v). The trainer's and the scorer's path; the
    paged engine reads its cache in the absorbed form (``_paged_mla_attend``)."""
    b, s, _ = h.shape
    r = c.kv_lora_rank
    q_nope, q_rope, latent = _mla_project(c, lp, h, cos, sin)
    w_kb, w_vb = _mla_up_weights(c, lp)
    c_kv, k_rope = latent[..., :r], latent[..., r:]
    exact = h.dtype == jnp.float32
    prec = jax.lax.Precision.HIGHEST if exact else None
    k_nope = jnp.einsum("bsr,rhn->bshn", c_kv, w_kb, precision=prec)
    v = jnp.einsum("bsr,rhv->bshv", c_kv, w_vb, precision=prec)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (b, s, c.num_heads, k_rope.shape[-1]))],
        axis=-1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=prec,
                        preferred_element_type=jnp.float32)
    scores = scores * c.attn_scale
    mask = jnp.tril(jnp.ones((s, s), bool))[None, None]
    if kv_mask is not None:
        mask = mask & kv_mask[:, None, None, :]
    probs = jax.nn.softmax(jnp.where(mask, scores, NEG_INF), axis=-1)
    out = jnp.einsum("bhqk,bkhv->bqhv", probs.astype(v.dtype), v,
                     precision=prec, preferred_element_type=jnp.float32)
    return out.reshape(b, s, c.num_heads * c.v_head_dim).astype(h.dtype)


def _self_attention(c: ModelConfig, q, k, v, kv_mask, mesh):
    """No-cache attention dispatch per ``c.attn_impl`` (training/scoring
    path). q (B,S,Hq,Dh), k/v (B,S,Hkv,Dh) → (B,S,Hq,Dh)."""
    if c.attn_impl == "einsum":
        return attention(q, k, v, q_offset=0, kv_mask=kv_mask, causal=True,
                         window=c.sliding_window)
    if c.attn_impl == "flash":
        from ..ops.flash_attention import flash_attention
        return flash_attention(q, k, v, q_offset=0, kv_mask=kv_mask,
                               causal=True, window=c.sliding_window)
    if c.sliding_window is not None:
        raise NotImplementedError(
            f"sliding_window is implemented for attn_impl='einsum'/'flash' "
            f"only (got {c.attn_impl!r}); the ring kernels would silently "
            f"attend outside the window")
    if c.attn_impl in ("ring", "ulysses"):
        from ..parallel.ring_attention import (make_ring_attention,
                                               make_ulysses_attention)
        if mesh is None or "sp" not in mesh.axis_names:
            raise ValueError(
                f"attn_impl={c.attn_impl!r} needs forward(mesh=...) with an "
                f"'sp' axis; got {mesh}")
        if c.attn_impl == "ulysses":
            if kv_mask is not None:
                raise NotImplementedError(
                    "ulysses attention does not take a kv mask; pre-mask "
                    "k/v or use attn_impl='ring'")
            return make_ulysses_attention(mesh)(q, k, v)
        if kv_mask is not None:
            return make_ring_attention(mesh, with_mask=True)(q, k, v, kv_mask)
        return make_ring_attention(mesh)(q, k, v)
    raise ValueError(f"unknown attn_impl {c.attn_impl!r}; expected "
                     f"einsum|flash|ring|ulysses")


def _cache_attention(c: ModelConfig, q, k_full, v_full, length, kv_mask):
    """Cache-path attention: einsum over the whole cache.

    Ring caches (SWA): ``kv_mask`` arrives as the full per-query
    (B, Sq, cap) validity mask — fill, causality, and window are all
    baked in by ``_forward_impl`` in ring coordinates, so the positional
    causal/window mask here must be OFF (ring index != absolute
    position)."""
    if _is_ring(c, k_full.shape[1]):
        return attention(q, k_full, v_full, kv_mask=kv_mask, causal=False)
    return attention(q, k_full, v_full, q_offset=length, kv_mask=kv_mask,
                     causal=True, window=c.sliding_window)


def _times(x: jax.Array, m: float) -> jax.Array:
    """x times a published multiplier: a constant of the forward. At 1
    nothing is added to the program."""
    return x if m == 1.0 else x * m


def _ssm_project(c: ModelConfig, lp: Dict[str, jax.Array], h: jax.Array):
    """The mixer's input projection of the block's normed input h
    (..., D): ``[z | xBC | dt] = (ssm_in_multiplier h) W_in`` with
    ``ssm_multipliers`` on its five parts (z, x, B, C, dt). -> z (..., I)
    the gate, xbc (..., I + 2 G N) the conv's input, dt (..., H) float32
    before the bias and the softplus."""
    i, gn = c.mamba_d_ssm, c.mamba_n_groups * c.mamba_d_state
    with jax.named_scope("ssm.in_proj"):
        p = _dense(_times(h, c.ssm_in_multiplier), lp, "ssm_in",
                   "bsd,de->bse")
        if any(m != 1.0 for m in c.ssm_multipliers):
            mz, mx, mb, mc, mdt = c.ssm_multipliers
            vec = np.concatenate([
                np.full(i, mz), np.full(i, mx), np.full(gn, mb),
                np.full(gn, mc), np.full(c.mamba_n_heads, mdt)])
            p = (p.astype(jnp.float32)
                 * jnp.asarray(vec, jnp.float32)).astype(p.dtype)
    return (p[..., :i], p[..., i:i + c.ssm_conv_dim],
            p[..., i + c.ssm_conv_dim:].astype(jnp.float32))


def _ssm_split(c: ModelConfig, lp: Dict[str, jax.Array], conv_out: jax.Array,
               dt: jax.Array, dtype):
    """The conv's output (..., I + 2 G N) f32 and the raw dt (..., H) ->
    what the scan reads: x (..., H, P), B and C (..., G, N) in ``dtype``
    after the silu; dt = softplus(dt + dt_bias) and A = -exp(A_log) in
    float32 (no clamp on dt: the configuration publishes none)."""
    i, g, n = c.mamba_d_ssm, c.mamba_n_groups, c.mamba_d_state
    xbc = jax.nn.silu(conv_out).astype(dtype)
    lead = xbc.shape[:-1]
    x = xbc[..., :i].reshape(lead + (c.mamba_n_heads, c.mamba_d_head))
    b = xbc[..., i:i + g * n].reshape(lead + (g, n))
    cc = xbc[..., i + g * n:].reshape(lead + (g, n))
    dt = jax.nn.softplus(dt + lp["ssm_dt_bias"])
    return x, b, cc, dt, -jnp.exp(lp["ssm_A_log"])


def _ssm_out(c: ModelConfig, lp: Dict[str, jax.Array], y: jax.Array,
             z: jax.Array) -> jax.Array:
    """The scan's output y (..., H, P) f32 and the gate z (..., I) -> the
    mixer's output (..., D): gated grouped RMSNorm, output projection."""
    with jax.named_scope("ssm.gate_norm"):
        g = ssm_ops.gated_norm(y.reshape(z.shape), z, lp["ssm_norm"],
                               c.mamba_n_groups, c.rms_norm_eps, z.dtype)
    with jax.named_scope("ssm.out_proj"):
        return _dense(g, lp, "ssm_out", "bse,ed->bsd")


def _ssm_mix(c: ModelConfig, lp: Dict[str, jax.Array],
             h: jax.Array) -> jax.Array:
    """The state-space mixer over whole sequences from a zero state: h
    (B, S, D), the block's normed input -> (B, S, D)."""
    z, xbc, dt = _ssm_project(c, lp, h)
    with jax.named_scope("ssm.conv"):
        conv = ssm_ops.conv_dense(xbc, lp["ssm_conv_w"], lp["ssm_conv_b"])
        x, b, cc, dt, a = _ssm_split(c, lp, conv, dt, h.dtype)
    with jax.named_scope("ssm.scan"):
        y = ssm_ops.scan_dense(x, dt, a, b, cc, lp["ssm_D"])
    return _ssm_out(c, lp, y, z)


def _paged_ssm_mix(c: ModelConfig, lp: Dict[str, jax.Array], h: jax.Array,
                   rows: Tuple[jax.Array, jax.Array], layer: jax.Array,
                   seq_row: jax.Array, run_plan):
    """``_ssm_mix`` over the flat batch and the pool's row-addressed
    leaves: h (T, 1, D); ``rows`` = (ssm (L, rows, H, P, N) f32, conv
    (L, rows, K-1, C)), read and written at ``(layer, row)`` once a run
    (``ops/ssm.py``); ``run_plan`` is ``ops.ssm.plan_runs`` of this batch.
    -> ((T, 1, D), rows')."""
    state, window = rows
    z, xbc, dt = _ssm_project(c, lp, h)
    with jax.named_scope("ssm.conv"):
        conv, window = ssm_ops.conv_flat(
            xbc[:, 0], lp["ssm_conv_w"], lp["ssm_conv_b"], window, layer,
            seq_row, run_plan)
        x, b, cc, dt, a = _ssm_split(c, lp, conv, dt[:, 0], h.dtype)
    with jax.named_scope("ssm.scan"):
        y, state = ssm_ops.scan_flat(x, dt, a, b, cc, lp["ssm_D"], state,
                                     layer, seq_row, run_plan)
    return _ssm_out(c, lp, y[:, None], z), (state, window)


def _mixers(c: ModelConfig, lp: Dict[str, jax.Array], x: jax.Array,
            attend: Callable, mix: Callable):
    """The first sublayer of a block that has a state-space mixer beside
    attention: both read ONE normed input and their scaled outputs are
    summed, ``ssm_out_multiplier SSM(h) + attention_out_multiplier
    Attn(attention_in_multiplier h)``. ``attend(h)`` and ``mix(h)`` each
    return (output, what else it yields). -> (the sum, (attention's,
    the mixer's))."""
    h = rms_norm(x, lp["attn_norm"], c.rms_norm_eps)
    attn, kv = attend(_times(h, c.attention_in_multiplier))
    mixed, state = mix(h)
    return (_times(attn, c.attention_out_multiplier)
            + _times(mixed, c.ssm_out_multiplier)), (kv, state)


def sinkhorn(logits: jax.Array, c: ModelConfig) -> jax.Array:
    """(..., n, n) float32 logits -> ``c.hc_sinkhorn_iters`` rounds of
    Sinkhorn-Knopp on ``exp(clip(logits))``: each round divides every
    column by its sum + ``hc_eps``, then every row by its sum + ``hc_eps``.

    The matrix is taken apart into its n x n entries, each an array over
    the tokens, and the rounds are unrolled over them: every sum is n - 1
    adds of equal shapes, so the whole chain is elementwise and XLA:TPU
    cuts it into about seven fusions. Written with ``sum(axis)`` each of
    the 40 sums is a reduction, a fusion of its own: 80 launches a
    sublayer (compiled for the v5e, PERF.md section 4). XLA's CPU backend
    takes ~16 s to compile the 20 unrolled rounds: tests that compile a
    model use fewer (``tiny_xing_mhc_test``)."""
    n = logits.shape[-1]
    e = jnp.exp(jnp.clip(logits, c.mhc_h_res_clamp_min,
                         c.mhc_h_res_clamp_max))
    m = [[e[..., i, j] for j in range(n)] for i in range(n)]
    for _ in range(c.hc_sinkhorn_iters):
        col = [sum(m[i][j] for i in range(n)) + c.hc_eps for j in range(n)]
        m = [[m[i][j] / col[j] for j in range(n)] for i in range(n)]
        row = [sum(m[i]) + c.hc_eps for i in range(n)]
        m = [[m[i][j] / row[i] for j in range(n)] for i in range(n)]
    return jnp.stack([jnp.stack(r, axis=-1) for r in m], axis=-2)


def _stream_maps(c: ModelConfig, lp: Dict[str, jax.Array], x: jax.Array,
                 sub: str):
    """The three maps of one sublayer from the token's own stream x
    (..., n, D), in float32 whatever the stream's dtype: H_pre (..., n) in
    (0, 1), H_post (..., n) in (0, 2), H_res (..., n, n) doubly stochastic
    (``sinkhorn``), and the largest distance of a row or column sum of any
    H_res from 1. The gain-free RMSNorm over all n * D values is a scalar
    a token, applied to the 24 projected values instead of the stream."""
    n = c.hc_mult
    flat = x.reshape(x.shape[:-2] + (n * x.shape[-1],)).astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                        + c.rms_norm_eps)
    u = jnp.einsum("...k,km->...m", flat, lp[f"{sub}_hc_phi"],
                   precision=jax.lax.Precision.HIGHEST) * inv
    gate, bias = lp[f"{sub}_hc_gate_norm"], lp[f"{sub}_hc_bias"][0]
    h_pre = jax.nn.sigmoid(u[..., :n] * gate[0] + bias[:n])
    h_post = 2.0 * jax.nn.sigmoid(u[..., n:2 * n] * gate[1]
                                  + bias[n:2 * n])
    res = u[..., 2 * n:] * gate[2] + bias[2 * n:]
    h_res = sinkhorn(res.reshape(res.shape[:-1] + (n, n)), c)
    off = [jnp.abs(sum(h_res[..., i, j] for j in range(n)) - 1.0)
           for i in range(n)]
    off += [jnp.abs(sum(h_res[..., i, j] for i in range(n)) - 1.0)
            for j in range(n)]
    return h_pre, h_post, h_res, functools.reduce(jnp.maximum, off).max()


def _residual(c: ModelConfig, lp: Dict[str, jax.Array], x: jax.Array,
              sub: str, f: Callable):
    """One sublayer through the residual path, the only place a layer
    adds to it. ``f`` is the sublayer with its norm: its input (..., D) ->
    (its output (..., D), whatever else it yields). Returns (x', that, the
    Sinkhorn error of this sublayer's H_res — None on the plain path).

    ``hc_mult == 0``: ``x + f(x)``.

    Otherwise x is the stream (..., n, D) and ``lp[sub + "_hc_*"]`` the
    sublayer's maps (manifold-constrained hyper-connections):

        y  = f(sum_j H_pre[j] x[j])
        x'[i] = sum_j H_res[i, j] x[j] + H_post[i] y

    The maps are float32 (``_stream_maps``) and so are the two mixes,
    written as n (n + 1) scaled adds over the rows so that each is one
    elementwise pass over the stream, not a product the MXU would round to
    bf16; the stream itself is held in x's dtype. The scope names
    (``mhc.maps``, ``mhc.pre``, ``mhc.post``) are docs/observability.md's.
    """
    if not c.hc_mult:
        y, extra = f(x)
        return x + y, extra, None
    n = c.hc_mult
    with jax.named_scope("mhc.maps"):
        h_pre, h_post, h_res, err = _stream_maps(c, lp, x, sub)
    rows = [x[..., j, :].astype(jnp.float32) for j in range(n)]
    with jax.named_scope("mhc.pre"):
        x_in = sum(h_pre[..., j, None] * rows[j] for j in range(n))
    y, extra = f(x_in.astype(x.dtype))
    with jax.named_scope("mhc.post"):
        y = y.astype(jnp.float32)
        x = jnp.stack(
            [sum(h_res[..., i, j, None] * rows[j] for j in range(n))
             + h_post[..., i, None] * y for i in range(n)],
            axis=-2).astype(x.dtype)
    return x, extra, err


def _stream_open(c: ModelConfig, x: jax.Array) -> jax.Array:
    """The embedding (..., D) -> the residual stream the layers carry: the
    same array, or with ``hc_mult`` streams the embedding in every row."""
    if not c.hc_mult:
        return x
    return jnp.broadcast_to(x[..., None, :],
                            x.shape[:-1] + (c.hc_mult, x.shape[-1]))


def _stream_close(c: ModelConfig, x: jax.Array) -> jax.Array:
    """The stream after the last layer -> (..., D) for the final norm: the
    sum of its rows."""
    if not c.hc_mult:
        return x
    return x.astype(jnp.float32).sum(-2).astype(x.dtype)


def _precision(c: ModelConfig):
    """The forward's matmul precision as a context: the platform default
    where the configuration names none."""
    if c.matmul_precision is None:
        return contextlib.nullcontext()
    return jax.default_matmul_precision(c.matmul_precision)


def _embed(c: ModelConfig, params: Params, tokens: jax.Array,
           flat: bool = False) -> jax.Array:
    """Tokens -> the residual stream the layers carry. ``flat``: the paged
    forward's batch of T entries, carried as (T, 1, ...)."""
    x = params["embed"][tokens]      # gather; sharded vocab → XLA collective
    if flat:
        x = x[:, None, :]
    return _stream_open(c, _times(x, c.embedding_multiplier))


def _logit_rows(x: jax.Array, entries: Optional[jax.Array]) -> jax.Array:
    """The stream's rows ``(T, 1, ...)`` at the entries whose logits are
    wanted (``forward_paged``'s ``logit_entries``; an index past the last
    entry is clamped), or all of them."""
    if entries is None:
        return x
    return jnp.take(x, entries, axis=0, mode="clip")


def _lm_head(c: ModelConfig, params: Params, x: jax.Array,
             logit_entries: Optional[jax.Array] = None,
             flat: bool = False) -> jax.Array:
    """The stream after the last layer -> float32 logits: the stream
    closed, the final norm, the head (the embedding where it is tied; its
    int8 shadow ``tied_head_q8`` where ``models/quantize.py`` made one:
    the head matmul streams half the bytes and ``_dense`` applies the
    per-vocab-row scale as the shared fused epilogue), the multiplier.
    ``logit_entries``: the rows of a ``flat`` stream whose logits are
    wanted (``_logit_rows``); ``flat`` logits are (T, V)."""
    x = _norm(c, _stream_close(c, _logit_rows(x, logit_entries)), params,
              "final_norm")
    if "lm_head" in params:
        logits = _dense(x, params, "lm_head", "bsd,dv->bsv")
    elif "tied_head_q8" in params:
        logits = _dense(x, params, "tied_head_q8", "bsd,vd->bsv")
    else:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    if flat:
        logits = logits[:, 0]
    return _times(logits.astype(jnp.float32), c.lm_head_multiplier)


def _worst(a, b):
    """The larger of two Sinkhorn errors, either of which may be None."""
    if a is None or b is None:
        return a if b is None else b
    return jnp.maximum(a, b)


def _layer(c: ModelConfig, lp: Dict[str, jax.Array], x: jax.Array,
           cos: jax.Array, sin: jax.Array,
           cache_kv: Optional[Tuple[jax.Array, jax.Array, jax.Array]],
           kv_mask, mesh=None):
    """One transformer block. x: (B, S, D), or the residual stream
    (B, S, hc_mult, D) of a multi-stream configuration (``_residual``).

    Without cache_kv: full self-attention over the block's own k/v, via the
    ``c.attn_impl`` kernel (einsum / flash / ring / ulysses — the latter two
    shard the sequence axis over the mesh's 'sp' axis).
    With cache_kv=(k_cache, v_cache, length): writes new k/v at ``length``,
    attends over the whole cache. Returns (x', (k_cache', v_cache'), aux)
    — in the no-cache case the returned pair is the block's own (k, v);
    aux is the MoE load-balancing loss (0 for dense layers).
    """
    if c.shortcut_moe:
        x, kv_out, aux, _ = _shortcut_block(
            c, lp, x, lambda lp_i, i, x_in, kv: _attend(
                c, lp_i, x_in, cos, sin, cache_kv, kv_mask, mesh), None)
        return x, kv_out, aux
    if c.ssm:
        # two mixers on one normed input (``_mixers``); the scan is causal,
        # so a mask of a right-padded tail changes nothing before it
        x, (kv_out, _), _ = _residual(
            c, lp, x, "attn", lambda x_in: _mixers(
                c, lp, x_in,
                lambda h: _attend(c, lp, None, cos, sin, cache_kv, kv_mask,
                                  mesh, h=h),
                lambda h: (_ssm_mix(c, lp, h), None)))
    else:
        x, kv_out, _ = _residual(
            c, lp, x, "attn", lambda x_in: _attend(
                c, lp, x_in, cos, sin, cache_kv, kv_mask, mesh))
    x, aux, _, _ = _mlp(c, lp, x)
    return x, kv_out, aux


def _attend(c: ModelConfig, lp: Dict[str, jax.Array], x: jax.Array,
            cos: jax.Array, sin: jax.Array, cache_kv, kv_mask, mesh,
            h: Optional[jax.Array] = None):
    """``_layer``'s attention sublayer, norm to output projection: x
    (B, S, D), what the residual path hands it -> (attention's output
    (B, S, D), the cache pair ``_layer`` returns). With ``h`` the caller
    has normed the input already (``_mixers``) and x is not read."""
    if h is None:
        h = rms_norm(x, lp["attn_norm"], c.rms_norm_eps)
    b, s, _ = h.shape
    if c.mla:       # no cache here: _forward_impl refuses one
        if c.attn_impl != "einsum" or c.sliding_window is not None:
            refuse(c, "forward(attn_impl=)")
        out = _mla_self_attention(c, lp, h, cos, sin, kv_mask)
        return _dense(out, lp, "wo", "bse,ed->bsd"), (None, None)
    q, k, v = _qkv(c, lp, h, cos, sin)

    if cache_kv is not None and len(cache_kv) == 5:
        # int8 cache: quantize the block's new k/v, scatter values AND
        # scales, attend over the dequantized cache (transient in compute
        # dtype; the HBM-resident cache stays int8).
        k_cache, v_cache, length, k_scale, v_scale = cache_kv
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        cap = k_cache.shape[1]
        ring = _is_ring(c, cap)
        out = None
        if ring and s > 1:
            # Attend BEFORE writing (see _forward_impl's ring notes): a
            # wrapping chunk's writes would destroy keys still inside
            # earlier queries' windows. kv axis = [pre-write cache ‖ chunk]
            # — unless the mask is chunk-width (fresh cache, nothing old
            # to read): then skip the concat and its masked-out FLOPs.
            if kv_mask.shape[-1] == s:
                out = attention(q, k, v, kv_mask=kv_mask, causal=False)
            else:
                k_all = jnp.concatenate(
                    [_dequantize_kv(k_cache, k_scale, h.dtype), k], axis=1)
                v_all = jnp.concatenate(
                    [_dequantize_kv(v_cache, v_scale, h.dtype), v], axis=1)
                out = attention(q, k_all, v_all, kv_mask=kv_mask,
                                causal=False)
        if length.ndim == 0 and not ring:
            k_cache = jax.lax.dynamic_update_slice(k_cache, kq,
                                                   (0, length, 0, 0))
            v_cache = jax.lax.dynamic_update_slice(v_cache, vq,
                                                   (0, length, 0, 0))
            k_scale = jax.lax.dynamic_update_slice(k_scale, ks,
                                                   (0, length, 0))
            v_scale = jax.lax.dynamic_update_slice(v_scale, vs,
                                                   (0, length, 0))
        elif length.ndim == 0:
            idx = (length + jnp.arange(s)) % cap               # ring write
            k_cache = k_cache.at[:, idx].set(kq)
            v_cache = v_cache.at[:, idx].set(vq)
            k_scale = k_scale.at[:, idx].set(ks)
            v_scale = v_scale.at[:, idx].set(vs)
        else:
            slot = jnp.arange(b)[:, None]                      # (B, 1)
            pos = length[:, None] + jnp.arange(s)[None, :]     # (B, s)
            if ring:
                pos = pos % cap
            k_cache = k_cache.at[slot, pos].set(kq, mode="drop")
            v_cache = v_cache.at[slot, pos].set(vq, mode="drop")
            k_scale = k_scale.at[slot, pos].set(ks, mode="drop")
            v_scale = v_scale.at[slot, pos].set(vs, mode="drop")
        if out is None:
            out = _cache_attention(c, q,
                                   _dequantize_kv(k_cache, k_scale, h.dtype),
                                   _dequantize_kv(v_cache, v_scale, h.dtype),
                                   length, kv_mask)
        kv_out = (k_cache, v_cache, k_scale, v_scale)
    elif cache_kv is not None:
        k_cache, v_cache, length = cache_kv
        cap = k_cache.shape[1]
        ring = _is_ring(c, cap)
        out = None
        if ring and s > 1:
            # Attend BEFORE writing — see the quantized branch above.
            if kv_mask.shape[-1] == s:
                out = attention(q, k, v, kv_mask=kv_mask, causal=False)
            else:
                k_all = jnp.concatenate([k_cache.astype(h.dtype), k], axis=1)
                v_all = jnp.concatenate([v_cache.astype(h.dtype), v], axis=1)
                out = attention(q, k_all, v_all, kv_mask=kv_mask,
                                causal=False)
        if length.ndim == 0 and not ring:
            k_cache = jax.lax.dynamic_update_slice(
                k_cache, k.astype(k_cache.dtype), (0, length, 0, 0))
            v_cache = jax.lax.dynamic_update_slice(
                v_cache, v.astype(v_cache.dtype), (0, length, 0, 0))
        elif length.ndim == 0:
            idx = (length + jnp.arange(s)) % cap               # ring write
            k_cache = k_cache.at[:, idx].set(k.astype(k_cache.dtype))
            v_cache = v_cache.at[:, idx].set(v.astype(v_cache.dtype))
        else:
            # Per-slot write offsets (continuous batching): scatter each
            # slot's s new positions at its own length.
            slot = jnp.arange(b)[:, None]                      # (B, 1)
            pos = length[:, None] + jnp.arange(s)[None, :]     # (B, s)
            if ring:
                pos = pos % cap
            k_cache = k_cache.at[slot, pos].set(k.astype(k_cache.dtype),
                                                mode="drop")
            v_cache = v_cache.at[slot, pos].set(v.astype(v_cache.dtype),
                                                mode="drop")
        if out is None:
            out = _cache_attention(c, q, k_cache, v_cache, length, kv_mask)
        kv_out = (k_cache, v_cache)
    else:
        out = _self_attention(c, q, k, v, kv_mask, mesh)
        kv_out = (k, v)

    return _dense(out.reshape(b, s, c.q_dim), lp, "wo", "bse,ed->bsd"), kv_out


def _swiglu(h: jax.Array, lp: Dict[str, jax.Array], gate: str, up: str,
            down: str, mults: Tuple[float, float] = (1.0, 1.0)) -> jax.Array:
    """``(up(h) * silu(mults[0] gate(h))) down * mults[1]``."""
    g = _times(_dense(h, lp, gate, "bsd,df->bsf"), mults[0])
    u = _dense(h, lp, up, "bsd,df->bsf")
    act = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * u
    return _times(_dense(act, lp, down, "bsf,fd->bsd"), mults[1])


def _ffn(c: ModelConfig, lp: Dict[str, jax.Array], h: jax.Array,
         count: Optional[jax.Array] = None,
         stack_layer: Optional[jax.Array] = None):
    """The FFN of a block on its normed input h (B, S, D): a dense SwiGLU,
    or where the layer's params hold a ``router`` the dropless expert
    layer of ``models/moe.py`` plus the shared expert. -> (its output in
    h's dtype, (moe aux loss — 0 for dense layers and for the bias
    routers, ``MoEStats`` over the entries ``count`` marks — None for
    dense)). ``stack_layer``: the expert banks in ``lp`` are the whole
    stack's and this is the layer's index in it (``moe._grouped``)."""
    if "router" not in lp:
        return (_swiglu(h, lp, "w_gate", "w_up", "w_down",
                        c.mlp_multipliers),
                (jnp.zeros((), jnp.float32), None))
    b, s, d = h.shape
    y, aux, stats = expert_ffn(c, lp, h.reshape(b * s, d), count,
                               stack_layer)
    y = y.reshape(b, s, d)
    if "ws_gate" in lp:
        with jax.named_scope("moe.shared"):
            y = y + _swiglu(h, lp, "ws_gate", "ws_up",
                            "ws_down").astype(jnp.float32)
    return y.astype(h.dtype), (aux, stats)


def _mlp(c: ModelConfig, lp: Dict[str, jax.Array], x: jax.Array,
         count: Optional[jax.Array] = None,
         stack_layer: Optional[jax.Array] = None):
    """Post-attention FFN block, shared by the contiguous-cache and paged
    layer bodies (a layer stack is all of one kind; a configuration with
    leading dense layers has two stacks). Returns (x + ffn(norm(x)) — or
    what the multi-stream residual path makes of it, ``_residual`` —
    ``_ffn``'s aux loss and ``MoEStats``, ``_residual``'s Sinkhorn error —
    None for the plain path)."""
    x, (aux, stats), err = _residual(
        c, lp, x, "mlp", lambda x_in: _ffn(
            c, lp, rms_norm(x_in, lp["mlp_norm"], c.rms_norm_eps), count,
            stack_layer))
    return x, aux, stats, err


def _shortcut_block(c: ModelConfig, lp: Dict[str, jax.Array], x: jax.Array,
                    attend: Callable, carry,
                    count: Optional[jax.Array] = None,
                    stack_layer: Optional[jax.Array] = None):
    """The shortcut-connected expert block (LongCat-Flash), for the
    no-cache and the paged layer alike:

        a0 = x  + Attn_0(Norm(x))          u0 = Norm(a0)
        m  = MoE(u0)                       the shortcut: read at the end
        b0 = a0 + FFN_0(u0)
        a1 = b0 + Attn_1(Norm(b0))
        x' = a1 + FFN_1(Norm(a1)) + m

    every addition through ``_residual``. ``attend(lp_i, i, x_in, carry)``
    is attention sublayer i with its norm -> (its output, carry'): the
    carry is what the two sublayers hand on (the pool's leaves). Nothing
    reads ``m`` before the last line, so the compiler may order the
    experts' grouped products beside FFN_0, Attn_1 and FFN_1: across chips
    that is where the exchange hides, and none is modelled here. ->
    (x', carry', aux, ``MoEStats``)."""
    lp0, lp1 = lp["sub0"], lp["sub1"]     # each a dense layer's leaves
    x, carry, _ = _residual(c, lp0, x, "attn",
                            lambda x_in: attend(lp0, 0, x_in, carry))

    def ffn_and_branch(x_in):
        u = rms_norm(x_in, lp0["mlp_norm"], c.rms_norm_eps)
        b, s, d = u.shape
        with jax.named_scope("moe.shortcut"):
            m, aux, stats = expert_ffn(c, lp, u.reshape(b * s, d), count,
                                       stack_layer)
        return _ffn(c, lp0, u)[0], (m.reshape(b, s, d), aux, stats)

    with jax.named_scope("mlp"):
        x, (m, aux, stats), _ = _residual(c, lp0, x, "mlp", ffn_and_branch)
    x, carry, _ = _residual(c, lp1, x, "attn",
                            lambda x_in: attend(lp1, 1, x_in, carry))
    with jax.named_scope("mlp"):
        x, _, _ = _residual(
            c, lp1, x, "mlp", lambda x_in: (
                (_ffn(c, lp1, rms_norm(x_in, lp1["mlp_norm"], c.rms_norm_eps)
                      )[0].astype(jnp.float32) + m).astype(x_in.dtype), None))
    return x, carry, aux, stats


# -- layers of unlike kinds in a fixed pattern (``c.layer_types``) ----------

def _mamba1_in(c: ModelConfig, lp: Dict[str, jax.Array], h: jax.Array):
    """Mamba-1's input projection of the layer's normed input h (B, S, D):
    ``[u | z] = h W_in`` -> u (B, S, I) the conv's input, z the gate."""
    with jax.named_scope("ssm.in_proj"):
        p = _dense(h, lp, "ssm_in", "bsd,de->bse")
    return p[..., :c.mamba_d_ssm], p[..., c.mamba_d_ssm:]


def _mamba1_inputs(c: ModelConfig, lp: Dict[str, jax.Array],
                   conv_out: jax.Array, dtype):
    """The conv's output (B, S, I) f32 -> what the scan reads: u =
    silu(conv) in ``dtype``; ``[r | B | C] = u W_x``; dt = softplus(r W_dt
    + dt_bias) and A = -exp(A_log) (I, N) in float32."""
    r, n = c.mamba_dt_rank, c.mamba_d_state
    u = jax.nn.silu(conv_out).astype(dtype)
    x = _dense(u, lp, "ssm_x", "bsi,ie->bse")
    dt = jax.nn.softplus(
        _dense(x[..., :r], lp, "ssm_dt", "bsr,ri->bsi").astype(jnp.float32)
        + lp["ssm_dt_bias"])
    return (u, dt, -jnp.exp(lp["ssm_A_log"].astype(jnp.float32)),
            x[..., r:r + n], x[..., r + n:])


def _mamba1_out(c: ModelConfig, lp: Dict[str, jax.Array], y: jax.Array,
                z: jax.Array):
    """The scan's output y (B, S, I) f32 and the gate z -> (the mixer's
    output ``(y * silu(z)) W_out`` (B, S, D), the memory: y itself, before
    the gate, in z's dtype)."""
    with jax.named_scope("ssm.out_proj"):
        g = (y * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype)
        return _dense(g, lp, "ssm_out", "bsi,id->bsd"), y.astype(z.dtype)


def _gmu(lp: Dict[str, jax.Array], h: jax.Array, m: jax.Array) -> jax.Array:
    """The gated memory unit: ``(silu(h W_in) * m) W_out``, m the same
    token's memory."""
    gate = jax.nn.silu(_dense(h, lp, "gmu_in", "bsd,di->bsi")
                       .astype(jnp.float32)).astype(h.dtype)
    return _dense(gate * m, lp, "gmu_out", "bsi,id->bsd")


def _diff_queries(c: ModelConfig, lp: Dict[str, jax.Array],
                  h: jax.Array) -> jax.Array:
    """The queries of differential attention, h (B, S, D) -> (B, S, Hq,
    2 head_dim). ``wq``'s columns are (pair p, set i, head j, head_dim):
    the ``Hq / Hkv`` heads j of set i that read kv pair p. A cache row of
    pair p is ``[k1_p | k2_p]``, so a query of set 1 is ``[q | 0]`` and one
    of set 2 ``[0 | q]``: its product with the row is its own set's score,
    and one attention call of ``Hq`` heads over ``Hkv / 2`` rows of
    ``2 head_dim`` serves both maps; over values ``[v1_p | v2_p]`` it
    yields ``[P v1 | P v2]`` a head, as the equations have it."""
    b, s, _ = h.shape
    rep = c.num_heads // c.num_kv_heads
    q = _dense(h, lp, "wq", "bsd,de->bse").reshape(
        b, s, c.cache_kv_heads, 2, rep, c.head_dim)
    zero = jnp.zeros_like(q[:, :, :, 0])
    q = jnp.stack([jnp.concatenate([q[:, :, :, 0], zero], axis=-1),
                   jnp.concatenate([zero, q[:, :, :, 1]], axis=-1)], axis=3)
    return q.reshape(b, s, c.num_heads, c.cache_head_dim)


def _diff_kv(c: ModelConfig, lp: Dict[str, jax.Array], h: jax.Array):
    """h (B, S, D) -> the cache rows k, v (B, S, Hkv / 2, 2 head_dim):
    ``wk``'s and ``wv``'s columns are (pair p, set i, head_dim)."""
    b, s, _ = h.shape
    shape = (b, s, c.cache_kv_heads, c.cache_head_dim)
    return (_dense(h, lp, "wk", "bsd,de->bse").reshape(shape),
            _dense(h, lp, "wv", "bsd,de->bse").reshape(shape))


def _diff_out(c: ModelConfig, lp: Dict[str, jax.Array], out: jax.Array,
              layer: jax.Array) -> jax.Array:
    """The two maps' outputs (B, S, Hq, 2 head_dim), heads as
    ``_diff_queries`` laid them, -> the attention sublayer's output
    (B, S, D): ``RMSNorm(a_1 - lam a_2; g_sub) (1 - lam0)`` a head, in
    float32, then ``W_o``. ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0``
    from the columns of ``attn_lambda``, ``lam0 = 0.8 - 0.6 exp(-0.3
    layer)`` with ``layer`` the layer's number in the model."""
    b, s = out.shape[:2]
    rep = c.num_heads // c.num_kv_heads
    o = out.astype(jnp.float32).reshape(b, s, c.cache_kv_heads, 2, rep,
                                        c.cache_head_dim)
    v = lp["attn_lambda"].astype(jnp.float32)
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * layer.astype(jnp.float32))
    lam = (jnp.exp(jnp.sum(v[:, 0] * v[:, 1]))
           - jnp.exp(jnp.sum(v[:, 2] * v[:, 3])) + lam0)
    d = o[:, :, :, 0] - lam * o[:, :, :, 1]
    d = d * jax.lax.rsqrt(jnp.mean(d * d, axis=-1, keepdims=True)
                          + c.rms_norm_eps)
    d = d * lp["attn_sub_norm"].astype(jnp.float32) * (1.0 - lam0)
    with jax.named_scope("attn.out"):
        return _dense(d.reshape(b, s, c.q_dim).astype(out.dtype), lp, "wo",
                      "bse,ed->bsd")


def _plain_qkv(c: ModelConfig, lp: Dict[str, jax.Array], h: jax.Array):
    """Plain GQA with no positional term, h (B, S, D) -> q (B, S, Hq, d),
    k, v (B, S, Hkv, d)."""
    b, s, _ = h.shape
    return (_dense(h, lp, "wq", "bsd,de->bse").reshape(
                b, s, c.num_heads, c.head_dim),
            _dense(h, lp, "wk", "bsd,de->bse").reshape(
                b, s, c.num_kv_heads, c.head_dim),
            _dense(h, lp, "wv", "bsd,de->bse").reshape(
                b, s, c.num_kv_heads, c.head_dim))


def _gated_out(c: ModelConfig, lp: Dict[str, jax.Array], out: jax.Array,
               h: jax.Array) -> jax.Array:
    """Plain attention's output (B, S, Hq, d) -> the sublayer's (B, S, D):
    under ``attn_out_gate`` times ``sigmoid(h W_g)`` elementwise, h the
    layer's normed input, the gate in float32; then ``W_o``."""
    b, s = out.shape[:2]
    o = out.reshape(b, s, c.q_dim)
    with jax.named_scope("attn.out"):
        if c.attn_out_gate:
            gate = jax.nn.sigmoid(_dense(h, lp, "w_attn_gate",
                                         "bsd,de->bse").astype(jnp.float32))
            o = (o.astype(jnp.float32) * gate).astype(h.dtype)
        return _dense(o, lp, "wo", "bse,ed->bsd")


# the divisor of q's and k's normalisation: ||x||^2 + this
_KDA_L2_EPS = 1e-6


def _kda_project(c: ModelConfig, lp: Dict[str, jax.Array],
                 h: jax.Array) -> jax.Array:
    """The delta-rule mixer's input projection of the layer's normed input
    h (B, S, D): ``[q | k | v] = h [W_q | W_k | W_v]`` (B, S, 3 W), the
    conv's input."""
    return _dense(h, lp, "kda_in", "bsd,de->bse")


def _kda_qkv(c: ModelConfig, conv_out: jax.Array):
    """The conv's output (B, S, 3 W) f32 -> what the recurrence reads, in
    float32: ``q = silu(.) / ||.|| / sqrt(d)``, ``k = silu(.) / ||.||``,
    ``v = silu(.)``, each (B, S, H, d)."""
    b, s, _ = conv_out.shape
    a = jax.nn.silu(conv_out).reshape(b, s, 3, c.kda_num_heads,
                                      c.kda_head_dim)
    unit = lambda x: x * jax.lax.rsqrt(
        jnp.sum(x * x, axis=-1, keepdims=True) + _KDA_L2_EPS)
    return (unit(a[:, :, 0]) * (1.0 / float(c.kda_head_dim) ** 0.5),
            unit(a[:, :, 1]), a[:, :, 2])


def _kda_gates(c: ModelConfig, lp: Dict[str, jax.Array], h: jax.Array):
    """h (B, S, D) -> (g (B, S, H, d) f32, the log decay a key channel:
    ``-exp(A_log) softplus(W_up (W_down h) + dt_bias)``; beta (B, S, H)
    f32: ``sigmoid(h W_beta)``, times 2 under ``kda_neg_eigval``)."""
    b, s, _ = h.shape
    f = _dense(_dense(h, lp, "kda_f_down", "bsd,dr->bsr"), lp, "kda_f_up",
               "bsr,re->bse").astype(jnp.float32) + lp["kda_dt_bias"][0]
    g = (-jnp.exp(lp["kda_A_log"][0].astype(jnp.float32))[:, None]
         * jax.nn.softplus(f).reshape(b, s, c.kda_num_heads, c.kda_head_dim))
    beta = jax.nn.sigmoid(_dense(h, lp, "kda_beta",
                                 "bsd,dh->bsh").astype(jnp.float32))
    return g, beta * (2.0 if c.kda_neg_eigval else 1.0)


def _kda_out(c: ModelConfig, lp: Dict[str, jax.Array], o: jax.Array,
             h: jax.Array) -> jax.Array:
    """The readout o (B, S, H, d) f32 -> the mixer's output (B, S, D):
    ``W_o [RMSNorm_head(o) * sigmoid(W_up (W_down h) + b)]``, one gain of d
    shared by the heads, norm and gate in float32."""
    b, s = o.shape[:2]
    with jax.named_scope("kda.out"):
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + c.rms_norm_eps)
        o = (o * lp["kda_o_norm"].astype(jnp.float32)).reshape(
            b, s, c.kda_dim)
        gate = jax.nn.sigmoid(
            _dense(_dense(h, lp, "kda_g_down", "bsd,dr->bsr"), lp,
                   "kda_g_up", "bsr,re->bse").astype(jnp.float32)
            + lp["kda_g_bias"][0].astype(jnp.float32))
        return _dense((o * gate).astype(h.dtype), lp, "kda_out",
                      "bse,ed->bsd")


def _pattern_layer(c: ModelConfig, kind: str, lp: Dict[str, jax.Array],
                   x: jax.Array, m: jax.Array, carry, mixer: Callable,
                   at: Dict[str, Any]):
    """One layer of a ``layer_types`` configuration, for the no-cache and
    the paged forward alike: ``x + mix(Norm(x))`` then ``x + FFN(Norm(
    x))``, both through ``_residual``; the FFN is the dense SwiGLU or the
    expert layer (``_ffn``). ``mixer(kind, lp, h, m, carry, at)`` is the
    layer's first sublayer on its normed input -> (its output, the memory,
    carry'); ``at`` says where the layer is (``_pattern_scan``), which
    entries an expert layer's stats count (``count``) and, where ``lp``
    holds the whole stack's expert banks, the layer's place in them
    (``rep``). -> (x, m, carry', the expert layer's ``MoEStats`` or None).
    The scope names (``ssm.mamba``, ``attn.window``, ``attn.full``, ``gmu``,
    ``attn.cross``, ``kda``, ``mlp``, ``moe``) are docs/observability.md's.
    """
    scope = {"mamba": "ssm.mamba", "gmu": "gmu", "kda": "kda"}.get(
        kind, "attn." + kind)

    def mix(x_in):
        out, m2, carry2 = mixer(kind, lp, _norm(c, x_in, lp, "attn_norm"),
                                m, carry, at)
        return out, (m2, carry2)

    with jax.named_scope(scope):
        x, (m, carry), _ = _residual(c, lp, x, "attn", mix)
    with jax.named_scope("moe" if "router" in lp else "mlp"):
        x, (_, stats), _ = _residual(
            c, lp, x, "mlp", lambda x_in: _ffn(
                c, lp, _norm(c, x_in, lp, "mlp_norm"), at.get("count"),
                at.get("rep")))
    return x, m, carry, stats


def _pattern_scan(c: ModelConfig, params: Params, x: jax.Array, carry,
                  mixer: Callable, remat: bool = False, count=None, *,
                  segments: Optional[Tuple[int, int]] = None, m=None,
                  acc=None):
    """The layers of a ``layer_types`` configuration: one scan a segment,
    whose body runs the period's unlike layers in order, with the memory
    ``m`` (the last "mamba" layer's scan output, read by the "gmu" layers)
    and ``carry`` (the cache leaves, or the last "full" layer's k and v)
    in the scan's carry. ``at`` tells a layer its number in the model
    (``layer``), among the layers of its kind (``index``, which addresses
    that kind's cache leaves), and the ``index`` of the last "full" layer
    before it (``full``, whose KV a "cross" layer reads). An expert
    configuration's banks stay whole outside the scan's xs (each layer's
    grouped products address its experts inside them, ``moe._grouped``),
    and its layers' ``MoEStats`` over the entries ``count`` marks are
    merged in the carry. ``segments=(i, j)`` runs segments ``[i, j)``
    alone, taking up the ``m`` and the merged stats ``acc`` that the
    segments before them returned (``x`` may then hold other entries than
    it did there: ``_forward_paged_pattern``); ``at`` counts the layers
    before ``i`` either way. -> (x, m, carry', ``MoEStats`` or None)."""
    lo, hi = segments or (0, len(c.layer_types))
    if m is None:
        m = jnp.zeros(x.shape[:-1] + (c.mamba_d_ssm,), x.dtype)
    seen = {kind: 0 for period, _ in c.layer_types for kind in period}
    first = 0
    if acc is None and c.num_experts:
        acc = MoEStats.zeros(c)
    layer_fn = _pattern_layer
    if remat:
        layer_fn = jax.checkpoint(
            _pattern_layer, static_argnums=(0, 1, 6), prevent_cse=False,
            policy=(jax.checkpoint_policies.checkpoint_dots
                    if c.remat == "dots" else None))
    for i, (period, n) in enumerate(c.layer_types[:hi]):
        base, start = dict(seen), first
        for kind in period:
            seen[kind] += n
        first += n * len(period)
        if i < lo:
            continue
        names = pattern_keys(period)
        seg = params["layers"][f"seg{i}"]
        banks = {name: {k: v for k, v in seg[name].items() if k in BANKS}
                 for name in names} if acc is not None else None
        if banks is not None:
            seg = {name: {k: v for k, v in seg[name].items()
                          if k not in BANKS} for name in names}

        def body(state, inp, period=period, base=base, start=start,
                 names=names, banks=banks):
            x, m, carry, acc = state
            lps, rep = inp
            for j, (name, kind) in enumerate(zip(names, period)):
                full = base.get("full", 0) - 1
                if "full" in period[:j + 1]:
                    full = full + rep + 1
                # "index": the layer among those of its kind, of which the
                # period may name more than one
                each = period.count(kind)
                at = {"layer": start + rep * len(period) + j,
                      "index": base[kind] + (
                          rep if each == 1
                          else rep * each + period[:j].count(kind)),
                      "full": full}
                lp = lps[name]
                if banks is not None:
                    lp = {**lp, **banks[name]}
                    at.update(count=count, rep=rep)
                x, m, carry, stats = layer_fn(c, kind, lp, x, m, carry,
                                              mixer, at)
                if stats is not None:
                    acc = acc.merge(stats)
            return (x, m, carry, acc), None

        (x, m, carry, acc), _ = jax.lax.scan(
            body, (x, m, carry, acc),
            (seg, jnp.arange(n, dtype=jnp.int32)))
    return x, m, carry, acc


def _dense_mixer(c: ModelConfig, attn_mask, kind: str,
                 lp: Dict[str, jax.Array], h: jax.Array, m: jax.Array, kv,
                 at: Dict[str, Any]):
    """A ``layer_types`` layer's first sublayer over whole sequences, h
    (B, S, D) its normed input: the mixer from a zero state, attention
    over the sequence's own k and v with a plain mask (a "window" layer's
    also ``t - s < layer_window``), a "cross" layer over ``kv``, the last
    "full" layer's k and v. -> (its output, the memory, kv')."""
    if kind == "mamba":
        u_in, z = _mamba1_in(c, lp, h)
        with jax.named_scope("ssm.conv"):
            conv = ssm_ops.conv_dense(u_in, lp["ssm_conv_w"],
                                      lp["ssm_conv_b"])
            u, dt, a, b, cc = _mamba1_inputs(c, lp, conv, h.dtype)
        with jax.named_scope("ssm.scan"):
            y = ssm_ops.scan1_dense(u, dt, a, b, cc, lp["ssm_D"])
        return _mamba1_out(c, lp, y, z) + (kv,)
    if kind == "gmu":
        return _gmu(lp, h, m), m, kv
    if kind == "kda":
        with jax.named_scope("kda.conv"):
            q, k, v = _kda_qkv(c, ssm_ops.conv_dense(
                _kda_project(c, lp, h), lp["kda_conv_w"], None))
        with jax.named_scope("kda.gates"):
            g, beta = _kda_gates(c, lp, h)
        with jax.named_scope("kda.chunk"):
            o = delta_rule.kda_dense(q, k, v, g, beta)
        return _kda_out(c, lp, o, h), m, kv
    if not c.diff_attn:
        q, k, v = _plain_qkv(c, lp, h)
        out = attention(q, k, v, q_offset=0, kv_mask=attn_mask, causal=True,
                        scale=1.0 / float(c.head_dim) ** 0.5)
        return _gated_out(c, lp, out, h), m, (k, v)
    q = _diff_queries(c, lp, h)
    if kind == "cross":
        k, v = kv
    else:
        k, v = _diff_kv(c, lp, h)
        if kind == "full":
            kv = (k, v)
    out = attention(q, k, v, q_offset=0, kv_mask=attn_mask, causal=True,
                    window=c.layer_window if kind == "window" else None,
                    scale=1.0 / float(c.head_dim) ** 0.5)
    return _diff_out(c, lp, out, at["layer"]), m, kv


def _forward_pattern(params: Params, c: ModelConfig, x: jax.Array,
                     attn_mask) -> jax.Array:
    """The no-cache forward of a ``layer_types`` configuration over whole
    sequences x (B, S, D) (``_dense_mixer``). The trainer's and the tests'
    path."""
    shape = x.shape[:2] + (c.cache_kv_heads, c.cache_head_dim)
    kv = (jnp.zeros(shape, x.dtype), jnp.zeros(shape, x.dtype))
    return _pattern_scan(c, params, x, kv,
                         functools.partial(_dense_mixer, c, attn_mask),
                         remat=bool(c.remat))[0]


def _rope_tables(c: ModelConfig, positions: jax.Array):
    """cos / sin for ``positions``, made for the whole head, or under
    latent attention for the decoupled rotary part alone. YaRN scaling
    also changes the softmax scale (``ModelConfig.attn_scale``), which only
    the latent attention paths read: any other configuration with it is
    refused."""
    if isinstance(c.rope_scaling, YarnScaling) and not c.mla:
        raise NotImplementedError(
            f"{c.name}: YaRN rotary scaling is implemented for latent "
            f"attention only (its softmax scale carries mscale_all_dim)")
    return rope_cos_sin(positions,
                        c.qk_rope_head_dim if c.mla else c.head_dim,
                        c.rope_theta, scaling=c.rope_scaling)


def _layer_stacks(params: Params) -> Tuple[Dict[str, jax.Array], ...]:
    """The model's layer stacks in the order they run: the leading
    dense-FFN stack where the configuration has one, then the main one."""
    if "dense_layers" in params:
        return params["dense_layers"], params["layers"]
    return (params["layers"],)


def forward(
    params: Params,
    config: ModelConfig,
    tokens: jax.Array,                 # (B, S) int32
    *,
    cache: Optional[KVCache] = None,
    positions: Optional[jax.Array] = None,   # (B, S) absolute positions
    attn_mask: Optional[jax.Array] = None,   # (B, S_kv) True = valid
    with_aux: bool = False,
    mesh=None,                               # required for ring/ulysses attn
    fresh_cache: bool = False,               # static: cache holds nothing yet
):
    """Run the model. Without cache: full causal self-attention over ``tokens``.
    With cache: ``tokens`` are appended at ``cache.length`` and attend to
    everything up to that point (prefill and decode use the same path).

    ``mesh`` (jax.sharding.Mesh) is only consulted when
    ``config.attn_impl`` is 'ring'/'ulysses' — the sequence axis then
    shards over its 'sp' axis inside shard_map.

    Returns (logits (B, S, V) fp32, updated cache or None); with
    ``with_aux=True`` also the summed MoE load-balancing loss (the router
    must see it in the objective or it is free to collapse).
    """
    c = config
    with _precision(c):
        logits, new_cache, aux = _forward_impl(
            params, c, tokens, cache=cache, positions=positions,
            attn_mask=attn_mask, mesh=mesh, fresh_cache=fresh_cache)
    if with_aux:
        return logits, new_cache, aux
    return logits, new_cache


def _forward_impl(params, c, tokens, *, cache, positions, attn_mask,
                  mesh=None, fresh_cache=False):
    b, s = tokens.shape
    if cache is not None:
        refuse(c, "forward(cache=)")
    if mesh is not None:
        refuse(c, "forward(mesh=)")
    if c.pattern:
        x = _forward_pattern(params, c, _embed(c, params, tokens),
                             attn_mask)
        return _lm_head(c, params, x), None, jnp.zeros((), jnp.float32)
    x = _embed(c, params, tokens)

    if positions is None:
        base = cache.length if cache is not None else jnp.zeros((), jnp.int32)
        if base.ndim == 1:
            base = base[:, None]                       # per-slot lengths
        positions = base + jnp.arange(s, dtype=jnp.int32)[None, :]
        positions = jnp.broadcast_to(positions, (b, s))
    cos, sin = _rope_tables(c, positions)

    if cache is None:
        def one_layer(x, lp, cos, sin):
            x, _, layer_aux = _layer(c, lp, x, cos, sin, None, attn_mask,
                                     mesh=mesh)
            return x, layer_aux

        if c.remat:
            # Per-layer rematerialization: backward recomputes this
            # layer's activations instead of holding all L layers' —
            # O(1) activation memory in depth for O(L) extra forward
            # FLOPs. "dots" keeps matmul outputs (cheaper backward,
            # more memory); True/"full" keeps nothing.
            policy = (jax.checkpoint_policies.checkpoint_dots
                      if c.remat == "dots" else None)
            # prevent_cse=False: under lax.scan the CSE barrier is
            # unnecessary (per the jax.checkpoint docs) and its
            # optimization_barrier ops would block fusion across every
            # layer boundary of the training hot path.
            one_layer = jax.checkpoint(one_layer, policy=policy,
                                       prevent_cse=False)

        def body(carry, lp):
            x, aux = carry
            x, layer_aux = one_layer(x, lp, cos, sin)
            return (x, aux + layer_aux), None

        carry = (x, jnp.zeros((), jnp.float32))
        for stack in _layer_stacks(params):
            carry, _ = jax.lax.scan(body, carry, stack)
        x, aux_total = carry
        new_cache = None
    else:
        max_len = cache.k.shape[2]
        length = cache.length
        if _is_ring(c, max_len):
            # Ring cache: capacity `cap` slots written at pos % cap.
            #
            # s == 1 (decode): write-then-attend is safe — the single new
            # token only overwrites the slot holding pos qp − cap, which
            # is outside its own window (cap ≥ window). Ring index i then
            # holds absolute position p(i) = the latest p ≡ i (mod cap);
            # the query may attend iff 0 ≤ p(i) ≤ qp > qp − window. Fill,
            # causality and the window all live in this one mask —
            # attention() runs with causal=False (ring index is NOT
            # absolute position).
            #
            # s > 1 (chunked prefill): write-first would DESTROY keys
            # still inside earlier queries' windows whenever the chunk
            # wraps (any wrapping chunk when cap == window), so _layer
            # attends BEFORE writing, over [pre-write cache ‖ chunk]:
            # the mask here is (B, s, cap + s) — old slots valid by their
            # pre-chunk positions, intra-chunk causal+window on the tail.
            cap = max_len
            if s > cap:
                raise ValueError(
                    f"chunk of {s} tokens exceeds the ring capacity "
                    f"{cap} (window {c.sliding_window}); prefill in "
                    f"chunks of at most the window size")
            base = length[:, None, None] if length.ndim == 1 else length
            i = jnp.arange(cap)[None, None, :]
            qp = base + jnp.arange(s)[None, :, None]           # query abs pos
            if s == 1:
                if attn_mask is not None:
                    raise NotImplementedError(
                        "attn_mask on a ring-cache decode step: ring "
                        "indices are modular positions — combine masks "
                        "upstream instead")
                total = base + 1                               # after write
                p = (total - 1) - ((total - 1 - i) % cap)      # pos per slot
                valid = (p >= 0) & (p <= qp) & (p > qp - c.sliding_window)
                valid = jnp.broadcast_to(valid, (b, 1, cap))
            else:
                t = jnp.arange(s)[None, None, :]               # chunk kv idx
                j = jnp.arange(s)[None, :, None]               # chunk q idx
                valid_new = (t <= j) & (j - t < c.sliding_window)
                if attn_mask is not None:
                    # Contract (serving-engine prefill): only meaningful
                    # on a FRESH slot (length == 0, nothing old to mask);
                    # positions then coincide with chunk indices.
                    valid_new = valid_new & attn_mask[:, None, :s]
                if fresh_cache:
                    # Chunk-width mask: _layer skips the [cache ‖ chunk]
                    # concat and its fully-masked score columns.
                    valid = jnp.broadcast_to(valid_new, (b, s, s))
                else:
                    p_old = (base - 1) - ((base - 1 - i) % cap)  # pre-chunk
                    valid_old = ((p_old >= 0)
                                 & (p_old > qp - c.sliding_window))
                    valid = jnp.concatenate(
                        [jnp.broadcast_to(valid_old, (b, s, cap)),
                         jnp.broadcast_to(valid_new, (b, s, s))], axis=-1)
        else:
            # kv validity: only slots < length + s are real.
            kv_pos = jnp.arange(max_len)[None, :]
            bound = (length[:, None] if length.ndim == 1 else length) + s
            valid = jnp.broadcast_to(kv_pos < bound, (b, max_len))
            if attn_mask is not None:
                valid = valid & attn_mask
        if cache.quantized:
            def body_q(carry, inputs):
                x, aux = carry
                lp, k_c, v_c, k_s, v_s = inputs
                x, kv_out, layer_aux = _layer(
                    c, lp, x, cos, sin,
                    (k_c, v_c, cache.length, k_s, v_s), valid)
                return (x, aux + layer_aux), kv_out

            (x, aux_total), (k_upd, v_upd, ks_upd, vs_upd) = jax.lax.scan(
                body_q, (x, jnp.zeros((), jnp.float32)),
                (params["layers"], cache.k, cache.v, cache.k_scale,
                 cache.v_scale))
            new_cache = KVCache(k=k_upd, v=v_upd, length=cache.length + s,
                                k_scale=ks_upd, v_scale=vs_upd)
        else:
            def body(carry, inputs):
                x, aux = carry
                lp, k_cache, v_cache = inputs
                x, (k_cache, v_cache), layer_aux = _layer(
                    c, lp, x, cos, sin, (k_cache, v_cache, cache.length),
                    valid)
                return (x, aux + layer_aux), (k_cache, v_cache)

            (x, aux_total), (k_upd, v_upd) = jax.lax.scan(
                body, (x, jnp.zeros((), jnp.float32)),
                (params["layers"], cache.k, cache.v))
            new_cache = KVCache(k=k_upd, v=v_upd, length=cache.length + s)

    return _lm_head(c, params, x), new_cache, aux_total


def _paged_layer(c: ModelConfig, lp: Dict[str, jax.Array], x: jax.Array,
                 cos: jax.Array, sin: jax.Array,
                 leaves: Tuple[jax.Array, ...], layer: jax.Array,
                 tables: jax.Array, seq_row: jax.Array,
                 positions: jax.Array, write_block: jax.Array,
                 write_off: jax.Array, use_kernel: bool = False,
                 adapters=None, adapter_ids=None, stack_layer=None,
                 row_plan=None, run_plan=None, state_layer=None):
    """One transformer block over a paged KV pool (rollout/paged_kv.py).

    ``x`` is a flat token batch ``(T, 1, D)`` — T independent
    (sequence, position) pairs, decode steps and chunked-prefill
    segments mixed freely. ``leaves`` are the pool's arrays STACKED over
    layers as they are stored — ``(k, v)``
    ``(L, num_blocks, block_size, Hkv, Dh)``, plus
    ``(k_scale, v_scale)`` ``(L, num_blocks, block_size, Hkv)`` on a
    quantized pool — and ``layer`` is this block's index into them.
    Nothing layer-sized is sliced out: each token first scatters its
    new k/v at ``(layer, write_block[t], write_off[t], head)``
    (``write_block == num_blocks`` is out of range on the block axis, so
    ``mode="drop"`` drops the write in every layer — padding and rescore
    entries), then attends over its own sequence through the block-table
    indirection ``(layer, tables[seq_row[t]])``. The scatter lands
    before the gather, so a chunk's later tokens see its earlier ones at
    the same layer — flat-batch chunked prefill is exactly block
    prefill. Returns ``(x, leaves', MoEStats or None, the residual
    path's Sinkhorn error or None)``: the caller carries the leaves
    through its layer scan, so a donated pool is updated in place. Both
    sublayers go through ``_residual``: x is (T, 1, D), or the stream
    (T, 1, hc_mult, D) of a multi-stream configuration. A latent
    configuration's attention sublayer is ``_paged_mla_attend``.

    With a ``row_plan`` (``ops.paged_attention.plan_rows`` of this
    batch; ``forward_paged`` makes one where the kernel runs) an
    unquantized pool is read by ``paged_attention_rows``: each run of
    entries of one row attends together over that row's live blocks,
    streamed from the stacked leaves where they lie. Without one, the
    XLA gather: a contiguous ``(T, MB*BS, Hkv, Dh)`` cache
    per token, attended with the SAME mask and attention call as the
    slot path (`kv_pos < pos+1`, causal with per-row ``q_offset``), so
    paged and slot decode agree to numerical identity of the masking
    and matmul shapes' element-wise dot products. It is the plain
    reference the kernel is tested against, and the path off the TPU.
    ``use_kernel`` on a QUANTIZED pool is the dequant-fused
    ``paged_flash_decode`` over this layer's slice.

    The ``jax.named_scope`` names (``attn.qkv``, ``attn.kv_write``,
    ``attn.kv_gather``, ``attn.scores``, ``attn.out``, ``mlp``) are the
    stable device-side names of docs/observability.md: they ride each
    HLO instruction's ``op_name`` metadata and change no computation.
    ``attn.kv_gather`` exists on the gather path alone; the kernel is
    ``paged_attention_rows`` in a trace.

    A configuration with a state-space mixer (``c.ssm``) carries the
    pool's row-addressed leaves (ssm, conv) as the LAST two of ``leaves``,
    indexed by ``state_layer`` (the block's absolute number) and cut by
    ``run_plan`` (``ops.ssm.plan_runs``); its first sublayer is the two
    mixers on one normed input (``_mixers``, ``_paged_ssm_mix``; scopes
    ``ssm.in_proj``, ``ssm.conv``, ``ssm.scan``, ``ssm.gate_norm``,
    ``ssm.out_proj``).
    """
    attend = _paged_mla_attend if c.mla else _paged_attend
    if c.shortcut_moe:
        def sublayer(lp_i, i, x_in, leaves):
            # sublayer i of block ``layer`` is pool layer 2 layer + i
            with jax.named_scope(f"attn.sub{i}"):
                return attend(c, lp_i, x_in, cos, sin, leaves,
                              2 * layer + i, tables, seq_row, positions,
                              write_block, write_off, use_kernel=use_kernel,
                              adapters=adapters, adapter_ids=adapter_ids,
                              row_plan=row_plan)
        x, leaves, _, stats = _shortcut_block(
            c, lp, x, sublayer, leaves,
            _writes(lp, write_block, leaves[0]), stack_layer)
        return x, leaves, stats, None
    if c.ssm:
        def both(x_in):
            out, (kv, rows) = _mixers(
                c, lp, x_in,
                lambda h: _paged_attend(
                    c, lp, None, cos, sin, leaves[:-2], layer, tables,
                    seq_row, positions, write_block, write_off,
                    use_kernel=use_kernel, adapters=adapters,
                    adapter_ids=adapter_ids, row_plan=row_plan, h=h),
                lambda h: _paged_ssm_mix(c, lp, h, leaves[-2:], state_layer,
                                         seq_row, run_plan))
            return out, kv + rows
        x, leaves, err = _residual(c, lp, x, "attn", both)
    else:
        x, leaves, err = _residual(
            c, lp, x, "attn", lambda x_in: attend(
                c, lp, x_in, cos, sin, leaves, layer, tables, seq_row,
                positions, write_block, write_off, use_kernel=use_kernel,
                adapters=adapters, adapter_ids=adapter_ids,
                row_plan=row_plan))
    with jax.named_scope("mlp"):
        x, _, stats, mlp_err = _mlp(
            c, lp, x, _writes(lp, write_block, leaves[0]), stack_layer)
    return x, leaves, stats, _worst(err, mlp_err)


def _paged_attend(c: ModelConfig, lp: Dict[str, jax.Array], x: jax.Array,
                  cos: jax.Array, sin: jax.Array,
                  leaves: Tuple[jax.Array, ...], layer: jax.Array,
                  tables: jax.Array, seq_row: jax.Array,
                  positions: jax.Array, write_block: jax.Array,
                  write_off: jax.Array, use_kernel: bool = False,
                  adapters=None, adapter_ids=None, row_plan=None,
                  h: Optional[jax.Array] = None):
    """``_paged_layer``'s attention sublayer over (k, v) leaves, norm to
    output projection: x (T, 1, D), what the residual path hands it ->
    (attention's output (T, 1, D), leaves'). With ``h`` the caller has
    normed the input already (``_mixers``) and x is not read."""
    quantized = len(leaves) == 4
    with jax.named_scope("attn.qkv"):
        if h is None:
            h = rms_norm(x, lp["attn_norm"], c.rms_norm_eps)
        t = h.shape[0]
        q, k, v = _qkv(c, lp, h, cos, sin, adapters, adapter_ids)
    # q (T,1,Hq,Dh), k/v (T,1,Hkv,Dh)
    with jax.named_scope("attn.kv_write"):
        if quantized:
            # Quantize-at-write: payload and scale scatter through the
            # SAME (layer, block, offset, head) indices with the same
            # mode="drop" out-of-range sentinel, so dropped writes
            # (padding / rescore entries) leave both tensors untouched
            # and quantization commutes with the sentinel, fork
            # refcounts, and COW — those act on whole blocks via the
            # pool movers, never element-wise.
            kq, ks = quantize_pool_kv(k[:, 0], leaves[0].dtype)
            vq, vs = quantize_pool_kv(v[:, 0], leaves[1].dtype)
            new = (kq, vq, ks, vs)
        else:
            new = (k[:, 0].astype(leaves[0].dtype),
                   v[:, 0].astype(leaves[1].dtype))
        # One update a (token, head): its window is the minor axis alone,
        # so the scatter runs in whatever layout the pool is stored in.
        # With a whole (Hkv, Dh) window, XLA:TPU gave a 1-byte pool two
        # layouts and converted the whole pool between them in every
        # layer.
        head = jnp.arange(leaves[0].shape[3])
        leaves = tuple(
            leaf.at[layer, write_block[:, None], write_off[:, None],
                    head].set(val, mode="drop")
            for leaf, val in zip(leaves, new))
    if row_plan is not None and not quantized:
        from ..ops.paged_attention import paged_attention_rows
        with jax.named_scope("attn.scores"):
            out = paged_attention_rows(q[:, 0], *leaves, layer, tables,
                                       positions, row_plan)[:, None]
    elif use_kernel and quantized:
        from ..ops.paged_attention import paged_flash_decode
        # the dequant-fused kernel takes ONE layer's pool and gathers
        # through the table itself
        k_pool, v_pool, k_scale, v_scale = (leaf[layer] for leaf in leaves)
        with jax.named_scope("attn.scores"):
            out = paged_flash_decode(q[:, 0], k_pool, v_pool,
                                     tables[seq_row], positions + 1,
                                     k_scale=k_scale,
                                     v_scale=v_scale)[:, None]
    else:
        with jax.named_scope("attn.kv_gather"):
            tbl = tables[seq_row]                              # (T, MB)
            mb, bs = tbl.shape[1], leaves[0].shape[2]
            k_seq, v_seq, *scales = (
                leaf[layer, tbl].reshape((t, mb * bs) + leaf.shape[3:])
                for leaf in leaves)
            if quantized:
                k_seq = dequantize_pool_kv(k_seq, scales[0], h.dtype)
                v_seq = dequantize_pool_kv(v_seq, scales[1], h.dtype)
        with jax.named_scope("attn.scores"):
            kv_pos = jnp.arange(mb * bs)[None, :]
            valid = kv_pos < positions[:, None] + 1
            out = attention(q, k_seq.astype(h.dtype),
                            v_seq.astype(h.dtype), q_offset=positions,
                            kv_mask=valid, causal=True)
    with jax.named_scope("attn.out"):
        attn_in = out.reshape(t, 1, c.q_dim)
        attn_out = _dense(attn_in, lp, "wo", "bse,ed->bsd")
        attn_out = _with_adapter(attn_out, attn_in, adapters, adapter_ids,
                                 "wo")
    return attn_out, leaves


def _writes(lp, write_block: jax.Array, leaf: jax.Array):
    """For an expert layer, the entries of the flat batch that write a
    cache row (the ones ``MoEStats`` counts); None for a dense layer."""
    return write_block < leaf.shape[1] if "router" in lp else None


def _paged_mla_attend(c: ModelConfig, lp: Dict[str, jax.Array], x: jax.Array,
                      cos: jax.Array, sin: jax.Array,
                      leaves: Tuple[jax.Array, ...], layer: jax.Array,
                      tables: jax.Array, seq_row: jax.Array,
                      positions: jax.Array, write_block: jax.Array,
                      write_off: jax.Array, use_kernel=None, adapters=None,
                      adapter_ids=None, row_plan=None):
    """``_paged_attend`` for latent attention: the pool's one payload leaf
    ``(L, num_blocks, block_size, 1, latent_row_dim)`` holds a token's
    ``[c_kv | k_rope | 0...]`` row (zero-padded to whole lane tiles, see
    ``ModelConfig.latent_row_dim``), written in place like a kv-head's row,
    and every entry of the flat batch — decode rows and prefill-chunk tokens
    alike — reads its sequence's rows through the table and attends in the
    ABSORBED form: with ``wkv_b`` split by head into W_kb and W_vb,

        score_i = (q_nope_i W_kb_i^T) . c_kv + q_rope_i . k_rope
        out_i   = (sum_s a_is c_kv_s) W_vb_i

    so nothing of the cached context is expanded to heads: the rows are
    read at their stored width, for the scores and for the weighted sum.
    The same mathematics as ``_mla_self_attention``'s expanded form.

    With a ``row_plan`` the rows are read where they lie by
    ``ops.paged_attention.paged_latent_attention_rows``: each run of one
    row's entries streams that row's live blocks once, and the one leaf
    serves both products. Without one, the XLA gather: every entry's whole
    table width copied, then read twice. It is the plain reference the
    kernel is tested against, and the path off the TPU."""
    t = x.shape[0]
    (leaf,) = leaves
    r = c.kv_lora_rank
    h = rms_norm(x, lp["attn_norm"], c.rms_norm_eps)
    q_nope, q_rope, latent = _mla_project(c, lp, h, cos, sin)
    exact = x.dtype == jnp.float32
    prec = jax.lax.Precision.HIGHEST if exact else None
    with jax.named_scope("attn.kv_write"):
        # one update a token, its window the minor axis alone (see
        # _paged_layer): the "head" axis of a latent pool has length 1
        pad = ((0, 0), (0, 0), (0, leaf.shape[-1] - latent.shape[-1]))
        leaf = leaf.at[layer, write_block[:, None], write_off[:, None],
                       jnp.arange(1)].set(
                           jnp.pad(latent.astype(leaf.dtype), pad),
                           mode="drop")
    with jax.named_scope("attn.absorb"):
        w_kb, w_vb = _mla_up_weights(c, lp)
        q_abs = jnp.einsum("thn,rhn->thr", q_nope[:, 0], w_kb,
                           precision=prec)
        # the row's zero tail meets a zero tail of the query: the product
        # runs over whole tiles and no slice of the gathered rows is made
        q_cat = jnp.pad(jnp.concatenate([q_abs, q_rope[:, 0]], axis=-1),
                        pad)
    # the scale is the model's (its q/k head width, YaRN's magnitude),
    # not the row's
    scale = c.attn_scale
    if row_plan is not None:
        from ..ops.paged_attention import paged_latent_attention_rows
        with jax.named_scope("attn.scores"):
            ctx = paged_latent_attention_rows(
                q_cat, leaf, layer, tables, positions, row_plan,
                scale=scale, value_dim=r)
    else:
        with jax.named_scope("attn.kv_gather"):
            tbl = tables[seq_row]                              # (T, MB)
            mb, bs = tbl.shape[1], leaf.shape[2]
            seq = leaf[layer, tbl].reshape(t, mb * bs, leaf.shape[-1])
        with jax.named_scope("attn.scores"):
            scores = jnp.einsum("thc,tsc->ths", q_cat, seq, precision=prec,
                                preferred_element_type=jnp.float32) * scale
            valid = jnp.arange(mb * bs)[None, :] < positions[:, None] + 1
            probs = jax.nn.softmax(
                jnp.where(valid[:, None, :], scores, NEG_INF), axis=-1)
            # over the whole row: a slice of the gathered rows would be a
            # copy of them; the rotary and zero columns of the small
            # result are cut
            ctx = jnp.einsum("ths,tsc->thc", probs.astype(x.dtype), seq,
                             precision=prec,
                             preferred_element_type=jnp.float32)[..., :r]
    with jax.named_scope("attn.out"):
        out = jnp.einsum("thr,rhv->thv", ctx.astype(x.dtype), w_vb,
                         precision=prec)
        out = _dense(out.reshape(t, 1, c.num_heads * c.v_head_dim), lp,
                     "wo", "bse,ed->bsd")
    return out, (leaf,)


def forward_paged(
    params: Params,
    config: ModelConfig,
    tokens: jax.Array,            # (T,) int32 — flat token batch
    *,
    pool,                         # rollout.paged_kv.PagedKVPool (duck-
                                  # typed pytree: k/v payload arrays,
                                  # optional k_scale/v_scale/k_hi/v_hi)
    tables: jax.Array,            # (R, MB) int32 — physical block per
                                  # (row, logical block)
    seq_row: jax.Array,           # (T,) int32 — table row per token
    positions: jax.Array,         # (T,) int32 — absolute position
    write_block: jax.Array,       # (T,) int32 — pool block to write
                                  # (num_blocks = drop)
    write_off: jax.Array,         # (T,) int32 — offset within block
    use_kernel: Optional[bool] = None,  # static: None = by what runs
    adapters=None,                # per-rung LoRA bank dicts, leading L
    adapter_ids=None,             # per-rung (T,) int32 slot ids
    with_moe_stats: bool = False,  # static: also return MoEStats
    with_mhc_stats: bool = False,  # static: also return the Sinkhorn error
    with_attn_stats: bool = False,  # static: also return the shared reads
    with_kda_stats: bool = False,  # static: also return the delta rule's
    logit_entries: Optional[jax.Array] = None,  # (S,) int32 — the entries
                                  # whose logits are wanted (None = all)
):
    """Run the model over a paged KV pool: every entry of the flat
    ``(T,)`` token batch is one (sequence, position) pair — a decode
    step or one token of a chunked-prefill segment — reading KV through
    the ``(row, logical_block) -> physical_block`` table. Returns
    ``(logits (T, V) fp32, pool')``. Token t's logits predict its next
    token, and only some entries' are ever read (decode entries and final
    prompt tokens): ``logit_entries``, an int32 vector of static length
    ``S``, names them, and the stream's rows at those entries are
    gathered BEFORE the final norm and the head, so the vocabulary is
    paid ``S`` times and the result is ``(logits (S, V) fp32, pool')``,
    row ``i`` what entry ``logit_entries[i]`` would have read in the
    every-entry form (an index out of range names no entry: a row whose
    logits are nobody's). Every layer that writes a cache row still runs
    over all ``T`` entries; a layer pattern's trailing segments whose
    kinds hold nothing (``models.config.STATELESS_KINDS``: a SambaY
    decoder's gated memory units and cross layers, whose one product for
    a prompt token is the logits nobody reads) run over the ``S``
    gathered entries, the gather moved up behind the last segment that
    writes (``_forward_paged_pattern``). Without the argument every entry
    runs every layer and pays the head (the draft paths, which read each
    entry's argmax or none).

    ``pool`` is the whole ``PagedKVPool`` pytree (accepted duck-typed
    to avoid a models → rollout import cycle). Its leaves travel in the
    layer scan's carry and are written and read in place at
    ``(layer, block, offset)``: jit the caller with the pool donated
    (``donate_argnames=("pool",)``, as every caller in rollout/ does)
    and the step holds no second pool; without the donation XLA copies
    the pool once on entry. A quantized pool
    (``k_scale is not None``) stores int8/fp8 payloads with per-token
    per-head f32 absmax scales, quantized AT WRITE TIME inside this one
    traced function — no extra device round-trips. An optional
    ``k_hi``/``v_hi`` full-width prefix holds the first
    ``pool.hi_layers`` layers (``kv_dtype_per_layer`` ladder: early
    layers, where divergence concentrates, stay bf16).

    A latent-attention configuration's pool has one payload leaf, ``k``
    ``(L, num_blocks, block_size, 1, latent_row_dim)``, and a zero-width ``v``
    (``rollout.paged_kv.init_paged_pool``). A configuration with leading
    dense layers scans its two stacks one after the other over the same
    carried leaves, each layer at its absolute index.

    ``use_kernel=None`` chooses by what the code sees
    (``reads_pool_in_place``): on a TPU an unquantized dense pool (and
    the full-width prefix layers of a ``kv_dtype_per_layer`` ladder)
    whose ``head_dim`` is a multiple of 128 is read by
    ``ops.paged_attention.paged_attention_rows`` and a latent pool by
    its one-leaf form ``paged_latent_attention_rows``; off the TPU, and
    for a quantized pool or narrower heads, the XLA gather. ``True``
    forces the kernels (interpreted off the TPU; on a quantized pool the
    dequant-fused ``paged_flash_decode``), ``False`` the gather: both
    are for tests.

    ``with_moe_stats=True`` (an expert configuration) returns a third
    value, ``MoEStats`` summed over the expert layers (``experts_touched``)
    and their largest (``expert_load_max``), counted over the entries that
    write a cache row: padding is routed, and is not work.

    ``with_mhc_stats=True`` (a multi-stream configuration, ``hc_mult``)
    returns one more value after it: the largest ``|row sum - 1|`` or
    ``|column sum - 1|`` of any H_res the call computed (``_residual``),
    a float32 scalar.

    ``with_attn_stats=True`` returns one more value, last: int32 ``(2,)``,
    what the attention kernels' plan found among the decode rows' tables
    (``ops.paged_attention.plan_rows``): the block reads the step did
    not make because rows that hold the same physical blocks attended
    them together (``kv_blocks_saved``), and the group items that did
    (``attn_group_items``); zeros where the gather runs.

    ``with_kda_stats=True`` (a configuration with "kda" layers) returns one
    more value, last: ``(entries that went through the chunked form, int32;
    the largest |o| any delta-rule layer read out before its head norm,
    float32)``."""
    c = config
    with _precision(c):
        logits, pool, moe, err, shared, kda = _forward_paged_impl(
            params, c, tokens, pool=pool, tables=tables,
            seq_row=seq_row, positions=positions, write_block=write_block,
            write_off=write_off, use_kernel=use_kernel, adapters=adapters,
            adapter_ids=adapter_ids, logit_entries=logit_entries)
    if shared is None and with_attn_stats:
        shared = jnp.zeros((2,), jnp.int32)
    return ((logits, pool) + ((moe,) if with_moe_stats else ())
            + ((err,) if with_mhc_stats else ())
            + ((shared,) if with_attn_stats else ())
            + ((kda,) if with_kda_stats else ()))


def reads_pool_in_place(c: ModelConfig, use_kernel: Optional[bool]) -> bool:
    """``forward_paged``'s choice for the unquantized leaves of a pool:
    True where ``paged_attention_rows`` (a latent pool:
    ``paged_latent_attention_rows``) reads them where they lie, False
    where the XLA gather copies each entry's table width. By what the
    code sees (``use_kernel=None``): a TPU takes the kernel where Mosaic
    can cut a block's rows out of the pool, which needs rows of whole
    128-lane tiles: a latent row is padded to them
    (``ModelConfig.latent_row_dim``); at a ``head_dim`` of 64 Mosaic
    refuses the block's window, and those models keep the gather. The
    engine asks too: where the kernel reads, a step's cost does not
    follow the table's width, so its table keeps one width."""
    if use_kernel is not None:
        return bool(use_kernel)
    from ..ops.paged_attention import on_tpu
    return on_tpu() and (c.mla or c.cache_head_dim % 128 == 0)


def ring_tables(rows: int, table_width: int, ring_blocks: int) -> jax.Array:
    """The block table of the "window" layers' rings, a constant of the
    shapes: row r's logical block b is ring block ``r ring_blocks + b %
    ring_blocks`` of the leaf read as ``rows x ring_blocks`` blocks
    (``rollout.paged_kv.StateRows.win_k``)."""
    return (jnp.arange(rows, dtype=jnp.int32)[:, None] * ring_blocks
            + jnp.arange(table_width, dtype=jnp.int32)[None, :]
            % ring_blocks)


def _write_rows(leaf: jax.Array, layer, block: jax.Array, off: jax.Array,
                val: jax.Array) -> jax.Array:
    """Each entry's cache row val (T, Hkv, D) into a payload leaf
    ``(L, NB, BS x f, Hkv / f, D)`` (the head axis folded,
    ``rollout.paged_kv.stored_kv_heads``) at ``(layer, block[t], off[t])``;
    a block past the leaf's is dropped. One update a (token, head), as
    ``_paged_attend``'s."""
    t, hkv, d = val.shape
    stored = leaf.shape[3]
    fold = hkv // stored
    rows = off[:, None] * fold + jnp.arange(fold)              # (T, f)
    return leaf.at[layer, block[:, None, None], rows[:, :, None],
                   jnp.arange(stored)].set(
        val.reshape(t, fold, stored, d).astype(leaf.dtype), mode="drop")


def _forward_paged_pattern(params, c, tokens, *, pool, tables, seq_row,
                           positions, write_block, write_off, use_kernel,
                           logit_entries=None):
    """``_forward_paged_impl`` for a ``layer_types`` configuration: the
    same ``_pattern_scan`` as the no-cache forward, over the pool's leaves.

    * "full" layers write and read the block-addressed ``k``/``v`` through
      the step's ``tables`` like any attention layer (PR 38's group items
      apply); the "cross" layers read the last "full" layer's blocks with
      that step's plan and tables and write nothing.
    * "window" layers write position p at slot ``p % capacity`` of their
      row's ring (``pool.rows.win_k`` / ``win_v``) and read the trailing
      ``layer_window`` positions through ``ring_tables``, the kernel by a
      window plan (``ops.paged_attention.plan_rows(window=)``): the ring
      holds a window and a step's entries, so the writes of a step never
      reach what its queries read, and a chunk that crosses the window's
      edge is whole prefill. A pattern without them has no rings
      (``pool.rows`` is a ``StateRows``).
    * "mamba" and "kda" layers read and write their rows' state and conv
      window (``ops.ssm.conv_flat``, ``scan1_flat``,
      ``ops.delta_rule.kda_flat``) once a run.

    Entries that keep no write (padding, dropped writes) advance no state
    and write no ring slot.

    With ``logit_entries`` the pattern's trailing segments whose kinds hold
    nothing (``ModelConfig.readers_from``: SambaY's cross-decoder) run over
    those ``S`` entries alone: the stream, the memory, ``seq_row`` and
    ``positions`` are gathered there behind the last segment that writes,
    the "cross" layers attend by a plan of the gathered entries (one a row:
    single-query items, a group's decode rows still one group item over
    their shared blocks), and the head takes the stream as it comes. An
    index past the last entry is nobody's: it runs as padding does, ``(row
    0, position 0)``, one block. A pattern that ends in a kind that writes
    gathers before the head, as ``_forward_paged_impl`` does.
    -> the tuple ``_forward_paged_impl`` returns."""
    t = tokens.shape[0]
    if pool.rows is None:
        refuse(c, "forward_paged(pool=)")
    with jax.named_scope("embed"):
        x = _embed(c, params, tokens, flat=True)                # (T, 1, D)
    hkv = c.cache_kv_heads
    fold = hkv // pool.k.shape[3]
    bs = pool.k.shape[2] // fold
    state, window = pool.rows.ssm, pool.rows.conv
    rings = c.kind_layers("window") > 0
    rows_r = tables.shape[0]
    keep = write_block < pool.k.shape[1]
    scale = 1.0 / float(c.head_dim) ** 0.5
    kernel = reads_pool_in_place(c, use_kernel)
    win_k = win_v = ring_tbl = ring_block = None
    if rings:
        win_k, win_v = pool.rows.win_k, pool.rows.win_v
        ring_blocks = win_k.shape[2] // (bs * fold)
        cap = ring_blocks * bs
        if t > cap - c.layer_window + 1:
            raise ValueError(
                f"{c.name}: a step of {t} entries over rings of {cap} "
                f"positions (window {c.layer_window}): the pool was made "
                f"for fewer step_tokens")
        # the rings as blocks: (Lw, rows x ring_blocks, BS x f, Hkv / f, D)
        as_blocks = lambda a: a.reshape(
            (a.shape[0], a.shape[1] * ring_blocks, bs * fold) + a.shape[3:])
        ring_shape = win_k.shape
        win_k, win_v = as_blocks(win_k), as_blocks(win_v)
        ring_tbl = ring_tables(rows_r, tables.shape[1], ring_blocks)
        ring_block = jnp.where(
            keep, seq_row * ring_blocks + (positions // bs) % ring_blocks,
            win_k.shape[1])

    def plan_of(rows, pos, window=0):
        """The kernel's plan of the entries at ``(rows, pos)`` over the
        block tables, or with ``window`` over the rings; None where the
        gather runs."""
        if not kernel:
            return None
        from ..ops.paged_attention import group_tile, plan_rows, query_tile
        blocks = {} if window else dict(
            tables=tables, group_tile=group_tile(c.num_heads))
        with jax.named_scope("attn.row_plan"):
            return plan_rows(
                rows, pos, block_size=bs, table_width=tables.shape[1],
                q_tile=query_tile(c.num_heads), window=window, **blocks)

    # a layer's entries: their rows, their positions, the plan of them
    step = (seq_row, positions, plan_of(seq_row, positions))
    ring = (seq_row, positions,
            plan_of(seq_row, positions, c.layer_window) if rings else None)
    shared = (jnp.stack([step[2].kv_blocks_saved, step[2].group_items])
              if kernel else None)
    # the segments from ``cut`` on hold nothing: where the step names the
    # entries whose logits are read they run over those (``read``), an
    # index that names none as padding does
    cut = c.readers_from if logit_entries is not None else len(c.layer_types)
    read = None
    if cut < len(c.layer_types):
        named = logit_entries < t
        at_entry = lambda a, none: jnp.where(
            named, jnp.take(a, logit_entries, mode="clip"), none)
        read = (at_entry(seq_row, 0), at_entry(positions, 0))
        read = (*read, plan_of(*read))
    with jax.named_scope("ssm.run_plan"):
        run_plan = ssm_ops.plan_runs(seq_row, positions, keep,
                                     num_rows=rows_r)
    # a delta-rule configuration's step also says how many entries its
    # chunked form took and the largest readout any layer computed
    kda = (jnp.zeros((), jnp.float32) if c.kind_layers("kda") else None)

    def attend(q, k_leaf, v_leaf, layer, tbl, span, ent):
        """q (T, 1, Hq, D), the queries of the entries ``ent``, over one
        layer of folded leaves -> the same shape; ``span`` > 0: the
        trailing positions a query reads."""
        seq_row, positions, plan = ent
        if plan is not None:
            from ..ops.paged_attention import paged_attention_rows
            with jax.named_scope("attn.scores"):
                return paged_attention_rows(
                    q[:, 0], k_leaf, v_leaf, layer, tbl, positions, plan,
                    scale=scale, kv_heads=hkv)[:, None]
        with jax.named_scope("attn.kv_gather"):
            mine = tbl[seq_row]                                 # (T, MB)
            seq = lambda leaf: leaf[layer, mine].reshape(
                q.shape[0], mine.shape[1] * bs, hkv,
                leaf.shape[-1]).astype(q.dtype)
            k_seq, v_seq = seq(k_leaf), seq(v_leaf)
        with jax.named_scope("attn.scores"):
            kv_pos = jnp.arange(k_seq.shape[1])[None, :]
            valid = kv_pos <= positions[:, None]
            if span:
                valid &= kv_pos > positions[:, None] - span
            return attention(q, k_seq, v_seq, kv_mask=valid, causal=False,
                             scale=scale)

    def mixer(kind, lp, h, m, leaves, at, ent=step):
        """``ent``: h's entries where they are not the step's (``read``:
        no kind that writes runs there)."""
        k_leaf, v_leaf, state, window, win_k, win_v, kda = leaves
        if ent is read and kind not in STATELESS_KINDS:
            raise ValueError(f"{c.name}: a {kind!r} layer writes its cache "
                             f"for every entry, not for the sampled ones")
        if kind == "mamba":
            u_in, z = _mamba1_in(c, lp, h)
            with jax.named_scope("ssm.conv"):
                conv, window = ssm_ops.conv_flat(
                    u_in[:, 0], lp["ssm_conv_w"], lp["ssm_conv_b"], window,
                    at["index"], seq_row, run_plan)
                u, dt, a, b, cc = _mamba1_inputs(c, lp, conv[:, None],
                                                 h.dtype)
            with jax.named_scope("ssm.scan"):
                y, state = ssm_ops.scan1_flat(
                    u[:, 0], dt[:, 0], a, b[:, 0], cc[:, 0], lp["ssm_D"],
                    state, at["index"], seq_row, run_plan)
            out, m = _mamba1_out(c, lp, y[:, None], z)
        elif kind == "gmu":
            out = _gmu(lp, h, m)
        elif kind == "kda":
            with jax.named_scope("kda.conv"):
                conv, window = ssm_ops.conv_flat(
                    _kda_project(c, lp, h)[:, 0], lp["kda_conv_w"], None,
                    window, at["index"], seq_row, run_plan)
                q, k, v = _kda_qkv(c, conv[:, None])
            with jax.named_scope("kda.gates"):
                g, beta = _kda_gates(c, lp, h)
            o, state = delta_rule.kda_flat(
                q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state,
                at["index"], seq_row, run_plan)
            kda = jnp.maximum(kda, jnp.max(jnp.abs(o)))
            out = _kda_out(c, lp, o[:, None], h)
        elif not c.diff_attn:
            with jax.named_scope("attn.qkv"):
                q, k, v = _plain_qkv(c, lp, h)
            with jax.named_scope("attn.kv_write"):
                k_leaf = _write_rows(k_leaf, at["index"], write_block,
                                     write_off, k[:, 0])
                v_leaf = _write_rows(v_leaf, at["index"], write_block,
                                     write_off, v[:, 0])
            out = _gated_out(c, lp, attend(q, k_leaf, v_leaf, at["index"],
                                           tables, 0, ent), h)
        else:
            with jax.named_scope("attn.qkv"):
                q = _diff_queries(c, lp, h)
                if kind != "cross":
                    k, v = _diff_kv(c, lp, h)
            if kind == "full":
                with jax.named_scope("attn.kv_write"):
                    k_leaf = _write_rows(k_leaf, at["index"], write_block,
                                         write_off, k[:, 0])
                    v_leaf = _write_rows(v_leaf, at["index"], write_block,
                                         write_off, v[:, 0])
            if kind == "window":
                with jax.named_scope("attn.kv_write"):
                    win_k = _write_rows(win_k, at["index"], ring_block,
                                        positions % bs, k[:, 0])
                    win_v = _write_rows(win_v, at["index"], ring_block,
                                        positions % bs, v[:, 0])
                got = attend(q, win_k, win_v, at["index"], ring_tbl,
                             c.layer_window, ring)
            else:
                got = attend(q, k_leaf, v_leaf,
                             at["index" if kind == "full" else "full"],
                             tables, 0, ent)
            out = _diff_out(c, lp, got, at["layer"])
        return out, m, (k_leaf, v_leaf, state, window, win_k, win_v, kda)

    x, m, leaves, moe = _pattern_scan(
        c, params, x, (pool.k, pool.v, state, window, win_k, win_v, kda),
        mixer, count=keep, segments=(0, cut))
    if read is not None:
        # behind the last layer that writes, the entries somebody samples
        # go on alone
        with jax.named_scope("lm_head"):
            x, m = _logit_rows(x, logit_entries), _logit_rows(m,
                                                              logit_entries)
        x, _, leaves, moe = _pattern_scan(
            c, params, x, leaves, functools.partial(mixer, ent=read),
            count=at_entry(keep, False),
            segments=(cut, len(c.layer_types)), m=m, acc=moe)
        logit_entries = None
    k_leaf, v_leaf, state, window, win_k, win_v, kda = leaves
    pool = pool._replace(k=k_leaf, v=v_leaf, rows=type(pool.rows)(
        state, window, *((win_k.reshape(ring_shape),
                          win_v.reshape(ring_shape)) if rings else ())))
    if kda is not None:
        kda = (jnp.sum(jnp.where(run_plan.row_len >= 2, run_plan.row_len, 0)
                       ).astype(jnp.int32), kda)
    with jax.named_scope("lm_head"):
        logits = _lm_head(c, params, x, logit_entries, flat=True)
    return logits, pool, moe, None, shared, kda


def _forward_paged_impl(params, c, tokens, *, pool, tables,
                        seq_row, positions, write_block, write_off,
                        use_kernel, adapters=None, adapter_ids=None,
                        logit_entries=None):
    if adapters is not None:
        refuse(c, "forward_paged(adapters=)")
    if pool.k_scale is not None:
        refuse(c, "forward_paged(pool=)")
    if c.pattern:
        return _forward_paged_pattern(
            params, c, tokens, pool=pool, tables=tables, seq_row=seq_row,
            positions=positions, write_block=write_block,
            write_off=write_off, use_kernel=use_kernel,
            logit_entries=logit_entries)
    with jax.named_scope("embed"):
        # (T, 1, D), or the stream (T, 1, hc_mult, D)
        x = _embed(c, params, tokens, flat=True)
        cos, sin = _rope_tables(c, positions[:, None])
    # STATIC under jit: derived from pytree structure (None-ness and
    # shapes), so the precision ladder never adds a trace argument.
    n_hi = 0 if pool.k_hi is None else pool.k_hi.shape[0]
    row_plan = shared = None
    if (pool.k_scale is None or n_hi) and reads_pool_in_place(c,
                                                              use_kernel):
        # once a step, outside the layer scans: the flat batch cut into
        # the runs of one row that the kernel attends together
        # and, by the tables, the rows that share their leading blocks
        from ..ops.paged_attention import group_tile, plan_rows, query_tile
        with jax.named_scope("attn.row_plan"):
            row_plan = plan_rows(seq_row, positions,
                                 block_size=pool.k.shape[2],
                                 table_width=tables.shape[1],
                                 q_tile=query_tile(c.num_heads),
                                 tables=tables,
                                 group_tile=group_tile(c.num_heads))
            shared = jnp.stack([row_plan.kv_blocks_saved,
                                row_plan.group_items])
    run_plan = None
    rows = ()
    if c.ssm:
        # likewise once a step: the kept entries of each row are one run
        # of the state-space mixer (padding and dropped writes advance
        # nothing). The row-addressed leaves ride the carry behind the
        # block-addressed ones.
        rows = (pool.rows.ssm, pool.rows.conv)
        with jax.named_scope("ssm.run_plan"):
            run_plan = ssm_ops.plan_runs(
                seq_row, positions, write_block < pool.k.shape[1],
                num_rows=tables.shape[0])

    def scan_layers(x, layers, ad, leaves, first=0, state_first=None):
        """The layer scan over one group of pool leaves. The leaves ride
        the CARRY, stacked as stored, and each layer scatters into and
        gathers from them at its own index: XLA aliases a while loop's
        carry in place (it cannot alias xs with ys), so with the pool
        donated no layer is sliced out, written back or copied. The xs
        are the layer params, the adapter banks (leading L axis,
        rollout/adapter_pool; ``None`` scans as an empty pytree and
        unpacks back to None) and the layer index, counted from
        ``first``: a stack that is not the leaves' first indexes them by
        its layers' absolute numbers. An expert stack also carries and
        returns its ``MoEStats`` (None for a dense stack), and a
        multi-stream configuration's the largest Sinkhorn error of its
        sublayers (None for the plain residual). ``state_first``: the
        absolute number of the stack's first layer, which indexes the
        row-addressed state leaves (``first`` where the block leaves are
        indexed by it too)."""
        state_first = first if state_first is None else state_first
        n = jax.tree_util.tree_leaves(layers)[0].shape[0]
        counts = banks = None
        worst = jnp.zeros((), jnp.float32) if c.hc_mult else None
        if "router" in layers:
            counts = MoEStats.zeros(c)
            # The expert banks stay whole outside the xs: each layer's
            # grouped products address its experts inside them
            # (moe._grouped) instead of taking a copy of its slice.
            banks = {k: v for k, v in layers.items() if k in BANKS}
            layers = {k: v for k, v in layers.items() if k not in BANKS}

        def body(carry, inputs):
            x, leaves, acc, worst = carry
            lp, ad_l, layer = inputs
            if banks is not None:
                lp = {**lp, **banks}
            x, leaves, stats, err = _paged_layer(
                c, lp, x, cos, sin, leaves, layer, tables, seq_row,
                positions, write_block, write_off, use_kernel=use_kernel,
                adapters=ad_l, adapter_ids=adapter_ids,
                stack_layer=None if banks is None else layer - first,
                row_plan=row_plan, run_plan=run_plan,
                state_layer=(layer + (state_first - first) if c.ssm
                             else None))
            if stats is not None:
                acc = acc.merge(stats)
            return (x, leaves, acc, _worst(worst, err)), None

        index = jnp.arange(first, first + n, dtype=jnp.int32)
        (x, leaves, counts, worst), _ = jax.lax.scan(
            body, (x, leaves, counts, worst), (layers, ad, index))
        return x, leaves, counts, worst

    layers, lo_ad, err = params["layers"], adapters, None
    names = ("k", "v") if pool.k_scale is None else (
        "k", "v", "k_scale", "v_scale")
    upd = {}
    if n_hi:
        # Full-width prefix layers scan first, then the quantized tail:
        # two scans over layer slices instead of one (the per-layer
        # ladder is a partition, so the slices are contiguous).
        sl_hi = functools.partial(jax.tree_util.tree_map,
                                  lambda a: a[:n_hi])
        sl_lo = functools.partial(jax.tree_util.tree_map,
                                  lambda a: a[n_hi:])
        x, (upd["k_hi"], upd["v_hi"], *rows), hi_moe, err = scan_layers(
            x, sl_hi(layers), sl_hi(adapters),
            (pool.k_hi, pool.v_hi) + rows)
        layers, lo_ad = sl_lo(layers), sl_lo(adapters)
    if c.mla:
        names = ("k",)      # the latent rows; ``v`` has no width
    leaves = tuple(getattr(pool, n) for n in names) + tuple(rows)
    first = 0
    if "dense_layers" in params:
        if n_hi or adapters is not None:
            raise NotImplementedError(
                "a kv_dtype_per_layer prefix or adapter banks over a "
                "configuration with leading dense layers")
        # the leading dense-FFN stack, over the same carried leaves
        x, leaves, _, err = scan_layers(x, params["dense_layers"], None,
                                        leaves)
        first = c.first_dense_layers
    x, leaves, moe, last_err = scan_layers(x, layers, lo_ad, leaves, first,
                                           state_first=first + n_hi)
    err = _worst(err, last_err)
    if n_hi and moe is not None:
        # the full-width prefix layers are expert layers of the same model
        moe = moe.merge(hi_moe)
    upd.update(zip(names, leaves))
    if c.ssm:
        upd["rows"] = type(pool.rows)(*leaves[-2:])

    with jax.named_scope("lm_head"):
        logits = _lm_head(c, params, x, logit_entries, flat=True)
    return logits, pool._replace(**upd), moe, err, shared, None


def count_params(params: Params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
