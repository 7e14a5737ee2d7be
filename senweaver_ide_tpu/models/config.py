"""Model configurations for the policy LLM families.

The reference targets remote/provider-hosted models (capability DB in
``common/modelCapabilities.ts``); the north star pins the local policy ladder
Qwen2.5-Coder-1.5B → DeepSeek-Coder-7B (BASELINE.json configs 3-5). Both
families are decoder-only pre-norm transformers with RoPE + SwiGLU; Qwen2 uses
GQA + QKV biases, DeepSeek-Coder is LLaMA-architecture (MHA at 1.3B/6.7B,
no attention biases).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple, Union

import jax.numpy as jnp


class FormUnsupported(NotImplementedError):
    """A mechanism has no form yet for one form of model: raised where the
    mechanism is asked for (``refuse``), never replaced by a silent
    fallback. ``mechanism`` names it. A subclass is one form: ``clause``
    ends the message, ``has`` tells whether a configuration is of it."""

    clause = ""
    has = staticmethod(lambda c: False)

    def __init__(self, mechanism: str, config_name: str):
        super().__init__(f"{mechanism} is not implemented for "
                         + self.clause.format(name=config_name))
        self.mechanism = mechanism


class LatentCacheUnsupported(FormUnsupported):
    """Latent attention (MLA): the cache row is one ``[c_kv | k_rope]``
    vector a token."""
    clause = ("the latent-attention configuration {name!r}: its paged "
              "cache holds one latent vector a token, not (k, v) by kv-head")
    has = staticmethod(lambda c: c.mla)


class ResidualStreamUnsupported(FormUnsupported):
    """A residual path ``hc_mult`` streams wide (``transformer._residual``):
    never the plain residual add."""
    clause = ("the multi-stream residual (hc_mult > 0) of configuration "
              "{name!r}")
    has = staticmethod(lambda c: c.hc_mult > 0)


class RecurrentStateUnsupported(FormUnsupported):
    """Recurrent state in every block (``paged_kv.StateRows``): it has no
    position to re-read and is copied, never shared by refcount. A layer
    pattern's mixers are ``LayerPatternUnsupported``'s."""
    clause = ("the recurrent state (mamba_d_ssm > 0) of configuration "
              "{name!r}: a row's state is overwritten every token and has "
              "no snapshot there")
    has = staticmethod(lambda c: c.ssm and not c.pattern)


class ExpertShareUnsupported(FormUnsupported):
    """An expert layer that holds a share of the experts its router
    addresses, has identity experts, or sits on a shortcut beside a double
    block: never a layer that would drop the absent experts' pairs."""
    clause = ("the expert share / identity experts / shortcut block of "
              "configuration {name!r}")
    has = staticmethod(lambda c: c.expert_share or c.shortcut_moe)


class LayerPatternUnsupported(FormUnsupported):
    """Layers of unlike kinds in a fixed pattern (``layer_types``), each
    kind with a cache of its own or none: never a path that would treat
    them as one kind or hold a window layer's cache at full length."""
    clause = ("the layer pattern (layer_types) of configuration {name!r}: "
              "its layers are of unlike kinds, each with a cache of its own "
              "or none")
    has = staticmethod(lambda c: c.pattern)


_Latent, _Streams, _State, _Share, _Pattern = (
    LatentCacheUnsupported, ResidualStreamUnsupported,
    RecurrentStateUnsupported, ExpertShareUnsupported,
    LayerPatternUnsupported)
_SLOTS = "the slot KVCache layout (EngineConfig.kv_layout='slots')"
_LADDER = "the quantized KV ladder (EngineConfig.kv_dtype int8/fp8)"
_POOL = "the multi-LoRA adapter pool"


def _row(text: str, *forms) -> dict:
    """One text for ``forms``, asked in that order."""
    return dict.fromkeys(forms, text)


# Which form of model lacks which mechanism: mechanism -> {form: what the
# error calls the mechanism}, the forms in the order the mechanism's entry
# point asks them (a configuration of two forms raises the first; a text
# given after a ``_row`` replaces that form's and keeps its place). Every
# cell raises (``refuse``); a pair that is not here is not refused.
# docs/serving.md shows this table as a matrix.
UNSUPPORTED = {
    # RolloutEngine(...): these forms serve from the paged pool on one chip
    # alone (a latent pool; the streams through forward_paged; state and
    # rings in row-addressed leaves). What the engine would answer with the
    # slot layout, which has no place for their caches and would hold a
    # window layer's at full length, is refused at construction instead.
    "RolloutEngine(kv_layout='slots')": {
        **_row(_SLOTS, _Latent, _Streams, _Pattern, _State),
        _Streams: "the slot KVCache path"},
    "RolloutEngine(config.kv_quant)": {
        **_row("the slot int8 cache (kv_quant)", _Latent, _Streams,
               _Pattern, _State),
        _Streams: "the slot KVCache path"},
    "RolloutEngine(kv_dtype=)": _row(_LADDER, _Pattern),
    "RolloutEngine(config.sliding_window)": {
        **_row("the sliding-window ring cache", _Latent, _Streams,
               _Pattern, _State),
        _Streams: "the slot KVCache path",
        _Pattern: "the sliding-window ring cache of the slot layout "
                  "(sliding_window)"},
    "RolloutEngine(mesh=)": {
        **_row("a mesh (mesh=...)", _Latent, _Streams, _Pattern, _State),
        _Latent: "tensor-parallel KV sharding (mesh=...)"},
    "RolloutEngine(adapter_pool=)": _row(_POOL, _Latent, _Streams, _Pattern,
                                         _State),
    # a rejected draft cannot roll a state back; the draft's form counts
    "enable_speculation": _row("fused draft/verify speculation", _Pattern,
                               _State, _Latent),
    # a prefix's, a branch's, a checkpoint's blocks are shared, grafted or
    # shipped by block: state and rings have no snapshot counterpart yet
    "register_prefix": {
        **_row("registered prefixes (register_prefix: a prefix's blocks are "
               "grafted, its state has no snapshot)", _Latent, _Pattern,
               _State),
        _Latent: "registered prefixes (their prefill runs over the slot "
                 "KVCache)"},
    "export_prefix": _row("prefix export (export_prefix)", _Pattern, _State),
    "import_prefix": _row(
        "prefix import (import_prefix: the peer's KV comes without the state "
        "behind it)", _Pattern, _State),
    "fork_request": _row(
        "fork_request (a branch shares KV blocks by refcount; the state has "
        "no fork yet)", _Pattern, _State),
    "checkpoint_request": _row(
        "request checkpoints and migration (checkpoint_request: a "
        "DecodeCheckpoint holds KV blocks, no state)", _Pattern, _State),
    "restore_request": _row(
        "request checkpoints and migration (restore_request)", _Pattern,
        _State),
    "init_kv_cache": _row("the slot KVCache layout", _Latent, _Pattern,
                          _State),
    "forward(cache=)": _row("forward(cache=...) over the slot KVCache",
                            _Pattern, _Streams, _State, _Latent),
    # the share over an 'ep' axis needs its exchange; parallel/expert.py
    # drops pairs for capacity and knows no identity expert
    "forward(mesh=)": _row("forward(mesh=...)", _Pattern, _Streams, _State,
                           _Share),
    "forward(attn_impl=)": _row(
        "attn_impl={c.attn_impl!r} / sliding_window={c.sliding_window} in "
        "the no-cache forward", _Latent),
    "forward_paged(adapters=)": {
        **_row("adapter banks in forward_paged", _Pattern, _State, _Latent,
               _Streams),
        _Latent: "adapter banks / a quantized pool in forward_paged"},
    "forward_paged(pool=)": {
        _Pattern: "a quantized pool / a pool without row-addressed state "
                  "in forward_paged",
        _Latent: "adapter banks / a quantized pool in forward_paged"},
    "init_paged_pool(kv_dtype=)": _row(_LADDER, _Pattern, _Latent),
    "init_lora": {
        _Latent: "LoRA on the latent projections (targets are "
                 "wq/wk/wv/wo)",
        _Streams: "LoRA adapters",
        _Pattern: "LoRA adapters (init_lora: the layers' leaves are by "
                  "segment and kind, and the trainer has no backward of "
                  "the Mamba-1 scan over runs)",
        _State: "LoRA adapters (init_lora: the mixer's projections are no "
                "target, and the trainer has no backward of the chunked "
                "scan)"},
    "AdapterPool": {
        **_row(_POOL, _Latent, _Streams, _Pattern, _State),
        _Latent: _POOL + " (its targets are wq/wk/wv/wo)"},
    # the checkpoint's names for the maps' and the mixer's leaves and for a
    # double block's sublayers are not known here, nor which of its heads
    # form a differential set, nor which experts a chip's share of a
    # checkpoint would be cut from
    "load_hf_params": _row("the HF loader", _Streams, _Pattern, _State,
                           _Share),
    "export_hf_params": _row("the HF exporter", _Streams, _Pattern, _State,
                             _Share),
}


def refuse(config, mechanism: str, also=None) -> None:
    """Raise for the first form of ``UNSUPPORTED[mechanism]`` that
    ``config`` is of (or ``also``, a second configuration the mechanism
    would run: a draft); return if it is of none."""
    for form, text in UNSUPPORTED[mechanism].items():
        if form.has(config) or (also is not None and form.has(also)):
            raise form(text.format(c=config), config.name)


# The kinds of layer a ``layer_types`` pattern may name, each ``x + mix(
# Norm(x))`` then ``x + MLP(Norm(x))`` (``models.transformer._pattern_layer``):
#   "mamba"   a Mamba-1 mixer; holds row-addressed state; publishes its
#             scan's output as the memory ``m`` of the layers after it
#   "window"  self-attention over the trailing ``layer_window`` positions;
#             holds a row-addressed ring of that many positions and a step
#   "full"    causal self-attention (differential under ``diff_attn``, else
#             plain GQA, its output gated under ``attn_out_gate``); holds
#             block-addressed KV
#   "gmu"     a gated memory unit: a gate of the layer's input on ``m``;
#             holds nothing
#   "cross"   attention of the layer's queries over the last "full"
#             layer's KV; holds nothing
#   "kda"     a gated delta-rule mixer with a decay a key channel
#             (``ops/delta_rule.py``); holds a row-addressed matrix state a
#             head and the conv window of its q, k and v
# The second sublayer is the dense SwiGLU, or the expert layer where the
# configuration has experts (``models.transformer._ffn``).
LAYER_KINDS = ("mamba", "window", "full", "gmu", "cross", "kda")
# The kinds that hold nothing: such a layer writes no state, ring or KV, so a
# token's pass through it leaves nothing behind for a later token but what it
# adds to the token's own stream (``ModelConfig.readers_from``).
STATELESS_KINDS = frozenset(("gmu", "cross"))


def pattern_keys(period) -> Tuple[str, ...]:
    """The names of a period's layers in ``params["layers"]["seg<i>"]``:
    the kind, with its place in the period behind it where the period
    names the kind more than once (``("full", "kda", "kda")`` ->
    ``("full", "kda1", "kda2")``): each layer of a period has leaves of its
    own, stacked ``repeats`` deep, so no leaf has two readers in the scan's
    body."""
    return tuple(kind if period.count(kind) == 1 else f"{kind}{j}"
                 for j, kind in enumerate(period))


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """Llama-3-style NTK-by-parts RoPE scaling (HF ``rope_type: llama3``).

    Frozen (hashable) because ModelConfig rides jit static args. Fields
    mirror the HF ``rope_scaling`` dict of Llama-3.1+ checkpoints."""
    factor: float = 8.0
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_position: int = 8192


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN rotary scaling (HF ``rope_scaling.type: yarn``, the DeepSeek-V2
    form): frequencies whose wavelength fits the original window
    ``beta_fast`` times or more are kept, those that fit it ``beta_slow``
    times or less are divided by ``factor``, a linear ramp over the pair
    index between (``ops.rotary.scale_frequencies_yarn``). cos and sin are
    multiplied by m(``mscale``) / m(``mscale_all_dim``) and the softmax
    scale of latent attention by m(``mscale_all_dim``)^2, with
    m(k) = 0.1 k ln(factor) + 1 (``ModelConfig.attn_scale``)."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def magnitude(self, k: float) -> float:
        """YaRN's m(k): 1 where nothing is stretched."""
        if self.factor <= 1.0 or not k:
            return 1.0
        return 0.1 * k * math.log(self.factor) + 1.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    max_seq_len: int
    rope_theta: float = 10000.0
    # Long-context frequency scaling: Llama-3.1+'s NTK-by-parts
    # (``RopeScaling``) or YaRN (``YarnScaling``, latent attention only);
    # None = plain RoPE.
    rope_scaling: Optional[Union[RopeScaling, YarnScaling]] = None
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    qkv_bias: bool = False
    # Qwen3-style per-head RMSNorm on q and k (over head_dim, before
    # RoPE) — replaces Qwen2's qkv biases as the attention stabilizer.
    qk_norm: bool = False
    # int8 KV cache with per-(position, head) scales: halves cache HBM so
    # memory-capacity-bound serving (6.7b on one 16 GB chip) fits 2× the
    # decode batch. See models/transformer.py _quantize_kv.
    kv_quant: bool = False
    dtype: jnp.dtype = jnp.bfloat16
    # Sliding-window attention width (None = full causal).
    sliding_window: Optional[int] = None
    # Attention implementation for the no-cache (training/scoring) path:
    #   "einsum"  — XLA einsum attention (ops/attention.py), materializes
    #               the (Sq, Skv) score matrix; fine for short sequences.
    #   "flash"   — Pallas flash-attention kernel (ops/flash_attention.py),
    #               O(S·block) memory; interpret-mode on non-TPU backends.
    #   "ring"    — ring attention over the 'sp' mesh axis
    #               (parallel/ring_attention.py); requires forward(mesh=...)
    #               with an sp axis and S divisible by its size.
    #   "ulysses" — Ulysses all-to-all head/sequence swap over 'sp'; head
    #               counts must divide by the sp axis size.
    attn_impl: str = "einsum"
    # Rematerialize layer activations in the no-cache (training) path:
    # jax.checkpoint around each scanned layer, so backward recomputes
    # activations instead of saving L layers of them — the HBM-for-FLOPs
    # trade that fits 7B long-trajectory batches (with ring attention and
    # train_step(accum_steps=...)). "dots" saves matmul outputs only
    # (checkpoint_dots); True/"full" saves nothing.
    remat: object = False    # False | True | "full" | "dots"
    # jax.default_matmul_precision for the forward pass. None = platform
    # default (bf16 MXU passes — the fast path for real models). The fp32
    # test config pins "highest" so cache-vs-full decode parity is exact.
    matmul_precision: Optional[str] = None
    # Mixture-of-experts FFN: 0 = dense. When > 0, every layer after the
    # ``first_dense_layers`` has a top-k routed expert bank that drops
    # nothing (models/moe.py) and the expert axis shards over 'ep'.
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # HF checkpoint layout for the expert banks on EXPORT ("mixtral":
    # block_sparse_moe w1/w3/w2; "qwen3": mlp.experts gate/up/down_proj).
    # The loader autodetects from the checkpoint keys.
    moe_layout: str = "mixtral"
    # Width of one routed (and one shared) expert; None = intermediate_size
    # (Mixtral/Qwen3-MoE presets, where every layer is an expert layer).
    moe_intermediate_size: Optional[int] = None
    # Shared experts: a dense SwiGLU of width n x moe width that every
    # token passes beside its routed experts (DeepSeek-V2/V3, GLM-4.x MoE).
    num_shared_experts: int = 0
    # Leading layers that keep a dense FFN of ``intermediate_size``
    # (HF ``first_k_dense_replace``); the rest are expert layers. Their
    # params live in ``params["dense_layers"]``, the expert stack in
    # ``params["layers"]``; both stacks are scanned one after the other.
    first_dense_layers: int = 0
    # Router form: "softmax" = softmax over experts, top-k, weights
    # renormalised over the chosen (Mixtral, Qwen3-MoE); "sigmoid_bias" =
    # sigmoid scores, choice by score + a per-expert correction bias,
    # weights = the chosen scores WITHOUT the bias, normalised, times
    # ``routed_scaling_factor`` (DeepSeek-V3 ``noaux_tc`` with one group);
    # "softmax_bias" = softmax scores over every router output, choice by
    # score + correction bias, weights = the chosen scores as they are (NO
    # renormalisation) times ``routed_scaling_factor`` (LongCat-Flash).
    router_type: str = "softmax"
    routed_scaling_factor: float = 1.0
    # The chip's share of the experts. ``num_experts`` stays the banks held
    # in ``w_gate/w_up/w_down``; the router addresses
    # ``moe_routed_experts`` real experts (0 = ``num_experts``: every
    # expert is held) of which this chip holds those numbered
    # [``moe_first_expert``, ``moe_first_expert`` + ``num_experts``), and
    # after them ``moe_zero_experts`` identity experts that return the
    # token itself and hold no weights (LongCat-Flash's
    # ``zero_expert_num``, ``zero_expert_type: identity``). A pair routed
    # to a real expert that is not held adds nothing here (models/moe.py).
    moe_routed_experts: int = 0
    moe_first_expert: int = 0
    moe_zero_experts: int = 0
    # The shortcut-connected expert block (LongCat-Flash): a layer is TWO
    # attention sublayers and TWO dense FFNs of ``intermediate_size``, and
    # the expert layer reads the first FFN's normed input and rejoins the
    # stream with the second FFN's output
    # (``models.transformer._shortcut_block``). The cache then holds two
    # attention layers a layer (``attn_layers``).
    shortcut_moe: bool = False
    # Multi-head latent attention (DeepSeek-V2; GLM-4.7-Flash): q through a
    # rank-``q_lora_rank`` bottleneck, keys and values through one shared
    # ``kv_lora_rank`` latent plus one decoupled rotary key of
    # ``qk_rope_head_dim`` a token. 0 = classic q/k/v projections. With MLA
    # ``head_dim`` is the query/key width (nope + rope), ``num_kv_heads``
    # equals ``num_heads`` and the paged cache holds ``latent_dim`` values
    # a token a layer instead of (k, v) by kv-head.
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # LongCat-Flash's two latent scales: the queries times
    # sqrt(hidden_size / q_lora_rank), the normed kv latent times
    # sqrt(hidden_size / kv_lora_rank); the rotary key is not scaled.
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    # Manifold-constrained hyper-connections (mHC): the residual stream is
    # ``hc_mult`` rows wide and every sublayer reads and writes it through
    # three maps computed from the token's own stream, one of them made
    # doubly stochastic by ``hc_sinkhorn_iters`` rounds of column-then-row
    # normalisation (``hc_eps`` in each divisor) of the exponential of its
    # logits clipped to [``mhc_h_res_clamp_min``, ``mhc_h_res_clamp_max``]
    # (``models.transformer._residual``). 0 = the plain ``x + f(x)``.
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    # A Mamba-2 state-space mixer beside attention in every block
    # (Falcon-H1): both read the block's one normed input and their scaled
    # outputs are summed into the residual (``models.transformer._mixers``,
    # ``ops/ssm.py``). ``mamba_d_ssm`` = ``mamba_n_heads`` x
    # ``mamba_d_head`` inner values, each head a (d_head, d_state) state;
    # ``mamba_n_groups`` groups of heads share one B and one C;
    # a causal depthwise conv of ``mamba_d_conv`` taps (with bias) over
    # [x | B | C]. 0 = no mixer. The names are the published keys.
    mamba_d_ssm: int = 0
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    # muP multipliers (Falcon-H1's published keys), constants of the
    # forward: on the embedding, the logits, the attention sublayer's input
    # and output, the keys, the mixer's input and output, the five parts
    # of the mixer's input projection (z, x, B, C, dt), and the MLP's gate
    # and output. At 1.0 no operation is added.
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)
    # Layers of unlike kinds in a fixed pattern (Phi-4-mini-flash's SambaY
    # decoder): segments ``(period, repeats)``, a period a tuple of
    # ``LAYER_KINDS``, run in order; ``num_layers`` is their sum. Each
    # segment is ONE scan over its repeats whose body runs the period's
    # layers (``params["layers"]["seg<i>"][k]``, k of ``pattern_keys``, stacked
    # ``repeats`` deep). Such a model has no rotary or other positional
    # term: causality, the window and the recurrence are its only sense of
    # order. Empty: every layer is of one kind, as above.
    layer_types: Tuple[Tuple[Tuple[str, ...], int], ...] = ()
    # Positions a "window" layer attends (its own included).
    layer_window: int = 0
    # Mamba-1 (``layer_types`` "mamba"): ``mamba_d_ssm`` inner values each
    # with a state of ``mamba_d_state``, a conv of ``mamba_d_conv`` taps,
    # and the step size through a rank-``mamba_dt_rank`` bottleneck.
    mamba_dt_rank: int = 0
    # "rms": RMSNorm with a gain; "layer": LayerNorm with gain and bias
    # (``rms_norm_eps`` is its epsilon too).
    norm: str = "rms"
    # Differential attention: the heads are two sets, each a softmax map
    # over its own keys, both maps applied to all values, the second
    # subtracted ``lambda`` times, a per-head RMSNorm behind
    # (``models.transformer._diff_heads``). The cache then holds
    # ``num_kv_heads / 2`` rows of ``2 head_dim`` a token: [k1 | k2] and
    # [v1 | v2] of a pair of kv heads.
    diff_attn: bool = False
    # A plain (not differential) attention layer's output is multiplied by
    # ``sigmoid(h W_g)``, h the layer's normed input, elementwise over the
    # heads' outputs, before ``W_o`` (``layer_types`` "full").
    attn_out_gate: bool = False
    # The gated delta-rule mixer (``layer_types`` "kda"): ``kda_num_heads``
    # heads of ``kda_head_dim`` for q, k and v alike, each through a causal
    # depthwise conv of ``kda_conv`` taps; the decay a key channel and the
    # output gate through bottlenecks of rank ``kda_rank``; ``beta`` in
    # (0, 2) where ``kda_neg_eigval`` (the state's transition may then have
    # negative eigenvalues), else in (0, 1). Its state is float32
    # ``(heads, head_dim, head_dim)`` a row a layer.
    kda_num_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4
    kda_rank: int = 0
    kda_neg_eigval: bool = False

    @property
    def pattern(self) -> bool:
        """The layers are of unlike kinds (``layer_types``)."""
        return bool(self.layer_types)

    def kind_layers(self, kind: str, first: int = 0) -> int:
        """Layers of ``kind`` in the pattern, from segment ``first`` on."""
        return sum(period.count(kind) * n
                   for period, n in self.layer_types[first:])

    @property
    def readers_from(self) -> int:
        """The first of the pattern's trailing segments all of whose kinds
        hold nothing (``STATELESS_KINDS``: SambaY's cross-decoder), or the
        number of segments where the last one writes: from that segment on
        a token's pass has one product, its logits, and the paged forward
        runs it for the entries whose logits somebody reads alone
        (``models.transformer._forward_paged_pattern``)."""
        cut = len(self.layer_types)
        while cut and STATELESS_KINDS.issuperset(self.layer_types[cut - 1][0]):
            cut -= 1
        return cut

    @property
    def cache_kv_heads(self) -> int:
        """Rows a token takes in one payload leaf of an attention layer."""
        return self.num_kv_heads // 2 if self.diff_attn else self.num_kv_heads

    @property
    def cache_head_dim(self) -> int:
        """Width of such a row."""
        return self.head_dim * 2 if self.diff_attn else self.head_dim

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def ssm(self) -> bool:
        """The blocks hold a state-space or delta-rule mixer, and the cache
        a recurrent state a row beside the KV blocks."""
        return self.mamba_d_ssm > 0 or self.kind_layers("kda") > 0

    @property
    def kda_dim(self) -> int:
        """Width of each of a "kda" layer's q, k and v."""
        return self.kda_num_heads * self.kda_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels the mixer's conv runs over: [x | B | C]; Mamba-1's
        runs over x alone."""
        if self.mamba_dt_rank:
            return self.mamba_d_ssm
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def ssm_proj_dim(self) -> int:
        """Width of the mixer's input projection: [z | x B C | dt]."""
        return self.mamba_d_ssm + self.ssm_conv_dim + self.mamba_n_heads

    @property
    def attn_scale(self) -> float:
        """Softmax scale of latent attention: 1/sqrt of the q/k head width,
        times YaRN's m(``mscale_all_dim``)^2 where the rotary is YaRN's."""
        scale = 1.0 / float(self.head_dim) ** 0.5
        if isinstance(self.rope_scaling, YarnScaling):
            scale *= self.rope_scaling.magnitude(
                self.rope_scaling.mscale_all_dim) ** 2
        return scale

    @property
    def hc_maps(self) -> int:
        """Values the stream is projected to for one sublayer's maps:
        H_pre (n), H_post (n), H_res (n x n)."""
        return self.hc_mult * (self.hc_mult + 2)

    @property
    def latent_dim(self) -> int:
        """Cached values a token a layer under MLA: [c_kv | k_rope]."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row_dim(self) -> int:
        """Width of a row of the paged latent pool: ``latent_dim`` rounded
        up to whole 128-lane tiles, the tail zero. At its own 576 GLM-4.7's
        pool has no layout without padding that keeps a row's values
        adjacent: XLA:TPU then stores it blocks-minor and converts the whole
        pool on entry to and exit from every step (compile only, v5e: two
        pool-sized copies, temp 1.9 GB; at 640 none, temp 0.25 GB)."""
        return -(-self.latent_dim // 128) * 128

    @property
    def expert_size(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def routed_experts(self) -> int:
        """Real experts the router addresses; ``num_experts`` are held."""
        return self.moe_routed_experts or self.num_experts

    @property
    def router_width(self) -> int:
        """Outputs of the router: the real experts, then the identity
        experts."""
        return self.routed_experts + self.moe_zero_experts

    @property
    def expert_share(self) -> bool:
        """The expert layer holds fewer experts than its router addresses,
        or some of those are identity experts: a pair may have no bank."""
        return self.router_width != self.num_experts

    @property
    def attn_layers(self) -> int:
        """Attention layers the block pool holds: sublayer i of layer l of
        a shortcut block is pool layer 2 l + i; of a layer pattern, its
        "full" layers alone."""
        if self.pattern:
            return self.kind_layers("full")
        return self.num_layers * (2 if self.shortcut_moe else 1)

    @property
    def mla_scales(self) -> Tuple[float, float]:
        """(s_q, s_kv) of latent attention; 1.0 where the flag is off."""
        d = float(self.hidden_size)
        return ((d / self.q_lora_rank) ** 0.5 if self.mla_scale_q_lora
                else 1.0,
                (d / self.kv_lora_rank) ** 0.5 if self.mla_scale_kv_lora
                else 1.0)

    @property
    def num_expert_layers(self) -> int:
        return self.num_layers - self.first_dense_layers

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim


def qwen2_5_coder_0_5b() -> ModelConfig:
    return ModelConfig(
        name="qwen2.5-coder-0.5b", vocab_size=151_936, hidden_size=896,
        intermediate_size=4864, num_layers=24, num_heads=14, num_kv_heads=2,
        head_dim=64, max_seq_len=32_768, rope_theta=1_000_000.0,
        tie_word_embeddings=True, qkv_bias=True)


def qwen2_5_coder_1_5b() -> ModelConfig:
    """The flagship bench model (BASELINE config 3).

    Pretrained weights: point ``models.load.load_hf_params`` at a local
    HF-layout directory (e.g. a downloaded Qwen/Qwen2.5-Coder-1.5B snapshot
    containing model.safetensors[.index.json]); same for every preset here.
    """
    return ModelConfig(
        name="qwen2.5-coder-1.5b", vocab_size=151_936, hidden_size=1536,
        intermediate_size=8960, num_layers=28, num_heads=12, num_kv_heads=2,
        head_dim=128, max_seq_len=32_768, rope_theta=1_000_000.0,
        tie_word_embeddings=True, qkv_bias=True)


def qwen2_5_coder_7b() -> ModelConfig:
    return ModelConfig(
        name="qwen2.5-coder-7b", vocab_size=152_064, hidden_size=3584,
        intermediate_size=18_944, num_layers=28, num_heads=28, num_kv_heads=4,
        head_dim=128, max_seq_len=131_072, rope_theta=1_000_000.0,
        qkv_bias=True)


def mistral_7b() -> ModelConfig:
    """Mistral-7B-v0.1: the sliding-window-attention family.

    The reference serves Mistral models through its mistral provider
    (codestral FIM entry in the capability DB; provider registry
    ``transport/providers.py``); this preset gives that family a local
    policy architecture: LLaMA-style GQA with a 4096-token sliding
    window — each token attends only to its trailing 4096 positions
    (``ops/attention.py causal_mask(window=...)``). HF-layout weights
    load via ``models.load`` (same q/k/v/gate/up/down key scheme)."""
    return ModelConfig(
        name="mistral-7b", vocab_size=32_000, hidden_size=4096,
        intermediate_size=14_336, num_layers=32, num_heads=32,
        num_kv_heads=8, head_dim=128, max_seq_len=32_768,
        rope_theta=10_000.0, rms_norm_eps=1e-5, sliding_window=4096)


def mixtral_8x7b() -> ModelConfig:
    """Mixtral-8x7B-v0.1: the SWA + MoE composition.

    Mistral-family GQA with an 8-expert top-2 routed FFN — exercises
    the expert-parallel path (parallel/expert.py, 'ep' mesh axis) on a
    real released architecture. Released Mixtral-8x7B checkpoints use
    FULL dense attention over 32k (HF config.json: sliding_window null),
    so this preset does too — serving real weights with a window would
    silently mask attention past it and corrupt long-context logits.
    (The SWA+MoE *composition* is still covered: tiny-moe-test + a
    sliding_window override exercises the ring KV cache with experts.)
    Reference serves Mixtral through its mistral/openai providers
    (capability DB substring families)."""
    return ModelConfig(
        name="mixtral-8x7b", vocab_size=32_000, hidden_size=4096,
        intermediate_size=14_336, num_layers=32, num_heads=32,
        num_kv_heads=8, head_dim=128, max_seq_len=32_768,
        rope_theta=1_000_000.0, rms_norm_eps=1e-5, sliding_window=None,
        num_experts=8, num_experts_per_tok=2)


def deepseek_coder_1_3b() -> ModelConfig:
    return ModelConfig(
        name="deepseek-coder-1.3b", vocab_size=32_256, hidden_size=2048,
        intermediate_size=5504, num_layers=24, num_heads=16, num_kv_heads=16,
        head_dim=128, max_seq_len=16_384, rope_theta=100_000.0)


def deepseek_coder_6_7b() -> ModelConfig:
    """The GRPO target (BASELINE config 4)."""
    return ModelConfig(
        name="deepseek-coder-6.7b", vocab_size=32_256, hidden_size=4096,
        intermediate_size=11_008, num_layers=32, num_heads=32, num_kv_heads=32,
        head_dim=128, max_seq_len=16_384, rope_theta=100_000.0)


def tiny_moe_test() -> ModelConfig:
    """MoE policy variant for unit tests / EP dry runs."""
    return ModelConfig(
        name="tiny-moe-test", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, max_seq_len=128, qkv_bias=True,
        dtype=jnp.float32, matmul_precision="highest",
        num_experts=4, num_experts_per_tok=2)


def tiny_glm_moe_test() -> ModelConfig:
    """GLM-4.7-Flash's layer (``glm4_moe_lite``) at test size: latent
    attention, one leading dense layer, 8 routed experts top-2 + 1 shared,
    sigmoid router with a correction bias; q/k width (8 + 4) differs from
    the value width on purpose."""
    return ModelConfig(
        name="tiny-glm-moe-test", vocab_size=512, hidden_size=64,
        intermediate_size=160, num_layers=3, num_heads=4, num_kv_heads=4,
        head_dim=12, max_seq_len=128, rope_theta=1_000_000.0,
        rms_norm_eps=1e-5, dtype=jnp.float32, matmul_precision="highest",
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=48,
        num_shared_experts=1, first_dense_layers=1,
        router_type="sigmoid_bias", routed_scaling_factor=1.8,
        kv_lora_rank=24, q_lora_rank=32, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=16)


def tiny_xing_mhc_test() -> ModelConfig:
    """Xing4.0's layer (``xing4_0``) at test size: ``tiny-glm-moe-test``'s
    latent attention and experts with two leading dense layers, a residual
    stream of 4 rows mixed by Sinkhorn maps of 4 rounds (unrolled, the
    published 20 take XLA's CPU backend half a minute a program to
    compile; ``sinkhorn`` itself is tested at 20), and YaRN rotary over 4
    rotary pairs of which the first is kept, the second half stretched and
    the last two stretched 8 times (lo 0, hi 2)."""
    return dataclasses.replace(
        tiny_glm_moe_test(), name="tiny-xing-mhc-test", num_layers=4,
        first_dense_layers=2, rope_theta=10_000.0, rms_norm_eps=1e-6,
        routed_scaling_factor=2.0, qk_rope_head_dim=8, head_dim=16,
        rope_scaling=YarnScaling(factor=8.0, original_max_position=16,
                                 beta_fast=2.0, beta_slow=0.25,
                                 mscale=1.0, mscale_all_dim=1.0),
        hc_mult=4, hc_sinkhorn_iters=4)


def tiny_longcat_flash_test() -> ModelConfig:
    """LongCat-Flash's layer (``longcat_flash``) at test size: a shortcut
    block of two latent-attention sublayers and two dense FFNs with the
    expert branch beside them, a softmax router 16 + 8 wide (8 identity
    experts) top-4 with a correction bias and no renormalisation, both
    latent scales (2 and sqrt(8 / 3)), and this chip's share of the
    experts: 8 of the 16, from the 4th."""
    return ModelConfig(
        name="tiny-longcat-flash-test", vocab_size=512, hidden_size=64,
        intermediate_size=96, num_layers=2, num_heads=4, num_kv_heads=4,
        head_dim=12, max_seq_len=128, rope_theta=10_000_000.0,
        rms_norm_eps=1e-5, dtype=jnp.float32, matmul_precision="highest",
        num_experts=8, num_experts_per_tok=4, moe_intermediate_size=32,
        router_type="softmax_bias", routed_scaling_factor=6.0,
        moe_routed_experts=16, moe_first_expert=4, moe_zero_experts=8,
        shortcut_moe=True, kv_lora_rank=24, q_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=16,
        mla_scale_q_lora=True, mla_scale_kv_lora=True)


# LongCat-Flash's published ``config.json`` keys (``model_type:
# longcat_flash``) that ``longcat_flash_config`` maps; any other key is
# something the program would have to model and does not.
_LONGCAT_FLASH_KEYS = frozenset((
    "attention_bias", "vocab_size", "hidden_size", "ffn_hidden_size",
    "expert_ffn_hidden_size", "num_layers", "num_attention_heads",
    "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim", "v_head_dim",
    "qk_nope_head_dim", "mla_scale_q_lora", "mla_scale_kv_lora",
    "routed_scaling_factor", "n_routed_experts", "max_position_embeddings",
    "rms_norm_eps", "rope_theta", "attention_method", "zero_expert_num",
    "zero_expert_type", "moe_topk"))


def longcat_flash_config(published: dict, *, name: str,
                         first_expert: int = 0,
                         routed_experts: Optional[int] = None,
                         dtype=jnp.bfloat16,
                         matmul_precision: Optional[str] = None
                         ) -> ModelConfig:
    """LongCat-Flash's published keys -> ``ModelConfig``. ``num_layers``
    counts its double layers (``shortcut_moe``); ``n_routed_experts`` is
    the experts HELD, those from ``first_expert`` of the
    ``routed_experts`` the router addresses (None: all are held). A key
    this does not map, or a value with no form here (a ``zero_expert_type``
    other than ``identity``, an attention bias, another
    ``attention_method``), raises: a silent default under a real model's
    name would be a guess."""
    p = published
    wrong = sorted(set(p) - _LONGCAT_FLASH_KEYS) + [
        k for k, ok in (
            ("zero_expert_type", p["zero_expert_type"] == "identity"),
            ("attention_bias", p["attention_bias"] is False),
            ("attention_method", p.get("attention_method", "MLA") == "MLA"))
        if not ok]
    if wrong:
        raise ValueError(f"{name}: {wrong} as set are not mapped by "
                         f"longcat_flash_config")
    heads = p["num_attention_heads"]
    return ModelConfig(
        name=name, vocab_size=p["vocab_size"], hidden_size=p["hidden_size"],
        intermediate_size=p["ffn_hidden_size"], num_layers=p["num_layers"],
        num_heads=heads, num_kv_heads=heads,
        head_dim=p["qk_nope_head_dim"] + p["qk_rope_head_dim"],
        max_seq_len=p["max_position_embeddings"],
        rope_theta=float(p["rope_theta"]),
        rms_norm_eps=float(p["rms_norm_eps"]), dtype=dtype,
        matmul_precision=matmul_precision,
        num_experts=p["n_routed_experts"], num_experts_per_tok=p["moe_topk"],
        moe_intermediate_size=p["expert_ffn_hidden_size"],
        router_type="softmax_bias",
        routed_scaling_factor=float(p["routed_scaling_factor"]),
        moe_routed_experts=routed_experts or p["n_routed_experts"],
        moe_first_expert=first_expert,
        moe_zero_experts=p["zero_expert_num"], shortcut_moe=True,
        kv_lora_rank=p["kv_lora_rank"], q_lora_rank=p["q_lora_rank"],
        qk_nope_head_dim=p["qk_nope_head_dim"],
        qk_rope_head_dim=p["qk_rope_head_dim"], v_head_dim=p["v_head_dim"],
        mla_scale_q_lora=bool(p["mla_scale_q_lora"]),
        mla_scale_kv_lora=bool(p["mla_scale_kv_lora"]))


def tiny_falcon_h1_test() -> ModelConfig:
    """Falcon-H1's block (``falcon_h1``) at test size: a Mamba-2 mixer of
    4 heads x 8 with a state of 16 in 2 groups and a 4-tap conv beside GQA
    attention (4 query / 2 kv heads) on one normed input, no biases, and
    all twelve multipliers away from 1 so that dropping one shows."""
    return ModelConfig(
        name="tiny-falcon-h1-test", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, max_seq_len=128, rope_theta=1e11, rms_norm_eps=1e-5,
        dtype=jnp.float32, matmul_precision="highest",
        mamba_d_ssm=32, mamba_n_heads=4, mamba_d_head=8, mamba_d_state=16,
        mamba_n_groups=2, mamba_d_conv=4,
        embedding_multiplier=5.0, lm_head_multiplier=0.25,
        attention_in_multiplier=0.9, attention_out_multiplier=0.5,
        key_multiplier=0.7, ssm_in_multiplier=0.8, ssm_out_multiplier=0.6,
        ssm_multipliers=(0.9, 0.8, 0.7, 1.2, 1.1),
        mlp_multipliers=(0.75, 0.4))


# Phi-4-mini-flash's pattern (``model_type: phi4flash``, arXiv:2507.06607):
# a self-decoder of (Mamba-1, window attention) pairs, one (Mamba-1, full
# attention) pair whose mixer's scan output is the memory and whose
# attention's KV is the cache of the cross-decoder, (gated memory unit,
# cross attention) pairs.
def sambay_layer_types(num_layers: int):
    """The SambaY pattern over ``num_layers`` layers (a multiple of 4):
    the full-attention layer is the second of the middle pair."""
    half = num_layers // 2
    if num_layers % 4 or half < 2:
        raise ValueError(f"a SambaY pattern has 4 n layers, not "
                         f"{num_layers}")
    return ((("mamba", "window"), half // 2), (("mamba", "full"), 1),
            (("gmu", "cross"), half // 2 - 1))


def phi4_mini_flash() -> ModelConfig:
    """Phi-4-mini-flash-reasoning (3.8 B) at its published sizes: 32
    layers, 9 Mamba-1 mixers, 8 window-512 and 1 full differential
    attention layers of 40/20 heads x 64, 7 gated memory units, 7 cross
    layers, LayerNorm, no positional term, the embedding tied."""
    return ModelConfig(
        name="phi-4-mini-flash-reasoning", vocab_size=200_064,
        hidden_size=2560, intermediate_size=10_240, num_layers=32,
        num_heads=40, num_kv_heads=20, head_dim=64, max_seq_len=262_144,
        rms_norm_eps=1e-5, tie_word_embeddings=True,
        layer_types=sambay_layer_types(32), layer_window=512,
        mamba_d_ssm=5120, mamba_d_state=16, mamba_d_conv=4,
        mamba_dt_rank=160, norm="layer", diff_attn=True)


def tiny_phi4flash_test() -> ModelConfig:
    """Phi-4-mini-flash's pattern at test size: 8 layers (two window
    pairs, the middle pair, one cross pair), a window of 8 so that a
    short sequence wraps its ring several times, 24/12 heads x 4 (6 cache
    rows of 8 a token, stored folded 3 x 2 as the published 10 are 5 x
    2), a mixer of 192 inner values x 8 with a step-size rank of 6."""
    return ModelConfig(
        name="tiny-phi4flash-test", vocab_size=512, hidden_size=96,
        intermediate_size=128, num_layers=8, num_heads=24, num_kv_heads=12,
        head_dim=4, max_seq_len=128, rms_norm_eps=1e-5,
        tie_word_embeddings=True, dtype=jnp.float32,
        matmul_precision="highest",
        layer_types=sambay_layer_types(8), layer_window=8,
        mamba_d_ssm=192, mamba_d_state=8, mamba_d_conv=4, mamba_dt_rank=6,
        norm="layer", diff_attn=True)


# Solar-Open2's published ``config.json`` keys (``model_type: solar_open2``)
# that ``solar_open2_config`` maps; any other key is something the program
# would have to model and does not.
_SOLAR_OPEN2_KEYS = frozenset((
    "partial_rotary_factor", "linear_attn_config", "hidden_size",
    "num_hidden_layers", "num_attention_heads", "head_dim",
    "num_key_value_heads", "vocab_size", "intermediate_size",
    "moe_intermediate_size", "rms_norm_eps", "rope_theta",
    "tie_word_embeddings", "max_position_embeddings",
    "first_k_dense_replace", "use_rope", "gqa_interval", "gqa_layers",
    "use_gqa_gate", "kda_use_full_proj", "kda_allow_neg_eigval",
    "n_routed_experts", "n_shared_experts", "norm_topk_prob",
    "routed_scaling_factor", "num_experts_per_tok"))


def solar_open2_config(published: dict, *, name: str, first_expert: int = 0,
                       routed_experts: Optional[int] = None,
                       dtype=jnp.bfloat16,
                       matmul_precision: Optional[str] = None
                       ) -> ModelConfig:
    """Solar-Open2's published keys -> ``ModelConfig``: periods of one
    gated NoPE GQA layer (``layer_types`` "full") and ``gqa_interval``
    gated delta-rule layers ("kda"), every layer's second sublayer the
    expert layer (a sigmoid router with a correction bias, the chosen
    scores normalised; ``n_shared_experts`` shared), an untied head, no
    positional term. ``n_routed_experts`` is the experts HELD, those from
    ``first_expert`` of the ``routed_experts`` the router addresses (None:
    all are held). The two bottlenecks' rank is the mixer's head size (the
    key does not give it). A key this does not map, or a value with no
    form here (rotary attention, a leading dense layer, full-rank decay
    projections, a tied head, chosen scores left unnormalised, ``gqa_layers``
    that are not every ``gqa_interval + 1``-th layer), raises: a silent
    default under a real model's name would be a guess."""
    p = published
    lin = p.get("linear_attn_config") or {}
    period = p.get("gqa_interval", 0) + 1
    layers = p.get("num_hidden_layers", 0)
    wrong = sorted(set(p) - _SOLAR_OPEN2_KEYS) + sorted(
        "linear_attn_config." + k for k in set(lin) - {
            "short_conv_kernel_size", "head_dim", "num_heads",
            "num_kv_heads"}) + [
        k for k, ok in (
            ("use_rope", p.get("use_rope") is False),
            ("partial_rotary_factor", p.get("partial_rotary_factor") == 1),
            ("first_k_dense_replace", p.get("first_k_dense_replace") == 0),
            ("kda_use_full_proj", p.get("kda_use_full_proj") is False),
            ("tie_word_embeddings", p.get("tie_word_embeddings") is False),
            ("norm_topk_prob", p.get("norm_topk_prob") is True),
            ("linear_attn_config.num_kv_heads",
             lin.get("num_kv_heads") in (None, lin.get("num_heads"))),
            ("num_hidden_layers", layers > 0 and layers % period == 0),
            ("gqa_layers",
             [l for l in p.get("gqa_layers", ()) if l < layers]
             == list(range(0, layers, period))))
        if not ok]
    if wrong:
        raise ValueError(f"{name}: {wrong} as set are not mapped by "
                         f"solar_open2_config")
    return ModelConfig(
        name=name, vocab_size=p["vocab_size"], hidden_size=p["hidden_size"],
        intermediate_size=p["intermediate_size"], num_layers=layers,
        num_heads=p["num_attention_heads"],
        num_kv_heads=p["num_key_value_heads"], head_dim=p["head_dim"],
        max_seq_len=p["max_position_embeddings"],
        rope_theta=float(p["rope_theta"]),
        rms_norm_eps=float(p["rms_norm_eps"]), dtype=dtype,
        matmul_precision=matmul_precision,
        num_experts=p["n_routed_experts"],
        num_experts_per_tok=p["num_experts_per_tok"],
        moe_intermediate_size=p["moe_intermediate_size"],
        num_shared_experts=p["n_shared_experts"],
        router_type="sigmoid_bias",
        routed_scaling_factor=float(p["routed_scaling_factor"]),
        moe_routed_experts=routed_experts or p["n_routed_experts"],
        moe_first_expert=first_expert,
        layer_types=((("full",) + ("kda",) * (period - 1),
                      layers // period),),
        attn_out_gate=bool(p["use_gqa_gate"]),
        kda_num_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        kda_conv=lin["short_conv_kernel_size"], kda_rank=lin["head_dim"],
        kda_neg_eigval=bool(p["kda_allow_neg_eigval"]))


# Solar-Open2's published keys at test size: two periods of (GQA, KDA, KDA,
# KDA), 4/2 attention heads x 16, a mixer of 4 heads x 8 (its width 32 is
# not the hidden size on purpose), a 16-wide router top-4 and one shared
# expert.
TINY_SOLAR_OPEN2_KEYS = {
    "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 8,
                           "num_heads": 4, "num_kv_heads": None},
    "hidden_size": 64, "num_hidden_layers": 8, "num_attention_heads": 4,
    "head_dim": 16, "num_key_value_heads": 2, "vocab_size": 512,
    "intermediate_size": 160, "moe_intermediate_size": 32,
    "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "tie_word_embeddings": False, "max_position_embeddings": 128,
    "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4], "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 8,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 4}


def tiny_solar_open2_test() -> ModelConfig:
    """Solar-Open2's layers at test size through the arch map of its
    published keys, this chip's share of the experts 8 of 16, from the
    4th."""
    return solar_open2_config(
        TINY_SOLAR_OPEN2_KEYS, name="tiny-solar-open2-test", first_expert=4,
        routed_experts=16, dtype=jnp.float32, matmul_precision="highest")


def tiny_test() -> ModelConfig:
    """Small config for unit tests and CPU-mesh dry runs."""
    return ModelConfig(
        name="tiny-test", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, max_seq_len=128, qkv_bias=True,
        dtype=jnp.float32, matmul_precision="highest")


def qwen3_1_7b() -> ModelConfig:
    """Qwen3-1.7B: QK-norm GQA, no attention biases, tied embeddings."""
    return ModelConfig(
        name="qwen3-1.7b", vocab_size=151_936, hidden_size=2048,
        intermediate_size=6144, num_layers=28, num_heads=16, num_kv_heads=8,
        head_dim=128, max_seq_len=32_768, rope_theta=1_000_000.0,
        tie_word_embeddings=True, qk_norm=True)


def qwen3_8b() -> ModelConfig:
    """Qwen3-8B: the 7B-class member of the Qwen3 ladder."""
    return ModelConfig(
        name="qwen3-8b", vocab_size=151_936, hidden_size=4096,
        intermediate_size=12_288, num_layers=36, num_heads=32,
        num_kv_heads=8, head_dim=128, max_seq_len=32_768,
        rope_theta=1_000_000.0, qk_norm=True)


def qwen3_30b_a3b() -> ModelConfig:
    """Qwen3-30B-A3B: the MoE member of the Qwen3 ladder (128 experts,
    8 active, QK-norm; ~3B active params per token)."""
    return ModelConfig(
        name="qwen3-30b-a3b", vocab_size=151_936, hidden_size=2048,
        intermediate_size=768, num_layers=48, num_heads=32, num_kv_heads=4,
        head_dim=128, max_seq_len=32_768, rope_theta=1_000_000.0,
        qk_norm=True, num_experts=128, num_experts_per_tok=8,
        moe_layout="qwen3")


def llama_3_2_1b() -> ModelConfig:
    """Llama-3.2-1B: GQA, tied embeddings, llama3 RoPE scaling (the
    128k-context serving config of an 8k-trained base)."""
    return ModelConfig(
        name="llama-3.2-1b", vocab_size=128_256, hidden_size=2048,
        intermediate_size=8192, num_layers=16, num_heads=32, num_kv_heads=8,
        head_dim=64, max_seq_len=131_072, rope_theta=500_000.0,
        rope_scaling=RopeScaling(factor=32.0), rms_norm_eps=1e-5,
        tie_word_embeddings=True)


def llama_3_1_8b() -> ModelConfig:
    """Llama-3.1-8B: the 7B-class member of the Llama family ladder."""
    return ModelConfig(
        name="llama-3.1-8b", vocab_size=128_256, hidden_size=4096,
        intermediate_size=14_336, num_layers=32, num_heads=32,
        num_kv_heads=8, head_dim=128, max_seq_len=131_072,
        rope_theta=500_000.0, rope_scaling=RopeScaling(factor=8.0),
        rms_norm_eps=1e-5)


def small_test() -> ModelConfig:
    """Between tiny-test and the real presets: enough capacity for
    prompt-CONDITIONAL behavior (the contextual learning eval needs the
    task tokens, buried in an ~1.8k-token prompt, to actually route the
    output distribution — tiny-test's 2×d64 could not), still
    seconds-per-round on one chip."""
    return ModelConfig(
        name="small-test", vocab_size=512, hidden_size=128,
        intermediate_size=384, num_layers=4, num_heads=8, num_kv_heads=4,
        head_dim=32, max_seq_len=4096, qkv_bias=True,
        dtype=jnp.float32, matmul_precision="highest")


PRESETS = {
    "qwen2.5-coder-0.5b": qwen2_5_coder_0_5b,
    "qwen2.5-coder-1.5b": qwen2_5_coder_1_5b,
    "qwen2.5-coder-7b": qwen2_5_coder_7b,
    "mistral-7b": mistral_7b,
    "mixtral-8x7b": mixtral_8x7b,
    "deepseek-coder-1.3b": deepseek_coder_1_3b,
    "deepseek-coder-6.7b": deepseek_coder_6_7b,
    "llama-3.2-1b": llama_3_2_1b,
    "llama-3.1-8b": llama_3_1_8b,
    "qwen3-1.7b": qwen3_1_7b,
    "qwen3-8b": qwen3_8b,
    "qwen3-30b-a3b": qwen3_30b_a3b,
    "tiny-test": tiny_test,
    "tiny-moe-test": tiny_moe_test,
    "tiny-glm-moe-test": tiny_glm_moe_test,
    "tiny-xing-mhc-test": tiny_xing_mhc_test,
    "tiny-falcon-h1-test": tiny_falcon_h1_test,
    "tiny-longcat-flash-test": tiny_longcat_flash_test,
    "phi-4-mini-flash-reasoning": phi4_mini_flash,
    "tiny-phi4flash-test": tiny_phi4flash_test,
    "tiny-solar-open2-test": tiny_solar_open2_test,
    "small-test": small_test,
}


def get_config(name: str) -> ModelConfig:
    if name not in PRESETS:
        raise KeyError(f"unknown model {name!r}; available: {sorted(PRESETS)}")
    return PRESETS[name]()
