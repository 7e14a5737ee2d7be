"""GRPO trainer: pjit-sharded train state + one-step update.

Mesh layout (parallel/mesh.py): gradients reduce over (dp, fsdp) — XLA lowers
the all-reduce/reduce-scatter onto ICI; params and Adam moments are sharded
per ``parallel/sharding.py`` (fsdp ZeRO-style + tp Megatron-style). The same
``train_step`` runs single-chip (trivial mesh) and on a v5e-64 layout
unchanged — only the Mesh differs.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig
from ..models.transformer import Params, forward, init_params
from ..obs.runtime_profile import ProfiledFunction
from ..parallel.mesh import make_mesh
from ..parallel.sharding import (param_shardings, param_specs,
                                 restrict_spec, shard_params)
from .grpo import (GRPOConfig, group_relative_advantages, grpo_objective,
                   token_logprobs)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("params", "opt_state", "step"),
                   meta_fields=("opt",))
@dataclasses.dataclass
class TrainState:
    params: Params
    opt_state: Any
    step: jax.Array
    # The transformation whose .init built opt_state — carried as STATIC
    # pytree metadata so every train_step applies updates with the same
    # optimizer. (r2 latent bug: train_step silently fell back to a
    # module-level lr-1e-5 default whenever the caller didn't re-pass
    # the optimizer, so make_train_state(learning_rate=X) built X-scaled
    # opt_state that was then stepped at 1e-5 — the GRPO loops trained
    # ~1000x slower than configured and no pytree error surfaced because
    # both chains have identical state structure.)
    opt: Optional[optax.GradientTransformation] = None

    def _asdict(self) -> Dict[str, Any]:
        """Array fields only (checkpoint serialization surface — the
        optimizer is code, not state; restore re-attaches it)."""
        return {"params": self.params, "opt_state": self.opt_state,
                "step": self.step}


@functools.lru_cache(maxsize=64)
def make_optimizer(learning_rate: float = 1e-5, *, weight_decay: float = 0.0,
                   max_grad_norm: float = 1.0,
                   warmup_steps: int = 0) -> optax.GradientTransformation:
    """Cached by config: equal arguments return the SAME transformation
    instance, so jit caches keyed on the (static) optimizer are shared
    across TrainStates instead of recompiling per state."""
    if warmup_steps > 0:
        schedule = optax.linear_schedule(0.0, learning_rate, warmup_steps)
    else:
        schedule = learning_rate
    return optax.chain(
        optax.clip_by_global_norm(max_grad_norm),
        optax.adamw(schedule, b1=0.9, b2=0.95, eps=1e-8,
                    weight_decay=weight_decay),
    )


def make_train_state(config: ModelConfig, key: jax.Array,
                     mesh: Optional[Mesh] = None, *,
                     learning_rate: float = 1e-5,
                     params: Optional[Params] = None,
                     optimizer: Optional[optax.GradientTransformation] = None,
                     ) -> TrainState:
    """Init (or adopt) params, shard them onto the mesh, init sharded opt state."""
    if params is None:
        params = init_params(config, key)
    if mesh is not None:
        params = shard_params(params, mesh)
    opt = optimizer or make_optimizer(learning_rate)
    opt_state = jax.jit(opt.init)(params) if mesh is None else \
        jax.jit(opt.init,
                out_shardings=_opt_state_shardings(opt, params, mesh))(params)
    return TrainState(params=params, opt_state=opt_state,
                      step=_zero_step(mesh), opt=opt)


def _zero_step(mesh: Optional[Mesh]) -> jax.Array:
    """Step counter placed like the step a train step returns
    (replicated on the mesh), so step 2 reuses step 1's compile."""
    step = jnp.zeros((), jnp.int32)
    if mesh is None:
        return step
    return jax.device_put(step, NamedSharding(mesh, P()))


def make_lora_train_state(config: ModelConfig, base_params: Params,
                          key: jax.Array, mesh: Optional[Mesh] = None, *,
                          rank: int = 16, alpha: Optional[float] = None,
                          targets: Optional[Tuple[str, ...]] = None,
                          learning_rate: float = 1e-4,
                          optimizer: Optional[
                              optax.GradientTransformation] = None,
                          ) -> TrainState:
    """TrainState whose params are ONLY the LoRA adapters for
    ``base_params`` (training/lora.py): pass the frozen base to
    ``train_step(..., lora_base=base_params)``. Adapters are replicated
    on the mesh (they are tiny; the base keeps its own shardings)."""
    from .lora import DEFAULT_TARGETS, init_lora
    wq = base_params["layers"]["wq"]
    expect = (config.num_layers, config.hidden_size, config.q_dim)
    if tuple(wq.shape) != expect:
        # adapter shapes come from config; a mismatched base would only
        # explode later, deep inside the jitted step
        raise ValueError(f"base_params do not match config "
                         f"{config.name!r}: wq {tuple(wq.shape)} != "
                         f"{expect}")
    lora = init_lora(config, key, rank=rank, alpha=alpha,
                     targets=targets or DEFAULT_TARGETS)
    if mesh is not None:
        repl = NamedSharding(mesh, P())
        lora = jax.device_put(lora, repl)
    opt = optimizer or make_optimizer(learning_rate)
    opt_state = jax.jit(opt.init)(lora)
    return TrainState(params=lora, opt_state=opt_state,
                      step=_zero_step(mesh), opt=opt)


def _opt_state_shardings(opt, params, mesh):
    """Shardings for the optimizer state: a leaf that sits under the same
    dict path as a param and has its shape (Adam moments mirror the param
    tree) inherits that param's spec; everything else (counts, scalars)
    replicates. By path, not by shape: wq and wo are both (L, D, D)
    wherever q_dim == hidden_size — qwen2.5-coder-1.5b for one — with
    transposed specs."""
    def dict_path(path):
        return tuple(k.key for k in path
                     if isinstance(k, jax.tree_util.DictKey))

    specs = jax.tree_util.tree_leaves(
        param_specs(params), is_leaf=lambda x: isinstance(x, P))
    by_path = {dict_path(path): (leaf.shape, spec) for (path, leaf), spec
               in zip(jax.tree_util.tree_flatten_with_path(params)[0], specs)}

    def leaf_sharding(path, leaf):
        shape, spec = by_path.get(dict_path(path), (None, P()))
        if shape != leaf.shape:
            spec = P()
        return NamedSharding(mesh, restrict_spec(spec, mesh))

    return jax.tree_util.tree_map_with_path(
        leaf_sharding, jax.eval_shape(opt.init, params))


@functools.partial(jax.jit,
                   static_argnames=("config", "grpo_config", "num_groups",
                                    "optimizer", "mesh", "accum_steps"))
def _grpo_step(state: TrainState, config: ModelConfig,
                     optimizer: optax.GradientTransformation,
                     tokens: jax.Array, completion_mask: jax.Array,
                     rewards: jax.Array, group_ids: jax.Array,
                     old_logp: Optional[jax.Array],
                     ref_logp: Optional[jax.Array],
                     branch_mask: Optional[jax.Array],
                     grpo_config: GRPOConfig,
                     num_groups: int,
                     accum_steps: int,
                     mesh: Optional[Mesh] = None,
                     lora_base: Optional[Params] = None,
                     ) -> Tuple[TrainState, Dict[str, jax.Array]]:
    """The GRPO step — always the accumulated form; ``accum_steps=1``
    is a length-1 scan and IS the monolithic step (single implementation,
    no second code path to keep in sync). Larger ``accum_steps`` splits
    the batch into sequentially-scanned microbatches holding one
    microbatch's activations at a time — how a 7B policy trains on long
    trajectories that don't fit as one batch (SURVEY.md §7 hard part
    'long-trajectory memory', alongside remat and ring attention).

    Equivalence to the monolithic step: advantages are group-relative
    over the FULL batch (computed before the split — group members may
    land in different microbatches), and each microbatch's gradient is
    weighted by its share of completion tokens, so the accumulated
    gradient equals the full-batch token-normalized objective's. The MoE
    aux loss uses the same weights (token-share weighting of a
    batch-mean term — exact when microbatches have equal token counts).
    """
    b = tokens.shape[0]
    if b % accum_steps != 0:
        raise ValueError(f"batch {b} not divisible by accum_steps "
                         f"{accum_steps}")
    adv = group_relative_advantages(
        rewards, group_ids, num_groups,
        normalize_std=grpo_config.normalize_std,
        min_std=grpo_config.min_group_std,
        leave_one_out=grpo_config.leave_one_out)

    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    tgt_mask = completion_mask[:, 1:]
    total_denom = jnp.maximum(jnp.sum(tgt_mask), 1.0)

    def micro(x):
        return x.reshape(accum_steps, b // accum_steps, *x.shape[1:])

    # lax.scan xs can't carry None leaves: absent ref_logp scans zeros
    # and the static has_ref closure keeps the KL term genuinely off.
    has_ref = ref_logp is not None
    has_old = old_logp is not None
    has_branch = branch_mask is not None
    zeros_f32 = jnp.zeros_like(micro(targets), dtype=jnp.float32)
    scan_xs = (micro(inputs), micro(targets), micro(tgt_mask), micro(adv),
               micro(ref_logp) if has_ref else zeros_f32,
               micro(old_logp) if has_old else zeros_f32,
               # branch mask is (B, S) like completion_mask; the shift
               # to target layout mirrors tgt_mask above.
               micro(branch_mask[:, 1:].astype(jnp.float32))
               if has_branch else zeros_f32)

    def loss_fn(params, m_in, m_tgt, m_mask, m_adv, m_ref, m_old, m_branch):
        if lora_base is not None:
            # LoRA: `params` is the adapter tree; the frozen base rides
            # as a closed-over constant — gradients and optimizer state
            # exist only for the adapters (training/lora.py).
            from .lora import merge_lora
            model_params = merge_lora(lora_base, params)
        else:
            model_params = params
        logits, _, moe_aux = forward(model_params, config, m_in,
                                     with_aux=True, mesh=mesh)
        logp = token_logprobs(logits, m_tgt)
        olp = m_old if has_old else jax.lax.stop_gradient(logp)
        loss, metrics = grpo_objective(
            logp, olp, m_adv, m_mask, grpo_config,
            ref_logp=m_ref if has_ref else None,
            branch_mask=m_branch if has_branch else None)
        if config.num_experts > 0:
            loss = loss + grpo_config.moe_aux_coef * moe_aux
        return loss, (metrics, moe_aux)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    zero_grads = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), state.params)

    # Same metrics schema as the monolithic step: every per-token-
    # normalized metric weight-sums across microbatches exactly like the
    # loss does.
    acc_keys = ("pg_loss", "kl", "entropy", "ratio_mean", "clip_frac",
                "grad_sparsity")
    if has_branch:
        acc_keys = acc_keys + ("branch_token_frac",)

    def body(carry, m):
        grads_acc, loss_acc, metr_acc = carry
        m_in, m_tgt, m_mask, m_adv, m_ref, m_old, m_branch = m
        (loss, (metrics, moe_aux)), grads = grad_fn(
            state.params, m_in, m_tgt, m_mask, m_adv, m_ref, m_old,
            m_branch)
        w = jnp.maximum(jnp.sum(m_mask), 0.0) / total_denom
        grads_acc = jax.tree_util.tree_map(
            lambda a, g: a + g.astype(jnp.float32) * w, grads_acc, grads)
        metr_acc = {k: metr_acc[k] + metrics[k] * w for k in acc_keys}
        metr_acc["moe_aux"] = metr_acc.get("moe_aux", 0.0) + moe_aux * w
        return (grads_acc, loss_acc + loss * w, metr_acc), None

    zero_metrics = {k: jnp.zeros(()) for k in acc_keys}
    zero_metrics["moe_aux"] = jnp.zeros(())
    (grads, loss, metr), _ = jax.lax.scan(
        body, (zero_grads, jnp.zeros(()), zero_metrics), scan_xs)
    grads = jax.tree_util.tree_map(
        lambda g, p: g.astype(p.dtype), grads, state.params)

    updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
    params = optax.apply_updates(state.params, updates)
    if mesh is not None and lora_base is None:
        # The new state keeps the layout the old one was given: left to
        # the compiler, step 1 returns its own choice, step 2 retraces
        # on it, and a checkpoint restore sees a third.
        params = jax.lax.with_sharding_constraint(
            params, param_shardings(params, mesh))
        opt_state = jax.lax.with_sharding_constraint(
            opt_state, _opt_state_shardings(optimizer, params, mesh))
    metrics = dict(metr)
    if config.num_experts == 0:
        del metrics["moe_aux"]
    metrics["loss"] = loss
    metrics["grad_norm"] = optax.global_norm(grads)
    metrics["adv_mean"] = jnp.mean(adv)
    # Carry the optimizer that ACTUALLY produced this opt_state — if the
    # caller passed one explicitly into a state built without, the next
    # step must keep using it, not fall back to the module default.
    return TrainState(params=params, opt_state=opt_state,
                      step=state.step + 1, opt=optimizer), metrics


# Default optimizer instance reused across steps (hashable for jit statics).
_DEFAULT_OPT = make_optimizer()

# Runtime observatory wiring (obs/runtime_profile.py): compile/retrace
# ledger for the GRPO update. ``block=False`` keeps the async-dispatch
# contract below (the span comment in train_step) — the step histogram
# records dispatch; device time stays with rl_loop's train_s, which
# obs/telemetry.py combines with this ledger's cost_analysis FLOPs for
# the measured MFU. State/config/optimizer trees are shape-stable and
# skipped from the signature scan (retraces they cause still count via
# the jit cache).
_grpo_step = ProfiledFunction(
    _grpo_step, "trainer.grpo_step", skip_args=(0, 1, 2),
    skip_kwargs=("mesh", "lora_base"), block=False)


def train_step(state: TrainState, config: ModelConfig, mesh: Optional[Mesh],
               tokens: jax.Array, completion_mask: jax.Array,
               rewards: jax.Array, group_ids: jax.Array, *,
               old_logp: Optional[jax.Array] = None,
               ref_logp: Optional[jax.Array] = None,
               branch_mask: Optional[jax.Array] = None,
               grpo_config: GRPOConfig = GRPOConfig(),
               optimizer: Optional[optax.GradientTransformation] = None,
               num_groups: Optional[int] = None,
               accum_steps: int = 1,
               lora_base: Optional[Params] = None,
               ) -> Tuple[TrainState, Dict[str, jax.Array]]:
    """One GRPO update. tokens: (B, S) prompt+completion; completion_mask True
    on completion positions; rewards: (B,) finalReward; group_ids: (B,) prompt
    group of each trajectory. ``accum_steps > 1`` splits the batch into
    sequentially-scanned microbatches (one microbatch of activations
    resident at a time) with token-share-weighted gradient accumulation —
    equivalent update, fraction of the memory.

    Optimizer resolution: an explicit ``optimizer`` wins, else the
    transformation the state was BUILT with (``state.opt``), else the
    module default — never a silent mismatch with the opt_state."""
    from ..models.quantize import is_quantized
    if is_quantized(state.params):
        # einsum would silently promote unscaled int8 → garbage grads
        raise TypeError(
            "train_step received int8-quantized params "
            "(models/quantize.py) — quantization is a SERVING transform; "
            "train on the full-precision state and publish quantized")
    # An int8 lora_base is ALLOWED: adapters differentiate through the
    # dequant epilogue wrt activations only (QLoRA; training/lora.py).
    opt = optimizer or state.opt or _DEFAULT_OPT
    n_groups = num_groups or int(tokens.shape[0])
    args = (state, config, opt, tokens, completion_mask, rewards, group_ids,
            old_logp, ref_logp, branch_mask, grpo_config, n_groups,
            accum_steps)
    # Span measures DISPATCH of the jitted step (results are async);
    # callers wanting completion time force with float()/block_until_ready
    # inside their own enclosing span (rl_loop does).
    from ..obs import get_tracer
    with get_tracer().span("trainer.grpo_step",
                           batch=int(tokens.shape[0]),
                           accum_steps=accum_steps):
        if mesh is not None:
            with mesh:
                return _grpo_step(*args, mesh=mesh, lora_base=lora_base)
        return _grpo_step(*args, lora_base=lora_base)


def train_step_guarded(state: TrainState, config: ModelConfig,
                       mesh: Optional[Mesh],
                       tokens: jax.Array, completion_mask: jax.Array,
                       rewards: jax.Array, group_ids: jax.Array, *,
                       guard, **kwargs
                       ) -> Tuple[TrainState, Dict[str, float],
                                  Optional[str]]:
    """``train_step`` behind a resilience.UpdateGuard.

    Runs the update, syncs the metrics to host floats (forcing device
    completion), and asks ``guard`` whether to ADOPT the new state.
    Returns ``(state, float_metrics, skip_reason)`` — on a veto the
    returned state is the INPUT state (params and optimizer moments
    untouched by the non-finite/spiking update) and ``skip_reason`` is
    the guard's verdict; otherwise ``skip_reason`` is None. A ``guard``
    of None degrades to plain train_step with float metrics."""
    new_state, metrics = train_step(state, config, mesh, tokens,
                                    completion_mask, rewards, group_ids,
                                    **kwargs)
    float_metrics = {k: float(v) for k, v in metrics.items()}
    if guard is None:
        return new_state, float_metrics, None
    reason = guard.check(float_metrics)
    if reason is not None:
        return state, float_metrics, reason
    return new_state, float_metrics, None
