"""Pipeline parallelism: GPipe-style microbatched stages over a 'pp' axis.

No reference counterpart exists (SURVEY.md §2.7: the reference has no
compute parallelism) — this is designed TPU-first: transformer blocks are
stage-sliced along their stacked layer axis, each stage lives on one 'pp'
mesh rank, and activations flow stage-to-stage with ``lax.ppermute`` over
ICI neighbors inside ``shard_map``. The schedule is the classic GPipe
pipeline: M microbatches drain through K stages in M+K−1 ticks, with
bubble fraction (K−1)/(M+K−1); differentiable end-to-end (ppermute's
transpose is the reverse permute), so the same code path serves training.

Embedding, final norm, and the LM head run replicated outside the
pipelined region (they are cheap relative to the blocks; the blocks carry
the FLOPs that matter for the MXU).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from ..models.config import ModelConfig
from ..models.transformer import Params, _layer
from ..ops.norms import rms_norm
from ..ops.rotary import rope_cos_sin


def split_layers_for_stages(params: Params, n_stages: int) -> Params:
    """Reshape stacked layer leaves (L, ...) → (n_stages, L//n_stages, ...).

    The leading stage axis is what gets sharded over 'pp'."""
    from ..models.quantize import is_quantized
    if is_quantized(params):
        # the stage bodies einsum lp["wq"] directly (no _dense dequant);
        # int8 would silently promote unscaled — refuse up front
        raise TypeError("pipeline stages do not support int8-quantized "
                        "params (models/quantize.py is a serving-path "
                        "transform); pass full-precision params")
    if any("_lora_" in name for name in params["layers"]):
        # adapter leaves would reshape into stages and ride along but
        # never be applied — the pipeline would silently serve the
        # UN-adapted base policy
        raise TypeError("pipeline stages do not apply LoRA adapter "
                        "leaves; fold them first (training.lora."
                        "materialize_lora) and pass the plain params")
    L = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]
    if L % n_stages != 0:
        raise ValueError(f"num_layers {L} not divisible by {n_stages} "
                         "pipeline stages")
    per = L // n_stages
    out = dict(params)
    out["layers"] = jax.tree_util.tree_map(
        lambda x: x.reshape((n_stages, per) + x.shape[1:]),
        params["layers"])
    return out


def stage_param_specs(params: Params) -> Params:
    """PartitionSpecs: stage-split layers on 'pp', everything else
    replicated."""
    out = {k: (jax.tree_util.tree_map(lambda x: P("pp"), v)
               if k == "layers" else jax.tree_util.tree_map(lambda x: P(), v))
           for k, v in params.items()}
    return out


@functools.partial(jax.jit,
                   static_argnames=("config", "mesh", "n_microbatches"))
def pipeline_forward(params: Params, config: ModelConfig,
                     tokens: jax.Array, *, mesh: Mesh,
                     n_microbatches: int = 4,
                     attn_mask: Optional[jax.Array] = None) -> jax.Array:
    """Full forward with the transformer blocks pipelined over 'pp'.

    ``params`` must be pre-split (split_layers_for_stages) and placed with
    stage_param_specs shardings. tokens: (B, S); B divisible by
    n_microbatches. Returns fp32 logits (B, S, V)."""
    c = config
    K = mesh.shape["pp"]
    M = n_microbatches
    b, s = tokens.shape
    if b % M != 0:
        raise ValueError(f"batch {b} not divisible by {M} microbatches")
    mb = b // M

    x = params["embed"][tokens]                          # (B, S, D)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :],
                                 (b, s))
    cos, sin = rope_cos_sin(positions, c.head_dim, c.rope_theta,
                            scaling=c.rope_scaling)
    mb_x = x.reshape(M, mb, s, c.hidden_size)
    mb_cos = cos.reshape(M, mb, *cos.shape[1:])
    mb_sin = sin.reshape(M, mb, *sin.shape[1:])
    mb_mask = (attn_mask.reshape(M, mb, *attn_mask.shape[1:])
               if attn_mask is not None else None)

    def stage_apply(stage_lp, h, cos_mb, sin_mb, mask_mb):
        def body(hh, lp):
            hh, _, _aux = _layer(c, lp, hh, cos_mb, sin_mb, None, mask_mb)
            return hh, None
        h, _ = jax.lax.scan(body, h, stage_lp)
        return h

    perm = [(i, (i + 1) % K) for i in range(K)]

    def pp_fn(stage_lp, mb_x, mb_cos, mb_sin, mb_mask):
        # Inside shard_map: stage_lp leaves lost their leading 'pp' axis
        # slice → (1, per, ...); squeeze it.
        stage_lp = jax.tree_util.tree_map(lambda a: a[0], stage_lp)
        stage = jax.lax.axis_index("pp")

        def tick(carry, t):
            prev_out = carry
            recv = jax.lax.ppermute(prev_out, "pp", perm)
            # Stage k at tick t is processing microbatch t−k, so every
            # per-microbatch input (mask, rope) must be gathered at that
            # index — not at the tick counter.
            i = jnp.clip(t - stage, 0, M - 1)
            first_in = jax.lax.dynamic_index_in_dim(mb_x, i, 0,
                                                    keepdims=False)
            my_in = jnp.where(stage == 0, first_in, recv)
            cos_mb = jax.lax.dynamic_index_in_dim(mb_cos, i, 0, False)
            sin_mb = jax.lax.dynamic_index_in_dim(mb_sin, i, 0, False)
            mask_mb = (jax.lax.dynamic_index_in_dim(mb_mask, i, 0, False)
                       if mb_mask is not None else None)
            out = stage_apply(stage_lp, my_in, cos_mb, sin_mb, mask_mb)
            return out, out

        init = jnp.zeros((mb, s, c.hidden_size), mb_x.dtype)
        _, ys = jax.lax.scan(tick, init,
                             jnp.arange(M + K - 1, dtype=jnp.int32))
        # Stage K-1 produced microbatch m at tick m + K - 1.
        outs = ys[K - 1:]                                # (M, mb, s, D)
        outs = jnp.where(stage == K - 1, outs, 0.0)
        return jax.lax.psum(outs, "pp")                  # broadcast result

    in_specs = (stage_param_specs(params)["layers"], P(), P(), P(),
                P() if mb_mask is not None else None)
    args = (params["layers"], mb_x, mb_cos, mb_sin, mb_mask)
    if mb_mask is None:
        in_specs = in_specs[:4]
        args = args[:4]

        def pp_fn_nomask(lp, a, b_, c_):
            return pp_fn(lp, a, b_, c_, None)
        fn = pp_fn_nomask
    else:
        fn = pp_fn
    outs = shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=P(),
                     check_vma=False)(*args)
    x = outs.reshape(b, s, c.hidden_size)

    x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
    head = params.get("lm_head")
    if head is None:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, head)
    return logits.astype(jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("config", "mesh", "n_microbatches",
                                    "clip_eps"))
def pipeline_train_grads_1f1b(params: Params, config: ModelConfig,
                              tokens: jax.Array, completion_mask: jax.Array,
                              advantages: jax.Array, *, mesh: Mesh,
                              n_microbatches: int = 4,
                              clip_eps: float = 0.2):
    """Loss + grads with the 1F1B (one-forward-one-backward) schedule.

    GPipe autodiff (``pipeline_forward`` under ``jax.grad``) runs ALL
    forwards then all backwards, so every stage holds M microbatches of
    activations at the forward/backward turnaround. 1F1B interleaves:
    stage s runs forward of microbatch ``t - s`` and backward of
    ``t - (2K-1) + s`` at tick t, so backward of microbatch m starts as
    soon as its forward drains and the resident window is bounded by the
    PIPELINE DEPTH — a ``min(M, 2K)``-slot ring buffer per stage —
    independent of M. Activations are REMATERIALIZED at the backward
    tick (the buffer keeps stage inputs, not internals), the standard
    memory-for-FLOPs trade on HBM-bound chips. Two ppermute streams ride
    ICI neighbors each tick: activations forward, cotangents backward.
    Wall-clock is M + 2K - 1 ticks vs GPipe-autodiff's 2(M + K - 1).

    The objective term mirrors ``pp_train_step``'s on-policy GRPO loss
    exactly (old_logp = stop_grad(logp) ⇒ ratio ≡ 1): each microbatch's
    pg term is normalized by the GLOBAL completion-token count, so the
    accumulated loss/grads are bit-for-bit the full-batch objective
    decomposed over microbatches. Returns ``(loss, grads)`` with grads
    matching the stage-split param tree (same pytree/shardings as
    ``make_pp_train_state``). Dense models; no attention mask plumbed
    (same envelope as ``pp_train_step``).
    """
    from ..training.grpo import token_logprobs

    c = config
    K = mesh.shape["pp"]
    M = n_microbatches
    b, s_full = tokens.shape
    if b % M != 0:
        raise ValueError(f"batch {b} not divisible by {M} microbatches")
    mb = b // M
    BUF = min(M, 2 * K)
    T = M + 2 * K - 1

    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    tgt_mask = completion_mask[:, 1:].astype(jnp.float32)
    s = s_full - 1
    denom = jnp.maximum(jnp.sum(tgt_mask), 1.0)       # GLOBAL normalizer

    x = params["embed"][inputs]                       # (B, S, D)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :],
                                 (mb, s))
    cos, sin = rope_cos_sin(positions, c.head_dim, c.rope_theta,
                            scaling=c.rope_scaling)

    mb_x = x.reshape(M, mb, s, c.hidden_size)
    mb_tok = inputs.reshape(M, mb, s)
    mb_tgt = targets.reshape(M, mb, s)
    mb_tmask = tgt_mask.reshape(M, mb, s)
    mb_adv = advantages.reshape(M, mb)

    tied = "lm_head" not in params
    head_w = params["embed"] if tied else params["lm_head"]
    norm_w = params["final_norm"]

    def stage_apply(stage_lp, h):
        def body(hh, lp):
            hh, _, _aux = _layer(c, lp, hh, cos, sin, None, None)
            return hh, None
        h, _ = jax.lax.scan(body, h, stage_lp)
        return h

    def mb_loss(stage_lp, h_in, head_w, norm_w, tgt, tmask, adv_mb):
        """Last-stage forward + head + this microbatch's pg term."""
        h_out = stage_apply(stage_lp, h_in)
        xh = rms_norm(h_out, norm_w, c.rms_norm_eps)
        if tied:
            logits = jnp.einsum("bsd,vd->bsv", xh, head_w)
        else:
            logits = jnp.einsum("bsd,dv->bsv", xh, head_w)
        logp = token_logprobs(logits.astype(jnp.float32), tgt)
        olp = jax.lax.stop_gradient(logp)
        ratio = jnp.exp(logp - olp)                   # ≡ 1 on-policy
        adv = adv_mb[:, None]
        unclipped = ratio * adv
        clipped = jnp.clip(ratio, 1.0 - clip_eps, 1.0 + clip_eps) * adv
        return -jnp.sum(jnp.minimum(unclipped, clipped) * tmask) / denom

    fwd_perm = [(i, (i + 1) % K) for i in range(K)]
    bwd_perm = [((i + 1) % K, i) for i in range(K)]

    def pp_fn(stage_lp, mb_x, mb_tok, mb_tgt, mb_tmask, mb_adv,
              head_w, norm_w):
        stage_lp = jax.tree_util.tree_map(lambda a: a[0], stage_lp)
        stage = jax.lax.axis_index("pp")
        zero_h = jnp.zeros((mb, s, c.hidden_size), mb_x.dtype)

        def tick(carry, t):
            (fwd_stream, bwd_stream, saved, g_lp, g_embed, g_head,
             g_norm, loss_acc) = carry
            recv_fwd = jax.lax.ppermute(fwd_stream, "pp", fwd_perm)
            recv_bwd = jax.lax.ppermute(bwd_stream, "pp", bwd_perm)

            # ---- forward of microbatch t - stage -----------------------
            mf = t - stage
            active_f = (mf >= 0) & (mf < M)
            mf_c = jnp.clip(mf, 0, M - 1)
            h_in = jnp.where(stage == 0,
                             jax.lax.dynamic_index_in_dim(mb_x, mf_c, 0,
                                                          False),
                             recv_fwd)
            slot = mf_c % BUF
            old_slot = jax.lax.dynamic_index_in_dim(saved, slot, 0, False)
            saved = jax.lax.dynamic_update_index_in_dim(
                saved, jnp.where(active_f, h_in, old_slot), slot, 0)
            h_out = stage_apply(stage_lp, h_in)
            fwd_stream = jnp.where(active_f, h_out, fwd_stream)

            # ---- backward of microbatch t - (2K-1) + stage -------------
            mbk = t - (2 * K - 1) + stage
            active_b = (mbk >= 0) & (mbk < M)
            mb_c = jnp.clip(mbk, 0, M - 1)
            h_saved = jax.lax.dynamic_index_in_dim(saved, mb_c % BUF, 0,
                                                   False)
            tgt = jax.lax.dynamic_index_in_dim(mb_tgt, mb_c, 0, False)
            tmask = jax.lax.dynamic_index_in_dim(mb_tmask, mb_c, 0, False)
            adv_mb = jax.lax.dynamic_index_in_dim(mb_adv, mb_c, 0, False)
            tok = jax.lax.dynamic_index_in_dim(mb_tok, mb_c, 0, False)

            def last_branch(op):
                lp, h_in, cot, tgt, tmask, adv_mb, hw, nw = op
                loss_m, (dlp, dh, dhw, dnw) = jax.value_and_grad(
                    mb_loss, argnums=(0, 1, 2, 3))(lp, h_in, hw, nw,
                                                   tgt, tmask, adv_mb)
                return dlp, dh, dhw, dnw, loss_m

            def mid_branch(op):
                lp, h_in, cot, tgt, tmask, adv_mb, hw, nw = op
                out_hole, vjp = jax.vjp(stage_apply, lp, h_in)
                dlp, dh = vjp(cot.astype(out_hole.dtype))
                return (dlp, dh, jnp.zeros_like(hw), jnp.zeros_like(nw),
                        jnp.zeros(()))

            dlp, dh_in, dhw, dnw, loss_m = jax.lax.cond(
                stage == K - 1, last_branch, mid_branch,
                (stage_lp, h_saved, recv_bwd, tgt, tmask, adv_mb,
                 head_w, norm_w))

            gate = active_b.astype(jnp.float32)
            g_lp = jax.tree_util.tree_map(
                lambda g, d: g + gate * d.astype(g.dtype), g_lp, dlp)
            g_head = g_head + gate * dhw.astype(g_head.dtype)
            g_norm = g_norm + gate * dnw.astype(g_norm.dtype)
            loss_acc = loss_acc + gate * loss_m
            # Stage 0's dh_in is the cotangent of the embedding rows.
            emb_gate = gate * (stage == 0).astype(jnp.float32)
            g_embed = g_embed.at[tok].add(
                emb_gate * dh_in.astype(g_embed.dtype))
            bwd_stream = jnp.where(active_b, dh_in.astype(bwd_stream.dtype),
                                   bwd_stream)
            return (fwd_stream, bwd_stream, saved, g_lp, g_embed, g_head,
                    g_norm, loss_acc), None

        init = (
            zero_h, jnp.zeros((mb, s, c.hidden_size), mb_x.dtype),
            jnp.zeros((BUF, mb, s, c.hidden_size), mb_x.dtype),
            jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, jnp.float32), stage_lp),
            jnp.zeros(params["embed"].shape, jnp.float32),
            jnp.zeros(head_w.shape, jnp.float32),
            jnp.zeros(norm_w.shape, jnp.float32),
            jnp.zeros(()),
        )
        (_, _, _, g_lp, g_embed, g_head, g_norm, loss_acc), _ = \
            jax.lax.scan(tick, init, jnp.arange(T, dtype=jnp.int32))

        # Layer grads stay stage-local (out_spec 'pp'); the shared tensors
        # were each produced by exactly one stage → psum = broadcast.
        g_lp = jax.tree_util.tree_map(lambda a: a[None], g_lp)
        g_embed = jax.lax.psum(g_embed, "pp")
        g_head = jax.lax.psum(g_head, "pp")
        g_norm = jax.lax.psum(g_norm, "pp")
        loss_acc = jax.lax.psum(loss_acc, "pp")
        return g_lp, g_embed, g_head, g_norm, loss_acc

    lp_specs = stage_param_specs(params)["layers"]
    outs = shard_map(
        pp_fn, mesh=mesh,
        in_specs=(lp_specs, P(), P(), P(), P(), P(), P(), P()),
        out_specs=(jax.tree_util.tree_map(lambda _: P("pp"), lp_specs),
                   P(), P(), P(), P()),
        check_vma=False,
    )(params["layers"], mb_x, mb_tok, mb_tgt, mb_tmask, mb_adv,
      head_w, norm_w)
    g_lp, g_embed, g_head, g_norm, loss = outs

    grads: Params = {"layers": g_lp, "final_norm": g_norm}
    if tied:
        grads["embed"] = g_embed + g_head
    else:
        grads["embed"] = g_embed
        grads["lm_head"] = g_head
    return loss, grads


def place_pipeline_params(params: Params, mesh: Mesh) -> Params:
    """Device-put pre-split params with stage shardings."""
    from jax.sharding import NamedSharding
    specs = stage_param_specs(params)
    return jax.tree_util.tree_map(
        lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
        params, specs)


def make_pp_train_state(config: ModelConfig, key: jax.Array, mesh: Mesh,
                        *, learning_rate: float = 1e-5,
                        params: Optional[Params] = None,
                        optimizer=None):
    """TrainState whose params are stage-split and placed on the 'pp'
    mesh; optimizer state inherits the param shardings (Adam moments are
    param-shaped, so GSPMD propagates the stage axis)."""
    from ..models.transformer import init_params
    from ..training.trainer import TrainState, make_optimizer

    if params is None:
        params = init_params(config, key)
    params = place_pipeline_params(
        split_layers_for_stages(params, mesh.shape["pp"]), mesh)
    opt = optimizer or make_optimizer(learning_rate)
    opt_state = jax.jit(opt.init)(params)
    return TrainState(params=params, opt_state=opt_state,
                      step=jnp.zeros((), jnp.int32), opt=opt)


def pp_train_step(state, config: ModelConfig, mesh: Mesh,
                  tokens: jax.Array, completion_mask: jax.Array,
                  rewards: jax.Array, group_ids: jax.Array, *,
                  optimizer=None, n_microbatches: int = 2,
                  grpo_config=None, num_groups: Optional[int] = None,
                  schedule: str = "gpipe"):
    """One GRPO update with the transformer blocks pipelined over 'pp'.

    The pp counterpart of training.trainer.train_step (which runs the
    dp/fsdp/tp/sp layouts): same clipped objective and group-relative
    advantages. ``schedule`` picks the pipeline schedule:

    - "gpipe": forward is ``pipeline_forward``; autodiff differentiates
      through the ppermute ring, so the backward pass is the reverse
      pipeline schedule and every stage holds all M microbatches of
      activations at the turnaround.
    - "1f1b": ``pipeline_train_grads_1f1b`` interleaves each stage's
      forwards and backwards, bounding resident activations by pipeline
      depth instead of M (same loss and grads — parity-tested).

    ``state`` comes from make_pp_train_state (stage-split params). Dense
    models only (the MoE aux loss is not plumbed through the pipelined
    region)."""
    import optax

    from ..training.grpo import (GRPOConfig, group_relative_advantages,
                                 grpo_objective, token_logprobs)
    from ..training.trainer import TrainState, make_optimizer

    if schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    grpo_config = grpo_config or GRPOConfig()
    opt = optimizer or state.opt or make_optimizer()
    n_groups = num_groups or int(tokens.shape[0])
    adv = group_relative_advantages(
        rewards, group_ids, n_groups,
        normalize_std=grpo_config.normalize_std,
        min_std=grpo_config.min_group_std,
        leave_one_out=grpo_config.leave_one_out)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    tgt_mask = completion_mask[:, 1:]

    if schedule == "1f1b":
        loss, grads = pipeline_train_grads_1f1b(
            state.params, config, tokens, completion_mask, adv,
            mesh=mesh, n_microbatches=n_microbatches,
            clip_eps=grpo_config.clip_eps)
        metrics = {}
    else:
        def loss_fn(params):
            logits = pipeline_forward(params, config, inputs, mesh=mesh,
                                      n_microbatches=n_microbatches)
            logp = token_logprobs(logits, targets)
            olp = jax.lax.stop_gradient(logp)
            return grpo_objective(logp, olp, adv, tgt_mask, grpo_config)

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params)
        metrics = dict(metrics)
    updates, opt_state = opt.update(grads, state.opt_state, state.params)
    params = optax.apply_updates(state.params, updates)
    metrics["loss"] = loss
    metrics["grad_norm"] = optax.global_norm(grads)
    # Carry the RESOLVED optimizer (an explicit one must stick).
    return TrainState(params=params, opt_state=opt_state,
                      step=state.step + 1, opt=opt), metrics
