"""Expert parallelism: top-k routed MoE FFN with all-to-all over 'ep'.

No reference counterpart (SURVEY.md §2.7) — included for API completeness
of the parallelism layer, designed TPU-first: experts are sharded over the
'ep' mesh axis; tokens are dispatched to their experts with
``lax.all_to_all`` over ICI (the canonical Switch/GShard pattern), FFN'd
locally, and combined back with the gate weights. A dense single-device
path (`moe_ffn`) is the semantic reference the sharded path is tested
against on a CPU-simulated mesh (SURVEY.md §4).

Routing: softmax router → top-k experts/token → capacity-bounded dispatch
(capacity = ceil(tokens/E · capacity_factor · top_k)); overflowed tokens
fall through with zero contribution (standard dropped-token semantics) and
gates are renormalized over the selected k. Aux load-balancing loss follows
the Switch formulation: E · Σ_e f_e · p_e.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

MoEParams = Dict[str, jax.Array]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    hidden_size: int
    intermediate_size: int
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    dtype: jnp.dtype = jnp.float32


def init_moe_params(cfg: MoEConfig, key: jax.Array) -> MoEParams:
    kr, kg, ku, kd = jax.random.split(key, 4)
    D, F, E = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts

    def dense(k, shape, fan_in):
        scale = 1.0 / jnp.sqrt(jnp.asarray(fan_in, jnp.float32))
        return (jax.random.normal(k, shape, jnp.float32)
                * scale).astype(cfg.dtype)

    return {
        "router": dense(kr, (D, E), D),
        "w_gate": dense(kg, (E, D, F), D),
        "w_up": dense(ku, (E, D, F), D),
        "w_down": dense(kd, (E, F, D), F),
    }


def _route(cfg: MoEConfig, router: jax.Array, x_flat: jax.Array,
           capacity: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Compute dispatch/combine tensors.

    x_flat: (T, D). Returns (dispatch (T, E, C) bool-ish fp, combine
    (T, E, C) fp32, aux_loss scalar)."""
    T, E = x_flat.shape[0], cfg.num_experts
    logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32),
                        router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)              # (T, E)
    gate_vals, expert_idx = jax.lax.top_k(probs, cfg.top_k)   # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    # Position of each (token, k) within its expert's capacity buffer:
    # cumulative count of prior assignments to the same expert.
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)  # (T, k, E)
    flat_assign = onehot.reshape(T * cfg.top_k, E)           # row-major:
    # token-major then k — tokens earlier in the batch win capacity slots.
    pos_in_expert = (jnp.cumsum(flat_assign, axis=0) - flat_assign)
    pos_in_expert = (pos_in_expert * flat_assign).sum(-1).reshape(
        T, cfg.top_k)                                       # (T, k)
    keep = pos_in_expert < capacity

    disp = (jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)
            [:, :, :, None]
            * jax.nn.one_hot(jnp.where(keep, pos_in_expert, 0), capacity,
                             dtype=jnp.float32)[:, :, None, :]
            * keep[:, :, None, None].astype(jnp.float32))   # (T,k,E,C)
    dispatch = disp.sum(1)                                  # (T, E, C)
    combine = (disp * gate_vals[:, :, None, None]).sum(1)   # (T, E, C)

    # Switch aux loss: E * sum_e fraction_tokens_e * mean_prob_e.
    frac = (onehot.sum(1).astype(jnp.float32).mean(0))      # (E,)
    mean_prob = probs.mean(0)
    aux = (frac * mean_prob).sum() * E
    return dispatch, combine, aux


def _expert_ffn(w_gate, w_up, w_down, h):
    """h: (..., D) for one expert."""
    gate = jnp.einsum("...d,df->...f", h, w_gate)
    up = jnp.einsum("...d,df->...f", h, w_up)
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(h.dtype) * up
    return jnp.einsum("...f,fd->...d", act, w_down)


def _expert_ffn_q(w_gate, g_s, w_up, u_s, w_down, d_s, h):
    """int8 expert bank variant (models/quantize.py): upcast at use,
    per-output-channel scale as a fused epilogue — halves the expert
    HBM each routed batch streams."""
    def mm(h_, w, s_, spec):
        out = jnp.einsum(spec, h_, w.astype(h_.dtype))
        return (out.astype(jnp.float32) * s_).astype(h_.dtype)

    gate = mm(h, w_gate, g_s, "...d,df->...f")
    up = mm(h, w_up, u_s, "...d,df->...f")
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(h.dtype) * up
    return mm(act, w_down, d_s, "...f,fd->...d")


def _run_experts(params: MoEParams, expert_in: jax.Array) -> jax.Array:
    """vmap over experts, int8-aware (both moe_ffn paths share it)."""
    if params["w_gate"].dtype == jnp.int8:
        return jax.vmap(_expert_ffn_q)(
            params["w_gate"], params["w_gate_scale"],
            params["w_up"], params["w_up_scale"],
            params["w_down"], params["w_down_scale"], expert_in)
    return jax.vmap(_expert_ffn)(params["w_gate"], params["w_up"],
                                 params["w_down"], expert_in)


def _capacity(cfg: MoEConfig, tokens: int) -> int:
    return max(1, math.ceil(tokens / cfg.num_experts
                            * cfg.capacity_factor * cfg.top_k))


@functools.partial(jax.jit, static_argnames=("cfg",))
def moe_ffn(params: MoEParams, cfg: MoEConfig,
            x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Dense reference path. x: (B, S, D) → (out, aux_loss)."""
    b, s, d = x.shape
    x_flat = x.reshape(b * s, d)
    C = _capacity(cfg, b * s)
    with jax.named_scope("moe.route"):
        dispatch, combine, aux = _route(cfg, params["router"], x_flat, C)
    with jax.named_scope("moe.experts"):
        expert_in = jnp.einsum("tec,td->ecd", dispatch,
                               x_flat.astype(jnp.float32)).astype(x.dtype)
        expert_out = _run_experts(params, expert_in)
        y = jnp.einsum("tec,ecd->td", combine,
                       expert_out.astype(jnp.float32))
    return y.reshape(b, s, d).astype(x.dtype), aux


@functools.partial(jax.jit, static_argnames=("cfg", "mesh"))
def moe_ffn_sharded(params: MoEParams, cfg: MoEConfig, x: jax.Array, *,
                    mesh: Mesh) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel path: tokens sharded over 'ep' (batch axis),
    experts sharded over 'ep' (expert axis); two all_to_alls move token
    buffers token-shard→expert-shard and back.

    x: (B, S, D) with B divisible by ep. Returns (out, aux_loss)."""
    ep = mesh.shape["ep"]
    E = cfg.num_experts
    if E % ep != 0:
        raise ValueError(f"num_experts {E} not divisible by ep={ep}")
    b, s, d = x.shape

    quantized = params["w_gate"].dtype == jnp.int8

    def fn(router, w_gate, w_up, w_down, x_local, *scales):
        # x_local: (B/ep, S, D); local experts: (E/ep, D, F).
        bl = x_local.shape[0]
        t_local = bl * s
        x_flat = x_local.reshape(t_local, d)
        C = _capacity(cfg, t_local)
        dispatch, combine, aux = _route(cfg, router, x_flat, C)
        # Local dispatch buffers per (global) expert: (E, C, D).
        buf = jnp.einsum("tec,td->ecd", dispatch,
                         x_flat.astype(jnp.float32)).astype(x_local.dtype)
        # all_to_all: split expert axis across ranks, gather token shards:
        # (E, C, D) → (E/ep, ep·C, D) on each rank.
        buf = buf.reshape(ep, E // ep, C, d)
        buf = jax.lax.all_to_all(buf, "ep", split_axis=0, concat_axis=1,
                                 tiled=False)              # (E/ep, ep, C, D)
        buf = buf.reshape(E // ep, ep * C, d)
        local = {"w_gate": w_gate, "w_up": w_up, "w_down": w_down}
        if quantized:
            local["w_gate_scale"], local["w_up_scale"], \
                local["w_down_scale"] = scales
        out = _run_experts(local, buf)                     # (E/ep, ep·C, D)
        # Return trip: back to token shards.
        out = out.reshape(E // ep, ep, C, d)
        out = jax.lax.all_to_all(out, "ep", split_axis=1, concat_axis=0,
                                 tiled=False)              # (E, 1?, C, D)
        out = out.reshape(E, C, d)
        y = jnp.einsum("tec,ecd->td", combine, out.astype(jnp.float32))
        aux = jax.lax.pmean(aux, "ep")
        return y.reshape(bl, s, d).astype(x_local.dtype), aux

    args = [params["router"], params["w_gate"], params["w_up"],
            params["w_down"], x]
    in_specs = [P(), P("ep"), P("ep"), P("ep"), P("ep")]
    if quantized:
        # per-expert scales shard over 'ep' exactly like their banks
        args += [params["w_gate_scale"], params["w_up_scale"],
                 params["w_down_scale"]]
        in_specs += [P("ep"), P("ep"), P("ep")]
    out, aux = shard_map(
        fn, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P("ep"), P()), check_vma=False)(*args)
    return out, aux
