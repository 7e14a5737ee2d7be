"""Trajectory dataset: rollouts → padded GRPO training batches.

The bridge between the rollout plane (sessions producing traces + token
logs) and the jit training step: trajectories are (prompt_ids,
completion_ids, reward, group_id); batches pad to a power-of-two bucket
(bounded recompilation, same policy as the rollout engine) with a
completion-token mask so the objective only scores generated tokens.

Deterministic order for resume (SURVEY.md §7 step 5): the dataset shuffles
with a seeded permutation per epoch and exposes a cursor that the
checkpoint meta records (training/checkpoint.py data_cursor), so a
restored run continues on the exact next batch.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np


@dataclasses.dataclass
class Trajectory:
    prompt_ids: List[int]
    completion_ids: List[int]
    reward: float
    group_id: int
    # Behavior log-prob per completion token, captured at SAMPLE time by
    # the engine (result_logps). When every trajectory in a batch has
    # them, make_batch_logps aligns them into the old_logp array and the
    # GRPO step trains with exact importance ratios (no second forward,
    # no retained behavior params).
    behavior_logp: Optional[List[float]] = None
    # Tree-rollout lineage (rollout/group_tree.py): 0-based positions
    # WITHIN completion_ids where this trajectory's path through the
    # rollout tree branched. make_branch_mask aligns them with a
    # make_batch output so grpo_objective can sharpen credit at split
    # points (GRPOConfig.branch_credit_boost). None/empty = unbranched.
    branch_points: Optional[List[int]] = None


def _bucket(n: int, minimum: int = 32) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


def make_batch(trajectories: Sequence[Trajectory], *, pad_id: int,
               max_len: Optional[int] = None
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Returns (tokens (B, S), completion_mask (B, S) bool, rewards (B,),
    group_ids (B,)). S = power-of-two bucket of the longest trajectory
    (clipped to max_len; overlong trajectories keep their completion tail
    — the prompt head is dropped, since the objective needs completion
    tokens in context, not the full prompt)."""
    if not trajectories:
        raise ValueError("empty batch")
    lens = [len(t.prompt_ids) + len(t.completion_ids) for t in trajectories]
    s = _bucket(max(lens))
    if max_len is not None:
        s = min(s, max_len)
    b = len(trajectories)
    tokens = np.full((b, s), pad_id, np.int32)
    mask = np.zeros((b, s), bool)
    rewards = np.zeros((b,), np.float32)
    group_ids = np.zeros((b,), np.int32)
    for i, t in enumerate(trajectories):
        seq = list(t.prompt_ids) + list(t.completion_ids)
        comp_start = len(t.prompt_ids)
        if len(seq) > s:
            drop = len(seq) - s
            seq = seq[drop:]
            comp_start = max(0, comp_start - drop)
        tokens[i, :len(seq)] = seq
        mask[i, comp_start:len(seq)] = True
        rewards[i] = t.reward
        group_ids[i] = t.group_id
    return tokens, mask, rewards, group_ids


def make_batch_logps(trajectories: Sequence[Trajectory],
                     tokens: np.ndarray,
                     mask: np.ndarray) -> Optional[np.ndarray]:
    """Align recorded behavior logps with a make_batch output.

    Returns old_logp shaped (B, S-1) — the trainer's target layout
    (position j-1 predicts token j) — or None unless EVERY trajectory
    carries a full logp list (a partial batch would silently mix exact
    ratios with the ratio-1 approximation). Positions outside the
    completion mask hold 0.0 (never read by the masked objective)."""
    if any(t.behavior_logp is None
           or len(t.behavior_logp) != len(t.completion_ids)
           for t in trajectories):
        return None
    b, s = tokens.shape
    old = np.zeros((b, s - 1), np.float32)
    for i, t in enumerate(trajectories):
        # completion tokens sit at the masked positions of row i, in
        # order; target index of seq position j is j-1. Position 0 can
        # never be a target (nothing precedes it) — the trainer's
        # shifted mask excludes it too.
        pos = np.nonzero(mask[i])[0]
        lps = np.asarray(t.behavior_logp[-len(pos):] if len(pos) else [],
                         np.float32)
        keep = pos >= 1
        old[i, pos[keep] - 1] = lps[keep]
    return old


def make_branch_mask(trajectories: Sequence[Trajectory],
                     tokens: np.ndarray,
                     mask: np.ndarray) -> Optional[np.ndarray]:
    """Align recorded tree branch points with a make_batch output.

    Returns a (B, S) float32 mask with 1.0 at the completion tokens
    where the trajectory's rollout-tree path branched, or None when no
    trajectory carries branch points (the common unbranched batch adds
    no operand to the train step). Points cropped away by an overlong
    row's front-drop are silently outside the kept tail."""
    if not any(t.branch_points for t in trajectories):
        return None
    b, s = tokens.shape
    out = np.zeros((b, s), np.float32)
    for i, t in enumerate(trajectories):
        if not t.branch_points:
            continue
        pos = np.nonzero(mask[i])[0]
        n = len(pos)
        dropped = len(t.completion_ids) - n
        for p in t.branch_points:
            q = int(p) - dropped
            if 0 <= q < n:
                out[i, pos[q]] = 1.0
    return out


def pad_batch_for_mesh(
    tokens: np.ndarray, mask: np.ndarray, rewards: np.ndarray,
    group_ids: np.ndarray, *, batch_multiple: int = 1,
    seq_multiple: int = 1, pad_id: int = 0,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Pad a make_batch output so it shards evenly on a mesh: batch axis to
    a multiple of (dp·fsdp), and the TRAINING sequence length (S−1, after
    the trainer's next-token shift) to a multiple of sp. Padded rows get an
    all-False mask, zero reward, and a fresh singleton group id each — they
    contribute nothing to the masked objective or group advantages."""
    b, s = tokens.shape
    target_s = ((s - 1 + seq_multiple - 1) // seq_multiple) * seq_multiple + 1
    if target_s > s:
        pad = target_s - s
        tokens = np.pad(tokens, ((0, 0), (0, pad)), constant_values=pad_id)
        mask = np.pad(mask, ((0, 0), (0, pad)))
    target_b = ((b + batch_multiple - 1) // batch_multiple) * batch_multiple
    if target_b > b:
        extra = target_b - b
        tokens = np.pad(tokens, ((0, extra), (0, 0)), constant_values=pad_id)
        mask = np.pad(mask, ((0, extra), (0, 0)))
        rewards = np.pad(rewards, (0, extra))
        next_gid = int(group_ids.max()) + 1 if b else 0
        group_ids = np.concatenate(
            [group_ids, np.arange(next_gid, next_gid + extra,
                                  dtype=group_ids.dtype)])
    return tokens, mask, rewards, group_ids


class TrajectoryDataset:
    """Seeded-permutation epochs + a resumable cursor."""

    def __init__(self, trajectories: Sequence[Trajectory], *,
                 batch_size: int, seed: int = 0):
        self._items = list(trajectories)
        self.batch_size = batch_size
        self.seed = seed
        self.cursor = 0              # global batch index across epochs

    def __len__(self) -> int:
        return len(self._items)

    @property
    def batches_per_epoch(self) -> int:
        # Ceil division: the final short batch is kept (dropping it would
        # silently skew GRPO groups by a permutation-dependent remainder).
        return max(1, -(-len(self._items) // self.batch_size))

    def _epoch_perm(self, epoch: int) -> np.ndarray:
        rng = np.random.default_rng((self.seed, epoch))
        return rng.permutation(len(self._items))

    def batch_at(self, cursor: int) -> List[Trajectory]:
        epoch = cursor // self.batches_per_epoch
        step = cursor % self.batches_per_epoch
        perm = self._epoch_perm(epoch)
        idx = perm[step * self.batch_size:(step + 1) * self.batch_size]
        return [self._items[i] for i in idx]

    def __iter__(self) -> Iterator[List[Trajectory]]:
        while True:
            yield self.batch_at(self.cursor)
            self.cursor += 1


def place_batch_for_mesh(mesh, tokens, mask, rewards, group_ids,
                         old_logp=None, *, pad_id: int = 0,
                         accum_steps: int = 1):
    """Pad a make_batch output for the mesh and device_put every array
    with its batch/sequence sharding.

    Explicit placement matters: feeding host numpy through jit relies on
    GSPMD propagation, which broadcasts the batch to every device before
    resharding (round-1 review). The sequence axis keeps S = k·sp+1
    (the TRAINING length S−1 shards over sp after the next-token shift
    inside the step), so grids place batch-axis-only here.
    Returns jnp/global arrays ready for train_step."""
    import jax as _jax
    import numpy as _np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.sharding import restrict_spec

    if mesh is None:
        import jax.numpy as _jnp
        if accum_steps > 1 and tokens.shape[0] % accum_steps != 0:
            # Same contract as the mesh path: the returned batch must
            # satisfy the microbatch scan's divisibility check.
            tokens, mask, rewards, group_ids = pad_batch_for_mesh(
                tokens, mask, rewards, group_ids,
                batch_multiple=accum_steps, pad_id=pad_id)
            if old_logp is not None and old_logp.shape[0] < tokens.shape[0]:
                old_logp = _np.pad(
                    old_logp, ((0, tokens.shape[0] - old_logp.shape[0]),
                               (0, 0)))
        out = tuple(map(_jnp.asarray, (tokens, mask, rewards, group_ids)))
        return out + ((_jnp.asarray(old_logp)
                       if old_logp is not None else None),)
    import math as _math
    axes = dict(zip(mesh.axis_names, _np.asarray(mesh.devices).shape))
    data_axes = axes.get("dp", 1) * axes.get("fsdp", 1)
    # The padded batch must ALSO stay divisible by accum_steps (the
    # microbatch scan rejects indivisible batches) → lcm of the two.
    batch_multiple = _math.lcm(data_axes, max(accum_steps, 1))
    tokens, mask, rewards, group_ids = pad_batch_for_mesh(
        tokens, mask, rewards, group_ids,
        batch_multiple=batch_multiple,
        seq_multiple=axes.get("sp", 1), pad_id=pad_id)
    if old_logp is not None and old_logp.shape != (tokens.shape[0],
                                                   tokens.shape[1] - 1):
        # Row AND column growth (sequence padding fires whenever sp>1:
        # bucketed S-1 is never sp-divisible) — padded positions are
        # outside the mask and never read.
        old_logp = _np.pad(old_logp,
                           ((0, tokens.shape[0] - old_logp.shape[0]),
                            (0, tokens.shape[1] - 1 - old_logp.shape[1])))
    row_sh = NamedSharding(mesh, restrict_spec(P(("dp", "fsdp")), mesh))
    grid_sh = NamedSharding(mesh, restrict_spec(P(("dp", "fsdp"), None),
                                                mesh))
    return (_jax.device_put(tokens, grid_sh),
            _jax.device_put(mask, grid_sh),
            _jax.device_put(rewards, row_sh),
            _jax.device_put(group_ids, row_sh),
            (_jax.device_put(old_logp, grid_sh)
             if old_logp is not None else None))
