"""Multi-host distributed initialization + mesh construction.

The comm backend of the framework (SURVEY.md §2.7): where a GPU stack
would initialize NCCL/MPI, the TPU build calls ``jax.distributed`` once
per host and lets XLA lower collectives onto ICI (within a slice) and DCN
(across slices). Mesh construction orders axes so the fastest-varying
axes (tp, then sp/ep/pp) map to ICI neighbors and the slowest (dp) spans
DCN — collectives that move the most bytes per step ride the fastest
links (the scaling-book recipe).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh


@dataclasses.dataclass(frozen=True)
class DistributedConfig:
    coordinator_address: Optional[str] = None   # host:port of process 0
    num_processes: Optional[int] = None
    process_id: Optional[int] = None


_initialized = False


def initialize(cfg: DistributedConfig = DistributedConfig()) -> None:
    """Idempotent jax.distributed.initialize — env-driven defaults (TPU
    pods populate them), explicit overrides for DCN-connected CPU/GPU
    test rigs. Single-process runs are a no-op.

    The guard must NOT touch jax.devices()/process_count(): those force
    XLA backend initialization, after which distributed init is illegal —
    so check the distributed client state directly."""
    global _initialized
    if _initialized:
        return
    if jax.distributed.is_initialized():
        _initialized = True
        return
    addr = cfg.coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS")
    nproc = cfg.num_processes if cfg.num_processes is not None else (
        int(os.environ["JAX_NUM_PROCESSES"])
        if "JAX_NUM_PROCESSES" in os.environ else None)
    if addr is None or nproc in (None, 1):
        return
    jax.distributed.initialize(
        coordinator_address=addr, num_processes=nproc,
        process_id=cfg.process_id if cfg.process_id is not None
        else int(os.environ.get("JAX_PROCESS_ID", "0")))
    _initialized = True


# Axis order: slowest (DCN-friendly) → fastest (ICI-neighbor-friendly).
AXIS_ORDER: Tuple[str, ...] = ("dp", "fsdp", "pp", "ep", "sp", "tp")


def make_named_mesh(axis_sizes: dict, *,
                    devices: Optional[Sequence] = None) -> Mesh:
    """Build a mesh with any subset of the canonical axes.

    make_named_mesh({'dp': 2, 'tp': 4}) on 8 devices → Mesh('dp','tp').
    Axis product must equal the device count."""
    devices = list(devices) if devices is not None else jax.devices()
    names = [a for a in AXIS_ORDER if axis_sizes.get(a, 1) > 1]
    sizes = [axis_sizes[a] for a in names]
    if not names:                      # single-axis fallback
        names, sizes = ["dp"], [len(devices)]
    total = int(np.prod(sizes))
    if total != len(devices):
        raise ValueError(f"axis product {total} != device count "
                         f"{len(devices)} for {dict(zip(names, sizes))}")
    arr = np.asarray(devices).reshape(sizes)
    return Mesh(arr, tuple(names))
