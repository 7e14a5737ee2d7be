"""Test harness: force an 8-device virtual CPU mesh before JAX initializes.

This is the TPU build's analogue of the reference's mocked-service unit
harness (SURVEY.md §4): multi-chip sharding paths are exercised on a
CPU-simulated mesh so the suite runs anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"   # tests never take the chip
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# Also through the live config: a jax imported before this file (a
# plugin, another conftest) has already read the environment.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture
def rng():
    import numpy as np

    return np.random.default_rng(0)


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Reset JAX's compiled-computation caches between test modules.

    Two full-suite runs (2026-07-30) died with a segfault INSIDE XLA's
    CPU backend_compile at the same late-suite test after ~500
    accumulated compilations in one process; the same module passes in
    isolation and shorter prefixes don't reproduce it. Clearing the
    traced/compiled caches at module boundaries bounds the compiler
    state any single module runs against (cost: per-module recompiles
    of shared tiny-model graphs)."""
    yield
    import jax
    jax.clear_caches()
