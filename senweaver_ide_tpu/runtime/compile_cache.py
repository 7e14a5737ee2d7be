"""Where the persistent XLA compile cache lives.

Entry points (``chip_smoke.py``, ``__graft_entry__.py``, ``examples/*``,
``eval_*.py``) call :func:`enable_compile_cache` before
their first compile. Never called at package import: tests and library
users keep whatever JAX is configured with.
"""

from __future__ import annotations

import os

import jax

# The cache directory is part of what a run can reuse, so it must not
# move between runs: one fixed path inside the checkout (git-ignored) —
# never a temp dir, a pid or a timestamp.
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Returns the directory compiled programs are cached in.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own
    and this sets nothing — the operator placed the cache from outside.
    Otherwise JAX is pointed at :data:`REPO_CACHE_DIR`."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
