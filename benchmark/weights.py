"""Seeded weights, made on the device by one jitted program.

The tree of shapes is the program's (``jax.eval_shape`` of its
``init_params``: abstract, nothing runs), the values are the benchmark's:
normal / sqrt(fan_in) in the serving dtype, embedding normal * 0.02, norms 1,
biases 0. Stacked layer tensors are drawn layer by layer inside a scan and
the embedding in row blocks, so the program's transients stay far below the
weights themselves and ``memory_peak_bytes`` is the served system's, not the
generator's. The plain reference is handed these same arrays: it takes
nothing the program has made.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number the driver may pass (a little over
    2**31): the low 31 bits make the key, the rest is folded in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _blocks(n: int, target: int = 32) -> int:
    """A divisor of ``n`` near ``target``: how many blocks a leading axis is
    drawn in."""
    for d in range(min(target, n), 0, -1):
        if n % d == 0:
            return d
    return 1


def _draw(key, shape, dtype, scale: float):
    """normal * scale of ``shape``, drawn in ``_blocks`` slabs of the
    leading axis."""
    nb = _blocks(shape[0]) if len(shape) >= 2 else 1
    slab = (shape[0] // nb,) + tuple(shape[1:])

    def one(i):
        return (jax.random.normal(jax.random.fold_in(key, i), slab, dtype)
                * jnp.asarray(scale, dtype))

    return jax.lax.map(one, jnp.arange(nb)).reshape(shape)


def _leaf(path: str, key, struct):
    name = path.rsplit("/", 1)[-1]
    shape, dtype = struct.shape, struct.dtype
    if name.endswith("norm"):
        return jnp.ones(shape, dtype)
    if name in ("bq", "bk", "bv"):
        return jnp.zeros(shape, dtype)
    if name == "embed":
        return _draw(key, shape, dtype, 0.02)
    # dense: (..., fan_in, fan_out); stacked tensors lead with the layer axis
    fan_in = shape[-2]
    return _draw(key, shape, dtype, 1.0 / float(fan_in) ** 0.5)


def make_weights(config, seed: int):
    """The program's parameter tree for ``config``, filled from ``seed``."""
    from senweaver_ide_tpu.models import init_params
    tree = jax.eval_shape(functools.partial(init_params, config),
                          jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    paths = ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]

    @jax.jit
    def build(key):
        leaves = [_leaf(path, jax.random.fold_in(key, i), struct)
                  for i, (path, (_, struct)) in enumerate(zip(paths, flat))]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return build(seed_key(seed))
