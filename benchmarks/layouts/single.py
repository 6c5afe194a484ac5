"""Layout `single`: the whole model on one chip, no mesh.

`build(model, devices, seed) -> (mesh_or_None, params)`.  The weights
are the benchmark's own data, made from `--seed` on the device in one
jitted call and in the dtype they are served in, over the tree that the
configuration's family gives (`families/<family>.py::shapes`); nothing
is taken from the program's initialisers, so the family's plain
reference and the program read the same numbers and neither made them.
"""
from __future__ import annotations

from typing import Any, Dict

from benchmarks import families


def seed_key(seed: int):
    """A PRNG key from any whole number (seeds pass 2**31)."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def build(model: Dict[str, Any], devices, seed: int):
    import jax
    import jax.numpy as jnp

    spec = families.of(model).shapes(model)
    dtype = jnp.dtype(model['torch_dtype'])

    def make(key):
        tree: Dict[str, Any] = {}
        for i, (path, (shape, fan_in)) in enumerate(sorted(spec.items())):
            k = jax.random.fold_in(key, i)
            if fan_in is None:
                # Norm scales near 1 and not all equal, so a dropped
                # scale shows; f32 as the program keeps them.
                leaf = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            else:
                leaf = (jax.random.normal(k, shape, jnp.float32) *
                        fan_in ** -0.5).astype(dtype)
            node = tree
            for name in path[:-1]:
                node = node.setdefault(name, {})
            node[path[-1]] = leaf
        return tree

    with jax.default_device(devices[0]):
        params = jax.jit(make)(seed_key(seed))
    return None, params
