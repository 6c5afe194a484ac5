"""Layout `single_seeded`: the whole model on one chip, no mesh, as
`single` makes it, and then the leaves whose values a random normal
does not give (a family's `reseed`: Mamba-2's decay rates and time
steps, drawn from the ranges the model is initialised from) set by the
family from the same `--seed`.

`build(model, devices, seed) -> (None, params)`.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

from benchmarks import families
from benchmarks.layouts import single


def build(model: Dict[str, Any], devices, seed: int):
    import jax

    _, params = single.build(model, devices, seed)
    reseed = functools.partial(families.of(model).reseed, model)
    with jax.default_device(devices[0]):
        params = jax.jit(reseed, donate_argnums=(0,))(
            params, jax.random.fold_in(single.seed_key(seed), 1 << 20))
    return None, params
