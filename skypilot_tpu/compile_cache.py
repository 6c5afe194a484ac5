"""Where this checkout's processes keep JAX's persistent compile cache.

Every entry point that compiles (the model server, the slice replica,
the example trainer, the bench scripts, the chip tests) calls `enable()`
before its first compile, so the processes of one command, and the
commands of one machine, share compiled programs.

The directory can be placed from outside: with
`JAX_COMPILATION_CACHE_DIR` set, JAX reads it itself and nothing is set
here.  Otherwise the cache is `<checkout>/.jax_cache`: a fixed path,
because the path is part of what a later process must find again — a
temp, pid or time-derived directory never hits.
"""
from __future__ import annotations

import os

ENV_CACHE_DIR = 'JAX_COMPILATION_CACHE_DIR'
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    '.jax_cache')


def enable() -> str:
    """Point JAX at the persistent compile cache; returns the directory
    in use.  Call before the first compile."""
    placed = os.environ.get(ENV_CACHE_DIR)
    if placed:
        return placed
    import jax  # pylint: disable=import-outside-toplevel
    jax.config.update('jax_compilation_cache_dir', DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
