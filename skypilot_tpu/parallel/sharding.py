"""Logical-axis sharding rules and helpers.

Models annotate parameters/activations with *logical* axis names
('batch', 'embed', 'heads', ...); these rules map them onto the physical
mesh axes from parallel/mesh.py.  GSPMD then inserts the collectives —
nothing here hand-schedules communication.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

# (logical axis, mesh axis or tuple of mesh axes) — first matching rule
# wins.  batch rides data(+fsdp) — DCN-safe; everything model-internal
# stays on ICI axes.
LOGICAL_AXIS_RULES: Tuple[Tuple[str, Optional[object]], ...] = (
    ('batch', ('data', 'fsdp')),
    ('seq', 'sequence'),
    ('embed', 'fsdp'),
    ('heads', 'tensor'),
    ('kv_heads', 'tensor'),
    ('mlp', 'tensor'),
    ('vocab', 'tensor'),
    ('expert', 'expert'),
    ('head_dim', None),
    ('kv', None),
    ('stage', 'pipeline'),
    ('layers', None),
)


def logical_sharding(mesh, *logical_axes: Optional[str]):
    """NamedSharding for an array whose dims carry these logical names."""
    import jax  # pylint: disable=import-outside-toplevel
    rules = dict(LOGICAL_AXIS_RULES)
    spec = []
    used = set()
    for name in logical_axes:
        mesh_axes = rules.get(name) if name is not None else None
        if mesh_axes is None:
            spec.append(None)
            continue
        if isinstance(mesh_axes, str):
            mesh_axes = (mesh_axes,)
        # Drop axes not in the mesh or already used by an earlier dim
        # (an axis may shard at most one dim of a given array).
        usable = tuple(a for a in mesh_axes
                       if a in mesh.axis_names and a not in used)
        used.update(usable)
        if not usable:
            spec.append(None)
        elif len(usable) == 1:
            spec.append(usable[0])
        else:
            spec.append(usable)
    return jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(*spec))


def batch_sharding(mesh):
    """Sharding for [batch, seq, ...] input arrays."""
    return logical_sharding(mesh, 'batch', 'seq')


def token_batch_sharding(mesh):
    """Sharding for raw token batches [batch, seq_len + 1].

    The +1 next-token column makes the seq dim indivisible by a
    non-trivial 'sequence' axis, so tokens shard on batch only; the
    model's logical constraints re-shard activations onto the sequence
    axis after the embedding (where the dim is seq_len again).
    """
    return logical_sharding(mesh, 'batch', None)


def head_kernel_sharding(mesh):
    """Sharding for the lm-head kernel [embed, vocab] when it travels
    as a PLAIN array rather than a flax param — the fused linear+CE
    hot path (models/losses.py) takes the kernel as a function
    argument, so its placement must match the in-module annotation
    ('embed', 'vocab') or GSPMD re-gathers the whole [d, V] matrix
    before every chunk matmul."""
    return logical_sharding(mesh, 'embed', 'vocab')


def page_pool_sharding(mesh):
    """Sharding for one paged-KV pool leaf
    [layers, n_pages, kv_heads, page_size, head_dim]: kv_heads ride
    'tensor' exactly like the attention params (the paged gather /
    scatter in the tick stays local per tensor shard); pages and
    in-page positions are replicated axes — the page POOL is the
    memory unit, every chip holds every page's slice of its own
    heads."""
    return logical_sharding(mesh, 'layers', None, 'kv_heads', None,
                            'head_dim')


def page_scale_sharding(mesh):
    """Sharding for int8-KV per-token scales
    [layers, n_pages, kv_heads, page_size] (the head_dim axis is
    reduced away by the absmax)."""
    return logical_sharding(mesh, 'layers', None, 'kv_heads', None)


def paged_cache_sharding(mesh, quantized: bool = False):
    """Sharding pytree matching `models/decode.init_paged_cache`:
    pool leaves per `page_pool_sharding` (int8 pools add the scale
    leaves), block tables and lengths replicated (tiny int32 arrays
    every tensor shard must agree on)."""
    kv = page_pool_sharding(mesh)
    if quantized:
        kv = {'q': kv, 'scale': page_scale_sharding(mesh)}
    rep = replicated(mesh)
    return {'k': kv, 'v': kv, 'block_tables': rep, 'lengths': rep}


def spec_drafts_sharding(mesh):
    """Sharding for the speculative-decoding draft batch [slots, k]
    the host stages each verify tick: fully replicated, like the rest
    of the per-slot engine state — every tensor shard must verify the
    same drafts, and the array is a handful of int32s, so an explicit
    placement keeps GSPMD from speculating about its tiny batch
    axis."""
    import jax  # pylint: disable=import-outside-toplevel
    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())


def engine_state_sharding(mesh):
    """Sharding for the engine's per-slot decode state arrays (tokens,
    masks, counters, keys): fully replicated — they are a few bytes per
    slot and every tensor shard needs them to agree, so GSPMD must not
    be tempted to shard the tiny batch axis."""
    import jax  # pylint: disable=import-outside-toplevel
    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())


def replicated(mesh):
    import jax  # pylint: disable=import-outside-toplevel
    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
