"""Shared shard_map plumbing for the attention ops.

ring_attention and ulysses_attention wrap the same mesh logic: batch
stays on the data axes, heads on the tensor axis, only the sequence dim
participates in the SP collective.  One copy here so axis selection and
the GQA fallback cannot diverge between the two strategies.

The single-device kernels (flash, paged decode) use the same plumbing
when they run under a mesh of more than one device: a Mosaic kernel
cannot be partitioned by GSPMD (JAX refuses a `pallas_call` under a
multi-device sharding context or a partly-manual shard_map), so each
device runs the kernel on its own batch/head shard inside a shard_map
that makes EVERY mesh axis manual (`batch_head_axes`).

Degenerate meshes are first-class: a slice-serving replica builds ONE
mesh per slice and runs the SAME prefill code whether the slice has one
host or eight — so `sp_degree` treats a missing sequence axis (or one
of size 1) as degree 1, and the wrappers fall back to the plain flash
kernel there instead of spinning up a one-party collective.  This is
what lets `serve/slice_replica.py` ship a single code path for every
`num_hosts:` value.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax


def sp_shard_map(fn, mesh, in_specs, out_specs):
    """shard_map over ALL mesh axes (none left to GSPMD), without the
    replication checker: it predates several collectives used here.
    Called inside a region that is already manual over some axes (the
    pipeline schedule, parallel/pipeline.py), it nests over the rest."""
    manual = jax.sharding.get_abstract_mesh().manual_axes
    # skytpu: lint-ok[tracer-safety] reason=manual_axes is the mesh context's static tuple of axis names, never a traced value
    if manual:
        return jax.shard_map(
            fn, in_specs=in_specs, out_specs=out_specs,
            axis_names=frozenset(mesh.axis_names) - frozenset(manual),
            check_vma=False)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def batch_head_axes(mesh, batch: Optional[int] = None
                    ) -> Tuple[Optional[tuple], Optional[tuple], int]:
    """→ (batch_axes, head_axes, tensor degree): the mesh axes that
    shard a [b, h, s, d] attention operand's batch and head dims.
    Size-1 axes are dropped; the batch axes are dropped too when they
    do not divide `batch` (the operand is then replicated over them —
    a serving prefill has batch 1)."""

    def _axes(*names):
        present = tuple(a for a in names if a in mesh.axis_names and
                        mesh.shape[a] > 1)
        return present if present else None

    batch_axes = _axes('data', 'fsdp')
    if batch is not None and batch_axes and batch % math.prod(
            mesh.shape[a] for a in batch_axes):
        batch_axes = None
    head_axes = _axes('tensor')
    tp = math.prod(mesh.shape[a] for a in (head_axes or ()))
    return batch_axes, head_axes, tp


def sp_degree(mesh, axis_name: str) -> int:
    """Size of the sequence-parallel axis; 1 when the mesh does not
    carry the axis at all (degenerate single-host slice) or carries it
    at size 1 — both mean "no sequence collective", and callers must
    treat them identically."""
    if mesh is None or axis_name not in mesh.axis_names:
        return 1
    return int(mesh.shape[axis_name])


def sp_partition(mesh, axis_name: str) -> Tuple[object, tuple, int]:
    """→ (PartitionSpec for [b, h, s, d], head_axes, tensor degree).

    Accepts a degenerate mesh (sequence axis of size 1): the axis still
    appears in the spec — shard_map over a size-1 axis is exact, the
    ring simply has one hop — so the same jitted program serves every
    slice width.  A mesh MISSING the axis entirely is the caller's cue
    to skip shard_map (see `sp_degree`); putting an unknown axis in a
    PartitionSpec would be an error, so it is omitted here.
    """
    batch_axes, head_axes, tp = batch_head_axes(mesh)
    seq_axis = axis_name if axis_name in mesh.axis_names else None
    return (jax.sharding.PartitionSpec(batch_axes, head_axes, seq_axis,
                                       None), head_axes, tp)


def broadcast_gqa_if_indivisible(q, k, v, divisor: int):
    """Broadcast kv heads up to q heads when they don't divide the head
    sharding (`divisor` = the product of head-sharding mesh axes)."""
    if k.shape[1] % divisor:
        from skypilot_tpu.ops.attention import _repeat_kv  # pylint: disable=import-outside-toplevel
        k, v = _repeat_kv(q, k, v)
    return k, v
