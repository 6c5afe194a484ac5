"""Pallas decode-state kernel of a Mamba-2 layer: one token a slot.

A Mamba-2 head keeps a matrix of state S [P, N] (head channels x state
size) and, at each token, decays it, adds the token's outer product
and reads it out:

    S <- exp(dt A) S + (dt x) B^T        y = S C

with x [P] the head's channels, B and C [N] its group's, dt a scalar
and A < 0 the head's decay rate.  At decode the state is what moves:
Nemotron-H's 64 heads of [64, 128] float32 are 2 MiB a slot a layer,
read and written once a token, against a few kilobytes of x, B and C.
So the kernel is a copy of the state through VMEM with the update on
the way, and nothing else: the grid has one program a slot, whose
block is the slot's whole state of the layer; the update and the
read-out run head by head on the block in VMEM; the block goes back
to the buffer it came from (`input_output_aliases`).

The kernel takes the WHOLE state of every layer (`[M, B, H, P, N]`, as
`models/decode.init_paged_cache` lays it out and the decode tick's
layer loop carries it) and the layer as a scalar-prefetch index: no
layer's share is sliced out or scattered back around the call, which
is what keeps the 3 GB of a serving engine's state in place.  A slot
that is not live (`active` 0: empty, parked, mid-admission) keeps its
state as it was.

The caller gives the update's pieces in the layouts the vector unit
reads without moving lanes: (dt x) as [B, P, H] (a head's channels on
sublanes, a column to broadcast over the state's lanes), exp(dt A) as
[B, 1, H], B and C as [B, G, N] (a group's row to broadcast over the
state's sublanes).  The read-out y comes back as [B, P, H].

Same interpret-mode pattern as ops/attention.py
(`SKYTPU_PALLAS_INTERPRET=1`, CPU backend only); off-TPU without
interpret mode `_ssm_decode_reference`, the same arithmetic in `jnp`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from skypilot_tpu.ops.attention import _use_pallas
from skypilot_tpu.ops.attention import interpret_mode


def _ssm_decode_kernel(layer_ref, active_ref, state_ref, dtx_ref,
                       decay_ref, b_ref, c_ref, state_out, y_ref, *,
                       heads_per_group: int):
    """One slot: every head's S of the layer in VMEM, updated and read
    out; the block is written back whether or not it changed."""
    from jax.experimental import pallas as pl  # pylint: disable=import-outside-toplevel
    del layer_ref                 # the index maps read it
    live = active_ref[pl.program_id(0)] > 0
    heads = state_ref.shape[2]
    for h in range(heads):
        g = h // heads_per_group
        s = state_ref[0, 0, h]                          # [P, N]
        new = (decay_ref[0, :, h:h + 1] * s +
               dtx_ref[0, :, h:h + 1] * b_ref[0, g:g + 1, :])
        new = jnp.where(live, new, s)
        state_out[0, 0, h] = new
        y_ref[0, :, h:h + 1] = jnp.sum(new * c_ref[0, g:g + 1, :],
                                       axis=-1, keepdims=True)


@functools.lru_cache(maxsize=None)
def _decode_call(state_shape, groups: int, interpret: bool):
    """The kernel's `pallas_call` for one state shape, built once a
    process (every program of an engine shares its trace)."""
    from jax.experimental import pallas as pl  # pylint: disable=import-outside-toplevel
    from jax.experimental.pallas import tpu as pltpu  # pylint: disable=import-outside-toplevel

    _, b, h, p, n = state_shape
    state_spec = pl.BlockSpec((1, 1, h, p, n),
                              lambda bb, layer, _: (layer[0], bb, 0, 0, 0))
    slot = lambda *shape: pl.BlockSpec(
        (1,) + shape, lambda bb, *_: (bb,) + (0,) * len(shape))
    return pl.pallas_call(
        functools.partial(_ssm_decode_kernel,
                          heads_per_group=h // groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[state_spec, slot(p, h), slot(1, h), slot(groups, n),
                      slot(groups, n)],
            out_specs=[state_spec, slot(p, h)]),
        out_shape=(jax.ShapeDtypeStruct(state_shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, p, h), jnp.float32)),
        # Operands: the layer, `active`, the state, ...: the state goes
        # back to its own buffer.
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',)),
        interpret=interpret,
        name='ssm_decode_step',
    )


def _ssm_decode_reference(state, layer, dtx, decay, b, c, active):
    """`ssm_decode_step` in `jnp`: the same float32 arithmetic."""
    heads = state.shape[2]
    rep = heads // b.shape[1]
    s = jax.lax.dynamic_index_in_dim(state, layer, axis=0, keepdims=False)
    b_h = jnp.repeat(b, rep, axis=1)                    # [B, H, N]
    c_h = jnp.repeat(c, rep, axis=1)
    new = (decay[:, 0, :, None, None] * s +
           jnp.swapaxes(dtx, 1, 2)[..., None] * b_h[:, :, None, :])
    new = jnp.where((active > 0)[:, None, None, None], new, s)
    y = jnp.sum(new * c_h[:, :, None, :], axis=-1)      # [B, H, P]
    state = jax.lax.dynamic_update_index_in_dim(state, new, layer, axis=0)
    return state, jnp.swapaxes(y, 1, 2)


def ssm_decode_step(state, layer, dtx, decay, b, c, active):
    """Layer `layer` of the state [M, B, H, P, N] (float32) advanced by
    one token a slot -> (the state, in the buffer it came in where the
    kernel runs, y [B, P, H] float32).

    dtx [B, P, H] is dt * x (a head's channels down the sublanes),
    decay [B, 1, H] exp(dt * A), b and c [B, G, N] the groups' B and C
    (head h reads group h // (H / G)), active [B] int32 (0: the slot's
    state stays as it was; its y is not read).  Every operand float32
    but `active`."""
    layer = jnp.asarray(layer, jnp.int32)
    active = jnp.asarray(active, jnp.int32)
    args = (dtx.astype(jnp.float32), decay.astype(jnp.float32),
            b.astype(jnp.float32), c.astype(jnp.float32))
    if not _use_pallas():
        return _ssm_decode_reference(state, layer, *args, active)
    return _decode_call(tuple(state.shape), b.shape[1], interpret_mode())(
        layer.reshape(1), active, state, *args)
