"""pjit-able training step for the model family.

Everything is sharding-annotated, jit-compiled once, and static-shaped:
params are placed by the logical-axis rules (parallel/sharding.py), the
batch rides ('data','fsdp'), and the optimizer is optax adamw.  This is
the "JAX-native job contract" end of the framework (SURVEY.md §7 build
plan item (c)) — what managed jobs checkpoint/resume and `bench`
measures.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from flax.training import train_state

from skypilot_tpu.models.configs import ModelConfig
from skypilot_tpu.models.transformer import Transformer
from skypilot_tpu.parallel.sharding import LOGICAL_AXIS_RULES


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    weight_decay: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    grad_clip: float = 1.0
    # --- training hot path (docs/training.md) ---
    # Fused linear+CE (models/losses.py): the forward returns final
    # hidden states + the lm-head kernel and the loss computes vocab
    # chunks on the fly, so the [b,s,V] logits tensor never exists.
    # Exact (online logsumexp), not an approximation.
    fused_ce: bool = False
    # Vocab chunk width for the streaming/fused CE.
    vocab_chunk: int = 8192
    # lax.scan microbatch gradient accumulation: the batch is split
    # into accum_steps microbatches whose SUMMED NLL gradients are
    # accumulated and normalized by the full-batch denominator, so
    # accum_steps=k matches one big batch (same loss trajectory)
    # while peak activation memory stays at one microbatch.
    accum_steps: int = 1


class TrainState(train_state.TrainState):
    pass


def make_optimizer(tcfg: TrainConfig) -> optax.GradientTransformation:
    return optax.chain(
        optax.clip_by_global_norm(tcfg.grad_clip),
        optax.adamw(tcfg.learning_rate, b1=tcfg.b1, b2=tcfg.b2,
                    weight_decay=tcfg.weight_decay),
    )


def loss_fn(logits, targets, mask=None, reduction: str = 'mean'):
    """Next-token cross entropy. logits [b,s,V]; targets [b,s].

    The reference implementation (full f32 log-softmax) — the fused
    hot path in models/losses.py is pinned against it.  reduction
    'sum' returns the raw summed NLL (microbatch accumulation divides
    by the full-batch denominator itself).
    """
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is not None:
        ll = ll * mask
    if reduction == 'sum':
        return -jnp.sum(ll)
    if mask is None:
        return -jnp.mean(ll)
    return -jnp.sum(ll) / jnp.maximum(jnp.sum(mask), 1)


def _init_fn(cfg: ModelConfig, tcfg: TrainConfig, mesh,
             batch_size: int, seq_len: int):
    model = Transformer(cfg, mesh)
    tokens = jnp.zeros((batch_size, seq_len), jnp.int32)
    tx = make_optimizer(tcfg)

    def init_fn(rng):
        params = model.init(rng, tokens)['params']
        return TrainState.create(apply_fn=model.apply, params=params, tx=tx)

    return init_fn


def abstract_train_state(cfg: ModelConfig,
                         tcfg: Optional[TrainConfig] = None,
                         *,
                         mesh,
                         batch_size: int = 8,
                         seq_len: Optional[int] = None) -> Tuple[Any, Any]:
    """Returns (abstract_state, state_shardings) WITHOUT materializing
    any params: the eval_shape'd TrainState plus its NamedShardings on
    `mesh`.

    The elastic-recovery entry point: after a gang resize the new mesh's
    shardings come from here, and checkpoints.restore_sharded streams
    the checkpoint straight onto them — no full-size init, no one-chip
    materialization (the restore-side counterpart of create_train_state
    never allocating the 8B flagship unsharded).
    """
    tcfg = tcfg or TrainConfig()
    seq_len = seq_len or min(cfg.max_seq_len, 2048)
    init_fn = _init_fn(cfg, tcfg, mesh, batch_size, seq_len)
    with mesh, nn.logical_axis_rules(LOGICAL_AXIS_RULES):
        abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
        specs = nn.get_partition_spec(abstract)
        shardings = nn.logical_to_mesh_sharding(specs, mesh,
                                                LOGICAL_AXIS_RULES)
    return abstract, shardings


def create_train_state(cfg: ModelConfig,
                       tcfg: Optional[TrainConfig] = None,
                       *,
                       mesh=None,
                       rng=None,
                       batch_size: int = 8,
                       seq_len: Optional[int] = None) -> Tuple[Any, Any]:
    """Returns (state, state_shardings); params initialized on-mesh.

    With a mesh, init runs under jit with NamedSharding outputs so the
    8B flagship never materialises unsharded on one device.
    """
    tcfg = tcfg or TrainConfig()
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    seq_len = seq_len or min(cfg.max_seq_len, 2048)
    init_fn = _init_fn(cfg, tcfg, mesh, batch_size, seq_len)

    if mesh is None:
        return init_fn(rng), None

    # NOTE: shardings must come from THIS init_fn (not a fresh
    # abstract_train_state call): TrainState's treedef carries
    # apply_fn/tx as static metadata, so trees from two model
    # instances never match under jit's out_shardings check.
    with mesh, nn.logical_axis_rules(LOGICAL_AXIS_RULES):
        abstract = jax.eval_shape(init_fn, rng)
        specs = nn.get_partition_spec(abstract)
        shardings = nn.logical_to_mesh_sharding(specs, mesh,
                                                LOGICAL_AXIS_RULES)
        state = jax.jit(init_fn, out_shardings=shardings)(rng)
    return state, shardings


def load_pretrained_params(state: TrainState, directory: str) -> TrainState:
    """Start a finetune from a CONVERTED checkpoint (import_weights) or
    any params-bearing checkpoint: restores the params subtree and
    places each leaf on the existing state's sharding/dtype (optimizer
    moments stay fresh — this is init, not resume).

    Leaf order pairs the restored plain tree with the state's boxed
    params (boxing preserves traversal order, same invariant
    checkpoints.restore_params relies on); every leaf is shape-checked.
    Each leaf is restored straight onto its state leaf's sharding, so
    on a mesh every device reads its own shard and the unsharded tree
    never sits on one chip.  Peak memory note: the random-init params
    exist until replaced.
    """
    from skypilot_tpu.data import checkpoints  # pylint: disable=import-outside-toplevel
    old_leaves, treedef = jax.tree_util.tree_flatten(state.params)
    plain = checkpoints.restore_params(
        directory, shardings=[leaf.sharding for leaf in old_leaves])
    if plain is None:
        raise FileNotFoundError(f'No checkpoint under {directory}')
    new_leaves = jax.tree_util.tree_leaves(plain)
    placed = []
    for old, new in zip(old_leaves, new_leaves):
        if tuple(old.shape) != tuple(new.shape):
            raise ValueError(f'Shape mismatch: checkpoint {new.shape} '
                             f'vs model {old.shape}')
        placed.append(new.astype(old.dtype))
    return state.replace(
        params=jax.tree_util.tree_unflatten(treedef, placed))


def _microbatch_nll(state, params, inputs, targets, mask,
                    tcfg: TrainConfig):
    """Summed (unnormalized) NLL of one microbatch — the unit both the
    single-shot and the accumulated path build on."""
    from skypilot_tpu.models import losses  # pylint: disable=import-outside-toplevel
    if tcfg.fused_ce:
        hidden, kernel = state.apply_fn({'params': params}, inputs,
                                        return_hidden=True)
        return losses.fused_linear_cross_entropy(
            hidden, kernel, targets, mask,
            vocab_chunk=tcfg.vocab_chunk, reduction='sum')
    logits = state.apply_fn({'params': params}, inputs)
    return loss_fn(logits, targets, mask, reduction='sum')


def train_step(state: TrainState, batch,
               tcfg: Optional[TrainConfig] = None):
    """One optimizer step. batch = {'tokens': [b,s+1] int32} or
    {'inputs','targets'} (+ optional 'mask').  Call under jit (see
    jit_train_step) — placement comes from the jit in/out shardings,
    not from here.

    With a TrainConfig, the hot-path knobs apply: fused_ce routes the
    loss through models/losses.py (the [b,s,V] logits tensor never
    materializes) and accum_steps>1 runs lax.scan microbatch gradient
    accumulation — summed-NLL grads accumulate across microbatches and
    are normalized by the FULL batch's denominator, so the update is
    equivalent to one big batch while peak activation memory stays at
    one microbatch.
    """
    if 'tokens' in batch:
        inputs = batch['tokens'][:, :-1]
        targets = batch['tokens'][:, 1:]
    else:
        inputs, targets = batch['inputs'], batch['targets']
    mask = batch.get('mask')

    if tcfg is None or (not tcfg.fused_ce and tcfg.accum_steps <= 1):
        def compute_loss(params):
            logits = state.apply_fn({'params': params}, inputs)
            return loss_fn(logits, targets, mask)

        loss, grads = jax.value_and_grad(compute_loss)(state.params)
    else:
        if mask is None:
            denom = jnp.asarray(float(targets.size), jnp.float32)
        else:
            denom = jnp.maximum(jnp.sum(mask), 1)
        accum = tcfg.accum_steps
        if accum <= 1:
            nll, grads = jax.value_and_grad(
                lambda p: _microbatch_nll(state, p, inputs, targets,
                                          mask, tcfg))(state.params)
        else:
            b = inputs.shape[0]
            if b % accum:
                raise ValueError(
                    f'batch size {b} not divisible by accum_steps '
                    f'{accum}')
            split = lambda a: (None if a is None else
                               a.reshape(accum, b // accum, *a.shape[1:]))
            micro = {'inputs': split(inputs), 'targets': split(targets)}
            if mask is not None:
                micro['mask'] = split(mask)

            def body(carry, mb):
                acc_nll, acc_grads = carry
                nll, grads = jax.value_and_grad(
                    lambda p: _microbatch_nll(
                        state, p, mb['inputs'], mb['targets'],
                        mb.get('mask'), tcfg))(state.params)
                acc_grads = jax.tree_util.tree_map(jnp.add, acc_grads,
                                                   grads)
                return (acc_nll + nll, acc_grads), None

            zeros = jax.tree_util.tree_map(jnp.zeros_like, state.params)
            (nll, grads), _ = jax.lax.scan(body, (jnp.zeros((),
                                                            jnp.float32),
                                                  zeros), micro)
        loss = nll / denom
        grads = jax.tree_util.tree_map(lambda g: g / denom.astype(g.dtype),
                                       grads)

    new_state = state.apply_gradients(grads=grads)
    metrics = {'loss': loss,
               'grad_norm': optax.global_norm(grads)}
    return new_state, metrics


def compiled_peak_memory(compiled) -> Optional[int]:
    """Peak temp allocation (bytes) of an AOT-compiled step, from XLA
    CompiledMemoryStats (None when the backend hides it).  Feeds the
    training telemetry (callbacks/base.record_peak_memory →
    skytpu_train_peak_memory_bytes gauge + summary.json), so the
    memory headroom of a run is a scrapeable number, not a one-off
    bench.py printout."""
    try:
        stats = compiled.memory_analysis()
        peak = int(stats.temp_size_in_bytes)
    except Exception:  # pylint: disable=broad-except
        return None
    from skypilot_tpu.callbacks import base as callbacks  # pylint: disable=import-outside-toplevel
    callbacks.record_peak_memory(peak)
    return peak


def jit_train_step(state_shardings, batch_sharding,
                   tcfg: Optional[TrainConfig] = None):
    """jit train_step with explicit in/out shardings (the NamedShardings
    carry their mesh); tcfg threads the hot-path knobs (fused CE,
    microbatch accumulation) into the compiled step."""

    def _step(state, batch):
        with nn.logical_axis_rules(LOGICAL_AXIS_RULES):
            return train_step(state, batch, tcfg)

    return jax.jit(
        _step,
        in_shardings=(state_shardings, batch_sharding),
        out_shardings=(state_shardings, None),
        donate_argnums=(0,),
    )
