"""Pipeline parallelism: GPipe microbatch schedule over the 'pipeline'
mesh axis with collective-permute stage handoff, composing with
TP/FSDP/DP (GSPMD) and SP (in-body ring attention).

TPU-first design (no reference equivalent — SkyPilot's parallelism ends
at gang scheduling, SURVEY.md §2.3; the 'pipeline' axis here is meant to
span DCN across slices, parallel/mesh.py DCN_AXES):

- The decoder stack is split into `n_stages` contiguous stages; stage
  parameters are stacked on a leading 'stage' axis sharded over the
  'pipeline' mesh axis; WITHIN a stage each leaf keeps its TP/FSDP
  placement from LOGICAL_AXIS_RULES (stage_param_shardings).
- The schedule runs under a PARTIAL-MANUAL `jax.shard_map`: manual only
  over 'pipeline' (and 'sequence' when SP is on).  Every other mesh
  axis stays in GSPMD auto mode, so the per-stage compute is
  tensor/fsdp/data-partitioned by the compiler exactly as in the
  non-pipelined path — that is how PP composes with TP/FSDP without
  hand-written collectives.
- Inside the manual region every device runs the same compiled tick
  `num_microbatches + n_stages - 1` times (a `lax.scan`, static trip
  count): apply my stage to the resident activation, then `ppermute`
  the result one hop down the pipeline.  XLA overlaps the permute DMA
  (DCN) with the next tick's matmuls.
- SP x PP: with a non-trivial 'sequence' axis the region is also manual
  over 'sequence'; each stage's attention rings over ICI via
  `_ring_attention_sharded` (transformer.Attention(sequence_axis=...))
  while activations stay sequence-sharded end to end — the DCN-PP x
  ICI-SP layout for long-context multi-slice training.
- Backward is autodiff through the scan+ppermute (ppermute transposes
  to the reverse hop), reproducing the GPipe backward schedule;
  `jax.checkpoint` on the stage body keeps activation memory at
  O(microbatches) stage boundaries.
- Embedding and the LM head run outside the shard_map under plain GSPMD
  (batch/sequence-sharded); the final-stage activations are returned to
  every pipeline rank with a masked psum.

Correctness contract (tests/unit/test_pipeline.py): the pipelined loss
and grads match the non-pipelined `models.train` path on the same
params at equal global batch — including pipeline x tensor and
pipeline x sequence meshes.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

P = jax.sharding.PartitionSpec


def split_stage_params(params: Dict[str, Any], n_stages: int) -> Dict[str, Any]:
    """Reshape the scanned-layer params [L, ...] -> [S, L//S, ...].

    `params` is the Transformer param tree with scan_layers=True, i.e.
    params['layers']['layer'] leaves carry a leading n_layers axis.
    """
    layers = params['layers']['layer']

    def _split(leaf):
        n_layers = leaf.shape[0]
        if n_layers % n_stages:
            raise ValueError(
                f'n_layers={n_layers} not divisible by n_stages={n_stages}')
        return leaf.reshape(n_stages, n_layers // n_stages, *leaf.shape[1:])

    out = dict(params)
    out['layers'] = {'layer': jax.tree.map(_split, layers)}
    return out


def merge_stage_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """Inverse of split_stage_params."""
    layers = params['layers']['layer']
    out = dict(params)
    out['layers'] = {'layer': jax.tree.map(
        lambda leaf: leaf.reshape(-1, *leaf.shape[2:]), layers)}
    return out


def stage_param_shardings(cfg, mesh, n_stages: int, *,
                          batch: int = 1, seq: int = 8):
    """NamedShardings for STAGE-SPLIT params with full composition:
    leading stage axis over 'pipeline'; within a stage every leaf keeps
    its TP/FSDP spec from the model's logical annotations.

    Derived from the model's own partition metadata (not hand-listed),
    so new layers/params inherit correct placement automatically.
    """
    import flax.linen as nn  # pylint: disable=import-outside-toplevel

    from skypilot_tpu.models.transformer import Transformer  # pylint: disable=import-outside-toplevel
    from skypilot_tpu.parallel.sharding import LOGICAL_AXIS_RULES  # pylint: disable=import-outside-toplevel

    if n_stages != mesh.shape.get('pipeline', 1):
        raise ValueError(
            f'n_stages={n_stages} != pipeline axis size '
            f'{mesh.shape.get("pipeline", 1)}')
    if cfg.n_layers % n_stages:
        raise ValueError(f'n_layers={cfg.n_layers} not divisible by '
                         f'n_stages={n_stages}')
    model = Transformer(cfg)
    tokens = jnp.zeros((batch, seq), jnp.int32)
    abstract = jax.eval_shape(
        lambda rng: model.init(rng, tokens)['params'],
        jax.random.PRNGKey(0))
    logical = nn.get_partition_spec(abstract)
    # Scanned-layer leaves carry logical ('layers', *rest); after the
    # stage split they are [S, L/S, *rest] == ('stage', 'layers', *rest).
    logical = dict(logical)
    logical['layers'] = jax.tree.map(
        lambda spec: P('stage', *spec),
        logical['layers'],
        is_leaf=lambda x: isinstance(x, P))
    return nn.logical_to_mesh_sharding(logical, mesh, LOGICAL_AXIS_RULES)


# Backwards-compatible alias (round-2 name).
def pipeline_param_shardings(params: Dict[str, Any], mesh):
    """DEPRECATED shape-only fallback: stage axis over 'pipeline',
    everything else replicated.  Prefer stage_param_shardings (full
    TP/FSDP composition)."""
    stage = jax.sharding.NamedSharding(mesh, P('pipeline'))
    repl = jax.sharding.NamedSharding(mesh, P())
    return {
        name: (jax.tree.map(lambda _: stage, sub) if name == 'layers'
               else jax.tree.map(lambda _: repl, sub))
        for name, sub in params.items()
    }


def _pipeline_body(stage_params, x_mb, *, cfg, mesh, n_stages: int,
                   remat: bool, sequence_axis: Optional[str]):
    """Per-device GPipe schedule (runs under partial-manual shard_map).

    stage_params leaves: [1, layers_per_stage, ...] on the pipeline
    axis (other dims auto-partitioned by GSPMD); x_mb: [M, mb, s, d]
    microbatched embeddings (sequence-sharded when SP is on; only stage
    0 reads it).  Returns [M, mb, s, d] final-stage activations, valid
    on every pipeline rank (masked psum).
    """
    from skypilot_tpu.models.transformer import DecoderLayer  # pylint: disable=import-outside-toplevel

    sp = jax.tree.map(lambda a: a[0], stage_params)
    stage_idx = jax.lax.axis_index('pipeline')
    num_mb, _, seq, _ = x_mb.shape
    if sequence_axis is not None:
        # Global positions for RoPE: this device holds the
        # axis_index-th contiguous sequence chunk.
        positions = (jax.lax.axis_index(sequence_axis) * seq +
                     jnp.arange(seq))
    else:
        positions = jnp.arange(seq)
    # The mesh lets the flash kernel nest its own shard_map over the
    # axes this region leaves to GSPMD (a Mosaic kernel cannot sit in a
    # partly-manual region).
    layer = DecoderLayer(cfg, mesh, sequence_axis=sequence_axis)

    def stage_fn(h):
        def body(carry, lp):
            return layer.apply({'params': lp}, carry, positions), None
        out, _ = jax.lax.scan(body, h, sp)
        return out

    if remat:
        stage_fn = jax.checkpoint(stage_fn)

    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        buf, outputs = carry
        # Stage 0 feeds microbatch t (clipped in the drain phase — the
        # result is garbage there and never written); others consume the
        # activation ppermuted from the previous stage.
        inp = jnp.where(stage_idx == 0,
                        jax.lax.dynamic_index_in_dim(
                            x_mb, jnp.clip(t, 0, num_mb - 1), 0,
                            keepdims=False),
                        buf)
        out = stage_fn(inp)
        # The last stage finishes microbatch t-(n_stages-1) at tick t.
        out_idx = jnp.clip(t - (n_stages - 1), 0, num_mb - 1)
        valid = t >= (n_stages - 1)
        upd = jnp.where(valid, out,
                        jax.lax.dynamic_index_in_dim(outputs, out_idx, 0,
                                                     keepdims=False))
        outputs = jax.lax.dynamic_update_index_in_dim(outputs, upd,
                                                      out_idx, 0)
        buf = jax.lax.ppermute(out, 'pipeline', perm)
        return (buf, outputs), None

    ticks = jnp.arange(num_mb + n_stages - 1)
    carry0 = (jnp.zeros_like(x_mb[0]), jnp.zeros_like(x_mb))
    (_, outputs), _ = jax.lax.scan(tick, carry0, ticks)
    # Only the last stage holds real outputs; broadcast around the ring.
    outputs = jax.lax.psum(
        jnp.where(stage_idx == n_stages - 1, outputs,
                  jnp.zeros_like(outputs)),
        'pipeline')
    return outputs


def pipeline_forward(cfg, params, inputs, *, mesh,
                     num_microbatches: int):
    """Pipelined Transformer forward: tokens [b, s] -> logits [b, s, V].

    `params` must be stage-split (split_stage_params).  Mathematically
    identical to models.transformer.Transformer on the merged params.
    Manual axes: 'pipeline' (+ 'sequence' when SP is on); every other
    mesh axis (tensor/fsdp/data) stays under GSPMD auto partitioning,
    composing PP with TP/FSDP without hand-written collectives.
    """
    n_stages = mesh.shape['pipeline']
    seq_parallel = mesh.shape.get('sequence', 1) > 1
    sequence_axis = 'sequence' if seq_parallel else None
    b, seq = inputs.shape
    if b % num_microbatches:
        raise ValueError(f'batch {b} not divisible by '
                         f'num_microbatches {num_microbatches}')
    if seq_parallel and seq % mesh.shape['sequence']:
        raise ValueError(f'seq {seq} not divisible by the sequence axis '
                         f'size {mesh.shape["sequence"]}')

    # Embedding outside the pipeline (plain GSPMD, batch-sharded).
    emb = params['embed']['embedding']
    x = jnp.take(emb, inputs, axis=0).astype(cfg.dtype)
    if cfg.scale_embeddings:  # Gemma
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    mb = b // num_microbatches
    x_mb = x.reshape(num_microbatches, mb, seq, cfg.d_model)

    manual_axes = {'pipeline'} | ({'sequence'} if seq_parallel else set())
    act_spec = P(None, None, sequence_axis, None)
    body = functools.partial(_pipeline_body, cfg=cfg, mesh=mesh,
                             n_stages=n_stages,
                             remat=cfg.remat,
                             sequence_axis=sequence_axis)
    out_mb = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P('pipeline'), act_spec),
        out_specs=act_spec,
        axis_names=manual_axes,
        check_vma=False,
    )(params['layers']['layer'], x_mb)

    from skypilot_tpu.models.transformer import RMSNorm  # pylint: disable=import-outside-toplevel
    x = out_mb.reshape(b, seq, cfg.d_model)
    x = RMSNorm(cfg.norm_eps, cfg.norm_scale_plus_one).apply(
        {'params': params['final_norm']}, x)
    from skypilot_tpu.models import heads  # pylint: disable=import-outside-toplevel
    return heads.unembed(x, params, cfg)


def pipeline_loss_fn(cfg, params, tokens, *, mesh, num_microbatches: int):
    """Next-token CE on a pipelined forward. tokens [b, s+1]."""
    from skypilot_tpu.models.train import loss_fn  # pylint: disable=import-outside-toplevel
    logits = pipeline_forward(cfg, params, tokens[:, :-1], mesh=mesh,
                              num_microbatches=num_microbatches)
    return loss_fn(logits, tokens[:, 1:])


# ------------------------------------------------------- TrainState path


def create_pipeline_train_state(cfg, tcfg=None, *, mesh,
                                batch_size: int, seq_len: int,
                                rng: Optional[jax.Array] = None
                                ) -> Tuple[Any, Any]:
    """TrainState with STAGE-SPLIT, fully-composed-sharded params.

    Mirrors models.train.create_train_state: returns (state,
    state_shardings); params/opt-state land directly on the mesh with
    stage x TP/FSDP placement (the flagship never materialises
    replicated).
    """
    from skypilot_tpu.models.train import TrainConfig  # pylint: disable=import-outside-toplevel
    from skypilot_tpu.models.train import TrainState  # pylint: disable=import-outside-toplevel
    from skypilot_tpu.models.train import make_optimizer  # pylint: disable=import-outside-toplevel
    from skypilot_tpu.models.transformer import Transformer  # pylint: disable=import-outside-toplevel
    import flax.linen as nn  # pylint: disable=import-outside-toplevel

    tcfg = tcfg or TrainConfig()
    if not cfg.scan_layers:
        raise ValueError('pipeline training requires scan_layers=True '
                         '(stacked layer params)')
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    n_stages = mesh.shape['pipeline']
    model = Transformer(cfg)
    init_tokens = jnp.zeros((batch_size, seq_len), jnp.int32)
    tx = make_optimizer(tcfg)

    param_shardings = stage_param_shardings(cfg, mesh, n_stages,
                                            batch=batch_size, seq=seq_len)

    def init_fn(rng):
        params = nn.meta.unbox(model.init(rng, init_tokens)['params'])
        params = split_stage_params(params, n_stages)
        return TrainState.create(apply_fn=None, params=params, tx=tx)

    abstract = jax.eval_shape(init_fn, rng)
    repl = jax.sharding.NamedSharding(mesh, P())
    params_struct = jax.tree.structure(abstract.params)

    def _is_param_tree(sub) -> bool:
        try:
            return jax.tree.structure(sub) == params_struct
        except Exception:  # pylint: disable=broad-except
            return False

    # Optimizer moments (adamw mu/nu) are param-tree-shaped subtrees:
    # give them the param placement; scalar counts stay replicated.
    opt_shardings = jax.tree.map(
        lambda sub: (param_shardings if _is_param_tree(sub)
                     else jax.tree.map(lambda _: repl, sub)),
        abstract.opt_state, is_leaf=_is_param_tree)
    state_shardings = abstract.replace(step=repl, params=param_shardings,
                                       opt_state=opt_shardings)

    with mesh:
        state = jax.jit(init_fn, out_shardings=state_shardings)(rng)
    return state, state_shardings


def pipeline_train_step(cfg, mesh, num_microbatches: int):
    """Returns a jit-able (state, batch) -> (state, metrics) step using
    the pipelined forward — the TrainState-integrated twin of
    models.train.train_step."""
    import optax  # pylint: disable=import-outside-toplevel

    def step(state, batch):
        tokens = batch['tokens']

        def compute_loss(params):
            return pipeline_loss_fn(cfg, params, tokens, mesh=mesh,
                                    num_microbatches=num_microbatches)

        loss, grads = jax.value_and_grad(compute_loss)(state.params)
        new_state = state.apply_gradients(grads=grads)
        return new_state, {'loss': loss,
                           'grad_norm': optax.global_norm(grads)}

    return step


def run_pipeline_train_step(cfg, tcfg, mesh, *, batch: int, seq: int,
                            num_microbatches: int,
                            rng: Optional[jax.Array] = None) -> float:
    """Init a stage-sharded TrainState on `mesh` and run ONE pipelined
    optimizer step; returns the loss.  Used by the multichip dryrun and
    the PP tests."""
    state, state_shardings = create_pipeline_train_state(
        cfg, tcfg, mesh=mesh, batch_size=batch, seq_len=seq, rng=rng)
    tokens = jax.random.randint(
        jax.random.fold_in(rng if rng is not None else jax.random.PRNGKey(0),
                           1),
        (batch, seq + 1), 0, cfg.vocab_size, dtype=jnp.int32)
    step = jax.jit(pipeline_train_step(cfg, mesh, num_microbatches),
                   in_shardings=(state_shardings, None),
                   out_shardings=(state_shardings, None),
                   donate_argnums=(0,))
    with mesh:
        state, metrics = step(state, {'tokens': tokens})
    return float(jax.device_get(metrics['loss']))
