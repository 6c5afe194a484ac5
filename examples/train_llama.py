"""Flagship workload: Llama-style finetune using the framework's
compute layer end-to-end.

Run under `skytpu launch examples/llama_finetune.yaml` — the gang exec
layer exports the job contract (SKYTPU_HOST_RANK / COORDINATOR /
CHECKPOINT_DIR), this script consumes it:

- jax.distributed bootstrap from env (parallel.initialize_from_env)
- [dcn, ici] mesh over all slices (parallel.build_mesh)
- sharded train state + pjit train step (models.train)
- auto-resume from the checkpoint contract (data.checkpoints)
- per-step timestamps for `skytpu bench` (callbacks)
"""
from __future__ import annotations

import argparse
import json
import time


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument('--model', default='tiny',
                        help="tiny | small | llama3-8b | llama3-70b | "
                             "'auto' (shape from --init-from's "
                             'model_config.json)')
    parser.add_argument('--init-from', default=None,
                        help='Converted checkpoint dir '
                             '(models/import_weights.py) to START the '
                             'finetune from; auto-resume from the '
                             'checkpoint contract still wins after a '
                             'preemption.')
    parser.add_argument('--steps', type=int, default=20)
    parser.add_argument('--batch-size', type=int, default=8)
    parser.add_argument('--seq-len', type=int, default=512)
    parser.add_argument('--fused-ce', action='store_true',
                        help='Fused linear+CE loss (models/losses.py): '
                             'the [b,s,V] logits tensor never '
                             'materializes — the big win for '
                             'Llama-class vocabs.')
    parser.add_argument('--accum-steps', type=int, default=1,
                        help='Microbatch gradient accumulation: '
                             'effective batch = batch-size, computed '
                             'in accum-steps scan slices of '
                             'batch-size/accum-steps rows each '
                             '(same loss trajectory, lower peak HBM).')
    parser.add_argument('--vocab-chunk', type=int, default=8192,
                        help='Vocab chunk width for the fused CE.')
    parser.add_argument('--fsdp', type=int, default=1)
    parser.add_argument('--tensor', type=int, default=1)
    parser.add_argument('--sequence', type=int, default=1)
    parser.add_argument('--sp-mode', default='ring',
                        choices=['ring', 'ulysses'],
                        help='Sequence-parallel strategy when '
                             '--sequence > 1 (ops/ring_attention vs '
                             'ops/ulysses_attention).')
    parser.add_argument('--data', default=None,
                        help='SKYTOK1 token file (data.loader); random '
                             'tokens when omitted.')
    parser.add_argument('--preflight', action='store_true',
                        help='Probe ICI/DCN collectives before training '
                             '(fail fast on a sick fabric).')
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from skypilot_tpu import compile_cache
    from skypilot_tpu import parallel
    from skypilot_tpu.callbacks import base as callbacks
    from skypilot_tpu.data import checkpoints
    from skypilot_tpu.models import configs
    from skypilot_tpu.models.train import TrainConfig
    from skypilot_tpu.models.train import create_train_state
    from skypilot_tpu.models.train import jit_train_step
    from skypilot_tpu.parallel.sharding import token_batch_sharding

    compile_cache.enable()
    parallel.initialize_from_env()
    mesh = parallel.build_mesh(
        parallel.MeshConfig(data=-1, fsdp=args.fsdp,
                            sequence=args.sequence, tensor=args.tensor),
        num_slices=parallel.distributed.num_slices())
    dev = jax.devices()[0]
    print(f'mesh: {dict(mesh.shape)} over {jax.device_count()} devices '
          f'(platform={dev.platform} device_kind={dev.device_kind!r} '
          f'jax={jax.__version__})')

    if args.preflight:
        from skypilot_tpu.parallel import preflight
        preflight.check_collectives(mesh)
        print('collective preflight: healthy')

    if args.model == 'auto':
        from skypilot_tpu.models import import_weights
        if not args.init_from:
            raise SystemExit('--model auto needs --init-from')
        cfg = import_weights.load_model_config(args.init_from)
        if cfg is None:
            raise SystemExit(
                f'No model_config.json under {args.init_from}')
        cfg = cfg.replace(sequence_parallel=args.sp_mode)
    else:
        cfg = configs.get_config(args.model,
                                 sequence_parallel=args.sp_mode)
    tcfg = TrainConfig(fused_ce=args.fused_ce,
                       accum_steps=args.accum_steps,
                       vocab_chunk=args.vocab_chunk)
    state, shardings = create_train_state(
        cfg, tcfg, mesh=mesh, batch_size=args.batch_size,
        seq_len=args.seq_len)
    step_fn = jit_train_step(shardings, token_batch_sharding(mesh), tcfg)

    start_step = 0
    mgr = None
    if checkpoints.checkpoint_dir():
        # Async saves: the bucket write runs on a background writer
        # (bounded in-flight, retry-with-backoff), so the checkpoint
        # interval stops taxing step time (docs/training.md, ISSUE 6).
        mgr = checkpoints.AsyncCheckpointManager(save_interval_steps=10)
        state, start_step = mgr.restore_or_init(state)
        print(f'resuming from step {start_step}')
    if start_step == 0 and args.init_from:
        # Real-weights finetune start (Llama-3-8B from a converted HF
        # checkpoint — the BASELINE.md north-star workload); a resumed
        # preemption recovery above takes precedence.
        from skypilot_tpu.models.train import load_pretrained_params
        state = load_pretrained_params(state, args.init_from)
        print(f'initialized params from {args.init_from}')

    cb = callbacks.init(total_steps=args.steps,
                        tokens_per_step=args.batch_size * args.seq_len)
    if args.data:
        # Real data path: host-sharded resumable batches + the
        # double-buffered device prefetcher (data/prefetch.py) — step
        # N+1's host->device transfer overlaps step N's compute
        # (resume continues at start_step deterministically).
        from skypilot_tpu.data import loader as loader_lib
        from skypilot_tpu.data import prefetch as prefetch_lib
        from skypilot_tpu.parallel import distributed
        batches = loader_lib.HostShardedBatches(
            loader_lib.TokenDataset(args.data),
            global_batch=args.batch_size * distributed.num_hosts(),
            seq_len=args.seq_len,
            host_rank=distributed.host_rank(),
            num_hosts=distributed.num_hosts())
        batch_iter = prefetch_lib.prefetch_to_device(
            batches.batches(start_step=start_step),
            sharding=token_batch_sharding(mesh))
    else:
        key = jax.random.PRNGKey(start_step)
        tokens = jax.random.randint(
            key, (args.batch_size, args.seq_len + 1), 0, cfg.vocab_size,
            dtype=jnp.int32)
        batch_iter = iter(lambda: {'tokens': tokens}, None)

    from skypilot_tpu.models.train import compiled_peak_memory
    compiled_fn = None
    for step in range(start_step, args.steps):
        batch = next(batch_iter)
        if compiled_fn is None:
            # AOT-compile on the first real batch (same shapes every
            # step) so the compiled step's peak-memory estimate feeds
            # the telemetry (skytpu_train_peak_memory_bytes +
            # summary.json) before the run is underway.
            compiled_fn = step_fn.lower(state, batch).compile()
            peak = compiled_peak_memory(compiled_fn)
            if peak is not None:
                print(f'compiled step peak temp memory: '
                      f'{peak / 1e9:.2f} GB')
        with cb.step():
            state, metrics = compiled_fn(state, batch)
            jax.block_until_ready(metrics['loss'])
        if step % 10 == 0 or step == args.steps - 1:
            print(f'step {step}: loss={float(metrics["loss"]):.4f} '
                  f'grad_norm={float(metrics["grad_norm"]):.3f}',
                  flush=True)
        if mgr is not None:
            mgr.save(step, state)
    if mgr is not None:
        mgr.close()  # wait-on-exit: drain in-flight saves
    cb.flush()
    # Where the state ended up: every device should hold its share, not
    # device 0 everything (None where the backend reports no stats).
    stats = {d.id: d.memory_stats() or {} for d in jax.local_devices()}
    print('device memory:', json.dumps([
        {'id': i, 'bytes_in_use': s.get('bytes_in_use'),
         'peak_bytes_in_use': s.get('peak_bytes_in_use')}
        for i, s in stats.items()]))
    print('done', time.strftime('%X'))


if __name__ == '__main__':
    main()
