"""Async-checkpoint overhead bench: pins the <10% step-time claim.

Three configurations over the same jitted train step:

    none      checkpointing disabled (the baseline step time)
    blocking  a full synchronous save every `--save-interval` steps —
              what every save cost before the async manager
    async     AsyncCheckpointManager: snapshot on the step thread,
              durable write on the background writer

The write itself is modeled as a SLOW BUCKET: a chaos `delay` fault on
the ``checkpoint.save`` site adds `--bucket-latency` seconds of
(GIL-releasing) I/O wait to every write, the dominant cost of real
checkpoint-to-GCS saves.  This keeps the bench honest on small CI
machines: serialization CPU is measured as-is (it contends for cores
either way), while the network wait — the part async checkpointing
actually removes from the step path — is explicit and tunable.

Reports per-mode avg/max step seconds and overhead vs the baseline.
The acceptance bar (BENCH_ckpt.json; asserted by
tests/unit/test_bench_checkpoint.py via --smoke) is async overhead
< 10% of step time while the blocking saves cost a large multiple.

    python bench_checkpoint.py [--steps 16] [--save-interval 4]
                               [--bucket-latency 1.0]
                               [--out BENCH_ckpt.json] [--smoke]
"""
from __future__ import annotations

import argparse
import json
import shutil
import tempfile
import time


def _avg_step_seconds(step_fn, state, batch, steps, on_step=None):
    import jax
    timings = []
    for step in range(steps):
        t0 = time.monotonic()
        state, metrics = step_fn(state, batch)
        jax.block_until_ready(metrics['loss'])
        if on_step is not None:
            on_step(step, state)
        timings.append(time.monotonic() - t0)
    return state, sum(timings) / len(timings), max(timings)


def run_bench(steps: int = 16, save_interval: int = 4,
              batch_size: int = 16, seq_len: int = 256,
              bucket_latency_s: float = 1.0) -> dict:
    import jax
    import jax.numpy as jnp

    from skypilot_tpu.chaos import faults as faults_lib
    from skypilot_tpu.chaos import injector
    from skypilot_tpu.data import checkpoints
    from skypilot_tpu.models import configs
    from skypilot_tpu.models import train as train_lib

    cfg = configs.get_config('tiny')
    tcfg = train_lib.TrainConfig()
    state, _ = train_lib.create_train_state(cfg, tcfg,
                                            batch_size=batch_size,
                                            seq_len=seq_len)
    step_fn = jax.jit(lambda s, b: train_lib.train_step(s, b, tcfg))
    batch = {'tokens': jax.random.randint(
        jax.random.PRNGKey(0), (batch_size, seq_len + 1), 0,
        cfg.vocab_size, dtype=jnp.int32)}
    # Warm the jit cache out of the measurement.
    state, _, _ = _avg_step_seconds(step_fn, state, batch, 2)

    results: dict = {'config': {'model': 'tiny', 'steps': steps,
                                'save_interval': save_interval,
                                'batch_size': batch_size,
                                'seq_len': seq_len,
                                'bucket_latency_s': bucket_latency_s,
                                'cpu_count': __import__('os').cpu_count()}}

    state, avg_none, max_none = _avg_step_seconds(step_fn, state, batch,
                                                  steps)
    results['none'] = {'avg_step_s': avg_none, 'max_step_s': max_none}

    slow_bucket = faults_lib.FaultPlan(
        seed=0, name='bench-slow-bucket',
        faults=[faults_lib.Fault(site='checkpoint.save', effect='delay',
                                 delay_s=bucket_latency_s, every=1)])
    for mode, async_save in (('blocking', False), ('async', True)):
        workdir = tempfile.mkdtemp(prefix=f'bench-ckpt-{mode}-')
        if bucket_latency_s > 0:
            injector.arm(slow_bucket)
        mgr = checkpoints.AsyncCheckpointManager(
            workdir, save_interval_steps=save_interval,
            async_save=async_save)
        try:
            state, avg, max_s = _avg_step_seconds(
                step_fn, state, batch, steps,
                on_step=lambda step, s, m=mgr: m.save(step, s))
            mgr.close()
            results[mode] = {
                'avg_step_s': avg,
                'max_step_s': max_s,
                'saves': mgr.saves_ok,
                'blocked_seconds': mgr.blocked_seconds,
                'overhead_pct':
                    100.0 * (avg - avg_none) / avg_none,
            }
        finally:
            injector.disarm()
            shutil.rmtree(workdir, ignore_errors=True)
    return results


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument('--steps', type=int, default=16)
    parser.add_argument('--save-interval', type=int, default=4)
    parser.add_argument('--batch-size', type=int, default=16)
    parser.add_argument('--seq-len', type=int, default=256)
    parser.add_argument('--bucket-latency', type=float, default=1.0)
    parser.add_argument('--out', default='BENCH_ckpt.json')
    parser.add_argument('--smoke', action='store_true',
                        help='fewer steps; assert the <10%% async bar')
    args = parser.parse_args()
    from skypilot_tpu import compile_cache
    compile_cache.enable()
    steps = 8 if args.smoke else args.steps
    results = run_bench(steps=steps, save_interval=args.save_interval,
                        batch_size=args.batch_size, seq_len=args.seq_len,
                        bucket_latency_s=args.bucket_latency)
    print(json.dumps(results, indent=2))
    with open(args.out, 'w', encoding='utf-8') as f:
        json.dump(results, f, indent=2)
    if args.smoke:
        async_oh = results['async']['overhead_pct']
        blocking_oh = results['blocking']['overhead_pct']
        assert async_oh < 10.0, (
            f'async checkpoint overhead {async_oh:.1f}% >= 10%')
        assert blocking_oh > async_oh, (
            f'blocking saves should cost more than async '
            f'({blocking_oh:.1f}% vs {async_oh:.1f}%)')
        print(f'SMOKE OK: async overhead {async_oh:.1f}% '
              f'(blocking: {blocking_oh:.1f}%)')


if __name__ == '__main__':
    main()
