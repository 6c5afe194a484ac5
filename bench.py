"""Training-throughput bench of the `small` preset on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
The reference publishes no training-throughput numbers (BASELINE.md —
`published: {}`), so vs_baseline is reported against the MFU-derived
roofline expectation for the detected chip (1.0 == hitting 40% MFU).

It measures a device, so it needs one: on any backend but `tpu`, or on
a TPU generation missing from the peak table, it raises; a phase that
fails fails the run.  There is no CPU configuration — a number from a
CPU run is never written under a device metric's name.  (ROADMAP
Speed 1 replaces this script with the cell benchmark.)
"""
from __future__ import annotations

import json
import sys
import time

_METRIC = 'llama_train_tokens_per_sec_per_chip'


def _param_count(params) -> int:
    import jax
    return sum(p.size for p in jax.tree_util.tree_leaves(params))


def _peak_flops(device) -> float:
    """Peak bf16 FLOP/s of a known TPU generation (Google Cloud TPU
    documentation; v5e: 197 TFLOP/s).  Matched against real device_kind
    strings ('TPU v5 lite', 'TPU v5p', 'TPU v6 lite', ...) — most
    specific key first.  A device that is not in the table is an error,
    not a default."""
    kind = device.device_kind.lower()
    table = (
        ('v6 lite', 918e12), ('v6e', 918e12),
        ('v5 lite', 197e12), ('v5litepod', 197e12), ('v5e', 197e12),
        ('v5p', 459e12), ('v4', 275e12), ('v3', 123e12), ('v2', 45e12),
    )
    for key, val in table:
        if key in kind:
            return val
    raise ValueError(
        f'no peak FLOP/s on record for device_kind '
        f'{device.device_kind!r}; add it to _peak_flops with its source')


def _run_config(cfg, batch: int, seq: int, n_steps: int, tcfg=None):
    """Compile + warm up + time one training config.

    Returns (tokens_per_sec, n_params, final_loss, peak_bytes).
    `tcfg` threads the hot-path knobs (fused CE, accumulation) into
    train_step; batches stream through the double-buffered
    DevicePrefetcher (data/prefetch.py) so step N+1's host->device
    transfer overlaps step N's compute — the same path the gang job
    contract uses.  peak_bytes is the compiled step's temp allocation
    (XLA CompiledMemoryStats; None when the backend hides it).

    The timed region ends in `jax.block_until_ready` on the final
    step's loss: each step consumes the previous step's donated
    TrainState, so that value cannot be ready before every timed step
    has run on the chip (tests/tpu pins that block_until_ready waits
    for the device on this backend).
    """
    import functools

    import jax
    import numpy as np

    from skypilot_tpu.data.prefetch import prefetch_to_device
    from skypilot_tpu.models.train import TrainConfig
    from skypilot_tpu.models.train import create_train_state
    from skypilot_tpu.models.train import train_step

    state, _ = create_train_state(cfg, tcfg or TrainConfig(),
                                  batch_size=batch, seq_len=seq)
    n_params = _param_count(state.params)
    step_fn = functools.partial(train_step, tcfg=tcfg)
    jitted = jax.jit(step_fn, donate_argnums=(0,))

    rng = np.random.default_rng(0)

    def host_batches(n):
        for _ in range(n):
            yield {'tokens': rng.integers(
                0, cfg.vocab_size,
                size=(batch, seq + 1)).astype(np.int32)}

    warmup = 2
    # One AOT compile serves both the memory stats and execution (a
    # second trace through jit would double the TPU compile time).
    first = next(prefetch_to_device(host_batches(1)))
    compiled = jitted.lower(state, first).compile()
    from skypilot_tpu.models.train import compiled_peak_memory
    # Also feeds the skytpu_train_peak_memory_bytes gauge.
    peak_bytes = compiled_peak_memory(compiled)

    prefetched = prefetch_to_device(host_batches(warmup + n_steps))
    for _ in range(warmup):
        state, metrics = compiled(state, next(prefetched))
    jax.block_until_ready(metrics['loss'])
    t0 = time.perf_counter()
    for _ in range(n_steps):
        state, metrics = compiled(state, next(prefetched))
    jax.block_until_ready(metrics['loss'])
    dt = time.perf_counter() - t0
    return (batch * seq * n_steps / dt, n_params,
            float(metrics['loss']), peak_bytes)


def main() -> None:
    import jax

    from skypilot_tpu import compile_cache
    from skypilot_tpu.models import configs
    from skypilot_tpu.models.train import TrainConfig

    compile_cache.enable()
    if jax.default_backend() != 'tpu':
        raise SystemExit(
            f'bench.py measures a TPU; the JAX backend is '
            f'{jax.default_backend()!r} ({jax.devices()[0].device_kind}). '
            f'Nothing was measured.')
    dev = jax.devices()[0]
    peak_flops = _peak_flops(dev)

    base = configs.get_config('small', logits_in_f32=False)
    batch, seq, n_steps = 16, 1024, 20
    # Fastest schedule first; each step down trades flops for HBM.
    # 'small' at b=16/s=1024 is estimated to fit without remat on a
    # 16 GB v5e but the estimate is not a guarantee, so an OOM moves on
    # to the next schedule; the result names the one that ran.
    candidates = [
        ('noremat+lmbf16', base.replace(remat=False)),
        ('dots+lmbf16', base.replace(remat_policy='dots')),
        ('full+lmbf16', base),
    ]
    for i, (config_name, cfg) in enumerate(candidates):
        try:
            tokens_per_sec, n_params, final_loss, peak_bytes = \
                _run_config(cfg, batch, seq, n_steps)
            break
        except Exception as e:  # pylint: disable=broad-except
            # Only a memory-style failure means "try a leaner
            # schedule"; anything else would fail every candidate
            # identically.
            msg = f'{type(e).__name__}: {e}'
            oom_like = ('RESOURCE_EXHAUSTED' in msg or 'OOM' in msg or
                        'out of memory' in msg.lower())
            print(f'# bench config {config_name} failed: {msg[:300]}',
                  file=sys.stderr)
            if not oom_like or i == len(candidates) - 1:
                raise

    # Fused linear+CE pass over the SAME schedule (models/losses.py):
    # the [b,s,V] logits tensor never materializes.
    chunk = min(8192, max(1024, cfg.vocab_size // 8))
    fused_tps, _, fused_loss, fused_peak = _run_config(
        cfg, batch, seq, n_steps,
        tcfg=TrainConfig(fused_ce=True, vocab_chunk=chunk))
    print(f'# fused CE: {fused_tps:.1f} tok/s '
          f'loss={fused_loss:.3f} peak={fused_peak}', file=sys.stderr)

    best_tps = max(tokens_per_sec, fused_tps)
    # Training FLOPs/token ~= 6 * params; MFU vs chip roofline.
    mfu = 6.0 * n_params * best_tps / peak_flops
    vs_baseline = mfu / 0.40  # 1.0 == 40% MFU (well-tuned TPU training)

    print(json.dumps({
        'metric': _METRIC,
        'value': round(best_tps, 1),
        'unit': 'tokens/s',
        'vs_baseline': round(vs_baseline, 3),
        'platform': dev.platform,
        'device': dev.device_kind,
        'device_count': jax.device_count(),
        'mfu': round(mfu, 4),
        'config': config_name,
        'tokens_per_sec_unfused': round(tokens_per_sec, 1),
        'tokens_per_sec_fused': round(fused_tps, 1),
        'peak_bytes_unfused': peak_bytes,
        'peak_bytes_fused': fused_peak,
        'synced_timing': 'block_until_ready_final_loss_chained',
    }))
    print(f'# device={dev.device_kind} config={config_name} '
          f'params={n_params/1e6:.1f}M mfu={mfu:.3f} '
          f'loss={final_loss:.3f}', file=sys.stderr)
    # Perf-regression observatory: one record per run (sky bench diff
    # compares against the committed history).
    from skypilot_tpu.observability import bench_history
    bench_history.append_record({
        'source': 'bench',
        'metric': _METRIC,
        'value': round(best_tps, 1),
        'unit': 'tokens/s',
        'config': {'model': config_name,
                   'device': dev.device_kind},
        'tokens_per_s': round(best_tps, 1),
        'mfu_estimate': round(mfu, 4),
    })
    # Feed the optimizer's fungibility prior with the measured MFU
    # (utils/throughput_registry).
    from skypilot_tpu.utils import throughput_registry
    key = throughput_registry.device_kind_to_key(dev.device_kind)
    if key is not None:
        throughput_registry.record_measurement(
            key, mfu, tokens_per_sec=best_tps,
            model=f'{cfg.d_model}x{cfg.n_layers}/{config_name}')


if __name__ == '__main__':
    main()
