"""Collective preflight: measure ICI/DCN health before committing a
long job to a slice.

SURVEY.md §7 build-plan item 9 and §5 failure-detection mandate: the
reference can only gang-schedule and hope; a TPU-native framework can
cheaply verify that the fabric actually delivers before the first real
step.  `probe_collectives(mesh)` runs a tiny-latency and a
bandwidth-sized psum per mesh axis and returns wall-clock numbers
('psum_latency_ms', 'psum_gbps'); `check_collectives` turns them into
a pass/fail against loose floors (a flaky ICI link shows up as 100x
latency, not 10%).

Used by examples/train_llama.py --preflight and callable from any job
via the public API.  Works identically on the virtual CPU mesh (tests)
and real slices.
"""
from __future__ import annotations

import time
from typing import Any, Dict, Optional

import numpy as np

from skypilot_tpu import sky_logging

logger = sky_logging.init_logger(__name__)

# Floors are deliberately loose: preflight catches BROKEN fabric
# (orders of magnitude off), not mild regressions.
DEFAULT_MIN_BANDWIDTH_GBPS = 0.05
DEFAULT_MAX_LATENCY_MS = 5000.0


def probe_collectives(mesh, *, bandwidth_mb: float = 64.0,
                      repeats: int = 3) -> Dict[str, Dict[str, float]]:
    """Measure per-axis collective latency and bandwidth.

    Returns {axis: {'size': n, 'psum_latency_ms': ..,
    'psum_gbps': ..}} for every mesh axis with size > 1.

    Multi-host safe by construction: probe inputs are assembled with
    `make_array_from_callback` (each process materialises exactly the
    shards it addresses, on any process/axis layout) and stay committed
    in their target sharding across the timed iterations; each timed
    call returns only a REPLICATED SCALAR
    (the collective's payload never crosses PCIe), synced by a
    `device_get` of that scalar, which keeps the timed region
    fabric-dominated.
    """
    import jax  # pylint: disable=import-outside-toplevel
    import jax.numpy as jnp  # pylint: disable=import-outside-toplevel
    P = jax.sharding.PartitionSpec

    results: Dict[str, Dict[str, float]] = {}
    axes = [a for a in mesh.axis_names if mesh.shape[a] > 1]
    for axis in axes:
        n = mesh.shape[axis]

        def _probe_fn(x, axis=axis):
            y = jax.lax.psum(x, axis)           # the measured collective
            # Tiny replicated scalar out: sync without payload D2H.
            return jnp.sum(y[:, :8])

        def _sharded(shape, axis=axis):
            sharding = jax.sharding.NamedSharding(mesh, P(axis))

            def _block(index):
                dims = [
                    (s.stop if s.stop is not None else dim) -
                    (s.start if s.start is not None else 0)
                    for s, dim in zip(index, shape)
                ]
                return np.ones(dims, np.float32)

            # make_array_from_callback asks each process only for the
            # shards it addresses — correct on ANY process/axis layout
            # (replicated axes, multi-slice meshes) where row-count
            # heuristics are not.
            return jax.make_array_from_callback(shape, sharding, _block)

        # Manual over `axis` only; the probe's collectives run without
        # the replication checker.
        probe = jax.jit(jax.shard_map(
            _probe_fn, mesh=mesh, in_specs=P(axis), out_specs=P(),
            axis_names={axis}, check_vma=False))

        tiny = _sharded((n, 8))
        # Each PARTICIPANT holds bandwidth_mb of payload (per-rank
        # bytes are what ring all-reduce cost scales with — sizing by
        # the global array would shrink wide axes' probes into
        # latency-dominated noise).
        elems = max(8, int(bandwidth_mb * 1e6 / 4))
        big = _sharded((n, elems))
        # Warm up (compile) outside the timed region.
        float(jax.device_get(probe(tiny)))
        float(jax.device_get(probe(big)))

        lat = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            float(jax.device_get(probe(tiny)))
            lat.append(time.perf_counter() - t0)
        bw = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            float(jax.device_get(probe(big)))
            bw.append(time.perf_counter() - t0)
        # Standard all-reduce bus bandwidth: each rank moves
        # 2*(n-1)/n x its payload over its links.
        per_rank_gb = elems * 4 / 1e9
        busbw = (2 * (n - 1) / n) * per_rank_gb / max(
            float(np.median(bw)), 1e-9)
        results[axis] = {
            'size': float(n),
            'psum_latency_ms': round(float(np.median(lat)) * 1e3, 3),
            'psum_gbps': round(busbw, 3),
        }
        logger.info(f'preflight[{axis}]: {results[axis]}')
    return results


def check_collectives(mesh, *,
                      min_bandwidth_gbps: float = DEFAULT_MIN_BANDWIDTH_GBPS,
                      max_latency_ms: float = DEFAULT_MAX_LATENCY_MS,
                      results: Optional[Dict[str, Any]] = None) -> None:
    """Probe and raise if any axis is outside the health floors."""
    from skypilot_tpu import exceptions  # pylint: disable=import-outside-toplevel
    results = results if results is not None else probe_collectives(mesh)
    problems = []
    for axis, stats in results.items():
        if stats['psum_latency_ms'] > max_latency_ms:
            problems.append(
                f'{axis}: psum latency {stats["psum_latency_ms"]}ms '
                f'> {max_latency_ms}ms')
        if stats['psum_gbps'] < min_bandwidth_gbps:
            problems.append(
                f'{axis}: bandwidth {stats["psum_gbps"]}GB/s '
                f'< {min_bandwidth_gbps}GB/s')
    if problems:
        raise exceptions.SkyTpuError(
            'Collective preflight failed — the fabric is unhealthy; '
            'relaunch or exclude the slice: ' + '; '.join(problems))
