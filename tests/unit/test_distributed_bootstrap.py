"""Multi-process jax.distributed bootstrap from the gang-exec env.

The framework's distributed contract (SURVEY §2.3 'collective comms
backend': coordinator bootstrap is OUR job, collectives are XLA's) is
exercised for real here: two OS processes, each a 'host' with the
SKYTPU_* env the gang supervisor exports, initialize jax.distributed
and run a cross-process psum on CPU.
"""
from __future__ import annotations

import os
import socket
import subprocess
import sys
import textwrap

_WORKER = textwrap.dedent("""
    import os
    os.environ['JAX_PLATFORMS'] = 'cpu'
    import jax
    from skypilot_tpu.parallel import distributed

    assert distributed.initialize_from_env(), 'bootstrap returned False'
    assert jax.process_count() == 2, jax.process_count()
    rank = distributed.host_rank()

    import numpy as np
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ('data',))
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec('data'))
    n = jax.device_count()
    arr = jax.make_array_from_callback(
        (n,), sharding,
        lambda idx: np.asarray(
            [float(idx[0].start if idx[0].start else 0)],
            dtype=np.float32))

    def total(x):
        return jax.lax.psum(x, 'data')

    out = jax.jit(jax.shard_map(total, mesh=mesh,
                                in_specs=jax.sharding.PartitionSpec('data'),
                                out_specs=jax.sharding.PartitionSpec()))(arr)
    # Sum of shard indices 0..n-1.
    expected = sum(range(n))
    got = float(jax.device_get(out.addressable_shards[0].data)[0])
    assert got == expected, (got, expected)
    print(f'RANK{rank}_PSUM_OK', flush=True)
""")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        return s.getsockname()[1]


def test_two_process_bootstrap_and_psum(tmp_path):
    port = _free_port()
    repo_root = str(__import__('pathlib').Path(__file__).parents[2])
    env_base = {
        **os.environ,
        'SKYTPU_COORDINATOR_ADDRESS': f'127.0.0.1:{port}',
        'SKYTPU_NUM_HOSTS': '2',
        'PYTHONPATH': repo_root,
    }
    env_base.pop('XLA_FLAGS', None)  # one device per process
    procs = []
    for rank in range(2):
        env = dict(env_base)
        env['SKYTPU_HOST_RANK'] = str(rank)
        procs.append(subprocess.Popen(
            [sys.executable, '-c', _WORKER], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for proc in procs:
        try:
            out, _ = proc.communicate(timeout=150)
        except subprocess.TimeoutExpired:
            proc.kill()
            out, _ = proc.communicate()
        outs.append(out)
    for rank, (proc, out) in enumerate(zip(procs, outs)):
        assert proc.returncode == 0, f'rank {rank} failed:\n{out[-2000:]}'
        assert f'RANK{rank}_PSUM_OK' in out
