"""What keeps the chip from being hidden (ISSUE 21), checked in seconds
on the CPU: the compile-cache placement rule, interpret mode refused
off the CPU backend, the device fields on `GET /` of both fronts, and
chip_smoke.py failing here by name.
"""
from __future__ import annotations

import ast
import json
import os
import subprocess
import sys
import urllib.request

import jax
import pytest

_REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


# ------------------------------------------------------------ compile cache


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of making them: turning
    the persistent cache on here would change every later test."""
    calls = []
    monkeypatch.setattr(jax.config, 'update',
                        lambda key, value: calls.append((key, value)))
    return calls


def test_cache_dir_placed_from_outside_is_left_to_jax(monkeypatch,
                                                      config_updates):
    from skypilot_tpu import compile_cache
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', '/some/dir')
    assert compile_cache.enable() == '/some/dir'
    assert config_updates == []   # JAX reads the variable itself


def test_cache_dir_default_is_one_fixed_path(monkeypatch, config_updates):
    from skypilot_tpu import compile_cache
    monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
    first = compile_cache.enable()
    second = compile_cache.enable()
    assert first == second == os.path.join(_REPO_ROOT, '.jax_cache')
    assert config_updates == [('jax_compilation_cache_dir', first)] * 2
    # Fixed means fixed: nothing of this process or moment in it.
    assert str(os.getpid()) not in first
    assert not first.startswith(('/tmp', '/var/tmp'))


# ----------------------------------------------------------- interpret mode


def test_interpret_mode_is_for_the_cpu_backend_only(monkeypatch):
    from skypilot_tpu.ops import attention
    from skypilot_tpu.ops import paged_attention
    monkeypatch.delenv('SKYTPU_DECODE_KERNEL', raising=False)
    monkeypatch.setenv('SKYTPU_PALLAS_INTERPRET', '1')
    assert attention.interpret_mode() is True       # this is the CPU
    assert paged_attention.decode_kernel_choice() == 'pallas'

    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    with pytest.raises(RuntimeError, match='SKYTPU_PALLAS_INTERPRET'):
        attention.interpret_mode()
    # The refusal is reached before the backend test short-circuits and
    # whatever the kernel pin says.
    with pytest.raises(RuntimeError, match='SKYTPU_PALLAS_INTERPRET'):
        attention._use_pallas()
    monkeypatch.setenv('SKYTPU_DECODE_KERNEL', 'gather')
    with pytest.raises(RuntimeError, match='SKYTPU_PALLAS_INTERPRET'):
        paged_attention.decode_kernel_choice()

    monkeypatch.delenv('SKYTPU_PALLAS_INTERPRET')
    assert attention.interpret_mode() is False
    assert attention._use_pallas() is True          # 'tpu': compiled kernels


def test_backend_that_fails_to_start_fails_the_process(monkeypatch):
    """No quiet blockwise run on a host whose chip did not come up."""
    from skypilot_tpu.ops import attention

    def broken():
        raise RuntimeError('Unable to initialize backend tpu')

    monkeypatch.delenv('SKYTPU_PALLAS_INTERPRET', raising=False)
    monkeypatch.setattr(jax, 'default_backend', broken)
    with pytest.raises(RuntimeError, match='Unable to initialize'):
        attention._use_pallas()


# ------------------------------------------------------------ health fields


def test_health_names_the_device_on_both_fronts():
    from skypilot_tpu.serve import async_server
    from skypilot_tpu.serve import model_server
    server = model_server.ModelServer('tiny', max_len=32)
    want = {
        'device': {'platform': 'cpu',
                   'kind': jax.devices()[0].device_kind,
                   'count': jax.device_count()},
        'jax_version': jax.__version__,
        'pallas_interpret': False,
        'decode_kernel': 'dense',
    }
    try:
        port, stop = model_server.start_background(server)
        try:
            with urllib.request.urlopen(f'http://127.0.0.1:{port}/',
                                        timeout=30) as resp:
                threaded = json.loads(resp.read())
        finally:
            stop()
        code, asynced = async_server.AsyncModelServer(server)._health()
    finally:
        server.close()
    assert code == 200
    for payload in (threaded, asynced):
        assert {k: payload[k] for k in want} == want


# --------------------------------------------------------------- chip smoke


def test_chip_smoke_fails_here_and_names_the_cpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO_ROOT, 'chip_smoke.py')],
        env=dict(os.environ, JAX_PLATFORMS='cpu'), cwd=_REPO_ROOT,
        capture_output=True, text=True, timeout=120, check=False)
    assert proc.returncode != 0
    assert 'device=cpu' in proc.stderr
    # No result: nothing on stdout parses as the {"ok": ...} object.
    assert '"ok"' not in proc.stdout


def test_chip_smoke_parent_imports_only_the_stdlib():
    """The parent must never touch JAX (a chip belongs to one process):
    every module-level import of chip_smoke.py is standard library."""
    with open(os.path.join(_REPO_ROOT, 'chip_smoke.py'),
              encoding='utf-8') as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in tree.body:
        if isinstance(node, ast.Import):
            imported.update(a.name.split('.')[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            imported.add(node.module.split('.')[0])
    assert imported <= set(sys.stdlib_module_names), (
        imported - set(sys.stdlib_module_names))


# ------------------------------------------------- one process for each chip


def test_control_plane_never_imports_jax():
    """Everything between `skytpu launch` and a task's `run:` command
    (CLI, skylet, gang supervisor, controllers, load balancer) is a
    PARENT of the process that needs the chip, so it must not hold it:
    importing those modules leaves JAX unloaded."""
    code = (
        'import importlib, sys\n'
        'for m in ("cli", "execution", "skylet.skylet", "skylet.job_lib",'
        ' "backends.gang_supervisor", "backends.slice_backend",'
        ' "jobs.controller", "serve.controller", "serve.service",'
        ' "serve.replica_managers", "serve.load_balancer"):\n'
        '    importlib.import_module("skypilot_tpu." + m)\n'
        'bad = sorted({k.split(".")[0] for k in sys.modules} &'
        ' {"jax", "jaxlib", "flax", "orbax", "optax"})\n'
        'assert not bad, bad\n')
    proc = subprocess.run(
        [sys.executable, '-c', code], cwd=_REPO_ROOT,
        env=dict(os.environ, PYTHONPATH=_REPO_ROOT),
        capture_output=True, text=True, timeout=120, check=False)
    assert proc.returncode == 0, proc.stderr[-2000:]


# ------------------------------------------------ weights live on the device


def test_converted_checkpoint_restores_onto_the_device(tmp_path):
    """A converted checkpoint is written from host arrays; restored as
    numpy, a one-chip server would upload every weight again on each
    jitted call (met on the chip: over a second per decode tick).
    restore_params hands back device arrays in the stored dtype."""
    import numpy as np

    from skypilot_tpu.data import checkpoints
    from skypilot_tpu.models import configs
    from skypilot_tpu.models import import_weights
    tree = {'embed': {'embedding': np.ones((8, 4), np.float16)},
            'final_norm': {'scale': np.ones((4,), np.float32)}}
    import_weights.save_converted(str(tmp_path), tree, configs.TINY)
    assert import_weights.load_model_config(str(tmp_path)) == configs.TINY
    leaves = jax.tree_util.tree_leaves(
        checkpoints.restore_params(str(tmp_path)))
    assert len(leaves) == 2
    for leaf in leaves:
        assert isinstance(leaf, jax.Array), type(leaf)
        assert leaf.devices() == {jax.devices()[0]}
    assert sorted(str(leaf.dtype) for leaf in leaves) == [
        'float16', 'float32']
