"""Shared test fixtures.

Test strategy mirrors SURVEY.md §4: unit tests are hermetic (temp
SKYTPU_HOME, no cloud access); compute tests run on a virtual 8-device CPU
mesh (`xla_force_host_platform_device_count`) so multi-chip sharding is
exercised without TPU hardware.
"""
from __future__ import annotations

import os

# Two modes.  Default: hermetic and fast — the CPU backend with eight
# virtual devices (Pallas kernels run interpreted where a test sets
# SKYTPU_PALLAS_INTERPRET=1).  SKYTPU_TPU_TESTS=1: the chip suite
# (tests/tpu) against the real TPU; interpret mode must never
# green-light a kernel that will not lower, so there a missing TPU
# fails the run before collection instead of skipping it.
_TPU_TESTS = os.environ.get('SKYTPU_TPU_TESTS') == '1'

if not _TPU_TESTS:
    os.environ['JAX_PLATFORMS'] = 'cpu'
    _flags = os.environ.get('XLA_FLAGS', '')
    if 'xla_force_host_platform_device_count' not in _flags:
        os.environ['XLA_FLAGS'] = (
            _flags + ' --xla_force_host_platform_device_count=8').strip()


def pytest_configure(config):
    del config
    if not _TPU_TESTS:
        return
    import jax

    from skypilot_tpu import compile_cache
    if jax.default_backend() != 'tpu':
        raise pytest.UsageError(
            f'SKYTPU_TPU_TESTS=1 asks for the chip suite, but the JAX '
            f'backend is {jax.default_backend()!r} '
            f'({jax.devices()[0].device_kind}).  Nothing was tested.')
    compile_cache.enable()


import time as _time  # noqa: E402

import pytest  # noqa: E402

# ----------------------------------------------------- tier-1 time budget
# The tier-1 verify command hard-kills the suite at 870s (`timeout -k 10
# 870`).  A suite that finishes at 860s is one flaky rerun away from a
# kill with NO failure attribution — so when a full tier-1 run crosses
# the trip fraction of the budget, this guard FAILS the run explicitly
# and names the top-10 slowest tests (the ones to slow-mark or speed
# up).  Partial dev runs (< _TIER1_MIN_ITEMS collected tests) never
# trip.

_TIER1_BUDGET_ENV = 'SKYTPU_TIER1_WALLCLOCK_BUDGET_S'
_TIER1_DEFAULT_BUDGET_S = 870.0
_TIER1_TRIP_FRACTION = 0.92
_TIER1_MIN_ITEMS = 400

_session_t0 = _time.monotonic()
_test_durations = {}


def tier1_wallclock_violation(elapsed_s, n_items, durations,
                              budget_s=_TIER1_DEFAULT_BUDGET_S,
                              trip_fraction=_TIER1_TRIP_FRACTION,
                              min_items=_TIER1_MIN_ITEMS):
    """Pure guard logic (unit-tested in test_wallclock_guard.py):
    returns the failure report string, or None when within budget or
    not a full-suite run."""
    if n_items < min_items:
        return None
    trip_s = budget_s * trip_fraction
    if elapsed_s <= trip_s:
        return None
    slowest = sorted(durations.items(), key=lambda kv: -kv[1])[:10]
    lines = [
        f'tier-1 wall clock {elapsed_s:.0f}s exceeded the guard '
        f'threshold {trip_s:.0f}s ({trip_fraction:.0%} of the '
        f'{budget_s:.0f}s timeout budget) — slow-mark or speed up the '
        f'worst offenders before the hard timeout starts killing CI '
        f'runs with no attribution.',
        'Top 10 slowest tests:',
    ]
    lines += [f'  {dur:8.1f}s  {nodeid}' for nodeid, dur in slowest]
    return '\n'.join(lines)


def pytest_sessionstart(session):
    del session
    global _session_t0
    _session_t0 = _time.monotonic()


def pytest_runtest_logreport(report):
    if report.when == 'call':
        _test_durations[report.nodeid] = report.duration


@pytest.hookimpl(hookwrapper=True)
def pytest_runtestloop(session):
    yield
    budget = float(os.environ.get(_TIER1_BUDGET_ENV,
                                  _TIER1_DEFAULT_BUDGET_S))
    message = tier1_wallclock_violation(
        _time.monotonic() - _session_t0, len(session.items),
        _test_durations, budget_s=budget)
    if message is not None:
        import sys as _sys
        print(f'\nFAILED (wall-clock guard)\n{message}',
              file=_sys.stderr)
        session.testsfailed += 1


def _reap_daemons(home: str) -> None:
    """Kill every daemon a test spawned under its SKYTPU_HOME.

    Local-provisioner 'hosts' live under the home dir; deleting the tmp
    dir without this sweep orphans their skylets/job supervisors (five
    such orphans were found after the round-1 test runs).  Two passes:
    (1) pid files written under the home, (2) any process whose cmdline
    or cwd references the home (controllers, LBs, tail loops).
    """
    import psutil

    def _kill_tree(pid: int) -> None:
        try:
            proc = psutil.Process(pid)
        except psutil.NoSuchProcess:
            return
        procs = [proc]
        try:
            procs += proc.children(recursive=True)
        except psutil.NoSuchProcess:
            pass
        for p in procs:
            try:
                p.kill()
            except psutil.NoSuchProcess:
                pass

    # os.walk (not glob) so pid files under dot-dirs like .skytpu are
    # found too.
    for dirpath, _, filenames in os.walk(home):
        for fname in filenames:
            if not fname.endswith('.pid'):
                continue
            try:
                with open(os.path.join(dirpath, fname),
                          encoding='utf-8') as f:
                    _kill_tree(int(f.read().strip()))
            except (OSError, ValueError):
                pass
    me = os.getpid()
    for proc in psutil.process_iter(['pid', 'cmdline', 'cwd']):
        if proc.info['pid'] == me:
            continue
        try:
            cmdline = ' '.join(proc.info['cmdline'] or ())
            cwd = proc.info['cwd'] or ''
        except (psutil.NoSuchProcess, psutil.AccessDenied,
                psutil.ZombieProcess):
            continue
        if home in cmdline or cwd.startswith(home):
            _kill_tree(proc.info['pid'])


def _skylet_pids() -> set:
    import psutil
    pids = set()
    for proc in psutil.process_iter(['pid', 'cmdline']):
        try:
            cmdline = ' '.join(proc.info['cmdline'] or ())
        except (psutil.NoSuchProcess, psutil.AccessDenied,
                psutil.ZombieProcess):
            continue
        if 'skypilot_tpu.skylet' in cmdline:
            pids.add(proc.info['pid'])
    return pids


@pytest.fixture(scope='session', autouse=True)
def _daemon_registry_env(tmp_path_factory):
    """Session-scoped spawn registry OUTSIDE per-test homes.

    Every daemon spawn records itself here (utils/daemon_registry); at
    session start we reap strays from crash-interrupted PREVIOUS runs —
    their registry is the default real-home path, so check that one too.
    """
    from skypilot_tpu.utils import daemon_registry
    # First: reap orphans left by earlier (possibly kill -9'd) runs,
    # recorded in the default registry.
    daemon_registry.reap_stale()
    # Then isolate this session's spawns in a session-local registry.
    path = str(tmp_path_factory.mktemp('daemon_registry') / 'reg.jsonl')
    os.environ['SKYTPU_DAEMON_REGISTRY'] = path
    yield path
    # Kill anything still alive that this session spawned.
    for rec in daemon_registry._load():  # pylint: disable=protected-access
        if daemon_registry._same_process(rec):  # pylint: disable=protected-access
            daemon_registry._kill_tree(rec['pid'])  # pylint: disable=protected-access
    os.environ.pop('SKYTPU_DAEMON_REGISTRY', None)


@pytest.fixture(scope='session', autouse=True)
def _no_skylet_orphans():
    """Hard guarantee: a pytest run leaves zero NEW skylet daemons
    behind, whatever path spawned them (VERDICT round-1 item 7)."""
    import psutil
    before = _skylet_pids()
    yield
    for pid in _skylet_pids() - before:
        try:
            psutil.Process(pid).kill()
        except psutil.NoSuchProcess:
            pass


@pytest.fixture(autouse=True)
def _isolated_home(tmp_path, monkeypatch):
    """Every test gets a fresh SKYTPU_HOME (state.db, config, jobs.db);
    daemons spawned under it are reaped at teardown."""
    home = tmp_path / 'skytpu_home'
    home.mkdir()
    monkeypatch.setenv('SKYTPU_HOME', str(home))
    monkeypatch.setenv('SKYTPU_JOB_DB', str(home / 'jobs.db'))
    monkeypatch.delenv('SKYTPU_CONFIG', raising=False)
    from skypilot_tpu import config as config_mod
    from skypilot_tpu.catalog import common as catalog_common
    config_mod.reload_config()
    # Catalog loads are lru-cached; a prior test's `catalog refresh`
    # (user catalog under ITS home) must not leak rows into this one.
    catalog_common.clear_catalog_caches()
    yield home
    _reap_daemons(str(home))
    config_mod.reload_config()
    catalog_common.clear_catalog_caches()


@pytest.fixture
def enable_all_infra(monkeypatch):
    """Pretend every infra has credentials (parity: reference
    tests/common.py enable_all_clouds), so optimizer/catalog tests run
    offline."""
    from skypilot_tpu import global_user_state
    from skypilot_tpu.clouds import registry
    global_user_state.set_enabled_clouds(list(registry.CLOUD_REGISTRY.keys()))
    for cloud in registry.CLOUD_REGISTRY.values():
        monkeypatch.setattr(type(cloud), 'check_credentials',
                            lambda self: (True, None))
    yield


# --------------------------------------------------------------- slow tier
# Measured tiering (VERDICT r3 item 6 / r4 item 5): tests >= ~5s wall on
# the CI box carry @pytest.mark.slow, so the default dev loop is
# `pytest tests/unit -m 'not slow'` (< 5 min) while CI runs everything.
# Maintained here centrally (one table, re-measured with --durations)
# instead of scattering decorators across files; match is by
# (file basename, test name prefix) so parametrized ids stay covered.

_SLOW_TESTS = {
    'test_batching_engine.py': (
        'test_single_request_matches_decode',
        'test_concurrent_requests_exact', 'test_moe_config_exact'),
    'test_benchmark.py': ('test_launch_collect_score',),
    'test_callbacks.py': ('test_keras_callback_gated',),
    'test_cli.py': ('test_launch_status_queue_logs_down',
                    'test_down_glob'),
    'test_compute.py': ('test_forward_shape', 'test_scan_matches_unrolled',
                        'test_remat_policy_and_logits_dtype_parity',
                        'test_sharded_train_step_loss_matches_single',
                        'test_grad_matches', 'test_matches_reference',
                        'test_gqa_matches_reference',
                        'test_model_sequence_parallel_ulysses',
                        'test_pipeline_sp_ulysses_gqa'),
    'test_controller_utils.py': ('test_job_reads_translated_mounts',),
    'test_decode.py': ('test_greedy_generation_parity',
                       'test_moe_greedy_generation_parity',
                       'test_family_variants_generation_parity',
                       'test_prefill_logits_match_full_forward'),
    'test_chaos.py': ('test_elastic_expand_round_trip',
                      'test_replica_rank_death_full_rebuild'),
    'test_distributed_bootstrap.py': (
        'test_two_process_bootstrap_and_psum',),
    'test_elastic.py': (
        'test_shrink_expand_round_trip_with_loss_continuity',),
    'test_flash_kernels.py': ('test_pallas_backward_bf16',
                              'test_pallas_backward_matches_reference',
                              'test_ring_attention_uses_pallas_kernels'),
    'test_gang_distributed_e2e.py': (
        'test_gang_task_runs_distributed_psum',),
    'test_import_weights.py': ('test_finetune_init_from_converted',),
    'test_launch_e2e.py': ('test_exec_reuses_cluster_and_queue',
                           'test_stop_start_cycle'),
    'test_managed_jobs.py': ('test_launch_detached_process_mode',
                             'test_cancel_terminal_job_noop',
                             'test_preemption_recovery'),
    'test_model_server.py': ('test_',),   # module: shared jit fixture
    'test_async_server.py': ('test_',),   # module: shared jit fixture
    'test_pipeline.py': ('test_pipeline_',),
    'test_quantize.py': ('test_generation_close_to_fp',
                         'test_moe_experts_quantized_router_not',
                         'test_tied_embeddings_not_quantized_path'),
    'test_serve_cluster_mode.py': ('test_',),
    'test_serve_real_checkpoint.py': ('test_',),
    'test_slice_replica.py': ('test_two_host_through_lb',
                              'test_four_host_through_lb'),
    'test_usage.py': ('test_exec_records_separately',),
    'test_stress.py': ('test_',),
}


def pytest_collection_modifyitems(config, items):
    del config
    for item in items:
        prefixes = _SLOW_TESTS.get(item.path.name)
        if prefixes and item.name.startswith(prefixes):
            item.add_marker(pytest.mark.slow)


# ------------------------------------------------------- sky lint index
# One parse of the whole package shared by every lint-plane test
# (test_sky_lint + the three migrated lint wrappers): the index is
# immutable, so session scope is safe and saves ~1s per consumer.
@pytest.fixture(scope='session')
def lint_index():
    import pathlib

    import skypilot_tpu
    from skypilot_tpu.analysis import index as index_lib
    return index_lib.PackageIndex(
        pathlib.Path(skypilot_tpu.__file__).resolve().parent)
