"""Serving lifecycle tests (ISSUE 10): graceful drain, controller
crash recovery, request deadlines, client-disconnect reaping, and the
LB's controller-sync hardening.

Hermetic like the rest of the suite: model servers run in-process,
"replica clusters" are serve_state rows pointing at live local HTTP
servers, journals live under the per-test SKYTPU_HOME.
"""
from __future__ import annotations

import http.server
import json
import os
import socket
import sqlite3
import threading
import time

import pytest
import requests

import skypilot_tpu as sky
from skypilot_tpu import global_user_state
from skypilot_tpu.chaos import invariants
from skypilot_tpu.observability import events as events_lib
from skypilot_tpu.serve import autoscalers
from skypilot_tpu.serve import batching_engine
from skypilot_tpu.serve import controller as controller_lib
from skypilot_tpu.serve import load_balancer as lb_lib
from skypilot_tpu.serve import model_server as model_server_lib
from skypilot_tpu.serve import replica_managers
from skypilot_tpu.serve import router as router_lib
from skypilot_tpu.serve import serve_state
from skypilot_tpu.serve.serve_state import ReplicaStatus
from skypilot_tpu.serve.service_spec import SkyServiceSpec


@pytest.fixture(autouse=True)
def _serve_env(monkeypatch, _isolated_home):
    monkeypatch.setenv('SKYTPU_SERVE_DB',
                       str(_isolated_home / 'serve.db'))
    global_user_state.set_enabled_clouds(['local'])
    yield


def _spec(**kw) -> SkyServiceSpec:
    kw.setdefault('initial_delay_seconds', 30)
    kw.setdefault('readiness_timeout_seconds', 2)
    return SkyServiceSpec(**kw)


def _make_manager(service='svc-drain', **spec_kw):
    task = sky.Task(name=service, run='sleep 1')
    task.set_resources(sky.Resources(cloud='local'))
    spec = _spec(**spec_kw)
    serve_state.add_service(service, spec_json={}, task_yaml_path='')
    return replica_managers.ReplicaManager(service, spec, task), spec


def _stub_replica(payload):
    """A live HTTP server answering GET with a JSON payload (the
    replica health surface the drain monitor / recovery probe reads);
    returns (url, set_payload, shutdown)."""
    state = {'payload': dict(payload)}

    class Handler(http.server.BaseHTTPRequestHandler):

        def do_GET(self):  # noqa: N802 (stdlib naming)
            body = json.dumps(state['payload']).encode()
            self.send_response(200)
            self.send_header('Content-Type', 'application/json')
            self.send_header('Content-Length', str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_POST(self):  # noqa: N802
            length = int(self.headers.get('Content-Length', 0))
            self.rfile.read(length)
            state.setdefault('posts', []).append(self.path)
            body = b'{"ok": true}'
            self.send_response(200)
            self.send_header('Content-Length', str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *args):
            del args

    server = http.server.ThreadingHTTPServer(('127.0.0.1', 0), Handler)
    threading.Thread(target=server.serve_forever, daemon=True).start()

    def set_payload(p):
        state['payload'] = dict(p)

    return (f'http://127.0.0.1:{server.server_address[1]}', set_payload,
            server.shutdown)


def _serve_events():
    return events_lib.get_journal(
        os.path.join(events_lib.journal_root(), 'serve.jsonl')).read()


# ------------------------------------------------------------ state layer


class TestDrainingState:

    def test_draining_is_not_terminal(self):
        assert not ReplicaStatus.DRAINING.is_terminal()
        assert ReplicaStatus.DRAINING not in \
            ReplicaStatus.failed_statuses()

    def test_additive_migration_from_old_db(self, tmp_path,
                                            monkeypatch):
        """A pre-drain DB (no role/num_hosts/drain_started_at columns)
        loads cleanly and gains the columns."""
        db = tmp_path / 'old-serve.db'
        conn = sqlite3.connect(db)
        conn.execute(
            'CREATE TABLE replicas (service_name TEXT, '
            'replica_id INTEGER, cluster_name TEXT, status TEXT, '
            'url TEXT, is_spot INTEGER DEFAULT 0, '
            'version INTEGER DEFAULT 1, launched_at REAL, '
            'PRIMARY KEY (service_name, replica_id))')
        conn.execute(
            "INSERT INTO replicas (service_name, replica_id, "
            "cluster_name, status, url) VALUES "
            "('svc', 1, 'svc-1', 'READY', 'http://x')")
        conn.commit()
        conn.close()
        monkeypatch.setenv('SKYTPU_SERVE_DB', str(db))
        rows = serve_state.get_replicas('svc')
        assert rows[0]['drain_started_at'] is None
        assert rows[0]['role'] == 'mixed'
        serve_state.set_replica_draining('svc', 1, 123.5)
        row = serve_state.get_replicas('svc')[0]
        assert row['status'] == ReplicaStatus.DRAINING.value
        assert row['drain_started_at'] == 123.5


# ------------------------------------------------- scale-down ordering


class TestRetirementOrder:

    def test_not_ready_first_then_newest(self):
        """The ISSUE 10 satellite fix: the old sort retired the OLDEST
        ready replica — the one with the warmest prefix cache."""
        pool = [
            {'replica_id': 1, 'status': 'READY'},
            {'replica_id': 4, 'status': 'READY'},
            {'replica_id': 2, 'status': 'STARTING'},
            {'replica_id': 3, 'status': 'READY'},
        ]
        order = [r['replica_id']
                 for r in controller_lib.retirement_order(pool)]
        assert order == [2, 4, 3, 1]

    def test_oldest_ready_survives_single_retire(self):
        pool = [{'replica_id': 1, 'status': 'READY'},
                {'replica_id': 2, 'status': 'READY'}]
        victim = controller_lib.retirement_order(pool)[0]
        assert victim['replica_id'] == 2


# ------------------------------------------------------- autoscaler


class TestWarmStart:

    def _scaler(self, **kw):
        kw.setdefault('min_replicas', 1)
        kw.setdefault('max_replicas', 5)
        kw.setdefault('target_qps_per_replica', 1.0)
        return autoscalers.RequestRateAutoscaler(_spec(**kw))

    def test_warm_start_adopts_live_count(self):
        scaler = self._scaler()
        assert scaler.target_num_replicas == 1
        scaler.warm_start(3)
        assert scaler.target_num_replicas == 3

    def test_warm_start_clamps_to_bounds(self):
        scaler = self._scaler(max_replicas=2)
        scaler.warm_start(7)
        assert scaler.target_num_replicas == 2
        scaler = self._scaler(min_replicas=2)
        scaler.warm_start(1)
        assert scaler.target_num_replicas == 2

    def test_warm_start_ignores_zero(self):
        scaler = self._scaler()
        scaler.target_num_replicas = 4
        scaler.warm_start(0)
        assert scaler.target_num_replicas == 4


# ------------------------------------------------------ drain monitor


class TestDrainMonitor:

    def test_idle_replica_drains_to_terminated(self):
        manager, _ = _make_manager('svc-idle')
        url, _, stop = _stub_replica(
            {'status': 'ok', 'draining': True,
             'engine': {'busy_slots': 0, 'slots': 2,
                        'queued_requests': 0}})
        try:
            rid = serve_state.allocate_replica('svc-idle', 'svc-idle')
            serve_state.set_replica_status(
                'svc-idle', rid, ReplicaStatus.READY, url=url)
            manager.scale_down(rid, drain=True, reason='scale_down')
            row = serve_state.get_replicas('svc-idle')[0]
            assert row['status'] == ReplicaStatus.DRAINING.value
            assert row['drain_started_at'] is not None
            # Idempotent: a second drain-retire is a no-op.
            manager.scale_down(rid, drain=True)
            manager.sync_draining()
            row = serve_state.get_replicas('svc-idle')[0]
            assert row['status'] == ReplicaStatus.TERMINATED.value
        finally:
            stop()
        names = [(e['event'], e.get('reason'))
                 for e in _serve_events()
                 if e['event'].startswith('replica_drain')]
        assert ('replica_drain_start', 'scale_down') in names
        assert ('replica_drain_end', 'drained') in names
        assert invariants.check(_serve_events(),
                                ['drain_no_lost_requests']) == []

    def test_busy_replica_waits_then_timeout_force_kill(
            self, monkeypatch):
        """A replica that never runs dry is force-killed at
        SKYTPU_SERVE_DRAIN_TIMEOUT_S — the bound that makes 'finish
        in-flight work' a promise, not a prayer."""
        monkeypatch.setenv('SKYTPU_SERVE_DRAIN_TIMEOUT_S', '0.3')
        manager, _ = _make_manager('svc-busy')
        url, _, stop = _stub_replica(
            {'status': 'ok', 'draining': True,
             'engine': {'busy_slots': 1, 'slots': 2,
                        'queued_requests': 3}})
        try:
            rid = serve_state.allocate_replica('svc-busy', 'svc-busy')
            serve_state.set_replica_status(
                'svc-busy', rid, ReplicaStatus.READY, url=url)
            manager.scale_down(rid, drain=True)
            manager.sync_draining()   # still busy, inside the window
            assert serve_state.get_replicas('svc-busy')[0]['status'] \
                == ReplicaStatus.DRAINING.value
            time.sleep(0.4)
            manager.sync_draining()
            assert serve_state.get_replicas('svc-busy')[0]['status'] \
                == ReplicaStatus.TERMINATED.value
        finally:
            stop()
        ends = [e for e in _serve_events()
                if e['event'] == 'replica_drain_end']
        assert ends and ends[-1]['reason'] == 'timeout'
        assert ends[-1]['inflight'] == 4

    def test_dead_replica_finishes_drain(self):
        manager, _ = _make_manager('svc-dead')
        rid = serve_state.allocate_replica('svc-dead', 'svc-dead')
        serve_state.set_replica_status(
            'svc-dead', rid, ReplicaStatus.READY,
            url='http://127.0.0.1:1')   # nothing listens here
        manager.scale_down(rid, drain=True)
        manager.sync_draining()
        assert serve_state.get_replicas('svc-dead')[0]['status'] == \
            ReplicaStatus.TERMINATED.value
        ends = [e for e in _serve_events()
                if e['event'] == 'replica_drain_end']
        assert ends and ends[-1]['reason'] == 'dead'

    def test_hard_paths_skip_drain(self):
        """Preemption/failure retirements never linger in DRAINING."""
        manager, _ = _make_manager('svc-hard')
        rid = serve_state.allocate_replica('svc-hard', 'svc-hard')
        serve_state.set_replica_status(
            'svc-hard', rid, ReplicaStatus.READY, url='http://x')
        manager.scale_down(rid,
                           final_status=ReplicaStatus.PREEMPTED)
        assert serve_state.get_replicas('svc-hard')[0]['status'] == \
            ReplicaStatus.PREEMPTED.value

    def test_preemption_warning_drains(self):
        manager, _ = _make_manager('svc-warn')
        url, _, stop = _stub_replica(
            {'status': 'ok', 'draining': True,
             'engine': {'busy_slots': 1, 'slots': 2,
                        'queued_requests': 0}})
        try:
            rid = serve_state.allocate_replica('svc-warn', 'svc-warn')
            serve_state.set_replica_status(
                'svc-warn', rid, ReplicaStatus.READY, url=url)
            manager.notify_preemption_warning(rid)
            row = serve_state.get_replicas('svc-warn')[0]
            assert row['status'] == ReplicaStatus.DRAINING.value
        finally:
            stop()
        starts = [e for e in _serve_events()
                  if e['event'] == 'replica_drain_start']
        assert starts[-1]['reason'] == 'preemption_warning'


# ------------------------------------------------- controller recovery


def _register_service(task, name):
    from skypilot_tpu.utils import common_utils
    yaml_dir = common_utils.ensure_dir(
        os.path.join(common_utils.skytpu_home(), 'serve'))
    yaml_path = os.path.join(yaml_dir, f'{name}.yaml')
    common_utils.dump_yaml(yaml_path, task.to_yaml_config())
    serve_state.add_service(name, task.service.to_yaml_config(),
                            yaml_path)


class TestControllerRecovery:

    def test_recover_fleet_adopts_and_warm_starts(self):
        task = sky.Task(name='svc-rec', run='sleep 1')
        task.set_resources(sky.Resources(cloud='local'))
        task.service = _spec(min_replicas=1, max_replicas=8,
                             target_qps_per_replica=1.0)
        _register_service(task, 'svc-rec')

        live_url, _, stop_live = _stub_replica({'status': 'ok'})
        flap_url, _, stop_flap = _stub_replica({'status': 'ok'})
        try:
            r1 = serve_state.allocate_replica('svc-rec', 'svc-rec')
            serve_state.set_replica_status(
                'svc-rec', r1, ReplicaStatus.READY, url=live_url)
            # NOT_READY but answering: adopted back to READY.
            r2 = serve_state.allocate_replica('svc-rec', 'svc-rec')
            serve_state.set_replica_status(
                'svc-rec', r2, ReplicaStatus.NOT_READY, url=flap_url)
            # READY but gone: demoted to NOT_READY (the probe loop
            # owns its fate — recovery never tears down).
            r3 = serve_state.allocate_replica('svc-rec', 'svc-rec')
            serve_state.set_replica_status(
                'svc-rec', r3, ReplicaStatus.READY,
                url='http://127.0.0.1:1')
            # Interrupted drain: resumed, not reset.
            r4 = serve_state.allocate_replica('svc-rec', 'svc-rec')
            serve_state.set_replica_status(
                'svc-rec', r4, ReplicaStatus.READY, url=live_url)
            serve_state.set_replica_draining('svc-rec', r4, 50.0)

            controller = controller_lib.SkyServeController('svc-rec')
            controller.recover_fleet()

            statuses = {r['replica_id']: r['status']
                        for r in serve_state.get_replicas('svc-rec')}
            assert statuses[r1] == 'READY'
            assert statuses[r2] == 'READY'
            assert statuses[r3] == 'NOT_READY'
            assert statuses[r4] == 'DRAINING'
            # Drain clock survived the restart.
            drain_row = [r for r in serve_state.get_replicas('svc-rec')
                         if r['replica_id'] == r4][0]
            assert drain_row['drain_started_at'] == 50.0
            # Warm start counts live non-draining replicas (3), not
            # min_replicas (1): no scale-to-min cliff.
            assert controller.autoscalers[
                'mixed'].target_num_replicas == 3
            recovered = [e for e in _serve_events()
                         if e['event'] == 'controller_recovered']
            assert recovered
            assert sorted(recovered[-1]['adopted']) == [r1, r2]
            assert recovered[-1]['draining_resumed'] == [r4]
            assert recovered[-1]['lost'] == [r3]
        finally:
            stop_live()
            stop_flap()


# --------------------------------------------------- LB control plane


class TestLBControlPlane:

    def test_retire_endpoint_drops_replica_now(self):
        lb = lb_lib.SkyServeLoadBalancer(
            'http://127.0.0.1:1',
            router=router_lib.Router(threshold=10_000))
        lb.set_replicas([{'url': 'http://127.0.0.1:11111'},
                         {'url': 'http://127.0.0.1:22222'}])
        port = lb.start()
        try:
            resp = requests.post(
                f'http://127.0.0.1:{port}/lb/retire',
                json={'url': 'http://127.0.0.1:11111'}, timeout=5)
            assert resp.status_code == 200
            assert resp.json()['retired'] is True
            assert lb.ready_urls == ['http://127.0.0.1:22222']
            assert [e.url for e in lb.router.endpoints()] == \
                ['http://127.0.0.1:22222']
            # Missing url -> 400; unknown control path -> 404 (never
            # proxied to a replica).
            assert requests.post(
                f'http://127.0.0.1:{port}/lb/retire', json={},
                timeout=5).status_code == 400
            assert requests.post(
                f'http://127.0.0.1:{port}/lb/nope', json={},
                timeout=5).status_code == 404
            metrics = requests.get(
                f'http://127.0.0.1:{port}/lb/metrics', timeout=5)
            assert metrics.status_code == 200
            assert 'skytpu_lb_controller_sync_age_seconds' in \
                metrics.text
            assert 'skytpu_lb_retired_total' in metrics.text
        finally:
            lb.stop()

    def test_retired_url_survives_stale_sync(self):
        """A sync payload that still carries a retired url (the race:
        retire nudge vs in-flight sync) must not resurrect it; once
        the controller's payload drops the url, the retired entry is
        forgotten so a future replica at the same address works."""
        payload = {'ready_replica_urls': ['http://a', 'http://b'],
                   'ready_replicas': [{'url': 'http://a'},
                                      {'url': 'http://b'}]}

        class Ctl(http.server.BaseHTTPRequestHandler):

            def do_POST(self):  # noqa: N802
                length = int(self.headers.get('Content-Length', 0))
                self.rfile.read(length)
                body = json.dumps(payload).encode()
                self.send_response(200)
                self.send_header('Content-Length', str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args):
                del args

        ctl = http.server.ThreadingHTTPServer(('127.0.0.1', 0), Ctl)
        threading.Thread(target=ctl.serve_forever, daemon=True).start()
        lb = lb_lib.SkyServeLoadBalancer(
            f'http://127.0.0.1:{ctl.server_address[1]}')
        try:
            lb._sync_with_controller()  # pylint: disable=protected-access
            assert sorted(lb.ready_urls) == ['http://a', 'http://b']
            lb.retire_url('http://a')
            assert lb.ready_urls == ['http://b']
            # Stale sync still lists http://a: stays excluded.
            lb._sync_with_controller()  # pylint: disable=protected-access
            assert lb.ready_urls == ['http://b']
            assert lb.sync_age() < 5.0
            # Controller catches up (drops the url): entry forgotten.
            payload['ready_replica_urls'] = ['http://b']
            payload['ready_replicas'] = [{'url': 'http://b'}]
            lb._sync_with_controller()  # pylint: disable=protected-access
            assert not lb._retired  # pylint: disable=protected-access
            # New replica at the old address is routable again.
            payload['ready_replica_urls'] = ['http://a', 'http://b']
            payload['ready_replicas'] = [{'url': 'http://a'},
                                         {'url': 'http://b'}]
            lb._sync_with_controller()  # pylint: disable=protected-access
            assert sorted(lb.ready_urls) == ['http://a', 'http://b']
        finally:
            ctl.shutdown()

    def test_sync_age_grows_without_controller(self):
        lb = lb_lib.SkyServeLoadBalancer('http://127.0.0.1:1')
        lb._last_sync_ok -= 100.0  # pylint: disable=protected-access
        assert lb.sync_age() >= 100.0

    def test_stale_warning_fires_once(self, monkeypatch):
        monkeypatch.setenv('SKYTPU_LB_SYNC_STALE_WARN_S', '0')
        warnings = []
        monkeypatch.setattr(
            lb_lib.logger, 'warning',
            lambda msg, *a, **k: warnings.append(str(msg)))
        lb = lb_lib.SkyServeLoadBalancer('http://127.0.0.1:1')
        lb._last_sync_ok -= 10.0  # pylint: disable=protected-access
        lb._sync_with_controller()  # pylint: disable=protected-access
        lb._sync_with_controller()  # pylint: disable=protected-access
        stale = [w for w in warnings if 'STALE' in w]
        assert len(stale) == 1
        assert lb._stale_warned is True  # pylint: disable=protected-access


# ------------------------------------------------------------- CLI bits


def test_rank_lag_column_helper():
    """`serve status --metrics` RANK LAG: max-min rank ticks from
    skytpu_slice_rank_ticks_total — a degraded-but-alive rank is
    visible before the gang fails (ROADMAP PR 9 follow-up)."""
    from skypilot_tpu import cli
    parsed = {'skytpu_slice_rank_ticks_total': {
        (('rank', '0'),): 100.0, (('rank', '1'),): 92.0}}
    assert cli._rank_lag(parsed) == '8'  # pylint: disable=protected-access
    assert cli._rank_lag({}) == '-'  # pylint: disable=protected-access
    assert cli._rank_lag(  # pylint: disable=protected-access
        {'skytpu_slice_rank_ticks_total': {(('rank', '0'),): 5.0}}) \
        == '-'


# --------------------------------------- engine: deadlines + drain 503


@pytest.fixture(scope='module')
def served():
    """One shared continuous-batching model server with BOTH fronts
    (threaded + async) — engine construction is the expensive part."""
    server = model_server_lib.ModelServer(
        'tiny', max_len=256, max_batch=2, continuous_batching=True,
        kv_pages=96, page_size=8, prefill_chunk=32)
    t_port, t_stop = model_server_lib.start_background(server)
    from skypilot_tpu.serve import async_server
    a_port, a_stop = async_server.start_background(server)
    yield server, f'http://127.0.0.1:{t_port}', \
        f'http://127.0.0.1:{a_port}'
    t_stop()
    a_stop()
    server.close()


def _raw_post(port: int, path: str, body: dict, headers=None):
    payload = json.dumps(body).encode()
    lines = [f'POST {path} HTTP/1.1', f'Host: 127.0.0.1:{port}',
             'Content-Type: application/json',
             f'Content-Length: {len(payload)}']
    lines += [f'{k}: {v}' for k, v in (headers or {}).items()]
    sock = socket.create_connection(('127.0.0.1', port), timeout=30)
    sock.sendall(('\r\n'.join(lines) + '\r\n\r\n').encode() + payload)
    return sock


class TestDeadlines:

    def test_deadline_expiry_frees_slots_and_pages(self, monkeypatch):
        """A reaped deadline must return the slot AND its KV pages —
        pool accounting proven by the PR 7 page_pool_balance invariant
        over the alloc/free journal."""
        import flax.linen as nn
        import jax
        import jax.numpy as jnp
        from skypilot_tpu.models import configs
        from skypilot_tpu.models.transformer import Transformer

        monkeypatch.setenv('SKYTPU_SERVE_PAGE_EVENTS', '1')
        t0 = time.time()
        cfg = configs.get_config('tiny')
        params = nn.meta.unbox(Transformer(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
            ['params'])
        # prefix_caching off: cached-prefix pins would legitimately
        # hold pages after the reap — this test wants the exact
        # "slot freed => pages freed" accounting.
        eng = batching_engine.ContinuousBatchingEngine(
            cfg, params, max_len=256, slots=1, prefill_chunk=32,
            kv_pages=64, page_size=8, prefix_caching=False)
        try:
            # Live reap: the deadline passes mid-prefill/mid-decode
            # (compile time alone exceeds it) after pages were
            # committed.
            request = eng.submit(list(range(1, 21)), 200,
                                 deadline_ms=300)
            with pytest.raises(batching_engine.DeadlineExceeded):
                request.result(timeout=60)
            deadline = time.time() + 10
            while time.time() < deadline and \
                    eng.stats()['kv_pages_used'] > 0:
                time.sleep(0.05)
            assert eng.stats()['kv_pages_used'] == 0
            assert eng.stats()['busy_slots'] == 0

            # Queued reap: a blocker pins the only slot; the deadlined
            # request fails fast from the queue, long before the
            # blocker finishes.
            blocker = eng.submit([1, 2, 3], 150)
            queued = eng.submit([4, 5, 6], 10, deadline_ms=100)
            with pytest.raises(batching_engine.DeadlineExceeded):
                queued.result(timeout=30)
            assert not blocker.done.is_set()
            blocker.cancel()
        finally:
            eng.stop()
        serve_events = [e for e in _serve_events()
                        if e.get('ts', 0) >= t0]
        assert any(e['event'] == 'kv_pages_alloc'
                   for e in serve_events)
        assert invariants.check(serve_events,
                                ['page_pool_balance']) == []

    def test_deadline_header_504_threaded(self, served):
        _, t_url, _ = served
        resp = requests.post(
            t_url + '/generate',
            json={'prompt_ids': [[1, 2, 3, 4]],
                  'max_new_tokens': 200},
            headers={router_lib.DEADLINE_HEADER: '120'}, timeout=60)
        assert resp.status_code == 504
        assert resp.json()['reason'] == 'deadline_exceeded'

    def test_deadline_header_504_async(self, served):
        # 250 tokens against a 60ms budget: even a fully jit-warm
        # engine (shared module fixture — earlier tests compile every
        # bucket) cannot finish before the reap, so the 504 is
        # deterministic, not a cold-compile artifact.
        _, _, a_url = served
        resp = requests.post(
            a_url + '/generate',
            json={'prompt_ids': [[5, 6, 7, 8]],
                  'max_new_tokens': 250},
            headers={router_lib.DEADLINE_HEADER: '60'}, timeout=60)
        assert resp.status_code == 504

    def test_env_default_deadline(self, monkeypatch):
        monkeypatch.setenv('SKYTPU_SERVE_DEFAULT_DEADLINE_MS', '2500')
        assert model_server_lib.default_deadline_ms() == 2500
        monkeypatch.setenv('SKYTPU_SERVE_DEFAULT_DEADLINE_MS', 'bogus')
        assert model_server_lib.default_deadline_ms() is None
        monkeypatch.delenv('SKYTPU_SERVE_DEFAULT_DEADLINE_MS')
        assert model_server_lib.default_deadline_ms() is None

    def test_lb_default_deadline_env(self, monkeypatch):
        monkeypatch.setenv('SKYTPU_LB_DEFAULT_DEADLINE_MS', '1500')
        assert lb_lib._default_deadline_ms() == 1500  # pylint: disable=protected-access
        monkeypatch.setenv('SKYTPU_LB_DEFAULT_DEADLINE_MS', '-1')
        assert lb_lib._default_deadline_ms() is None  # pylint: disable=protected-access


class TestDrainEndpoint:

    def test_drain_503s_both_fronts(self, served):
        server, t_url, a_url = served
        try:
            resp = requests.post(t_url + '/drain', json={}, timeout=10)
            assert resp.status_code == 200
            assert resp.json()['draining'] is True
            for url in (t_url, a_url):
                gen = requests.post(
                    url + '/generate',
                    json={'prompt_ids': [[1, 2, 3]],
                          'max_new_tokens': 4}, timeout=30)
                assert gen.status_code == 503
                assert 'Retry-After' in gen.headers
                health = requests.get(url + '/', timeout=10)
                assert health.json()['draining'] is True
            # kv_import refused while draining (pages would die with
            # the replica); /drain itself is idempotent.
            assert requests.post(
                t_url + '/kv_import', json={}, timeout=10
            ).status_code == 503
            assert requests.post(
                a_url + '/drain', json={},
                timeout=10).json()['draining'] is True
        finally:
            server.draining = False

    def test_drain_503_keeps_keepalive_framing(self, served):
        """The 503 must consume the request body: unread bytes would
        desync the NEXT request on a keep-alive connection."""
        server, t_url, _ = served
        port = int(t_url.rsplit(':', 1)[1])

        def read_response(sock):
            """One full HTTP response (status line, headers,
            content-length body) off the socket."""
            buf = b''
            while b'\r\n\r\n' not in buf:
                chunk = sock.recv(4096)
                assert chunk, f'connection closed early ({buf!r})'
                buf += chunk
            head, rest = buf.split(b'\r\n\r\n', 1)
            length = next(
                int(line.split(b':')[1])
                for line in head.split(b'\r\n')
                if line.lower().startswith(b'content-length'))
            while len(rest) < length:
                rest += sock.recv(4096)
            status = int(head.split(b' ', 2)[1])
            return status, rest[:length]

        try:
            requests.post(t_url + '/drain', json={}, timeout=10)
            sock = _raw_post(port, '/generate',
                             {'prompt_ids': [[1, 2, 3]],
                              'max_new_tokens': 4})
            status, body = read_response(sock)
            assert status == 503 and b'draining' in body
            # Second request on the SAME connection parses cleanly.
            payload = json.dumps({'prompt_ids': [[4, 5, 6]],
                                  'max_new_tokens': 4}).encode()
            sock.sendall((f'POST /generate HTTP/1.1\r\n'
                          f'Host: x\r\nContent-Type: application/json'
                          f'\r\nContent-Length: {len(payload)}\r\n\r\n'
                          ).encode() + payload)
            status, body = read_response(sock)
            assert status == 503 and b'draining' in body
            sock.close()
        finally:
            server.draining = False

    def test_inflight_finishes_during_drain(self, served):
        """The 503 gate is for NEW work only: a request already in the
        engine keeps decoding to completion."""
        server, t_url, _ = served
        request = server._engine.submit(  # pylint: disable=protected-access
            [9, 8, 7], 6)
        try:
            requests.post(t_url + '/drain', json={}, timeout=10)
            assert request.result(timeout=60) is not None
            assert len(request.tokens) == 6
        finally:
            server.draining = False


class TestDisconnectReap:

    def _assert_reaped(self, server, rid):
        deadline = time.time() + 15
        span = None
        while time.time() < deadline:
            span = server._engine.span(rid)  # pylint: disable=protected-access
            if span is not None:
                break
            time.sleep(0.1)
        assert span is not None, 'request never finished after hangup'
        assert span['status'] == 'cancelled'
        deadline = time.time() + 10
        while time.time() < deadline and \
                server._engine.stats()['busy_slots'] > 0:  # pylint: disable=protected-access
            time.sleep(0.05)
        assert server._engine.stats()['busy_slots'] == 0  # pylint: disable=protected-access

    def _hang_up(self, server, port, rid, headers, monkeypatch):
        # Ticks of at least 3 ms (the tiny model's take well under one
        # on an idle host): the 220 tokens then outlast the fronts'
        # disconnect poll (0.1 s), so the request cannot finish before
        # the hang-up is seen.
        engine = server._engine  # pylint: disable=protected-access
        step = engine._dispatch_step  # pylint: disable=protected-access

        def slow_step():
            time.sleep(0.003)
            return step()

        monkeypatch.setattr(engine, '_dispatch_step', slow_step)
        sock = _raw_post(port, '/generate',
                         {'prompt_ids': [[11, 12, 13, 14]],
                          'max_new_tokens': 220},
                         headers=headers)
        # Let the request admit (slot goes busy), then vanish.
        deadline = time.time() + 20
        while time.time() < deadline:
            if server._engine.stats()['busy_slots'] > 0:  # pylint: disable=protected-access
                break
            time.sleep(0.05)
        sock.close()
        self._assert_reaped(server, rid)

    def test_threaded_front_reaps_on_hangup(self, served, monkeypatch):
        server, t_url, _ = served
        port = int(t_url.rsplit(':', 1)[1])
        self._hang_up(server, port, 'disc-threaded-1',
                      {'X-SkyTPU-Request-Id': 'disc-threaded-1'},
                      monkeypatch)

    def test_async_front_reaps_on_hangup(self, served, monkeypatch):
        server, _, a_url = served
        port = int(a_url.rsplit(':', 1)[1])
        self._hang_up(server, port, 'disc-async-1',
                      {'X-SkyTPU-Request-Id': 'disc-async-1',
                       'Connection': 'close'}, monkeypatch)


# ------------------------------------------------------------ role morph


class TestRoleMorph:
    """ISSUE 17 state layer: the DB role column tracks live morphs,
    and a failed budget commit rolls the replica back instead of
    wedging it DRAINING."""

    def test_set_replica_role_pins_db(self):
        serve_state.add_service('svc-role', spec_json={},
                                task_yaml_path='')
        rid = serve_state.allocate_replica('svc-role', 'svc-role',
                                           role='prefill')
        assert serve_state.get_replicas('svc-role')[0]['role'] == \
            'prefill'
        serve_state.set_replica_role('svc-role', rid, 'decode')
        assert serve_state.get_replicas('svc-role')[0]['role'] == \
            'decode'

    def test_morph_rollback_when_budget_push_not_applied(self):
        """The stub accepts /drain and /role_budget but never answers
        `applied: true` -> the commit fails, the morph journals
        status=error, and the replica is re-opened READY in its OLD
        role (never stuck DRAINING)."""
        manager, _ = _make_manager('svc-morph')
        url, _set, shutdown = _stub_replica(
            {'status': 'ok', 'engine': {'busy_slots': 0,
                                        'queued_requests': 0}})
        try:
            rid = serve_state.allocate_replica('svc-morph',
                                               'svc-morph',
                                               role='prefill')
            serve_state.set_replica_status(
                'svc-morph', rid, ReplicaStatus.READY, url=url)
            t0 = time.time()
            assert manager.morph_replica(rid, 'decode',
                                         timeout_s=2) is False
            row = serve_state.get_replicas('svc-morph')[0]
            assert row['status'] == ReplicaStatus.READY.value
            assert row['role'] == 'prefill'  # commit never landed
            ends = [e for e in _serve_events()
                    if e.get('ts', 0) >= t0 and
                    e.get('event') == 'role_morph_end']
            assert len(ends) == 1
            assert ends[0]['status'] == 'error'
            assert (ends[0]['from_role'], ends[0]['to_role']) == \
                ('prefill', 'decode')
        finally:
            shutdown()

    def test_morph_noops(self):
        """Same-role and non-READY morphs are refused outright —
        before the drain machinery ever engages."""
        manager, _ = _make_manager('svc-noop')
        rid = serve_state.allocate_replica('svc-noop', 'svc-noop',
                                           role='decode')
        serve_state.set_replica_status(
            'svc-noop', rid, ReplicaStatus.READY,
            url='http://127.0.0.1:1')
        assert manager.morph_replica(rid, 'decode') is False
        serve_state.set_replica_status('svc-noop', rid,
                                       ReplicaStatus.STARTING)
        assert manager.morph_replica(rid, 'prefill') is False
