"""Slice-serving runtime tests (ISSUE 9).

The load-bearing claims:

- a 2-host EMULATED sharded replica (weights + KV pool over the slice
  mesh, coordinated ticks, sequence-parallel prefill) is TOKEN-EXACT
  against the single-process engine — float and int8-KV pools, greedy
  and sampled;
- the rank protocol degrades a slice AS A UNIT: one dead rank fails
  the engine, /health turns 503 with slice.degraded, and the replica
  manager retires the replica;
- the degenerate mesh fix (ops/sp_common): ring/ulysses attention run
  on a mesh whose sequence axis is size 1 — or absent — through the
  same code path (the regression the `num_hosts: 1` slice needs);
- `num_hosts` flows end to end: service_spec roles -> scale_up env ->
  serve_state column (additive migration; old DBs load cleanly).
"""
from __future__ import annotations

import socket
import sqlite3
import threading
import time

import pytest

from skypilot_tpu.serve import batching_engine
from skypilot_tpu.serve import coordinator as coordinator_lib
from skypilot_tpu.serve import slice_replica


@pytest.fixture(scope='module')
def tiny():
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from skypilot_tpu.models import configs
    from skypilot_tpu.models.transformer import Transformer
    cfg = configs.get_config('tiny')
    params = nn.meta.unbox(Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))['params'])
    return cfg, params


_PROMPTS = [list(range(1, 49)),          # spans the sp threshold
            list(range(5, 70)),          # longer, odd length
            [3, 1, 4, 1, 5]]             # short (chunked path)


def _sampling():
    from skypilot_tpu.models import decode
    return decode.SamplingConfig(temperature=0.8, top_k=8, seed=7)


def _outputs(engine):
    """Greedy + sampled generations for the standard prompt set."""
    greedy = [engine.generate(p, 8, timeout=120) for p in _PROMPTS]
    sampled = [engine.generate(p, 8, sampling=_sampling(), timeout=120)
               for p in _PROMPTS]
    return greedy, sampled


# ------------------------------------------------------------ mesh layout


class TestSliceAxes:

    def test_default_prefers_tensor_then_sequence(self, tiny):
        cfg, _ = tiny                       # tiny: n_kv_heads=2
        assert slice_replica.slice_axes(1, cfg) == {
            'sequence': 1, 'tensor': 1}
        assert slice_replica.slice_axes(2, cfg) == {
            'sequence': 1, 'tensor': 2}
        # n_kv_heads=2 caps tensor at 2; the rest rides 'sequence'.
        assert slice_replica.slice_axes(4, cfg) == {
            'sequence': 2, 'tensor': 2}
        assert slice_replica.slice_axes(8, cfg) == {
            'sequence': 4, 'tensor': 2}

    def test_pinned_factors(self, tiny):
        cfg, _ = tiny
        assert slice_replica.slice_axes(4, cfg, sequence=4) == {
            'sequence': 4, 'tensor': 1}
        assert slice_replica.slice_axes(4, cfg, tensor=1) == {
            'sequence': 4, 'tensor': 1}
        with pytest.raises(ValueError, match='must equal'):
            slice_replica.slice_axes(4, cfg, sequence=2, tensor=3)
        with pytest.raises(ValueError, match='divide'):
            slice_replica.slice_axes(4, cfg, sequence=3)
        with pytest.raises(ValueError, match='n_kv_heads'):
            slice_replica.slice_axes(4, cfg, tensor=4)

    def test_mesh_device_bound(self, tiny):
        cfg, _ = tiny
        with pytest.raises(ValueError, match='devices'):
            slice_replica.build_slice_mesh(64, cfg)


# --------------------------------------------- degenerate sequence meshes


class TestSequenceParallelDegenerate:
    """ops/sp_common satellite: the SAME SP code path must run on a
    mesh whose sequence axis is size 1 (single-host slice) or absent —
    previously both wrappers required `jax.shard_map` (jax 0.6+) and a
    non-trivial axis."""

    def _qkv(self):
        import jax
        import jax.numpy as jnp
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 16, 8),
                              jnp.float32)
        k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 16, 8),
                              jnp.float32)
        v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 16, 8),
                              jnp.float32)
        return q, k, v

    @pytest.mark.parametrize('kind', ['ring', 'ulysses'])
    def test_sequence_axis_size_one(self, kind):
        import jax.numpy as jnp

        from skypilot_tpu.ops.attention import flash_attention
        from skypilot_tpu.ops.ring_attention import ring_attention
        from skypilot_tpu.ops.ulysses_attention import ulysses_attention
        from skypilot_tpu.parallel import mesh as mesh_lib
        import jax
        q, k, v = self._qkv()
        ref = flash_attention(q, k, v, causal=True)
        mesh = mesh_lib.build_mesh(
            mesh_lib.MeshConfig(sequence=1, tensor=2),
            devices=jax.devices()[:2])
        fn = ring_attention if kind == 'ring' else ulysses_attention
        out = fn(q, k, v, mesh=mesh)
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-5

    @pytest.mark.parametrize('kind', ['ring', 'ulysses'])
    def test_mesh_without_sequence_axis(self, kind):
        import numpy as np

        import jax
        import jax.numpy as jnp

        from skypilot_tpu.ops.attention import flash_attention
        from skypilot_tpu.ops.ring_attention import ring_attention
        from skypilot_tpu.ops.ulysses_attention import ulysses_attention
        q, k, v = self._qkv()
        ref = flash_attention(q, k, v, causal=True)
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]),
                                 ('tensor',))
        fn = ring_attention if kind == 'ring' else ulysses_attention
        out = fn(q, k, v, mesh=mesh)
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-5

    def test_real_split_still_exact(self):
        import jax
        import jax.numpy as jnp

        from skypilot_tpu.ops.attention import flash_attention
        from skypilot_tpu.ops.ring_attention import ring_attention
        from skypilot_tpu.parallel import mesh as mesh_lib
        q, k, v = self._qkv()
        ref = flash_attention(q, k, v, causal=True)
        mesh = mesh_lib.build_mesh(mesh_lib.MeshConfig(sequence=4),
                                   devices=jax.devices()[:4])
        out = ring_attention(q, k, v, mesh=mesh)
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-5

    def test_sp_degree(self):
        import numpy as np

        import jax

        from skypilot_tpu.ops import sp_common
        from skypilot_tpu.parallel import mesh as mesh_lib
        mesh = mesh_lib.build_mesh(mesh_lib.MeshConfig(sequence=2),
                                   devices=jax.devices()[:2])
        assert sp_common.sp_degree(mesh, 'sequence') == 2
        bare = jax.sharding.Mesh(np.array(jax.devices()[:1]),
                                 ('tensor',))
        assert sp_common.sp_degree(bare, 'sequence') == 1
        assert sp_common.sp_degree(None, 'sequence') == 1


# --------------------------------------------------------- rank protocol


class TestCoordinator:

    def test_local_broadcast_and_stats(self):
        coord = coordinator_lib.SliceCoordinator(3)
        try:
            for _ in range(4):
                coord.tick()
            coord.broadcast(coordinator_lib.CMD_ADMIT, slot=1, tokens=9)
            stats = coord.stats()
            assert stats['num_hosts'] == 3
            assert stats['ranks_alive'] == 3
            assert stats['degraded'] is False
            assert stats['sync_count'] == 5
            assert stats['sync_ms_mean'] > 0
        finally:
            coord.close()

    def test_follower_exception_is_rank_death_as_a_unit(self):
        executed = []

        def boom(cmd):
            executed.append(cmd.kind)
            if len(executed) >= 3:
                raise RuntimeError('host OOM')

        coord = coordinator_lib.SliceCoordinator(
            2, channels=[coordinator_lib.LocalRank(1, executor=boom)])
        try:
            coord.tick()
            coord.tick()
            with pytest.raises(coordinator_lib.RankDead) as err:
                coord.tick()
            assert err.value.rank == 1
            assert coord.degraded and coord.dead_ranks == [1]
            # Every later command fails fast: a half-dead slice never
            # half-serves.
            with pytest.raises(coordinator_lib.RankDead):
                coord.tick()
        finally:
            coord.close()

    def test_ack_timeout_is_rank_death(self):
        def hang(cmd):
            del cmd
            time.sleep(5)

        coord = coordinator_lib.SliceCoordinator(
            2, channels=[coordinator_lib.LocalRank(1, executor=hang)],
            ack_timeout=0.2)
        try:
            with pytest.raises(coordinator_lib.RankDead,
                               match='timeout'):
                coord.tick()
        finally:
            coord.close()

    def test_tcp_follower_roundtrip(self):
        """The REAL-slice transport: commands out, acks back, shutdown
        ends the follower loop."""
        a, b = socket.socketpair()
        seen = []
        follower = threading.Thread(
            target=coordinator_lib.follower_serve,
            args=(b, 1, lambda cmd: seen.append((cmd.kind, cmd.seq))),
            daemon=True)
        follower.start()
        coord = coordinator_lib.SliceCoordinator(
            2, channels=[coordinator_lib.TcpRank(1, a)])
        coord.tick()
        coord.broadcast(coordinator_lib.CMD_PREFILL, tokens=128)
        assert coord.stats()['sync_count'] == 2
        coord.close()
        follower.join(timeout=5)
        assert not follower.is_alive()
        assert seen == [(coordinator_lib.CMD_TICK, 1),
                        (coordinator_lib.CMD_PREFILL, 2),
                        (coordinator_lib.CMD_SHUTDOWN, 3)]

    def test_tcp_disconnect_is_rank_death(self):
        a, b = socket.socketpair()
        coord = coordinator_lib.SliceCoordinator(
            2, channels=[coordinator_lib.TcpRank(1, a)],
            ack_timeout=5.0)
        b.close()   # the follower host vanished
        with pytest.raises(coordinator_lib.RankDead):
            coord.tick()
        coord.close()

    def test_command_json_roundtrip(self):
        cmd = coordinator_lib.Command(kind='admit', seq=7,
                                      payload={'slot': 2, 'tokens': 33})
        back = coordinator_lib.Command.from_json(cmd.to_json())
        assert (back.kind, back.seq, back.payload) == (
            'admit', 7, {'slot': 2, 'tokens': 33})


# ----------------------------------------------- sequence-parallel prefill


class TestPrefillSp:

    @pytest.mark.parametrize('name', ['tiny', 'tiny-moe'])
    def test_matches_flash_prefill(self, tiny, name):
        """The dense block, and an expert model: its layer drops no
        token, so a prompt split over the sequence axis routes as it
        does whole, and both prefill paths carry the one stream dtype."""
        import flax.linen as nn
        import jax
        import jax.numpy as jnp

        from skypilot_tpu.models import configs
        from skypilot_tpu.models import decode
        from skypilot_tpu.models.transformer import Transformer
        cfg, params = tiny
        if name != 'tiny':
            cfg = configs.get_config(name)
            params = nn.meta.unbox(Transformer(cfg).init(
                jax.random.PRNGKey(0),
                jnp.zeros((1, 8), jnp.int32))['params'])
        prompt = jnp.asarray([list(range(1, 49))], jnp.int32)
        _, ref = decode.prefill(cfg, params, prompt, max_len=64)
        mesh = slice_replica.build_slice_mesh(2, cfg, sequence=2)
        sp_cache = jax.jit(lambda p, t: decode.prefill_sp(
            cfg, p, t, mesh=mesh, max_len=64))(params, prompt)
        assert int(sp_cache['index']) == 48
        for leaf in ('k', 'v'):
            got = jnp.asarray(sp_cache[leaf], jnp.float32)[..., :48, :]
            want = jnp.asarray(ref[leaf], jnp.float32)[..., :48, :]
            assert float(jnp.max(jnp.abs(got - want))) < 1e-4

    def test_layer_pattern_rejected(self, tiny):
        """Ring attention has no window: a model with a layer pattern
        takes the chunked path (expert models no longer do: their
        layer drops no token, so a split prompt routes as a whole)."""
        import dataclasses

        import jax.numpy as jnp

        from skypilot_tpu.models import decode
        cfg, params = tiny
        win_cfg = dataclasses.replace(cfg, layer_pattern=('window',),
                                      sliding_window=4)
        mesh = slice_replica.build_slice_mesh(2, cfg, sequence=2)
        with pytest.raises(ValueError, match='layer_pattern'):
            decode.prefill_sp(win_cfg, params,
                              jnp.zeros((1, 8), jnp.int32),
                              mesh=mesh, max_len=64)


# ------------------------------------------------------- token exactness


class TestSliceEngineExactness:

    def test_two_host_token_exact(self, tiny):
        """2-host emulated sharded replica (default layout: tensor=2)
        vs the single-process engine — float KV pool, greedy AND
        sampled, with the SP one-shot prefill on the long prompts."""
        cfg, params = tiny
        ref = batching_engine.ContinuousBatchingEngine(
            cfg, params, max_len=128, slots=2, prefill_chunk=16,
            kv_pages=48, page_size=8)
        try:
            want = _outputs(ref)
        finally:
            ref.stop()
        eng = slice_replica.SliceReplicaEngine(
            cfg, params, num_hosts=2, sp_threshold=32, max_len=128,
            slots=2, prefill_chunk=16, kv_pages=48, page_size=8)
        try:
            got = _outputs(eng)
            stats = eng.stats()
        finally:
            eng.stop()
        assert got == want
        assert stats['num_hosts'] == 2
        assert stats['slice']['tensor_degree'] == 2
        # The two long prompts went through the one-shot SP prefill
        # on first encounter; the sampled pass reuses their pages via
        # the prefix cache, and the short prompt stayed chunked.
        assert stats['slice']['sp_prefills'] == 2
        assert stats['slice']['sync_count'] > 0
        # The span records the coordinated-tick overhead.
        spans = stats['recent_spans']
        assert all('slice_sync_ms' in s for s in spans)

    def test_two_host_expert_model_token_exact(self):
        """An expert model on the slice replica: the long prompt takes
        the one-shot SP prefill (sequence=2, a real ring split), the
        short one the chunked path, and both give the single-process
        engine's tokens."""
        import flax.linen as nn
        import jax
        import jax.numpy as jnp

        from skypilot_tpu.models import configs
        from skypilot_tpu.models.transformer import Transformer
        cfg = configs.get_config('tiny-moe')
        params = nn.meta.unbox(Transformer(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))['params'])
        kw = dict(max_len=128, slots=2, prefill_chunk=16, kv_pages=48,
                  page_size=8)
        prompts = (_PROMPTS[0], _PROMPTS[2])
        ref = batching_engine.ContinuousBatchingEngine(cfg, params, **kw)
        try:
            want = [ref.generate(p, 8, timeout=120) for p in prompts]
        finally:
            ref.stop()
        eng = slice_replica.SliceReplicaEngine(
            cfg, params, num_hosts=2, sequence=2, sp_threshold=32, **kw)
        try:
            got = [eng.generate(p, 8, timeout=120) for p in prompts]
            stats = eng.stats()
        finally:
            eng.stop()
        assert got == want
        assert stats['slice']['sp_prefills'] == 1

    def test_two_host_sequence_axis_int8_kv_token_exact(self, tiny):
        """sequence=2 layout (real ring split) + int8 KV pages: still
        token-exact vs the single-process int8 engine."""
        cfg, params = tiny
        ref = batching_engine.ContinuousBatchingEngine(
            cfg, params, max_len=128, slots=2, prefill_chunk=16,
            kv_pages=48, page_size=8, quantize_kv=True)
        try:
            want = _outputs(ref)
        finally:
            ref.stop()
        eng = slice_replica.SliceReplicaEngine(
            cfg, params, num_hosts=2, sequence=2, sp_threshold=32,
            max_len=128, slots=2, prefill_chunk=16, kv_pages=48,
            page_size=8, quantize_kv=True)
        try:
            got = _outputs(eng)
            stats = eng.stats()
        finally:
            eng.stop()
        assert got == want
        assert stats['slice']['sp_degree'] == 2
        assert stats['slice']['sp_prefills'] == 2


# ------------------------------------------------------------ rank death


class TestRankDeath:

    def test_rank_death_fails_replica_as_a_unit(self, tiny):
        from skypilot_tpu.chaos import faults as faults_lib
        from skypilot_tpu.chaos import injector
        cfg, params = tiny
        plan = faults_lib.FaultPlan(
            seed=0, name='t',
            faults=[faults_lib.Fault(site='serve.rank_exec',
                                     effect='raise',
                                     where={'rank': 1}, nth=[6])])
        injector.arm(plan)
        eng = slice_replica.SliceReplicaEngine(
            cfg, params, num_hosts=2, sp_threshold=10_000,
            max_len=128, slots=2, prefill_chunk=16)
        try:
            with pytest.raises(RuntimeError, match='rank 1 died'):
                eng.generate(list(range(1, 30)), 20, timeout=60)
            stats = eng.stats()
            assert stats['failed'] is True
            assert stats['slice']['degraded'] is True
            assert stats['slice']['dead_ranks'] == [1]
            # Submits after the death fail fast, like any dead engine.
            with pytest.raises(RuntimeError):
                eng.submit([1, 2, 3], 4)
        finally:
            eng.stop()
            injector.disarm()


# ----------------------------------------------------- num_hosts plumbing


class TestNumHostsPlumbing:

    def test_role_pool_num_hosts_round_trip(self):
        from skypilot_tpu import exceptions
        from skypilot_tpu.serve.service_spec import SkyServiceSpec
        spec = SkyServiceSpec.from_yaml_config({
            'roles': {
                'decode': {'replicas': 2, 'num_hosts': 4},
                'prefill': {'replicas': 1},
            }})
        assert spec.role_specs['decode'].num_hosts == 4
        assert spec.role_specs['prefill'].num_hosts == 1
        back = SkyServiceSpec.from_yaml_config(spec.to_yaml_config())
        assert back.role_specs['decode'].num_hosts == 4
        with pytest.raises(exceptions.InvalidTaskError,
                           match='num_hosts'):
            SkyServiceSpec(roles={'decode': {'replicas': 1,
                                             'num_hosts': 0}})

    def test_serve_state_num_hosts_column_and_migration(
            self, monkeypatch, tmp_path):
        """Old DBs (no num_hosts / no role column) load cleanly; new
        rows persist the slice width."""
        from skypilot_tpu.serve import serve_state
        db = tmp_path / 'serve.db'
        monkeypatch.setenv('SKYTPU_SERVE_DB', str(db))
        # Build a PRE-slice (and pre-role) schema by hand.
        conn = sqlite3.connect(str(db))
        conn.execute("""CREATE TABLE replicas (
            service_name TEXT, replica_id INTEGER, cluster_name TEXT,
            status TEXT, url TEXT, is_spot INTEGER DEFAULT 0,
            version INTEGER DEFAULT 1, launched_at REAL,
            PRIMARY KEY (service_name, replica_id))""")
        conn.execute(
            'INSERT INTO replicas (service_name, replica_id, '
            "cluster_name, status) VALUES ('svc', 1, 'svc-1', 'READY')")
        conn.commit()
        conn.close()
        rows = serve_state.get_replicas('svc')
        assert rows[0]['num_hosts'] == 1      # migrated default
        assert rows[0]['role'] == 'mixed'
        rid = serve_state.allocate_replica('svc', 'svc', role='decode',
                                           num_hosts=4)
        row = [r for r in serve_state.get_replicas('svc')
               if r['replica_id'] == rid][0]
        assert row['num_hosts'] == 4

    def test_scale_up_threads_num_hosts_env(self, monkeypatch):
        """scale_up(num_hosts=N) lands SKYTPU_SERVE_REPLICA_NUM_HOSTS
        in the replica env and widens the replica cluster to N nodes."""
        import skypilot_tpu as sky
        from skypilot_tpu.serve import replica_managers
        from skypilot_tpu.serve import serve_state
        from skypilot_tpu.serve import service_spec

        captured = {}

        def fake_launch(task, **kwargs):
            captured['envs'] = dict(task.envs)
            captured['num_nodes'] = task.num_nodes
            raise sky.exceptions.SkyTpuError('stop here')

        monkeypatch.setattr('skypilot_tpu.execution.launch',
                            fake_launch)
        spec = service_spec.SkyServiceSpec()
        task = sky.Task(name='t', run='true')
        task.set_resources(sky.Resources(cloud='local'))
        serve_state.add_service('svc-nh', spec_json={},
                                task_yaml_path='')
        manager = replica_managers.ReplicaManager('svc-nh', spec, task)
        rid = manager.scale_up(role='decode', num_hosts=2)
        deadline = time.time() + 10
        while 'envs' not in captured and time.time() < deadline:
            time.sleep(0.05)
        assert captured['envs'][
            replica_managers.ENV_REPLICA_NUM_HOSTS] == '2'
        assert captured['envs'][
            replica_managers.ENV_REPLICA_ROLE] == 'decode'
        assert captured['num_nodes'] == 2
        row = serve_state.get_replicas('svc-nh')[0]
        assert row['replica_id'] == rid and row['num_hosts'] == 2


# ----------------------------------------------------- through the real LB


def _serve_and_compare(tiny, num_hosts, **slice_kwargs):
    """One slice-replica model server + one single-process reference
    behind the REAL LB: tokens through the LB must match the reference
    exactly."""
    import requests

    from skypilot_tpu.serve import load_balancer as lb_lib
    from skypilot_tpu.serve import model_server as model_server_lib
    from skypilot_tpu.serve import router as router_lib
    del tiny
    slice_server = model_server_lib.ModelServer(
        'tiny', max_len=64, max_batch=2, continuous_batching=True,
        prefill_chunk=16, kv_pages=48, page_size=8,
        num_hosts=num_hosts, **slice_kwargs)
    reference = model_server_lib.ModelServer(
        'tiny', max_len=64, max_batch=2, continuous_batching=True,
        prefill_chunk=16, kv_pages=48, page_size=8)
    lb = lb_lib.SkyServeLoadBalancer(
        'http://127.0.0.1:1',
        router=router_lib.Router(threshold=10_000))
    stop = None
    try:
        port, stop = model_server_lib.start_background(slice_server)
        lb.set_replicas([{'url': f'http://127.0.0.1:{port}',
                          'role': 'mixed'}])
        lb_port = lb.start()
        for prompt in ([1, 2, 3, 4, 5], list(range(1, 45))):
            resp = requests.post(
                f'http://127.0.0.1:{lb_port}/generate',
                json={'prompt_ids': [prompt], 'max_new_tokens': 6},
                timeout=120)
            assert resp.status_code == 200
            assert resp.json()['tokens'] == reference.generate(
                [prompt], 6)
        health = requests.get(f'http://127.0.0.1:{port}/', timeout=10)
        payload = health.json()
        assert payload['num_hosts'] == num_hosts
        assert payload['slice']['ranks_alive'] == num_hosts
    finally:
        lb.stop()
        if stop is not None:
            stop()
        slice_server.close()
        reference.close()


def test_two_host_through_lb_token_exact(tiny):
    _serve_and_compare(tiny, num_hosts=2, sp_threshold=24)


def test_four_host_through_lb_token_exact(tiny):
    # 4 hosts factor as sequence=2 x tensor=2 for tiny.
    _serve_and_compare(tiny, num_hosts=4, sp_threshold=24)


# -------------------------------------------------- follower executors


class TestFollowerExecutor:
    """Real-slice followers execute the command log against their own
    devices: replaying rank 0's broadcasts through a FollowerExecutor
    must mirror the engine's device state — the gang contract a real
    multi-host slice rests on."""

    GEOM = dict(max_len=64, slots=2, prefill_chunk=8, kv_pages=48,
                page_size=8)

    def _run(self, tiny, spec_tokens):
        import numpy as np
        cfg, params = tiny
        follower = slice_replica.FollowerExecutor(cfg, params,
                                                  **self.GEOM)
        chan = coordinator_lib.LocalRank(1, follower)
        eng = slice_replica.SliceReplicaEngine(
            cfg, params, num_hosts=2, rank_channels=[chan],
            spec_tokens=spec_tokens, **self.GEOM)
        try:
            outs = [eng.generate(p, n, timeout=300)
                    for p, n in (([3, 1, 4, 1, 5, 9, 2, 6], 8),
                                 ([7], 4), (list(range(1, 25)), 6))]
            # Broadcasts ack synchronously, so the follower has fully
            # executed the log: its sampler state and block tables
            # must equal rank 0's BIT-FOR-BIT (same jitted ops, same
            # order), and the KV pool must match to float rounding
            # (rank 0 computes under the slice mesh, the follower
            # unsharded).
            for k in eng._state:
                assert np.array_equal(np.asarray(eng._state[k]),
                                      np.asarray(follower._state[k])), k
            for k in ('block_tables', 'lengths'):
                assert np.array_equal(
                    np.asarray(eng._cache[k]),
                    np.asarray(follower._cache[k])), k
            a, b = eng._cache['k'], follower._cache['k']
            diff = np.abs(np.asarray(a, np.float32) -
                          np.asarray(b, np.float32)).max()
            assert diff < 1e-3, diff
            assert follower._commands > 0
        finally:
            eng.stop()
        return outs

    def test_follower_mirrors_engine_state(self, tiny):
        self._run(tiny, spec_tokens=0)

    def test_follower_mirrors_spec_ticks(self, tiny):
        """Draft batches ride the TICK broadcast: a spec engine's
        follower dispatches the identical verify steps and lands in
        the identical state — and outputs stay byte-identical to the
        non-spec slice."""
        assert self._run(tiny, spec_tokens=0) == \
            self._run(tiny, spec_tokens=3)

    def test_without_kv_pages_both_ranks_derive_the_pool(self, tiny):
        """No `kv_pages`: rank 0 and a follower work the same pool out
        of the geometry, the slice runs the paged tick, and the
        follower's replay lands in rank 0's pages."""
        import jax.numpy as jnp
        import numpy as np

        from skypilot_tpu.models import decode
        cfg, params = tiny
        geom = {k: v for k, v in self.GEOM.items() if k != 'kv_pages'}
        follower = slice_replica.FollowerExecutor(cfg, params, **geom)
        chan = coordinator_lib.LocalRank(1, follower)
        eng = slice_replica.SliceReplicaEngine(
            cfg, params, num_hosts=2, rank_channels=[chan], **geom)
        prompt = [3, 1, 4, 1, 5, 9, 2, 6]
        try:
            got = eng.generate(prompt, 6, timeout=300)
            stats = eng.stats()
            assert stats['kv_pages_total'] == (
                geom['slots'] * geom['max_len'] // geom['page_size'])
            assert stats['kv_pages_used'] == stats['prefix_cache_entries']
            assert eng._cache['k'].shape == follower._cache['k'].shape
            assert eng._step.__wrapped__.__name__ == 'paged_engine_step'
            diff = np.abs(
                np.asarray(eng._cache['k'], np.float32) -
                np.asarray(follower._cache['k'], np.float32)).max()
            assert diff < 1e-3, diff
        finally:
            eng.stop()
        _, want = decode.generate(
            cfg, params, jnp.asarray([prompt], jnp.int32),
            max_new_tokens=6, max_len=geom['max_len'])
        assert got == [int(t) for t in np.asarray(want)[0]]

    def test_follower_builds_the_pool_rank_0_builds(self, monkeypatch):
        """Where the kernel's rows are 128 lanes (a TPU), two heads of
        64 share a row on one device but not under the slice mesh: the
        follower's pool takes rank 0's layout, one head a row."""
        import flax.linen as nn
        import jax
        import jax.numpy as jnp

        from skypilot_tpu.models import configs
        from skypilot_tpu.models import decode
        from skypilot_tpu.models.transformer import Transformer
        from skypilot_tpu.ops import paged_attention as paged_attention_lib
        cfg = configs.get_config('tiny', d_model=128, n_heads=2)
        assert (cfg.head_dim, cfg.n_kv_heads) == (64, 2)
        params = nn.meta.unbox(Transformer(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))['params'])
        monkeypatch.setattr(paged_attention_lib, 'pool_head_lanes',
                            lambda kernel: 128)
        alone = decode.init_paged_cache(cfg, 48, 8, 2, 8, head_lanes=128)
        assert decode.heads_per_row(cfg, alone['k']) == 2
        follower = slice_replica.FollowerExecutor(cfg, params,
                                                  **self.GEOM)
        eng = slice_replica.SliceReplicaEngine(
            cfg, params, num_hosts=2,
            rank_channels=[coordinator_lib.LocalRank(1, follower)],
            **self.GEOM)
        try:
            assert eng._cache['k'].shape == follower._cache['k'].shape == (
                cfg.n_layers, self.GEOM['kv_pages'], 2, 8, 128)
            assert decode.heads_per_row(cfg, follower._cache['k']) == 1
            assert eng.stats()['paged_kernel']['heads_per_row'] == 1
        finally:
            eng.stop()

    def test_follower_release_parks_tables(self, tiny):
        import numpy as np
        cfg, params = tiny
        follower = slice_replica.FollowerExecutor(cfg, params,
                                                  **self.GEOM)
        chan = coordinator_lib.LocalRank(1, follower)
        eng = slice_replica.SliceReplicaEngine(
            cfg, params, num_hosts=2, rank_channels=[chan],
            **self.GEOM)
        try:
            eng.generate([3, 1, 4, 1, 5], 4, timeout=300)
            # The finished slot's RELEASE was broadcast: the
            # follower's table row is parked on the null page.
            tables = np.asarray(follower._cache['block_tables'])
            assert (tables == 0).all()
        finally:
            eng.stop()
