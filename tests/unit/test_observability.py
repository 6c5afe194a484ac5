"""Observability layer: metrics core, Prometheus exposition, request
tracing, and the serving/training wiring (tier-1, CPU-only).

Covers the ISSUE-3 acceptance surface: label cardinality, histogram
bucket boundaries, concurrent increments from threads, a round-trip
test parsing the /metrics exposition of a LIVE model_server, and a
request submitted with X-SkyTPU-Request-Id yielding a span record
(queue/prefill/TTFT/decode) retrievable via stats() and visible in the
Chrome-trace timeline file.
"""
from __future__ import annotations

import json
import threading

import pytest
import requests

from skypilot_tpu.observability import metrics as metrics_lib
from skypilot_tpu.observability import tracing
from skypilot_tpu.utils import timeline


# ------------------------------------------------------------- metrics core


class TestCounterGauge:

    def test_counter_inc_and_expose(self):
        reg = metrics_lib.Registry()
        c = reg.counter('t_requests_total', 'Requests.')
        c.inc()
        c.inc(4)
        assert c.value == 5
        text = reg.expose()
        assert '# TYPE t_requests_total counter' in text
        assert 't_requests_total 5' in text

    def test_counter_rejects_negative(self):
        reg = metrics_lib.Registry()
        c = reg.counter('t_neg_total', 'x')
        with pytest.raises(ValueError, match='only go up'):
            c.inc(-1)

    def test_gauge_set_inc_dec(self):
        reg = metrics_lib.Registry()
        g = reg.gauge('t_depth', 'x')
        g.set(7)
        g.inc(2)
        g.dec()
        assert g.value == 8

    def test_labels_make_distinct_series(self):
        reg = metrics_lib.Registry()
        c = reg.counter('t_by_reason_total', 'x', ('reason',))
        c.labels(reason='full').inc(2)
        c.labels(reason='expired').inc(3)
        parsed = metrics_lib.parse_exposition(reg.expose())
        series = parsed['t_by_reason_total']
        assert series[(('reason', 'full'),)] == 2
        assert series[(('reason', 'expired'),)] == 3

    def test_label_validation(self):
        reg = metrics_lib.Registry()
        c = reg.counter('t_lab_total', 'x', ('a', 'b'))
        with pytest.raises(ValueError, match='unknown labels'):
            c.labels(a='1', nope='2')
        with pytest.raises(ValueError, match='label value'):
            c.labels('only-one')
        with pytest.raises(ValueError, match='has labels'):
            c.inc()  # labeled metric needs .labels(...) first

    def test_label_cardinality_overflow_folds(self):
        reg = metrics_lib.Registry()
        c = metrics_lib.Counter('t_card_total', 'x', ('k',),
                                max_series=4)
        reg.register(c)
        for i in range(10):
            c.labels(k=f'v{i}').inc()
        series = c.series()
        # 4 real series + one overflow bucket, never 10.
        assert len(series) == 5
        overflow = series[('_overflow_',)]
        assert overflow[0] == 6  # the folded increments

    def test_get_or_create_and_conflict(self):
        reg = metrics_lib.Registry()
        a = reg.counter('t_same_total', 'x')
        b = reg.counter('t_same_total', 'x')
        assert a is b
        with pytest.raises(ValueError, match='already registered'):
            reg.gauge('t_same_total', 'x')
        with pytest.raises(ValueError, match='already registered'):
            reg.counter('t_same_total', 'x', ('extra',))

    def test_concurrent_increments_from_threads(self):
        reg = metrics_lib.Registry()
        c = reg.counter('t_race_total', 'x')
        h = reg.histogram('t_race_seconds', 'x', buckets=(0.5, 1.0))

        def worker():
            for _ in range(1000):
                c.inc()
                h.observe(0.25)

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == 8000
        assert h.count == 8000
        assert h.bucket_counts() == [8000, 0, 0]


class TestHistogram:

    def test_bucket_boundaries_le_inclusive(self):
        reg = metrics_lib.Registry()
        h = reg.histogram('t_hist_seconds', 'x', buckets=(0.1, 1.0, 5.0))
        # On-boundary observations land IN the bucket (Prometheus `le`
        # is <=); above the top bound lands in +Inf.
        for v in (0.1, 0.05, 1.0, 4.9, 5.0, 100.0):
            h.observe(v)
        assert h.bucket_counts() == [2, 1, 2, 1]
        assert h.count == 6
        assert h.sum == pytest.approx(111.05)

    def test_exposition_cumulative_with_inf(self):
        reg = metrics_lib.Registry()
        h = reg.histogram('t_exp_seconds', 'x', buckets=(1.0, 2.0))
        for v in (0.5, 1.5, 99.0):
            h.observe(v)
        parsed = metrics_lib.parse_exposition(reg.expose())
        buckets = parsed['t_exp_seconds_bucket']
        assert buckets[(('le', '1'),)] == 1
        assert buckets[(('le', '2'),)] == 2
        assert buckets[(('le', '+Inf'),)] == 3
        assert parsed['t_exp_seconds_count'][()] == 3
        assert parsed['t_exp_seconds_sum'][()] == pytest.approx(101.0)

    def test_rejects_bad_buckets(self):
        with pytest.raises(ValueError):
            metrics_lib.Histogram('t_bad', 'x', buckets=())
        with pytest.raises(ValueError, match='duplicate'):
            metrics_lib.Histogram('t_bad2', 'x', buckets=(1.0, 1.0))


def test_label_value_escaping_round_trip():
    reg = metrics_lib.Registry()
    c = reg.counter('t_escape_total', 'x', ('path',))
    tricky = 'a"b\\c\nd'
    c.labels(path=tricky).inc()
    parsed = metrics_lib.parse_exposition(reg.expose())
    assert parsed['t_escape_total'][(('path', tricky),)] == 1


def test_exposition_http_server():
    reg = metrics_lib.Registry()
    reg.counter('t_http_total', 'x').inc(3)
    port, shutdown = metrics_lib.start_exposition_server(registry=reg)
    try:
        resp = requests.get(f'http://127.0.0.1:{port}/metrics',
                            timeout=10)
        assert resp.status_code == 200
        assert 'text/plain' in resp.headers['Content-Type']
        parsed = metrics_lib.parse_exposition(resp.text)
        assert parsed['t_http_total'][()] == 3
        assert requests.get(f'http://127.0.0.1:{port}/nope',
                            timeout=10).status_code == 404
    finally:
        shutdown()


# ----------------------------------------------------------------- tracing


class TestRequestSpan:

    def test_phases_recorded(self):
        span = tracing.RequestSpan('req-1')
        span.mark_admitted()
        span.mark_prefill_chunk(0.01)
        span.mark_prefill_chunk(0.02)
        assert span.mark_token() is None      # first token -> TTFT
        gap = span.mark_token()
        assert gap is not None and gap >= 0
        span.finish('ok')
        d = span.to_dict()
        assert d['request_id'] == 'req-1'
        assert d['queue_wait_ms'] is not None
        assert d['prefill_chunks'] == 2
        assert d['prefill_ms'] == pytest.approx(30.0, abs=0.5)
        assert d['ttft_ms'] is not None
        assert d['tokens'] == 2
        assert d['total_ms'] is not None
        assert d['status'] == 'ok'

    def test_ttft_is_the_sum_of_its_three_marks(self):
        """queue_wait + prefill_wall + first_token_wait = ttft, from
        the marks alone (one clock, no engine)."""
        span = tracing.RequestSpan('req-2')
        assert span.prefill_wall_s is None
        span.mark_live(3)                      # not admitted: ignored
        assert span.prefill_wall_s is None
        span.mark_admitted(iteration=7)
        span.mark_prefill_chunk(0.001)
        span.mark_live(iteration=8)
        span.mark_live(iteration=9)            # idempotent
        span.mark_token()
        span.mark_token()
        assert span.prefill_iterations == 2
        assert (span.queue_wait_s + span.prefill_wall_s +
                span.first_token_wait_s) == pytest.approx(span.ttft_s,
                                                          abs=1e-9)
        d = span.to_dict()
        assert d['prefill_iterations'] == 2
        assert d['prefill_wall_ms'] is not None
        assert d['first_token_wait_ms'] is not None
        # prefill_ms keeps its name and its meaning: dispatch seconds.
        assert d['prefill_ms'] == pytest.approx(1.0)

    def test_first_token_before_the_slot_is_live(self):
        """Where the first token comes out of the prefill itself (the
        expert models' admission), its arrival is the slot going live:
        the identity holds with a zero wait."""
        span = tracing.RequestSpan()
        span.mark_admitted(iteration=4)
        span.mark_token()
        assert span.first_token_wait_s == 0.0
        assert span.prefill_iterations == 1
        assert span.queue_wait_s + span.prefill_wall_s == \
            pytest.approx(span.ttft_s, abs=1e-9)

    def test_finish_idempotent(self):
        span = tracing.RequestSpan()
        span.finish('ok')
        total = span.total_s
        span.finish('error')
        assert span.status == 'ok' and span.total_s == total

    def test_store_bounded_and_lookup(self):
        store = tracing.SpanStore(maxlen=3)
        for i in range(5):
            s = tracing.RequestSpan(f'r{i}')
            s.finish()
            store.add(s)
        assert len(store) == 3
        assert store.get('r0') is None           # aged out
        assert store.get('r4')['request_id'] == 'r4'
        recent = store.recent(2)
        assert [s['request_id'] for s in recent] == ['r4', 'r3']

    def test_ids_unique(self):
        ids = {tracing.new_request_id() for _ in range(100)}
        assert len(ids) == 100


# ------------------------------------------- spans on a running engine


@pytest.fixture(scope='module')
def tiny_paged_engine():
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from skypilot_tpu.models import configs
    from skypilot_tpu.models.transformer import Transformer
    from skypilot_tpu.serve import batching_engine
    cfg = configs.get_config('tiny')
    params = nn.meta.unbox(Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))['params'])
    eng = batching_engine.ContinuousBatchingEngine(
        cfg, params, max_len=64, slots=2, prefill_chunk=8, kv_pages=48,
        page_size=8)
    yield eng
    eng.stop()


# prompt, engine iterations from admission to slot live (chunks of 8,
# the last prompt token rides the first tick; a prefix hit spends one
# iteration on the seed alone, then one on the tail chunk).
_SPAN_CASES = {
    'plain': (list(range(200, 220)), 3),
    'prefix-hit': (list(range(200, 217)) + [3, 4, 5], 2),
    'one-token': ([7], 1),
}


@pytest.mark.parametrize('case', list(_SPAN_CASES))
def test_engine_span_marks_add_up_to_ttft(tiny_paged_engine, case):
    """`queue_wait_s + prefill_wall_s + first_token_wait_s == ttft_s`
    on the `tiny` engine, to within clock reads."""
    eng = tiny_paged_engine
    prompt, iterations = _SPAN_CASES[case]
    if case == 'prefix-hit':
        eng.generate(_SPAN_CASES['plain'][0], 2, timeout=180)
    handle = eng.submit(prompt, 3)
    handle.result(timeout=180)
    span = handle.span
    assert (span.prefix_hit_pages > 0) == (case == 'prefix-hit')
    assert span.prefill_wall_s >= 0 and span.first_token_wait_s > 0
    assert (span.queue_wait_s + span.prefill_wall_s +
            span.first_token_wait_s) == pytest.approx(span.ttft_s,
                                                      abs=1e-6)
    assert span.prefill_iterations == iterations
    # What the chunks' dispatch cost the host lies inside the wall.
    assert span.prefill_s <= span.prefill_wall_s + 1e-6
    d = eng.span(handle.request_id)
    assert d['prefill_wall_ms'] == pytest.approx(
        span.prefill_wall_s * 1e3, abs=1e-3)


def test_every_jitted_entry_is_a_named_function(tiny_paged_engine):
    """The device trace names a program after the jitted function:
    none may be a lambda or a `functools.partial` (`jit__lambda_`,
    `jit__unknown`), and the tick's must be `paged_engine_step`, the
    name `benchmarks/layers/paged_attn_roofline.py` finds it by."""
    import functools

    names = {}
    for attr, entry in vars(tiny_paged_engine).items():
        jitted = getattr(entry, '__wrapped__', None)
        if not hasattr(jitted, 'lower'):
            continue                  # not a sentinel-wrapped jit
        fn = jitted.__wrapped__
        assert not isinstance(fn, functools.partial), attr
        assert fn.__name__ not in ('<lambda>', 'call'), attr
        names[attr] = jitted.__name__
    assert names['_step'] == 'paged_engine_step'
    assert names['_spec_step'] == 'paged_spec_engine_step'
    assert names['_prefill'] == 'prefill'
    assert names['_prefill_chunk'] == 'prefill_chunk'
    assert names['_seed_private'] == 'paged_seed_private'
    assert names['_insert_pages'] == 'insert_prefill_pages'
    assert names['_admit_paged'] == 'paged_admit_slot'
    assert names['_chunk_step'] == 'paged_engine_step_with_chunk'
    assert names['_save_snapshot'] == 'save_snapshot'
    assert len(names) == 12


def test_stats_tick_loop_counts_the_loop(tiny_paged_engine):
    """`stats()['tick_loop']`: cumulative, with `device-wait` split
    out of `sample`, and no more seconds in phases than in the loop."""
    eng = tiny_paged_engine
    before = eng.stats()['tick_loop']
    eng.generate(list(range(300, 312)), 6, timeout=180)
    after = eng.stats()['tick_loop']
    assert after['iterations'] > before['iterations']
    assert after['loop_s'] > before['loop_s']
    for phase in ('admit', 'prefill-chunk', 'page-scatter',
                  'decode-step', 'device-wait', 'sample'):
        assert after['phase_s'][phase] > before['phase_s'].get(phase, 0)
    assert sum(after['phase_s'].values()) <= after['loop_s'] + 1e-9
    assert after['starved_ticks'] >= before['starved_ticks']
    assert after['starved_s'] >= before['starved_s']
    ring = eng.profile()['ring']
    assert [rec['n'] for rec in ring] == sorted(rec['n'] for rec in ring)
    chunk = next(p for rec in ring for p in rec['phases']
                 if p[0] == 'prefill-chunk')
    # Chunk 0's width is its flash bucket (16), the id its request's.
    assert chunk[3] == 16 and chunk[4]


# ----------------------------------------------------------- timeline fixes


class TestTimelineSatellite:

    def test_programmatic_start_then_save(self, tmp_path, monkeypatch):
        path = str(tmp_path / 'trace.json')
        monkeypatch.setattr(timeline, '_events', [])
        monkeypatch.setattr(timeline, '_enabled_path', None)
        monkeypatch.setattr(timeline, '_atexit_registered', True)
        timeline.start(path)
        with timeline.Event('late-span'):
            pass
        timeline.add_complete_event('retro', 123.0, 0.5, {'k': 'v'})
        timeline.save_timeline()
        events = json.load(open(path))['traceEvents']
        names = [e['name'] for e in events]
        assert 'late-span' in names and 'retro' in names
        retro = next(e for e in events if e['name'] == 'retro')
        assert retro['ph'] == 'X' and retro['dur'] == 500000
        monkeypatch.setattr(timeline, '_enabled_path', None)

    def test_env_checked_after_import(self, tmp_path, monkeypatch):
        """SKYTPU_TIMELINE_FILE set AFTER import still records + dumps
        (it was read once at import before)."""
        path = str(tmp_path / 'late_env.json')
        monkeypatch.setattr(timeline, '_events', [])
        monkeypatch.setattr(timeline, '_enabled_path', None)
        monkeypatch.setenv('SKYTPU_TIMELINE_FILE', path)
        with timeline.Event('env-span'):
            pass
        timeline.save_timeline()
        events = json.load(open(path))['traceEvents']
        assert any(e['name'] == 'env-span' for e in events)

    def test_atexit_registered_exactly_once(self, tmp_path, monkeypatch):
        calls = []
        monkeypatch.setattr(timeline, '_atexit_registered', False)
        monkeypatch.setattr(timeline.atexit, 'register',
                            lambda fn: calls.append(fn))
        timeline.start(str(tmp_path / 'a.json'))
        timeline.start(str(tmp_path / 'b.json'))
        timeline.start(str(tmp_path / 'c.json'))
        assert calls == [timeline.save_timeline]
        monkeypatch.setattr(timeline, '_enabled_path', None)


# ---------------------------------------------- live server round trip


@pytest.fixture(scope='module')
def cb_server():
    """One continuous-batching model server shared by the round-trip
    tests (the jit warmup dominates; module scope amortizes it)."""
    from skypilot_tpu.serve import model_server
    srv = model_server.ModelServer('tiny', max_len=64, max_batch=2,
                                   continuous_batching=True)
    port, shutdown = model_server.start_background(srv)
    yield srv, port
    shutdown()
    srv.close()


def test_metrics_endpoint_round_trip(cb_server):
    """GET /metrics on a live model_server: valid Prometheus text that
    parses, with the queue-wait and ITL histograms present and the
    engine counters advancing across requests."""
    _, port = cb_server
    url = f'http://127.0.0.1:{port}'
    before = metrics_lib.parse_exposition(
        requests.get(url + '/metrics', timeout=30).text)
    resp = requests.post(url + '/generate',
                         json={'prompt_ids': [[1, 2, 3]],
                               'max_new_tokens': 4}, timeout=300)
    assert resp.status_code == 200
    after_text = requests.get(url + '/metrics', timeout=30).text
    assert after_text.startswith('# HELP')
    after = metrics_lib.parse_exposition(after_text)
    # Histograms the acceptance criteria name.
    assert any(k.startswith('skytpu_engine_queue_wait_seconds_bucket')
               for k in after)
    assert any(k.startswith('skytpu_engine_itl_seconds_bucket')
               for k in after)

    def total(parsed, name):
        return sum((parsed.get(name) or {}).values())

    assert (total(after, 'skytpu_engine_decode_tokens_total') >=
            total(before, 'skytpu_engine_decode_tokens_total') + 4)
    assert (total(after, 'skytpu_engine_queue_wait_seconds_count') >
            total(before, 'skytpu_engine_queue_wait_seconds_count'))
    assert total(after, 'skytpu_engine_slots') == 2


def test_request_id_span_via_stats_and_timeline(cb_server, tmp_path,
                                                monkeypatch):
    """A request submitted with X-SkyTPU-Request-Id yields a span
    record (queue/prefill/TTFT/decode) retrievable via stats() and
    visible in the Chrome-trace timeline file."""
    srv, port = cb_server
    trace_path = str(tmp_path / 'serve_trace.json')
    monkeypatch.setattr(timeline, '_events', [])
    monkeypatch.setattr(timeline, '_atexit_registered', True)
    timeline.start(trace_path)
    try:
        rid = 'trace-me-123'
        resp = requests.post(
            f'http://127.0.0.1:{port}/generate',
            json={'prompt_ids': [[5, 6, 7, 8]], 'max_new_tokens': 4},
            headers={tracing.REQUEST_ID_HEADER: rid}, timeout=300)
        assert resp.status_code == 200
        # The id round-trips onto the response.
        assert resp.headers[tracing.REQUEST_ID_HEADER] == rid
        engine = srv._engine  # pylint: disable=protected-access
        # Retrievable via stats() ...
        stats = engine.stats()
        spans = {s['request_id']: s for s in stats['recent_spans']}
        assert rid in spans, stats['recent_spans']
        span = spans[rid]
        for key in ('queue_wait_ms', 'prefill_ms', 'ttft_ms',
                    'total_ms'):
            assert span[key] is not None and span[key] >= 0, (key, span)
        assert span['tokens'] == 4
        assert span['status'] == 'ok'
        # ... and via the direct lookup.
        assert engine.span(rid)['request_id'] == rid
        # ... and in the Chrome-trace timeline file.
        timeline.save_timeline()
        events = json.load(open(trace_path))['traceEvents']
        names = [e['name'] for e in events]
        assert f'request:{rid}' in names
        assert f'request:{rid}/decode' in names
    finally:
        monkeypatch.setattr(timeline, '_enabled_path', None)


def test_request_id_generated_when_absent(cb_server):
    _, port = cb_server
    resp = requests.post(
        f'http://127.0.0.1:{port}/generate',
        json={'prompt_ids': [[9, 8]], 'max_new_tokens': 2}, timeout=300)
    assert resp.status_code == 200
    rid = resp.headers[tracing.REQUEST_ID_HEADER]
    assert rid  # server minted one


def test_async_front_metrics_and_request_id(cb_server):
    """The asyncio front serves /metrics and honors the header too."""
    from skypilot_tpu.serve import async_server
    srv, _ = cb_server
    port, shutdown = async_server.start_background(srv)
    try:
        text = requests.get(f'http://127.0.0.1:{port}/metrics',
                            timeout=30).text
        parsed = metrics_lib.parse_exposition(text)
        assert 'skytpu_engine_ticks_total' in parsed
        rid = 'async-abc'
        resp = requests.post(
            f'http://127.0.0.1:{port}/generate',
            json={'prompt_ids': [[4, 2]], 'max_new_tokens': 2},
            headers={tracing.REQUEST_ID_HEADER: rid}, timeout=300)
        assert resp.status_code == 200
        assert resp.headers[tracing.REQUEST_ID_HEADER] == rid
        assert srv._engine.span(rid) is not None  # pylint: disable=protected-access
    finally:
        shutdown()


# ------------------------------------------------- training telemetry


class TestCallbacksSplit:

    @pytest.fixture(autouse=True)
    def _fresh(self, monkeypatch, _isolated_home):
        from skypilot_tpu.callbacks import base
        monkeypatch.setenv(base.ENV_LOG_DIR,
                           str(_isolated_home / 'bench_logs'))
        monkeypatch.setattr(base, '_instance', None)
        yield

    def test_compute_vs_data_wait_split(self):
        """Regression (ISSUE 3 satellite): inter-end seconds_per_step
        folds data gaps into step time; the split view must not."""
        from skypilot_tpu.callbacks import base
        cb = base.init()
        # Synthetic timeline: 1s steps separated by 2s data stalls.
        cb.step_begins = [0.0, 3.0, 6.0]
        cb.step_ends = [1.0, 4.0, 7.0]
        summary = cb.summary()
        # Legacy metric: (7 - 1) / 2 = 3s — compute AND wait.
        assert summary['seconds_per_step'] == pytest.approx(3.0)
        # Split: pure compute is 1s/step, the 4s of gaps are reported
        # separately.
        assert summary['compute_seconds_per_step'] == pytest.approx(1.0)
        assert summary['data_wait_seconds'] == pytest.approx(4.0)

    def test_tokens_per_s_and_peak_memory(self):
        from skypilot_tpu.callbacks import base
        cb = base.init(tokens_per_step=1000)
        cb.step_begins = [0.0, 10.0]
        cb.step_ends = [2.0, 10.5]
        summary = cb.summary()
        # Steady state (first step excluded): 0.5s compute -> 2000 t/s.
        assert summary['tokens_per_s'] == pytest.approx(2000.0)
        base.record_peak_memory(123456)
        assert cb.summary()['peak_memory_bytes'] == 123456

    def test_prefetch_reports_data_wait(self):
        """A slow producer shows up in prefetch_wait_seconds and the
        data-wait counter."""
        import time as _time

        from skypilot_tpu.callbacks import base
        from skypilot_tpu.data import prefetch
        cb = base.init()

        def slow_src():
            for i in range(3):
                _time.sleep(0.05)
                yield {'x': i}

        # No sharding/jax needed: plain objects pass through tree_map.
        items = list(prefetch.DevicePrefetcher(iter(slow_src())))
        assert len(items) == 3
        assert cb.prefetch_wait_seconds > 0

    def test_late_tokens_per_step_adopted(self):
        from skypilot_tpu.callbacks import base
        base.init()
        cb = base.init(tokens_per_step=64)
        assert cb.tokens_per_step == 64


# ----------------------------------------------- LB bounded timestamps


class TestLoadBalancerSatellite:

    def test_timestamps_bounded_on_sync_failure(self, monkeypatch):
        from skypilot_tpu.serve import load_balancer
        monkeypatch.setenv('SKYTPU_LB_MAX_PENDING_TIMESTAMPS', '50')
        lb = load_balancer.SkyServeLoadBalancer('http://127.0.0.1:1')

        def boom(*args, **kwargs):
            raise requests.ConnectionError('controller down')

        monkeypatch.setattr(load_balancer.requests, 'post', boom)
        for i in range(80):
            lb.request_timestamps.append(float(i))
        lb._sync_with_controller()  # pylint: disable=protected-access
        # Bounded drop-oldest: newest 50 kept, 30 counted as dropped.
        assert len(lb.request_timestamps) == 50
        assert lb.request_timestamps[0] == 30.0
        assert lb.dropped_timestamps == 30
        # Repeated failures keep it bounded (samples accrue between
        # sync attempts).
        for i in range(40):
            lb.request_timestamps.append(float(100 + i))
        lb._sync_with_controller()  # pylint: disable=protected-access
        assert len(lb.request_timestamps) == 50
        assert lb.dropped_timestamps == 70

    def test_sync_failure_warns_with_backoff(self, monkeypatch):
        from skypilot_tpu.serve import load_balancer
        lb = load_balancer.SkyServeLoadBalancer('http://127.0.0.1:1')
        monkeypatch.setattr(
            load_balancer.requests, 'post',
            lambda *a, **k: (_ for _ in ()).throw(
                requests.ConnectionError('down')))
        warnings, infos = [], []
        monkeypatch.setattr(load_balancer.logger, 'warning',
                            lambda msg, *a: warnings.append(msg))
        monkeypatch.setattr(load_balancer.logger, 'info',
                            lambda msg, *a: infos.append(msg))
        for _ in range(10):
            lb._sync_with_controller()  # pylint: disable=protected-access
        # WARNING at attempts 1, 2, 4, 8 — not 10 copies of the spam.
        assert len(warnings) == 4
        # Recovery logs once at INFO and resets the backoff.
        monkeypatch.setattr(
            load_balancer.requests, 'post',
            lambda *a, **k: type(
                'R', (), {'json': lambda self:
                          {'ready_replica_urls': []}})())
        lb._sync_with_controller()  # pylint: disable=protected-access
        assert len(infos) == 1 and 'recovered' in infos[0]
        assert lb._sync_failures == 0  # pylint: disable=protected-access
