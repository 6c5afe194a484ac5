"""Continuous profiling plane tests (ISSUE 18 tentpole).

TickProfiler (exclusive phase laps, bounded ring, idle-tick skip,
disable gate), what holds the worker besides an iteration (the parked
engine, collector pauses, late reads) and the starvation probe's
reading of them, the recompile sentinel (warm-up compiles free,
steady-state recompiles journaled + counted exactly once), the
collapsed-stack / Chrome-trace exports, `/profile` on BOTH HTTP
fronts, the sharpened MFU numerator, and the ≤3% overhead budget.
"""
from __future__ import annotations

import gc
import json
import time

import jax
import jax.numpy as jnp
import pytest
import requests

from skypilot_tpu.models import configs
from skypilot_tpu.observability import metrics as metrics_lib
from skypilot_tpu.observability import profiling
from skypilot_tpu.serve import async_server, model_server


class FakeClock:
    """Deterministic monotonic clock: every read advances by `step`
    unless ticks are queued explicitly."""

    def __init__(self, step: float = 1.0) -> None:
        self.now = 0.0
        self.step = step
        self.queued = []

    def __call__(self) -> float:
        self.now += self.queued.pop(0) if self.queued else self.step
        return self.now


class RecordingJournal:
    def __init__(self) -> None:
        self.events = []

    def append(self, name, **fields) -> None:
        self.events.append((name, fields))


class RecordingAnnotation:
    """Stand-in for `jax.profiler.TraceAnnotation`: keeps what the
    profiler would put on the trace's host plane."""

    def __init__(self) -> None:
        self.opened = []     # (name, kwargs) in the order entered
        self.depth = 0

    def __call__(self, name, **kw):
        rec = self

        class _Span:
            def __enter__(self):
                rec.opened.append((name, kw))
                rec.depth += 1
                return self

            def __exit__(self, *exc):
                rec.depth -= 1
                return False

            def set_metadata(self, **more):
                kw.update(more)

        return _Span()


def _profiler(**kw):
    kw.setdefault('clock', FakeClock())
    kw.setdefault('memory_cb', lambda: None)
    kw.setdefault('disabled', False)
    kw.setdefault('annotate', RecordingAnnotation())
    return profiling.TickProfiler(**kw)


def _run_phase(prof, name, record=True, **kw):
    with prof.phase(name, **kw) as phase:
        phase.record = record


class FakeInflight:
    def __init__(self, ready: bool) -> None:
        self.ready = ready

    def is_ready(self) -> bool:
        return self.ready


class TestTickProfiler:

    def test_laps_are_exclusive_and_one_read_each(self):
        # Every clock read advances 1 s: a phase is two reads, so each
        # lasts 1 s and 1 s passes between two of them.
        clock = FakeClock(step=1.0)
        prof = _profiler(clock=clock)
        prof.begin_tick()                              # t=1
        _run_phase(prof, 'handoff', record=False)      # 2..3, dropped
        _run_phase(prof, 'admit')                      # 4..5
        _run_phase(prof, 'decode-step')                # 6..7
        prof.end_tick()                                # t=8
        snap = prof.snapshot()
        assert snap['ticks'] == 1
        assert set(snap['phases']) == {'admit', 'decode-step'}
        assert snap['phases']['admit']['total_s'] == pytest.approx(1.0)
        assert snap['phases']['decode-step']['total_s'] == \
            pytest.approx(1.0)
        # The unrecorded handoff phase was attributed to NO phase
        # (phases sum < iteration).
        [rec] = snap['ring']
        assert rec['dur_s'] == pytest.approx(7.0)
        assert sum(p[2] for p in rec['phases']) == pytest.approx(2.0)
        assert [p[1] for p in rec['phases']] == [3.0, 5.0]

    def test_device_wait_is_split_from_sample(self):
        """`sample` no longer holds the blocking read: phases of an
        iteration stay exclusive and, with the gaps between them, sum
        to it with `device-wait` its own term."""
        clock = FakeClock(step=0.0)
        prof = _profiler(clock=clock)
        clock.queued = [0.0,            # begin
                        0.0, 0.002,     # decode-step: 2 ms dispatch
                        0.0, 0.090,     # device-wait: 90 ms
                        0.0, 0.003,     # sample: 3 ms
                        0.0]            # end
        prof.begin_tick()
        _run_phase(prof, 'decode-step', count=16)
        _run_phase(prof, 'device-wait', count=16)
        _run_phase(prof, 'sample')
        prof.end_tick()
        [rec] = prof.snapshot()['ring']
        by_name = {p[0]: p[2] for p in rec['phases']}
        assert by_name == {'decode-step': pytest.approx(0.002),
                           'device-wait': pytest.approx(0.090),
                           'sample': pytest.approx(0.003)}
        assert sum(by_name.values()) == pytest.approx(rec['dur_s'])
        loop = prof.tick_loop()
        host = loop['loop_s'] - loop['phase_s']['device-wait']
        assert host == pytest.approx(0.005)

    def test_nested_phase_comes_out_of_the_enclosing_one(self):
        clock = FakeClock(step=1.0)
        prof = _profiler(clock=clock)
        prof.begin_tick()
        with prof.phase('decode-step'):          # 2 ..
            _run_phase(prof, 'slice-sync')       # 3..4
        prof.end_tick()                          # .. 5
        [rec] = prof.snapshot()['ring']
        by_name = {p[0]: p[2] for p in rec['phases']}
        # decode-step spans 3 s of which the sync took 1.
        assert by_name == {'slice-sync': pytest.approx(1.0),
                           'decode-step': pytest.approx(2.0)}

    def test_phases_reach_the_trace_with_number_id_and_count(self):
        ann = RecordingAnnotation()
        prof = _profiler(annotate=ann)
        del ann.opened[:]       # the constructor's self-calibration
        for _ in range(2):
            prof.begin_tick()
            _run_phase(prof, 'admit', request_id='req-7', count=512)
            _run_phase(prof, 'handoff', record=False)
            prof.end_tick()
        assert ann.depth == 0                   # every span closed
        assert [name for name, _ in ann.opened] == [
            'skytpu/tick', 'skytpu/admit', 'skytpu/handoff'] * 2
        assert ann.opened[0][1] == {'n': 1}
        assert ann.opened[3][1] == {'n': 2}
        assert ann.opened[4][1] == {'n': 2, 'request_id': 'req-7',
                                    'count': 512}
        # A count known only when the phase ends still reaches the
        # trace (as metadata set on the open span).
        prof.begin_tick()
        with prof.phase('sample') as phase:
            phase.count = 9
        prof.end_tick()
        assert ann.opened[-1] == ('skytpu/sample', {'n': 3, 'count': 9})
        ring = prof.snapshot()['ring'][:2]
        assert [rec['n'] for rec in ring] == [1, 2]
        assert ring[1]['phases'][0][3:] == [512, 'req-7']

    def test_idle_ticks_never_enter_the_ring(self):
        prof = _profiler()
        for _ in range(5):
            prof.begin_tick()
            _run_phase(prof, 'admit', record=False)  # ran, no work
            prof.end_tick()
        assert prof.ticks == 0
        assert prof.snapshot()['ring'] == []
        assert prof.iteration == 5               # numbered all the same

    def test_ring_is_bounded_but_aggregates_are_cumulative(self):
        prof = _profiler(ring_ticks=4)
        for _ in range(10):
            prof.begin_tick()
            _run_phase(prof, 'decode-step')
            prof.end_tick()
        snap = prof.snapshot()
        assert len(snap['ring']) == 4
        assert snap['ticks'] == 10
        assert snap['phases']['decode-step']['count'] == 10

    def test_tick_loop_is_monotone_and_equals_the_ring(self):
        """`stats()['tick_loop']` is what a reader differences: every
        field only grows, and while the ring still holds every
        iteration its phase seconds are the ring's."""
        prof = _profiler(clock=FakeClock(step=0.5), ring_ticks=64)
        seen = [prof.tick_loop()]
        for i in range(6):
            prof.begin_tick()
            _run_phase(prof, 'admit', record=bool(i % 2))
            _run_phase(prof, 'decode-step')
            _run_phase(prof, 'device-wait')
            prof.end_tick()
            seen.append(prof.tick_loop())
        for a, b in zip(seen, seen[1:]):
            assert b['iterations'] == a['iterations'] + 1
            assert b['loop_s'] > a['loop_s']
            assert b['elapsed_s'] > a['elapsed_s']
            for name, total in a['phase_s'].items():
                assert b['phase_s'][name] >= total
        snap = prof.snapshot()
        ring_s = {}
        for rec in snap['ring']:
            for name, _, dur, _, _ in rec['phases']:
                ring_s[name] = ring_s.get(name, 0.0) + dur
        assert seen[-1]['phase_s'] == pytest.approx(ring_s)
        assert seen[-1]['loop_s'] == pytest.approx(
            sum(rec['dur_s'] for rec in snap['ring']))
        # The snapshot's totals are the last reader's; only the clock
        # has moved on.
        later = dict(snap['tick_loop'])
        assert later.pop('elapsed_s') > seen[-1]['elapsed_s']
        assert later == {k: v for k, v in seen[-1].items()
                         if k != 'elapsed_s'}

    def test_disable_gate_is_a_noop(self):
        prof = _profiler(disabled=True, annotate=None)
        prof.begin_tick()
        with prof.phase('decode-step', count=3) as phase:
            phase.record = False
        assert prof.probe_starved(FakeInflight(True)) is False
        prof.end_tick()
        snap = prof.snapshot()
        assert snap['enabled'] is False
        assert snap['ticks'] == 0 and snap['ring'] == []
        assert snap['tick_loop']['iterations'] == 0

    def test_env_knobs(self, monkeypatch):
        monkeypatch.setenv('SKYTPU_PROFILE_RING_TICKS', '7')
        monkeypatch.setenv('SKYTPU_PROFILE_DISABLE', '1')
        prof = profiling.TickProfiler(memory_cb=lambda: None)
        assert prof.ring_ticks == 7
        assert prof.disabled is True

    def test_quantiles_over_the_ring(self):
        clock = FakeClock(step=0.0)
        prof = _profiler(clock=clock, ring_ticks=128)
        for dur in (1.0, 2.0, 3.0, 4.0):
            clock.queued = [0.0, 0.0, dur]     # begin, phase in, out
            prof.begin_tick()
            _run_phase(prof, 'sample')
            prof.end_tick()
        agg = prof.snapshot()['phases']['sample']
        assert agg['p50_s'] == pytest.approx(3.0)
        assert agg['max_s'] == pytest.approx(4.0)
        assert agg['total_s'] == pytest.approx(10.0)

    def test_memory_watermark_and_dead_backend(self):
        """The watermark is read once a snapshot, on the reader's
        thread, and never from `end_tick` (it only rises, so a
        per-iteration series carried no more)."""
        mems = [100, 300, 200]
        asked = []

        def memory_cb():
            asked.append(len(asked))
            return mems.pop(0) if mems else None

        prof = _profiler(memory_cb=memory_cb)
        for _ in range(3):
            prof.begin_tick()
            _run_phase(prof, 'decode-step')
            prof.end_tick()
        assert asked == []                      # the loop never asks
        assert 'mem_bytes' not in prof.snapshot()['ring'][0]
        snaps = [prof.snapshot()['device_memory'] for _ in range(2)]
        assert asked == [0, 1, 2]
        assert snaps[0] == {'watermark_bytes': 300, 'last_bytes': 300}
        assert snaps[1] == {'watermark_bytes': 300, 'last_bytes': 200}
        # Backend went dark: the profiler stops asking (no raise).
        dark = prof.snapshot()['device_memory']
        assert dark == {'watermark_bytes': 300, 'last_bytes': None}
        assert prof._mem_dead is True
        prof.snapshot()
        assert len(asked) == 4


class TestStarvationProbe:
    """The probe sees, right before an iteration's first dispatch,
    whether the tick in flight has already finished."""

    TICK = 0.100

    def _iteration(self, prof, clock, *, host_s, ready, wait_s):
        """One pipelined iteration: `host_s` of host work, the probe,
        a dispatch, then the blocking read returning after `wait_s`."""
        clock.queued = [0.0]
        prof.begin_tick()
        clock.queued = [host_s]                 # the probe's own read
        starved = prof.probe_starved(FakeInflight(ready))
        if not starved:
            clock.queued = []
            clock.now += host_s                 # (no read was made)
        clock.queued = [0.0, 0.0]
        _run_phase(prof, 'decode-step')
        clock.queued = [0.0, wait_s]
        _run_phase(prof, 'device-wait')
        clock.queued = [0.0]
        prof.end_tick()
        return starved

    def test_counts_exactly_and_estimates_from_the_running_tick(self):
        clock = FakeClock(step=0.0)
        prof = _profiler(clock=clock)
        # Two unstarved iterations teach the tick length (100 ms
        # between two device-wait returns).
        for _ in range(2):
            assert not self._iteration(prof, clock, host_s=0.005,
                                       ready=False, wait_s=0.095)
        assert prof.tick_loop()['starved_ticks'] == 0
        # The host stands still 400 ms: the tick in flight (100 ms)
        # finished 300 ms before the next dispatch.
        assert self._iteration(prof, clock, host_s=0.400, ready=True,
                               wait_s=0.0)
        loop = prof.tick_loop()
        assert loop['starved_ticks'] == 1
        assert loop['starved_s'] == pytest.approx(0.300)
        # A starved interval is not taken for a tick length: the next
        # stall is still judged against 100 ms.
        assert not self._iteration(prof, clock, host_s=0.005,
                                   ready=False, wait_s=0.095)
        assert self._iteration(prof, clock, host_s=0.150, ready=True,
                               wait_s=0.0)
        loop = prof.tick_loop()
        assert loop['starved_ticks'] == 2
        assert loop['starved_s'] == pytest.approx(0.350)

    def test_estimate_is_floored_at_zero(self):
        clock = FakeClock(step=0.0)
        prof = _profiler(clock=clock)
        for _ in range(2):
            self._iteration(prof, clock, host_s=0.005, ready=False,
                            wait_s=0.095)
        # Ready after only 60 ms of a 100 ms running tick (a short
        # tick): counted, but no negative seconds.
        assert self._iteration(prof, clock, host_s=0.060, ready=True,
                               wait_s=0.0)
        loop = prof.tick_loop()
        assert loop['starved_ticks'] == 1
        assert loop['starved_s'] == 0.0

    def test_no_estimate_across_an_idle_engine(self):
        """An iteration without a device-wait (no tick was in flight)
        breaks the chain: the wait that follows an idle spell is not a
        tick length, and a starved tick right after it counts with no
        seconds."""
        clock = FakeClock(step=0.0)
        prof = _profiler(clock=clock)
        for _ in range(2):
            self._iteration(prof, clock, host_s=0.005, ready=False,
                            wait_s=0.095)
        clock.queued = [0.0, 0.0, 0.0, 5.0]     # idle: 5 s, no wait
        prof.begin_tick()
        _run_phase(prof, 'decode-step')
        prof.end_tick()
        assert self._iteration(prof, clock, host_s=0.500, ready=True,
                               wait_s=0.0)
        loop = prof.tick_loop()
        assert loop['starved_ticks'] == 1
        assert loop['starved_s'] == 0.0


class RaisingInflight:
    def is_ready(self) -> bool:
        raise AssertionError('is_ready() asked of a disabled profiler')


@pytest.fixture
def quiet_collector():
    """Only the test's own `gc.collect()` calls collect, so a fake
    clock's queued reads go where the test puts them."""
    was = gc.isenabled()
    gc.disable()
    yield
    if was:
        gc.enable()


class TestHostStalls:
    """What holds the worker outside an iteration (`skytpu/idle`,
    `skytpu/gc`) and inside the blocking read (`ready`), as spans and
    as `tick_loop` counters, and what the probe makes of a stall."""

    def test_parked_worker_is_a_span_and_no_iteration(self):
        clock = FakeClock(step=0.0)
        ann = RecordingAnnotation()
        prof = _profiler(clock=clock, annotate=ann)
        del ann.opened[:]
        seen = [prof.tick_loop()]
        clock.queued = [0.0, 0.250]
        with prof.idle('empty'):
            pass
        seen.append(prof.tick_loop())
        clock.queued = [0.0, 0.005]
        with prof.idle('pages'):
            pass
        clock.queued = [0.1]          # the reader's own clock read
        seen.append(prof.tick_loop())
        assert ann.opened == [('skytpu/idle', {'n': 0, 'reason': 'empty'}),
                              ('skytpu/idle', {'n': 0, 'reason': 'pages'})]
        assert ann.depth == 0
        loop = seen[-1]
        assert loop['idle_s'] == pytest.approx(0.250)
        assert loop['deferred_s'] == pytest.approx(0.005)
        assert loop['iterations'] == 0 and loop['loop_s'] == 0.0
        assert prof.snapshot()['ring'] == []
        assert [s['elapsed_s'] for s in seen] == pytest.approx(
            [0.0, 0.250, 0.355])

    def test_a_collection_is_a_span_and_a_counter(self, quiet_collector):
        ann = RecordingAnnotation()
        prof = _profiler(clock=FakeClock(step=1.0), annotate=ann)
        del ann.opened[:]
        prof.start()
        try:
            before = prof.tick_loop()
            gc.collect()
            gc.collect(0)
            after = prof.tick_loop()
        finally:
            prof.close()
        assert after['gc_pauses'] == before['gc_pauses'] + 2
        # Two clock reads a collection, each 1 s on this clock.
        assert after['gc_s'] == pytest.approx(before['gc_s'] + 2.0)
        spans = [(name, kw) for name, kw in ann.opened
                 if name == 'skytpu/gc']
        assert [kw['gen'] for _, kw in spans] == [2, 0]
        assert all(kw['collected'] >= 0 for _, kw in spans)
        assert ann.depth == 0
        # Gone with close(): a later collection counts nowhere.
        gc.collect()
        assert prof.tick_loop()['gc_pauses'] == after['gc_pauses']

    def test_collections_from_many_threads_all_count(self):
        """The hook's counters take no lock (a collection can start
        while any thread holds it); collections are one at a time, so
        none is lost under many collecting threads and a fast switch."""
        import sys
        import threading
        prof = _profiler(clock=time.monotonic)
        stops = []
        lock = threading.Lock()

        def reference(phase, info):
            if phase == 'stop':
                with lock:
                    stops.append(info['generation'])

        def collect():
            for _ in range(100):
                gc.collect(0)
                prof.tick_loop()

        switch = sys.getswitchinterval()
        prof.start()
        gc.callbacks.append(reference)
        try:
            sys.setswitchinterval(1e-6)
            workers = [threading.Thread(target=collect) for _ in range(16)]
            for w in workers:
                w.start()
            for w in workers:
                w.join(timeout=60)
            assert not any(w.is_alive() for w in workers)
        finally:
            sys.setswitchinterval(switch)
            gc.callbacks.remove(reference)
            prof.close()
        # A call made while another thread collects returns at once, so
        # there are fewer collections than calls; each one counts once.
        loop = prof.tick_loop()
        assert loop['gc_pauses'] == len(stops) > 0
        assert loop['gc_s'] > 0.0

    def test_hook_lifecycle_leaves_the_collector_as_it_was(self):
        before = list(gc.callbacks)
        for _ in range(20):
            prof = _profiler()
            prof.start()
            prof.start()                      # once a profiler
            assert len(gc.callbacks) == len(before) + 1
            prof.close()
            prof.close()
        held = [_profiler() for _ in range(20)]
        for prof in held:
            prof.start()
        assert len(gc.callbacks) == len(before) + 20
        for prof in held:
            prof.close()
        assert gc.callbacks == before

    def test_disabled_installs_nothing_and_asks_nothing(self):
        before = list(gc.callbacks)
        prof = _profiler(disabled=True, annotate=None)
        prof.start()
        assert gc.callbacks == before
        prof.begin_tick()
        with prof.device_wait(RaisingInflight(), count=3):
            pass
        with prof.idle('empty'):
            pass
        prof.end_tick()
        prof.close()
        loop = prof.tick_loop()
        assert (loop['late_reads'], loop['idle_s'], loop['gc_pauses']) == \
            (0, 0.0, 0)

    @staticmethod
    def _iteration(prof, clock, *, host_s, probe_ready, wait_ready,
                   wait_s, inside=None, in_dispatch=None):
        """One pipelined iteration as `TestStarvationProbe` runs it, the
        read through `device_wait`; `inside` runs within the read,
        `in_dispatch` within the dispatch."""
        clock.queued = [0.0]
        prof.begin_tick()
        clock.queued = [host_s]
        if not prof.probe_starved(FakeInflight(probe_ready)):
            clock.queued = []
            clock.now += host_s
        clock.queued = [0.0, 0.0]
        with prof.phase('decode-step'):
            if in_dispatch is not None:
                in_dispatch()
        clock.queued = [0.0, wait_s]
        with prof.device_wait(FakeInflight(wait_ready), count=4):
            if inside is not None:
                inside()
        clock.queued = [0.0]
        prof.end_tick()

    def _stall(self, prof, clock, *, late, inside=None):
        """Two clean 100 ms ticks, then a read that takes 400 ms,
        then a probe 5 ms later."""
        for _ in range(2):
            self._iteration(prof, clock, host_s=0.005, probe_ready=False,
                            wait_ready=False, wait_s=0.095)
        self._iteration(prof, clock, host_s=0.005, probe_ready=False,
                        wait_ready=late, wait_s=0.400, inside=inside)
        self._iteration(prof, clock, host_s=0.005, probe_ready=True,
                        wait_ready=False, wait_s=0.0)
        return prof.tick_loop()

    def test_a_late_read_is_counted_and_starves_the_next_probe(self):
        clock = FakeClock(step=0.0)
        ann = RecordingAnnotation()
        prof = _profiler(clock=clock, annotate=ann)
        loop = self._stall(prof, clock, late=True)
        assert loop['late_reads'] == 1
        assert loop['late_read_s'] == pytest.approx(0.400)
        # The tick in flight started by the late read's entry at the
        # latest: 405 ms of host time less a 100 ms tick.
        assert loop['starved_ticks'] == 1
        assert loop['starved_s'] == pytest.approx(0.305)
        waits = [kw for name, kw in ann.opened
                 if name == 'skytpu/device-wait']
        assert [kw['ready'] for kw in waits] == [0, 0, 1, 0]

    def test_a_read_not_ready_at_entry_changes_nothing(self):
        """The same stall with the tick still running at the read's
        entry: no late read, and the probe's estimate is the parent's,
        step for step the plain `device-wait` phase's."""
        runs = []
        for plain in (False, True):
            clock = FakeClock(step=0.0)
            prof = _profiler(clock=clock)
            if plain:      # what the probe made of the same loop before
                prof.device_wait = lambda tick, count: prof.phase(
                    'device-wait', count=count)
            runs.append(self._stall(prof, clock, late=False))
        for loop in runs:
            loop.pop('elapsed_s')
        assert runs[0] == runs[1]
        assert runs[0]['late_reads'] == 0
        assert runs[0]['starved_ticks'] == 1
        assert runs[0]['starved_s'] == 0.0

    def test_a_collection_inside_the_read_moves_the_reference(
            self, quiet_collector):
        clock = FakeClock(step=0.0)
        ann = RecordingAnnotation()
        prof = _profiler(clock=clock, annotate=ann)
        prof.start()

        def collect():   # starts 10 ms into the read, lasts 300 ms
            clock.queued = [0.010, 0.300]
            gc.collect()
            clock.queued = [0.090]     # the read returns 400 ms in

        try:
            loop = self._stall(prof, clock, late=False, inside=collect)
        finally:
            prof.close()
        assert loop['gc_pauses'] == 1
        assert loop['gc_s'] == pytest.approx(0.300)
        assert loop['late_reads'] == 0
        # From the collection's start (10 ms into the read) to the
        # probe: 395 ms, less the 100 ms tick.
        assert loop['starved_s'] == pytest.approx(0.295)

    def test_a_collection_inside_the_dispatch_starves_the_device(
            self, quiet_collector):
        """The probe asks before the dispatch; a pause inside it lets the
        tick in flight finish to an idle device.  The read then finds
        its tick finished at entry, and the device counts as dry from
        when that tick should have ended to the dispatch's end."""
        clock = FakeClock(step=0.0)
        prof = _profiler(clock=clock)
        prof.start()

        def collect():   # 300 ms, begun as the dispatch began
            clock.queued = [0.0, 0.300]
            gc.collect()
            clock.queued = [0.0]

        try:
            for _ in range(2):
                self._iteration(prof, clock, host_s=0.005,
                                probe_ready=False, wait_ready=False,
                                wait_s=0.095)
            self._iteration(prof, clock, host_s=0.005, probe_ready=False,
                            wait_ready=True, wait_s=0.0001,
                            in_dispatch=collect)
        finally:
            prof.close()
        loop = prof.tick_loop()
        assert (loop['gc_pauses'], loop['late_reads']) == (1, 1)
        assert loop['starved_ticks'] == 0        # the probe saw nothing
        # The tick in flight should have ended 100 ms after the last
        # return; the dispatch ended 305 ms after it.
        assert loop['starved_s'] == pytest.approx(0.205)

    def test_a_parked_engine_counts_idle_and_drops_its_hook(self):
        import flax.linen as nn

        from skypilot_tpu.models.transformer import Transformer
        from skypilot_tpu.serve import batching_engine
        cfg = configs.get_config('tiny')
        params = nn.meta.unbox(Transformer(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))['params'])
        eng = batching_engine.ContinuousBatchingEngine(
            cfg, params, max_len=64, slots=2, prefill_chunk=8,
            kv_pages=48, page_size=8)
        prof = eng._profiler
        try:
            first = eng.stats()['tick_loop']
            time.sleep(0.3)                 # nothing to do: parked
            later = eng.stats()['tick_loop']
            assert prof._gc_hook in gc.callbacks
        finally:
            eng.stop()
        assert later['idle_s'] > first['idle_s']
        assert later['elapsed_s'] - first['elapsed_s'] >= \
            later['idle_s'] - first['idle_s']
        assert later['iterations'] == first['iterations'] == 0
        assert prof._gc_hook is None
        assert all(getattr(cb, '__self__', None) is not prof
                   for cb in gc.callbacks)


def _counter_value(name, **labels):
    parsed = metrics_lib.parse_exposition(metrics_lib.expose())
    want = set(labels.items())
    for got_labels, value in parsed.get(name, {}).items():
        if want <= set(got_labels):
            return value
    return 0.0


class TestRecompileSentinel:

    def test_warmup_compiles_are_free_steady_trips_exactly_once(self):
        journal = RecordingJournal()
        sentinel = profiling.RecompileSentinel(
            steady_after=8, journal_factory=lambda: journal,
            disabled=False)
        fn = sentinel.wrap('step', jax.jit(lambda x: x * 2))
        before = _counter_value('skytpu_engine_recompiles_total',
                                fn='step')
        # Warm-up compile + a steady run of identical shapes.
        for _ in range(12):
            fn(jnp.ones((4,), jnp.float32))
        snap = sentinel.snapshot()['fns']['step']
        assert snap['compiles'] == 1
        assert snap['steady_recompiles'] == 0
        assert journal.events == []
        # Shape-buster after a quiet streak: exactly one detection.
        fn(jnp.ones((5,), jnp.float32))
        snap = sentinel.snapshot()['fns']['step']
        assert snap['compiles'] == 2
        assert snap['steady_recompiles'] == 1
        [(event, fields)] = journal.events
        assert event == 'recompile_detected'
        assert fields['fn'] == 'step'
        assert 'float32[5]' in fields['shapes']
        assert fields['quiet_calls'] >= 8
        after = _counter_value('skytpu_engine_recompiles_total',
                               fn='step')
        assert after == before + 1
        # The new shape is now cached: steady state again, no retrips.
        for _ in range(12):
            fn(jnp.ones((5,), jnp.float32))
        assert sentinel.snapshot()['fns']['step'][
            'steady_recompiles'] == 1
        assert len(journal.events) == 1

    def test_immediate_reshape_is_warmup_not_steady(self):
        journal = RecordingJournal()
        sentinel = profiling.RecompileSentinel(
            steady_after=8, journal_factory=lambda: journal,
            disabled=False)
        fn = sentinel.wrap('prefill', jax.jit(lambda x: x + 1))
        # Back-to-back new shapes (bucketed prefill warm-up): compiles
        # counted, but none had a quiet streak -> zero steady.
        for n in (1, 2, 3, 4):
            fn(jnp.ones((n,), jnp.float32))
        snap = sentinel.snapshot()['fns']['prefill']
        assert snap['compiles'] == 4
        assert snap['steady_recompiles'] == 0
        assert journal.events == []

    def test_signature_fallback_for_uncached_callables(self):
        sentinel = profiling.RecompileSentinel(
            steady_after=2, journal_factory=RecordingJournal,
            disabled=False)
        fn = sentinel.wrap('plain', lambda x: x)   # no _cache_size()
        for _ in range(5):
            fn(jnp.ones((3,), jnp.float32))
        fn(jnp.ones((9,), jnp.float32))
        snap = sentinel.snapshot()['fns']['plain']
        assert snap['compiles'] == 2
        assert snap['steady_recompiles'] == 1

    def test_disabled_wrap_is_identity(self):
        sentinel = profiling.RecompileSentinel(disabled=True)
        fn = lambda x: x            # noqa: E731
        assert sentinel.wrap('f', fn) is fn
        assert sentinel.wrap('g', None) is None


class TestExports:

    def _snapshot_all_phases(self):
        clock = FakeClock(step=0.001)
        prof = _profiler(clock=clock, memory_cb=lambda: 4096)
        prof.begin_tick()
        for phase in profiling.PHASES:
            _run_phase(prof, phase, count=2)
        prof.end_tick()
        return prof.snapshot()

    def test_collapsed_stacks(self):
        lines = profiling.collapsed_stacks(
            self._snapshot_all_phases()).splitlines()
        assert len(lines) == len(profiling.PHASES)
        for line in lines:
            frame, count = line.rsplit(' ', 1)
            assert frame.startswith('engine;')
            assert int(count) > 0
        assert {l.split(';')[1].split(' ')[0] for l in lines} == \
            set(profiling.PHASES)

    def test_chrome_trace_is_valid_and_carries_all_phases(self):
        trace = profiling.chrome_trace(self._snapshot_all_phases(),
                                       pid=3)
        blob = json.loads(json.dumps(trace))   # JSON-serializable
        assert blob['displayTimeUnit'] == 'ms'
        events = blob['traceEvents']
        bars = [e for e in events if e['ph'] == 'X']
        assert {e['name'] for e in bars} == set(profiling.PHASES)
        assert 'device-wait' in {e['name'] for e in bars}
        for e in bars:
            assert e['dur'] > 0 and e['ts'] > 0 and e['pid'] == 3
            assert e['args'] == {'n': 1, 'count': 2}
        assert [e for e in events if e['ph'] != 'X'] == []


@pytest.fixture(scope='module')
def profiled_server():
    srv = model_server.ModelServer('tiny', max_len=64, max_batch=2,
                                   continuous_batching=True)
    yield srv
    srv.close()


class TestProfileEndpoint:

    def _check_payload(self, payload):
        prof = payload['profile']
        assert prof['enabled'] is True
        assert prof['ticks'] > 0
        assert 'decode-step' in prof['phases']
        # Steady-state must be clean on a well-behaved run.
        assert prof['recompiles']['steady_recompiles_total'] == 0
        assert 'step' in prof['recompiles']['fns']

    def test_threaded_front(self, profiled_server):
        port, shutdown = model_server.start_background(profiled_server)
        try:
            gen = requests.post(f'http://127.0.0.1:{port}/generate',
                                json={'prompt_ids': [[3, 1, 4]],
                                      'max_new_tokens': 4},
                                timeout=120)
            assert gen.status_code == 200, gen.text
            resp = requests.get(f'http://127.0.0.1:{port}/profile',
                                timeout=10)
        finally:
            shutdown()
        assert resp.status_code == 200
        self._check_payload(resp.json())

    def test_async_front(self, profiled_server):
        port, shutdown = async_server.start_background(profiled_server)
        try:
            resp = requests.get(f'http://127.0.0.1:{port}/profile',
                                timeout=10)
        finally:
            shutdown()
        assert resp.status_code == 200
        self._check_payload(resp.json())


class TestServeProfileCli:

    def test_export_trace_carries_all_phases(self, tmp_path,
                                             monkeypatch):
        """`sky serve profile --export-trace` against a replica whose
        ring saw every phase writes a valid Chrome trace with every
        phase's bar."""
        import http.server
        import threading

        from click.testing import CliRunner

        from skypilot_tpu import cli, serve

        clock = FakeClock(step=0.001)
        prof = profiling.TickProfiler(ring_ticks=16, disabled=False,
                                      memory_cb=lambda: 2048,
                                      clock=clock)
        prof.begin_tick()
        for phase in profiling.PHASES:
            _run_phase(prof, phase)
        prof.end_tick()
        sentinel = profiling.RecompileSentinel(
            disabled=False, journal_factory=RecordingJournal)
        snap = prof.snapshot()
        snap['recompiles'] = sentinel.snapshot()
        payload = json.dumps({'status': 'ok', 'profile': snap}).encode()

        class Handler(http.server.BaseHTTPRequestHandler):

            def do_GET(self):          # noqa: N802
                self.send_response(200)
                self.send_header('Content-Type', 'application/json')
                self.send_header('Content-Length',
                                 str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

            def log_message(self, *args):
                pass

        httpd = http.server.ThreadingHTTPServer(('127.0.0.1', 0),
                                                Handler)
        threading.Thread(target=httpd.serve_forever,
                         daemon=True).start()
        port = httpd.server_address[1]
        record = {'name': 'svc', 'status': 'READY',
                  'load_balancer_port': None,
                  'replicas': [{'replica_id': 1, 'role': 'mixed',
                                'status': 'READY',
                                'url': f'http://127.0.0.1:{port}'}]}
        monkeypatch.setattr(serve, 'status', lambda names=None: [record])
        out_path = tmp_path / 'tick.json'
        try:
            result = CliRunner().invoke(
                cli.cli, ['serve', 'profile', 'svc',
                          '--export-trace', str(out_path)])
        finally:
            httpd.shutdown()
            httpd.server_close()
        assert result.exit_code == 0, result.output
        assert 'steady-state recompiles: 0' in result.output
        assert 'engine;decode-step' in result.output
        trace = json.loads(out_path.read_text())
        assert trace['displayTimeUnit'] == 'ms'
        bars = [e for e in trace['traceEvents'] if e['ph'] == 'X']
        assert {e['name'] for e in bars} == set(profiling.PHASES)


class TestModelFlopsPerToken:

    def test_computed_path_includes_attention_term(self):
        cfg = configs.get_config('tiny')
        n_params, max_len = 100_000, 64
        attn = 2.0 * cfg.n_layers * cfg.n_heads * cfg.head_dim * max_len
        got = model_server.model_flops_per_token(cfg, n_params, max_len)
        assert got == pytest.approx(2.0 * n_params + attn)
        # The attention term is sequence-length dependent.
        longer = model_server.model_flops_per_token(cfg, n_params, 128)
        assert longer - got == pytest.approx(attn)

    def test_env_override_wins_and_non_numeric_falls_back(
            self, monkeypatch):
        cfg = configs.get_config('tiny')
        monkeypatch.setenv('SKYTPU_MODEL_FLOPS_PER_TOKEN', '3.5e9')
        assert model_server.model_flops_per_token(cfg, 1, 64) == 3.5e9
        monkeypatch.setenv('SKYTPU_MODEL_FLOPS_PER_TOKEN', 'banana')
        got = model_server.model_flops_per_token(cfg, 1000, 64)
        assert got == pytest.approx(
            2000 + 2.0 * cfg.n_layers * cfg.n_heads * cfg.head_dim * 64)


class TestOverheadBudget:
    """The always-on budget: profile-on vs SKYTPU_PROFILE_DISABLE=1
    may differ by at most 3% of a tick's work.

    Wall-clocking two full workloads head-to-head is hopeless on a
    noisy CI box (run-to-run jitter alone exceeds 3%), so the A/B is
    factored: the profiler's marginal per-tick cost comes from a tight
    on-vs-off microbenchmark of the instrumentation alone (stable —
    both arms are long uniform loops), and the budget is asserted
    against a measured representative tick's compute."""

    TICKS = 4000

    @classmethod
    def _per_tick_cost(cls, prof):
        """Seconds per tick of the instrumentation calls alone, at the
        real call pattern of a steady pipelined iteration (the probe,
        three phases, the read's own readiness check, begin/end; the
        real `TraceAnnotation`, with no profiler session open)."""
        inflight = FakeInflight(False)
        t0 = time.perf_counter()
        for _ in range(cls.TICKS):
            prof.begin_tick()
            prof.probe_starved(inflight)
            with prof.phase('decode-step', count=16):
                pass
            with prof.device_wait(inflight, count=16):
                pass
            with prof.phase('sample') as phase:
                phase.count = 16
            prof.end_tick()
        return (time.perf_counter() - t0) / cls.TICKS

    def test_profiler_overhead_within_3_percent(self):
        on = profiling.TickProfiler(disabled=False,
                                    memory_cb=lambda: None)
        off = profiling.TickProfiler(disabled=True,
                                     memory_cb=lambda: None)
        self._per_tick_cost(on), self._per_tick_cost(off)   # warm-up
        marginal = min(self._per_tick_cost(on) -
                       self._per_tick_cost(off) for _ in range(5))
        # A representative tick's work: even the tiny model's decode
        # step is milliseconds; 300us is a conservative floor.
        def tick_work():
            t0 = time.perf_counter()
            assert sum(range(30000)) > 0
            return time.perf_counter() - t0
        work = min(tick_work() for _ in range(20))
        assert marginal <= 0.03 * work, (marginal, work)
        # The profiler's own overhead model stays in the same regime.
        snap = on.snapshot()
        per_tick_model = snap['overhead_s'] / max(1, snap['ticks'])
        assert per_tick_model <= 0.03 * work
