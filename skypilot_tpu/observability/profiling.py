"""Continuous profiling plane: tick-phase spans + recompile sentinel
for the serving engines.

Two always-on, low-overhead instruments:

- **TickProfiler** -- the engine loop's only span recorder.  The worker
  opens each phase of an iteration where it happens (`with
  prof.phase('admit'):`); a phase is recorded twice from the same two
  clock reads: into a bounded ring (host clock, `time.monotonic`, the
  clock `RequestSpan` uses) and as a `jax.profiler.TraceAnnotation`
  named `skytpu/<phase>`, which lands on the host plane of the
  profiler's `.xplane.pb` on the clock the device events are on.  The
  whole iteration is `skytpu/tick` and carries the iteration's number
  (`n`), so a ring record is tied to trace time by that number.  With
  no profiler session open an annotation is a no-op check.  Phases
  are exclusive: a phase opened inside another (the slice's
  `slice-sync` inside `decode-step`) takes its time out of the
  enclosing one.  Iterations that did no work never enter the ring.
  `decode-step`, `prefill-chunk` and `spec-verify`'s dispatch are
  asynchronous: those phases time the host's dispatch, and
  `device-wait` (the blocking read of the tick in flight) is the one
  phase in which the host waits for the device.  Cumulative totals
  (`tick_loop()`) are what `stats()` carries; phase durations also
  feed the process-global `skytpu_engine_tick_phase_seconds{phase}`
  histogram so the fleet aggregator sees the breakdown without
  touching `/profile`.

  The **starvation probe** (`probe_starved`) is asked right before an
  iteration's first dispatch with a tick in flight: if that tick has
  already finished, the device has run dry.  The count is exact; the
  seconds are an estimate, also made where the read finds its tick
  finished at entry (see `_wait_returned`).

  Outside the iterations the profiler names what else holds the
  worker, so that every idle gap of the device has a host span open at
  its start: `skytpu/idle` (stat `reason`: `empty`, parked waiting for
  work; `pages`, the pool-exhausted retry throttle) and `skytpu/gc`
  (stats `gen`, `collected`: a collector pause, from a `gc.callbacks`
  hook installed by `start()` and removed by `close()`).  Neither is a
  phase: they never enter the ring or `loop_s`, and are counted in
  `idle_s`, `deferred_s`, `gc_s` and `gc_pauses`.  `device_wait()`
  asks the tick it reads whether it is already finished (stat `ready`):
  such a read is host time, counted in `late_reads` and `late_read_s`.

- **RecompileSentinel** -- wraps the engine's resolved jit entries
  (incl. the Pallas kernel path, a closure constant of the wrapped
  step) and watches `fn._cache_size()` after every call: an increase
  means THIS call compiled.  Compiles during warm-up are expected;
  a compile after `steady_after` quiet calls is the classic silent
  TPU perf killer -- it bumps `skytpu_engine_recompiles_total{fn}` and
  journals `recompile_detected{fn, shapes}` so the post-mortem names
  the shape that busted the cache.

Knobs: `SKYTPU_PROFILE_RING_TICKS` (ring capacity, default 512),
`SKYTPU_PROFILE_DISABLE` (=1 turns both instruments into no-ops).
"""
from __future__ import annotations

import collections
import gc
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from skypilot_tpu.observability import metrics as metrics_lib

# The complete tick-phase vocabulary (docs/observability.md mirrors
# this table).  An iteration records only the phases that ran;
# decode-step and spec-verify are mutually exclusive per iteration,
# slice-sync appears only on multi-host replicas.
PHASES = ('admit', 'prefill-chunk', 'decode-step', 'spec-verify',
          'device-wait', 'sample', 'page-scatter', 'handoff',
          'slice-sync')
DEVICE_WAIT = 'device-wait'
# Phases that hand the device its next program: the first of them to
# end in an iteration is when the device has work again.
DISPATCHES = ('decode-step', 'prefill-chunk', 'spec-verify')
# Names on the profiler trace's host plane: `skytpu/tick` (stat `n`)
# around `skytpu/<phase>` (stats `n`, `count`, `request_id`; `ready` on
# `device-wait`); between iterations `skytpu/idle` (stats `n`,
# `reason`), and `skytpu/gc` (stats `gen`, `collected`) on whichever
# thread collected.
TRACE_PREFIX = 'skytpu/'
# Why the worker parked, and the `tick_loop` key its seconds go to.
IDLE_KEYS = {'empty': 'idle_s', 'pages': 'deferred_s'}

DEFAULT_RING_TICKS = 512
# Steady-state threshold: a compile after this many quiet calls of the
# same jit entry is a regression signal, not warm-up.
DEFAULT_STEADY_AFTER = 64

_M_PHASE = metrics_lib.histogram(
    'skytpu_engine_tick_phase_seconds',
    'Engine tick time by phase (exclusive: phases of one tick sum to '
    'the tick duration).',
    ('phase',),
    buckets=(50e-6, 200e-6, 1e-3, 5e-3, 20e-3, 0.1, 0.5))
_M_RECOMPILES = metrics_lib.counter(
    'skytpu_engine_recompiles_total',
    'Steady-state recompilations detected per jit entry (compiles '
    'after the warm-up window — each one is a served-tick stall).',
    ('fn',))
# Pre-bound histogram children: .labels() validates and rebuilds the
# label tuple on every call, which is most of the per-phase cost — the
# phase vocabulary is closed, so bind once.
_PHASE_OBSERVERS = {name: _M_PHASE.labels(phase=name)
                    for name in PHASES}


def profiling_disabled() -> bool:
    return bool(os.environ.get('SKYTPU_PROFILE_DISABLE'))


def ring_ticks_default() -> int:
    raw = os.environ.get('SKYTPU_PROFILE_RING_TICKS')
    try:
        n = int(raw) if raw else DEFAULT_RING_TICKS
    except ValueError:
        n = DEFAULT_RING_TICKS
    return max(1, n)


def serve_journal():
    """The serving flight recorder (`<journal_root>/serve.jsonl`) —
    recompile detections and the tick_profile lifecycle land next to
    the page alloc/free events chaos scenarios already replay."""
    from skypilot_tpu.observability import events as events_lib  # pylint: disable=import-outside-toplevel
    return events_lib.get_journal(
        os.path.join(events_lib.journal_root(), 'serve.jsonl'))


def _default_memory_cb() -> Optional[int]:
    """Device-memory watermark in bytes (None when the backend does
    not report memory stats — CPU jax returns None)."""
    try:
        import jax  # pylint: disable=import-outside-toplevel
        dev = jax.local_devices()[0]
        stats = dev.memory_stats()
    except Exception:  # pylint: disable=broad-except
        return None
    if not stats:
        return None
    peak = stats.get('peak_bytes_in_use', stats.get('bytes_in_use'))
    return int(peak) if peak is not None else None


def _quantile(sorted_vals: List[float], q: float) -> Optional[float]:
    if not sorted_vals:
        return None
    idx = min(len(sorted_vals) - 1, int(q * len(sorted_vals)))
    return sorted_vals[idx]


class _NullPhase:
    """What `phase()` hands out under SKYTPU_PROFILE_DISABLE."""
    record = True
    count = None
    dur_s = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class _Phase:
    """One open phase: a context manager that, from one pair of clock
    reads, appends `[name, start, seconds, count, request_id]` to the
    iteration's record and closes the `skytpu/<name>` annotation.  Set
    `record = False` before leaving a phase whose machinery ran but did
    no work (the trace still shows it; the ring does not), and `count`
    once what it worked on is known.  A `device-wait` from
    `device_wait()` carries `ready` (the tick read was finished at
    entry) and, set by the collector hook, `gc_t0` (when the first
    collection inside it began)."""

    __slots__ = ('_prof', 'name', 'request_id', 'count', 'record',
                 'dur_s', '_t0', '_inner_s', '_outer', '_span', '_count0',
                 'ready', 'gc_t0')

    def __init__(self, prof: 'TickProfiler', name: str,
                 request_id: Optional[str], count: Optional[int],
                 ready: Optional[bool] = None):
        self._prof = prof
        self.name = name
        self.request_id = request_id
        self.count = count
        self.record = True
        self.dur_s = 0.0
        self.ready = ready
        self.gc_t0: Optional[float] = None

    def __enter__(self):
        prof = self._prof
        args: Dict[str, Any] = {'n': prof.iteration}
        if self.count is not None:
            args['count'] = self.count
        if self.request_id is not None:
            args['request_id'] = self.request_id
        if self.ready is not None:
            args['ready'] = int(self.ready)
        self._count0 = self.count
        self._span = prof._annotate(TRACE_PREFIX + self.name, **args)  # pylint: disable=protected-access
        self._outer = prof._open  # pylint: disable=protected-access
        prof._open = self  # pylint: disable=protected-access
        self._inner_s = 0.0
        self._span.__enter__()
        self._t0 = prof._clock()  # pylint: disable=protected-access
        return self

    def __exit__(self, *exc):
        prof = self._prof
        now = prof._clock()  # pylint: disable=protected-access
        if self.count != self._count0:
            self._span.set_metadata(count=self.count)   # known at the end
        self._span.__exit__(*exc)
        whole = now - self._t0
        self.dur_s = whole - self._inner_s
        prof._open = self._outer  # pylint: disable=protected-access
        if self._outer is not None:
            self._outer._inner_s += whole  # pylint: disable=protected-access
        if self.name == DEVICE_WAIT:
            prof._wait_returned(self, now)  # pylint: disable=protected-access
        elif self.name in DISPATCHES and prof._t_dispatched is None:  # pylint: disable=protected-access
            prof._t_dispatched = now  # pylint: disable=protected-access
        if self.record:
            prof._cur.append(  # pylint: disable=protected-access
                [self.name, self._t0 - prof._t_tick0, self.dur_s,  # pylint: disable=protected-access
                 self.count, self.request_id])
        return False


_NULL_PHASE = _NullPhase()


class _Idle:
    """The worker parked between iterations: the `skytpu/idle` span and
    its seconds under `IDLE_KEYS[reason]`, from one pair of clock
    reads."""

    __slots__ = ('_prof', 'reason', '_span', '_t0')

    def __init__(self, prof: 'TickProfiler', reason: str):
        self._prof = prof
        self.reason = reason

    def __enter__(self):
        prof = self._prof
        self._span = prof._annotate(TRACE_PREFIX + 'idle',  # pylint: disable=protected-access
                                    n=prof.iteration, reason=self.reason)
        self._span.__enter__()
        self._t0 = prof._clock()  # pylint: disable=protected-access
        return self

    def __exit__(self, *exc):
        prof = self._prof
        seconds = prof._clock() - self._t0  # pylint: disable=protected-access
        self._span.__exit__(*exc)
        key = IDLE_KEYS[self.reason]
        with prof._lock:  # pylint: disable=protected-access
            prof._parked[key] += seconds  # pylint: disable=protected-access
        return False


class TickProfiler:
    """Per-iteration phase spans in a bounded ring, and on the
    profiler trace.

    Single-writer (the engine worker thread) / multi-reader
    (`snapshot()`, `tick_loop()` from HTTP threads): the iteration in
    progress belongs to the writer; only the ring append and the
    cumulative totals take the lock.
    """

    def __init__(self, *, ring_ticks: Optional[int] = None,
                 disabled: Optional[bool] = None,
                 memory_cb: Optional[Callable[[], Optional[int]]] = None,
                 clock: Callable[[], float] = time.monotonic,
                 annotate: Optional[Callable[..., Any]] = None) -> None:
        self.disabled = (profiling_disabled() if disabled is None
                         else bool(disabled))
        self.ring_ticks = (ring_ticks_default() if ring_ticks is None
                           else max(1, int(ring_ticks)))
        self._clock = clock
        self._memory_cb = (_default_memory_cb if memory_cb is None
                           else memory_cb)
        self._mem_dead = False   # backend reported nothing; stop asking
        if annotate is None and not self.disabled:
            from jax.profiler import TraceAnnotation  # pylint: disable=import-outside-toplevel
            annotate = TraceAnnotation
        self._annotate = annotate
        # Ring records carry both clocks: `t0_s` on `clock` and `ts`,
        # the same instant as wall time through this one offset.
        self._wall_offset = time.time() - clock()
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(
            maxlen=self.ring_ticks)
        self._ticks = 0          # iterations retained (cumulative)
        self._laps = 0           # recorded phases (cumulative)
        self._loop_s = 0.0       # their durations (cumulative)
        self._phase_totals: Dict[str, float] = {}
        self._phase_counts: Dict[str, int] = {}
        self._starved_ticks = 0
        self._starved_s = 0.0
        self._late_reads = 0
        self._late_read_s = 0.0
        self._parked = {key: 0.0 for key in IDLE_KEYS.values()}
        self._t_born = clock()   # `elapsed_s` counts from here
        self._mem_watermark: Optional[int] = None
        # Collector pauses.  Written by the hook alone, without the
        # lock: CPython runs one collection at a time, and one may
        # start while this or any thread holds the lock.
        self._gc_s = 0.0
        self._gc_pauses = 0
        self._gc_t0 = 0.0
        self._gc_span: Any = None
        self._gc_hook: Optional[Callable[[str, Dict[str, Any]], None]] = None
        # Worker-thread state for the iteration in progress.
        self.iteration = 0       # numbers every begin_tick, idle or not
        self._t_tick0 = 0.0
        self._cur: List[List[Any]] = []
        self._open: Optional[_Phase] = None
        self._tick_span: Any = None
        # Starvation probe: the reference point the last device-wait
        # left (see `_wait_returned`) and whether a stall moved it off
        # the return, the last interval between two clean returns of an
        # unstarved iteration (the running tick length), and this
        # iteration's findings: when the probe found the tick in flight
        # finished, when the first dispatch ended, the dry estimate.
        self._t_ref: Optional[float] = None
        self._ref_stalled = False
        self._tick_len: Optional[float] = None
        self._waited = False
        self._starved_now: Optional[float] = None
        self._t_dispatched: Optional[float] = None
        self._dry_s = 0.0
        # Self-overhead model: one phase's clock reads and annotation,
        # measured once, multiplied by the cumulative phase count in
        # snapshot().
        self._per_lap_s = 0.0 if self.disabled else self._calibrate()

    def _calibrate(self) -> float:
        n = 256
        t0 = time.perf_counter()
        for _ in range(n):
            with self._annotate(TRACE_PREFIX + 'calibrate'):
                self._clock()
                self._clock()
        # Twice the measured cost is a deliberately pessimistic bound
        # on the list append and the bookkeeping around them.
        return (time.perf_counter() - t0) / n * 2.0

    # ------------------------------------------------------- lifecycle

    def start(self) -> None:
        """Install this profiler's `gc.callbacks` hook (once; never
        when disabled).  The engine's worker calls it as it starts and
        `close()` as it exits."""
        if self.disabled or self._gc_hook is not None:
            return
        self._gc_hook = self._on_gc
        gc.callbacks.append(self._gc_hook)

    def close(self) -> None:
        """Remove the collector hook; the profiler's totals stay."""
        if self._gc_hook is None:
            return
        gc.callbacks.remove(self._gc_hook)
        self._gc_hook = None

    def _on_gc(self, phase: str, info: Dict[str, Any]) -> None:
        """Every collection, on any thread, stops the worker: `skytpu/gc`
        from `start` to `stop`, and `gc_s`, `gc_pauses`.  A collection
        that begins inside a `device-wait` marks it for the probe."""
        now = self._clock()
        if phase == 'start':
            self._gc_t0 = now
            wait = self._open
            if (wait is not None and wait.name == DEVICE_WAIT and
                    wait.gc_t0 is None):
                wait.gc_t0 = now
            self._gc_span = self._annotate(TRACE_PREFIX + 'gc',
                                           gen=info['generation'])
            self._gc_span.__enter__()
        elif self._gc_span is not None:
            span, self._gc_span = self._gc_span, None
            span.set_metadata(collected=info['collected'])
            span.__exit__(None, None, None)
            self._gc_s += now - self._gc_t0
            self._gc_pauses += 1

    # ---------------------------------------------- worker-thread API

    def begin_tick(self) -> None:
        self.iteration += 1
        if self.disabled:
            return
        self._cur = []
        self._open = None
        self._waited = False
        self._starved_now = None
        self._t_dispatched = None
        self._dry_s = 0.0
        self._tick_span = self._annotate(TRACE_PREFIX + 'tick',
                                         n=self.iteration)
        self._tick_span.__enter__()
        self._t_tick0 = self._clock()

    def phase(self, name: str, *, request_id: Optional[str] = None,
              count: Optional[int] = None):
        """Open phase `name` of this iteration (a context manager).
        `request_id` for a phase that works for one request (admit,
        chunk and scatter do), `count` for what it works on (live
        slots at dispatch, chunk width, pages scattered); a count
        known only at the end is set on the phase before it closes."""
        if self.disabled:
            return _NULL_PHASE
        return _Phase(self, name, request_id, count)

    def device_wait(self, tick, *, count: Optional[int] = None):
        """The `device-wait` phase around the blocking read of `tick`
        (a handle of the tick's outputs).  Asks `tick.is_ready()` once
        on entry, onto the span as stat `ready`: a read of a tick that
        had already finished is host time (transfer, runtime, the GIL),
        counted in `late_reads` and `late_read_s`."""
        if self.disabled:
            return _NULL_PHASE
        return _Phase(self, DEVICE_WAIT, None, count,
                      ready=bool(tick.is_ready()))

    def idle(self, reason: str):
        """The worker parked between iterations (a context manager):
        `reason` 'empty' waits for work, 'pages' throttles the retry of
        an admission the exhausted pool deferred.  Not an iteration."""
        if self.disabled:
            return _NULL_PHASE
        return _Idle(self, reason)

    def probe_starved(self, inflight_finished) -> bool:
        """Ask, right before an iteration's first dispatch, whether the
        tick in flight has already finished (`is_ready()`, no
        blocking): if so nothing is queued behind it and the device
        has run dry.  Counted exactly in `starved_ticks`; the seconds
        are estimated when the iteration's read returns
        (`_wait_returned`)."""
        if self.disabled or not inflight_finished.is_ready():
            return False
        self._starved_now = self._clock()
        return True

    def _wait_returned(self, wait: _Phase, now: float) -> None:
        """Two jobs.  First, where the tick this read waited for had
        finished before the iteration's first dispatch ended -- the
        probe found it so, or the read found it so at entry (a stall
        inside the dispatch) -- `starved_s` gets an ESTIMATE of how
        long the device ran dry: from the last reference point plus the
        running tick length (when that tick should have finished) to
        the end of the first dispatch, floored at 0; good to the
        tick-to-tick variation, and 0 until one clean interval has been
        seen.  Second, the reference point this read leaves: the
        earliest of its return, its entry where the tick was already
        finished there, and the start of a collection inside it.  A
        host stalled inside the read then counts toward `starved_s`
        and not as device time.  Only an interval between two clean
        returns (no stall in either) teaches the tick length."""
        if self._starved_now is not None or wait.ready:
            end = self._t_dispatched
            if end is None:
                end = (self._starved_now if self._starved_now is not None
                       else wait._t0)  # pylint: disable=protected-access
            if self._t_ref is not None and self._tick_len is not None:
                self._dry_s = max(0.0, end - self._t_ref - self._tick_len)
        ref = now
        if wait.ready:
            ref = wait._t0  # pylint: disable=protected-access
            with self._lock:
                self._late_reads += 1
                self._late_read_s += now - ref
        if wait.gc_t0 is not None:
            ref = min(ref, wait.gc_t0)
        stalled = bool(wait.ready) or wait.gc_t0 is not None
        if (self._t_ref is not None and self._starved_now is None and
                not stalled and not self._ref_stalled):
            self._tick_len = now - self._t_ref
        self._t_ref = ref
        self._ref_stalled = stalled
        self._waited = True

    def end_tick(self) -> None:
        """Retain the iteration if any phase recorded; idle spins of
        the worker loop never enter the ring."""
        if self.disabled:
            return
        now = self._clock()
        self._tick_span.__exit__(None, None, None)
        if not self._waited:
            # No tick was in flight: the next device-wait return does
            # not follow the last one by a tick.
            self._t_ref = None
        cur = self._cur
        self._cur = []
        if not cur:
            return
        dur = now - self._t_tick0
        rec = {
            'n': self.iteration,
            'ts': self._t_tick0 + self._wall_offset,
            't0_s': self._t_tick0,
            'dur_s': dur,
            'phases': cur,
        }
        with self._lock:
            self._ring.append(rec)
            self._ticks += 1
            self._laps += len(cur)
            self._loop_s += dur
            for name, _, seconds, _, _ in cur:
                self._phase_totals[name] = (
                    self._phase_totals.get(name, 0.0) + seconds)
                self._phase_counts[name] = (
                    self._phase_counts.get(name, 0) + 1)
            if self._starved_now is not None:
                self._starved_ticks += 1
            self._starved_s += self._dry_s
        for name, _, seconds, _, _ in cur:
            obs = _PHASE_OBSERVERS.get(name)
            if obs is None:
                obs = _M_PHASE.labels(phase=name)
            obs.observe(seconds)

    # ------------------------------------------------- reader-side API

    @property
    def ticks(self) -> int:
        with self._lock:
            return self._ticks

    def tick_loop(self) -> Dict[str, Any]:
        """Cumulative totals since the engine started, all monotone (a
        reader takes a difference): iterations that did work, their
        summed durations, seconds by phase, the starvation probe's
        count and estimated seconds, the late reads, the seconds parked
        (`idle_s` waiting for work, `deferred_s` throttled by the
        pool), collector pauses (every thread's), and `elapsed_s`,
        seconds on the profiler's clock since it was made: the
        denominator of a share over a reader's window."""
        elapsed = self._clock() - self._t_born
        with self._lock:
            return {'iterations': self._ticks,
                    'loop_s': self._loop_s,
                    'phase_s': dict(self._phase_totals),
                    'starved_ticks': self._starved_ticks,
                    'starved_s': self._starved_s,
                    'late_reads': self._late_reads,
                    'late_read_s': self._late_read_s,
                    **self._parked,
                    'gc_pauses': self._gc_pauses,
                    'gc_s': self._gc_s,
                    'elapsed_s': elapsed}

    def _read_memory(self) -> Optional[int]:
        """One read of the backend's watermark, on the reader's
        thread (it only rises, so a per-iteration series said no
        more)."""
        if self._mem_dead:
            return None
        mem = self._memory_cb()
        if mem is None:
            self._mem_dead = True
        return mem

    def snapshot(self) -> Dict[str, Any]:
        """JSON-ready view: ring, per-phase aggregates + quantiles over
        the ring, the cumulative totals, the device-memory watermark,
        and the profiler's own modeled overhead (what the ≤3% budget is
        asserted against)."""
        mem = self._read_memory()
        with self._lock:
            ring = [dict(rec, phases=[list(p) for p in rec['phases']])
                    for rec in self._ring]
            totals = dict(self._phase_totals)
            counts = dict(self._phase_counts)
            ticks = self._ticks
            laps = self._laps
            if mem is not None and (self._mem_watermark is None or
                                    mem > self._mem_watermark):
                self._mem_watermark = mem
            watermark = self._mem_watermark
        durs_by_phase: Dict[str, List[float]] = {}
        for rec in ring:
            for entry in rec['phases']:
                durs_by_phase.setdefault(entry[0], []).append(entry[2])
        phases: Dict[str, Dict[str, Any]] = {}
        for name, total in sorted(totals.items()):
            durs = sorted(durs_by_phase.get(name, ()))
            phases[name] = {
                'count': counts.get(name, 0),
                'total_s': total,
                'p50_s': _quantile(durs, 0.5),
                'p90_s': _quantile(durs, 0.9),
                'p99_s': _quantile(durs, 0.99),
                'max_s': durs[-1] if durs else None,
            }
        return {
            'enabled': not self.disabled,
            'ring_ticks': self.ring_ticks,
            'ticks': ticks,
            'phases': phases,
            'ring': ring,
            'tick_loop': self.tick_loop(),
            'device_memory': {'watermark_bytes': watermark,
                              'last_bytes': mem},
            'overhead_s': laps * self._per_lap_s,
        }


class RecompileSentinel:
    """Counts compilations per wrapped jit entry and flags the
    steady-state ones (compile after `steady_after` quiet calls)."""

    def __init__(self, *, steady_after: int = DEFAULT_STEADY_AFTER,
                 journal_factory: Optional[Callable[[], Any]] = None,
                 disabled: Optional[bool] = None) -> None:
        self.disabled = (profiling_disabled() if disabled is None
                         else bool(disabled))
        self.steady_after = int(steady_after)
        self._journal_factory = (serve_journal if journal_factory is None
                                 else journal_factory)
        self._lock = threading.Lock()
        self._fns: Dict[str, Dict[str, Any]] = {}

    def wrap(self, name: str, fn):
        """Pass-through wrapper; after every call, an O(1) cache-size
        probe decides whether THIS call compiled.  Shape signatures
        are only computed on a detected compile — the hot path pays
        one lock and one `len()` probe."""
        if self.disabled or fn is None:
            return fn
        with self._lock:
            self._fns.setdefault(name, {
                'calls': 0, 'compiles': 0, 'steady_recompiles': 0,
                'quiet_calls': 0, 'signatures': {},
                'cache_size': None,
            })

        def wrapped(*args, **kwargs):
            out = fn(*args, **kwargs)
            self._after_call(name, fn, args)
            return out

        wrapped.__name__ = name
        wrapped.__wrapped__ = fn
        return wrapped

    @staticmethod
    def _cache_size(fn) -> Optional[int]:
        try:
            return int(fn._cache_size())  # pylint: disable=protected-access
        except Exception:  # pylint: disable=broad-except
            return None

    @staticmethod
    def _signature(args, limit: int = 16) -> str:
        """Compact abstract signature of a call's positional args:
        dtype[shape] per array leaf, capped so a full params pytree
        does not explode the journal line."""
        try:
            import jax  # pylint: disable=import-outside-toplevel
            leaves = jax.tree_util.tree_leaves(args)
        except Exception:  # pylint: disable=broad-except
            leaves = list(args)
        parts: List[str] = []
        for leaf in leaves:
            shape = getattr(leaf, 'shape', None)
            if shape is not None:
                dtype = getattr(leaf, 'dtype', '?')
                dims = ','.join(str(d) for d in shape)
                parts.append(f'{dtype}[{dims}]')
            else:
                parts.append(type(leaf).__name__)
        if len(parts) > limit:
            parts = parts[:limit] + [f'...+{len(parts) - limit} leaves']
        return '(' + ', '.join(parts) + ')'

    def _after_call(self, name: str, fn, args) -> None:
        size = self._cache_size(fn)
        steady_hit = None
        with self._lock:
            st = self._fns[name]
            st['calls'] += 1
            if size is not None:
                compiled = (st['cache_size'] is not None and
                            size > st['cache_size'])
                first = st['cache_size'] is None and size > 0
                st['cache_size'] = size
                compiled = compiled or first
            else:
                # No cache probe on this callable: fall back to the
                # signature set (pay the signature on every call).
                sig = self._signature(args)
                compiled = sig not in st['signatures']
                if compiled:
                    st['signatures'][sig] = 0
            if compiled:
                st['compiles'] += 1
                sig = self._signature(args)
                st['signatures'][sig] = st['signatures'].get(sig, 0) + 1
                quiet = st['quiet_calls']
                st['quiet_calls'] = 0
                if quiet >= self.steady_after:
                    st['steady_recompiles'] += 1
                    steady_hit = (sig, quiet)
            else:
                st['quiet_calls'] += 1
        if steady_hit is None:
            return
        sig, quiet = steady_hit
        _M_RECOMPILES.labels(fn=name).inc()
        try:
            journal = self._journal_factory()
        except Exception:  # pylint: disable=broad-except
            journal = None
        if journal is not None:
            journal.append('recompile_detected', fn=name, shapes=sig,
                           quiet_calls=quiet)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            out: Dict[str, Any] = {}
            for name, st in sorted(self._fns.items()):
                sigs = dict(list(st['signatures'].items())[:8])
                out[name] = {
                    'calls': st['calls'],
                    'compiles': st['compiles'],
                    'steady_recompiles': st['steady_recompiles'],
                    'signatures': sigs,
                }
        out_total = sum(v['steady_recompiles'] for v in out.values())
        return {'fns': out, 'steady_recompiles_total': out_total,
                'steady_after': self.steady_after,
                'enabled': not self.disabled}


# --------------------------------------------------------------- exports

def collapsed_stacks(snapshot: Dict[str, Any],
                     root: str = 'engine') -> str:
    """Brendan-Gregg collapsed-stack lines (`engine;phase count_us`)
    from a profiler snapshot — pipe into any flamegraph tool."""
    lines = []
    for name, agg in sorted(snapshot.get('phases', {}).items()):
        us = int(round(float(agg.get('total_s') or 0.0) * 1e6))
        lines.append(f'{root};{name} {us}')
    return '\n'.join(lines) + ('\n' if lines else '')


def chrome_trace(snapshot: Dict[str, Any], *, pid: int = 0,
                 tid: int = 0) -> Dict[str, Any]:
    """Chrome trace-event JSON (`chrome://tracing` / Perfetto) from a
    profiler snapshot's ring: one complete ('X') event per recorded
    phase, carrying the iteration's number and the phase's count and
    request id."""
    events: List[Dict[str, Any]] = []
    for rec in snapshot.get('ring', ()):
        base_us = float(rec.get('ts', 0.0)) * 1e6
        for entry in rec.get('phases', ()):
            name, rel, dur = entry[0], float(entry[1]), float(entry[2])
            args = {'n': rec.get('n')}
            if len(entry) > 3 and entry[3] is not None:
                args['count'] = entry[3]
            if len(entry) > 4 and entry[4] is not None:
                args['request_id'] = entry[4]
            events.append({
                'name': name, 'cat': 'engine-tick', 'ph': 'X',
                'ts': base_us + rel * 1e6,
                'dur': max(dur * 1e6, 0.01),
                'pid': pid, 'tid': tid, 'args': args,
            })
    return {'traceEvents': events, 'displayTimeUnit': 'ms'}
