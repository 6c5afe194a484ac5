"""Fleet telemetry aggregation: the controller-side time-series plane.

Until PR 11 every metric in the system was a point-in-time scrape:
`serve status --metrics` showed what a replica said *right now*, the
autoscalers consumed the single latest load probe, and nothing kept
history — so "is TTFT p99 degrading", "is the prefill pool's QPS
trending up", and any SLO question were unanswerable without an
external Prometheus.  This module gives the serve controller its own
small one:

- :class:`TimeSeriesStore` — bounded ring buffers of (ts, value)
  samples per series, keyed by (metric name, full label set).  Both
  retention (seconds) and per-series sample count are capped, so a
  controller supervising a large fleet for months holds a constant
  amount of telemetry.
- :class:`FleetAggregator` — scrapes `GET /metrics` from every READY
  replica and `GET /lb/metrics` from the load balancer on the
  controller's reconcile cadence (interval-gated by
  ``SKYTPU_SERVE_SCRAPE_INTERVAL``), ingests every ``skytpu_*`` series
  into the store with ``replica_id``/``role`` target labels attached
  (so same-named series from different replicas never collapse), and
  derives:

  * **windowed autoscaler signals** (`role_signals`) — smoothed QPS
    and per-replica load over a trailing window, replacing the
    instantaneous signals the role autoscalers used to consume;
  * **per-replica MFU/roofline gauges** (``skytpu_mfu_estimate``) —
    decode tokens/s x the replica's model FLOPs/token over the chip
    roofline (``SKYTPU_CHIP_PEAK_FLOPS``);
  * **windowed latency quantiles** (TTFT/ITL p99 from histogram bucket
    deltas) — what observability/slo.py evaluates burn rates against
    and `sky serve top` displays;
  * **slowest recent traces** — span segments scraped from the
    replicas' `GET /spans?since=`, kept as a bounded worst-N list.

All scraping is best-effort with short timeouts: a wedged replica
degrades the telemetry, never the reconcile loop.
"""
from __future__ import annotations

import collections
import os
import threading
import time
from typing import Any, Deque, Dict, List, Optional, Tuple

import requests

from skypilot_tpu import sky_logging
from skypilot_tpu.observability import metrics as metrics_lib
from skypilot_tpu.serve import http_protocol
from skypilot_tpu.serve import roles as roles_lib

logger = sky_logging.init_logger(__name__)

# Per-replica roofline gauge the aggregator computes on every scrape:
# the fleet-level counterpart of bench.py's MFU math (ROADMAP item 1's
# ladder reports against this same series).
_M_MFU = metrics_lib.gauge(
    'skytpu_mfu_estimate',
    'Estimated model FLOPs utilization per replica: decode tokens/s x '
    'model FLOPs/token over the chip roofline '
    '(SKYTPU_CHIP_PEAK_FLOPS x num_hosts).',
    ('service', 'replica_id', 'role'))
_M_SCRAPES = metrics_lib.counter(
    'skytpu_fleet_scrapes_total',
    'Fleet telemetry scrape attempts by the controller aggregator, '
    'by outcome (ok / error).', ('outcome',))
_M_SERIES = metrics_lib.gauge(
    'skytpu_fleet_series',
    'Distinct series held in the controller aggregator store.')

# Series ingested from scrapes (everything the fleet exposes).
_INGEST_PREFIX = 'skytpu_'

# Decode-path peak FLOP/s per chip for the MFU estimate; default = TPU
# v5e bf16 (the controller cannot see a replica's device; ROADMAP
# Speed 10 replaces the default).  Serving MFU uses 2*params
# FLOPs/token (forward only).
_DEFAULT_PEAK_FLOPS = 197e12


def scrape_interval() -> float:
    return float(os.environ.get('SKYTPU_SERVE_SCRAPE_INTERVAL', '10'))


def retention_s() -> float:
    return float(os.environ.get('SKYTPU_SERVE_METRICS_RETENTION_S',
                                '600'))


def max_samples() -> int:
    return int(os.environ.get('SKYTPU_SERVE_METRICS_MAX_SAMPLES',
                              '512'))


def peak_flops() -> float:
    try:
        return float(os.environ.get('SKYTPU_CHIP_PEAK_FLOPS',
                                    _DEFAULT_PEAK_FLOPS))
    except ValueError:
        return _DEFAULT_PEAK_FLOPS


def _slow_trace_count() -> int:
    return int(os.environ.get('SKYTPU_SERVE_SLOW_TRACES', '16'))


_SeriesKey = Tuple[str, Tuple[Tuple[str, str], ...]]


class TimeSeriesStore:
    """Bounded (ts, value) ring buffers keyed by (name, labels)."""

    def __init__(self, retention: Optional[float] = None,
                 samples: Optional[int] = None) -> None:
        self._retention = retention
        self._max_samples = samples
        self._series: Dict[_SeriesKey,
                           Deque[Tuple[float, float]]] = {}
        self._lock = threading.Lock()

    def _retention_s(self) -> float:
        return self._retention if self._retention is not None \
            else retention_s()

    def add(self, name: str, labels: Dict[str, Any], ts: float,
            value: float) -> None:
        key = (name, tuple(sorted((str(k), str(v))
                                  for k, v in labels.items())))
        cutoff = ts - self._retention_s()
        with self._lock:
            buf = self._series.get(key)
            if buf is None:
                buf = collections.deque(
                    maxlen=self._max_samples or max_samples())
                self._series[key] = buf
            buf.append((ts, float(value)))
            while buf and buf[0][0] < cutoff:
                buf.popleft()

    def prune(self, now: float) -> None:
        """Drop samples past retention and series that ran dry (a
        retired replica's series must not linger forever)."""
        cutoff = now - self._retention_s()
        with self._lock:
            for key in list(self._series):
                buf = self._series[key]
                while buf and buf[0][0] < cutoff:
                    buf.popleft()
                if not buf:
                    del self._series[key]
            _M_SERIES.set(len(self._series))

    def series(self, name: str, **label_filter: Any
               ) -> List[Tuple[Dict[str, str],
                               List[Tuple[float, float]]]]:
        """Matching series as (labels, samples oldest-first); a filter
        key must equal the series' value to match."""
        want = {str(k): str(v) for k, v in label_filter.items()}
        out = []
        with self._lock:
            for (sname, labels), buf in self._series.items():
                if sname != name:
                    continue
                ldict = dict(labels)
                if any(ldict.get(k) != v for k, v in want.items()):
                    continue
                out.append((ldict, list(buf)))
        return out

    def names(self) -> List[str]:
        with self._lock:
            return sorted({name for name, _ in self._series})

    def latest(self, name: str, **label_filter: Any
               ) -> List[Tuple[Dict[str, str], float]]:
        return [(labels, samples[-1][1])
                for labels, samples in self.series(name, **label_filter)
                if samples]

    # -------------------------------------------------- derived views

    @staticmethod
    def _window(samples: List[Tuple[float, float]], window_s: float,
                now: float) -> List[Tuple[float, float]]:
        cutoff = now - window_s
        return [(t, v) for t, v in samples if t >= cutoff]

    def counter_rate(self, name: str, window_s: float, now: float,
                     **label_filter: Any) -> Optional[float]:
        """Summed per-second rate across matching counter series over
        the trailing window.  Counter resets (value drops — a replica
        restart) contribute the post-reset value, Prometheus-style.
        None when no series has two samples in the window."""
        total = 0.0
        seen = False
        for _, samples in self.series(name, **label_filter):
            pts = self._window(samples, window_s, now)
            if len(pts) < 2:
                continue
            increase = 0.0
            for (_, prev), (_, cur) in zip(pts, pts[1:]):
                increase += (cur - prev) if cur >= prev else cur
            dt = pts[-1][0] - pts[0][0]
            if dt > 0:
                total += increase / dt
                seen = True
        return total if seen else None

    def gauge_mean(self, name: str, window_s: float, now: float,
                   **label_filter: Any) -> Optional[float]:
        """Mean of every sample across matching series in the window."""
        values = [v for _, samples in self.series(name, **label_filter)
                  for _, v in self._window(samples, window_s, now)]
        if not values:
            return None
        return sum(values) / len(values)

    def per_series_mean(self, name: str, window_s: float, now: float,
                        **label_filter: Any
                        ) -> Dict[Tuple[Tuple[str, str], ...], float]:
        """Windowed mean per matching series (keyed by its labels)."""
        out = {}
        for labels, samples in self.series(name, **label_filter):
            pts = self._window(samples, window_s, now)
            if pts:
                out[tuple(sorted(labels.items()))] = (
                    sum(v for _, v in pts) / len(pts))
        return out

    def bucket_deltas(self, name: str, window_s: float, now: float,
                      **label_filter: Any) -> Dict[float, float]:
        """Cumulative-count increase per histogram bucket bound over
        the window, summed across matching `<name>_bucket` series —
        i.e. the distribution of observations that happened INSIDE the
        window (reset-tolerant like counter_rate)."""
        deltas: Dict[float, float] = {}
        for labels, samples in self.series(f'{name}_bucket',
                                           **label_filter):
            le = labels.get('le')
            if le is None:
                continue
            bound = float('inf') if le == '+Inf' else float(le)
            pts = self._window(samples, window_s, now)
            if len(pts) < 2:
                continue
            increase = 0.0
            for (_, prev), (_, cur) in zip(pts, pts[1:]):
                increase += (cur - prev) if cur >= prev else cur
            deltas[bound] = deltas.get(bound, 0.0) + increase
        return deltas

    def quantile(self, name: str, q: float, window_s: float,
                 now: float, **label_filter: Any) -> Optional[float]:
        """Windowed histogram quantile (metrics.histogram_quantile
        semantics, incl. in-bucket interpolation) from bucket deltas."""
        deltas = self.bucket_deltas(name, window_s, now, **label_filter)
        if not deltas:
            return None
        parsed = {f'{name}_bucket': {
            (('le', '+Inf' if bound == float('inf')
              else repr(bound)),): count
            for bound, count in deltas.items()}}
        return metrics_lib.histogram_quantile(parsed, name, q)

    def binned(self, name: str, window_s: float, bins: int, now: float,
               mode: str = 'mean', **label_filter: Any
               ) -> List[Optional[float]]:
        """The window chopped into `bins` equal slots, oldest first —
        the `sky serve top` sparkline input.  mode 'mean' averages
        gauge samples per bin (summing across series); mode 'rate'
        spreads counter increases across the bins they span.  Empty
        bins are None."""
        if bins < 1:
            return []
        width = window_s / bins
        t0 = now - window_s
        if mode == 'rate':
            # Spread each sample pair's counter increase evenly across
            # the bins it spans, then divide by bin width -> per-second
            # rate per bin.
            totals = [0.0] * bins
            seen = [False] * bins
            for _, samples in self.series(name, **label_filter):
                pts = self._window(samples, window_s, now)
                for (pt, pv), (ct, cv) in zip(pts, pts[1:]):
                    inc = (cv - pv) if cv >= pv else cv
                    lo = max(0, min(bins - 1, int((pt - t0) / width)))
                    hi = max(0, min(bins - 1, int((ct - t0) / width)))
                    for b in range(lo, hi + 1):
                        totals[b] += inc / (hi - lo + 1)
                        seen[b] = True
            return [totals[i] / width if seen[i] else None
                    for i in range(bins)]
        sums: List[List[float]] = [[] for _ in range(bins)]
        # Gauge bins: sum simultaneous series (fleet tokens/s is the
        # sum over replicas), then average within the bin.
        per_bin_series: List[Dict[Tuple, List[float]]] = [
            collections.defaultdict(list) for _ in range(bins)]
        for labels, samples in self.series(name, **label_filter):
            key = tuple(sorted(labels.items()))
            for t, v in self._window(samples, window_s, now):
                b = max(0, min(bins - 1, int((t - t0) / width)))
                per_bin_series[b][key].append(v)
        for b in range(bins):
            if per_bin_series[b]:
                sums[b].append(sum(
                    sum(vs) / len(vs)
                    for vs in per_bin_series[b].values()))
        return [s[0] if s else None for s in sums]


class FleetAggregator:
    """Scrape the fleet into a TimeSeriesStore; derive fleet signals."""

    def __init__(self, service_name: str,
                 store: Optional[TimeSeriesStore] = None,
                 timeout: float = 3.0) -> None:
        self.service_name = service_name
        self.store = store or TimeSeriesStore()
        self.timeout = timeout
        self._last_scrape = 0.0
        self._span_since: Dict[str, float] = {}
        self._slow_traces: List[Dict[str, Any]] = []
        self._lock = threading.Lock()

    # ---------------------------------------------------------- scrape

    def maybe_scrape(self, targets: List[Dict[str, Any]],
                     now: Optional[float] = None) -> bool:
        """Interval-gated scrape (the reconcile loop calls this every
        pass; actual scraping honors SKYTPU_SERVE_SCRAPE_INTERVAL)."""
        now = time.time() if now is None else now
        if now - self._last_scrape < scrape_interval():
            return False
        self.scrape_fleet(targets, now)
        return True

    def scrape_fleet(self, targets: List[Dict[str, Any]],
                     now: Optional[float] = None) -> None:
        """One scrape pass over `targets`: dicts with `url`, `kind`
        ('replica' | 'lb'), and for replicas `replica_id`, `role`,
        `num_hosts`."""
        now = time.time() if now is None else now
        self._last_scrape = now
        for target in targets:
            try:
                self._scrape_one(target, now)
                _M_SCRAPES.labels(outcome='ok').inc()
            except (requests.RequestException, ValueError,
                    KeyError, TypeError) as e:
                _M_SCRAPES.labels(outcome='error').inc()
                logger.debug(f'fleet scrape failed for '
                             f'{target.get("url")}: {e}')
        self.store.prune(now)

    def _scrape_one(self, target: Dict[str, Any], now: float) -> None:
        url = target['url'].rstrip('/')
        kind = target.get('kind', 'replica')
        path = (http_protocol.LB_METRICS if kind == 'lb'
                else http_protocol.METRICS)
        resp = requests.get(url + path, timeout=self.timeout)
        resp.raise_for_status()
        parsed = metrics_lib.parse_exposition(resp.text)
        if kind == 'lb':
            extra = {'process': 'lb'}
        else:
            role = self._live_role(target, url)
            extra = {'replica_id': str(target.get('replica_id', '')),
                     'role': role}
        for name, by_labels in parsed.items():
            if not name.startswith(_INGEST_PREFIX):
                continue
            for labels, value in by_labels.items():
                merged = dict(labels)
                merged.update(extra)
                self.store.add(name, merged, now, value)
        if kind == 'replica':
            self._update_mfu(target, parsed, role)
            self._scrape_spans(target, url)

    def _live_role(self, target: Dict[str, Any], url: str) -> str:
        """The replica's CURRENT role, from its health payload.

        Registration-time target labels pin the role a replica was
        LAUNCHED with; after a live role morph (serve/role_morph.py)
        the replica answers with its new role while the controller's
        target dict still says the old one — and every windowed
        per-role signal (the rebalancer's inputs) would keep flowing
        into the stale series.  Falls back to the target label when
        the health probe fails or answers something unparseable."""
        try:
            resp = requests.get(url + '/', timeout=self.timeout)
            live = roles_lib.normalize((resp.json() or {}).get('role'))
            target['role'] = live   # keep span/top labels in step
            return live
        except (requests.RequestException, ValueError, KeyError,
                TypeError, AttributeError):
            return roles_lib.role_of(target)

    def _update_mfu(self, target: Dict[str, Any],
                    parsed: Dict[str, Any], role: str) -> None:
        """skytpu_mfu_estimate{replica_id,role}: decode tokens/s x the
        replica's advertised model FLOPs/token over the slice's
        roofline.  0 when the replica does not advertise FLOPs (user
        containers) — absent data must not read as a good number."""
        def total(name: str) -> float:
            return sum((parsed.get(name) or {}).values())

        tokens_per_s = total('skytpu_engine_decode_tokens_per_s')
        flops_per_token = total('skytpu_engine_model_flops_per_token')
        hosts = max(1, int(target.get('num_hosts') or 1))
        mfu = (tokens_per_s * flops_per_token /
               (peak_flops() * hosts)) if flops_per_token else 0.0
        rid = str(target.get('replica_id', ''))
        _M_MFU.labels(service=self.service_name, replica_id=rid,
                      role=role).set(mfu)
        self.store.add('skytpu_mfu_estimate',
                       {'replica_id': rid, 'role': role},
                       time.time(), mfu)

    def _scrape_spans(self, target: Dict[str, Any], url: str) -> None:
        """Pull new span segments since the last scrape and fold them
        into the bounded slowest-traces list (`sky serve top`'s
        SLOWEST TRACES table)."""
        since = self._span_since.get(url, 0.0)
        resp = requests.get(url + http_protocol.SPANS,
                            params={'since': since or None},
                            timeout=self.timeout)
        if resp.status_code != 200:
            return
        segments = (resp.json() or {}).get('segments') or []
        newest = since
        for seg in segments:
            newest = max(newest, float(seg.get('start') or 0.0))
            seg.setdefault('replica_id', target.get('replica_id'))
            seg.setdefault('role', target.get('role'))
        self._span_since[url] = newest
        keep = _slow_trace_count()
        cutoff = time.time() - self.store._retention_s()  # pylint: disable=protected-access

        def key(seg: Dict[str, Any]):
            # The since= cursor is inclusive (the newest segment comes
            # back on the next scrape): dedupe on identity, keeping
            # the LATER copy (a streaming LB segment's duration is
            # refreshed at relay end).
            return (seg.get('request_id'), seg.get('name'),
                    seg.get('replica_id'), seg.get('attempt'),
                    round(float(seg.get('start') or 0.0), 6))

        with self._lock:
            merged = {key(s): s for s in self._slow_traces + segments
                      if (s.get('start') or 0.0) >= cutoff and
                      s.get('duration_ms') is not None}
            ranked = sorted(merged.values(),
                            key=lambda s: -(s.get('duration_ms') or
                                            0.0))
            self._slow_traces = ranked[:keep]

    # --------------------------------------------------------- signals

    def role_signals(self, role: str, window_s: float = 60.0,
                     now: Optional[float] = None) -> Dict[str, Any]:
        """Smoothed autoscaler inputs for one role pool: windowed QPS
        (LB route counter rate) and per-replica windowed load
        (mean (busy+queued)/slots).  Values are None when the store
        has no data yet — callers fall back to the instantaneous
        signals, so a cold controller behaves exactly as before."""
        now = time.time() if now is None else now
        qps = self.store.counter_rate('skytpu_lb_route_total',
                                      window_s, now, role=role)
        busy = self.store.per_series_mean('skytpu_engine_busy_slots',
                                          window_s, now, role=role)
        queued = self.store.per_series_mean('skytpu_engine_queue_depth',
                                            window_s, now, role=role)
        slots = self.store.per_series_mean('skytpu_engine_slots',
                                           window_s, now, role=role)
        loads: List[float] = []
        for key, mean_busy in busy.items():
            cap = slots.get(key)
            if cap:
                q = queued.get(key, 0.0)
                loads.append(min(1.0, (mean_busy + q) / cap))
        return {'qps': qps, 'loads': loads or None}

    def latency_quantiles(self, window_s: float = 60.0,
                          now: Optional[float] = None,
                          **label_filter: Any) -> Dict[str, Any]:
        now = time.time() if now is None else now

        def ms(v: Optional[float]) -> Optional[float]:
            return None if v is None else round(v * 1e3, 3)

        return {
            'ttft_p50_ms': ms(self.store.quantile(
                'skytpu_engine_ttft_seconds', 0.5, window_s, now,
                **label_filter)),
            'ttft_p99_ms': ms(self.store.quantile(
                'skytpu_engine_ttft_seconds', 0.99, window_s, now,
                **label_filter)),
            'itl_p50_ms': ms(self.store.quantile(
                'skytpu_engine_itl_seconds', 0.5, window_s, now,
                **label_filter)),
            'itl_p99_ms': ms(self.store.quantile(
                'skytpu_engine_itl_seconds', 0.99, window_s, now,
                **label_filter)),
        }

    def slow_traces(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._slow_traces)

    def fleet_snapshot(self, roles: List[str],
                       window_s: float = 120.0, bins: int = 24,
                       now: Optional[float] = None) -> Dict[str, Any]:
        """JSON-ready snapshot for `/controller/telemetry` — what
        `sky serve top` renders: per-role sparkline series + windowed
        quantiles, per-replica MFU, and the slowest recent traces."""
        now = time.time() if now is None else now
        out_roles: Dict[str, Any] = {}
        for role in roles:
            sig = self.role_signals(role, min(60.0, window_s), now)
            out_roles[role] = {
                'qps': sig['qps'],
                'qps_spark': self.store.binned(
                    'skytpu_lb_route_total', window_s, bins, now,
                    mode='rate', role=role),
                'tokens_per_s_spark': self.store.binned(
                    'skytpu_engine_decode_tokens_per_s', window_s,
                    bins, now, role=role),
                'load_spark': self.store.binned(
                    'skytpu_engine_busy_slots', window_s, bins, now,
                    role=role),
                **self.latency_quantiles(min(60.0, window_s), now,
                                         role=role),
            }
        # No decimal rounding: an emulated tiny model's real MFU is
        # ~1e-8 and must not floor to 0.
        mfu = {labels.get('replica_id'): float(f'{value:.3g}')
               for labels, value in self.store.latest(
                   'skytpu_mfu_estimate')}
        # Per-replica tick-phase breakdown (seconds of phase time per
        # wall second over the window; falls back to the cumulative
        # total until two scrapes land) and steady-state recompile
        # counts — `sky serve top`'s TICK-BREAKDOWN / RECOMPILES
        # columns.
        tick_breakdown: Dict[str, Dict[str, float]] = {}
        for labels, value in self.store.latest(
                'skytpu_engine_tick_phase_seconds_sum'):
            rid = labels.get('replica_id')
            phase = labels.get('phase')
            if rid is None or phase is None:
                continue
            rate = self.store.counter_rate(
                'skytpu_engine_tick_phase_seconds_sum',
                min(60.0, window_s), now, phase=phase, replica_id=rid)
            tick_breakdown.setdefault(rid, {})[phase] = (
                rate if rate is not None else value)
        recompiles: Dict[str, float] = {}
        for labels, value in self.store.latest(
                'skytpu_engine_recompiles_total'):
            rid = labels.get('replica_id')
            if rid is None:
                continue
            recompiles[rid] = recompiles.get(rid, 0.0) + value
        # Per-replica WARN+ERROR log rate out of the scraped
        # skytpu_log_records_total counters — `sky serve top`'s ERR/s
        # column.  Deferred import: logs is import-light but keeping
        # the aggregator importable without the serve package matters
        # for analysis tooling.
        from skypilot_tpu.observability import logs as logs_lib  # pylint: disable=import-outside-toplevel
        log_error_rates = logs_lib.error_rates(
            self.store, min(60.0, window_s), now)
        # Batch-infer plane: the replica-side bulk-inference signals
        # (rows served under QoS class batch, live weight-swap epochs)
        # — only present while a batch driver is actually running, so
        # `sky serve top` can hide the BATCH line otherwise.
        batch: Optional[Dict[str, Any]] = None
        batch_rows = self.store.latest('skytpu_batch_rows_served_total')
        if batch_rows:
            rate = self.store.counter_rate(
                'skytpu_batch_rows_served_total',
                min(60.0, window_s), now)
            epochs = {labels.get('replica_id'): int(value)
                      for labels, value in self.store.latest(
                          'skytpu_batch_weight_epoch')}
            swaps = sum(value for _, value in self.store.latest(
                'skytpu_batch_weight_swaps_total'))
            batch = {
                'rows_total': sum(v for _, v in batch_rows),
                'rows_per_s': rate,
                'weight_epochs': epochs,
                'weight_swaps_total': swaps,
            }
        return {'window_s': window_s, 'roles': out_roles, 'mfu': mfu,
                'tick_breakdown': tick_breakdown,
                'recompiles': recompiles,
                'log_error_rates': log_error_rates,
                'batch': batch,
                'slow_traces': self.slow_traces(),
                'series_names': self.store.names()}
