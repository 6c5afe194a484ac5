"""The benchmark's command: one run of one cell.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

looks the cell up in `BENCHMARK.json`, loads `configs/<config>.json`
and `workloads/<traffic>.json`, imports `families/<family>.py` (the
architecture: program config, weight shapes, plain reference, work
counts) and `layouts/<layout>.py` and, for a traced run,
`layers/<metric>.py` for each per-layer metric of the cell.  Nothing
here switches on the name of a cell, a configuration, a family, a
layout or a per-layer metric: each is a file found by its name.

One run, in order: find the chips or fail; persistent compile cache;
weights on the device from `--seed` (the layout's `build`); the
program's `ContinuousBatchingEngine` with the configuration's geometry;
warm-up of exactly the cell's shapes through the engine's own `submit`
(and the prefix cache filled where the traffic shares documents);
`setup_s` ends here and compilations are counted from here on; the
window of `--seconds`; the engine stopped and freed; outputs compared
with the plain reference; one JSON line.

`--dry-run` is the rehearsal: the same path on whatever backend JAX
has, with cells read from `benchmarks/tests/dryrun.json`.  It prints
`"platform": "cpu"` and is never a measurement.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()   # set-up counts from process start (imports too)

# pylint: disable=wrong-import-position
import argparse
import gc
import importlib
import json
import os
import shutil
import sys
import tempfile
import threading
import types
from typing import Any, Dict, List, Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

# Requests the reference is run over once the window has closed: the
# longest finished one and more drawn from the seed, until there are
# this many and they hold the cell's `sampled_tokens_min` served tokens.
_SAMPLE_REQUESTS = 6
# The reference's sequences are padded to a multiple of this, and the
# rows it unembeds to `_ROWS`, so that few shapes compile; an answer
# longer than that is read in blocks of `_ROWS`.
_SEQ_BUCKET = 512
_ROWS = 512
# Set-up's requests in flight at once.
_WARM_CLIENTS = 4
# Seconds of a traced run's window that the profiler records.
_TRACE_SECONDS = 4.0


def _log(msg: str) -> None:
    print(f'[bench +{time.perf_counter() - _T0:7.2f}s] {msg}',
          file=sys.stderr, flush=True)


def _load_json(path: str) -> Dict[str, Any]:
    with open(path, encoding='utf-8') as f:
        return json.load(f)


def _find(rows: List[Dict[str, Any]], name: str, what: str):
    for row in rows:
        if row['name'] == name:
            return row
    raise SystemExit(f'no {what} named {name!r}; have '
                     f'{[r["name"] for r in rows]}')


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    return 'workloads' not in metric or cell in metric['workloads']


def find_devices(chips: int, dry_run: bool):
    """The chips the cell asks for, or no run: a measurement never
    falls back to another backend.  Also the seconds the accelerator's
    runtime took to come up (the first `jax.devices()`): 7 to 11 s on
    the same machine and code (PR 25), nobody's work to shorten, and so
    left out of `setup_s`."""
    import jax
    from benchmarks import cost
    t_a = time.perf_counter()
    devices = jax.devices()
    attach_s = time.perf_counter() - t_a
    platform = devices[0].platform
    if dry_run:
        return devices[:chips], None, attach_s
    if platform != 'tpu' or len(devices) < chips:
        raise SystemExit(
            f'this cell needs {chips} TPU chip(s); JAX found '
            f'{len(devices)} device(s) of platform {platform!r} '
            f'({devices[0].device_kind!r}).  Only --dry-run runs here.')
    return devices[:chips], cost.peaks(devices[0].device_kind), attach_s


class _CompileCounter:
    """Counts programs that reach the compiler (a hit in the persistent
    cache included: tracing and lowering already cost the window)."""

    def __init__(self) -> None:
        self.count = 0
        import jax
        from jax._src import dispatch
        self._event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        del duration, kw
        if event == self._event:
            self.count += 1


def end_to_end(run) -> Dict[str, Any]:
    """Every end-to-end metric, over all requests and all the window."""
    from benchmarks import traffic
    sec = run.seconds
    ttft_ms = []
    for r in run.requests:
        if r.token_s:
            ttft_ms.append((r.token_s[0] - r.start_s) * 1e3)
        else:
            # Failed, refused or never answered: as bad as the wait.
            ttft_ms.append((run.closed_s - r.start_s) * 1e3)
    gaps_ms = [(b - a) * 1e3 for r in run.requests
               for a, b in zip(r.token_s, r.token_s[1:]) if b <= sec]
    arrived = sum(1 for r in run.requests for t in r.token_s if t <= sec)
    return {
        'ttft_p95_ms': (traffic.percentile(ttft_ms, 95), 'ms'),
        'ttft_p50_ms': (traffic.percentile(ttft_ms, 50), 'ms'),
        'itl_p95_ms': (traffic.percentile(gaps_ms, 95) if gaps_ms else None, 'ms'),
        'out_tok_per_s': (arrived / sec, 'tokens/s'),
        'setup_s': (run.setup_s, 's'),
    }


def _pad(tokens: List[int]) -> List[int]:
    n = -(-len(tokens) // _SEQ_BUCKET) * _SEQ_BUCKET
    return tokens + [0] * (n - len(tokens))


def _blocks(n: int, m: int, length: int):
    """Row n - 1 + j of a sequence of `length` gives served token j of
    m: yields (first, lo, j, k), blocks of `_ROWS` rows from `first` of
    which rows lo.. hold tokens j..j + k.  One block where the answer
    fits one, as many as it needs where it does not."""
    j = 0
    while j < m:
        first = min(n - 1 + j, length - _ROWS)
        lo = n - 1 + j - first
        k = min(m - j, _ROWS - lo)
        yield first, lo, j, k
        j += k


def compare(run, params, control: Optional[str],
            min_tokens: int) -> Dict[str, Any]:
    """How `correct` is decided: the served tokens of a seeded sample of
    finished requests, the longest among them, against the reference's
    logits at the same positions.  Returns the numbers compared."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    done = [r for r in run.requests
            if r.error is None and r.done_s is not None and
            len(r.token_s) == r.max_new]
    short = sum(1 for r in run.requests
                if r.error is None and r.handle is not None and
                r.handle.done.is_set() and not r.handle.cancelled and
                r.handle.error is None and
                len(r.handle.tokens) != r.max_new)
    numbers = {'short_outputs': short}
    if not done:
        # Nothing finished: nothing shown correct.
        numbers.update(sampled_tokens=0, logit_gap_max=1e9)
        if control:
            numbers['control_logit_gap_max'] = 1e9
        return numbers
    longest = max(done, key=lambda r: len(r.prompt) + r.max_new)
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([run.seed, 7]).permutation(len(rest))
    sample = [longest]
    for i in order:
        if len(sample) >= _SAMPLE_REQUESTS and sum(
                r.max_new for r in sample) >= min_tokens:
            break
        sample.append(rest[i])

    @jax.jit
    def gaps_of(ref_logits, picked, valid):
        best = jnp.max(ref_logits, axis=-1)
        got = jnp.take_along_axis(ref_logits, picked[:, None], axis=1)[:, 0]
        return jnp.where(valid, best - got, 0.0)

    worst, n_tokens, flips = 0.0, 0, 0
    worst_ctrl = None
    for r in sample:
        served = list(r.handle.tokens)
        n, m = len(r.prompt), len(served)
        seq = _pad(r.prompt + served[:-1])
        for first, lo, j, k in _blocks(n, m, len(seq)):
            ref = run.family.logits(run.model, params, seq, first, _ROWS)
            valid = np.zeros((_ROWS,), bool)
            picked = np.zeros((_ROWS,), np.int32)
            valid[lo:lo + k] = True
            picked[lo:lo + k] = served[j:j + k]
            gaps = np.asarray(gaps_of(ref, jnp.asarray(picked),
                                      jnp.asarray(valid)))
            worst = max(worst, float(gaps.max()))
            flips += int((gaps > 0).sum())
            if control:
                low = run.family.logits(run.model, params, seq, first,
                                        _ROWS, precision=control)
                gaps_c = np.asarray(gaps_of(
                    ref, jnp.argmax(low, axis=-1).astype(jnp.int32),
                    jnp.asarray(valid)))
                worst_ctrl = max(worst_ctrl or 0.0, float(gaps_c.max()))
                del low
            del ref
        n_tokens += m
    numbers.update(sampled_tokens=n_tokens, sampled_flips=flips,
                   logit_gap_max=worst)
    if control:
        numbers['control_logit_gap_max'] = worst_ctrl
    return numbers


def judge(numbers: Dict[str, Any], limits: Dict[str, Any],
          control: Optional[str]) -> Dict[str, Any]:
    """Each number compared beside its limit; `ok` per number."""
    checks = {}

    def add(name, value, limit, kind):
        good = (value <= limit) if kind == 'max' else (value >= limit)
        checks[name] = {'value': value, 'limit': limit, 'kind': kind,
                        'ok': bool(good)}

    gap = numbers['control_logit_gap_max'] if control else \
        numbers['logit_gap_max']
    add('logit_gap_max', gap, limits['logit_gap_max'], 'max')
    add('sampled_tokens', numbers['sampled_tokens'],
        limits['sampled_tokens_min'], 'min')
    add('short_outputs', numbers['short_outputs'], 0, 'max')
    add('window_compiles', numbers['window_compiles'], 0, 'max')
    return checks


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    ap.add_argument('--dry-run', action='store_true',
                    help='rehearse on any backend; never a measurement')
    ap.add_argument('--control', choices=('int8',),
                    help='judge the control (the reference in this lower '
                    'precision, in the program\'s place): must come out '
                    'not correct')
    ap.add_argument('--set', action='append', default=[],
                    metavar='KEY=JSON',
                    help='override a key of the traffic file (the rate '
                    'sweep that finds the knee); never used by a check')
    ap.add_argument('--keep-trace', metavar='DIR',
                    help='with --trace 1: copy the .xplane.pb to DIR, to '
                    'be read by hand')
    args = ap.parse_args(argv)

    bench = _load_json(os.path.join(
        _HERE, 'tests', 'dryrun.json') if args.dry_run else os.path.join(
            _ROOT, 'BENCHMARK.json'))
    cell = _find(bench['workloads'], args.workload, 'workload')
    model = _load_json(os.path.join(
        _ROOT, _find(bench['configs'], cell['config'], 'config')['file']))
    spec = _load_json(os.path.join(_HERE, 'workloads',
                                   f'{cell["traffic"]}.json'))
    for item in args.set:
        key, _, value = item.partition('=')
        spec[key] = json.loads(value)
    geometry = model['engine']
    chips = int(cell['chips'])

    import jax
    from skypilot_tpu import compile_cache
    from benchmarks import families, traffic

    devices, peak, attach_s = find_devices(chips, args.dry_run)
    cache_dir = None
    if not args.dry_run:
        cache_dir = compile_cache.enable()
        # Small programs too (a page scatter compiles in well under a
        # second): every run after a checkout's first finds them all.
        jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
        jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
    _log(f'devices {[d.device_kind for d in devices]} (runtime up in '
         f'{attach_s:.2f} s, not counted in setup_s) cache {cache_dir}')

    family = families.of(model)
    layout = importlib.import_module(f'benchmarks.layouts.{model["layout"]}')
    mesh, params = layout.build(model, devices, args.seed)
    jax.block_until_ready(params)
    _log('weights on device')

    from skypilot_tpu.serve import batching_engine
    cfg = family.program_config(model, geometry['max_len'])
    engine = batching_engine.ContinuousBatchingEngine(
        cfg, params, mesh=mesh, **geometry)
    mix = traffic.Mix(spec, args.seed, model['vocab_size'])
    try:
        warm = traffic.Driver(engine.submit)
        for phase in mix.warmup():
            # A few at a time: every slot prefilling at once holds a
            # private cache each, a peak the window need not have.
            warm.run_closed(iter(phase), _WARM_CLIENTS, 1100.0)
            bad = [r.error for r in phase if r.error]
            if bad or engine.stats()['failed'] or len(
                    [r for r in phase if r.done_s is not None]) < len(phase):
                raise SystemExit(f'set-up requests failed: {bad[:3]}')
        n_warm = len(warm.sent)
        compiles = _CompileCounter()
        stats0 = engine.stats()
        setup_s = time.perf_counter() - _T0 - attach_s
        _log(f'set-up done: {n_warm} warm-up requests, setup_s '
             f'{setup_s:.2f}')

        # ------------------------------------------------- the window
        driver = traffic.Driver(engine.submit)
        if spec['loop'] == 'open':
            schedule = mix.open_schedule(args.seconds)
            worker = threading.Thread(
                target=driver.run_open, args=(schedule, args.seconds))
        else:
            stream = (mix.request(i) for i in range(1 << 30))
            worker = threading.Thread(
                target=driver.run_closed,
                args=(stream, int(spec['clients']), args.seconds))
        trace_dir = trace_span = None
        worker.start()
        if args.trace:
            trace_dir = tempfile.mkdtemp(prefix='bench_trace_')
            length = min(_TRACE_SECONDS, args.seconds / 3)
            time.sleep(max(0.0, args.seconds * 0.4 - driver.now()))
            # The device planes are what the reduction reads; Python's
            # own tracer would add some 50,000 host events a second.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            t_a = driver.now()
            time.sleep(length)
            t_b = driver.now()
            jax.profiler.stop_trace()
            trace_span = (t_a, t_b)
        worker.join()
        stats1 = engine.stats()
        window_compiles = compiles.count
        if spec['judged'] == 'tails':
            # A first token still out is late, not lost: its wait counts.
            _log('window over; waiting for first tokens still out')
            driver.wait_first_tokens(timeout=60.0)
        closed_s = driver.now()
        # Failed: refused or ended in an error, or (open loop, where
        # every request of the window is waited for) never answered.  A
        # closed loop's requests still running or queued at the close
        # are cancelled below; those are not failures.
        failed = sum(1 for r in driver.sent
                     if r.error is not None or
                     (r.handle is not None and r.handle.error is not None)
                     or (spec['loop'] == 'open' and not r.token_s))
        for r in driver.sent:
            if r.handle is not None and not r.handle.done.is_set():
                r.handle.cancel()
        memory_peak = max(
            (d.memory_stats() or {}).get('peak_bytes_in_use', 0)
            for d in devices)
    finally:
        engine.stop()
    late = driver.lateness_s or [0.0]
    _log(f'window closed: {len(driver.sent)} requests offered, generator '
         f'late by mean {sum(late) / len(late) * 1e3:.2f} ms, worst '
         f'{max(late) * 1e3:.2f} ms; compilations inside the window: '
         f'{window_compiles}')

    requests = driver.sent
    del warm, driver    # they hold the engine's entry
    run = types.SimpleNamespace(
        cell=cell, model=model, family=family, spec=spec, geometry=geometry,
        seed=args.seed, seconds=args.seconds, chips=chips, peak=peak,
        requests=requests, setup_s=setup_s, closed_s=closed_s,
        stats0=stats0, stats1=stats1, trace=None, trace_span=trace_span,
        kv_dtype='int8' if geometry.get('quantize_kv') else
        model['torch_dtype'])

    # The program's state goes before the reference runs; the weights
    # stay: they are the benchmark's own data.
    del engine
    gc.collect()
    _log(f'engine freed; bytes in use '
         f'{(devices[0].memory_stats() or {}).get("bytes_in_use")}')
    limits = _load_json(os.path.join(_HERE, 'limits',
                                     f'{cell["name"]}.json'))
    numbers = compare(run, params, args.control,
                      limits['sampled_tokens_min'])
    numbers['window_compiles'] = window_compiles
    _log(f'compared: {numbers}')
    checks = judge(numbers, limits, args.control)
    correct = all(c['ok'] for c in checks.values())
    del params

    values = end_to_end(run)
    if spec['judged'] == 'tails':
        # Two runs that took different courses part at one request.
        _log('first-token ms by request: ' + ' '.join(
            f'{(r.token_s[0] - r.start_s) * 1e3:.1f}' if r.token_s else '-'
            for r in requests))
    _log('end to end: ' + ', '.join(
        f'{k} {v[0]:.2f}' for k, v in values.items() if v[0] is not None))
    metrics: Dict[str, Any] = {}
    device = {'platform': devices[0].platform,
              'kind': devices[0].device_kind, 'count': len(devices),
              'memory_peak_bytes': int(memory_peak)}
    breakdown = None
    if args.trace:
        from benchmarks import reduce as reduce_lib
        try:
            xplane = reduce_lib.find_xplane(trace_dir)
            if args.keep_trace:
                os.makedirs(args.keep_trace, exist_ok=True)
                shutil.copy(xplane, args.keep_trace)
            try:
                run.trace = reduce_lib.reduce_trace(xplane, chips)
            except RuntimeError:
                if not args.dry_run:
                    raise
                _log('dry run: the trace has no device plane')
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        if run.trace is not None:
            device['busy_s'] = run.trace['busy_s']
            device['window_s'] = run.trace['window_s']
            breakdown = run.trace['breakdown']
        for metric in bench['per_layer']:
            if not _reports(metric, cell['name']):
                continue
            reader = importlib.import_module(
                f'benchmarks.layers.{metric["name"]}')
            value = reader.compute(run)
            if value is not None:
                metrics[metric['name']] = {'value': float(value),
                                           'unit': metric['unit']}
    else:
        for metric in bench['end_to_end']:
            if not _reports(metric, cell['name']):
                continue
            value, unit = values[metric['name']]
            if value is not None:
                metrics[metric['name']] = {'value': float(value),
                                           'unit': unit}

    attempted = len(requests)
    result = {'correct': bool(correct), 'attempted': attempted,
              'failed': failed, 'metrics': metrics, 'device': device}
    if breakdown is not None:
        result['breakdown'] = breakdown
    result['workload'] = cell['name']
    result['seed'] = args.seed
    result['lateness_ms'] = {'mean': sum(late) / len(late) * 1e3,
                             'worst': max(late) * 1e3}
    result['checks'] = {k: {'value': v['value'], 'limit': v['limit']}
                        for k, v in checks.items()}
    for name, c in checks.items():
        word = '<=' if c['kind'] == 'max' else '>='
        print(f'check {name}: {c["value"]} {word} {c["limit"]} '
              f'{"ok" if c["ok"] else "FAILED"}', file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
