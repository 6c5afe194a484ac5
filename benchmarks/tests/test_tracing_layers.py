"""The four readers of the engine's own spans and counters
(`tick_host_ms`, `device_fed_share`, `prefill_wall_p50_ms`,
`prefill_device_share`): each on a synthetic `run` with a known answer,
each one's `None` where its input is missing (an engine without
`tick_loop` or the span marks, an untraced run, a trace of unnamed
programs), and all four through one rehearsal of `run.py` on `tiny`.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

Nothing here is a measurement.
"""
import json
import os
import sys
import types

import pytest

os.environ.setdefault('JAX_PLATFORMS', 'cpu')
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmarks import run as run_lib  # noqa: E402
from benchmarks.layers import device_fed_share  # noqa: E402
from benchmarks.layers import prefill_device_share  # noqa: E402
from benchmarks.layers import prefill_wall_p50_ms  # noqa: E402
from benchmarks.layers import tick_host_ms  # noqa: E402

_NEW = ('tick_host_ms', 'device_fed_share', 'prefill_wall_p50_ms',
        'prefill_device_share')


def _loop(iterations, loop_s, wait_s, starved_ticks=0, starved_s=0.0):
    return {'iterations': iterations, 'loop_s': loop_s,
            'phase_s': {'decode-step': 0.001 * iterations,
                        'device-wait': wait_s},
            'starved_ticks': starved_ticks, 'starved_s': starved_s}


def _run(stats0=None, stats1=None, requests=(), trace=None):
    return types.SimpleNamespace(
        stats0=stats0 or {}, stats1=stats1 or {}, requests=list(requests),
        trace=trace)


def _request(**span):
    return types.SimpleNamespace(handle=types.SimpleNamespace(
        span=types.SimpleNamespace(**span)))


def test_tick_host_ms_is_the_loop_less_the_wait_per_iteration():
    # 500 iterations of 100 ms in the window, 97 ms of each waiting.
    run = _run({'tick_loop': _loop(100, 10.0, 9.7)},
               {'tick_loop': _loop(600, 60.0, 58.2)})
    assert tick_host_ms.compute(run) == pytest.approx(3.0)


def test_device_fed_share_over_the_whole_window():
    fed = _run({'tick_loop': _loop(100, 10.0, 9.7)},
               {'tick_loop': _loop(600, 60.0, 58.2)})
    assert device_fed_share.compute(fed) == 100.0
    # One stall: the device dry for an estimated 0.5 s of 50 s.
    stalled = _run({'tick_loop': _loop(100, 10.0, 9.7, 2, 0.25)},
                   {'tick_loop': _loop(600, 60.0, 58.2, 3, 0.75)})
    assert device_fed_share.compute(stalled) == pytest.approx(99.0)
    assert device_fed_share.compute(stalled) <= 100.0


@pytest.mark.parametrize('reader', [tick_host_ms, device_fed_share])
def test_tick_loop_readers_return_none_without_their_input(reader):
    loop = _loop(100, 10.0, 9.7)
    # An engine whose stats() has no tick_loop (the parent's).
    assert reader.compute(_run({'ticks': 1}, {'ticks': 9})) is None
    assert reader.compute(_run({'tick_loop': loop}, {'ticks': 9})) is None
    # A window in which the loop did no work.
    assert reader.compute(_run({'tick_loop': loop},
                               {'tick_loop': loop})) is None


def test_prefill_wall_p50_ms_is_the_median_over_admitted_requests():
    requests = [_request(prefill_wall_s=w)
                for w in (0.400, 0.100, 0.220, 0.300, 0.215)]
    requests.append(_request(prefill_wall_s=None))    # never admitted
    requests.append(types.SimpleNamespace(handle=None))   # refused
    assert prefill_wall_p50_ms.compute(_run(requests=requests)) == \
        pytest.approx(220.0)


def test_prefill_wall_p50_ms_none_without_the_marks():
    assert prefill_wall_p50_ms.compute(_run()) is None
    # Spans of an engine that does not set the mark (the parent's).
    old = [_request(queue_wait_s=0.01), _request(queue_wait_s=0.02)]
    assert prefill_wall_p50_ms.compute(_run(requests=old)) is None


def _trace(own, devices=1, window_s=4.0):
    return {'devices': devices, 'window_s': window_s, 'busy_s': window_s,
            'by_name': dict(own), 'own_by_name': dict(own)}


def test_prefill_device_share_sums_the_named_prefill_programs():
    own = {
        'paged_engine_step/%paged_decode_attention.5 custom-call '
        'tpu_custom_call': 1.96,
        'paged_engine_step/%copy.189 copy': 0.167,
        'prefill/%flash_fwd.3 custom-call tpu_custom_call': 0.020,
        'prefill/%fusion.4 fusion': 0.040,
        'prefill_chunk/%fusion.9 fusion': 0.090,
        'paged_seed_private/%copy.1 copy': 0.010,
        'insert_prefill_pages/%scatter.2 scatter': 0.015,
        'paged_admit_slot/%dynamic-update-slice.1 fusion': 0.005,
        'admit_slot_state/%fusion.1 fusion': 0.001,       # not prefill
    }
    run = _run(trace=_trace(own))
    assert prefill_device_share.compute(run) == pytest.approx(
        100.0 * 0.180 / 4.0)
    # Two chips: the same own time over twice the device seconds.
    assert prefill_device_share.compute(
        _run(trace=_trace(own, devices=2))) == pytest.approx(2.25)
    # A traced span in which no prefill ran reads 0, not nothing.
    quiet = {k: v for k, v in own.items()
             if k.startswith(('paged_engine_step/', 'admit_slot_state/'))}
    assert prefill_device_share.compute(_run(trace=_trace(quiet))) == 0.0


def test_prefill_device_share_none_without_its_input():
    assert prefill_device_share.compute(_run()) is None   # untraced
    # The parent's trace: the tick is `unknown/`, prefill `_lambda_/`;
    # `insert_prefill_pages` was named already and must not read as
    # the whole of prefill.
    parent = {'unknown/%closed_call.8 custom-call tpu_custom_call': 1.96,
              'lambda_/%fusion.4 fusion': 0.040,
              'insert_prefill_pages/%scatter.2 scatter': 0.015}
    assert prefill_device_share.compute(
        _run(trace=_trace(parent))) is None
    assert prefill_device_share.compute(
        _run(trace=_trace({}, window_s=0.0))) is None


def test_benchmark_json_lists_the_four_at_the_end():
    with open(os.path.join(_ROOT, 'BENCHMARK.json'), encoding='utf-8') as f:
        bench = json.load(f)
    tail = bench['per_layer'][-len(_NEW):]
    assert tuple(m['name'] for m in tail) == _NEW
    layers = {m['layer'] for m in bench['per_layer'][:-len(_NEW)]}
    for m in tail:
        assert m['layer'] in layers        # no new layer, letter for letter
        assert sorted(m) in (
            ['better', 'layer', 'moves', 'name', 'source', 'unit'],
            ['better', 'layer', 'moves', 'name', 'source', 'unit',
             'workloads'])


def test_rehearsal_reports_the_readers_on_the_tiny_engine(capsys):
    """`run.py --dry-run --trace 1`, whose `dryrun.json` lists the four
    as `BENCHMARK.json` does: the three that read the program report a
    number from the real engine's `stats()` and spans; the one that
    reads the device trace has no device plane on a CPU and is left
    out."""
    with open(os.path.join(_ROOT, 'benchmarks', 'tests', 'dryrun.json'),
              encoding='utf-8') as f:
        listed = [m['name'] for m in json.load(f)['per_layer']]
    assert tuple(listed[-len(_NEW):]) == _NEW
    rc = run_lib.main(['--dry-run', '--seed', str(2**31 + 777),
                       '--seconds', '2', '--workload', 'tiny.dryrun-shared',
                       '--trace', '1'])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line['correct'] is True
    metrics = line['metrics']
    assert metrics['tick_host_ms']['value'] > 0
    assert 0.0 <= metrics['device_fed_share']['value'] <= 100.0
    assert metrics['prefill_wall_p50_ms']['value'] > 0
    assert 'prefill_device_share' not in metrics
    assert metrics['prefill_wall_p50_ms']['unit'] == 'ms'
