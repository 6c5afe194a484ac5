"""The readers `gc_pause_share` and `late_read_share` on synthetic runs:
the share of the window the collector held the process, and of the
loop's time spent reading ticks that had already finished; `None` on a
program without the counters and on an empty window; and their entries
in `BENCHMARK.json`, found by name.  Nothing here is a measurement.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import json
import os
import sys
import types

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmarks.layers import gc_pause_share, late_read_share  # noqa: E402


def _loop(iterations, loop_s, elapsed_s, gc_s, late_read_s):
    return {'iterations': iterations, 'loop_s': loop_s,
            'phase_s': {'device-wait': 0.8 * loop_s},
            'starved_ticks': 0, 'starved_s': 0.0,
            'late_reads': int(late_read_s * 100),
            'late_read_s': late_read_s, 'idle_s': 0.0, 'deferred_s': 0.0,
            'gc_pauses': int(gc_s * 100), 'gc_s': gc_s,
            'elapsed_s': elapsed_s}


def _run(before, after):
    return types.SimpleNamespace(stats0={'tick_loop': before},
                                 stats1={'tick_loop': after})


@pytest.mark.parametrize('before,after,gc_share,late_share', [
    # 0.25 s of collections in 50 s; 0.1 s of late reads in 40 s of loop.
    (_loop(100, 10.0, 20.0, 0.05, 0.02),
     _loop(4100, 50.0, 70.0, 0.30, 0.12), 0.5, 0.25),
    # Nothing collected, nothing read late: both read 0, not None.
    (_loop(100, 10.0, 20.0, 0.05, 0.02),
     _loop(4100, 50.0, 70.0, 0.05, 0.02), 0.0, 0.0),
    # One 67 ms pause inside a late read of a 4 s window.
    (_loop(0, 0.0, 1.0, 0.0, 0.0),
     _loop(400, 3.9, 5.0, 0.067, 0.067), 1.675, 100 * 0.067 / 3.9),
])
def test_shares_of_the_window(before, after, gc_share, late_share):
    run = _run(before, after)
    assert gc_pause_share.compute(run) == pytest.approx(gc_share)
    assert late_read_share.compute(run) == pytest.approx(late_share)


@pytest.mark.parametrize('reader', [gc_pause_share, late_read_share])
def test_none_without_the_counters_or_a_window(reader):
    # A program whose tick_loop predates the counters.
    parent = {'iterations': 9, 'loop_s': 1.0, 'phase_s': {},
              'starved_ticks': 0, 'starved_s': 0.0}
    assert reader.compute(_run(parent, dict(parent, iterations=90,
                                            loop_s=9.0))) is None
    assert reader.compute(types.SimpleNamespace(stats0={},
                                                stats1={})) is None
    # A window in which the clock, or the loop, did not move.
    still = _loop(100, 10.0, 20.0, 0.05, 0.02)
    assert reader.compute(_run(still, dict(still))) is None


def test_late_reads_need_an_iteration():
    before = _loop(100, 10.0, 20.0, 0.05, 0.02)
    parked = dict(before, elapsed_s=30.0, gc_s=0.15, idle_s=10.0)
    assert late_read_share.compute(_run(before, parked)) is None
    assert gc_pause_share.compute(_run(before, parked)) == \
        pytest.approx(1.0)


def test_benchmark_json_has_both_metrics():
    with open(os.path.join(_ROOT, 'BENCHMARK.json'), encoding='utf-8') as f:
        per_layer = {m['name']: m for m in json.load(f)['per_layer']}
    for name in ('gc_pause_share', 'late_read_share'):
        assert per_layer[name] == {
            'name': name, 'unit': '%', 'better': 'lower',
            'source': 'program_span', 'layer': 'engine tick',
            'moves': 'out_tok_per_s'}
