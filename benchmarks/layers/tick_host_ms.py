"""Engine tick: host milliseconds an engine iteration takes outside
`device-wait`, the one phase in which the host blocks on the device
(`stats()['tick_loop']`: `loop_s` less `phase_s['device-wait']`, over
`iterations`; close minus open, so the whole window).  The head-room
the host has before it, and not the device, sets the pace of a tick.
Phases are set in `serve/batching_engine.py::_run_pipelined` and timed
by `observability/profiling.TickProfiler`."""


def compute(run):
    a, b = run.stats0.get('tick_loop'), run.stats1.get('tick_loop')
    if not a or not b:
        return None
    n = b['iterations'] - a['iterations']
    if n <= 0:
        return None
    wait = (b['phase_s'].get('device-wait', 0.0) -
            a['phase_s'].get('device-wait', 0.0))
    return 1e3 * (b['loop_s'] - a['loop_s'] - wait) / n
