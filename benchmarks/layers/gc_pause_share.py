"""Engine tick: the share of the window the collector held the process,
100 x Δ`gc_s` / Δ`elapsed_s` of `stats()['tick_loop']`, close minus
open.  `gc_s` sums every collection, on any thread, from the `start`
to the `stop` of the profiler's `gc.callbacks` hook (`skytpu/gc` on the
trace's host plane): each one stops the engine's worker, so a tick in
flight finishes to an idle device if the pause outlasts it.
`elapsed_s` is the profiler's own clock.  A program without these
counters reports nothing."""


def compute(run):
    a, b = run.stats0.get('tick_loop'), run.stats1.get('tick_loop')
    if not a or not b or any('gc_s' not in t or 'elapsed_s' not in t
                             for t in (a, b)):
        return None
    elapsed = b['elapsed_s'] - a['elapsed_s']
    if elapsed <= 0:
        return None
    return 100.0 * (b['gc_s'] - a['gc_s']) / elapsed
