"""Engine tick: share of the engine loop's time in which the device had
work queued, over the whole window and not the traced seconds only:
100 x (1 - starved_s / loop_s), both from `stats()['tick_loop']`, close
minus open.  `starved_s` is the starvation probe's ESTIMATE
(`TickProfiler.probe_starved`: right before an iteration's first
dispatch the tick in flight had already finished; seconds = host time
since the last device-wait returned, less the running tick length):
good to the tick-to-tick variation.  A share of time, so never over
100."""


def compute(run):
    a, b = run.stats0.get('tick_loop'), run.stats1.get('tick_loop')
    if not a or not b:
        return None
    loop_s = b['loop_s'] - a['loop_s']
    if loop_s <= 0:
        return None
    return 100.0 * (1.0 - (b['starved_s'] - a['starved_s']) / loop_s)
