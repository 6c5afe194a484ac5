"""Engine tick: the share of the loop's time spent reading ticks that
had already finished, 100 x Δ`late_read_s` / Δ`loop_s` of
`stats()['tick_loop']`, close minus open.  `TickProfiler.device_wait`
asks the tick it reads `is_ready()` on entry (stat `ready` on
`skytpu/device-wait`): a read of a finished tick is host time --
transfer, runtime, the GIL -- and the device has nothing queued behind
the next tick while it lasts.  A program without these counters, or a
window without an iteration, reports nothing."""


def compute(run):
    a, b = run.stats0.get('tick_loop'), run.stats1.get('tick_loop')
    if not a or not b or any('late_read_s' not in t for t in (a, b)):
        return None
    loop_s = b['loop_s'] - a['loop_s']
    if b['iterations'] - a['iterations'] <= 0 or loop_s <= 0:
        return None
    return 100.0 * (b['late_read_s'] - a['late_read_s']) / loop_s
