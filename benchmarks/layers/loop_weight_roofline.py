"""Model step (`models/decode.py`): the share of the engine loop's time
that reading a looped stack's weights once a pass would take at the
table's HBM peak.  Passes over the stack that the decode ticks ran
(`stats()['loop']['passes']`) x the bytes one pass must read (the
family's `loop_stack_bytes`: the layers' matrices at the served dtype)
over the peak, per second of the loop (`stats()['tick_loop']['loop_s']`:
the worker loop's seconds in iterations that did work); both close
minus open.  In a closed loop that keeps every slot live the loop's
seconds are the window's.  They are taken from the engine and not from
the benchmark's clock because a traced run reads the closing counters
only when the profiler has written its trace, many seconds after the
window closed, while the slots drain: passes and seconds are then read
at the same moment.  A tick decodes every live slot on one read of the
stack a pass, so no batch can pass 100%; what is missing is the caches'
reads, the kernel's and the scatters' fixed cost a cache layer, the
head, the prefill programs and the host.  A program without the counter
(or a family without `loop_stack_bytes`: a model that runs its layers
once) reports nothing."""


def compute(run):
    loop0, loop1 = run.stats0.get('loop'), run.stats1.get('loop')
    stack_bytes = getattr(run.family, 'loop_stack_bytes', None)
    if not loop0 or not loop1 or stack_bytes is None or run.peak is None:
        return None
    passes = loop1['passes'] - loop0['passes']
    seconds = (run.stats1['tick_loop']['loop_s'] -
               run.stats0['tick_loop']['loop_s'])
    if passes <= 0 or seconds <= 0:
        return None
    return (100.0 * passes * stack_bytes(run.model) /
            run.peak['hbm_bytes_per_s'] / seconds)
