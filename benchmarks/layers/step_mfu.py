"""Model step: the whole step's share of the chip's peak.  FLOPs the
model needs for every prompt token prefilled and every token decoded
inside the traced span (`_work.needed_flops`; cached prefix tokens need
none), over the span's seconds x chips x the table's bf16 peak.  Both
sides on the benchmark's own clock."""
from benchmarks.layers import _work


def compute(run):
    if run.trace_span is None or run.peak is None:
        return None
    t_a, t_b = run.trace_span
    flops = _work.needed_flops(run)
    if flops <= 0:
        return None
    return 100.0 * flops / ((t_b - t_a) * run.chips *
                            run.peak['flops_bf16'])
