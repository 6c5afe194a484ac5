"""Kernels (`ops/ssm_decode.py`): the decode-state kernel's share of its
roofline.  Least time for what every token decoded inside the traced
span has to move at the mamba layers (the family's `ssm_step_bytes`:
each head's float32 state read and written once, and the update's
operands) at the table's HBM peak, per second of the span; over the
device seconds per traced second of the events the trace names for the
kernel.  A family without `ssm_step_bytes`, or a program without the
kernel, reports nothing."""
from benchmarks.layers import _work

# The kernel is told by its own name, which the Pallas call gives its
# instruction (`paged_engine_step/%ssm_decode_step.55 custom-call`).
KERNEL = '%ssm_decode_step'


def compute(run):
    step_bytes = getattr(run.family, 'ssm_step_bytes', None)
    if (step_bytes is None or run.trace is None or run.trace_span is None
            or run.peak is None):
        return None
    kernel_s = sum(v for k, v in run.trace['by_name'].items()
                   if KERNEL in k)
    tokens = len(_work.decoded_contexts(run))
    if kernel_s <= 0 or not tokens:
        return None
    t_a, t_b = run.trace_span
    needed_per_s = (tokens * step_bytes(run.model) /
                    run.peak['hbm_bytes_per_s'] / (t_b - t_a))
    spent_per_s = kernel_s / run.trace['devices'] / run.trace['window_s']
    return 100.0 * needed_per_s / spent_per_s
