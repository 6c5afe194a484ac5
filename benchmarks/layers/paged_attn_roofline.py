"""Kernels (`ops/paged_attention.py`): the paged decode kernel's share
of its roofline.  Least time for the K and V bytes of every context a
token was decoded at inside the traced span (x layers, at the pool's
dtype) at the table's HBM peak, or for the FLOPs at the compute peak,
whichever is longer (`cost.paged_attention_floor_s` says which: with
grouped queries it is 4 FLOPs a byte, so HBM), per second of the span;
over the device seconds per traced second of the events the trace
names for the kernel."""
from benchmarks import cost
from benchmarks.layers import _work

# What the device trace calls the kernel (read by hand from the traced
# chip runs of PR 25): the one Mosaic custom call inside the tick's
# program, `%closed_call.8 = bf16[16,8,4,128] custom-call(s32[16,160] ...
# custom_call_target="tpu_custom_call"`.  The events carry no name of
# the Pallas kernel, so it is told by its program: the engine jits a
# `functools.partial`, which the trace's "XLA Modules" line calls
# `jit__unknown(<hash>)`; a program named after `paged_engine_step`
# would be the same tick.  Flash prefill is a Mosaic call too, but in
# the prefill's program (`jit__lambda`).
KERNEL_PROGRAMS = ('unknown/', 'paged_engine_step/')
KERNEL_EVENT = 'custom-call tpu_custom_call'


def compute(run):
    if run.trace is None or run.trace_span is None or run.peak is None:
        return None
    kernel_s = sum(v for k, v in run.trace['by_name'].items()
                   if k.startswith(KERNEL_PROGRAMS) and KERNEL_EVENT in k)
    contexts = sum(_work.decoded_contexts(run))
    if kernel_s <= 0 or contexts <= 0:
        return None
    t_a, t_b = run.trace_span
    floor = cost.paged_attention_floor_s(run.model, contexts,
                                         run.kv_dtype, run.peak)
    needed_per_s = floor['seconds'] / (t_b - t_a)
    spent_per_s = kernel_s / run.trace['devices'] / run.trace['window_s']
    return 100.0 * needed_per_s / spent_per_s
