"""Kernels (`ops/paged_attention.py`): the paged decode kernel's share
of its roofline.  Least time for what every token decoded inside the
traced span has to read from the caches at its context (the family's
`decode_cache_bytes`, at the pool's dtype) at the table's HBM peak, or
for the FLOPs over them (`decode_attention_flops`) at the compute peak,
whichever is longer (`cost.paged_attention_floor_s` says which: with
grouped queries it is 4 FLOPs a byte, so HBM), per second of the span;
over the device seconds per traced second of the events the trace
names for the kernel."""
from benchmarks import cost
from benchmarks.layers import _work

# The kernel is told by its own name, which the Pallas call gives its
# instruction (`paged_engine_step/%paged_decode_attention.6 custom-call
# tpu_custom_call`): another Mosaic call in the tick's program is
# another kernel's time.
KERNEL = '%paged_decode_attention'


def compute(run):
    if run.trace is None or run.trace_span is None or run.peak is None:
        return None
    kernel_s = sum(v for k, v in run.trace['by_name'].items()
                   if KERNEL in k)
    contexts = _work.decoded_contexts(run)
    if kernel_s <= 0 or not contexts:
        return None
    t_a, t_b = run.trace_span
    floor = cost.paged_attention_floor_s(
        sum(run.family.decode_cache_bytes(run.model, c, run.kv_dtype)
            for c in contexts),
        sum(run.family.decode_attention_flops(run.model, c)
            for c in contexts), run.peak)
    needed_per_s = floor['seconds'] / (t_b - t_a)
    spent_per_s = kernel_s / run.trace['devices'] / run.trace['window_s']
    return 100.0 * needed_per_s / spent_per_s
