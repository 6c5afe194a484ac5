"""Engine tick: median time from a request's admission to its slot
going live (`span.prefill_wall_s`: the prefix-cache seed, the prefill
chunks, the page scatter and every tick that ran between them), over
every request the window admitted.  With `queue_wait_s` and
`first_token_wait_s` it adds up to the span's `ttft_s`.  Set by
`RequestSpan.mark_admitted` / `mark_live` from
`serve/batching_engine.py::_start_admission` and `_activate`."""
from benchmarks import traffic


def compute(run):
    walls = [w * 1e3 for w in (
        getattr(r.handle.span, 'prefill_wall_s', None)
        for r in run.requests if r.handle is not None) if w is not None]
    return traffic.percentile(walls, 50) if walls else None
