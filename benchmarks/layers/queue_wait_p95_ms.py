"""Admission queue and scheduler: 95th percentile of the time a request
waited for a slot, from the program's own span (`queue_wait_s`, host
clock inside the program), over every request the window admitted."""


from benchmarks import traffic


def compute(run):
    waits = [r.handle.span.queue_wait_s * 1e3 for r in run.requests
             if r.handle is not None and
             r.handle.span.queue_wait_s is not None]
    return traffic.percentile(waits, 95) if waits else None
