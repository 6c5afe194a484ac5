"""What the traffic needed inside the traced span, from the benchmark's
own record of prompt lengths, cached prefixes and token arrival times:
shared by the readers that set needed work against device time."""


def decoded_contexts(run):
    """Context length (keys attended) of every token that arrived
    inside the traced span."""
    t_a, t_b = run.trace_span
    return [len(r.prompt) + i for r in run.requests
            for i, t in enumerate(r.token_s) if t_a <= t < t_b]


def needed_flops(run) -> float:
    """Model FLOPs for every token decoded in the span and every prompt
    token prefilled in it.  A request's prefill (its prompt less the
    cached prefix and less the last token, which rides the first tick)
    is spread evenly from admission to first token; cached tokens need
    none."""
    t_a, t_b = run.trace_span
    page = run.geometry['page_size']
    total = sum(run.family.decode_flops(run.model, c)
                for c in decoded_contexts(run))
    for r in run.requests:
        span = getattr(r.handle, 'span', None)
        if span is None or span.queue_wait_s is None or not r.token_s:
            continue
        begin = r.sent_s + span.queue_wait_s
        end = r.token_s[0]
        overlap = min(end, t_b) - max(begin, t_a)
        if overlap <= 0 or end <= begin:
            continue
        cached = span.prefix_hit_pages * page
        fresh = len(r.prompt) - 1 - cached
        if fresh > 0:
            total += (run.family.prefill_flops(run.model, cached, fresh) *
                      overlap / (end - begin))
    return total
