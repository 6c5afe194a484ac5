"""Page pool and prefix cache: share of the admitted requests' prompt
tokens that came from cached pages (`span.prefix_hit_pages` x the page
size over prompt tokens), whole window.  A cell whose traffic shares
nothing reads 0 here by design, so it lists no such cell."""


def compute(run):
    page = run.geometry['page_size']
    admitted = [r for r in run.requests if r.handle is not None and
                r.handle.span.queue_wait_s is not None]
    prompt = sum(len(r.prompt) for r in admitted)
    if not prompt:
        return None
    hit = sum(r.handle.span.prefix_hit_pages * page for r in admitted)
    return 100.0 * hit / prompt
