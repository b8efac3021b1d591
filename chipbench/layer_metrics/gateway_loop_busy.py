"""Share of the traced span the gateway's loop thread spent inside its own
spans (``atpu:gw.*``: accept, route, one SSE token write, the done event) —
the thread is the line that holds ``atpu:gw.sse_write``."""

from chipbench import host_spans


def compute(ctx):
    if ctx.trace is None:
        return None
    events = host_spans.load()
    loop = host_spans.thread_of(events, host_spans.GATEWAY_MARK)
    skew = host_spans.skew_of(events)
    window = tuple(t + skew for t in ctx.trace.span_ns())       # on the host plane's clock
    share = host_spans.thread_busy_share(events, loop, window, prefix="gw.")
    return None if share is None else 100.0 * share
