"""Share of the device's idle time in the traced span that lies under a named
span of the engine thread (100 - ``unnamed``). The whole table goes to
standard error: seconds by span, the waits (the device idle while the host
waits for it: wake-up latency) in rows of their own."""

import sys

from chipbench import host_spans


def compute(ctx):
    if ctx.trace is None:
        return None
    idle = host_spans.idle_by_span(ctx.trace, host_spans.load())
    total = sum(idle.values())
    if not total:
        return None
    print(host_spans.idle_table(idle), file=sys.stderr, flush=True)
    return 100.0 * (1.0 - idle.get(host_spans.UNNAMED, 0.0) / total)
