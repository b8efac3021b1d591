"""Mean wait in the admission queue, submit to slot, over the requests
admitted in the window (ServingStats)."""


def compute(ctx):
    return ctx.stats.get("queue_wait_ms") or None
