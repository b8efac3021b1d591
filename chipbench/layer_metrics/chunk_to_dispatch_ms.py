"""Mean time from a prefill chunk's result being ready on the host to the
return of the engine thread's next device launch (decode tick or next chunk):
the device has nothing queued meanwhile (ServingStats, over the window)."""


def compute(ctx):
    return ctx.stats.get("chunk_to_dispatch_ms") or None
