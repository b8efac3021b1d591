"""Mean time from a token's commit on the engine thread to the return of its
``on_token`` callback on the emitter thread (ServingStats)."""


def compute(ctx):
    return ctx.stats.get("emit_lag_ms") or None
