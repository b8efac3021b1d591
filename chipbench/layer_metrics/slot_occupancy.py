"""Active slots / slot capacity over the window's ticks (ServingStats)."""


def compute(ctx):
    value = ctx.stats.get("slot_occupancy")
    return None if not value else 100.0 * value
