"""Share of the KV rows held that no later query can read: rows of sliding
layers more than a window behind their stream's position / all rows held,
summed over the window's ticks (ServingStats ``kv_dead_rows_share``): what an
allocator with a class of pages per layer kind would free. None where the
program has no such counter."""


def compute(ctx):
    value = ctx.stats.get("kv_dead_rows_share")
    return None if value is None else 100.0 * value
