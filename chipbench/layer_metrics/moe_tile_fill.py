"""Share of the rows the sorted expert tiles computed that were routed rows:
rows routed through ``ops.moe``'s sorted path / (tiles run x tile rows),
summed over the window's prefill chunks (ServingStats ``moe_tile_fill``). The
rest is the padding of each touched expert's last tile: what a tile sized
from the shape trades for reading that expert's weights once. None where the
program has no such counter."""


def compute(ctx):
    value = ctx.stats.get("moe_tile_fill")
    return None if value is None else 100.0 * value
