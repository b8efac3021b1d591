"""Host time per decode tick to dispatch it: flow-control scan, page cover,
mask, page-table copy and the jit call (ServingStats, phase ``tick_launch``)."""


def compute(ctx):
    return ctx.stats.get("host_us/tick_launch") or None
