"""Host time per decode tick after its tokens are on the host: commit loop,
retirements, emitter puts, page samples (ServingStats, phase ``tick_commit``)."""


def compute(ctx):
    return ctx.stats.get("host_us/tick_commit") or None
