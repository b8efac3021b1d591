"""Host time per prefill chunk from its result being ready to the chunk call's
return: stats, prefix-cache put, first-token commit (ServingStats, phase
``prefill_commit``)."""


def compute(ctx):
    return ctx.stats.get("host_us/prefill_commit") or None
