"""Host work per engine tick over the window (ServingStats, reset at the
window's first instant): scheduling, dispatch and emission, device waits out."""


def compute(ctx):
    return ctx.stats.get("host_us_per_tick") or None
