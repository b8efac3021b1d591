"""Share of the traced span in which no operation ran on the device."""

from chipbench import trace_reduce


def compute(ctx):
    return None if ctx.trace is None else trace_reduce.idle_percent(ctx.trace)
