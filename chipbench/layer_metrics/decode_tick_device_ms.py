"""Mean device time of one execution of the paged decode program."""

from chipbench import trace_reduce

DECODE = r"^jit__paged_decode_fn"


def compute(ctx):
    return None if ctx.trace is None else trace_reduce.mean_module_ms(ctx.trace, DECODE)
