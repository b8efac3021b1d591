"""Mean device time of one execution of the compiled train step."""

from chipbench import trace_reduce

STEP = r"^jit_train_step"


def compute(ctx):
    return None if ctx.trace is None else trace_reduce.mean_module_ms(ctx.trace, STEP)
