"""Mean device time of one execution of the paged prefill-chunk program."""

from chipbench import trace_reduce

CHUNK = r"^jit__paged_prefill_chunk_fn"


def compute(ctx):
    return None if ctx.trace is None else trace_reduce.mean_module_ms(ctx.trace, CHUNK)
