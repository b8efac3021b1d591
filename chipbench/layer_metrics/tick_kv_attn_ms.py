"""Device time one decode tick spends reading its caches: the part ``kv_attn``
(scores, softmax, value product and the merge over the cached rows — the work
list with its page gathers), mean over the whole executions of the decode
program in the traced span (op_scopes.py). None where the trace names no such
part."""

from chipbench import op_scopes


def compute(ctx):
    return op_scopes.part_ms(ctx.trace, "decode", "kv_attn")
