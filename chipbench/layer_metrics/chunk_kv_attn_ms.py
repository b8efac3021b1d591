"""Device time one prefill chunk spends reading its cache view: the part
``kv_attn`` (a chunk's key-block loops), mean over the whole executions of the
chunk program in the traced span (op_scopes.py). None where the trace names
no such part."""

from chipbench import op_scopes


def compute(ctx):
    return op_scopes.part_ms(ctx.trace, "chunk", "kv_attn")
