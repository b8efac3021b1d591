"""Device time one prefill chunk spends in its Mamba layers: the parts
``ssm_in``, ``ssm_conv``, ``ssm_scan`` and ``ssm_out`` together, mean over the
whole executions of the chunk program in the traced span (op_scopes.py). None
where the trace names none of them (a family without such layers)."""

from chipbench import op_scopes


def compute(ctx):
    return op_scopes.part_ms(ctx.trace, "chunk", *op_scopes.SSM_PARTS)
