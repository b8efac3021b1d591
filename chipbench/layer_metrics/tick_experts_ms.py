"""Device time one decode tick spends in its expert layers: the parts
``moe_router``, ``moe_experts`` and ``moe_shared`` together, mean over the
whole executions of the decode program in the traced span (op_scopes.py).
None where the trace names none of them (a family without experts)."""

from chipbench import op_scopes


def compute(ctx):
    return op_scopes.part_ms(ctx.trace, "decode", *op_scopes.EXPERT_PARTS)
