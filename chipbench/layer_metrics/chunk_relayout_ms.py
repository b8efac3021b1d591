"""Device time one prefill chunk spends only moving data: the leaf events
whose ``hlo_category`` is ``data formatting`` or that carry no ``tf_op``
(``copy.N``, ``copy-done.N``: the compiler's), under whatever part; mean over
the whole executions of the chunk program in the traced span (op_scopes.py).
It does NOT see a re-layout the compiler fused with arithmetic
(``slice_bitcast_fusion``, ``convert_bitcast_fusion``). None where the
trace's program names no parts."""

from chipbench import op_scopes


def compute(ctx):
    return op_scopes.relayout_ms(ctx.trace, "chunk")
