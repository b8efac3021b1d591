"""Device time one decode tick spends only moving data: the leaf events whose
``hlo_category`` is ``data formatting`` (copy, transpose, reshape, pad, slice
as instructions of their own) or that carry no ``tf_op`` (``copy.N``,
``copy-done.N``: copies the compiler put in itself), under whatever part; mean
over the whole executions of the decode program in the traced span
(op_scopes.py). It does NOT see a re-layout the compiler fused with arithmetic
(``slice_bitcast_fusion``, ``convert_bitcast_fusion``: their category is a
fusion's). None where the trace's program names no parts."""

from chipbench import op_scopes


def compute(ctx):
    return op_scopes.relayout_ms(ctx.trace, "decode")
