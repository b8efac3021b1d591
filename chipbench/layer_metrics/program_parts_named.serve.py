"""Share of the two serving programs' device time (the whole executions of
the decode and the chunk program in the traced span) that lies under a part of
the program's vocabulary: 100 - ``unscoped`` - ``ambiguous``, the device's
twin of ``idle_named.serve``. The tables go to standard error: per program one
row a part (ms an execution, share, own ms, GB/s by XLA's own byte count),
then ``unscoped``, ``ambiguous`` and ``relayout``."""

import sys

from chipbench import op_scopes


def compute(ctx):
    tables = {k: v for k, v in op_scopes.tables(ctx.trace).items() if k in ("decode", "chunk")}
    busy = sum(t.busy_ns for t in tables.values())
    named = sum(t.named_ns() for t in tables.values())
    if not busy or not named:
        return None
    for name, times in tables.items():
        print(op_scopes.table(name, times), file=sys.stderr, flush=True)
    return 100.0 * named / busy
