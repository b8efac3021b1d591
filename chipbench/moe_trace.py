"""Device time of the expert products of a cohere2_moe program in a trace.

The program names its parts (``jax.named_scope``: moe_experts, moe_shared),
but an event of the ``XLA Ops`` line is named by its HLO instruction alone —
result, opcode, operand shapes — and carries no scope. So the expert ops are
found by what they read: an instruction that has a whole expert stack
(``bf16[count, hidden, width]`` held, ``bf16[shared, hidden, width]`` shared)
among its operands. That covers the batched products of a decode tick and,
in a prefill chunk, the loop over expert tiles (the ``while`` carries the
stacks; the union of intervals counts it and its body once). The routing
around them (top-k, sort, gathers) reads no stack and is not counted.
"""

from __future__ import annotations

import re

from chipbench import trace_reduce


def stack_pattern(cfg: dict) -> str:
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    first, stop = cfg["held_experts"]
    counts = {int(stop) - int(first), int(cfg["num_shared_experts"])}
    shapes = [f"{n},{a},{b}" for n in sorted(counts) for a, b in {(h, f), (f, h)}]
    return r"\[(" + "|".join(re.escape(s) for s in shapes) + r")\]"


def expert_ms_per_execution(trace, cfg: dict, module_pattern: str) -> float | None:
    """Mean over the executions of the programs matching ``module_pattern``
    of the device time in which an expert op ran; None where none ran."""
    runs = trace.select(trace_reduce.MODULES_LINE, module_pattern)
    if not runs:
        return None
    plane = runs[0].plane
    runs = sorted((r for r in runs if r.plane == plane), key=lambda r: r.start_ns)
    ops = trace.select(trace_reduce.OPS_LINE, stack_pattern(cfg), plane=plane)
    inside = _inside(ops, runs)
    if not inside:
        return None
    return trace_reduce.union_ns(inside) * 1e-6 / len(runs)


def _inside(ops, runs):
    """Ops that start inside one of ``runs`` (sorted, disjoint): a merge."""
    out, i = [], 0
    for e in sorted(ops, key=lambda e: e.start_ns):
        while i < len(runs) and runs[i].end_ns <= e.start_ns:
            i += 1
        if i < len(runs) and runs[i].start_ns <= e.start_ns:
            out.append(e)
    return out
