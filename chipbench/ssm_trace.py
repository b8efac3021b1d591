"""Device time of the parts of a phi4flash program that no other family has,
found in a trace by what they hold.

The program names its parts (``jax.named_scope``: ssm_scan, attn_cross_shared),
but an event of the ``XLA Ops`` line is named by its HLO instruction alone. So,
as moe_trace.py and mla_trace.py do, ops are found by the shapes in their text:

* the selective scan: an instruction that holds a float32 array whose last two
  axes are ``[d_state, d_inner]`` (the decay and input terms of a block of
  steps, the states between them), as a result or an operand; the ``while``
  that steps over the blocks carries one, and the union of intervals counts it
  and its body once.
"""

from __future__ import annotations

import re

from chipbench import moe_trace, trace_reduce


def state_pattern(cfg: dict) -> str:
    a = cfg["assumed"]
    tail = f"{a['mamba_d_state']},{a['mamba_expand'] * cfg['hidden_size']}"
    return r"f32\[(\d+,)*" + re.escape(tail) + r"\]"


def ms_per_execution(trace, module_pattern: str, op_pattern: str) -> float | None:
    """Mean over the executions of the programs matching ``module_pattern`` of
    the device time in which an op matching ``op_pattern`` ran; None where
    none ran."""
    runs = trace.select(trace_reduce.MODULES_LINE, module_pattern)
    if not runs:
        return None
    plane = runs[0].plane
    runs = sorted((r for r in runs if r.plane == plane), key=lambda r: r.start_ns)
    inside = moe_trace._inside(trace.select(trace_reduce.OPS_LINE, op_pattern, plane=plane), runs)
    if not inside:
        return None
    return trace_reduce.union_ns(inside) * 1e-6 / len(runs)


def scan_ms_per_execution(trace, cfg: dict, module_pattern: str) -> float | None:
    if "mamba_d_state" not in cfg.get("assumed", {}):
        return None
    return ms_per_execution(trace, module_pattern, state_pattern(cfg))


def pool_leaf_pattern(cfg: dict) -> str:
    """A ``while`` whose operands hold a page-pool leaf of this configuration:
    ``[max_pages + 1, 1, page, kv_heads * head_dim]`` (a row keeps its heads
    side by side), the page as wide as a prefill chunk."""
    a = cfg["assumed"]
    if a.get("max_pages") is None:
        return r"$^"                                            # no stated pool: nothing to find
    width = cfg["num_key_value_heads"] * (cfg["hidden_size"] // cfg["num_attention_heads"])
    leaf = f"{a['max_pages'] + 1},1,{a['prefill_chunk']},{width}"
    return r"\swhile\(.*\[" + re.escape(leaf) + r"\]"


def shared_readers_ms_per_execution(trace, cfg: dict, module_pattern: str, entries: int,
                                    readers: int) -> float | None:
    """Mean over the executions of the programs matching ``module_pattern`` of
    the device time of the work-list loops that read the SHARED cache entry:
    every attention of a tick is one ``while`` with the pool's leaves among its
    operands (all entries' leaves are shaped alike, so the shape does not tell
    them apart), they run in layer order, and the shared entry is the last one
    written: its owner and its ``readers - entries`` further readers are the
    last ``readers - entries + 1`` loops of an execution. None unless every
    execution holds exactly ``readers`` such loops."""
    runs = trace.select(trace_reduce.MODULES_LINE, module_pattern)
    if not runs or not entries or readers <= entries:
        return None
    plane = runs[0].plane
    runs = sorted((r for r in runs if r.plane == plane), key=lambda r: r.start_ns)
    loops = sorted(trace.select(trace_reduce.OPS_LINE, pool_leaf_pattern(cfg), plane=plane),
                   key=lambda e: e.start_ns)
    total, complete = 0.0, 0
    for run in runs:
        mine = [e for e in loops if run.start_ns <= e.start_ns < run.end_ns]
        if len(mine) != readers:
            continue                                            # an execution the trace cut in two
        total += sum(e.dur_ns for e in mine[entries - 1:])
        complete += 1
    return total * 1e-6 / complete if complete else None
