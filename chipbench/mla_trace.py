"""Device time of the latent attention of a pangu_ultra_moe program in a trace.

The program names its parts (``jax.named_scope``: attn_mla, mla_scores), but
an event of the ``XLA Ops`` line is named by its HLO instruction alone. So the
attention ops are found by what they read, as moe_trace.py finds expert ops:
an instruction that has the latent view — ``[.., max_len, rank]``, the
slot's gathered latents (the rotary keys' leaf beside it is an eighth as wide
and read by the same instructions) — among its OPERANDS (the text after the opcode's
parenthesis; the gather that builds the view has it as its result only and
is not counted). That covers a tick's score and value products over every
lane's view, the write of the new row, and in a prefill chunk the loop over
key blocks (the ``while`` carries the view; the union of intervals counts it
and its body once).
"""

from __future__ import annotations

import re

from chipbench import moe_trace, trace_reduce


def view_pattern(cfg: dict) -> str:
    """An operand list that holds a ``[..., max_len, rank]`` array."""
    rows, width = cfg["assumed"]["max_len"], cfg["kv_lora_rank"]
    return r"\s[a-z][a-z0-9\-]*\(.*\[(\d+,)*" + re.escape(f"{rows},{width}") + r"\]"


def attention_ms_per_execution(trace, cfg: dict, module_pattern: str) -> float | None:
    """Mean over the executions of the programs matching ``module_pattern``
    of the device time in which an op that reads the latent view ran; None
    where none ran (a program without a latent cache)."""
    if "kv_lora_rank" not in cfg:
        return None
    runs = trace.select(trace_reduce.MODULES_LINE, module_pattern)
    if not runs:
        return None
    plane = runs[0].plane
    runs = sorted((r for r in runs if r.plane == plane), key=lambda r: r.start_ns)
    ops = trace.select(trace_reduce.OPS_LINE, view_pattern(cfg), plane=plane)
    inside = moe_trace._inside(ops, runs)
    if not inside:
        return None
    return trace_reduce.union_ns(inside) * 1e-6 / len(runs)
