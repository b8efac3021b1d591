"""The expert layer's share of its bandwidth roofline in a decode tick: the
bytes of the shared experts and of the held experts the active slots are
expected to touch (flops_cohere2_moe.py) / HBM rate, over the device time of
the expert ops in one execution of the decode program (moe_trace.py)."""

from chipbench import flops_cohere2_moe as flops
from chipbench import moe_trace

DECODE = r"^jit__paged_decode_fn"


def compute(ctx):
    if ctx.trace is None or "held_experts" not in ctx.config:
        return None
    ms = moe_trace.expert_ms_per_execution(ctx.trace, ctx.config, DECODE)
    occupancy = ctx.stats.get("slot_occupancy")
    if ms is None or not occupancy:
        return None
    nbytes = flops.moe_bytes(ctx.config, occupancy * ctx.counts["slots"])
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
