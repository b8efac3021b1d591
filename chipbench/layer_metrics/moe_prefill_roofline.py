"""The expert layer's share of its roofline in a prefill chunk: the least
time for one chunk's tokens through the shared experts and the held experts
they pick (the larger of FLOPs / peak and weight bytes / HBM rate;
flops_cohere2_moe.py), over the device time of the expert ops in one
execution of the prefill-chunk program (moe_trace.py)."""

from chipbench import flops, moe_trace
from chipbench import flops_cohere2_moe as moe_flops

CHUNK = r"^jit__paged_prefill_chunk_fn"


def compute(ctx):
    if ctx.trace is None or "held_experts" not in ctx.config:
        return None
    ms = moe_trace.expert_ms_per_execution(ctx.trace, ctx.config, CHUNK)
    if ms is None:
        return None
    tokens = ctx.config["assumed"]["prefill_chunk"]
    least, _ = flops.roofline_seconds(moe_flops.moe_flops(ctx.config, tokens),
                                      moe_flops.moe_bytes(ctx.config, tokens), ctx.peaks)
    return 100.0 * least / (ms * 1e-3)
