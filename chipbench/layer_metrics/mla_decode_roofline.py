"""The latent attention's share of its roofline in a decode tick: the least
time for the rows the active slots' queries can see (the larger of FLOPs /
peak and bytes / HBM rate, in the cheaper form; flops_pangu_ultra_moe.py),
over the device time of the ops that read a latent view in one execution of
the decode program (mla_trace.py). None where no such op ran."""

from chipbench import flops, mla_trace
from chipbench import flops_pangu_ultra_moe as mla_flops

DECODE = r"^jit__paged_decode_fn"


def compute(ctx):
    if ctx.trace is None or "kv_lora_rank" not in ctx.config:
        return None
    ms = mla_trace.attention_ms_per_execution(ctx.trace, ctx.config, DECODE)
    contexts = [c for _, _, later in ctx.counts.get("_work") or [] for c in later]
    occupancy = ctx.stats.get("slot_occupancy")
    if ms is None or not occupancy or not contexts:
        return None
    need = mla_flops.decode_attention_need(ctx.config, occupancy * ctx.counts["slots"], contexts)
    least, _ = flops.roofline_seconds(*need, ctx.peaks)
    return 100.0 * least / (ms * 1e-3)
