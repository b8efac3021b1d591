"""The share of their roofline of the decode tick's attentions over the ONE
cache entry that several layers read (phi4flash: layer 17's keys and values,
read by itself and by the 7 cross-attentions): the least time for the rows
the running slots' queries can see, once a reader (the larger of FLOPs / peak
and bytes / HBM rate; flops_phi4flash.shared_kv_decode_need), over the device
time of those readers' work-list loops in one execution of the decode program
(ssm_trace.shared_readers_ms_per_execution: the last ``kv_reader_layers -
kv_cache_layers + 1`` loops over the pool, the program's own counters). None
where the program has no such counters or the trace no such loops."""

from chipbench import flops, ssm_trace
from chipbench import flops_phi4flash as kv_flops

DECODE = r"^jit__paged_decode_fn"


def compute(ctx):
    if ctx.trace is None or ctx.config.get("family") != "phi4flash":
        return None
    entries, readers = ctx.stats.get("kv_cache_layers"), ctx.stats.get("kv_reader_layers")
    contexts = [c for _, _, later in ctx.counts.get("_work") or [] for c in later]
    occupancy = ctx.stats.get("slot_occupancy")
    if not entries or not readers or not occupancy or not contexts:
        return None
    ms = ssm_trace.shared_readers_ms_per_execution(ctx.trace, ctx.config, DECODE, entries, readers)
    if ms is None:
        return None
    need = kv_flops.shared_kv_decode_need(ctx.config, occupancy * ctx.counts["slots"], contexts)
    least, _ = flops.roofline_seconds(*need, ctx.peaks)
    return 100.0 * least / (ms * 1e-3)
