"""A decode tick's share of its bandwidth roofline, for the longcat_flash
family: the bytes one tick has to read (both attentions, both dense MLPs and
the router of every double layer, the held experts the active slots are
expected to touch, each once, the head, and the latent rows of their contexts
in all eight cache entries; flops_longcat_flash.py) / HBM rate, over the mean
device time of one execution of the decode program."""

from chipbench import flops_longcat_flash as flops
from chipbench import trace_reduce

DECODE = r"^jit__paged_decode_fn"


def compute(ctx):
    if ctx.trace is None or ctx.config.get("family") != "longcat_flash":
        return None
    contexts = [c for _, _, later in ctx.counts.get("_work") or [] for c in later]
    tick_ms = trace_reduce.mean_module_ms(ctx.trace, DECODE)
    occupancy = ctx.stats.get("slot_occupancy")
    if tick_ms is None or not occupancy or not contexts:
        return None
    nbytes = flops.decode_tick_bytes(ctx.config, occupancy * ctx.counts["slots"], contexts)
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / (tick_ms * 1e-3)
