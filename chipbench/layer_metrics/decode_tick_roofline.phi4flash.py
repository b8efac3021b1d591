"""A decode tick's share of its bandwidth roofline, for the phi4flash family:
the bytes one tick has to move (every weight once, the full-attention layer's
rows once for itself and once for each cross-attention that reads them, the
windowed layers' rows inside the window, the running slots' recurrent state in
and out; flops_phi4flash.py) / HBM rate, over the mean device time of one
execution of the decode program."""

from chipbench import flops_phi4flash as flops
from chipbench import trace_reduce

DECODE = r"^jit__paged_decode_fn"


def compute(ctx):
    if ctx.trace is None or ctx.config.get("family") != "phi4flash":
        return None
    contexts = [c for _, _, later in ctx.counts.get("_work") or [] for c in later]
    tick_ms = trace_reduce.mean_module_ms(ctx.trace, DECODE)
    occupancy = ctx.stats.get("slot_occupancy")
    if tick_ms is None or not occupancy or not contexts:
        return None
    nbytes = flops.decode_tick_bytes(ctx.config, occupancy * ctx.counts["slots"], contexts)
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / (tick_ms * 1e-3)
