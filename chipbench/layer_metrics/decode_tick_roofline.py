"""A decode tick's share of its bandwidth roofline: the bytes one tick has to
read (the matmul weights it touches once — every expert the active slots are
expected to pick — and the live KV rows at the window's mean context;
flops.py) / HBM rate, over the mean device time of one execution of the
decode program."""

from chipbench import flops, trace_reduce

DECODE = r"^jit__paged_decode_fn"


def compute(ctx):
    if ctx.trace is None or not ctx.counts.get("mean_decode_context"):
        return None
    tick_ms = trace_reduce.mean_module_ms(ctx.trace, DECODE)
    occupancy = ctx.stats.get("slot_occupancy")
    if tick_ms is None or not occupancy:
        return None
    active = occupancy * ctx.counts["slots"]
    nbytes = flops.decode_tick_bytes(ctx.config, active, ctx.counts["mean_decode_context"])
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / (tick_ms * 1e-3)
