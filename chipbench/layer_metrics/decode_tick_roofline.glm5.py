"""A decode tick's share of its bandwidth roofline, for the glm_moe_dsa
family: the bytes one tick has to read (every layer's attention with its
up-projection, the two indexers, the dense MLP, the routers and shared
experts, the held experts the active slots are expected to touch, each once,
the head, the ``index_topk`` picked latent rows of every layer and the index
keys of every visible row of the full layers; flops_glm_moe_dsa.py) / HBM
rate, over the mean device time of one execution of the decode program."""

from chipbench import flops_glm_moe_dsa as flops
from chipbench import trace_reduce

DECODE = r"^jit__paged_decode_fn"


def compute(ctx):
    if ctx.trace is None or ctx.config.get("family") != "glm_moe_dsa":
        return None
    contexts = [c for _, _, later in ctx.counts.get("_work") or [] for c in later]
    tick_ms = trace_reduce.mean_module_ms(ctx.trace, DECODE)
    occupancy = ctx.stats.get("slot_occupancy")
    if tick_ms is None or not occupancy or not contexts:
        return None
    nbytes = flops.decode_tick_bytes(ctx.config, occupancy * ctx.counts["slots"], contexts)
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / (tick_ms * 1e-3)
