"""The decode tick's learned selection against its roofline: the device time
of the parts ``dsa_index`` (the indexers' projections and their scores of
every visible index key) and ``dsa_select`` (the top-k and the gather of the
picked latent rows), mean over the whole executions of the decode program in
the traced span (op_scopes.py), against the larger of its FLOPs / peak and
its bytes / HBM rate (flops_glm_moe_dsa.index_tick_need: index keys, weights,
picked rows, at the window's mean active slots and contexts). None where the
trace names no such part."""

from chipbench import flops_glm_moe_dsa as flops
from chipbench import op_scopes


def compute(ctx):
    if ctx.trace is None or ctx.config.get("family") != "glm_moe_dsa":
        return None
    ms = op_scopes.part_ms(ctx.trace, "decode", "dsa_index", "dsa_select")
    contexts = [c for _, _, later in ctx.counts.get("_work") or [] for c in later]
    occupancy = ctx.stats.get("slot_occupancy")
    if not ms or not occupancy or not contexts:
        return None
    need, nbytes = flops.index_tick_need(ctx.config, occupancy * ctx.counts["slots"], contexts)
    least_s = max(need / ctx.peaks["bf16_flops_per_s"], nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3)
