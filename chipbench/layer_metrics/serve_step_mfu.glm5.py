"""The serving programs' share of the chip's bf16 peak over the window, for
the glm_moe_dsa family: FLOPs needed for every prompt prefilled and every
token decoded in it (flops_glm_moe_dsa.py: the projections, the indexers,
the dense MLP, the routers, the shared experts, the held experts a token is
expected to pick, the indexer's scores of every visible row of a full layer,
latent attention over the ``index_topk`` picked rows alone — a form that
scores more rows under a mask counts the rest as waste —, the head over the
held slice) / window / peak."""

from chipbench import flops_glm_moe_dsa as flops


def compute(ctx):
    work = ctx.counts.get("_work")
    if not work or ctx.config.get("family") != "glm_moe_dsa":
        return None
    total = sum(flops.request_flops(ctx.config, p, first, later) for p, first, later in work)
    return 100.0 * total / ctx.window_s / ctx.peaks["bf16_flops_per_s"]
