"""The serving programs' share of the chip's bf16 peak over the window, for
the longcat_flash family: FLOPs needed for every prompt prefilled and every
token decoded in it (flops_longcat_flash.py: both attentions' projections and
both dense MLPs of every double layer, the router, the held experts a token is
expected to pick — a zero-compute pick counts nothing —, latent attention in
the cheaper of its two forms for each call shape, the head over the held
slice) / window / peak."""

from chipbench import flops_longcat_flash as flops


def compute(ctx):
    work = ctx.counts.get("_work")
    if not work or ctx.config.get("family") != "longcat_flash":
        return None
    total = sum(flops.request_flops(ctx.config, p, first, later) for p, first, later in work)
    return 100.0 * total / ctx.window_s / ctx.peaks["bf16_flops_per_s"]
