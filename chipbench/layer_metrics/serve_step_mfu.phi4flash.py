"""The serving programs' share of the chip's bf16 peak over the window, for
the phi4flash family: FLOPs needed for every prompt prefilled and every token
decoded in it (flops_phi4flash.py: every projection, the convolutions and
selective scans, two softmaxes a head pair over the keys each attention can
see, the tied head) / window / peak."""

from chipbench import flops_phi4flash as flops


def compute(ctx):
    work = ctx.counts.get("_work")
    if not work or ctx.config.get("family") != "phi4flash":
        return None
    total = sum(flops.request_flops(ctx.config, p, first, later) for p, first, later in work)
    return 100.0 * total / ctx.window_s / ctx.peaks["bf16_flops_per_s"]
