"""The serving programs' share of the chip's bf16 peak over the window, for
the cohere2_moe family: FLOPs needed for every prompt prefilled and every
token decoded in it (flops_cohere2_moe.py: attention, router, the shared
experts and the held experts a token is expected to pick; window-capped
attention on sliding layers; the head over the held slice) / window / peak."""

from chipbench import flops_cohere2_moe as flops


def compute(ctx):
    work = ctx.counts.get("_work")
    if not work or "held_experts" not in ctx.config:
        return None
    total = sum(flops.request_flops(ctx.config, p, first, later) for p, first, later in work)
    return 100.0 * total / ctx.window_s / ctx.peaks["bf16_flops_per_s"]
