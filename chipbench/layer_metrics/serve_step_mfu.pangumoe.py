"""The serving programs' share of the chip's bf16 peak over the window, for
the pangu_ultra_moe family: FLOPs needed for every prompt prefilled and every
token decoded in it (flops_pangu_ultra_moe.py: the projections, the dense
layer, router, shared expert and the held experts a token is expected to
pick; latent attention in the cheaper of its two forms for each call shape;
the head over the held slice) / window / peak."""

from chipbench import flops_pangu_ultra_moe as flops


def compute(ctx):
    work = ctx.counts.get("_work")
    if not work or "kv_lora_rank" not in ctx.config:
        return None
    total = sum(flops.request_flops(ctx.config, p, first, later) for p, first, later in work)
    return 100.0 * total / ctx.window_s / ctx.peaks["bf16_flops_per_s"]
