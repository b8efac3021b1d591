"""The serving programs' share of the chip's bf16 peak over the window: FLOPs
needed for every prompt prefilled and every token decoded in it (2 x active
matmul parameters per token through the layers, the head once per token
produced, causal attention over each token's true context; flops.py) / the
window / peak."""

from chipbench import flops


def compute(ctx):
    work = ctx.counts.get("_work")
    if not work:
        return None
    total = sum(flops.serve_request_flops(ctx.config, p, first, later) for p, first, later in work)
    return 100.0 * total / ctx.window_s / ctx.peaks["bf16_flops_per_s"]
