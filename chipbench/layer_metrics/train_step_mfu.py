"""The whole train step's share of the chip's bf16 peak: FLOPs the forward
and backward need per token (flops.py; recomputation not counted) x this
run's tokens/s / peak."""

from chipbench import flops


def compute(ctx):
    rate = ctx.rates.get("train_tok_s")
    if rate is None:
        return None
    per_token = flops.train_flops_per_token(ctx.config, ctx.traffic["seq_len"])
    return 100.0 * per_token * rate / ctx.peaks["bf16_flops_per_s"]
