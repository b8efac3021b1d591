"""The latent attention's share of its roofline in a prefill chunk: the least
time for one full chunk at the window's mean chunk offset (the larger of
FLOPs / peak and bytes / HBM rate, in the cheaper form;
flops_pangu_ultra_moe.py), over the device time of the ops that read a latent
view in one execution of the prefill-chunk program (mla_trace.py). The mean
offset is that of the prompts whose first token fell in the window, chunk by
chunk. None where no such op ran."""

from chipbench import flops, mla_trace
from chipbench import flops_pangu_ultra_moe as mla_flops

CHUNK = r"^jit__paged_prefill_chunk_fn"


def compute(ctx):
    if ctx.trace is None or "kv_lora_rank" not in ctx.config:
        return None
    ms = mla_trace.attention_ms_per_execution(ctx.trace, ctx.config, CHUNK)
    chunk = ctx.config["assumed"]["prefill_chunk"]
    offsets = [o for p, first, _ in ctx.counts.get("_work") or [] if first
               for o in range(0, p, chunk)]
    if ms is None or not offsets:
        return None
    need = mla_flops.chunk_attention_need(ctx.config, sum(offsets) / len(offsets))
    least, _ = flops.roofline_seconds(*need, ctx.peaks)
    return 100.0 * least / (ms * 1e-3)
