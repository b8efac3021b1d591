"""The selective scans' share of their roofline in a prefill chunk: the least
time for the nine Mamba layers' scans over one chunk (the larger of
operations / peak and bytes / HBM rate; flops_phi4flash.chunk_scan_need: from
the shapes, whatever implements the scan), over the device time of the ops
that hold a ``[.., d_state, d_inner]`` float32 array — the scan's decay and
input terms and its states — in one execution of the prefill-chunk program
(ssm_trace.py). None where no such op ran."""

from chipbench import flops, ssm_trace
from chipbench import flops_phi4flash as ssm_flops

CHUNK = r"^jit__paged_prefill_chunk_fn"


def compute(ctx):
    if ctx.trace is None or ctx.config.get("family") != "phi4flash":
        return None
    ms = ssm_trace.scan_ms_per_execution(ctx.trace, ctx.config, CHUNK)
    if ms is None:
        return None
    need = ssm_flops.chunk_scan_need(ctx.config, ctx.config["assumed"]["prefill_chunk"])
    least, _ = flops.roofline_seconds(*need, ctx.peaks)
    return 100.0 * least / (ms * 1e-3)
