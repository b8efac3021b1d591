"""Flash attention's share of its roofline in the train step: the least time
the chip could take for the causal forward + backward at this batch and
sequence (flops.py: the larger of FLOPs / peak and bytes / HBM rate) over the
device time the attention kernels took per step. The remat forward runs the
kernel a second time; that is time spent and not work needed."""

from chipbench import flops, trace_reduce

# The XLA Ops line names an event by its whole HLO instruction. The Mosaic
# (Pallas) kernels of ops/flash_pallas.py are the custom calls to
# "tpu_custom_call" that the attention module emitted (%self_attn.N = ...).
KERNELS = r"(?i)^%[\w.\-]*(attn|flash)[\w.\-]* = .*tpu_custom_call"
STEP = r"^jit_train_step"


def compute(ctx):
    if ctx.trace is None:
        return None
    kernels = ctx.trace.select(trace_reduce.OPS_LINE, KERNELS)
    steps = ctx.trace.select(trace_reduce.MODULES_LINE, STEP)
    if not kernels or not steps:
        return None
    per_step = sum(e.dur_ns for e in kernels) * 1e-9 / len(steps)
    batch, seq = ctx.traffic["batch"], ctx.traffic["seq_len"]
    least, _ = flops.roofline_seconds(flops.flash_train_flops(ctx.config, batch, seq),
                                      flops.flash_train_bytes(ctx.config, batch, seq), ctx.peaks)
    return 100.0 * least / per_step
