"""Model FLOPs of the blocks whose step program ran in the window, over
that program's summed device time, over the chip's bf16 peak, in %.

The block step is the jitted ``block`` of ``ModelTrainingWorkload``
(module ``jit_block`` in the trace).  FLOPs come from the config's
shapes (``bench/flops.py``: causal attention, no remat)."""
from bench import flops, peaks

PROGRAM = "jit_block"


def read(ctx):
    if ctx.reduction is None:
        return None
    seconds, count = ctx.reduction.program_time(PROGRAM)
    if count == 0 or seconds <= 0:
        return None
    work = flops.train_flops_per_block(ctx.cell.config, ctx.cell.traffic)
    peak = peaks.peak(ctx.device_kind)["bf16_flops_per_s"]
    return 100.0 * work * count / seconds / peak
