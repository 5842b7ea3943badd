"""The least time the chip could take for the causal attention of the
traced steps (operations over the bf16 peak: the kernels are bound by
compute at 4,096 positions) over the flash kernels' device time."""

from benchmarks.harness import counts, reduce_trace
from benchmarks.harness.driver_train import TRACE_STEPS


def read(ctx):
    if not ctx.trace or ctx.peaks is None:
        return None
    s = reduce_trace.kernel_seconds(ctx.trace, reduce_trace.FLASH_KERNELS)
    if not s:
        return None
    need = (counts.flash_train_flops_per_seq(ctx.arch, ctx.seq_len)
            * ctx.rows * TRACE_STEPS / ctx.chips)
    return 100.0 * need / ctx.peaks["bf16_flops"] / s
