"""Device time of the flash-attention kernels (forward, dq, dkv) over the
device's busy time in the traced steps."""

from benchmarks.harness import reduce_trace


def read(ctx):
    if not ctx.trace:
        return None
    s = reduce_trace.kernel_seconds(ctx.trace, reduce_trace.FLASH_KERNELS)
    return 100.0 * s / ctx.trace["busy_s"] if s else None
