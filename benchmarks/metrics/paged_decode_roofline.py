"""The least time the chip could take to read the K and V that the traced
decode steps' contexts hold (bytes over the HBM bandwidth: one query per
sequence is bound by memory) over the paged decode kernel's device time.
A token emitted by a decode step as the j-th of its request read a context
of prompt + j positions."""

from benchmarks.harness import counts, reduce_trace


def read(ctx):
    if not ctx.trace or ctx.peaks is None:
        return None
    s = reduce_trace.kernel_seconds(
        ctx.trace, reduce_trace.PAGED_DECODE_KERNEL)
    t0, t1 = ctx.trace_host_window
    positions = sum(len(r.prompt) + j
                    for r in ctx.run.recs.values()
                    for j, t in enumerate(r.times) if j and t0 <= t < t1)
    if not s or not positions:
        return None
    need = positions * counts.kv_bytes_per_token(ctx.arch)
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / s
