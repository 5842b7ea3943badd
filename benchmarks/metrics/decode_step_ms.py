"""Median host time of one `step()` that was a decode, whole run."""

from benchmarks.harness.driver_serve import median_step_ms


def read(ctx):
    return median_step_ms(ctx.spans, "decode")
