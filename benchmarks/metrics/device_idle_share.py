"""Share of the traced slice in which no operation ran on the device."""

from benchmarks.harness.reduce_trace import idle_share_pct


def read(ctx):
    return idle_share_pct(ctx.trace)
