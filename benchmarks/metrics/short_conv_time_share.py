"""Device time under the scope `pt.short_conv` (the linear layers' causal
depthwise convolution over a prefill window's rows and over a decode step's,
with the move of its state) over the device's busy time in the traced slice."""

from benchmarks.harness import program_scopes


def read(ctx):
    return program_scopes.share_of_busy(ctx, "pt.short_conv")
