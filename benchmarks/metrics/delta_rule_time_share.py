"""Device time under the scope `pt.delta_rule` (the linear layers' chunked
scan of a prefill window and their one-step update of a decode step) over
the device's busy time in the traced slice."""

from benchmarks.harness import program_scopes


def read(ctx):
    return program_scopes.share_of_busy(ctx, "pt.delta_rule")
