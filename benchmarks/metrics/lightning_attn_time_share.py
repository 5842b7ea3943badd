"""Device time under the scope `pt.lightning_attention` (the lightning
layers' chunked scan of a prefill window and their one-step recurrence of a
decode step) over the device's busy time in the traced slice."""

from benchmarks.harness import program_scopes


def read(ctx):
    return program_scopes.share_of_busy(ctx, "pt.lightning_attention")
