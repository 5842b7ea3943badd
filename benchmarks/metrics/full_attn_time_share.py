"""Device time under the scope `pt.paged_attention` (a prefill window's
attention over its slot's pages and the paged decode kernel: the full
layers' attention itself, projections apart) over the device's busy time in
the traced slice."""

from benchmarks.harness import program_scopes


def read(ctx):
    return program_scopes.share_of_busy(ctx, "pt.paged_attention")
