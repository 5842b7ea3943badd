"""Device time under the scope `pt.sparse_attention` (a prefill window's
attention over the slot's pages under the block mask, and the paged decode
kernel over the compacted table) over the device's busy time in the traced
slice."""

from benchmarks.harness import program_scopes


def read(ctx):
    return program_scopes.share_of_busy(ctx, "pt.sparse_attention")
