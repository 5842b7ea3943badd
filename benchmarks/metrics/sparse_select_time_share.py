"""Device time under the scope `pt.sparse_select` (gathering a slot's
compressed keys, scoring them, the top-k and the compacted block table) over
the device's busy time in the traced slice."""

from benchmarks.harness import program_scopes


def read(ctx):
    return program_scopes.share_of_busy(ctx, "pt.sparse_select")
