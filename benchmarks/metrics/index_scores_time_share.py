"""Device time under the scope `pt.index_scores` (the token selector's
projections, the index key's norm, both rotations and the scores I(t, s) of
every (query, visible key): a decode step's kernel over the index pool, a
prefill window's blocks) over the device's busy time in the traced slice."""

from benchmarks.metrics import router_time_share


def read(ctx):
    return router_time_share.read(ctx, "pt.index_scores")
