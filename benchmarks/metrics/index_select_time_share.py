"""Device time under the scope `pt.index_select` (the `index_topk` largest
scores of every query: a decode step's top-k, a prefill window's k-th
largest found bit by bit) over the device's busy time in the traced slice."""

from benchmarks.metrics import router_time_share


def read(ctx):
    return router_time_share.read(ctx, "pt.index_select")
