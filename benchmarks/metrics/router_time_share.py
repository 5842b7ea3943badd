"""Device time under the scope `pt.moe_route` (the router's scores, the
groups, both top-k steps, the sort by expert, the gather of the dispatched
rows and the weighted combine) over the device's busy time in the traced
slice."""

from benchmarks.harness import program_scopes


def read(ctx, scope="pt.moe_route"):
    return program_scopes.share_of_busy(ctx, scope) or None
