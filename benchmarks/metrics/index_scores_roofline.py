"""The least time the chip could take for the index scores the EQUATIONS
need in the traced slice (the family's `index_work`: every (query, visible
key) pair a layer, the larger of the index key's bytes over the HBM
bandwidth, a prefill window reading a key once for all its queries, and
the pair's flops over the bf16 peak) over the device time under
`pt.index_scores`, which also holds the selector's projections: a scope
that does more than the count reads low here, never over 100%."""

from benchmarks.harness import program_scopes


def read(ctx):
    share = program_scopes.share_of_busy(ctx, "pt.index_scores")
    work = getattr(ctx.family, "index_work", None)
    need = work(ctx) if share and work else None
    if not need:
        return None
    return 100.0 * need / (share / 100.0 * ctx.trace["busy_s"])
