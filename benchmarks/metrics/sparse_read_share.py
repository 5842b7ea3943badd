"""Mean of the engine's own `sparse_read_share` observation, one per decode
step: the pages a sparse layer's KV head reads (a selection past dense_len,
every page before) over the pages the step's rows hold."""


def read(ctx):
    obs = ctx.counters["observations"].get("sparse_read_share")
    return 100.0 * obs["mean"] if obs else None
