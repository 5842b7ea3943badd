"""Pages in use at their peak over the pages of the pool."""


def read(ctx):
    g = ctx.counters["gauges"].get("pages_in_use")
    if not g:
        return None
    return 100.0 * g["max"] / ctx.engine_kw["num_pages"]
