"""Mean of the engine's own `decode_live_page_share` observation, one per
decode step: the pages the step's rows hold over slots x pages a slot."""


def read(ctx):
    obs = ctx.counters["observations"].get("decode_live_page_share")
    return 100.0 * obs["mean"] if obs else None
