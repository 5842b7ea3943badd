"""Mean of the engine's own `slot_occupancy` observation, one per step."""


def read(ctx):
    obs = ctx.counters["observations"].get("slot_occupancy")
    return 100.0 * obs["mean"] if obs else None
