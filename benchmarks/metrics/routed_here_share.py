"""Mean of the engine's own `serve.routed_here_share` observation, one per
decode step: picks that land on an expert this chip holds over all the
step's picks (one group of eight held: 12.5% where routing is level)."""


def read(ctx):
    obs = ctx.counters["observations"].get("serve.routed_here_share")
    return 100.0 * obs["mean"] if obs else None
