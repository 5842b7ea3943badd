"""Mean of the engine's own `serve.expert_load_max_over_mean` observation,
one per decode step: tokens at the busiest expert this chip holds over the
mean of the experts it holds, summed over the expert layers (1 is a level
load)."""


def read(ctx):
    obs = ctx.counters["observations"].get("serve.expert_load_max_over_mean")
    return obs["mean"] if obs else None
