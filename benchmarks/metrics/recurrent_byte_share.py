"""Of the per-request memory a decode step must move, the part that is
recurrent STATE and not cache: the sum over the run's decode steps of the
engine's own `serve.state_bytes_step` (live rows times one slot's state,
read and written) over that plus the sum of `serve.cache_bytes_step` (every
live row's cached tokens times a cached row's bytes times the latent
layers). The state's part is flat in a request's length and the cache's
grows with it: at this family's 17.17 MB a request against 1,280 B a token
the two meet at a context of 26,829 tokens. None on a program that makes
neither observation."""


def read(ctx):
    obs = ctx.counters["observations"]
    state, cache = (obs.get("serve.state_bytes_step"),
                    obs.get("serve.cache_bytes_step"))
    if not state or not cache or not state["sum"] + cache["sum"]:
        return None
    return 100.0 * state["sum"] / (state["sum"] + cache["sum"])
