"""Share of the programs the engine handed to the device (every prefill
window and decode step: `serve.dispatched`) that went out while another was
still in flight (`serve.dispatched_ahead`), whole run since the warm-up's
reset. 100 less one program a pipeline bubble where the serving loop keeps
one program ahead of the one it reads; 0 where it reads each before it
dispatches the next (a draft model); None for a program without the
counters (the parent of the PR that added them)."""


def read(ctx):
    counters = ctx.counters["counters"]
    ahead = counters.get("serve.dispatched_ahead")
    dispatched = counters.get("serve.dispatched")
    if ahead is None or not dispatched:
        return None
    return 100.0 * ahead / dispatched
