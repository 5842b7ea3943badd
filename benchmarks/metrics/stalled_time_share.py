"""Share of the engine's step time lost to stalled steps: the counter
`serve.stalled_s` (a stalled step's seconds past the mean of its own kind
and size) over the four phase sums, whole run since the warm-up's reset.
0 in a clean run; ~10 in a window that holds one 2.9 s step."""

from benchmarks.harness import program_scopes


def read(ctx):
    stalled = ctx.counters["counters"].get("serve.stalled_s")
    obs = ctx.counters["observations"]
    total = sum((obs.get(p + "_s") or {}).get("sum", 0.0)
                for p in program_scopes.PHASES)
    if stalled is None or not total:
        return None       # a program without the stall counters
    return 100.0 * stalled / total
