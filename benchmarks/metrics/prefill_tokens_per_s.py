"""Prompt tokens that were computed (not prefix hits) over the host time
inside `step()` calls that were a prefill or a prefill chunk."""


def read(ctx):
    c = ctx.counters["counters"]
    t = sum(b - a for kind, a, b, _ in ctx.spans
            if kind in ("prefill", "prefill_chunk"))
    done = c.get("prompt_tokens", 0) - c.get("prefix_tokens_hit", 0)
    return done / t if t and done else None
