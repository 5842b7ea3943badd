"""Device time of the routed experts' feed-forward over the device's busy
time in the traced slice: the operations under the scope `pt.expert_ffn`
(the SwiGLU between the projections) plus XLA's grouped-matmul kernels,
`ragged-dot-*`, which the TPU compiler names itself and gives no `op_name`
(so no scope reaches them, and `unscoped_time_share` counts them): only this
family's expert layer makes them."""

import re

from benchmarks.harness import program_scopes

GROUPED_MATMUL = r"^ragged-dot"


def seconds(ctx):
    """Device seconds of the experts' feed-forward in the traced slice, or
    None where the trace holds none of the program's scopes."""
    share = program_scopes.share_of_busy(ctx, "pt.expert_ffn")
    if share is None:
        return None
    named = sum(s for n, s in ctx.trace["ops"].items()
                if re.search(GROUPED_MATMUL, n)
                and "pt.expert_ffn" not in ctx.trace["labels"].get(n, ""))
    return share / 100.0 * ctx.trace["busy_s"] + named


def read(ctx):
    s = seconds(ctx)
    return 100.0 * s / ctx.trace["busy_s"] if s else None
