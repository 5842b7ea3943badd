"""The least time the chip could take for the routed experts' work in the
traced slice (the family's counts: a decode step the bytes of the held
experts that a row picked, read once, over the HBM bandwidth; a prefill
window the larger of those bytes over the bandwidth and its (token, held
expert) pairs' flops over the bf16 peak) over the device time of the
experts' feed-forward (`expert_ffn_time_share`'s seconds).

The numerator's two routing numbers are RUN-WIDE means of the engine's own
observations, one sample a decode step (`serve.held_experts_hit`: held
experts a step hit, a layer; `serve.routed_here_share`: picks that land on
a held expert), not counts of the picks inside the traced slice; the
steps and windows they multiply are the traced slice's own."""

from benchmarks.metrics import expert_ffn_time_share


def read(ctx):
    s = expert_ffn_time_share.seconds(ctx)
    need = ctx.family.traced_work(ctx) if s else None
    return 100.0 * need["experts"] / s if need else None
