"""Device time under the scope `pt.latent_attention` (the attention core
over the latent pool: the absorbed decode kernel, and a prefill window's
attention in blocks over its decompressed keys) over the device's busy time
in the traced slice."""

from benchmarks.metrics import router_time_share


def read(ctx):
    return router_time_share.read(ctx, "pt.latent_attention")
