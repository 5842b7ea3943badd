"""The least time the chip could take for the lightning layers' work in the
traced slice (the family's counts: the scan's operations a prefill token
over the bf16 peak, the state read and written once a decoding row over the
HBM bandwidth) over the device time under `pt.lightning_attention`."""

from benchmarks.harness import program_scopes


def read(ctx, scope="pt.lightning_attention", kind="lightning"):
    share = program_scopes.share_of_busy(ctx, scope)
    need = ctx.family.traced_work(ctx) if share else None
    if not need:
        return None
    return 100.0 * need[kind] / (share / 100.0 * ctx.trace["busy_s"])
