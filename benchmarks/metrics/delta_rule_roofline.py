"""The least time the chip could take for the delta rule's work in the
traced slice (the family's counts: the chunked form's operations a prefill
token over the bf16 peak, the float32 matrix state read once and written
once a decoding row and a window over the HBM bandwidth) over the device
time under `pt.delta_rule`. A step that makes more than one pass over the
state, or keeps it in a padded layout, reads low here, never over 100%."""

from benchmarks.harness import program_scopes


def read(ctx):
    share = program_scopes.share_of_busy(ctx, "pt.delta_rule")
    need = ctx.family.traced_work(ctx) if share else None
    if not need:
        return None
    return 100.0 * need["delta"] / (share / 100.0 * ctx.trace["busy_s"])
