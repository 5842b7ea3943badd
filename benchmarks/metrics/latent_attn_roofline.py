"""The least time the chip could take for the attention the EQUATIONS need
in the traced slice (the family's counts: a decode step the larger of a
cached row's bytes a visible key over the HBM bandwidth and the absorbed
form's flops a (query, key) pair over the bf16 peak; a prefill window its
pairs' flops in the form the reference uses over the peak) over the device
time under `pt.latent_attention`. A kernel that reads a padded row or
computes a masked block reads low here, never over 100%. The numerator
counts the traced slice's own tokens (each decode token's visible keys from
the run's records, each prefill window's pairs): no run-wide mean."""

from benchmarks.harness import program_scopes


def read(ctx):
    share = program_scopes.share_of_busy(ctx, "pt.latent_attention")
    need = ctx.family.traced_work(ctx) if share else None
    if not need:
        return None
    return 100.0 * need["latent"] / (share / 100.0 * ctx.trace["busy_s"])
