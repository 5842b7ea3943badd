"""Tokens per second per chip x the operations a token needs (matmuls of
the layers and the head, causal attention; forward and backward) over the
chip's bf16 peak. The rate is the window's, taken before the traced steps."""

from benchmarks.harness import counts


def read(ctx):
    if ctx.peaks is None:
        return None
    need = counts.train_flops_per_token(ctx.arch, ctx.seq_len)
    return 100.0 * ctx.e2e["train_tokens_per_s"] * need / ctx.peaks["bf16_flops"]
