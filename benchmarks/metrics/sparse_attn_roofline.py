"""The least time the chip could take for the attention the EQUATIONS need
in the traced slice (the family's counts: the selected blocks only; a
prefill window's operations over the bf16 peak, a decoding row's selected K
and V over the HBM bandwidth) over the device time under
`pt.sparse_attention`. A prefill that visits every page and masks reads low
here, never over 100%."""

from benchmarks.metrics import lightning_attn_roofline


def read(ctx):
    return lightning_attn_roofline.read(ctx, "pt.sparse_attention", "sparse")
