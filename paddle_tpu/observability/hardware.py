"""Chip peak tables + model-FLOPs helpers shared by bench, the
TrainingMonitor's MFU math, and the xprof report's roofline fields.

Kept dependency-free at module scope (no jax import) so importing it never
initializes a backend; `detect_*` helpers import jax only when called.
"""

from __future__ import annotations

__all__ = ["PEAK_FLOPS", "PEAK_HBM_BW", "peak_flops_for", "peak_hbm_bw_for",
           "detect_device_kind", "detect_peak_flops",
           "llama_param_count", "llama_flops_per_token"]

# peak dense bf16 FLOP/s per chip by device kind substring (vendor
# datasheets; the v5e row is Google Cloud's "TPU v5e" page)
PEAK_FLOPS = [
    ("v5 lite", 197e12), ("v5e", 197e12),
    ("v5p", 459e12),
    ("v6", 918e12), ("trillium", 918e12),
    ("v4", 275e12), ("v3", 123e12),
]

# peak HBM bandwidth (bytes/s) per chip — the decode roofline
PEAK_HBM_BW = [
    ("v5 lite", 819e9), ("v5e", 819e9),
    ("v5p", 2765e9),
    ("v6", 1640e9), ("trillium", 1640e9),
    ("v4", 1228e9), ("v3", 900e9),
]


def _lookup(kind, table):
    k = str(kind).lower()
    for sub, peak in table:
        if sub in k:
            return peak
    raise KeyError(
        f"device_kind {kind!r} is not in the peak table "
        "(paddle_tpu/observability/hardware.py); add its datasheet row — a "
        "utilization against a guessed peak is worse than none")


def peak_flops_for(kind):
    """Peak bf16 FLOP/s for a device kind; an unknown kind raises."""
    return _lookup(kind, PEAK_FLOPS)


def peak_hbm_bw_for(kind):
    """Peak HBM bytes/s for a device kind; an unknown kind raises."""
    return _lookup(kind, PEAK_HBM_BW)


def detect_device_kind():
    import jax

    return jax.devices()[0].device_kind


def detect_peak_flops():
    """Peak bf16 FLOP/s of the local chip. None off the TPU (a CPU run has
    no MFU); a TPU whose kind is not in the table raises."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    return peak_flops_for(dev.device_kind)


def llama_param_count(args):
    """Parameter count from a LlamaArgs-shaped object (hidden_size,
    intermediate_size, vocab_size, num_layers, num_heads, num_kv_heads)."""
    h, i, v, L = (args.hidden_size, args.intermediate_size, args.vocab_size,
                  args.num_layers)
    hd = h // args.num_heads
    per_layer = (h * args.num_heads * hd + 2 * h * args.num_kv_heads * hd
                 + args.num_heads * hd * h + 3 * h * i + 2 * h)
    return v * h * 2 + L * per_layer + h


def llama_flops_per_token(args, seq):
    """Training FLOPs/token: 6*N for the matmuls + causal attention
    12*L*h*s*0.5 (fwd+bwd with remat ~ an extra fwd is NOT counted: MFU is
    model FLOPs, matching the convention the A100 baselines use)."""
    n = llama_param_count(args)
    attn = 6 * args.num_layers * args.hidden_size * seq  # causal 12*L*h*s/2
    return 6 * n + attn
