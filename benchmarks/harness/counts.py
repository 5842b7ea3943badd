"""Operations and bytes the algorithm needs, from shapes alone.

Only what the mathematics requires is counted: no embedding lookup (a
lookup multiplies nothing), no recomputation, the causal half of attention.
`arch` is a configuration file's dict (Hugging Face key names).
"""


def head_dim(arch):
    return arch.get("head_dim") or (arch["hidden_size"]
                                    // arch["num_attention_heads"])


def layer_matmul_params(arch):
    """Weights of one decoder layer that a token is multiplied by."""
    h, i, hd = arch["hidden_size"], arch["intermediate_size"], head_dim(arch)
    nh, nkv = arch["num_attention_heads"], arch["num_key_value_heads"]
    attn = h * nh * hd + 2 * h * nkv * hd + nh * hd * h
    return attn + 3 * h * i


def matmul_params(arch):
    """Layers plus the output head; the embedding table is a lookup."""
    return (arch["num_hidden_layers"] * layer_matmul_params(arch)
            + arch["hidden_size"] * arch["vocab_size"])


def total_params(arch):
    h = arch["hidden_size"]
    return (matmul_params(arch) + arch["vocab_size"] * h
            + arch["num_hidden_layers"] * 2 * h + h)


def attn_flops_fwd(arch, seq_len):
    """Causal self-attention of ONE sequence, all layers, forward: QK^T and
    PV, each 2*hd flops per (query, visible key) pair per head; a query at
    position t sees t+1 keys."""
    pairs = seq_len * (seq_len + 1) // 2
    return (arch["num_hidden_layers"] * arch["num_attention_heads"]
            * 4 * head_dim(arch) * pairs)


def train_flops_per_token(arch, seq_len):
    """Forward + backward (2x forward) of matmuls and causal attention."""
    return 3 * (2 * matmul_params(arch)
                + attn_flops_fwd(arch, seq_len) / seq_len)


def flash_train_flops_per_seq(arch, seq_len):
    """What the flash kernels must do for one sequence in a training step:
    forward (QK^T, PV) + backward (recompute QK^T, dV, dP, dQ, dK) = 2 + 5
    matmuls of the causal half, 2*hd flops per pair each."""
    pairs = seq_len * (seq_len + 1) // 2
    return (arch["num_hidden_layers"] * arch["num_attention_heads"]
            * 7 * 2 * head_dim(arch) * pairs)


def kv_bytes_per_token(arch, itemsize=2):
    """K and V of one token over all layers."""
    return (arch["num_hidden_layers"] * 2 * arch["num_key_value_heads"]
            * head_dim(arch) * itemsize)


def weight_bytes(arch, itemsize=2):
    return total_params(arch) * itemsize
