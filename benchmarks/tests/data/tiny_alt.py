"""A second model family for the CPU tests. `tiny.py` copies this file to
`<temporary root>/benchmarks/families/tiny_alt.py`, as a later PR would add
a family's file: its own copy of a decoder's equations (attention in one
piece, no blocking), its own key names, a head size read from its own key
and never worked out from the hidden size, an init of its own for one leaf,
its own rule for the served tokens' logits, and the record of a finished
request that the rule reads. Its leaf NAMES are the program's: the engines
read the tree they are handed by name.
"""

import math
import types

import jax
import jax.numpy as jnp

from benchmarks.harness import reference

REQUEST_RECORD = "finish_reason"


def serve_args(arch):
    from paddle_tpu.models import llama_functional as lf

    if arch["heads"] * arch["head_size"] != arch["hidden_size"]:
        raise ValueError("the program's head size is hidden // heads")
    return lf.LlamaArgs(arch["vocab_size"], arch["hidden_size"],
                        arch["ffn_size"], arch["num_hidden_layers"],
                        arch["heads"], arch["kv_heads"], arch["rope_theta"],
                        arch["rms_norm_eps"])


def train_config(arch):
    from paddle_tpu.models.llama import LlamaConfig

    a = serve_args(arch)
    return LlamaConfig(
        vocab_size=a.vocab_size, hidden_size=a.hidden_size,
        intermediate_size=a.intermediate_size,
        num_hidden_layers=a.num_layers, num_attention_heads=a.num_heads,
        num_key_value_heads=a.num_kv_heads, rms_norm_eps=a.rms_eps,
        rope_theta=a.rope_theta)


def layer_shapes(arch):
    h, f, hd = arch["hidden_size"], arch["ffn_size"], arch["head_size"]
    return {"wq": (h, arch["heads"] * hd), "wk": (h, arch["kv_heads"] * hd),
            "wv": (h, arch["kv_heads"] * hd), "wo": (arch["heads"] * hd, h),
            "w_gate": (h, f), "w_up": (h, f), "w_down": (f, h),
            "ln1": (h,), "ln2": (h,)}


def leaf_init(arch):
    return {"wo": (0.0, 0.02 / math.sqrt(2 * arch["num_hidden_layers"]))}


def _norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [b, s, heads, hd], the two halves of a head rotated together."""
    s, hd = x.shape[1], x.shape[3]
    freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def decoder_layer(x, w, arch, mm):
    nh, nkv, hd = arch["heads"], arch["kv_heads"], arch["head_size"]
    eps, theta = arch["rms_norm_eps"], arch["rope_theta"]
    b, s, _ = x.shape
    hin = _norm(x, w["ln1"], eps)
    q = _rope(mm(hin, w["wq"]).reshape(b, s, nh, hd), theta)
    k = _rope(mm(hin, w["wk"]).reshape(b, s, nkv, hd), theta)
    v = mm(hin, w["wv"]).reshape(b, s, nkv, hd)
    k, v = (jnp.repeat(t, nh // nkv, axis=2) for t in (k, v))
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                    precision=reference.HIGHEST) / math.sqrt(hd)
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v,
                     precision=reference.HIGHEST).reshape(b, s, nh * hd)
    x = x + mm(att, w["wo"])
    hin = _norm(x, w["ln2"], eps)
    return x + mm(jax.nn.silu(mm(hin, w["w_gate"])) * mm(hin, w["w_up"]),
                  w["w_down"])


_SELF = types.SimpleNamespace(layer_shapes=layer_shapes, leaf_init=leaf_init,
                              decoder_layer=decoder_layer)


def served_logits(arch, seed, requests, mm):
    """This family's tokens are chosen left to right too, so its rule is the
    generic pass; it reads the record it asked for to show that it came."""
    for _, _, record in requests:
        if record != "length":
            raise RuntimeError(f"request record {record!r}, not 'length'")
    return reference.served_logits(_SELF, arch, seed, requests, mm)
