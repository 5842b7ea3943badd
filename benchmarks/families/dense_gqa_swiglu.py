"""The dense decoder block: grouped-query attention with rotary positions,
RMS norms before attention and feed-forward, a SwiGLU feed-forward. What
the harness knows of it and of nothing else (`harness/spec.py` says what a
family's file holds):

  serve_args, train_config   the program's static description of the model
  layer_shapes               one layer's leaves, under the names the
                             program's entry points read them by
  decoder_layer              the plain layer, from the published equations

It states no init of its own (`leaf_init`), its tokens are chosen left to
right (`reference.served_logits` serves), and its readers take their counts
from `harness/counts.py`. `arch` is a configuration file's dict (Hugging
Face key names).
"""

import math

import jax
import jax.numpy as jnp

from benchmarks.harness.counts import head_dim
from benchmarks.harness.reference import HIGHEST, Q_BLOCK, rms_norm


# -- the program's side: imported here and nowhere in the reference ----------

def serve_args(arch):
    from paddle_tpu.models import llama_functional as lf

    return lf.LlamaArgs(
        vocab_size=arch["vocab_size"], hidden_size=arch["hidden_size"],
        intermediate_size=arch["intermediate_size"],
        num_layers=arch["num_hidden_layers"],
        num_heads=arch["num_attention_heads"],
        num_kv_heads=arch["num_key_value_heads"],
        rope_theta=arch["rope_theta"], rms_eps=arch["rms_norm_eps"])


def train_config(arch):
    from paddle_tpu.models.llama import LlamaConfig

    return LlamaConfig(
        vocab_size=arch["vocab_size"], hidden_size=arch["hidden_size"],
        intermediate_size=arch["intermediate_size"],
        num_hidden_layers=arch["num_hidden_layers"],
        num_attention_heads=arch["num_attention_heads"],
        num_key_value_heads=arch["num_key_value_heads"],
        rms_norm_eps=arch["rms_norm_eps"], rope_theta=arch["rope_theta"])


# -- the weights ---------------------------------------------------------------

def layer_shapes(arch):
    h, i, hd = arch["hidden_size"], arch["intermediate_size"], head_dim(arch)
    nh, nkv = arch["num_attention_heads"], arch["num_key_value_heads"]
    return {"wq": (h, nh * hd), "wk": (h, nkv * hd), "wv": (h, nkv * hd),
            "wo": (nh * hd, h), "w_gate": (h, i), "w_up": (h, i),
            "w_down": (i, h), "ln1": (h,), "ln2": (h,)}


# -- the plain layer -------------------------------------------------------------

def rotary(x, theta):
    """x [s, heads, hd]; pairs (i, i + hd/2) rotate by pos * theta^(-2i/hd)."""
    s, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v):
    """One sequence. q [s, nh, hd], k/v [s, nkv, hd]; query head j reads
    key/value head j // (nh/nkv). Queries go in blocks of Q_BLOCK."""
    s, nh, hd = q.shape
    nkv = k.shape[1]
    qg = q.reshape(s, nkv, nh // nkv, hd)
    blk = min(Q_BLOCK, s)
    if s % blk:
        raise ValueError(f"sequence {s} is not a multiple of {blk}")

    @jax.checkpoint
    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qg, i * blk, blk, 0)
        sc = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HIGHEST)
        sc = sc / math.sqrt(hd)
        qpos = i * blk + jnp.arange(blk)[:, None]
        sc = jnp.where(jnp.arange(s)[None, :] <= qpos, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(s // blk))
    return out.reshape(s, nh * hd)


def decoder_layer(x, w, arch, mm):
    """x [b, s, h] and w, one layer's weights, float32; every weight goes
    through `mm`."""
    hd, eps = head_dim(arch), arch["rms_norm_eps"]
    nh, nkv = arch["num_attention_heads"], arch["num_key_value_heads"]
    theta = arch["rope_theta"]
    b, s, _ = x.shape
    hin = rms_norm(x, w["ln1"], eps)
    q = mm(hin, w["wq"]).reshape(b, s, nh, hd)
    k = mm(hin, w["wk"]).reshape(b, s, nkv, hd)
    v = mm(hin, w["wv"]).reshape(b, s, nkv, hd)

    def one(qkv):
        q1, k1, v1 = qkv
        return causal_attention(rotary(q1, theta), rotary(k1, theta), v1)

    attn = jax.lax.map(one, (q, k, v))
    x = x + mm(attn, w["wo"])
    hin = rms_norm(x, w["ln2"], eps)
    act = jax.nn.silu(mm(hin, w["w_gate"])) * mm(hin, w["w_up"])
    return x + mm(act, w["w_down"])
