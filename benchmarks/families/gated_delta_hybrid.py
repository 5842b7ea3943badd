"""Olmo-Hybrid's stack: LINEAR layers (the gated delta rule of Gated Delta
Networks, arXiv:2412.06464, behind a short causal convolution) beside FULL
layers (multi-head softmax attention, every head its own K and V), every
layer normed AFTER its sublayers, SwiGLU feed-forward. What the harness
knows of it (`harness/spec.py`):

  serve_args      the program's static description (`GatedDeltaArgs`)
  layer_kinds     the configuration's `layer_types`: the two kinds differ IN
                  THEIR LEAVES, one stack a kind
  layer_shapes    / leaf_init, keyed by kind
  decoder_layer   the plain layer, told its kind
  served_logits   the plain float32 forward of each served request, in
                  blocks of fixed shapes: one program a kind of call whatever
                  the request's length
  the counts of its readers (`traced_work` and below)

THE EQUATIONS the reference is written from (x_t the residual stream):
every layer is x += RMSNorm_w(Mixer(x)); x += RMSNorm_w(SwiGLU(x)), SwiGLU =
W_down(silu(W_gate x) * W_up x), no bias. Input embedding[ids]; output
lm_head(RMSNorm_w(x)), untied.

  linear_attention  H heads, key width dk, value width dv. u_t = [Wq x_t; Wk
             x_t; Wv x_t]; c_t = silu(sum_{i=0..K-1} w_conv[:, i] *
             u_{t-K+1+i}) (depthwise, causal, zeros before position 0),
             split into q~, k~ (H x dk) and v (H x dv); per head q_t = q~ /
             |q~|_2 * dk^-1/2, k_t = k~ / |k~|_2; b_t = 2 sigmoid(Wb x_t);
             a_t = exp(-exp(A_log) * softplus(Wa x_t + dt_bias)). Per head,
             S_0 = 0 in R^{dk x dv}:
                 S_t = a_t (I - b_t k_t k_t^T) S_{t-1} + b_t k_t v_t^T
                 o_t = S_t^T q_t
             out = Wo concat_h(RMSNorm_{w_o}(o_t) * silu(Wg x_t)_h), the norm
             per head over its dv values with a learned weight [dv].
  full_attention    H heads = H KV heads of width d: q = RMSNorm_w(Wq x), k =
             RMSNorm_w(Wk x) over the whole projection, v = Wv x; causal
             softmax(q k^T / sqrt(d)) v per head; Wo.

ASSUMED (the published config.json carries none of these; the configuration
file lists them too, each with this reason):
  (1) the block's shape, a norm on each sublayer's OUTPUT and none before
      it, is the Olmo 2 / 3 family's; the config has no key for it;
  (2) the q / k norm over the whole projection (not a head), likewise;
  (3) NO rotary embedding in the full layers: `rope_parameters.rope_theta`
      is null in the published config, so there is no base to rotate by;
  (4) the leaves' seeded ranges (`leaf_init`): the mechanism's paper draws
      A_log = log U(1, 16) and dt_bias = softplus^-1(exp U(log 1e-3, log
      1e-1)); the harness's `leaf_init` states a normal's (mean, std), so
      each is the normal of that draw's mean and spread (1.96 +- 0.67; -4.6
      +- 1.33); the convolution's weight is normal(0, 0.5), wide enough that
      four taps of it carry the projections' scale and the fp8 control
      still fails. head_dim 128 = 3840 / 30.

Nothing here is the program's: `jax.numpy`, float32, matmul precision
`highest`, no kernel, no cache, no batching; the recurrence is a scan over
tokens, written as above. Every weight matrix goes through `mm` (the
control swaps it for fp8).
"""

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import weights
from benchmarks.harness.reference import (HIGHEST, f32_mm, pad_rows,
                                          rms_norm, served_rows)

LINEAR, FULL = "linear_attention", "full_attention"
T_BLOCK = 1024      # tokens a call takes: a sequence comes padded to whole
                    # blocks, so every call has one shape
K_BUCKET = 2048     # a query block sees its keys padded up to a multiple: at
                    # most max_context / K_BUCKET key counts, so that many
                    # programs, whatever the requests' lengths


# -- the program's side: imported here and nowhere in the reference ----------

def serve_args(arch):
    from paddle_tpu.models import gated_delta_functional as gdf

    return gdf.GatedDeltaArgs(
        vocab_size=arch["vocab_size"], hidden_size=arch["hidden_size"],
        intermediate_size=arch["intermediate_size"],
        num_heads=arch["num_attention_heads"], head_dim=head_dim(arch),
        linear_heads=arch["linear_num_value_heads"],
        linear_key_dim=arch["linear_key_head_dim"],
        linear_value_dim=arch["linear_value_head_dim"],
        conv_kernel=arch["linear_conv_kernel_dim"],
        layer_kinds=tuple(arch["layer_types"]),
        rms_eps=arch["rms_norm_eps"])


# -- the weights ---------------------------------------------------------------

def head_dim(arch):
    return arch["hidden_size"] // arch["num_attention_heads"]


def layer_kinds(arch):
    return tuple(arch["layer_types"])


def _widths(arch):
    """(H, dk, dv, K, conv channels) of a linear layer."""
    if arch["linear_num_key_heads"] != arch["linear_num_value_heads"]:
        raise ValueError("key heads and value heads of a linear layer are "
                         "one count in this family")
    H, dk, dv = (arch["linear_num_value_heads"], arch["linear_key_head_dim"],
                 arch["linear_value_head_dim"])
    return H, dk, dv, arch["linear_conv_kernel_dim"], H * (2 * dk + dv)


def layer_shapes(arch):
    h, i = arch["hidden_size"], arch["intermediate_size"]
    H, dk, dv, K, C = _widths(arch)
    ffn = {"w_gate": (h, i), "w_up": (h, i), "w_down": (i, h),
           "ln1": (h,), "ln2": (h,)}
    return {
        LINEAR: {"wq": (h, H * dk), "wk": (h, H * dk), "wv": (h, H * dv),
                 "wg": (h, H * dv), "wo": (H * dv, h), "wa": (h, H),
                 "wb": (h, H), "conv_w": (C, K), "A_log": (H,),
                 "dt_bias": (H,), "o_norm": (dv,), **ffn},
        FULL: {"wq": (h, h), "wk": (h, h), "wv": (h, h), "wo": (h, h),
               "q_norm": (h,), "k_norm": (h,), **ffn}}


def leaf_init(arch):
    """ASSUMED (4). The matrices take `initializer_range` where the
    configuration states one (the tests' toy presets do, to make their
    attention sharp); the published file states none, so the real
    configuration takes the harness's rules for them."""
    out = {LINEAR: {"A_log": (1.96, 0.67), "dt_bias": (-4.6, 1.33),
                    "conv_w": (0.0, 0.5)}, FULL: {}}
    std = arch.get("initializer_range")
    if std is not None:
        for kind, shapes in layer_shapes(arch).items():
            out[kind].update({n: (0.0, std) for n, s in shapes.items()
                              if len(s) == 2 and n != "conv_w"})
    return out


def param_count(arch):
    """Parameters of the configuration as it is run: the layers by kind,
    embedding, final norm and head."""
    per_kind = {kind: sum(math.prod(s) for s in shapes.values())
                for kind, shapes in layer_shapes(arch).items()}
    outer = 2 * arch["vocab_size"] * arch["hidden_size"] + arch["hidden_size"]
    return sum(per_kind[k] for k in layer_kinds(arch)) + outer


# -- the plain layers ------------------------------------------------------------

def conv_input(x, w, mm):
    """u [s, channels] = [Wq x; Wk x; Wv x]."""
    return jnp.concatenate([mm(x, w["wq"]), mm(x, w["wk"]), mm(x, w["wv"])],
                           axis=-1)


def short_conv(u, before, conv_w):
    """c_t = silu(sum_i w[:, i] u_{t-K+1+i}); `before` [K - 1, channels] the
    rows just before u's first (zeros before position 0)."""
    K, s = conv_w.shape[1], u.shape[0]
    ext = jnp.concatenate([before, u])
    return jax.nn.silu(sum(conv_w[:, i] * ext[i:i + s] for i in range(K)))


def delta_operands(x, c, w, arch, mm):
    """q, k [s, H, dk], v [s, H, dv], a, b [s, H] of the equations."""
    H, dk, dv, _, _ = _widths(arch)
    s = x.shape[0]
    q = c[:, :H * dk].reshape(s, H, dk)
    k = c[:, H * dk:2 * H * dk].reshape(s, H, dk)
    v = c[:, 2 * H * dk:].reshape(s, H, dv)
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / math.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    b = 2.0 * jax.nn.sigmoid(mm(x, w["wb"]))
    a = jnp.exp(-jnp.exp(w["A_log"])
                * jax.nn.softplus(mm(x, w["wa"]) + w["dt_bias"]))
    return q, k, v, a, b


def delta_scan(q, k, v, a, b, S):
    """The recurrence, a token a step, as the equations have it. S [H, dk,
    dv]. Returns (o [s, H, dv], S after the last token)."""
    def step(S, x):
        qt, kt, vt, at, bt = x
        kS = jnp.einsum("hk,hkv->hv", kt, S, precision=HIGHEST)
        S = (at[:, None, None] * (S - bt[:, None, None] * kt[:, :, None]
                                  * kS[:, None, :])
             + bt[:, None, None] * kt[:, :, None] * vt[:, None, :])
        return S, jnp.einsum("hk,hkv->hv", qt, S, precision=HIGHEST)

    S, o = jax.lax.scan(step, S, (q, k, v, a, b), unroll=8)
    return o, S


def linear_out(x, o, w, arch, mm):
    """Wo concat_h(RMSNorm_{w_o}(o) * silu(Wg x)_h)."""
    s = x.shape[0]
    o = rms_norm(o, w["o_norm"], arch["rms_norm_eps"]).reshape(s, -1)
    return mm(o * jax.nn.silu(mm(x, w["wg"])), w["wo"])


def full_qkv(x, w, arch, mm):
    H, d, eps = arch["num_attention_heads"], head_dim(arch), \
        arch["rms_norm_eps"]
    s = x.shape[0]
    q = rms_norm(mm(x, w["wq"]), w["q_norm"], eps).reshape(s, H, d)
    k = rms_norm(mm(x, w["wk"]), w["k_norm"], eps).reshape(s, H, d)
    return q, k, mm(x, w["wv"]).reshape(s, H, d)


def causal_attention(q, qpos, k, v):
    """q [n, H, d] at positions qpos [n] over k, v [m, H, d] of positions 0
    .. m - 1 (rows past a query's position are never read)."""
    d = q.shape[-1]
    sc = jnp.einsum("nhd,mhd->hnm", q, k, precision=HIGHEST) / math.sqrt(d)
    see = jnp.arange(k.shape[0])[None, :] <= qpos[:, None]
    p = jax.nn.softmax(jnp.where(see[None], sc, -jnp.inf), axis=-1)
    return jnp.einsum("hnm,mhd->nhd", p, v, precision=HIGHEST)


def after(x, mixed, w, arch, mm):
    """The two residuals, each sublayer normed AFTER."""
    eps = arch["rms_norm_eps"]
    x = x + rms_norm(mixed, w["ln1"], eps)
    act = jax.nn.silu(mm(x, w["w_gate"])) * mm(x, w["w_up"])
    return x + rms_norm(mm(act, w["w_down"]), w["ln2"], eps)


def decoder_layer(x, w, arch, mm, kind):
    """One whole layer over sequences x [b, s, h] from position 0."""
    H, dk, dv, K, C = _widths(arch)

    def one(x1):
        s = x1.shape[0]
        if kind == LINEAR:
            c = short_conv(conv_input(x1, w, mm), jnp.zeros((K - 1, C)),
                           w["conv_w"])
            q, k, v, a, b = delta_operands(x1, c, w, arch, mm)
            o, _ = delta_scan(q, k, v, a, b, jnp.zeros((H, dk, dv)))
            mixed = linear_out(x1, o, w, arch, mm)
        else:
            q, k, v = full_qkv(x1, w, arch, mm)
            attn = causal_attention(q, jnp.arange(s), k, v)
            mixed = mm(attn.reshape(s, -1), w["wo"])
        return after(x1, mixed, w, arch, mm)

    return jax.lax.map(one, x)


# -- a served model: logits at the served positions --------------------------------

def _f32(w):
    return jax.tree.map(lambda a: a.astype(jnp.float32), w)


def _frozen(arch):
    return json.dumps({k: arch[k] for k in (
        "hidden_size", "num_attention_heads", "rms_norm_eps",
        "linear_num_key_heads", "linear_num_value_heads",
        "linear_key_head_dim", "linear_value_head_dim",
        "linear_conv_kernel_dim")}, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _linear_block_fn(frozen, mm):
    """One block of tokens through a linear layer, carrying the last K - 1
    rows of the convolution's input and the matrix state."""
    arch = json.loads(frozen)

    def block(x, w, before, S):
        w = _f32(w)
        u = conv_input(x, w, mm)
        c = short_conv(u, before, w["conv_w"])
        q, k, v, a, b = delta_operands(x, c, w, arch, mm)
        o, S = delta_scan(q, k, v, a, b, S)
        x = after(x, linear_out(x, o, w, arch, mm), w, arch, mm)
        return x, u[-before.shape[0]:], S

    return jax.jit(block)


@functools.lru_cache(maxsize=None)
def _full_qkv_fn(frozen, mm):
    arch = json.loads(frozen)
    return jax.jit(lambda x, w: full_qkv(x, _f32(w), arch, mm))


@functools.lru_cache(maxsize=None)
def _full_block_fn(frozen, mm):
    arch = json.loads(frozen)

    def block(x, w, q, qpos, k, v):
        w = _f32(w)
        attn = causal_attention(q, qpos, k, v)
        return after(x, mm(attn.reshape(x.shape[0], -1), w["wo"]), w, arch,
                     mm)

    return jax.jit(block)


@functools.lru_cache(maxsize=None)
def _head_fn(eps, mm):
    def head(x, norm_w, head_w):
        return mm(rms_norm(x, norm_w.astype(jnp.float32), eps),
                  head_w.astype(jnp.float32))

    return jax.jit(head)


def _blocks(n, size):
    return [(a, min(a + size, n)) for a in range(0, n, size)]


def layer_forward(x, w, arch, mm, kind):
    """One layer over one sequence x [s, h] (s whole blocks of `T_BLOCK`),
    block by block: every jitted call has one of a few fixed shapes whatever
    the sequence's length. Rows past the sequence's tokens sit after
    everything they could influence."""
    fz, s = _frozen(arch), x.shape[0]
    tb = min(T_BLOCK, s)
    H, dk, dv, K, C = _widths(arch)
    out = []
    if kind == LINEAR:
        before = jnp.zeros((K - 1, C), jnp.float32)
        S = jnp.zeros((H, dk, dv), jnp.float32)
        for a, b in _blocks(s, tb):
            y, before, S = _linear_block_fn(fz, mm)(x[a:b], w, before, S)
            out.append(y)
        return jnp.concatenate(out)
    parts = [_full_qkv_fn(fz, mm)(x[a:b], w) for a, b in _blocks(s, tb)]
    q, k, v = (jnp.concatenate(p) for p in zip(*parts))
    # rows past a query's position are never read: the key counts are the
    # buckets' alone
    bucket = K_BUCKET if s > K_BUCKET else s
    k, v = pad_rows(bucket, k, v)
    for a, b in _blocks(s, tb):
        m = -(-b // bucket) * bucket
        out.append(_full_block_fn(fz, mm)(x[a:b], w, q[a:b],
                                          jnp.arange(a, b), k[:m], v[:m]))
    return jnp.concatenate(out)


def _embedded(ids, embedding):
    """embedding[ids] in float32, ids padded with zeros to whole blocks."""
    padded = np.zeros(-(-len(ids) // T_BLOCK) * T_BLOCK, np.int32)
    padded[:len(ids)] = ids
    return embedding[jnp.asarray(padded)].astype(jnp.float32)


def forward_hidden(arch, ids, layer_weights, embedding, mm=f32_mm):
    """ids [n] -> the last layer's output [n, h]: the whole model's plain
    forward over one sequence. `layer_weights(i)` gives layer i's leaves."""
    n = len(ids)
    x = _embedded(ids, embedding)
    for i, kind in enumerate(layer_kinds(arch)):
        x = layer_forward(x, layer_weights(i), arch, mm, kind)
    return x[:n]


def head_logits(arch, x, final_norm, lm_head, mm=f32_mm):
    return _head_fn(arch["rms_norm_eps"], mm)(x, final_norm, lm_head)


def served_logits(arch, seed, requests, mm=f32_mm):
    """For each (prompt, tokens, ...) of `requests`, the reference logits
    [len(tokens), vocab] at the positions where the server chose `tokens`
    after `prompt`. Layer by layer over all the requests, so each layer's
    weights are made once."""
    outer = weights.outer_params(arch, seed)
    xs, sizes = [], []
    for prompt, tokens, *_ in requests:
        seq = np.concatenate([np.asarray(prompt), np.asarray(tokens)[:-1]])
        xs.append(_embedded(seq, outer["embedding"]))
        sizes.append((len(prompt), len(tokens)))
    for i, kind in enumerate(layer_kinds(arch)):
        w = weights.layer_params(_LEAVES, arch, seed, i)
        for j, x in enumerate(xs):
            xs[j] = layer_forward(x, w, arch, mm, kind)
    return [served_rows(
        lambda rows: head_logits(arch, rows, outer["final_norm"],
                                 outer["lm_head"], mm), x, n, m)
        for x, (n, m) in zip(xs, sizes)]


class _LEAVES:
    """What `weights.layer_params` asks of a family (this file is loaded
    by its path and is in no `sys.modules`)."""

    layer_kinds = staticmethod(layer_kinds)
    layer_shapes = staticmethod(layer_shapes)
    leaf_init = staticmethod(leaf_init)


# -- the counts of this family's readers ------------------------------------------

def _layers(arch, kind):
    return sum(1 for k in layer_kinds(arch) if k == kind)


def delta_flops_per_token(arch, chunk=64):
    """The chunked form's operations a prefill token, every head and linear
    layer, by the equations of `kernels/gated_delta_rule.py`'s docstring at
    a chunk of C tokens: K K^T and Q K^T (2 C dk each), the triangular
    solve for U's two right-hand sides ((I + N)^-1 applied to V and to e^G
    K: C (dk + dv), the substitution's own count), W_k S, Q S and the
    state's update K^T U (2 dk dv each), A U (2 C dv)."""
    H, dk, dv, _, _ = _widths(arch)
    C = chunk
    per_head = (2 * 2 * C * dk + C * (dk + dv) + 3 * 2 * dk * dv
                + 2 * C * dv)
    return _layers(arch, LINEAR) * H * per_head


def delta_state_bytes(arch):
    """One request's float32 matrix state read once and written once, every
    linear layer: what a decode step must move for a row."""
    H, dk, dv, _, _ = _widths(arch)
    return _layers(arch, LINEAR) * H * dk * dv * 4 * 2


def traced_work(ctx):
    """The least seconds the chip could take for the work the EQUATIONS
    need under `pt.delta_rule` in the traced slice, {"delta": s}: a prefill
    token's chunk-form operations over the bf16 peak (and its window's state
    read and written once), a decoding row's state read once and written
    once over the HBM bandwidth. Prefill windows are rebuilt from the run's
    own records: the engine streams prompts in the order they were
    submitted, a `prefill_chunk` of tokens a step, no prefix hit on an
    unshared mix. None where that cannot be rebuilt (or on a run with no
    device trace)."""
    if not ctx.trace or ctx.peaks is None:
        return None
    arch, chunk = ctx.arch, ctx.engine_kw["prefill_chunk"]
    t0, t1 = ctx.trace_host_window
    flops, bw = ctx.peaks["bf16_flops"], ctx.peaks["hbm_bytes_per_s"]
    recs = sorted((r for r in ctx.run.recs.values()
                   if r.submitted is not None), key=lambda r: r.rid)
    windows = [(a, min(a + chunk, len(r.prompt)), len(r.prompt))
               for r in recs for a in range(0, len(r.prompt), chunk)]
    steps = [s for s in ctx.spans if s[0] in ("prefill", "prefill_chunk")]
    need = 0.0
    for (kind, a, _, _), (lo, hi, n) in zip(steps, windows):
        if (kind == "prefill") != (hi == n):
            return None           # the order is not the one assumed
        if t0 <= a < t1:
            need += ((hi - lo) * delta_flops_per_token(arch) / flops
                     + delta_state_bytes(arch) / bw)
    for r in recs:
        for j, t in enumerate(r.times):
            if j and t0 <= t < t1:     # the j-th token came from a decode
                need += delta_state_bytes(arch) / bw
    return {"delta": need}
