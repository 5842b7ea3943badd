"""MiniCPM-SALA's hybrid stack: LIGHTNING layers (linear attention with a
per-head decay and a recurrent state) beside SPARSE layers (InfLLM-v2:
grouped-query softmax attention without rotary positions that, past
`dense_len`, attends only the blocks a selector picks from compressed keys),
every layer with q/k norms, an output gate, a SwiGLU feed-forward and muP
scalings. What the harness knows of it (`harness/spec.py`):

  serve_args      the program's static description (`HybridArgs`)
  layer_shapes    ONE set of leaves, the lightning layer's; a sparse layer
                  reads the leading nkv*d columns of the same wk / wv and no
                  o_norm (189 MB of the 12-layer tree unread)
  decoder_layer   the plain layer, kind by index
  served_logits   the plain float32 forward of each served request, in
                  blocks so that 49k positions fit
  the counts of its readers (`traced_work` and below)

THE EQUATIONS the reference is written from (h = RMSNorm(x), a =
scale_depth / sqrt(published layer count)): every layer is x += a *
Mixer(h); x += a * SwiGLU(RMSNorm(x)). Input: embedding[ids] * scale_emb.
Output: lm_head(RMSNorm(x) / (hidden_size / dim_model_base)).

  lightning  q, k, v = h Wq, h Wk, h Wv; per head an RMS norm with a learned
             weight on q and on k, then RoPE on q and k. Per head, S_0 = 0:
             S_t = lambda_h S_{t-1} + k_t v_t^T, o_t = d^-1/2 q_t^T S_t,
             lambda_h = exp(-s_h). out = (RMSNorm(concat_h o_t) *
             sigmoid(h Wg)) Wo.
  sparse     H query heads in nkv groups, one KV head a group, NO RoPE, the
             same q/k norms, out = (attn * sigmoid(h Wg)) Wo. For the query
             at position t (n = t + 1), per KV head: n <= dense_len: causal
             softmax attention over all n positions. Else: kbar_j = mean of
             the normed keys of [stride*j, stride*j + kernel) for every j
             with stride*j + kernel <= n; p^a = softmax_j(q_a . kbar_j /
             sqrt(d)) for each head a of the group, s_j = sum_a p^a_j;
             block b scores the max of s_j over the kernels that overlap
             it; selected: the first `init_blocks`, the `window / block`
             newest (the query's own included) and the best-scored others,
             `topk` in all; causal softmax attention over the positions <= t
             of the selected blocks, real K and V.

ASSUMED (the published config.json carries none of these; the configuration
file lists them too): s_h = 2^(-8 (h + 1) / H), Lightning Attention-2's
slopes, the same in every layer; sigmoid as the gates' activation; the
sparse sizes of MiniCPM4's published `sparse_config` (block 64, kernel 32,
stride 16, top-k 64, 1 init block, window 2048, dense_len 8192); the rule
applied PER QUERY by its own n (the published code switches on the length
of the call's context, which would depend on how a prompt was chunked).

Nothing here is the program's: `jax.numpy`, float32, matmul precision
`highest`, no kernel, no cache; the recurrence is a scan over tokens, the
selection is written as above. Every weight goes through `mm` (the control
swaps it for fp8).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import weights
from benchmarks.harness.reference import (HIGHEST, f32_mm, pad_rows,
                                          rms_norm, served_rows)

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
T_BLOCK = 1024      # tokens a projection / feed-forward call takes
Q_SPARSE = 128      # queries a sparse layer attends at once
K_BUCKET = 16384    # a query block sees its keys padded up to a multiple:
                    # at most four key counts, so four programs, whatever
                    # the requests' lengths (PR 36; 8,192 and the request's
                    # own length before: a program a length)


# -- the program's side: imported here and nowhere in the reference ----------

def serve_args(arch):
    from paddle_tpu.kernels.sparse_attention import SparseConfig
    from paddle_tpu.models import hybrid_functional as hf

    sp = arch["sparse_config"]
    kinds = tuple(hf.SPARSE if m == SPARSE else hf.LIGHTNING
                  for m in arch["mixer_types"])
    return hf.HybridArgs(
        vocab_size=arch["vocab_size"], hidden_size=arch["hidden_size"],
        intermediate_size=arch["intermediate_size"],
        num_heads=arch["num_attention_heads"], head_dim=arch["head_dim"],
        sparse_kv_heads=arch["num_key_value_heads"], layer_kinds=kinds,
        rope_theta=float(arch["rope_theta"]), rms_eps=arch["rms_norm_eps"],
        scale_emb=float(arch["scale_emb"]),
        residual_scale=arch["scale_depth"] / math.sqrt(
            published_layers(arch)),
        logit_divisor=arch["hidden_size"] / arch["dim_model_base"],
        sparse=SparseConfig(
            block_size=sp["block_size"], kernel_size=sp["kernel_size"],
            kernel_stride=sp["kernel_stride"], topk=sp["topk"],
            init_blocks=sp["init_blocks"],
            local_blocks=sp["window_size"] // sp["block_size"],
            dense_len=sp["dense_len"]))


# -- the weights ---------------------------------------------------------------

def layer_shapes(arch):
    h, i = arch["hidden_size"], arch["intermediate_size"]
    w = arch["num_attention_heads"] * arch["head_dim"]
    d = arch["head_dim"]
    return {"wq": (h, w), "wk": (h, w), "wv": (h, w), "wg": (h, w),
            "wo": (w, h), "w_gate": (h, i), "w_up": (h, i), "w_down": (i, h),
            "ln1": (h,), "ln2": (h,), "q_norm": (d,), "k_norm": (d,),
            "o_norm": (w,)}


def leaf_init(arch):
    """The matrices' init where the configuration states one
    (`initializer_range`: the toy presets of the tests do, to make their
    attention sharp); the published file states none, so the real
    configuration takes the harness's rules."""
    std = arch.get("initializer_range")
    if std is None:
        return {}
    return {name: (0.0, std) for name, shape in layer_shapes(arch).items()
            if len(shape) == 2}


def published_layers(arch):
    """The layer count of the residual scale: the PUBLISHED one, which a
    cut in depth keeps."""
    return arch.get("published", {}).get("num_hidden_layers",
                                         arch["num_hidden_layers"])


def slopes(arch):
    """s_h = 2^(-8 (h + 1) / H), h = 0 .. H - 1 (assumed)."""
    H = arch["num_attention_heads"]
    return jnp.exp2(-8.0 * jnp.arange(1, H + 1, dtype=jnp.float32) / H)


# -- the plain layers ------------------------------------------------------------

def rotary(x, pos, theta):
    """x [s, heads, d] at positions pos [s]; pairs (i, i + d/2) rotate by
    pos * theta^(-2i/d)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def project(x, w, arch, mm, kind, pos):
    """x [s, h] -> (q [s, H, d], k, v [s, nkv, d], gate [s, H*d]) with the
    per-head norms on q and k and, in a lightning layer, RoPE."""
    H, d, eps = arch["num_attention_heads"], arch["head_dim"], \
        arch["rms_norm_eps"]
    nkv = arch["num_key_value_heads"] if kind == SPARSE else H
    s = x.shape[0]
    hin = rms_norm(x, w["ln1"], eps)
    q = mm(hin, w["wq"]).reshape(s, H, d)
    k = mm(hin, w["wk"][:, :nkv * d]).reshape(s, nkv, d)
    v = mm(hin, w["wv"][:, :nkv * d]).reshape(s, nkv, d)
    q, k = rms_norm(q, w["q_norm"], eps), rms_norm(k, w["k_norm"], eps)
    if kind == LIGHTNING:
        q = rotary(q, pos, arch["rope_theta"])
        k = rotary(k, pos, arch["rope_theta"])
    return q, k, v, mm(hin, w["wg"])


def lightning_scan(q, k, v, S, valid, arch):
    """The recurrence, a token a step. q, k, v [s, H, d]; S [H, d, d];
    valid [s] (a padded token leaves S alone). Returns (o [s, H, d], S)."""
    lam = jnp.exp(-slopes(arch))[:, None, None]
    d = q.shape[-1]

    def step(S, x):
        qt, kt, vt, ok = x
        new = lam * S + kt[:, :, None] * vt[:, None, :]
        S = jnp.where(ok, new, S)
        return S, jnp.einsum("hd,hde->he", qt, S, precision=HIGHEST)

    S, o = jax.lax.scan(step, S, (q, k, v, valid), unroll=8)
    return o / math.sqrt(d), S


def selected_blocks(qg, qpos, k, arch):
    """Which blocks each query attends. qg [n, nkv, g, d] at positions qpos
    [n]; k [m, nkv, d] the keys of positions 0 .. m - 1. Returns bool [nkv,
    n, m / block]."""
    sp = arch["sparse_config"]
    B, K, T = sp["block_size"], sp["kernel_size"], sp["kernel_stride"]
    m, nkv, d = k.shape
    ctx = qpos + 1                                        # n of the equations

    # compressed keys: kernel j = mean of the keys of [T j, T j + K)
    J = (m - K) // T + 1
    strides = k[:(m // T) * T].reshape(m // T, T, nkv, d).sum(1)
    kbar = sum(strides[i:i + J] for i in range(K // T)) / K   # [J, nkv, d]
    j = jnp.arange(J)
    inside = (T * j + K)[None, :] <= ctx[:, None]             # [n, J]
    lg = jnp.einsum("nkgd,jkd->kgnj", qg, kbar,
                    precision=HIGHEST) / math.sqrt(d)
    p = jax.nn.softmax(jnp.where(inside[None, None], lg, -jnp.inf), -1)
    s = jnp.sum(jnp.where(inside[None, None], p, 0.0), axis=1)  # [nkv,n,J]
    s = jnp.where(inside[None], s, -1.0)
    # block b = positions [B b, B b + B): the best score of the kernels
    # that overlap it (their indices, padded with a kernel that scores -1)
    nb = m // B
    b = jnp.arange(nb)
    over = [[i for i in range(J) if T * i < B * (c + 1) and T * i + K > B * c]
            for c in range(nb)]
    width = max(len(o) for o in over)
    idx = np.array([o + [J] * (width - len(o)) for o in over])
    s_pad = jnp.concatenate([s, jnp.full_like(s[..., :1], -1.0)], axis=-1)
    score = jnp.max(s_pad[..., idx], axis=-1)                 # [nkv, n, nb]
    cur = (qpos // B)[None, :, None]
    held = b[None, None, :] <= cur
    forced = held & ((b < sp["init_blocks"])[None, None, :]
                     | (b[None, None, :] > cur - sp["window_size"] // B))
    others = min(sp["topk"] - sp["init_blocks"] - sp["window_size"] // B,
                 nb)
    cand = held & ~forced
    best = jax.lax.top_k(jnp.where(cand, score, -jnp.inf), others)[1]
    picked = jnp.any(jax.nn.one_hot(best, nb, dtype=bool), axis=-2) & cand
    return jnp.where((ctx <= sp["dense_len"])[None, :, None], held,
                     forced | picked)


def sparse_attention(q, qpos, k, v, arch):
    """The queries q [n, H, d] at positions qpos [n] over the keys and
    values k, v [m, nkv, d] of positions 0 .. m - 1 (m a multiple of the
    block size, every qpos < m; rows past a query's position are never
    read). Returns [n, H, d]."""
    B = arch["sparse_config"]["block_size"]
    n, H, d = q.shape
    m, nkv = k.shape[0], k.shape[1]
    qg = q.reshape(n, nkv, H // nkv, d)
    selected = selected_blocks(qg, qpos, k, arch)             # [nkv, n, nb]
    kpos = jnp.arange(m)
    see = jnp.repeat(selected, B, axis=-1) & \
        (kpos[None, None, :] <= qpos[None, :, None])          # [nkv, n, m]
    sc = jnp.einsum("nkgd,mkd->kgnm", qg, k,
                    precision=HIGHEST) / math.sqrt(d)
    w = jax.nn.softmax(jnp.where(see[:, None], sc, -jnp.inf), axis=-1)
    out = jnp.einsum("kgnm,mkd->nkgd", w, v, precision=HIGHEST)
    return out.reshape(n, H, d)


def finish(x, attn, gate, w, arch, mm, kind):
    """The mixer's gate and output projection, the residual, then the
    SwiGLU feed-forward. x [s, h], attn [s, H, d], gate [s, H*d]."""
    eps = arch["rms_norm_eps"]
    a = arch["scale_depth"] / math.sqrt(published_layers(arch))
    o = attn.reshape(attn.shape[0], -1)
    if kind == LIGHTNING:
        o = rms_norm(o, w["o_norm"], eps)
    x = x + a * mm(o * jax.nn.sigmoid(gate), w["wo"])
    hin = rms_norm(x, w["ln2"], eps)
    act = jax.nn.silu(mm(hin, w["w_gate"])) * mm(hin, w["w_up"])
    return x + a * mm(act, w["w_down"])


def decoder_layer(x, w, arch, mm, index=0):
    """One whole layer over one sequence x [b, s, h] from position 0, the
    kind by the layer's index (s a multiple of the block size)."""
    kind = arch["mixer_types"][index]
    H, d = arch["num_attention_heads"], arch["head_dim"]

    def one(x1):
        s = x1.shape[0]
        pos = jnp.arange(s)
        q, k, v, gate = project(x1, w, arch, mm, kind, pos)
        if kind == LIGHTNING:
            attn, _ = lightning_scan(q, k, v, jnp.zeros((H, d, d)),
                                     jnp.ones(s, bool), arch)
        else:
            attn = sparse_attention(q, pos, k, v, arch)
        return finish(x1, attn, gate, w, arch, mm, kind)

    return jax.lax.map(one, x)


# -- a served model: logits at the served positions --------------------------------

def _frozen(arch):
    def freeze(v):
        if isinstance(v, dict):
            return tuple(sorted((k, freeze(x)) for k, x in v.items()))
        return tuple(freeze(x) for x in v) if isinstance(v, list) else v

    return freeze({k: arch[k] for k in (
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "rms_norm_eps", "rope_theta", "scale_depth", "num_hidden_layers",
        "sparse_config")} | {"published": {
            "num_hidden_layers": published_layers(arch)}})


def _thaw(frozen):
    def thaw(v):
        if isinstance(v, tuple) and v and all(
                isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], str)
                for x in v):
            return {k: thaw(x) for k, x in v}
        return v

    return thaw(frozen)


def _f32(w):
    return jax.tree.map(lambda a: a.astype(jnp.float32), w)


@functools.lru_cache(maxsize=None)
def _project_fn(frozen, mm, kind):
    arch = _thaw(frozen)
    return jax.jit(lambda x, w, pos: project(x, _f32(w), arch, mm, kind,
                                             pos))


@functools.lru_cache(maxsize=None)
def _finish_fn(frozen, mm, kind):
    arch = _thaw(frozen)
    return jax.jit(lambda x, attn, gate, w: finish(x, attn, gate, _f32(w),
                                                   arch, mm, kind))


@functools.lru_cache(maxsize=None)
def _scan_fn(frozen):
    arch = _thaw(frozen)
    return jax.jit(lambda q, k, v, S, valid: lightning_scan(q, k, v, S,
                                                            valid, arch))


@functools.lru_cache(maxsize=None)
def _sparse_fn(frozen):
    arch = _thaw(frozen)
    return jax.jit(lambda q, qpos, k, v: sparse_attention(q, qpos, k, v,
                                                          arch))


@functools.lru_cache(maxsize=None)
def _head_fn(eps, divisor, mm):
    def head(x, norm_w, head_w):
        x = rms_norm(x, norm_w.astype(jnp.float32), eps) / divisor
        return mm(x, head_w.astype(jnp.float32))

    return jax.jit(head)


def _blocks(n, size):
    return [(a, min(a + size, n)) for a in range(0, n, size)]


def layer_forward(x, w, arch, mm, index, real):
    """One layer over one sequence x [s, h] (s a multiple of the token
    block) whose first `real` rows are tokens, block by block: every jitted
    call has one of a few fixed shapes whatever the sequence's length."""
    fz, kind = _frozen(arch), arch["mixer_types"][index]
    H, d = arch["num_attention_heads"], arch["head_dim"]
    B = arch["sparse_config"]["block_size"]
    s = x.shape[0]
    tb = min(T_BLOCK, s)
    proj, fin = _project_fn(fz, mm, kind), _finish_fn(fz, mm, kind)
    parts = [proj(x[a:b], w, jnp.arange(a, b)) for a, b in _blocks(s, tb)]
    q, k, v, gate = (jnp.concatenate(p) for p in zip(*parts))
    del parts
    if kind == LIGHTNING:
        S, outs = jnp.zeros((H, d, d), jnp.float32), []
        for a, b in _blocks(s, tb):
            o, S = _scan_fn(fz)(q[a:b], k[a:b], v[a:b], S,
                                jnp.arange(a, b) < real)
            outs.append(o)
    else:
        outs, qb = [], min(Q_SPARSE, tb)
        bucket = K_BUCKET if s > T_BLOCK else max(qb, B)
        if s > T_BLOCK:
            # rows past a query's position are never read: the key counts
            # are the buckets' alone, whatever the request's own length
            k, v = pad_rows(K_BUCKET, k, v)
        for a, b in _blocks(s, qb):
            m = min(-(-b // bucket) * bucket, k.shape[0])
            outs.append(_sparse_fn(fz)(q[a:b], jnp.arange(a, b), k[:m],
                                       v[:m]))
    attn = jnp.concatenate(outs)
    del outs, q, k, v
    return jnp.concatenate([fin(x[a:b], attn[a:b], gate[a:b], w)
                            for a, b in _blocks(s, tb)])


def forward_hidden(arch, ids, layer_weights, embedding, mm=f32_mm):
    """ids [n] -> the last layer's output [n, h]: the whole model's plain
    forward over one sequence. `layer_weights(i)` gives layer i's leaves."""
    n = len(ids)
    tb = T_BLOCK if n > T_BLOCK else -(-n // 64) * 64
    padded = np.zeros(-(-n // tb) * tb, np.int32)
    padded[:n] = ids
    x = embedding[jnp.asarray(padded)].astype(jnp.float32) * arch["scale_emb"]
    for i in range(arch["num_hidden_layers"]):
        x = layer_forward(x, layer_weights(i), arch, mm, i, n)
    return x[:n]


def head_logits(arch, x, final_norm, lm_head, mm=f32_mm):
    divisor = arch["hidden_size"] / arch["dim_model_base"]
    return _head_fn(arch["rms_norm_eps"], divisor, mm)(x, final_norm,
                                                       lm_head)


def served_logits(arch, seed, requests, mm=f32_mm):
    """For each (prompt, tokens, ...) of `requests`, the reference logits
    [len(tokens), vocab] at the positions where the server chose `tokens`
    after `prompt`. One request at a time through every layer (a 49k-token
    request's activations are 0.8 GB a tensor), the layer's weights made
    from the seed as they are needed."""
    outer = weights.outer_params(arch, seed)
    out = []
    for prompt, tokens, *_ in requests:
        seq = np.concatenate([np.asarray(prompt), np.asarray(tokens)[:-1]])
        x = forward_hidden(
            arch, seq,
            lambda i: weights.layer_params(_LEAVES, arch, seed, i),
            outer["embedding"], mm)
        out.append(served_rows(
            lambda rows: head_logits(arch, rows, outer["final_norm"],
                                     outer["lm_head"], mm),
            x, len(prompt), len(tokens)))
    return out


class _LEAVES:
    """What `weights.layer_params` asks of a family (this file is loaded
    by its path and is in no `sys.modules`)."""

    layer_shapes = staticmethod(layer_shapes)
    leaf_init = staticmethod(leaf_init)


# -- the counts of this family's readers ------------------------------------------

def visible_positions(arch, t):
    """Keys the query at position t attends in a sparse layer, by the
    equations: its whole context while that is no longer than dense_len,
    else `topk - 1` whole blocks and its own block up to itself."""
    sp = arch["sparse_config"]
    if t + 1 <= sp["dense_len"]:
        return t + 1
    return (sp["topk"] - 1) * sp["block_size"] + t % sp["block_size"] + 1


def _layers(arch, kind):
    return sum(1 for m in arch["mixer_types"] if m == kind)


def sparse_attn_flops(arch, positions):
    """QK^T and PV of the selected keys, every head, every sparse layer:
    4 d flops a (query, visible key) pair and head."""
    pairs = sum(visible_positions(arch, t) for t in positions)
    return (_layers(arch, SPARSE) * arch["num_attention_heads"]
            * 4 * arch["head_dim"] * pairs)


def sparse_kv_bytes(arch, t, itemsize=2):
    """Selected K and V a decode query at position t reads, every KV head
    and sparse layer."""
    return (_layers(arch, SPARSE) * 2 * arch["num_key_value_heads"]
            * arch["head_dim"] * itemsize * visible_positions(arch, t))


def lightning_flops_per_token(arch):
    """The scan's operations a token: the state's update k v^T and its
    read-out q^T S, 2 d^2 flops each a head, every lightning layer."""
    d = arch["head_dim"]
    return _layers(arch, LIGHTNING) * arch["num_attention_heads"] * 4 * d * d


def lightning_state_bytes(arch):
    """One request's float32 state read and written once, every lightning
    layer: what a decode step must move for a row."""
    d = arch["head_dim"]
    return (_layers(arch, LIGHTNING) * arch["num_attention_heads"]
            * d * d * 4 * 2)


def traced_work(ctx):
    """The least seconds the chip could take for the work the equations
    need in the traced slice, {"lightning": s, "sparse": s}: a prefill
    window is bound by operations (over the bf16 peak), a decode step by
    the bytes it must read (over the HBM bandwidth). Which window of which
    prompt a prefill step ran is rebuilt from the run's own records: the
    engine streams prompts in the order they were submitted, a
    `prefill_chunk` of tokens a step, no prefix hit on this unshared mix.
    None where that cannot be rebuilt (or on a run with no device trace)."""
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
    need = {"lightning": 0.0, "sparse": 0.0}
    for (kind, a, _, _), (lo, hi, n) in zip(steps, windows):
        if (kind == "prefill") != (hi == n):
            return None           # the order is not the one assumed
        if t0 <= a < t1:
            need["lightning"] += ((hi - lo) * lightning_flops_per_token(arch)
                                  / flops + lightning_state_bytes(arch) / bw)
            need["sparse"] += sparse_attn_flops(arch, range(lo, hi)) / flops
    for r in recs:
        for j, t in enumerate(r.times):
            if j and t0 <= t < t1:     # the j-th token came from a decode
                need["lightning"] += lightning_state_bytes(arch) / bw
                need["sparse"] += sparse_kv_bytes(
                    arch, len(r.prompt) + j - 1) / bw
    return need
