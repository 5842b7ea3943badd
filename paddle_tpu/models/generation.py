"""Compiled autoregressive generation: one XLA program for the whole decode.

The reference decodes eagerly — each step re-dispatches every op with a
grown cache (`LlamaForCausalLM.generate`-style loops; cache plumbing in
`paddle/phi/kernels/fusion/gpu/masked_multihead_attention_kernel.cu` and
`incubate/nn/functional/masked_multihead_attention`). On TPU, dynamic
shapes force a recompile per length, so the TPU-native design is the
static-shape serving loop:

  - the KV cache is ONE fixed buffer [L, B, Hkv, max_len, D] written with
    `dynamic_update_slice` at the current position (heads-major: the layout
    the attention kernels consume directly, so no per-step transpose);
  - attention masks invalid cache slots (iota > pos) instead of slicing a
    dynamic length — every step has identical shapes; on TPU the decode
    step (s_new=1) runs the Pallas decode-attention kernel
    (kernels/quantized_matmul.decode_attention), whose online max/sum stops
    at the position watermark instead of re-softmaxing the padded length;
  - the entire decode (prefill + lax.scan over steps + greedy/temperature/
    top-p sampling) traces into ONE `jax.jit`, so a 128-token generation
    is one device program launch, not 128 Python round-trips.

Works over the pure-functional param tree (`llama_functional`);
`params_from_layer` bridges a trained eager `LlamaForCausalLM` into it.
`quantize_params` converts the tree to weight-only int8 (QuantizedWeight
leaves); the same `generate` then streams int8 weights through the fused
Pallas dequant-matmul — the quantized-decode fast path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from typing import NamedTuple

from paddle_tpu.models import llama_functional as lf

__all__ = ["generate", "params_from_layer", "prefill", "decode_step",
           "paged_decode_step", "gpt_generate", "gpt_params_from_layer",
           "GPTGenArgs", "QuantizedWeight", "QuantizedKVPage",
           "quantize_params", "draft_from_params"]


class QuantizedWeight(NamedTuple):
    """Weight-only int8 leaf in a functional param tree: `q` int8 [..., K, N]
    with per-out-channel absmax `scale` [..., N] (dequant = q * scale / 127).
    A pytree node, so stacked [L, ...] leaves slice per layer under
    lax.scan like plain weights."""

    q: jax.Array
    scale: jax.Array


class QuantizedKVPage(NamedTuple):
    """int8 KV page-pool half: `q` int8 [..., num_pages, nkv, page_size,
    hd] with per-(page, kv-head) absmax `scale` [..., num_pages, nkv] f32
    (dequant = q * scale / 127 — the QuantizedWeight convention). A
    pytree node: the stacked [L, ...] pool is the decode scan's carry
    (the verify scan's xs) like the bf16 pool arrays, and jit donation /
    shard_map specs treat (q, scale) as ONE pool operand — both leaves
    shard on the nkv axis, so the bf16 `P(None, None, mp)` pool spec
    applies to the pair as a pytree prefix unchanged."""

    q: jax.Array
    scale: jax.Array


@jax.named_scope("pt.kv_write")
def _write_rows(pool, new, page, at):
    """pool[page[r], :, at[r]] = new[r] for every row r, as a read-modify-
    write of WHOLE pages: a page is the unit the pool's layout tiles, and
    a write of one row of a tile (a scatter over two axes, or an update of
    a [1, nkv, 1, d] slice) has XLA re-lay the whole pool around it, every
    step. Rows own their pages; the null page takes the others' garbage."""
    old = pool[page]                                  # [b, nkv, n, d]
    here = jnp.arange(pool.shape[2], dtype=jnp.int32)[None, :] == at[:, None]
    new = jnp.where(here[:, None, :, None],
                    new[:, :, None, :].astype(pool.dtype), old)
    return pool.at[page].set(new)


@jax.named_scope("pt.kv_write")
def _kv_quant_write(pool, page, off, new):
    """Write one token's K or V rows `new` [b, nkv, hd] into an int8 page
    pool at (page[r], :, off[r]) keeping the per-(page, kv-head) absmax
    scale RUNNING: when a token's absmax exceeds the page's scale, the
    page's existing codes are re-scaled in-registers (round(q*old/new))
    before the write — no page is ever dequantized through HBM. Rows own
    their target pages exclusively (the host COW gate), except the null
    page 0, which is a garbage sink on every write path."""
    q, scale = pool
    b = page.shape[0]
    newf = new.astype(jnp.float32)
    tok_abs = jnp.max(jnp.abs(newf), axis=-1)              # [b, nkv]
    # positions fill pages sequentially, so a write at offset 0 is always
    # the page's FIRST live write — restart its running scale there
    # instead of inheriting a stale absmax from the page's previous owner
    # (pages return to the pool carrying old codes and scales)
    old_s = jnp.where(off[:, None] == 0, 0.0, scale[page])  # [b, nkv]
    new_s = jnp.maximum(old_s, tok_abs)
    safe = jnp.maximum(new_s, 1e-9)
    pg = q[page].astype(jnp.float32) * (old_s / safe)[:, :, None, None]
    pg = pg.at[jnp.arange(b), :, off].set(newf / safe[..., None] * 127.0)
    qpg = jnp.clip(jnp.round(pg), -127, 127).astype(jnp.int8)
    return QuantizedKVPage(q.at[page].set(qpg), scale.at[page].set(new_s))


def _quantize_weight(w):
    from paddle_tpu.kernels.quantized_matmul import quantize_absmax

    return QuantizedWeight(*quantize_absmax(w))


_QUANT_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def quantize_params(params):
    """Weight-only int8 quantization of a Llama functional param tree for
    decode: every per-layer matmul weight and the lm_head become
    QuantizedWeight leaves (embedding and norms stay float — a gather
    cannot fuse with the dequant). `generate` consumes the result
    unchanged; its matmuls stream int8 through the fused Pallas kernel."""
    layers = {k: (_quantize_weight(v) if k in _QUANT_KEYS else v)
              for k, v in params["layers"].items()}
    out = dict(params, layers=layers)
    out["lm_head"] = _quantize_weight(params["lm_head"])
    return out


def _wmm(x, w):
    """Matmul that understands QuantizedWeight leaves: float weights take
    the plain `@`, int8 weights stream through the fused dequant-matmul
    dispatch (Pallas on TPU, jnp elsewhere)."""
    if isinstance(w, QuantizedWeight):
        from paddle_tpu.kernels import quantized_matmul as qm

        return qm.weight_only_matmul(x, w.q, w.scale, out_dtype=x.dtype)
    return x @ w


def _tp_reduce(x, tp_axis):
    """Row-parallel output reduction for the tensor-parallel decode path:
    psum over the mp axis inside shard_map (the Megatron pattern
    llama_functional.decoder_layer uses for training), identity when the
    forward runs unsharded."""
    return x if tp_axis is None else jax.lax.psum(x, tp_axis)


def draft_from_params(params, args, num_layers):
    """Truncate a Llama functional tree to its first `num_layers` decoder
    layers (embedding/final_norm/lm_head shared) — a cheap draft model for
    speculative decoding whose early-layer predictions track the full
    target closely. Works on float and `quantize_params` trees (stacked
    QuantizedWeight leaves slice like plain weights). Returns
    (draft_params, draft_args)."""
    if not 1 <= num_layers <= args.num_layers:
        raise ValueError(
            f"draft must keep 1..{args.num_layers} layers, got {num_layers}")
    layers = jax.tree_util.tree_map(lambda x: x[:num_layers],
                                    params["layers"])
    return dict(params, layers=layers), args._replace(num_layers=num_layers)


def params_from_layer(model):
    """Stack an eager `LlamaForCausalLM`/`LlamaModel`'s weights into the
    functional tree `llama_functional` uses (layers stacked on a leading
    [L] dim). The transpose conventions match lf.init_params: every weight
    is [in, out]."""
    core = getattr(model, "model", model)
    lm_head = getattr(model, "lm_head", None)

    def arr(t):
        return t._data if hasattr(t, "_data") else jnp.asarray(t)

    layers = core.layers
    stacked = {}
    names = [("wq", lambda l: arr(l.self_attn.q_proj.weight)),
             ("wk", lambda l: arr(l.self_attn.k_proj.weight)),
             ("wv", lambda l: arr(l.self_attn.v_proj.weight)),
             ("wo", lambda l: arr(l.self_attn.o_proj.weight)),
             ("w_gate", lambda l: arr(l.mlp.gate_proj.weight)),
             ("w_up", lambda l: arr(l.mlp.up_proj.weight)),
             ("w_down", lambda l: arr(l.mlp.down_proj.weight)),
             ("ln1", lambda l: arr(l.input_layernorm.weight)),
             ("ln2", lambda l: arr(l.post_attention_layernorm.weight))]
    for key, get in names:
        stacked[key] = jnp.stack([get(l) for l in layers])
    return {
        "embedding": arr(core.embed_tokens.weight),
        "layers": stacked,
        "final_norm": arr(core.norm.weight),
        "lm_head": (arr(lm_head.weight) if lm_head is not None
                    else arr(core.embed_tokens.weight).T),
    }


def _cached_attention(q, cache_k, cache_v, pos):
    """Masked attention of q [b, s, nh, hd] over the full fixed-size cache
    [b, nkv, max_len, hd] (invalid slots masked by position — static shapes
    every step). Shared by the Llama and GPT decode layers. The decode step
    (s == 1) dispatches to the Pallas decode-attention kernel when
    supported: single query against the cache, online max/sum bounded to
    the valid prefix, GQA without repeating kv heads.

    pos: scalar (every row at the same depth — the compiled generate), or
    an int32 [b] vector of per-row positions (continuous-batching decode:
    each slot at its own depth; with s > 1 query row i of batch row r sits
    at pos[r] + i — the speculative-verify window)."""
    b, s, nh, hd = q.shape
    nkv, max_len = cache_k.shape[1], cache_k.shape[2]
    from paddle_tpu.kernels import quantized_matmul as qm

    if s == 1:
        if qm.fused_enabled() and qm.decode_supported(
                q.shape, cache_k.shape, q.dtype.itemsize):
            return qm.decode_attention(q, cache_k, cache_v, pos)
    elif qm.fused_enabled() and qm.window_supported(
            q.shape, cache_k.shape, q.dtype.itemsize):
        # a SHORT query window at a traced offset — the chunk-offset
        # prefill tail and the speculative-verify window ride the Pallas
        # window kernel (online max/sum bounded to the last query's
        # watermark) instead of re-softmaxing the padded cache length
        return qm.window_decode_attention(q, cache_k, cache_v, pos)
    with jax.named_scope("pt.paged_attention"):   # as the kernels above
        if nkv != nh:
            rep = nh // nkv
            kh = jnp.repeat(cache_k, rep, axis=1)
            vh = jnp.repeat(cache_v, rep, axis=1)
        else:
            kh, vh = cache_k, cache_v
        qh = jnp.swapaxes(q, 1, 2)
        scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) / np.sqrt(hd)
        key_pos = jax.lax.broadcasted_iota(jnp.int32, (1, 1, s, max_len), 3)
        row_iota = jax.lax.broadcasted_iota(jnp.int32, (1, 1, s, max_len), 2)
        if jnp.ndim(pos) == 1:
            query_pos = jnp.asarray(pos).reshape(b, 1, 1, 1) + row_iota
        else:
            query_pos = pos + row_iota
        scores = jnp.where(key_pos <= query_pos, scores, -1e30)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
        attn = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(vh.dtype), vh)
        return jnp.swapaxes(attn, 1, 2)


def _rope_rows(q, k, cos_r, sin_r):
    """RoPE at per-row positions: q/k [b, 1, nh, hd], cos_r/sin_r [b, hd]
    (the rows of the RoPE tables gathered at each row's own position) —
    the same rotate-half math as lf.apply_rope, broadcast over batch
    instead of sequence."""
    return lf.apply_rope_bcast(q, k, cos_r[:, None, None, :],
                               sin_r[:, None, None, :])


def _mlp_block(lp, h, args, tp_axis):
    """The SwiGLU half of a decoder layer, shared by the stripe, paged and
    verify steps: h + down(silu(gate(norm(h))) * up(norm(h)))."""
    hin = lf.rms_norm(h, lp["ln2"], args.rms_eps)
    with jax.named_scope("pt.mlp"):
        act = jax.nn.silu(_wmm(hin, lp["w_gate"])) * _wmm(hin, lp["w_up"])
        return h + _tp_reduce(_wmm(act, lp["w_down"]), tp_axis)


def _serving_layer(lp, h, args, attend, tp_axis=None, tp_degree=1):
    """One Llama decoder layer over `h` [b, s, hid] as the serving steps run
    it: what the stripe, the paged decode and the verify step share. Norm,
    the three projections split into heads, the output projection with its
    tensor-parallel reduce and the SwiGLU half are written here; `attend(q,
    k, v) -> (attn [b, s, nh, hd], *caches)` is the caller's: RoPE at the
    rows its positions name, the write of k / v into its cache, and
    attention over that cache (write-before-attend). Returns (h, *caches).

    tp_axis/tp_degree: when set, this body runs inside shard_map over a
    tensor-parallel mesh axis — lp holds the Megatron shards (wq/wk/wv/
    w_gate/w_up split on the out dim, wo/w_down on the in dim), a cache
    holds this device's nkv/tp_degree heads, and the row-parallel outputs
    are psum-reduced so `h` stays replicated."""
    b, s = h.shape[0], h.shape[1]
    nh = args.num_heads // tp_degree
    nkv = args.num_kv_heads // tp_degree
    hd = lf.head_dim(args)

    hin = lf.rms_norm(h, lp["ln1"], args.rms_eps)
    with jax.named_scope("pt.attention"):
        q = _wmm(hin, lp["wq"]).reshape(b, s, nh, hd)
        k = _wmm(hin, lp["wk"]).reshape(b, s, nkv, hd)
        v = _wmm(hin, lp["wv"]).reshape(b, s, nkv, hd)
        attn, *caches = attend(q, k, v)
        h = h + _tp_reduce(_wmm(attn.reshape(b, s, nh * hd), lp["wo"]),
                           tp_axis)

    return (_mlp_block(lp, h, args, tp_axis), *caches)


def _layer_step(lp, h, cache_k, cache_v, pos, cos, sin, args,
                tp_axis=None, tp_degree=1):
    """One decoder layer over `h` [b, s, hid] with a fixed-size cache
    [b, nkv, max_len, hd] (heads-major).

    prefill (pos == 0, s == prompt len): causal attention within the
    block, cache slots [0, s) written. decode (s == 1): attend over
    cache[: pos+1] via masking, slot [pos] written. Both are the same
    masking rule: key_pos <= pos + query_row.

    pos may be an int32 [b] vector (requires s == 1): every row sits at its
    own position — per-row RoPE, per-row cache-slot writes, per-row
    attention masking. This is the continuous-batching decode step."""
    def attend(q, k, v):
        s = q.shape[1]
        if jnp.ndim(pos) == 1:
            if s != 1:
                raise ValueError("per-row pos vector requires s == 1 "
                                 f"(got s={s})")
            q, k = _rope_rows(q, k, jnp.take(cos, pos, axis=0),
                              jnp.take(sin, pos, axis=0))

            # each row's new kv lands at that row's own position
            def write_row(c, new, p):
                return jax.lax.dynamic_update_slice_in_dim(c, new, p, axis=1)

            with jax.named_scope("pt.kv_write"):
                ck = jax.vmap(write_row)(cache_k, jnp.swapaxes(k, 1, 2), pos)
                cv = jax.vmap(write_row)(cache_v, jnp.swapaxes(v, 1, 2), pos)
        else:
            q, k = lf.apply_rope(
                q, k, jax.lax.dynamic_slice_in_dim(cos, pos, s, 0),
                jax.lax.dynamic_slice_in_dim(sin, pos, s, 0))
            with jax.named_scope("pt.kv_write"):
                ck = jax.lax.dynamic_update_slice_in_dim(
                    cache_k, jnp.swapaxes(k, 1, 2), pos, axis=2)
                cv = jax.lax.dynamic_update_slice_in_dim(
                    cache_v, jnp.swapaxes(v, 1, 2), pos, axis=2)
        return _cached_attention(q, ck, cv, pos), ck, cv

    return _serving_layer(lp, h, args, attend, tp_axis, tp_degree)


def _last_hidden(h, last_idx):
    """h [b, s, hid] at each row's LAST REAL token. last_idx: optional traced
    per-row (or scalar) index of it — serving prefills pad prompts up to a
    length bucket, so the next-token logits live at true_len-1, not at s-1.
    None keeps the plain h[:, -1] gather."""
    if last_idx is None:
        return h[:, -1, :]
    idx = jnp.broadcast_to(jnp.asarray(last_idx, jnp.int32).reshape(-1),
                           (h.shape[0],))
    return jnp.take_along_axis(h, idx[:, None, None], axis=1)[:, 0, :]


def _forward_cached(params, ids, caches_k, caches_v, pos, cos, sin, args,
                    last_idx=None, tp_axis=None, tp_degree=1):
    """ids [b, s] -> (next-token logits [b, vocab], new caches); `last_idx`
    as in `_last_hidden`."""
    h = jnp.take(params["embedding"], ids, axis=0)

    def step(carry, xs):
        h = carry
        lp, ck, cv = xs
        h, ck, cv = _layer_step(lp, h, ck, cv, pos, cos, sin, args,
                                tp_axis, tp_degree)
        return h, (ck, cv)

    h, (new_k, new_v) = jax.lax.scan(step, h,
                                     (params["layers"], caches_k, caches_v))
    h = lf.rms_norm(h, params["final_norm"], args.rms_eps)
    logits = _wmm(_last_hidden(h, last_idx), params["lm_head"])
    return logits.astype(jnp.float32), new_k, new_v


def _layer_step_paged(lp, h, pool_k, pool_v, bt, pos, cos, sin, args,
                      page_size, tp_axis=None, tp_degree=1, layer=0,
                      num_layers=1):
    """One decoder layer's decode step (s == 1) over a PAGED KV cache.

    pool_k/pool_v: the page pools of `num_layers` layers, layer-major
    [num_layers * num_pages, nkv, ps, hd], of which this is layer `layer`
    (a traced index): its pages are the run that starts at `layer *
    num_pages`, written and read where they lie. The defaults say that the
    pool is this layer's own. bt: int32 block tables [b, P] of a layer's
    page numbers (page i of row r holds positions [i*ps, (i+1)*ps) of that
    row — unused entries point at the null page); pos: int32 [b] per-row
    write positions. Each row's new k/v goes to (bt[r, pos[r]//ps], pos[r]
    % ps) as a read-modify-write of that page (`_write_rows`) —
    write-before-attend, like the stripe path — then attention gathers K/V
    through the block table (Pallas paged kernel on TPU, jnp gather
    elsewhere). Nothing here has a pool's or a layer's size but the pools
    themselves, returned updated in place. Under tensor parallelism
    (`_serving_layer`) the pool is sharded on nkv and the tables replicated."""
    if h.shape[1] != 1:
        raise ValueError(f"paged decode requires s == 1 (got s={h.shape[1]})")
    ps = page_size
    quantized = isinstance(pool_k, QuantizedKVPage)
    num_pages = (pool_k.q if quantized else pool_k).shape[0] // num_layers
    base = layer * num_pages

    from paddle_tpu.kernels import quantized_matmul as qm

    def attend(q, k, v):
        q, k = _rope_rows(q, k, jnp.take(cos, pos, axis=0),
                          jnp.take(sin, pos, axis=0))

        # rows own their tail page exclusively (the host-side COW gate
        # guarantees it), so writes never collide on a live page; rows
        # that do not decode name the null page, the garbage sink
        page = base + jnp.take_along_axis(bt, (pos // ps)[:, None],
                                          axis=1)[:, 0]
        off = pos % ps
        if quantized:
            pk = _kv_quant_write(pool_k, page, off, k[:, 0])
            pv = _kv_quant_write(pool_v, page, off, v[:, 0])
            kq, vq = pk.q, pv.q
            # the layer's own scales: what the kernel holds in SMEM
            ks = jax.lax.dynamic_slice_in_dim(pk.scale, base, num_pages)
            vs = jax.lax.dynamic_slice_in_dim(pv.scale, base, num_pages)
        else:
            pk = _write_rows(pool_k, k[:, 0], page, off)
            pv = _write_rows(pool_v, v[:, 0], page, off)
            kq, ks, vq, vs = pk, None, pv, None

        if qm.fused_enabled() and qm.paged_decode_supported(
                q.shape, kq.shape, bt.shape, kq.dtype.itemsize):
            attn = qm.paged_decode_attention(q, kq, vq, bt, pos, k_scale=ks,
                                             v_scale=vs, page_base=base)
        else:
            # gather pages into the contiguous per-row layout (dequantized
            # under an int8 pool) and reuse the stripe attention (jnp mask
            # fallback; contiguous Pallas kernel if eligible) — table order
            # IS sequence order, so positions line up
            attn = _cached_attention(
                q, qm.paged_gather(kq, bt, ks, q.dtype, base),
                qm.paged_gather(vq, bt, vs, q.dtype, base), pos)
        return attn, pk, pv

    return _serving_layer(lp, h, args, attend, tp_axis, tp_degree)


def _layer_step_paged_verify(lp, h, pool_k_l, pool_v_l, bt, pos, limit,
                             cos, sin, args, page_size, tp_axis=None,
                             tp_degree=1):
    """One decoder layer over a SPECULATION WINDOW of s draft tokens
    against the paged cache: query i of row r sits at position pos[r]+i.

    The s new k/v of each row scatter into its tail pages
    (write-before-attend; the host pre-allocates pages through
    pos+s-1, COW-cleared). Writes past `limit[r]` — the row's last legal
    KV index, i.e. beyond its admission-time page reservation — are
    REDIRECTED to the null page (the garbage sink): a row about to finish
    never touches pages it does not own, and the position mask keeps the
    skipped slots unread. Attention gathers the row's whole table and
    masks per row per query (`_cached_attention`'s vector-pos branch)."""
    ps = page_size

    from paddle_tpu.kernels import quantized_matmul as qm

    def attend(q, k, v):
        b, s, nkv, hd = k.shape
        prow = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        cos_r = jnp.take(cos, prow, axis=0)                  # [b, s, hd]
        sin_r = jnp.take(sin, prow, axis=0)
        q, k = lf.apply_rope_bcast(q, k, cos_r[:, :, None, :],
                                   sin_r[:, :, None, :])

        page = jnp.take_along_axis(bt, prow // ps, axis=1)   # [b, s]
        page = jnp.where(prow <= limit[:, None], page, 0)    # null-page sink
        off = prow % ps
        pk, pv = pool_k_l, pool_v_l
        if isinstance(pk, QuantizedKVPage):
            # token-at-a-time running-absmax writes (s is tiny — the draft
            # window) so a window straddling a page boundary re-scales each
            # touched page exactly once per token that exceeds its scale
            for i in range(s):
                pk = _kv_quant_write(pk, page[:, i], off[:, i], k[:, i])
                pv = _kv_quant_write(pv, page[:, i], off[:, i], v[:, i])
            kq, ks = pk
            vq, vs = pv
        else:
            with jax.named_scope("pt.kv_write"):
                pk = pk.at[page.reshape(-1), :, off.reshape(-1)].set(
                    k.reshape(b * s, nkv, hd))
                pv = pv.at[page.reshape(-1), :, off.reshape(-1)].set(
                    v.reshape(b * s, nkv, hd))
            kq, ks, vq, vs = pk, None, pv, None

        # gather the row's table and run the window through the shared
        # masked attention (its vector-pos s>1 branch: query i of row r at
        # pos[r]+i). s is tiny (draft length + 1), so gather-then-mask is
        # the dispatch on every backend; a fused window kernel is a
        # follow-up once TPU-measured numbers justify it
        attn = _cached_attention(
            q, qm.paged_gather(kq, bt, scale=ks, out_dtype=q.dtype),
            qm.paged_gather(vq, bt, scale=vs, out_dtype=q.dtype), pos)
        return attn, pk, pv

    return _serving_layer(lp, h, args, attend, tp_axis, tp_degree)


def _paged_forward_decode(params, ids, pool_k, pool_v, bt, pos, cos, sin,
                          args, page_size, tp_axis=None, tp_degree=1):
    """ids [b, 1] -> (next-token logits [b, vocab], new pools). The paged
    analogue of `_forward_cached`'s decode step, except that the pools
    [L, num_pages, nkv, ps, hd] are the layer scan's CARRY, viewed
    layer-major [L * num_pages, nkv, ps, hd] (a bitcast): a layer writes
    and reads its own run of pages in the carried pool
    (`_layer_step_paged`), so the program never slices a layer out, copies
    a pool or writes one back. What a step touches is the pages its rows
    write and read."""
    h = jnp.take(params["embedding"], ids, axis=0)
    L, num_pages = jax.tree_util.tree_leaves(pool_k)[0].shape[:2]
    tree_map = jax.tree_util.tree_map

    def step(carry, xs):
        h, pk, pv = carry
        lp, layer = xs
        return _layer_step_paged(lp, h, pk, pv, bt, pos, cos, sin, args,
                                 page_size, tp_axis, tp_degree, layer, L), None

    (h, *pools), _ = jax.lax.scan(
        step,
        (h, *tree_map(lambda a: a.reshape((L * num_pages,) + a.shape[2:]),
                      (pool_k, pool_v))),
        (params["layers"], jnp.arange(L, dtype=jnp.int32)))
    new_k, new_v = tree_map(
        lambda a: a.reshape((L, num_pages) + a.shape[1:]), pools)
    h = lf.rms_norm(h, params["final_norm"], args.rms_eps)
    logits = _wmm(h[:, -1, :], params["lm_head"])
    return logits.astype(jnp.float32), new_k, new_v


@jax.named_scope("pt.kv_write")
def _quant_write_window_pages(pool, new, h, end, bt_row, new_pages, ps):
    """`hybrid_functional._write_window_pages` for an int8 pool: the
    window's rows `new` [s, nkv, hd] (positions h ..; s whole pages, as
    there: `s // ps + 1` pages then hold the window from any h) go into
    `new_pages` (from the page that holds h on), QUANTISED as they are
    written: per (page, kv head) absmax over the valid positions only (`end`
    is the first position past the window's last real token: what a padded
    row computed would inflate the scale and crush the real values; masked
    positions store 0), the kept half of a straddled page dequantised
    first."""
    s, nkv, hd = new.shape
    n = min(s // ps + 1, new_pages.shape[0])
    page0 = bt_row[h // ps]
    first = (pool.q[page0].astype(jnp.float32)
             * (pool.scale[page0] / 127.0)[:, None, None])      # [nkv, ps, hd]
    buf = jnp.zeros(((n + 1) * ps, nkv, hd), jnp.float32)
    buf = jax.lax.dynamic_update_slice_in_dim(
        buf, jnp.swapaxes(first, 0, 1), 0, 0)
    buf = jax.lax.dynamic_update_slice_in_dim(
        buf, new.astype(jnp.float32), h % ps, 0)
    pos = h - h % ps + jnp.arange(n * ps, dtype=jnp.int32)
    x = jnp.where((pos < end)[:, None, None], buf[:n * ps], 0.0)
    x = x.reshape(n, ps, nkv, hd)
    scale = jnp.max(jnp.abs(x), axis=(1, 3))                    # [n, nkv]
    codes = jnp.clip(jnp.round(
        x / jnp.maximum(scale, 1e-9)[:, None, :, None] * 127.0),
        -127, 127).astype(jnp.int8)
    # heads-major pages last, next to the write, as `_write_window_pages`
    # has it: a transpose further up the chain has XLA re-lay the POOL
    return QuantizedKVPage(
        pool.q.at[new_pages[:n]].set(jnp.swapaxes(codes, 1, 2)),
        pool.scale.at[new_pages[:n]].set(scale))


def _paged_forward_prefill(params, ids, pool_k, pool_v, h, last_idx, bt_row,
                           new_pages, cos, sin, args, page_size,
                           tp_axis=None, tp_degree=1):
    """One prefill window of one slot over the PAGED cache: ids [1, sb] at
    positions h .. h + sb - 1 (h traced; real up to `last_idx`) -> (logits
    [1, vocab] at `last_idx`, new pools). The prefill twin of
    `_paged_forward_decode`: the pools [L, num_pages, nkv, ps, hd] are the
    layer scan's carry, viewed layer-major; a layer writes the window's k /
    v into the window's own pages of its run (`new_pages`: the slot's pages
    from the one that holds h on; write-before-attend) and attends over the
    pool through the slot's table `bt_row` [P], as far as the window's last
    position. Nothing here has the table's width in positions, a layer's or
    a pool's size but the pools themselves, returned updated in place."""
    from paddle_tpu.kernels.paged_prefill_attention import (
        paged_prefill_attention)
    from paddle_tpu.models.hybrid_functional import _write_window_pages

    x = jnp.take(params["embedding"], ids, axis=0)
    sb, ps = ids.shape[1], page_size
    L, num_pages = jax.tree_util.tree_leaves(pool_k)[0].shape[:2]
    quantized = isinstance(pool_k, QuantizedKVPage)
    tree_map = jax.tree_util.tree_map
    cos_w = jax.lax.dynamic_slice_in_dim(cos, h, sb, 0)
    sin_w = jax.lax.dynamic_slice_in_dim(sin, h, sb, 0)

    def rows(a):
        # the writers put `s // ps + 1` pages, which hold a window from
        # any h only if s is whole pages: a bucket that is not (the
        # engine's `min_bucket` may lie below `page_size`) is written as
        # whole pages of rows, zeros past its own (positions no query
        # reads before its own token's write)
        pad = -sb % ps
        return jnp.pad(a[0], ((0, pad), (0, 0), (0, 0))) if pad else a[0]

    def step(carry, xs):
        x, pk, pv = carry
        lp, layer = xs
        base = layer * num_pages
        table, pages = base + bt_row, base + new_pages

        def attend(q, k, v):
            q, k = lf.apply_rope(q, k, cos_w, sin_w)
            k, v = rows(k), rows(v)
            if quantized:
                end = h + last_idx + 1
                nk = _quant_write_window_pages(pk, k, h, end, table, pages,
                                               ps)
                nv = _quant_write_window_pages(pv, v, h, end, table, pages,
                                               ps)
                kq, vq = nk.q, nv.q
                # the layer's own scales: what the kernel holds in SMEM
                ks = jax.lax.dynamic_slice_in_dim(nk.scale, base, num_pages)
                vs = jax.lax.dynamic_slice_in_dim(nv.scale, base, num_pages)
            else:
                nk = _write_window_pages(pk, k.astype(pk.dtype), h, table,
                                         pages, ps)
                nv = _write_window_pages(pv, v.astype(pv.dtype), h, table,
                                         pages, ps)
                kq, ks, vq, vs = nk, None, nv, None
            attn = paged_prefill_attention(q[0], kq, vq, bt_row, h, last_idx,
                                           page_base=base, k_scale=ks,
                                           v_scale=vs)
            return attn[None], nk, nv

        return _serving_layer(lp, x, args, attend, tp_axis, tp_degree), None

    (x, *pools), _ = jax.lax.scan(
        step,
        (x, *tree_map(lambda a: a.reshape((L * num_pages,) + a.shape[2:]),
                      (pool_k, pool_v))),
        (params["layers"], jnp.arange(L, dtype=jnp.int32)))
    new_k, new_v = tree_map(
        lambda a: a.reshape((L, num_pages) + a.shape[1:]), pools)
    x = lf.rms_norm(x, params["final_norm"], args.rms_eps)
    logits = _wmm(_last_hidden(x, last_idx), params["lm_head"])
    return logits.astype(jnp.float32), new_k, new_v


def _paged_forward_verify(params, ids, pool_k, pool_v, bt, pos, limit,
                          cos, sin, args, page_size, tp_axis=None,
                          tp_degree=1):
    """Speculative-verify forward: ids [b, s] (the last committed token
    followed by s-1 draft tokens, row r's token i at position pos[r]+i)
    -> (logits [b, s, vocab] at EVERY window position, new pools). One
    batched program scores a whole draft window — the target-model half
    of speculative decoding (Leviathan et al.; greedy exact-match
    acceptance happens on host)."""
    h = jnp.take(params["embedding"], ids, axis=0)

    def step(carry, xs):
        h = carry
        lp, pk, pv = xs
        h, pk, pv = _layer_step_paged_verify(
            lp, h, pk, pv, bt, pos, limit, cos, sin, args, page_size,
            tp_axis, tp_degree)
        return h, (pk, pv)

    h, (new_k, new_v) = jax.lax.scan(step, h,
                                     (params["layers"], pool_k, pool_v))
    h = lf.rms_norm(h, params["final_norm"], args.rms_eps)
    logits = _wmm(h, params["lm_head"])
    return logits.astype(jnp.float32), new_k, new_v


def paged_decode_step(params, args, token, pool_k, pool_v, block_tables,
                      pos, page_size):
    """One continuous-batching decode step over a paged KV cache: token
    [b] at per-row positions pos [b], K/V stored as pages [L, num_pages,
    nkv, page_size, hd] indexed through block_tables [b, P]. Rows are
    independent; unused/inactive table entries must point at a valid page
    index (conventionally the null page 0) and are never read thanks to
    the position mask. float and `quantize_params` int8 trees both work —
    every matmul rides the fused dequant-matmul dispatch — and the pools
    may be `QuantizedKVPage` pairs (int8 pages + per-(page, kv-head)
    scales): writes then quantize in place and attention dequantizes
    in-registers."""
    hd = lf.head_dim(args)
    P = block_tables.shape[1]
    cos, sin = lf.rope_tables(P * int(page_size), hd, args.rope_theta)
    return _paged_forward_decode(
        params, jnp.asarray(token)[:, None], pool_k, pool_v,
        jnp.asarray(block_tables, jnp.int32), jnp.asarray(pos, jnp.int32),
        cos, sin, args, int(page_size))


def _row_keys(seeds, pos):
    """Per-request sampling keys [b]: fold (seed, position) into a fixed
    base key — a request's randomness is a pure function of its own seed
    and the position being sampled, independent of batch composition.
    THE one derivation shared by `generate(seeds=...)` and the serving
    engines' per-slot sampler (the documented common key stream)."""
    base = jax.vmap(
        lambda s: jax.random.fold_in(jax.random.key(0), s))(seeds)
    return jax.vmap(jax.random.fold_in)(
        base, jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1),
                               (base.shape[0],)))


def _warp_logits(logits, temperature, top_p, top_k):
    """The per-request logit warp shared by `_sample` and rejection-
    sampling speculation: temperature scale, then top-k mask, then
    nucleus mask over the k-survivors (-1e30 for killed entries).
    Returns (masked [b, vocab], greedy_rows [b]). Rejection sampling
    needs the warped DISTRIBUTION itself (softmax of `masked`), not just
    a draw — and draft/target must warp with bit-identical math for the
    acceptance ratio p_target/p_draft to mean anything, hence the single
    shared implementation."""
    b, vocab = logits.shape
    t = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (b,))
    greedy_rows = t <= 0.0
    scaled = logits / jnp.where(greedy_rows, 1.0, t)[:, None]

    # top-k: mask everything below the k-th largest (k <= 0 or >= vocab
    # keeps all). Computed on the DESCENDING sort shared with top-p.
    k_vec = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (b,))
    k_eff = jnp.where(k_vec <= 0, vocab, jnp.minimum(k_vec, vocab))
    sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]
    rank = jax.lax.broadcasted_iota(jnp.int32, (b, vocab), 1)
    kth = jnp.take_along_axis(sorted_logits, (k_eff - 1)[:, None], axis=-1)
    sorted_masked = jnp.where(rank < k_eff[:, None], sorted_logits, -1e30)

    # nucleus mask over the k-survivors (a no-op when top_p == 1.0: the
    # cutoff lands on the smallest surviving logit and everything stays)
    p_vec = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32).reshape(-1),
                             (b,))[:, None]
    probs = jax.nn.softmax(sorted_masked, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_idx = jnp.sum(cum < p_vec, axis=-1, keepdims=True)
    cutoff = jnp.take_along_axis(sorted_masked, cutoff_idx, axis=-1)
    masked = jnp.where((scaled >= cutoff) & (scaled >= kth), scaled, -1e30)
    return masked, greedy_rows


def _sample(logits, sample, temperature, top_p, key, top_k=0,
            row_keys=None):
    """The per-request sampler. `sample` is the only STATIC switch (argmax
    vs categorical program structure); temperature/top_p/top_k are traced
    scalars OR per-row [b] vectors, so serving can vary them per request —
    per SLOT — without recompiling the decode program. Rows with
    temperature <= 0 stay exactly greedy (argmax), which is what keeps a
    greedy request's output bit-identical inside a mixed sampling batch.

    top_k <= 0 disables the top-k mask (all of vocab survives); top_p and
    top_k compose (k-mask first, nucleus over what remains — the
    huggingface/vLLM order). Sampling draws from `key` (one shared PRNG
    stream, split by the caller per step) or, when `row_keys` [b] is
    given, per-row gumbel-max draws — the per-request-seed path, where a
    request's randomness depends only on its own seed and position, not
    on which other requests share its batch."""
    if not sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    masked, greedy_rows = _warp_logits(logits, temperature, top_p, top_k)

    if row_keys is not None:
        # gumbel-max: argmax(logits + g) ~ categorical(softmax(logits)),
        # one independent draw per row from that row's own key
        vocab = logits.shape[-1]
        u = jax.vmap(lambda k_: jax.random.uniform(
            k_, (vocab,), jnp.float32, minval=1e-20, maxval=1.0))(row_keys)
        drawn = jnp.argmax(masked - jnp.log(-jnp.log(u)), axis=-1)
    else:
        drawn = jax.random.categorical(key, masked, axis=-1)
    return jnp.where(greedy_rows, jnp.argmax(logits, axis=-1),
                     drawn).astype(jnp.int32)


def _decode_loop(fwd, prompt_ids, ck, cv, max_new_tokens, sample,
                 temperature, top_p, key, use_eos=False, eos_id=0, pad_id=0,
                 top_k=0, seeds=None):
    """Shared prefill->sample->scan->concat driver (traced inside the
    per-architecture jit): fwd(ids, ck, cv, pos) -> (logits, ck, cv).

    use_eos (the only STATIC eos switch — program structure): rows that
    emit eos_id are DONE and emit pad_id from then on (the output stays a
    static [b, s + max_new_tokens] rectangle; per-row dynamic lengths
    would defeat the one-program design). eos_id/pad_id themselves are
    traced operands, so changing token ids never recompiles. The scan
    still runs max_new_tokens steps — XLA cannot early-exit a compiled
    loop — but finished rows carry a done mask, matching the reference's
    eager stopping criterion semantically."""
    b, s = prompt_ids.shape

    def rkeys(pos):
        # per-request seeds: a row's key depends only on (its seed, the
        # position being sampled) — stable across batch compositions
        return None if seeds is None else _row_keys(seeds, pos)

    logits, ck, cv = fwd(prompt_ids, ck, cv, 0)
    key, sub = jax.random.split(key)
    first = _sample(logits, sample, temperature, top_p, sub, top_k,
                    rkeys(jnp.int32(s)))
    done0 = first == eos_id if use_eos else jnp.zeros((b,), bool)
    if max_new_tokens == 1:
        return jnp.concatenate([prompt_ids, first[:, None]], axis=1)

    def step(carry, xs):
        token, ck, cv, pos, key, done = carry
        logits, ck, cv = fwd(token[:, None], ck, cv, pos)
        key, sub = jax.random.split(key)
        nxt = _sample(logits, sample, temperature, top_p, sub, top_k,
                      rkeys(pos + 1))
        if use_eos:
            nxt = jnp.where(done, pad_id.astype(jnp.int32), nxt)
            done = done | (nxt == eos_id)
        return (nxt, ck, cv, pos + 1, key, done), token

    (last, *_), toks = jax.lax.scan(
        step, (first, ck, cv, jnp.int32(s), key, done0), None,
        length=max_new_tokens - 1)
    new_tokens = jnp.concatenate([jnp.swapaxes(toks, 0, 1), last[:, None]],
                                 axis=1)
    return jnp.concatenate([prompt_ids, new_tokens], axis=1)


def _init_cache(params, args, b, max_len):
    """Fixed-size KV cache buffers [L, b, nkv, max_len, hd] + RoPE tables —
    shared by the public prefill/decode_step incremental API and the
    compiled generate."""
    L = lf.stack_leading_dim(params["layers"])
    hd = lf.head_dim(args)
    ck = jnp.zeros((L, b, args.num_kv_heads, max_len, hd),
                   params["embedding"].dtype)
    cv = jnp.zeros_like(ck)
    cos, sin = lf.rope_tables(max_len, hd, args.rope_theta)
    return ck, cv, cos, sin


def prefill(params, args, prompt_ids, max_len):
    """Run the prompt through the model once, filling the caches.
    Returns (next_logits [b, vocab], caches_k, caches_v) with caches
    [L, b, nkv, max_len, hd]."""
    b, s = prompt_ids.shape
    ck, cv, cos, sin = _init_cache(params, args, b, max_len)
    return _forward_cached(params, prompt_ids, ck, cv, 0, cos, sin, args)


def decode_step(params, args, token, caches_k, caches_v, pos, max_len):
    """One incremental step: token [b] at position pos.

    pos: scalar (uniform batch — every row at the same depth), or an int32
    [b] vector of PER-ROW positions: each row attends its own valid prefix
    [0, pos[i]] and writes its kv at pos[i]. The vector form is the
    continuous-batching decode step (paddle_tpu.serving): slots admitted at
    different times sit at different sequence depths inside one batched
    program. Rows are independent — an inactive/garbage slot cannot perturb
    the others."""
    hd = lf.head_dim(args)
    cos, sin = lf.rope_tables(max_len, hd, args.rope_theta)
    if jnp.ndim(pos) == 1:
        pos = jnp.asarray(pos, jnp.int32)
    return _forward_cached(params, token[:, None], caches_k, caches_v, pos,
                           cos, sin, args)


def generate(params, args, prompt_ids, max_new_tokens=32, temperature=0.0,
             top_p=1.0, key=None, eos_token_id=None, pad_token_id=0,
             top_k=0, seeds=None):
    """Whole generation as one compiled program.

    prompt_ids: [b, s] int32. Returns [b, s + max_new_tokens] int32.
    temperature 0 = greedy; top_p < 1 = nucleus sampling; top_k > 0 keeps
    only the k largest logits. temperature/top_p/top_k are traced and may
    be scalars or per-row [b] vectors (vary per call and per request
    without recompiling); only the greedy/sampling mode switch and shapes
    are compile-time.
    seeds: optional per-row int seeds [b]. Each row then samples from its
    own (seed, position)-derived PRNG stream — the same row with the same
    seed reproduces its tokens regardless of what else is in the batch.
    eos_token_id: rows that emit it produce pad_token_id afterwards (the
    output stays rectangular)."""
    if max_new_tokens <= 0:
        return jnp.asarray(prompt_ids)
    if key is None:
        key = jax.random.key(0)
    sample = bool(np.any(np.asarray(temperature) != 0.0))
    use_eos = eos_token_id is not None
    return _generate_jit(params, args, jnp.asarray(prompt_ids),
                         max_new_tokens, sample,
                         jnp.asarray(temperature if sample else 1.0,
                                     jnp.float32),
                         jnp.asarray(top_p, jnp.float32), key, use_eos,
                         jnp.int32(eos_token_id if use_eos else 0),
                         jnp.int32(pad_token_id),
                         jnp.asarray(top_k, jnp.int32),
                         (None if seeds is None
                          else jnp.asarray(seeds, jnp.int32)))


@functools.partial(jax.jit, static_argnames=("args", "max_new_tokens",
                                             "sample", "use_eos"))
def _generate_jit(params, args, prompt_ids, max_new_tokens, sample,
                  temperature, top_p, key, use_eos=False, eos_id=0,
                  pad_id=0, top_k=0, seeds=None):
    b, s = prompt_ids.shape
    max_len = s + max_new_tokens
    ck, cv, cos, sin = _init_cache(params, args, b, max_len)

    def fwd(ids, ck, cv, pos):
        return _forward_cached(params, ids, ck, cv, pos, cos, sin, args)

    return _decode_loop(fwd, prompt_ids, ck, cv, max_new_tokens, sample,
                        temperature, top_p, key, use_eos,
                        jnp.asarray(eos_id), jnp.asarray(pad_id),
                        jnp.asarray(top_k), seeds)


# --------------------------------------------------------------------------
# GPT-2 family (models/gpt.py): pre-LN blocks, learned positions, tied head
# --------------------------------------------------------------------------


class GPTGenArgs(NamedTuple):
    """Static (hashable) GPT shape for the compiled decode."""

    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    max_position_embeddings: int
    ln_eps: float = 1e-5

    @staticmethod
    def from_config(cfg):
        return GPTGenArgs(cfg.vocab_size, cfg.hidden_size,
                          cfg.num_hidden_layers, cfg.num_attention_heads,
                          cfg.max_position_embeddings,
                          getattr(cfg, "layer_norm_eps", 1e-5))


def gpt_params_from_layer(model):
    """Stack an eager `GPTForCausalLM`/`GPTModel` into a functional tree
    (weights [in, out]; biases as-is; layers stacked on a leading [L])."""
    core = getattr(model, "gpt", model)

    def arr(t):
        return t._data if hasattr(t, "_data") else jnp.asarray(t)

    names = [
        ("ln1_w", lambda l: arr(l.ln1.weight)),
        ("ln1_b", lambda l: arr(l.ln1.bias)),
        ("wq", lambda l: arr(l.attn.q_proj.weight)),
        ("bq", lambda l: arr(l.attn.q_proj.bias)),
        ("wk", lambda l: arr(l.attn.k_proj.weight)),
        ("bk", lambda l: arr(l.attn.k_proj.bias)),
        ("wv", lambda l: arr(l.attn.v_proj.weight)),
        ("bv", lambda l: arr(l.attn.v_proj.bias)),
        ("wo", lambda l: arr(l.attn.out_proj.weight)),
        ("bo", lambda l: arr(l.attn.out_proj.bias)),
        ("ln2_w", lambda l: arr(l.ln2.weight)),
        ("ln2_b", lambda l: arr(l.ln2.bias)),
        ("fc1_w", lambda l: arr(l.fc1.weight)),
        ("fc1_b", lambda l: arr(l.fc1.bias)),
        ("fc2_w", lambda l: arr(l.fc2.weight)),
        ("fc2_b", lambda l: arr(l.fc2.bias)),
    ]
    stacked = {k: jnp.stack([get(l) for l in core.layers])
               for k, get in names}
    return {
        "word_emb": arr(core.embeddings.word_embeddings.weight),
        "pos_emb": arr(core.embeddings.position_embeddings.weight),
        "layers": stacked,
        "lnf_w": arr(core.final.ln_f.weight),
        "lnf_b": arr(core.final.ln_f.bias),
    }


def _layer_norm(x, w, b, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    return (((x32 - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w + b)


def _gpt_layer_step(lp, h, cache_k, cache_v, pos, args: GPTGenArgs):
    b, s = h.shape[0], h.shape[1]
    nh = args.num_heads
    hd = lf.head_dim(args)

    hin = _layer_norm(h, lp["ln1_w"], lp["ln1_b"], args.ln_eps)
    q = (hin @ lp["wq"] + lp["bq"]).reshape(b, s, nh, hd)
    k = (hin @ lp["wk"] + lp["bk"]).reshape(b, s, nh, hd)
    v = (hin @ lp["wv"] + lp["bv"]).reshape(b, s, nh, hd)
    if jnp.ndim(pos) == 1:
        # per-row positions (serving decode; s must be 1) — the same
        # vmapped per-row cache write the llama `_layer_step` uses
        write = jax.vmap(lambda c, new, p: jax.lax.dynamic_update_slice_in_dim(
            c, new, p, axis=1))
        cache_k = write(cache_k, jnp.swapaxes(k, 1, 2), pos)
        cache_v = write(cache_v, jnp.swapaxes(v, 1, 2), pos)
    else:
        cache_k = jax.lax.dynamic_update_slice_in_dim(
            cache_k, jnp.swapaxes(k, 1, 2), pos, axis=2)
        cache_v = jax.lax.dynamic_update_slice_in_dim(
            cache_v, jnp.swapaxes(v, 1, 2), pos, axis=2)
    attn = _cached_attention(q, cache_k, cache_v, pos).reshape(b, s, nh * hd)
    h = h + (attn @ lp["wo"] + lp["bo"])

    hin = _layer_norm(h, lp["ln2_w"], lp["ln2_b"], args.ln_eps)
    act = jax.nn.gelu(hin @ lp["fc1_w"] + lp["fc1_b"], approximate=False)
    h = h + (act @ lp["fc2_w"] + lp["fc2_b"])
    return h, cache_k, cache_v


def _gpt_forward_cached(params, ids, caches_k, caches_v, pos,
                        args: GPTGenArgs, last_idx=None):
    """pos: scalar, or int32 [b] per-row positions (serving decode, s=1);
    `last_idx` as in `_last_hidden`."""
    b, s = ids.shape
    if jnp.ndim(pos) == 1:
        positions = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
        pe = jnp.take(params["pos_emb"], positions, axis=0)
    else:
        positions = pos + jnp.arange(s, dtype=jnp.int32)
        pe = jnp.take(params["pos_emb"], positions, axis=0)[None]
    h = jnp.take(params["word_emb"], ids, axis=0) + pe

    def step(carry, lp_kv):
        h = carry
        lp, ck, cv = lp_kv
        h, ck, cv = _gpt_layer_step(lp, h, ck, cv, pos, args)
        return h, (ck, cv)

    h, (new_k, new_v) = jax.lax.scan(step, h,
                                     (params["layers"], caches_k, caches_v))
    h = _layer_norm(h, params["lnf_w"], params["lnf_b"], args.ln_eps)
    logits = _last_hidden(h, last_idx) @ params["word_emb"].T  # tied head
    return logits.astype(jnp.float32), new_k, new_v


def gpt_generate(params, args: GPTGenArgs, prompt_ids, max_new_tokens=32,
                 temperature=0.0, top_p=1.0, key=None, eos_token_id=None,
                 pad_token_id=0):
    """GPT-2 whole-generation-as-one-program (same machinery as the Llama
    `generate`, incl. eos early-stop semantics; learned positions bound
    max_len by args.max_position_embeddings)."""
    if max_new_tokens <= 0:
        return jnp.asarray(prompt_ids)
    if key is None:
        key = jax.random.key(0)
    b, s = np.asarray(prompt_ids).shape
    if s + max_new_tokens > args.max_position_embeddings:
        raise ValueError(
            f"prompt {s} + max_new_tokens {max_new_tokens} exceeds the "
            f"learned position table ({args.max_position_embeddings})")
    sample = bool(np.asarray(temperature) != 0.0)
    use_eos = eos_token_id is not None
    return _gpt_generate_jit(params, args, jnp.asarray(prompt_ids),
                             max_new_tokens, sample,
                             jnp.float32(temperature if sample else 1.0),
                             jnp.float32(top_p), key, use_eos,
                             jnp.int32(eos_token_id if use_eos else 0),
                             jnp.int32(pad_token_id))


@functools.partial(jax.jit, static_argnames=("args", "max_new_tokens",
                                             "sample", "use_eos"))
def _gpt_generate_jit(params, args, prompt_ids, max_new_tokens, sample,
                      temperature, top_p, key, use_eos=False, eos_id=0,
                      pad_id=0):
    b, s = prompt_ids.shape
    max_len = s + max_new_tokens
    L = args.num_layers
    hd = lf.head_dim(args)
    ck = jnp.zeros((L, b, args.num_heads, max_len, hd),
                   params["word_emb"].dtype)
    cv = jnp.zeros_like(ck)

    def fwd(ids, ck, cv, pos):
        return _gpt_forward_cached(params, ids, ck, cv, pos, args)

    return _decode_loop(fwd, prompt_ids, ck, cv, max_new_tokens, sample,
                        temperature, top_p, key, use_eos,
                        jnp.asarray(eos_id), jnp.asarray(pad_id))
