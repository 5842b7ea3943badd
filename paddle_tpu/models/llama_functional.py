"""Pure-functional Llama core for the compiled (jit/pjit/shard_map) path.

This is the TPU-native replacement for the reference's static-graph hybrid
pipeline (`python/paddle/distributed/fleet/meta_parallel/pipeline_parallel.py:684`
forward_backward_pipeline + `fleet/layers/mpu/mp_layers.py` TP layers +
`mp_ops.py:77-385` collectives): one set of pure functions over a params
pytree, usable three ways —

  1. plain single-device:            forward_and_loss(params, ids, labels, cfg)
  2. GSPMD (jit + NamedSharding):    same functions; XLA inserts collectives
  3. manual SPMD (shard_map):        pass mp_axis='mp' (+ sp=True) and the
     functions issue the exact Megatron collectives by hand — psum for
     row-parallel matmuls (reference `_mp_allreduce`, mp_ops.py:259),
     all_gather/psum_scatter on the sequence dim for sequence parallelism
     (reference `sequence_parallel_utils.py:85-147`), and vocab-parallel
     embedding + cross entropy (reference mp_layers.py:49,744).

Every weight is stored [in, out] so contractions land on the MXU untransposed.
Layer params are *stacked* along a leading n_layers dim and iterated with
`lax.scan` — static control flow, one compiled layer body, and the leading
dim is exactly what pipeline parallelism shards over the 'pp' mesh axis.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class LlamaArgs(NamedTuple):
    """Static (hashable) model config used inside jit."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    rope_theta: float
    rms_eps: float
    use_flash: bool = True

    @staticmethod
    def from_config(cfg):
        return LlamaArgs(
            vocab_size=cfg.vocab_size,
            hidden_size=cfg.hidden_size,
            intermediate_size=cfg.intermediate_size,
            num_layers=cfg.num_hidden_layers,
            num_heads=cfg.num_attention_heads,
            num_kv_heads=cfg.num_key_value_heads,
            rope_theta=cfg.rope_theta,
            rms_eps=cfg.rms_norm_eps,
            use_flash=cfg.use_flash_attention,
        )


def head_dim(args):
    """Width of one attention head. THE one derivation: every cache shape,
    RoPE table and projection reshape of the dense family asks here."""
    return args.hidden_size // args.num_heads


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------


def init_layer_params(args: LlamaArgs, key, dtype=jnp.float32):
    """One decoder layer's params (unstacked)."""
    h, i = args.hidden_size, args.intermediate_size
    hd = head_dim(args)
    ks = jax.random.split(key, 7)
    init = jax.nn.initializers.normal(0.02)
    return {
        "wq": init(ks[0], (h, args.num_heads * hd), dtype),
        "wk": init(ks[1], (h, args.num_kv_heads * hd), dtype),
        "wv": init(ks[2], (h, args.num_kv_heads * hd), dtype),
        "wo": init(ks[3], (args.num_heads * hd, h), dtype),
        "w_gate": init(ks[4], (h, i), dtype),
        "w_up": init(ks[5], (h, i), dtype),
        "w_down": init(ks[6], (i, h), dtype),
        "ln1": jnp.ones((h,), dtype),
        "ln2": jnp.ones((h,), dtype),
    }


def init_params(args: LlamaArgs, key, dtype=jnp.float32):
    """Full model params. layers.* leaves have leading dim [num_layers]."""
    k_emb, k_head, k_layers = jax.random.split(key, 3)
    init = jax.nn.initializers.normal(0.02)
    layer_keys = jax.random.split(k_layers, args.num_layers)
    layers = jax.vmap(lambda k: init_layer_params(args, k, dtype))(layer_keys)
    return {
        "embedding": init(k_emb, (args.vocab_size, args.hidden_size), dtype),
        "layers": layers,
        "final_norm": jnp.ones((args.hidden_size,), dtype),
        "lm_head": init(k_head, (args.hidden_size, args.vocab_size), dtype),
    }


# --------------------------------------------------------------------------
# building blocks (mp_axis=None -> single device / GSPMD; else shard_map SPMD)
# --------------------------------------------------------------------------


@jax.named_scope("pt.norm")
def rms_norm(x, w, eps):
    # Deliberately the jnp composition, NOT the Pallas kernel
    # (kernels/rms_norm.py): inside the compiled train step a pallas_call
    # is a fusion BARRIER — measured 21.5k -> 20.3k tok/s on the v5e
    # champion config when swapped in, because XLA can no longer fold the
    # norm into the neighboring matmul prologues. (The Pallas pair also
    # lost standalone; see its module docstring — it is dispatched
    # nowhere and kept as a recorded negative result.)
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def rope_tables(seq_len, head_dim, theta):
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


def apply_rope_bcast(q, k, c, s):
    """RoPE with cos/sin ALREADY broadcast to q/k's rank — the one
    rotate-half implementation behind both the sequence-major path
    (apply_rope) and the per-row serving decode path (each batch row at
    its own position; generation._layer_step)."""
    def rot(x):
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([-x2, x1], axis=-1)

    dt = q.dtype
    q32, k32 = q.astype(jnp.float32), k.astype(jnp.float32)
    return ((q32 * c + rot(q32) * s).astype(dt),
            (k32 * c + rot(k32) * s).astype(dt))


def apply_rope(q, k, cos, sin):
    return apply_rope_bcast(q, k, cos[None, :, None, :],
                            sin[None, :, None, :])


def _attention(q, k, v, use_flash):
    """q: [b, s, h, d]; k/v: [b, s, hk, d] (GQA: hk may divide h), causal."""
    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.nn.functional.flash_attention import _sdpa_reference

    if (use_flash and jax.default_backend() == "tpu"
            and fa.supports(q.shape, k.shape, q.dtype.itemsize)):
        return fa.flash_attention_fwd(q, k, v, causal=True)
    return _sdpa_reference(q, k, v, causal=True)


def decoder_layer(p, h, cos, sin, args: LlamaArgs, mp_axis=None, mp_degree=1,
                  sp=False, cp_axis=None, cp_mode="ring"):
    """One decoder block. Under shard_map (mp_axis set) the weights held by
    this device are the mp-shards: wq/wk/wv/w_gate/w_up sharded on the out
    dim, wo/w_down on the in dim; heads are local heads.

    cp_axis: context parallelism — h arrives SEQUENCE-sharded over this
    mesh axis (the caller slices RoPE tables to the local chunk); attention
    runs ring_attention (kv rotating over the cp ring) or ulysses
    (all_to_all seq<->head reshard) instead of the local kernel. MLP and
    norms are per-token, so they need no cp collective at all — long
    context costs exactly one attention exchange per layer."""
    nh = args.num_heads // (mp_degree if mp_axis else 1)
    nkv = max(1, args.num_kv_heads // (mp_degree if mp_axis else 1))
    hd = head_dim(args)

    def maybe_gather_seq(x):
        # SP: activations arrive seq-sharded over the mp axis; gather full seq
        # for attention/matmul (reference AllGatherOp,
        # sequence_parallel_utils.py:120).
        if sp and mp_axis:
            return jax.lax.all_gather(x, mp_axis, axis=1, tiled=True)
        return x

    def reduce_out(x):
        # Row-parallel output reduction: psum (reference _mp_allreduce,
        # mp_ops.py:259), or reduce-scatter back to seq shards under SP
        # (reference ReduceScatterOp, sequence_parallel_utils.py:134).
        if mp_axis is None:
            return x
        if sp:
            return jax.lax.psum_scatter(x, mp_axis, scatter_dimension=1, tiled=True)
        return jax.lax.psum(x, mp_axis)

    from jax.ad_checkpoint import checkpoint_name

    # --- attention ---
    hin = checkpoint_name(rms_norm(h, p["ln1"], args.rms_eps), "ln1")
    with jax.named_scope("pt.attention"):
        hin = maybe_gather_seq(hin)
        b, s = hin.shape[0], hin.shape[1]
        q = (hin @ p["wq"]).reshape(b, s, nh, hd)
        k = (hin @ p["wk"]).reshape(b, s, nkv, hd)
        v = (hin @ p["wv"]).reshape(b, s, nkv, hd)
        cos_t, sin_t = cos[:s], sin[:s]
        q, k = apply_rope(q, k, cos_t, sin_t)
        q = checkpoint_name(q, "rope_q")
        k = checkpoint_name(k, "rope_k")
        if cp_axis is not None:
            from paddle_tpu.distributed.ring_attention import (
                ring_attention, ulysses_attention)

            attn_fn = (ring_attention if cp_mode == "ring"
                       else ulysses_attention)
            attn = attn_fn(q, k, v, axis_name=cp_axis, causal=True)
        else:
            attn = _attention(q, k, v, args.use_flash)
        # remat='lean' saves the flash residuals by name — the tags live
        # inside the kernel's custom-vjp fwd (kernels/flash_attention.py
        # _fa_fwd)
        attn = attn.reshape(b, s, nh * hd)
        h = h + reduce_out(attn @ p["wo"])

    # --- MLP (SwiGLU) ---
    hin = checkpoint_name(rms_norm(h, p["ln2"], args.rms_eps), "ln2")
    with jax.named_scope("pt.mlp"):
        hin = maybe_gather_seq(hin)
        act = jax.nn.silu(hin @ p["w_gate"]) * (hin @ p["w_up"])
        h = h + reduce_out(act @ p["w_down"])
    return h


def run_layers(stack, h, cos, sin, args: LlamaArgs, mp_axis=None, mp_degree=1,
               sp=False, remat=True, zero_axis=None, zero_skip=(),
               cp_axis=None, cp_mode="ring", unroll=False):
    """lax.scan over stacked layer params (leading dim = layers).

    unroll=True replaces the scan with a Python loop over static slices of
    the stack. Profiling the scan on TPU (r5) showed ~17% of the train step
    in `dynamic-update-slice` fusions: scan must STACK every layer's
    remat-saved residuals into [L, ...] buffers in forward and re-slice
    them in backward — pure HBM copy traffic. The unrolled loop keeps each
    layer's residuals as separate buffers (no copies) at the cost of an
    L-times-larger program (slower first compile, same steady-state cache).
    Only the no-pipeline fast path uses it; the pp-sharded engine needs the
    stacked scan form.

    remat: True/'full' (recompute everything — min memory), 'half'
    (checkpoint every other layer — half the activation memory of no-remat
    for half the recompute of full, the MFU sweet spot on chips where full
    no-remat doesn't fit), 'dots' (save matmul outputs, recompute
    elementwise), or False.

    zero_axis: ZeRO-3 (reference group_sharded_stage3.py:85): layer params
    arrive SHARDED over this mesh axis; each scan step all-gathers just its
    layer's weights right before use (the stage-3 pre-forward hook) and the
    gather's AD transpose is psum_scatter — grads leave reduce-scattered to
    their owner shards with no hand-written reducer.

    zero_skip: leaf names that arrive REPLICATED over zero_axis (their first
    param axis did not divide the shard degree — the engine's per-leaf
    fallback) and therefore must not be gathered."""
    base_body = functools.partial(decoder_layer, args=args, mp_axis=mp_axis,
                                  mp_degree=mp_degree, sp=sp,
                                  cp_axis=cp_axis, cp_mode=cp_mode)
    if zero_axis is None:
        body = base_body
    else:
        def body(lp, h, cos, sin):
            full = {k: (a if k in zero_skip else
                        jax.lax.all_gather(a, zero_axis, axis=0, tiled=True))
                    for k, a in lp.items()}
            return base_body(full, h, cos, sin)
    if remat == "half" and stack_leading_dim(stack) % 2 != 0:
        import warnings

        warnings.warn("remat='half' needs an even layer count; falling back "
                      "to full remat")
        remat = True
    if remat == "half":
        ck = jax.checkpoint(body)
        if unroll:
            for i in range(stack_leading_dim(stack)):
                lp = jax.tree.map(lambda a: a[i], stack)
                h = (body if i % 2 == 0 else ck)(lp, h, cos, sin)
            return h

        def pair_step(carry, lp2):
            lp_a = jax.tree.map(lambda a: a[0], lp2)
            lp_b = jax.tree.map(lambda a: a[1], lp2)
            h = body(lp_a, carry, cos, sin)   # internals saved
            h = ck(lp_b, h, cos, sin)         # internals recomputed
            return h, None

        paired = jax.tree.map(
            lambda a: a.reshape((a.shape[0] // 2, 2) + a.shape[1:]), stack)
        h, _ = jax.lax.scan(pair_step, h, paired)
        return h
    if remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    elif remat == "lean":
        # dots + the flash-attention output by name: the flash output is a
        # pallas custom call — not a dot — so the plain 'dots' policy pays a
        # FULL attention-forward recompute in backward on top of running the
        # flash bwd kernels. Saving it costs one [b,s,h,d] tensor per layer
        # and removes that recompute (measured ~18ms/step on the h2048
        # primary config, TPU v5e).
        body = jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                jax.checkpoint_policies.save_only_these_names(
                    "attn", "attn_lse")))
    elif remat:
        body = jax.checkpoint(body)

    if unroll:
        for i in range(stack_leading_dim(stack)):
            lp = jax.tree.map(lambda a: a[i], stack)
            h = body(lp, h, cos, sin)
        return h

    def step(carry, lp):
        return body(lp, carry, cos, sin), None

    h, _ = jax.lax.scan(step, h, stack)
    return h


def stack_leading_dim(stack):
    return jax.tree.leaves(stack)[0].shape[0]


def embed_lookup(table, ids, args: LlamaArgs, mp_axis=None, mp_degree=1):
    """Vocab-parallel embedding (reference VocabParallelEmbedding,
    mp_layers.py:49): table local shard [V/mp, h]; out-of-shard ids
    contribute zeros, psum combines."""
    if mp_axis is None:
        return jnp.take(table, ids, axis=0)
    per = args.vocab_size // mp_degree
    rank = jax.lax.axis_index(mp_axis)
    start = rank * per
    local = ids - start
    valid = (local >= 0) & (local < per)
    local = jnp.clip(local, 0, per - 1)
    out = jnp.take(table, local, axis=0)
    out = jnp.where(valid[..., None], out, 0.0)
    return jax.lax.psum(out, mp_axis)


@jax.named_scope("pt.ce_epilogue")
def parallel_cross_entropy(logits, labels, args: LlamaArgs, mp_axis=None,
                           mp_degree=1):
    """Softmax cross entropy over (possibly vocab-sharded) logits.

    Reference ParallelCrossEntropy (mp_layers.py:744) /
    `_c_softmax_with_cross_entropy` (mp_ops.py:385): max and sum-exp are
    psum-reduced over the mp axis; the true-label logit is recovered with a
    mask + psum.
    """
    logits = logits.astype(jnp.float32)
    if mp_axis is None:
        m = jnp.max(logits, axis=-1, keepdims=True)
        lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
        true_logit = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - true_logit)
    per = args.vocab_size // mp_degree
    rank = jax.lax.axis_index(mp_axis)
    start = rank * per
    m_local = jnp.max(logits, axis=-1, keepdims=True)
    # max is only a numerical shift; stop_gradient keeps pmax out of the vjp
    m = jax.lax.pmax(jax.lax.stop_gradient(m_local), mp_axis)
    sum_local = jnp.sum(jnp.exp(logits - m), axis=-1)
    lse = jnp.log(jax.lax.psum(sum_local, mp_axis)) + m[..., 0]
    local_lab = labels - start
    valid = (local_lab >= 0) & (local_lab < per)
    local_lab = jnp.clip(local_lab, 0, per - 1)
    tl = jnp.take_along_axis(logits, local_lab[..., None], axis=-1)[..., 0]
    true_logit = jax.lax.psum(jnp.where(valid, tl, 0.0), mp_axis)
    return jnp.mean(lse - true_logit)


def ce_blocking(b, s, vocab_local, chunk):
    """How the fused head + CE cuts its [b * s, vocab_local] logits:
    (token tile T, vocab block Vb, number of vocab blocks).

    `chunk` bounds the live logits block as it always has: T * Vb is at
    most b * chunk * vocab_local elements. Inside that budget the float32
    accumulators' traffic picks T, a divisor of b * s: the hidden gradient
    is read and written once a vocab block (ceil(vocab_local / Vb) passes
    over b * s rows), a block of the head's gradient once a token tile
    (b * s / T passes over vocab_local columns), `hidden` wide both. Ties
    go to the wider tile. Vb is whole 128-lane groups where it holds one.
    """
    n = b * s
    budget = b * max(1, min(int(chunk), s)) * vocab_local
    best = None
    for k in range(1, n + 1):
        if best is not None and k * vocab_local >= best[0]:
            break
        if n % k:
            continue
        vb = max(1, min(budget // (n // k), vocab_local))
        if 128 <= vb < vocab_local:
            vb -= vb % 128
        nb = -(-vocab_local // vb)
        cost = nb * n + k * vocab_local
        if best is None or cost < best[0]:
            best = (cost, n // k, vb, nb)
    return best[1:]


def _ce_logits(tile, block):
    return (tile @ block).astype(jnp.float32)  # [.., T, Vb]


def _label_hits(lab, col0, width):
    """[.., T, width] mask of each token's true-label column inside the
    block that starts at column `col0`; a label of another block (under mp:
    of another shard) matches none."""
    cols = jax.lax.broadcasted_iota(lab.dtype, lab.shape + (width,), lab.ndim)
    return (lab - col0)[..., None] == cols


def _ce_block_stats(stats, logits, lab, col0):
    """Fold one [.., T, Vb] logits block into the running softmax
    statistics: (max, sum of exponentials rescaled to that max, true-label
    logit), three [.., T] float32 vectors."""
    m, l, tl = stats
    m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
    l = l * jnp.exp(m - m_new) + jnp.sum(
        jnp.exp(logits - m_new[..., None]), axis=-1)
    hits = _label_hits(lab, col0, logits.shape[-1])
    return m_new, l, tl + jnp.sum(jnp.where(hits, logits, 0.0), axis=-1)


def _ce_block_grads(logits, lse, lab, col0, tile, block, inv_n):
    """One block's share of both gradients, float32 accumulation: the
    softmax-CE logits gradient is the closed form (softmax - onehot) / n
    (the Liger-kernel observation), so forward knows it once the
    normaliser is known. Returns (tile^T @ d_logits [hidden, Vb],
    d_logits @ block^T [.., T, hidden])."""
    onehot = _label_hits(lab, col0, logits.shape[-1]).astype(jnp.float32)
    dl = ((jnp.exp(logits - lse[..., None]) - onehot)
          * inv_n).astype(tile.dtype)
    d_block = jnp.einsum("...th,...tv->hv", tile, dl,
                         preferred_element_type=jnp.float32)
    d_tile = jnp.einsum("...tv,hv->...th", dl, block,
                        preferred_element_type=jnp.float32)
    return d_block, d_tile


def _zeros_varying_like(shape, *operands):
    """float32 zeros that vary over every mesh axis one of `operands`
    does: under shard_map(check_vma=True) a scan's initial carry must
    carry exactly the varying-mesh-axes type its body produces, so plain
    `jnp.zeros` only works off the mesh."""
    z = jnp.zeros(shape, jnp.float32)
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.lax.pcast(z, tuple(vma), to="varying") if vma else z


def _over_blocks(fn, carry, head, vb):
    """Fold `fn(carry, block, col0) -> (carry, y)` over the head's column
    blocks [hidden, vb]; a short last block runs after the scan at its own
    static width (no padding, no mask). Returns (carry, the full blocks'
    ys stacked, the last block's y or None)."""
    nfull, tail = divmod(head.shape[1], vb)

    def body(c, j):
        block = jax.lax.dynamic_slice_in_dim(head, j * vb, vb, axis=1)
        return fn(c, block, j * vb)

    carry, ys = jax.lax.scan(body, carry, jnp.arange(nfull))
    y_tail = None
    if tail:
        carry, y_tail = fn(carry, head[:, nfull * vb:], nfull * vb)
    return carry, ys, y_tail


@jax.tree_util.register_static
class _MeshAxes(frozenset):
    """A primal's varying-mesh-axes set, carried through the residuals as
    static data."""


def _ce_operands(h, head, labels, mp_axis, chunk):
    """The epilogue's operands as its passes take them: h as token tiles
    [n_tiles, T, hidden], the labels [n_tiles, T] counted from this vocab
    shard's first column, and the vocab block's width."""
    b, s, hidden = h.shape
    t, vb, _ = ce_blocking(b, s, head.shape[1], chunk)
    if mp_axis is not None:
        labels = labels - jax.lax.axis_index(mp_axis) * head.shape[1]
    return h.reshape(-1, t, hidden), labels.reshape(-1, t), vb


@jax.named_scope("pt.ce_stats")
def _ce_stats(tiles, labs, head, vb, mp_axis):
    """Pass 1: every token's log-sum-exp and true-label logit ([n_tiles,
    T] float32 each) from one sweep of the head's blocks. Under `mp_axis`
    the shards' statistics meet once, after the sweep. Where one block is
    the whole problem its logits are returned too, for pass 2 to keep."""
    zero = _zeros_varying_like(labs.shape, tiles, labs, head)
    stats = (zero - jnp.inf, zero, zero)
    kept = None
    if tiles.shape[0] == 1 and vb == head.shape[1]:
        kept = _ce_logits(tiles, head)
        stats = _ce_block_stats(stats, kept, labs, 0)
    else:
        def block_stats(stats, block, col0):
            def tile_stats(_, x):
                tile, lab, *st = x
                return None, _ce_block_stats(st, _ce_logits(tile, block),
                                             lab, col0)

            return jax.lax.scan(tile_stats, None,
                                (tiles, labs, *stats))[1], None

        stats = _over_blocks(block_stats, stats, head, vb)[0]
    m, l, tl = stats
    if mp_axis is not None:
        # the max is only a numerical shift
        m_all = jax.lax.pmax(m, mp_axis)
        l, tl = jax.lax.psum((l * jnp.exp(m - m_all), tl), mp_axis)
        m = m_all
    return jnp.log(l) + m, tl, kept


@jax.named_scope("pt.ce_grads")
def _ce_grads(tiles, labs, lse, head, vb, inv_n, kept):
    """Pass 2: re-form each block's logits (or take the one `kept`) and
    form both gradients. A block of the head's gradient is complete after
    one sweep of the token tiles and is written once, in the head's dtype;
    what is accumulated across blocks is the hidden gradient. Returns
    (d_tiles [n_tiles, T, hidden] float32, partial over the local vocab
    shard; d_head [hidden, vocab_local], vocab-sharded like the weight)."""
    if kept is not None:
        d_head, d_tiles = _ce_block_grads(kept, lse, labs, 0, tiles, head,
                                          inv_n)
        return d_tiles, d_head.astype(head.dtype)
    operands = (tiles, labs, lse, head)

    def block_grads(d_tiles, block, col0):
        def tile_grads(d_block, x):
            tile, lab, lse_t, d_tile = x
            db, dt = _ce_block_grads(_ce_logits(tile, block), lse_t, lab,
                                     col0, tile, block, inv_n)
            return d_block + db, d_tile + dt

        d_block, d_tiles = jax.lax.scan(
            tile_grads, _zeros_varying_like(block.shape, *operands),
            (tiles, labs, lse, d_tiles))
        return d_tiles, d_block.astype(head.dtype)

    d_tiles, d_blocks, d_tail = _over_blocks(
        block_grads, _zeros_varying_like(tiles.shape, *operands), head, vb)
    d_head = jnp.moveaxis(d_blocks, 0, 1).reshape(head.shape[0], -1)
    if d_tail is not None:
        d_head = jnp.concatenate([d_head, d_tail], axis=1)
    return d_tiles, d_head


@jax.named_scope("pt.ce_epilogue")
def _fused_ce_loss_only(h, head, labels, args: LlamaArgs, mp_axis, mp_degree,
                        chunk):
    """Primal (not-being-differentiated) path: pass 1 alone."""
    tiles, labs, vb = _ce_operands(h, head, labels, mp_axis, chunk)
    lse, true_logit, _ = _ce_stats(tiles, labs, head, vb, mp_axis)
    return jnp.mean(lse - true_logit)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def fused_linear_cross_entropy(h, head, labels, args: LlamaArgs,
                               mp_axis=None, mp_degree=1, chunk=128):
    """lm_head matmul + softmax CE, in blocks over the VOCABULARY.

    Mean CE over all b*s tokens, numerically matching
    `parallel_cross_entropy(h @ head, labels, ...)` — but the [b, s, vocab]
    logits never materialize in forward OR backward. `chunk` bounds the
    live logits block at b * chunk * vocab_local elements; `ce_blocking`
    turns that budget into a token tile of T rows (the whole micro-batch
    where it can be) and vocab blocks of Vb columns. Forward sweeps the
    head's blocks twice: pass 1 finds every token's log-sum-exp, pass 2
    re-forms a block's logits and forms d(hidden)/d(head) from the closed
    form (softmax - onehot) / n, each block of d(head) once, so backward
    only scales the stored gradients and no float32 [hidden, vocab] value
    exists. Four head matmuls of T rows in the place of three of `chunk`
    rows. Composes with the vocab-parallel (mp_axis) path: the shards'
    softmax statistics meet in one pmax and one psum, d_head stays the
    local shard's grad. Any b, s and chunk.
    """
    return _fused_ce_loss_only(h, head, labels, args, mp_axis, mp_degree,
                               chunk)


@jax.named_scope("pt.ce_epilogue")
def _fused_ce_fwd(h, head, labels, args: LlamaArgs, mp_axis, mp_degree,
                  chunk):
    tiles, labs, vb = _ce_operands(h, head, labels, mp_axis, chunk)
    lse, true_logit, kept = _ce_stats(tiles, labs, head, vb, mp_axis)
    d_tiles, d_head = _ce_grads(tiles, labs, lse, head, vb,
                                1.0 / labels.size, kept)
    d_h = d_tiles.astype(h.dtype).reshape(h.shape)
    # the cotangent carries its primal's type: an h that is replicated over
    # mp wants the shards' partials summed; an h typed VARYING over mp (the
    # sequence-parallel all_gather's output) wants this rank's partial —
    # its producer's transpose (psum_scatter) does the sum
    if mp_axis is not None and mp_axis not in jax.typeof(h).vma:
        d_h = jax.lax.psum(d_h, mp_axis)
    res = (d_h, d_head, labels,
           _MeshAxes(jax.typeof(h).vma), _MeshAxes(jax.typeof(head).vma))
    return jnp.mean(lse - true_logit), res


@jax.named_scope("pt.ce_epilogue")
def _fused_ce_bwd(args, mp_axis, mp_degree, chunk, res, g):
    d_h, d_head, labels, h_vma, head_vma = res

    def cotangent(ct, primal_vma):
        # under shard_map(check_vma=True) a cotangent carries its primal's
        # type: sum over the mesh axes it varies over and the primal does
        # not — what AD's own transpose of the implicit replicated->varying
        # cast does (e.g. the dp sum of a dp-replicated head's grads)
        ct = ct * g.astype(ct.dtype)
        extra = tuple(jax.typeof(ct).vma - primal_vma)
        return jax.lax.psum(ct, extra) if extra else ct

    return (cotangent(d_h, h_vma), cotangent(d_head, head_vma),
            np.zeros(labels.shape, dtype=jax.dtypes.float0))


fused_linear_cross_entropy.defvjp(_fused_ce_fwd, _fused_ce_bwd)


def forward(params, ids, args: LlamaArgs, mp_axis=None, mp_degree=1, sp=False,
            remat=True, unroll=False):
    """Full forward to logits. ids: [b, s] int32."""
    h = forward_hidden(params, ids, args, mp_axis, mp_degree, sp, remat,
                       unroll=unroll)
    return h @ params["lm_head"]


def forward_and_loss(params, ids, labels, args: LlamaArgs, mp_axis=None,
                     mp_degree=1, sp=False, remat=True, loss_chunk=None,
                     unroll=False):
    """loss_chunk: fused lm_head + CE (`fused_linear_cross_entropy`) — the
    [b, s, vocab] logits never materialize in forward or backward: the
    live logits block holds b * loss_chunk * vocab_local elements (peak
    memory drops by ~s/loss_chunk) and backward re-runs no vocab matmul.
    Works on the vocab-parallel (mp_axis) path too, for any b, s and
    loss_chunk."""
    if loss_chunk:
        h = forward_hidden(params, ids, args, mp_axis, mp_degree, sp, remat,
                           unroll=unroll)
        return fused_linear_cross_entropy(h, params["lm_head"], labels,
                                          args, mp_axis, mp_degree,
                                          int(loss_chunk))
    logits = forward(params, ids, args, mp_axis, mp_degree, sp, remat,
                     unroll=unroll)
    return parallel_cross_entropy(logits, labels, args, mp_axis, mp_degree)


def forward_hidden(params, ids, args: LlamaArgs, mp_axis=None, mp_degree=1,
                   sp=False, remat=True, unroll=False):
    """Forward up to the final hidden states (pre lm_head)."""
    h = embed_lookup(params["embedding"], ids, args, mp_axis, mp_degree)
    if sp and mp_axis:
        # enter the seq-sharded region (reference ScatterOp,
        # sequence_parallel_utils.py:85): keep this rank's seq slice
        s_local = ids.shape[1] // mp_degree
        rank = jax.lax.axis_index(mp_axis)
        h = jax.lax.dynamic_slice_in_dim(h, rank * s_local, s_local, axis=1)
    cos, sin = rope_tables(ids.shape[1], head_dim(args), args.rope_theta)
    h = run_layers(params["layers"], h, cos, sin, args, mp_axis, mp_degree,
                   sp, remat, unroll=unroll)
    h = rms_norm(h, params["final_norm"], args.rms_eps)
    if sp and mp_axis:
        h = jax.lax.all_gather(h, mp_axis, axis=1, tiled=True)
    return h
