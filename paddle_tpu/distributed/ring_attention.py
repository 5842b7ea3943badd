"""Ring attention: context parallelism over a mesh axis.

The reference snapshot has NO ring/context parallelism (SURVEY §2.2 —
long context there is Megatron-SP + flash attention + recompute). On TPU,
sequence scale-out beyond one chip is a first-class requirement, and the
ICI torus makes the ring pattern native: shard the sequence over a 'cp'
mesh axis, keep q resident, and rotate the k/v shards around the ring with
`ppermute` while merging per-block flash attention results with online
log-sum-exp combining ("Ring Attention with Blockwise Transformers",
Liu et al., 2023 — the public recipe; see PAPERS.md).

Non-causal: each rank does s/P x s FLOPs with one ICI hop per step, and
XLA overlaps the next ppermute with the current block's compute. Causal
with contiguous sharding is imbalanced — rank r computes r+1 of P blocks,
so lockstep wall-clock follows the last rank (~half the ring's compute
idles); zig-zag (striped) sequence sharding that gives every rank an
early+late slice is the planned fix. The per-block kernel is the
framework's Pallas flash attention (paddle_tpu/kernels/flash_attention.py)
on TPU, the fused XLA fallback elsewhere.

Use inside shard_map with the sequence dim of q/k/v sharded over
`axis_name`:

    out = ring_attention(q, k, v, axis_name="cp", causal=True)

Backward is jax AD: ppermute transposes to the reverse rotation and each
block replays through the flash kernel's custom vjp. The rotated kv shards
the scan carries are saved for backward, so per-rank residual memory is
O(s) while *compute and activations* scale as O(s/P) — the compute win of
ring attention; a recompute-in-reverse custom vjp (O(s/P) memory) is the
planned refinement.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["ring_attention", "ulysses_attention"]


def _full_block(q, k, v, fa, sm_scale, causal, interpret=False):
    b, sq, h, d = q.shape
    if (interpret or jax.default_backend() == "tpu") and fa.supports(
            q.shape, k.shape, q.dtype.itemsize):
        # differentiable (out, lse): the custom vjp folds the lse cotangent
        # from the ring merge into the flash backward's delta
        # (tests/test_flash_attention.py::test_with_lse_vjp checks the math)
        try:
            return fa.flash_attention_with_lse(q, k, v, causal, sm_scale,
                                               interpret)
        except Exception as e:  # vma-typed lowering gaps: fall back loudly
            import warnings

            warnings.warn(f"ring attention: Pallas block failed "
                          f"({type(e).__name__}: {e}); using the XLA path")
    # XLA fallback with explicit lse (GQA: repeat kv heads here; the Pallas
    # path above handles fewer kv heads natively)
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qh, kh, vh = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh.astype(jnp.float32),
                        kh.astype(jnp.float32)) * sm_scale
    if causal:
        ql, kl = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((ql, kl), bool), k=kl - ql)
        logits = jnp.where(mask, logits, -1e30)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    lse = (m + jnp.log(jnp.maximum(l, 1e-30)))[..., 0]
    out = jnp.einsum("bhqk,bhkd->bhqd", (p / jnp.maximum(l, 1e-30)),
                     vh.astype(jnp.float32))
    return jnp.swapaxes(out, 1, 2).astype(q.dtype), lse


def _merge(out_a, lse_a, out_b, lse_b):
    """Combine two normalized partial attentions via log-sum-exp weights."""
    new_lse = jnp.logaddexp(lse_a, lse_b)
    wa = jnp.exp(lse_a - new_lse)[..., None]           # [b,h,sq,1]
    wb = jnp.exp(lse_b - new_lse)[..., None]
    oa = jnp.swapaxes(out_a, 1, 2).astype(jnp.float32)
    ob = jnp.swapaxes(out_b, 1, 2).astype(jnp.float32)
    merged = jnp.swapaxes(oa * wa + ob * wb, 1, 2)
    return merged.astype(out_a.dtype), new_lse


def ring_attention(q, k, v, axis_name, causal=True, sm_scale=None,
                   interpret=False):
    """q/k/v: LOCAL sequence shards [b, s_local, h(,hk), d] inside a
    shard_map over `axis_name` (P ranks; global seq = P * s_local, rank r
    holding positions [r*s_local, (r+1)*s_local))."""
    import math

    b, s_local, h, d = q.shape
    sm_scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    P = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % P) for i in range(P)]

    def block(q, kk, vv, diag):
        from paddle_tpu.kernels import flash_attention as fa

        return _full_block(q, kk, vv, fa, sm_scale, causal=diag,
                           interpret=interpret)

    def step(carry, i):
        kk, vv, out, lse = carry
        # at step i this rank holds the kv shard of rank (rank - i) mod P
        src = jnp.mod(rank - i, P)

        def visible(op):
            # src == rank is the diagonal block (causal within); src < rank
            # is strictly in the past (fully visible)
            return jax.lax.cond(
                src == rank,
                lambda o: block(q, o[0], o[1], True),
                lambda o: block(q, o[0], o[1], False), op)

        def hidden(op):
            # strictly-in-the-future shard: contributes nothing; zero-scaled
            # adds keep the branch outputs' vma types identical
            tie = jnp.sum(op[0]).astype(jnp.float32) * 0
            z = jnp.zeros_like(q) + tie.astype(q.dtype)
            l = jnp.full((b, h, s_local), -1e30, jnp.float32) + tie
            return z, l

        if causal:
            blk_out, blk_lse = jax.lax.cond(src <= rank, visible, hidden,
                                            (kk, vv))
        else:
            blk_out, blk_lse = block(q, kk, vv, False)
        out, lse = _merge(out, lse, blk_out, blk_lse)
        kk = jax.lax.ppermute(kk, axis_name, perm)
        vv = jax.lax.ppermute(vv, axis_name, perm)
        return (kk, vv, out, lse), None

    out0 = jnp.zeros_like(q)  # inherits q's cp-varying type
    # tie lse0 to q's FULL vma set (inside a hybrid mesh q may vary over
    # dp/pp too, not just the ring axis — a hard-coded pcast under-types
    # the scan carry)
    tie0 = jnp.sum(q).astype(jnp.float32) * 0
    lse0 = jnp.full((b, h, s_local), -1e30, jnp.float32) + tie0
    (_, _, out, _), _ = jax.lax.scan(step, (k, v, out0, lse0),
                                     jnp.arange(P))
    return out


def ulysses_attention(q, k, v, axis_name, causal=True, sm_scale=None,
                      interpret=False):
    """DeepSpeed-Ulysses-style sequence parallelism ("Ulysses: System
    Optimizations for Enabling Long-Sequence Transformer Training",
    Jacobs et al., 2023 — public recipe; the reference snapshot has no
    equivalent): q/k/v arrive SEQUENCE-sharded [b, s/P, h, d] over
    `axis_name`; one all_to_all re-shards them to HEAD-sharded
    [b, s, h/P, d], every rank runs ordinary (flash) attention over the
    FULL sequence for its head group, and the inverse all_to_all restores
    sequence sharding.

    vs ring attention: two all_to_alls of O(s*h/P) per call instead of P
    ppermute hops of O(s/P * h); causal balance is perfect (each rank owns
    whole heads, not sequence slices), but P must divide num_heads and
    peak activation is O(s) per rank (full-sequence attention per head
    group). Prefer ulysses when heads >> P and the ICI all_to_all is
    cheap; ring when sequence alone must scale past per-rank memory.

    Use inside shard_map with the seq dim sharded over `axis_name`:

        out = ulysses_attention(q, k, v, axis_name="sp", causal=True)

    Backward is jax AD (all_to_all transposes to the inverse all_to_all).
    """
    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.nn.functional.flash_attention import _sdpa_reference

    P = jax.lax.axis_size(axis_name)
    h, hk = q.shape[2], k.shape[2]
    if h % P != 0 or hk % P != 0:
        raise ValueError(
            f"ulysses needs q heads ({h}) AND kv heads ({hk}) divisible by "
            f"the '{axis_name}' axis size ({P}); for GQA with few kv heads "
            "use ring_attention instead")

    def seq_to_heads(x):
        # [b, s/P, h, d] -> [b, s, h/P, d]
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    out = None
    if (interpret or jax.default_backend() == "tpu") and fa.supports(
            qh.shape, kh.shape, qh.dtype.itemsize):
        try:
            out = fa.flash_attention_fwd(qh, kh, vh, causal=causal,
                                         scale=sm_scale,
                                         interpret=interpret)
        except Exception:  # unsupported tiling: fused-XLA fallback
            out = None
    if out is None:
        out = _sdpa_reference(qh, kh, vh, causal=causal, scale=sm_scale)
    return heads_to_seq(out)
