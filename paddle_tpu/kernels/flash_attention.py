"""Flash attention forward + backward as Pallas TPU kernels, with native GQA.

Reference counterpart: `paddle/phi/kernels/gpu/flash_attn_kernel.cu` and
`flash_attn_grad_kernel.cu` (CUDA flash-attn v2). TPU-native design:

- forward: online-softmax blockwise attention tiled for VMEM — q is blocked
  over the grid, k/v stream through a `fori_loop` with a running
  (max, sum, acc) triple; the causal variant bounds the k loop at the query
  block's diagonal so the MXU never touches fully-masked tiles. The kernel
  additionally emits the per-row logsumexp needed by the backward pass.
- backward: two kernels, the flash-attn-v2 recompute strategy. `dq` is
  blocked over query blocks (stream k/v), `dk`/`dv` are blocked over key
  blocks (stream q/dO) — both rebuild the probabilities from the stored
  logsumexp instead of materialising the [S, S] matrix, so backward memory
  stays O(S·D) like forward.
- GQA: `num_kv_heads < num_heads` is handled natively by the BlockSpec index
  maps (query head h reads kv head h // group) — kv is never repeated to the
  full head count, preserving the KV-memory win. The dk/dv grid carries the
  group as its innermost dimension so consecutive grid steps accumulate into
  the same kv-head output block in VMEM.

Layout at the public boundary is paddle's [batch, seq, heads, head_dim];
kernels run in [batch, heads, seq, head_dim].
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.kernels import tuning

_NEG_INF = -1e30

# autotune candidate grid (filtered per shape by _pick_block divisibility);
# tools/perf_sweep.py --blocks sweeps the same grid end-to-end
_BLOCK_CANDIDATES = (
    {"block_q": 256, "block_k": 256},
    {"block_q": 256, "block_k": 512},
    {"block_q": 512, "block_k": 256},
    {"block_q": 512, "block_k": 512},
    {"block_q": 512, "block_k": 1024},
    {"block_q": 1024, "block_k": 512},
    {"block_q": 1024, "block_k": 1024},
)


def _mk_measure(which, q_shape, k_shape, dtype, causal, sm_scale):
    """Build the autotuner's measure(blocks) -> seconds probe: compile the
    kernel at the candidate blocks on synthetic inputs and time it. Only
    invoked when PADDLE_KERNEL_AUTOTUNE=1 on a real TPU backend."""

    def measure(blocks):
        import time

        q = jnp.zeros(q_shape, dtype)
        k = jnp.zeros(k_shape, dtype)
        v = jnp.zeros(k_shape, dtype)
        bq, bk = blocks["block_q"], blocks["block_k"]
        if which == "fwd":
            fn = jax.jit(lambda q, k, v: _flash_fwd(
                q, k, v, causal, sm_scale, bq, bk)[0])
            args = (q, k, v)
        else:
            o, lse = jax.jit(functools.partial(
                _flash_fwd, causal=causal, sm_scale=sm_scale))(q, k, v)
            fn = jax.jit(lambda q, k, v, o, lse: _flash_bwd(
                q, k, v, o, lse, q, causal, sm_scale, bq, bk)[0])
            args = (q, k, v, o, lse)
        fn(*args).block_until_ready()  # compile outside the timed region
        t0 = time.perf_counter()
        for _ in range(3):
            out = fn(*args)
        out.block_until_ready()
        return (time.perf_counter() - t0) / 3

    return measure


def _pick_block(seq, preferred, floor=128, fallback=None):
    """Largest power-of-two block <= preferred that divides seq, not going
    below `floor`; `fallback` (if set) is returned when even the floor does
    not divide seq. Shared by the attention kernels and kernels/rms_norm."""
    b = preferred
    while b > floor and seq % b != 0:
        b //= 2
    if fallback is not None and seq % b != 0:
        return fallback
    return b


def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying `like`'s varying-mesh-axes type, so the
    kernels compose with shard_map(check_vma=True) (e.g. under the hybrid
    engine's mp axis or ring attention's cp axis)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale, causal,
                block_q, block_k, seq_q, seq_k):
    # block shapes: q/o [1, 1, block_q, d]; k/v [1, 1, seq_k, d];
    # lse [1, 1, block_q]
    qi = pl.program_id(2)
    q = q_ref[0, 0]  # [bq, d] native dtype: bf16 inputs stay on the MXU path
    d = q.shape[-1]

    m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)

    q_start = qi * block_q

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, 0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, 0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [bq, bk] f32 acc
        if causal:
            # offset diagonal for cross-length (sq != sk): query i may see
            # keys j <= i + (sk - sq), matching tril(k=sk-sq) in the fallback
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                          (block_q, block_k), 1)
            s = jnp.where(rows + (seq_k - seq_q) >= cols, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l

    if causal:
        # only k blocks at or left of this q block's (offset) diagonal
        diag_end = q_start + block_q + (seq_k - seq_q)
        num_kb = jnp.clip((diag_end + block_k - 1) // block_k, 0,
                          seq_k // block_k)
    else:
        num_kb = seq_k // block_k
    acc, m, l = jax.lax.fori_loop(0, num_kb, body, (acc0, m0, l0))
    # rows with no visible keys (sq > sk fully-masked tail) produce l == 0
    visible = l > 0
    o_ref[0, 0] = jnp.where(visible, acc / jnp.where(visible, l, 1.0),
                            0.0).astype(o_ref.dtype)
    # lse layout is [B, H, Sq, 1]: the trailing singleton keeps the block's
    # last-two dims TPU-tileable (block_q, 1)
    lse_ref[0, 0] = jnp.where(visible,
                              m + jnp.log(jnp.where(visible, l, 1.0)),
                              _NEG_INF)


def _flash_fwd(q, k, v, causal, sm_scale, block_q=None, block_k=None,
               interpret=False):
    """q: [B, H, Sq, D]; k/v: [B, Hk, Sk, D] -> (out [B, H, Sq, D],
    lse [B, H, Sq, 1] f32). Seq lengths must be multiples of 128.

    block_q/block_k default to the autotuner's pick for this (shape, dtype,
    chip); pass them explicitly to pin (the sweep/measure path does)."""
    b, h, sq, d = q.shape
    hk, sk = k.shape[1], k.shape[2]
    g = h // hk
    if block_q is None or block_k is None:
        picked = tuning.get_blocks(
            "flash_fwd", {"seq_q": sq, "seq_k": sk, "head_dim": d}, q.dtype,
            {"block_q": 512, "block_k": 1024},
            measure=_mk_measure("fwd", q.shape, k.shape, q.dtype, causal,
                                sm_scale),
            candidates=_BLOCK_CANDIDATES)
        block_q = picked["block_q"] if block_q is None else block_q
        block_k = picked["block_k"] if block_k is None else block_k
    block_q = _pick_block(sq, min(block_q, sq))
    block_k = _pick_block(sk, min(block_k, sk))
    kern = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                             block_q=block_q, block_k=block_k, seq_q=sq,
                             seq_k=sk)
    return pl.pallas_call(
        kern,
        out_shape=(_sds((b, h, sq, d), q.dtype, q),
                   _sds((b, h, sq, 1), jnp.float32, q)),
        grid=(b, h, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, i: (bi, hi, i, 0)),
            pl.BlockSpec((1, 1, sk, d), lambda bi, hi, i: (bi, hi // g, 0, 0)),
            pl.BlockSpec((1, 1, sk, d), lambda bi, hi, i: (bi, hi // g, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, i: (bi, hi, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, i: (bi, hi, i, 0)),
        ),
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward (flash-attn v2 recompute strategy)
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
                   sm_scale, causal, block_q, block_k, seq_q, seq_k):
    # q/do/dq: [1, 1, block_q, d]; k/v: [1, 1, seq_k, d];
    # lse/delta: [1, 1, block_q, 1] f32
    qi = pl.program_id(2)
    q = q_ref[0, 0]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0]      # [bq, 1]
    delta = delta_ref[0, 0]  # [bq, 1]
    d = q.shape[-1]
    q_start = qi * block_q
    off = seq_k - seq_q

    def body(j, dq):
        k = k_ref[0, 0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, 0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        p = jnp.exp(s - lse)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                          (block_q, block_k), 1)
            p = jnp.where(rows + off >= cols, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [bq, bk]
        ds = p * (dp - delta)
        return dq + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        diag_end = q_start + block_q + off
        num_kb = jnp.clip((diag_end + block_k - 1) // block_k, 0,
                          seq_k // block_k)
    else:
        num_kb = seq_k // block_k
    dq = jax.lax.fori_loop(0, num_kb, body,
                           jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0, 0] = (dq * sm_scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                    dk_ref, dv_ref, *, sm_scale, causal, block_q, block_k,
                    seq_q, seq_k):
    # k/v: [1, 1, block_k, d]; q/do: [1, 1, seq_q, d] (the group-head gi's
    # full sequence); lse/delta: [1, 1, seq_q, 1] f32; dk/dv out: [1, 1,
    # block_k, d] f32, revisited by the `group` innermost grid dim so partial
    # sums across the query heads sharing this kv head accumulate in VMEM.
    ki = pl.program_id(2)
    gi = pl.program_id(3)
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    d = k.shape[-1]
    k_start = ki * block_k
    off = seq_k - seq_q

    def body(i, carry):
        dk, dv = carry
        qb = q_ref[0, 0, pl.ds(i * block_q, block_q), :]
        dob = do_ref[0, 0, pl.ds(i * block_q, block_q), :]
        lse = lse_ref[0, 0, pl.ds(i * block_q, block_q), :]      # [bq, 1]
        delta = delta_ref[0, 0, pl.ds(i * block_q, block_q), :]  # [bq, 1]
        s = jax.lax.dot_general(
            qb, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [bq, bk]
        p = jnp.exp(s - lse)
        if causal:
            rows = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            p = jnp.where(rows + off >= cols, p, 0.0)
        dv = dv + jax.lax.dot_general(
            p.astype(dob.dtype), dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [bk, d]
        dp = jax.lax.dot_general(
            dob, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [bq, bk]
        ds = p * (dp - delta)
        dk = dk + jax.lax.dot_general(
            ds.astype(qb.dtype), qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk, dv

    if causal:
        # first q block whose diagonal reaches this k block
        start_qb = jnp.clip((k_start - off) // block_q, 0, seq_q // block_q)
    else:
        start_qb = 0
    dk, dv = jax.lax.fori_loop(
        start_qb, seq_q // block_q, body,
        (jnp.zeros((block_k, d), jnp.float32),
         jnp.zeros((block_k, d), jnp.float32)))
    dk = dk * sm_scale

    @pl.when(gi == 0)
    def _init():
        dk_ref[0, 0] = dk
        dv_ref[0, 0] = dv

    @pl.when(gi > 0)
    def _accum():
        dk_ref[0, 0] += dk
        dv_ref[0, 0] += dv


def _flash_bwd(q, k, v, o, lse, do, causal, sm_scale, block_q=None,
               block_k=None, interpret=False, g_lse=None):
    """All operands in [B, H(:k), S, D]; returns (dq, dk, dv) with dk/dv in
    f32 (caller casts). g_lse [B, H, Sq, 1]: cotangent of the logsumexp
    output (ring attention's merge differentiates through lse); folding it
    into delta is exact because dlse_i/ds_ij = p_ij, the same softmax
    weights delta multiplies. block_q/block_k default to the autotuner's
    pick; explicit values pin them."""
    b, h, sq, d = q.shape
    hk, sk = k.shape[1], k.shape[2]
    g = h // hk
    if block_q is None or block_k is None:
        picked = tuning.get_blocks(
            "flash_bwd", {"seq_q": sq, "seq_k": sk, "head_dim": d}, q.dtype,
            {"block_q": 512, "block_k": 1024},
            measure=_mk_measure("bwd", q.shape, k.shape, q.dtype, causal,
                                sm_scale),
            candidates=_BLOCK_CANDIDATES)
        block_q = picked["block_q"] if block_q is None else block_q
        block_k = picked["block_k"] if block_k is None else block_k
    block_q = _pick_block(sq, min(block_q, sq))
    block_k = _pick_block(sk, min(block_k, sk))
    # delta_i = rowsum(dO_i * O_i): plain XLA, fuses into one pass.
    # [B, H, Sq, 1] like lse (TPU-tileable trailing dims)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_q=sq,
                          seq_k=sk),
        out_shape=_sds((b, h, sq, d), q.dtype, q),
        grid=(b, h, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, i: (bi, hi, i, 0)),
            pl.BlockSpec((1, 1, sk, d), lambda bi, hi, i: (bi, hi // g, 0, 0)),
            pl.BlockSpec((1, 1, sk, d), lambda bi, hi, i: (bi, hi // g, 0, 0)),
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, i: (bi, hi, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, i: (bi, hi, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, i: (bi, hi, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, i: (bi, hi, i, 0)),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_q=sq,
                          seq_k=sk),
        out_shape=(_sds((b, hk, sk, d), jnp.float32, q),
                   _sds((b, hk, sk, d), jnp.float32, q)),
        grid=(b, hk, sk // block_k, g),
        in_specs=[
            pl.BlockSpec((1, 1, sq, d),
                         lambda bi, hi, i, gi: (bi, hi * g + gi, 0, 0)),
            pl.BlockSpec((1, 1, sq, d),
                         lambda bi, hi, i, gi: (bi, hi * g + gi, 0, 0)),
            pl.BlockSpec((1, 1, sq, 1),
                         lambda bi, hi, i, gi: (bi, hi * g + gi, 0, 0)),
            pl.BlockSpec((1, 1, sq, 1),
                         lambda bi, hi, i, gi: (bi, hi * g + gi, 0, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, i, gi: (bi, hi, i, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, i, gi: (bi, hi, i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, i, gi: (bi, hi, i, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, i, gi: (bi, hi, i, 0)),
        ),
        interpret=interpret,
    )(q, do, lse, delta, k, v)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-vjp wrapper (paddle layout [B, S, H, D])
# ---------------------------------------------------------------------------

def _sdpa_xla(q, k, v, causal, sm_scale):
    """Reference attention in [b, s, h, d]; the unaligned-shape fallback.
    Single source of truth lives in nn.functional.flash_attention."""
    from paddle_tpu.nn.functional.flash_attention import _sdpa_reference

    return _sdpa_reference(q, k, v, causal=causal, scale=sm_scale)


def _to_bhsd(x):
    return jnp.swapaxes(x, 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_attention(q, k, v, causal, sm_scale, interpret):
    out, _ = _fa_fwd(q, k, v, causal, sm_scale, interpret)
    return out


def _fa_fwd(q, k, v, causal, sm_scale, interpret):
    from jax.ad_checkpoint import checkpoint_name

    qt, kt, vt = _to_bhsd(q), _to_bhsd(k), _to_bhsd(v)
    o, lse = _flash_fwd(qt, kt, vt, causal, sm_scale, interpret=interpret)
    # name the residuals the bwd kernels need, so a remat policy that saves
    # "attn"/"attn_lse" (models.llama_functional remat='lean') skips the
    # flash-forward recompute entirely — without the lse name, saving just
    # the layer output still re-runs the kernel to rebuild lse
    o = checkpoint_name(o, "attn")
    lse = checkpoint_name(lse, "attn_lse")
    return _to_bhsd(o), (qt, kt, vt, o, lse)


def _fa_bwd(causal, sm_scale, interpret, res, g):
    qt, kt, vt, o, lse = res
    do = _to_bhsd(g)
    dq, dk, dv = _flash_bwd(qt, kt, vt, o, lse, do, causal, sm_scale,
                            interpret=interpret)
    return (_to_bhsd(dq), _to_bhsd(dk).astype(kt.dtype),
            _to_bhsd(dv).astype(vt.dtype))


_flash_attention.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_with_lse(q, k, v, causal, sm_scale, interpret=False):
    """Differentiable (out, lse) pair in paddle layout — the building block
    ring attention merges across kv shards. lse: [B, H, Sq] f32."""
    return _fal_fwd(q, k, v, causal, sm_scale, interpret)[0]


def _fal_fwd(q, k, v, causal, sm_scale, interpret):
    qt, kt, vt = _to_bhsd(q), _to_bhsd(k), _to_bhsd(v)
    o, lse = _flash_fwd(qt, kt, vt, causal, sm_scale, interpret=interpret)
    return (_to_bhsd(o), lse[..., 0]), (qt, kt, vt, o, lse)


def _fal_bwd(causal, sm_scale, interpret, res, g):
    qt, kt, vt, o, lse = res
    g_out, g_lse = g
    do = _to_bhsd(g_out)
    dq, dk, dv = _flash_bwd(qt, kt, vt, o, lse, do, causal, sm_scale,
                            interpret=interpret, g_lse=g_lse[..., None])
    return (_to_bhsd(dq), _to_bhsd(dk).astype(kt.dtype),
            _to_bhsd(dv).astype(vt.dtype))


flash_attention_with_lse.defvjp(_fal_fwd, _fal_bwd)


# the backward dk/dv kernel streams the full q and dO sequences (plus k/v
# blocks) through VMEM; stay well under the ~16 MB/core budget so the
# kernels always compile — longer sequences route to the fused XLA path
_VMEM_BUDGET_BYTES = 10 * 1024 * 1024


def supports(q_shape, k_shape, itemsize=4):
    """True when the Pallas kernels can take these [B, S, H, D] shapes:
    128-aligned sequences, query heads an integer multiple of kv heads, and
    a per-grid-step working set that fits VMEM."""
    sq, h, d = q_shape[1], q_shape[2], q_shape[3]
    sk, hk = k_shape[1], k_shape[2]
    if sq % 128 != 0 or sk % 128 != 0 or hk <= 0 or h % hk != 0:
        return False
    # worst per-step residency: k+v full seq (fwd/dq) or q+dO full seq plus
    # f32 lse/delta rows (dkv), double-buffered by the pipeline
    per_step = 2 * max(sq, sk) * d * itemsize * 2
    return per_step <= _VMEM_BUDGET_BYTES


def flash_attention_fwd(q, k, v, causal=False, scale=None, interpret=False):
    """q: [batch, seq, heads, head_dim]; k/v may carry fewer (kv) heads (GQA).
    Differentiable: backward runs the Pallas recompute kernels."""
    d = q.shape[-1]
    sm_scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if not supports(q.shape, k.shape, q.dtype.itemsize):
        # unpadded tails: fall back to the fused XLA path
        return _sdpa_xla(q, k, v, causal, sm_scale)
    # a shape supports() accepts and Mosaic refuses is a bug in supports()
    # or in the kernel: it raises, it does not become the XLA path
    return _flash_attention(q, k, v, causal, sm_scale, interpret)
