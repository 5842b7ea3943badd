"""Fused int8 dequant-matmul + decode attention: the quantized-decode
fast path as Pallas TPU kernels.

Reference counterparts: `paddle/phi/kernels/gpu/weight_only_linear_kernel.cu`
(fused dequant-GEMM — weights stay int8 in memory, per-channel scales applied
after the MACs) and
`paddle/phi/kernels/fusion/gpu/masked_multihead_attention_kernel.cu`
(single-query decode attention over the growing cache).

Why a kernel and not XLA: through plain StableHLO the weight-only dequant
(`convert(int8) * scale`) is materialized as a full-width bf16 weight in HBM
before every matmul, so small-batch decode pays the int8 read AND a bf16
round trip — measured 0.892x bf16 (v5e, 2026-08-01, before PR 1).
Small-batch decode is weight-stream bound, so the only lever is bytes moved:

- `fused_dequant_matmul`: int8 weight tiles DMA from HBM into VMEM at 1-byte
  width, upcast + per-output-channel scale happen in-registers between the
  load and the MXU, the f32 accumulator is scaled once per output tile.
  Weight-stream bytes halve vs bf16; nothing full-width ever touches HBM.
- `decode_attention`: one query row (s_new=1) against the fixed-size KV
  cache, online max/sum bounded to the valid prefix `[0, pos]` — the full
  flash kernel (and the jnp fallback) recompute softmax over the whole
  padded cache length and, under GQA, `jnp.repeat` the cache to the full
  head count; here kv heads are read once and the loop stops at the
  position watermark.

Dispatch: `weight_only_matmul` / `decode_attention` pick Pallas on TPU and
a jnp composition elsewhere; `fused_dispatch(...)` overrides the choice
(interpret-mode CPU tests, multi-platform exports that must stay
Pallas-free). Layouts at the public boundary: activations `[..., K]`,
weights `[K, N]` int8, scales `[N]` (absmax convention: dequant is
`q * scale / 127`), caches `[b, n_kv_heads, max_len, head_dim]`.
"""

from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.kernels.flash_attention import _pick_block

__all__ = ["fused_dequant_matmul", "weight_only_matmul", "decode_attention",
           "window_decode_attention", "paged_decode_attention",
           "paged_gather", "fused_dispatch", "fused_enabled",
           "matmul_supported", "decode_supported", "window_supported",
           "paged_decode_supported", "quantize_absmax"]

_NEG_INF = -1e30

# (use_pallas, interpret) override; None = auto (Pallas on TPU, compiled)
_OVERRIDE = None


@contextlib.contextmanager
def fused_dispatch(enabled=True, interpret=False):
    """Force the dispatch decision for the scope: enabled=True routes to the
    Pallas kernels (interpret=True runs them in the Pallas interpreter — the
    CPU test path), enabled=False forces the jnp composition (multi-platform
    jax.export traces, which cannot carry a TPU-only Mosaic call)."""
    global _OVERRIDE
    saved = _OVERRIDE
    _OVERRIDE = (enabled, interpret)
    try:
        yield
    finally:
        _OVERRIDE = saved


def _mode():
    if _OVERRIDE is not None:
        return _OVERRIDE
    return jax.default_backend() == "tpu", False


def fused_enabled():
    """True when dispatch would pick the Pallas kernels (TPU, or forced by
    fused_dispatch)."""
    return _mode()[0]


# the kernels stream whole weight/cache blocks through VMEM; stay well under
# the ~16 MB/core budget (same discipline as kernels/flash_attention)
_VMEM_BUDGET_BYTES = 10 * 1024 * 1024


def _round_up(x, m):
    return -(-x // m) * m


# ---------------------------------------------------------------------------
# fused dequant-matmul
# ---------------------------------------------------------------------------


def _dqmm_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, block_k, n_kb,
                 k_total):
    # blocks: x [bm, bk]; w [bk, bn] int8; s [1, bn] f32; o [bm, bn];
    # acc scratch [bm, bn] f32, revisited across the innermost k grid dim
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    w = w_ref[...]
    k_start = ki * block_k
    if k_total % block_k != 0:
        # K-tail block: the out-of-range tail of a partial block holds
        # arbitrary padding — zero BOTH operands so 0*garbage never leaks
        # a NaN into the accumulator
        rows = k_start + jax.lax.broadcasted_iota(jnp.int32, w.shape, 0)
        w = jnp.where(rows < k_total, w, 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        x = jnp.where(cols < k_total, x, 0)
    # the fusion: int8 -> activation dtype in-registers (every int8 value is
    # exact in bf16), straight to the MXU with an f32 accumulator — the
    # full-width weight never exists outside registers
    acc_ref[...] += jax.lax.dot_general(
        x, w.astype(x.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == n_kb - 1)
    def _finish():
        # per-output-channel epilogue: one multiply of the f32 accumulator
        o_ref[...] = (acc_ref[...] * (s_ref[0] / 127.0)).astype(o_ref.dtype)


def fused_dequant_matmul(x, w, scale, out_dtype=None, block_m=None,
                         block_n=None, block_k=None, interpret=False):
    """`x @ (w * scale / 127)` with w int8 [K, N] staying int8 through HBM
    and VMEM; scale [N] is the per-output-channel absmax. x: [..., K]
    (leading dims flatten into M — decode batches are tiny, the M tile pads).
    Tile-remainder shapes on any of M/N/K are handled by in-kernel masking
    (K) and dropped out-of-range writes (M/N). Tiles default to the
    autotuner's pick for this (shape, dtype, chip); explicit values pin."""
    *lead, k_total = x.shape
    n_total = w.shape[1]
    if block_m is None or block_n is None or block_k is None:
        from paddle_tpu.kernels import tuning

        picked = tuning.get_blocks(
            "dequant_matmul", {"k": k_total, "n": n_total}, x.dtype,
            {"block_m": 256, "block_n": 512, "block_k": 512})
        block_m = picked["block_m"] if block_m is None else block_m
        block_n = picked["block_n"] if block_n is None else block_n
        block_k = picked["block_k"] if block_k is None else block_k
    x2 = x.reshape(-1, k_total)
    m_total = x2.shape[0]
    out_dtype = out_dtype or x.dtype

    # round the M tile to the widest dtype's sublane minimum (int8: 32) so
    # tiny decode batches land on a natively-tileable block
    bm = min(block_m, _round_up(m_total, 32))
    bn = min(block_n, _round_up(n_total, 128))
    bk = min(block_k, _round_up(k_total, 128))
    n_kb = pl.cdiv(k_total, bk)
    if m_total < 8 and k_total % bk:
        # Mosaic cannot mask the K tail of an activation with fewer rows
        # than one sublane tile ("Not implemented: Sublane broadcast" on
        # the v5e, libtpu 0.0.34): batch-1..7 decode pads up to 8 rows
        x2 = jnp.pad(x2, ((0, 8 - m_total), (0, 0)))
    grid = (pl.cdiv(x2.shape[0], bm), pl.cdiv(n_total, bn), n_kb)

    out = pl.pallas_call(
        functools.partial(_dqmm_kernel, block_k=bk, n_kb=n_kb,
                          k_total=k_total),
        out_shape=jax.ShapeDtypeStruct((x2.shape[0], n_total), out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, ki: (i, ki)),
            pl.BlockSpec((bk, bn), lambda i, j, ki: (ki, j)),
            pl.BlockSpec((1, bn), lambda i, j, ki: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, ki: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x2, w, scale.reshape(1, n_total).astype(jnp.float32))
    return out[:m_total].reshape(*lead, n_total)


def matmul_supported(x_shape, w_shape, itemsize=2, block_n=512, block_k=512):
    """True when the fused kernel can take x [..., K] @ w [K, N] int8:
    2-D weight and a per-grid-step working set that fits VMEM."""
    if len(w_shape) != 2 or x_shape[-1] != w_shape[0]:
        return False
    k_total, n_total = w_shape
    if k_total < 1 or n_total < 1:
        return False
    m = 1
    for d in x_shape[:-1]:
        m *= d
    bm = min(256, _round_up(m, 32))
    bn = min(block_n, _round_up(n_total, 128))
    bk = min(block_k, _round_up(k_total, 128))
    # per-step residency: int8 w tile + x tile + f32 acc + out, double-buffered
    per_step = 2 * (bk * bn + bm * bk * itemsize) + bm * bn * (4 + itemsize)
    return per_step <= _VMEM_BUDGET_BYTES


def quantize_absmax(w):
    """Per-out-channel absmax int8 quantization of [..., K, N] weights:
    (q int8, scale [..., N] f32) with dequant = q * scale / 127 — the ONE
    convention every quantized entry point shares (weight_quantize, the
    weight_only_int8 export patch, generation.quantize_params) and the
    fused kernel's /127 epilogue assumes."""
    a = jnp.asarray(w, jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(a), axis=-2), 1e-9)
    q = jnp.clip(jnp.round(a / scale[..., None, :] * 127.0), -127,
                 127).astype(jnp.int8)
    return q, scale


def _dequant_matmul_xla(x, w, scale, out_dtype=None):
    """The unfused reference: dequantize to the activation dtype, then
    matmul (what XLA gets through plain StableHLO — also the fallback and
    the parity oracle for the kernel tests)."""
    wf = w.astype(x.dtype) * (scale.astype(x.dtype) / 127.0)
    out = x @ wf
    return out.astype(out_dtype) if out_dtype else out


def weight_only_matmul(x, w, scale, out_dtype=None):
    """Dispatch waist for weight-only int8 matmuls: the fused Pallas kernel
    on TPU (or when forced by `fused_dispatch`), the jnp composition
    elsewhere. All inference entry points (quantization.weight_only_linear,
    the weight_only_int8 export patch, generation's quantized decode) route
    through here."""
    use_pallas, interpret = _mode()
    if use_pallas and w.dtype == jnp.int8 and \
            matmul_supported(x.shape, w.shape, x.dtype.itemsize):
        return fused_dequant_matmul(x, w, scale, out_dtype,
                                    interpret=interpret)
    return _dequant_matmul_xla(x, w, scale, out_dtype)


# ---------------------------------------------------------------------------
# decode attention (single query vs the static KV cache)
# ---------------------------------------------------------------------------


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, *, block_k, sm_scale):
    # blocks: q/o [1, 1, g, d] (the g query heads sharing this kv head);
    # k/v [1, 1, max_len, d]; pos is scalar-prefetched PER ROW [b] — the
    # serving decode step has every slot at its own sequence position
    pos = pos_ref[pl.program_id(0)]
    q = q_ref[0, 0]  # [g, d]
    g, d = q.shape

    m0 = jnp.full((g, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((g, 1), jnp.float32)
    acc0 = jnp.zeros((g, d), jnp.float32)

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, 0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, 0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [g, bk]
        cols = j * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                      (g, block_k), 1)
        s = jnp.where(cols <= pos, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l

    # the decode specialization: the loop stops at the position watermark —
    # cache slots past `pos` are never scored (the flash kernel and the jnp
    # fallback softmax over the full padded max_len every step)
    n_kb = (pos + block_k) // block_k  # cdiv(pos + 1, block_k), pos >= 0
    acc, m, l = jax.lax.fori_loop(0, n_kb, body, (acc0, m0, l0))
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)


def decode_supported(q_shape, cache_shape, itemsize=2):
    """True when the Pallas decode kernel can take q [b, 1, nh, hd] against
    cache [b, nkv, max_len, hd]: single query, 128-aligned cache length,
    query heads a multiple of kv heads, working set within VMEM."""
    if len(q_shape) != 4 or q_shape[1] != 1:
        return False
    nh, hd = q_shape[2], q_shape[3]
    nkv, max_len = cache_shape[1], cache_shape[2]
    if max_len % 128 != 0 or nkv <= 0 or nh % nkv != 0:
        return False
    # k + v streamed whole per (batch, kv head) grid step, double-buffered
    per_step = 2 * 2 * max_len * hd * itemsize
    return per_step <= _VMEM_BUDGET_BYTES


def _decode_attention_pallas(q, cache_k, cache_v, pos, sm_scale, block_k,
                             interpret):
    b, _, nh, hd = q.shape
    nkv, max_len = cache_k.shape[1], cache_k.shape[2]
    g = nh // nkv
    bk = _pick_block(max_len, min(block_k, max_len))
    q4 = q[:, 0].reshape(b, nkv, g, hd)
    # scalar pos broadcasts to the per-row form the kernel reads
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda bi, hi, pos_ref: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, max_len, hd),
                         lambda bi, hi, pos_ref: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, max_len, hd),
                         lambda bi, hi, pos_ref: (bi, hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd),
                               lambda bi, hi, pos_ref: (bi, hi, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_k=bk, sm_scale=sm_scale),
        out_shape=jax.ShapeDtypeStruct((b, nkv, g, hd), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(pos_arr, q4, cache_k, cache_v)
    return out.reshape(b, nh, hd)[:, None]


def _decode_attention_xla(q, cache_k, cache_v, pos, sm_scale):
    """Masked full-length reference (static shapes; what _cached_attention
    computes at s=1) — fallback and parity oracle."""
    b, _, nh, hd = q.shape
    nkv, max_len = cache_k.shape[1], cache_k.shape[2]
    if nkv != nh:
        cache_k = jnp.repeat(cache_k, nh // nkv, axis=1)
        cache_v = jnp.repeat(cache_v, nh // nkv, axis=1)
    qh = jnp.swapaxes(q, 1, 2)  # [b, nh, 1, hd]
    scores = jnp.einsum("bhqd,bhkd->bhqk", qh, cache_k) * sm_scale
    key_pos = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, max_len), 3)
    if jnp.ndim(pos) == 1:  # per-row valid prefixes [b]
        pos = jnp.asarray(pos).reshape(b, 1, 1, 1)
    scores = jnp.where(key_pos <= pos, scores, _NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    attn = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(cache_v.dtype), cache_v)
    return jnp.swapaxes(attn, 1, 2)


# ---------------------------------------------------------------------------
# window attention (a short run of queries at a traced offset vs the cache)
# ---------------------------------------------------------------------------


def _window_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, *, block_k,
                   sm_scale, gsize, window, kv_blocks):
    # blocks: q/o [1, 1, s*g, d] — the window's s queries for the g query
    # heads sharing this kv head, flattened query-major; k/v
    # [1, 1, max_len, d]; pos is scalar-prefetched PER ROW [b]. Query i
    # of the window sits at sequence position pos + i: the chunk-offset
    # prefill / speculative-verify masking rule (key <= pos + i), with
    # the online max/sum stopping at the LAST query's watermark instead
    # of re-softmaxing the padded cache length.
    pos = pos_ref[pl.program_id(0)]
    q = q_ref[0, 0]  # [s*g, d]
    sg, d = q.shape
    qidx = jax.lax.broadcasted_iota(jnp.int32, (sg, 1), 0) // gsize

    m0 = jnp.full((sg, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((sg, 1), jnp.float32)
    acc0 = jnp.zeros((sg, d), jnp.float32)

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, 0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, 0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [sg, bk]
        cols = j * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                      (sg, block_k), 1)
        s = jnp.where(cols <= pos + qidx, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l

    # stop at the last query's watermark pos + window - 1, clamped to the
    # cache: a tail speculation window can overhang max_len (writes past
    # the reservation go to the null page, but the watermark still lands
    # beyond the cache) and an unclamped bound would read k/v out of range
    n_kb = jnp.minimum((pos + window - 1 + block_k) // block_k, kv_blocks)
    acc, m, l = jax.lax.fori_loop(0, n_kb, body, (acc0, m0, l0))
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)


# windows larger than this fall back to the masked-einsum composition:
# the kernel streams the whole [s*g, block_k] score tile through VMEM per
# step, which is only a win for the SHORT windows speculation and
# chunk-tail prefills produce (a full-length prefill wants real flash
# query tiling instead)
_WINDOW_MAX_ROWS = 64


def window_supported(q_shape, cache_shape, itemsize=2):
    """True when the Pallas window kernel can take q [b, s, nh, hd]
    (query i of row r at position pos[r] + i) against cache
    [b, nkv, max_len, hd]: a SHORT window (s*g <= 64 flattened rows —
    the speculative-verify / chunk-offset regime), 128-aligned cache
    length, query heads a multiple of kv heads, working set in VMEM."""
    if len(q_shape) != 4 or q_shape[1] < 1:
        return False
    b, s, nh, hd = q_shape
    nkv, max_len = cache_shape[1], cache_shape[2]
    if max_len % 128 != 0 or nkv <= 0 or nh % nkv != 0:
        return False
    if s * (nh // nkv) > _WINDOW_MAX_ROWS:
        return False
    per_step = 2 * 2 * max_len * hd * itemsize
    return per_step <= _VMEM_BUDGET_BYTES


def _window_attention_pallas(q, cache_k, cache_v, pos, sm_scale, block_k,
                             interpret):
    b, s, nh, hd = q.shape
    nkv, max_len = cache_k.shape[1], cache_k.shape[2]
    g = nh // nkv
    bk = _pick_block(max_len, min(block_k, max_len))
    # [b, s, nkv, g, hd] -> [b, nkv, s*g, hd], query-major per kv head
    q4 = jnp.swapaxes(q.reshape(b, s, nkv, g, hd), 1, 2) \
            .reshape(b, nkv, s * g, hd)
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, s * g, hd),
                         lambda bi, hi, pos_ref: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, max_len, hd),
                         lambda bi, hi, pos_ref: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, max_len, hd),
                         lambda bi, hi, pos_ref: (bi, hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, s * g, hd),
                               lambda bi, hi, pos_ref: (bi, hi, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_window_kernel, block_k=bk, sm_scale=sm_scale,
                          gsize=g, window=s, kv_blocks=max_len // bk),
        out_shape=jax.ShapeDtypeStruct((b, nkv, s * g, hd), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(pos_arr, q4, cache_k, cache_v)
    return jnp.swapaxes(out.reshape(b, nkv, s, g, hd), 1, 2) \
              .reshape(b, s, nh, hd)


def _window_attention_xla(q, cache_k, cache_v, pos, sm_scale):
    """Masked full-length reference (what `generation._cached_attention`
    computes for a window) — fallback and parity oracle."""
    b, s, nh, hd = q.shape
    nkv, max_len = cache_k.shape[1], cache_k.shape[2]
    if nkv != nh:
        cache_k = jnp.repeat(cache_k, nh // nkv, axis=1)
        cache_v = jnp.repeat(cache_v, nh // nkv, axis=1)
    qh = jnp.swapaxes(q, 1, 2)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qh, cache_k) * sm_scale
    key_pos = jax.lax.broadcasted_iota(jnp.int32, (1, 1, s, max_len), 3)
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (1, 1, s, max_len), 2)
    qpos = jnp.asarray(pos, jnp.int32).reshape(-1, 1, 1, 1) + row_iota
    scores = jnp.where(key_pos <= qpos, scores, _NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    attn = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(cache_v.dtype),
                      cache_v)
    return jnp.swapaxes(attn, 1, 2)


def window_decode_attention(q, cache_k, cache_v, pos, scale=None,
                            block_k=None):
    """Attention of a SHORT query window q [b, s, nh, hd] over the
    fixed-size cache [b, nkv, max_len, hd]: query i of row r sits at
    position pos[r] + i and attends keys [0, pos[r] + i]. pos may be a
    scalar (one row / uniform rows — the chunk-offset prefill) or an
    int32 [b] vector (per-row offsets — the speculative-verify window).
    Pallas on TPU for windows up to 64 flattened query rows (the online
    max/sum stops at the last query's watermark; GQA native), the masked
    jnp composition elsewhere."""
    sm_scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if block_k is None:
        from paddle_tpu.kernels import tuning

        block_k = tuning.get_blocks(
            "decode_attention", {"seq": cache_k.shape[2]}, q.dtype,
            {"block_k": 512})["block_k"]
    use_pallas, interpret = _mode()
    if use_pallas and window_supported(q.shape, cache_k.shape,
                                       q.dtype.itemsize):
        return _window_attention_pallas(q, cache_k, cache_v, pos,
                                        sm_scale, block_k, interpret)
    return _window_attention_xla(q, cache_k, cache_v, pos, sm_scale)


# ---------------------------------------------------------------------------
# paged decode attention (single query vs a page pool through a block table)
# ---------------------------------------------------------------------------


def _paged_decode_kernel(pos_ref, bt_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, page_size, sm_scale):
    # grid (b, nkv, P): the innermost dim walks the row's block table; the
    # k/v BlockSpec index maps read bt_ref (scalar-prefetched) so each step
    # DMAs the PAGE the table points at — the gather never materializes a
    # contiguous cache. Online max/sum state lives in VMEM scratch because
    # it must survive across grid steps (the non-paged kernel keeps it in
    # registers inside one fori_loop).
    bi, j = pl.program_id(0), pl.program_id(2)
    pos = pos_ref[bi]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # pages past the row's position watermark are skipped entirely (their
    # index map re-points at the watermark page, so no fresh DMA either)
    @pl.when(j * page_size <= pos)
    def _page():
        q = q_ref[0, 0]                       # [g, d]
        k = k_ref[0, 0]                       # [page_size, d]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # [g, ps]
        cols = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols <= pos, s, _NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def paged_decode_supported(q_shape, pool_shape, bt_shape, itemsize=2):
    """True when the Pallas paged kernel can take q [b, 1, nh, hd] against
    a page pool [num_pages, nkv, page_size, hd] via block tables [b, P]:
    single query, query heads a multiple of kv heads, page_size a
    sublane-tileable multiple and hd lane-aligned, working set in VMEM.
    `itemsize` is the POOL element width — int8 pools (itemsize 1) need
    page_size % 32 == 0 (the int8 sublane minimum)."""
    if len(q_shape) != 4 or q_shape[1] != 1:
        return False
    if len(pool_shape) != 4 or len(bt_shape) != 2:
        return False
    b, nh, hd = q_shape[0], q_shape[2], q_shape[3]
    nkv, ps, hd2 = pool_shape[1], pool_shape[2], pool_shape[3]
    if hd2 != hd or nkv <= 0 or nh % nkv != 0 or bt_shape[0] != b:
        return False
    min_sublane = 32 // max(int(itemsize), 1)   # f32: 8, bf16: 16
    if ps % min_sublane != 0 or hd % 128 != 0:
        return False
    per_step = 2 * 2 * ps * hd * itemsize      # k + v page, double-buffered
    return per_step <= _VMEM_BUDGET_BYTES


def _paged_decode_kernel_q8(pos_ref, bt_ref, sk_ref, sv_ref, q_ref, k_ref,
                            v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                            page_size, sm_scale, nkv):
    # int8-pool variant of `_paged_decode_kernel`: k/v blocks arrive as
    # int8 PAGES; the per-(page, kv-head) absmax scales ride in SMEM
    # (scalar-prefetched, flattened [num_pages * nkv]) and are read as
    # scalars at the page the block table names — a (1, 1) VMEM block of
    # the [num_pages, nkv] array is not a tile Mosaic can window. The
    # dequant is the PR-1 in-registers pattern — int8 upcasts between the
    # DMA and the MXU (exact in bf16), and the page's scale folds into
    # the score scale (k) and the accumulator contribution (v), so a
    # full-width page never exists outside registers.
    bi, hi, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    pos = pos_ref[bi]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j * page_size <= pos)
    def _page():
        q = q_ref[0, 0]                       # [g, d]
        k = k_ref[0, 0].astype(q.dtype)       # int8 -> compute dtype, exact
        v = v_ref[0, 0].astype(q.dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        sidx = bt_ref[bi, j] * nkv + hi
        s = s * (sk_ref[sidx] * (sm_scale / 127.0))          # [g, ps]
        cols = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols <= pos, s, _NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * (sv_ref[sidx] / 127.0)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _paged_decode_attention_pallas(q, pool_k, pool_v, block_tables, pos,
                                   sm_scale, interpret, k_scale=None,
                                   v_scale=None):
    b, _, nh, hd = q.shape
    nkv, ps = pool_k.shape[1], pool_k.shape[2]
    P = block_tables.shape[1]
    g = nh // nkv
    q4 = q[:, 0].reshape(b, nkv, g, hd)
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    bt_arr = jnp.asarray(block_tables, jnp.int32)

    def kv_map(bi, hi, j, pos_ref, bt_ref, *scale_refs):
        # clamp to the watermark page: steps past the row's valid prefix
        # keep mapping the same block, so Pallas elides the re-fetch
        jj = jnp.minimum(j, pos_ref[bi] // ps)
        return (bt_ref[bi, jj], hi, 0, 0)

    if k_scale is None:
        kernel = functools.partial(_paged_decode_kernel, page_size=ps,
                                   sm_scale=sm_scale)
        prefetch = [pos_arr, bt_arr]
    else:
        kernel = functools.partial(_paged_decode_kernel_q8, page_size=ps,
                                   sm_scale=sm_scale, nkv=nkv)
        prefetch = [pos_arr, bt_arr,
                    k_scale.astype(jnp.float32).reshape(-1),
                    v_scale.astype(jnp.float32).reshape(-1)]

    def q_map(bi, hi, j, *prefetch_refs):
        return (bi, hi, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, nkv, P),
        in_specs=[pl.BlockSpec((1, 1, g, hd), q_map),
                  pl.BlockSpec((1, 1, ps, hd), kv_map),
                  pl.BlockSpec((1, 1, ps, hd), kv_map)],
        out_specs=pl.BlockSpec((1, 1, g, hd), q_map),
        scratch_shapes=[pltpu.VMEM((g, hd), jnp.float32),
                        pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, 1), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, nkv, g, hd), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(*prefetch, q4, pool_k, pool_v)
    return out.reshape(b, nh, hd)[:, None]


def paged_gather(pool, block_tables, scale=None, out_dtype=None):
    """Gather a pool [num_pages, nkv, page_size, hd] through block tables
    [b, P] into the contiguous per-row cache layout [b, nkv, P*ps, hd] —
    the jnp fallback path and the parity oracle for the paged kernel
    (pages laid out in table order ARE the row's sequence). With `scale`
    [num_pages, nkv] the pool is int8 and the gather dequantizes
    (q * scale / 127) into `out_dtype` (default f32) — the oracle for the
    int8 kernel's in-registers dequant."""
    b, P = block_tables.shape
    nkv, ps, hd = pool.shape[1], pool.shape[2], pool.shape[3]
    g = jnp.swapaxes(pool[block_tables], 1, 2)   # [b, nkv, P, ps, hd]
    if scale is not None:
        sc = jnp.swapaxes(scale[block_tables], 1, 2)   # [b, nkv, P]
        g = (g.astype(jnp.float32)
             * (sc / 127.0)[..., None, None]).astype(out_dtype
                                                     or jnp.float32)
    elif out_dtype is not None:
        g = g.astype(out_dtype)
    return g.reshape(b, nkv, P * ps, hd)


def _paged_decode_attention_xla(q, pool_k, pool_v, block_tables, pos,
                                sm_scale, k_scale=None, v_scale=None):
    return _decode_attention_xla(
        q, paged_gather(pool_k, block_tables, k_scale, q.dtype),
        paged_gather(pool_v, block_tables, v_scale, q.dtype),
        pos, sm_scale)


def paged_decode_attention(q, pool_k, pool_v, block_tables, pos, scale=None,
                           k_scale=None, v_scale=None):
    """Single-query attention of q [b, 1, nh, hd] over a PAGED KV cache:
    pool_k/pool_v [num_pages, nkv, page_size, hd] indexed through per-row
    block tables [b, P] (page i of row r holds that row's positions
    [i*ps, (i+1)*ps)), valid prefix [0, pos[r]]. Unused table entries may
    point anywhere valid (the null page); the position mask keeps them
    unread. Pallas on TPU (per-row page-index prefetch: the block-table
    lookup happens in the BlockSpec index map, so K/V stream page-by-page
    straight from HBM with no contiguous copy), jnp gather elsewhere.

    k_scale/v_scale [num_pages, nkv]: the pools are int8 pages with
    per-(page, kv-head) absmax scales — the kernel dequantizes
    in-registers (q * scale / 127) so the HBM stream stays 1 byte/elem;
    the fallback dequantizes in the gather."""
    sm_scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    use_pallas, interpret = _mode()
    if use_pallas and paged_decode_supported(q.shape, pool_k.shape,
                                             jnp.shape(block_tables),
                                             pool_k.dtype.itemsize):
        return _paged_decode_attention_pallas(
            q, pool_k, pool_v, block_tables, pos, sm_scale, interpret,
            k_scale=k_scale, v_scale=v_scale)
    return _paged_decode_attention_xla(q, pool_k, pool_v, block_tables, pos,
                                       sm_scale, k_scale, v_scale)


def decode_attention(q, cache_k, cache_v, pos, scale=None, block_k=None):
    """Single-query attention of q [b, 1, nh, hd] over the fixed-size cache
    [b, nkv, max_len, hd], valid prefix [0, pos] (pos is the traced write
    position of q's own k/v — the decode step of the compiled generate).
    pos may be a scalar (uniform batch) or an int32 [b] vector — per-row
    positions, the continuous-batching decode step where every slot sits at
    its own sequence depth. GQA native: kv heads are never repeated."""
    sm_scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if block_k is None:
        from paddle_tpu.kernels import tuning

        block_k = tuning.get_blocks(
            "decode_attention", {"seq": cache_k.shape[2]}, q.dtype,
            {"block_k": 512})["block_k"]
    use_pallas, interpret = _mode()
    if use_pallas and decode_supported(q.shape, cache_k.shape,
                                       q.dtype.itemsize):
        return _decode_attention_pallas(q, cache_k, cache_v, pos,
                                        sm_scale, block_k, interpret)
    return _decode_attention_xla(q, cache_k, cache_v, pos, sm_scale)
