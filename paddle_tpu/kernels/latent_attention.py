"""Attention over a LATENT paged cache (MLA): one row a token and layer,
`[c_kv; k_pe]`, shared by every head; a head's value is a map of the row's
leading `v_width` columns.

`latent_decode_attention`  one query a row, the ABSORBED form: the query
    heads `[b, H, row]` (their no-rotary part already carried into the
    latent space) attend the cached rows themselves through the block
    tables; the result `[b, H, v_width]` is still latent. On TPU a Pallas
    kernel in the mould of `quantized_matmul._paged_decode_kernel` (a grid
    step a row, the pool left in HBM, ONE async copy a live page, two
    buffers, a row's last block starts the next row's first), with what
    that kernel cannot be taught without changing its text for the two
    cells it serves: a page is `[page, row]` with no KV-head axis, V is a
    prefix of K's row and not a second pool (so one copy and one buffer
    serve both products), and the score is a sum of two contractions, over
    the latent columns and over the rest of the row (the rotary columns
    and the zeros that pad the row to whole lane tiles: 512 + 64 values
    are laid out as 640 lanes, and Mosaic copies no page whose rows are
    not whole tiles). A kernel file of its own leaves that kernel's text
    as cells 2 and 3 measure it.
`latent_prefill_attention`  a window of queries against DECOMPRESSED keys
    and values `kv [T, H * (nope + v)]` (a head's key then its value) and
    the shared rotary keys `k_pe [T, rope]`, causal by absolute position,
    in blocks over the keys with a running (max, sum, accumulator): a
    `[H, window, context]` score matrix is never formed (17 GB at 128
    heads, a 2,048 window and 16k keys). On TPU a Pallas kernel, grid
    (head, query block, key block): key blocks past a query block's last
    visible position are skipped (their copy too: the index map stays on
    the last needed block), and a head's key and value are lane blocks of
    the one `kv` array, so nothing is transposed.

Elsewhere, and as the parity oracles, jnp compositions of the same
blocking (`fused_dispatch` overrides the choice, as for the other kernels).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.kernels import quantized_matmul as qm

__all__ = ["latent_decode_attention", "latent_prefill_attention",
           "latent_decode_supported", "latent_prefill_supported"]

_NEG_INF = -1e30
DECODE_BLOCK_K = 512       # positions of one decode compute block
PREFILL_BLOCK_Q = 512
PREFILL_BLOCK_K = 1024


# ---------------------------------------------------------------------------
# decode: one query a row over the row's pages, absorbed form
# ---------------------------------------------------------------------------

def _decode_kernel(pos_ref, bt_ref, base_ref, ql_ref, qr_ref, pool_hbm, o_ref,
                   buf, sems, slot_ref, *, page_size, pages_per_block,
                   sm_scale, v_width):
    # grid (b,): one step a ROW. A compute block is `pages_per_block` pages,
    # each one async copy of pool[base + bt[row, j]] into its rows of a
    # [block, row width] VMEM buffer; the loop's trip count follows the
    # row's position. Two buffers: block i + 1 streams while block i
    # computes, and a row's last block starts the next row's first.
    ps, ppb = page_size, pages_per_block
    bk = ps * ppb
    last_table = bt_ref.shape[1] - 1
    row, rows = pl.program_id(0), pl.num_programs(0)

    def last_page(r):
        return jnp.minimum(pos_ref[r] // ps, last_table)

    def block_pages(r, blk):
        lp = last_page(r)
        js = [blk * ppb + i for i in range(ppb)]
        return [(j <= lp, bt_ref[r, jnp.minimum(j, lp)]) for j in js]

    def copy(page, i, slot):
        return pltpu.make_async_copy(
            pool_hbm.at[base_ref[0] + page],
            buf.at[slot, pl.ds(i * ps, ps), :], sems.at[slot])

    def start_block(r, blk, slot):
        for i, (live, page) in enumerate(block_pages(r, blk)):
            @pl.when(live)
            def _start():
                copy(page, i, slot).start()

    def wait_block(pages, slot):
        for i, (live, page) in enumerate(pages):
            @pl.when(live)
            def _wait():
                copy(page, i, slot).wait()

            # a page nobody fetched holds whatever the buffer held: its
            # scores are masked below, and as a value it must be zero
            @pl.when(jnp.logical_not(live))
            def _zero():
                buf[slot, pl.ds(i * ps, ps), :] = jnp.zeros(
                    (ps, buf.shape[-1]), buf.dtype)

    @pl.when(row == 0)
    def _first():
        slot_ref[0] = 0
        start_block(0, 0, 0)

    pos = pos_ref[row]
    n_blocks = last_page(row) // ppb + 1
    slot0 = slot_ref[0]
    ql, qr = ql_ref[0], qr_ref[0]            # [H, v_width], [H, rope]
    H = ql.shape[0]

    def body(i, carry):
        acc, m, l = carry
        slot = (slot0 + i) % 2

        @pl.when(i + 1 < n_blocks)
        def _next_block():
            start_block(row, i + 1, 1 - slot)

        @pl.when(jnp.logical_and(i + 1 == n_blocks, row + 1 < rows))
        def _next_row():
            start_block(row + 1, 0, 1 - slot)

        wait_block(block_pages(row, i), slot)
        c = buf[slot, :, :v_width]           # [bk, v_width]: key and value
        r = buf[slot, :, v_width:]           # [bk, rope]
        s = jax.lax.dot_general(ql, c, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s + jax.lax.dot_general(qr, r, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        s = s * sm_scale                                   # [H, bk]
        cols = i * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols <= pos, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [H, v_width]
        return acc, m_new, l

    acc, _, l = jax.lax.fori_loop(
        0, n_blocks, body,
        (jnp.zeros((H, v_width), jnp.float32),
         jnp.full((H, 1), _NEG_INF, jnp.float32),
         jnp.zeros((H, 1), jnp.float32)))
    slot_ref[0] = (slot0 + n_blocks) % 2
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def latent_decode_supported(q_shape, pool_shape, bt_shape, v_width,
                            itemsize=2):
    """True when the Pallas kernel can take q [b, H, row] against a pool
    [pages, page_size, row] through tables [b, P]: the page a sublane
    multiple, the row and its latent part whole lane tiles (Mosaic copies
    no page of another width; the rotary part is the rest of the row), a
    block's buffers in VMEM."""
    if len(q_shape) != 3 or len(pool_shape) != 3 or len(bt_shape) != 2:
        return False
    b, H, row = q_shape
    ps = pool_shape[1]
    if pool_shape[2] != row or bt_shape[0] != b or not 0 < v_width < row:
        return False
    if ps % (32 // itemsize) or v_width % 128 or row % 128 or H % 8:
        return False
    return (2 * ps * row * itemsize + 3 * H * ps * 4) <= qm._VMEM_BUDGET_BYTES


def _decode_pallas(q, pool, block_tables, pos, sm_scale, v_width, page_base,
                   interpret):
    b, H, row = q.shape
    ps, P = pool.shape[1], block_tables.shape[1]
    ppb = max(1, min(DECODE_BLOCK_K // ps, P))
    ql, qr = q[..., :v_width], q[..., v_width:]
    prefetch = [jnp.asarray(pos, jnp.int32).reshape(b),
                jnp.asarray(block_tables, jnp.int32),
                jnp.asarray(0 if page_base is None else page_base,
                            jnp.int32).reshape(1)]

    def row_map(bi, *prefetch_refs):
        return (bi, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b,),
        in_specs=[pl.BlockSpec((1, H, v_width), row_map),
                  pl.BlockSpec((1, H, row - v_width), row_map),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, H, v_width), row_map),
        scratch_shapes=[pltpu.VMEM((2, ppb * ps, row), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, page_size=ps, pages_per_block=ppb,
                          sm_scale=sm_scale, v_width=v_width),
        out_shape=jax.ShapeDtypeStruct((b, H, v_width), q.dtype),
        grid_spec=grid_spec,
        # rows in order on one core: a row starts its successor's copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_decode_attention",
    )(*prefetch, ql, qr, pool)


def _decode_xla(q, pool, block_tables, pos, sm_scale, v_width, page_base):
    """Gather each row's pages into [b, T, row] and attend under a position
    mask: the oracle (a copy of every table's width, every step)."""
    b, P = block_tables.shape
    pages = block_tables if page_base is None else page_base + block_tables
    rows = pool[pages].reshape(b, P * pool.shape[1], pool.shape[2])
    s = jnp.einsum("bhc,btc->bht", q, rows,
                   preferred_element_type=jnp.float32) * sm_scale
    t = jnp.arange(rows.shape[1], dtype=jnp.int32)
    s = jnp.where(t[None, None, :] <= jnp.asarray(pos)[:, None, None], s,
                  _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bht,btc->bhc", p.astype(q.dtype),
                      rows[..., :v_width],
                      preferred_element_type=jnp.float32).astype(q.dtype)


def latent_decode_attention(q, pool, block_tables, pos, sm_scale, v_width,
                            page_base=None):
    """q [b, H, row] (absorbed queries: latent part, then rotary part) over
    the pool [pages, page_size, row] through block tables [b, P], valid
    prefix [0, pos[r]]; `page_base` (a traced scalar) is where the tables'
    page 0 lies in the pool (a layer's run in a stack of layers). Returns
    the latent result [b, H, v_width]. Table entries past a row's last live
    page are never read."""
    use_pallas, interpret = qm._mode()
    if use_pallas and latent_decode_supported(
            q.shape, pool.shape, jnp.shape(block_tables), v_width,
            pool.dtype.itemsize):
        return _decode_pallas(q, pool, block_tables, pos, sm_scale, v_width,
                              page_base, interpret)
    return _decode_xla(q, pool, block_tables, pos, sm_scale, v_width,
                       page_base)


# ---------------------------------------------------------------------------
# prefill: a window of queries over decompressed keys, blocks over the keys
# ---------------------------------------------------------------------------

def _last_needed(meta_ref, qi, block_q, block_k):
    """The last key block the query block `qi` can see: that of its last
    real query's position."""
    last_q = jnp.minimum((qi + 1) * block_q - 1, meta_ref[1])
    return (meta_ref[0] + last_q) // block_k


def _prefill_kernel(meta_ref, qn_ref, qr_ref, k_ref, kr_ref, v_ref, o_ref,
                    m_ref, l_ref, acc_ref, *, block_q, block_k, sm_scale):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(ki <= _last_needed(meta_ref, qi, block_q, block_k))
    def _block():
        s = jax.lax.dot_general(qn_ref[0], k_ref[...],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s + jax.lax.dot_general(qr_ref[0], kr_ref[...],
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        s = s * sm_scale                                   # [bq, bk]
        qpos = meta_ref[0] + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos <= qpos, s, _NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _done():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _prefill_blocks(s, T):
    bq = min(PREFILL_BLOCK_Q, s)
    bk = min(PREFILL_BLOCK_K, T)
    return bq, bk


def latent_prefill_supported(qn_shape, qr_shape, kv_shape, v_dim):
    """True when the Pallas kernel can take q_nope [s, H, nope], q_pe [s,
    H, rope], kv [T, H * (nope + v)]: a head's key and value are lane
    blocks of `kv` (nope == v, a lane multiple) and the window and the
    table are whole blocks."""
    s, H, nope = qn_shape
    T = kv_shape[0]
    if nope != v_dim or nope % 128 or kv_shape[1] != H * (nope + v_dim):
        return False
    bq, bk = _prefill_blocks(s, T)
    return s % bq == 0 and T % bk == 0 and bq % 16 == 0 and bk % 128 == 0


def _prefill_pallas(q_nope, q_pe, kv, k_pe, h, last_idx, sm_scale, v_dim,
                    interpret):
    s, H, nope = q_nope.shape
    rope, T = q_pe.shape[-1], kv.shape[0]
    bq, bk = _prefill_blocks(s, T)
    meta = jnp.stack([jnp.asarray(h, jnp.int32),
                      jnp.asarray(last_idx, jnp.int32)])

    def q_map(hh, qi, ki, meta_ref):
        return (hh, qi, 0)

    def key_block(qi, ki, meta_ref):
        return jnp.minimum(ki, _last_needed(meta_ref, qi, bq, bk))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(H, s // bq, T // bk),
        in_specs=[
            pl.BlockSpec((1, bq, nope), q_map),
            pl.BlockSpec((1, bq, rope), q_map),
            pl.BlockSpec((bk, nope), lambda hh, qi, ki, m: (
                key_block(qi, ki, m), 2 * hh)),
            pl.BlockSpec((bk, rope), lambda hh, qi, ki, m: (
                key_block(qi, ki, m), 0)),
            pl.BlockSpec((bk, v_dim), lambda hh, qi, ki, m: (
                key_block(qi, ki, m), 2 * hh + 1)),
        ],
        out_specs=pl.BlockSpec((1, bq, v_dim), q_map),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, v_dim), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, block_q=bq, block_k=bk,
                          sm_scale=sm_scale),
        out_shape=jax.ShapeDtypeStruct((H, s, v_dim), q_nope.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="latent_prefill_attention",
    )(meta, jnp.swapaxes(q_nope, 0, 1), jnp.swapaxes(q_pe, 0, 1), kv, k_pe,
      kv)
    return jnp.swapaxes(out, 0, 1)


def _prefill_xla(q_nope, q_pe, kv, k_pe, h, last_idx, sm_scale, v_dim):
    """The same blocking in jnp: a loop over key blocks whose trip count
    follows the window's last real position."""
    s, H, nope = q_nope.shape
    T = kv.shape[0]
    bk = min(PREFILL_BLOCK_K, T)
    while T % bk:
        bk //= 2
    kvh = kv.reshape(T, H, nope + v_dim)
    qpos = h + jnp.arange(s, dtype=jnp.int32)

    def body(i, carry):
        acc, m, l = carry
        blk = jax.lax.dynamic_slice_in_dim(kvh, i * bk, bk, axis=0)
        kr = jax.lax.dynamic_slice_in_dim(k_pe, i * bk, bk, axis=0)
        sc = jnp.einsum("shn,thn->hst", q_nope, blk[..., :nope],
                        preferred_element_type=jnp.float32)
        sc = sc + jnp.einsum("shr,tr->hst", q_pe, kr,
                             preferred_element_type=jnp.float32)
        kpos = i * bk + jnp.arange(bk, dtype=jnp.int32)
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None],
                       sc * sm_scale, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "hst,thv->hsv", p.astype(kv.dtype), blk[..., nope:],
            preferred_element_type=jnp.float32)
        return acc, m_new, l

    n_blocks = jnp.minimum((h + last_idx) // bk + 1, T // bk)
    acc, _, l = jax.lax.fori_loop(
        0, n_blocks, body,
        (jnp.zeros((H, s, v_dim), jnp.float32),
         jnp.full((H, s, 1), _NEG_INF, jnp.float32),
         jnp.zeros((H, s, 1), jnp.float32)))
    return jnp.swapaxes(acc / l, 0, 1).astype(q_nope.dtype)


def latent_prefill_attention(q_nope, q_pe, kv, k_pe, h, last_idx, sm_scale,
                             v_dim):
    """Queries q_nope [s, H, nope], q_pe [s, H, rope] at positions h .. h +
    s - 1 (real up to `last_idx`; h and last_idx traced) over the keys and
    values of positions 0 .. T - 1: kv [T, H * (nope + v_dim)], a head's
    key then its value, and the rotary keys k_pe [T, rope] all heads
    share. Causal by absolute position. Returns [s, H, v_dim]; a padded
    query's row is finite and meaningless."""
    use_pallas, interpret = qm._mode()
    if use_pallas and latent_prefill_supported(q_nope.shape, q_pe.shape,
                                               kv.shape, v_dim):
        return _prefill_pallas(q_nope, q_pe, kv, k_pe, h, last_idx,
                               sm_scale, v_dim, interpret)
    return _prefill_xla(q_nope, q_pe, kv, k_pe, h, last_idx, sm_scale, v_dim)
