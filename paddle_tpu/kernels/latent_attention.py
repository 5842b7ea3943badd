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

Behind a learned TOKEN SELECTOR (a query attends the k keys of largest index
score alone) the same two kernels run under the selection's MASK:
`index_decode_scores` / `index_window_keys` make the scores (a decode row's
by the decode kernel's walk over the INDEX pool's live pages), `kth_largest`
finds each query's k-th largest bit by bit with its rows held in VMEM (no
sort: a sort of a row's 71,680 scores was 2.6 ms a layer on the chip, and an
index list needs a gather of 2,048 rows a query beside it: together 22 of a
35 ms decode step, my chip run, PR 41), `selected_of` turns that into the
mask (ties from the left), and the attention is `latent_decode_attention(..,
bias=)`, every live page read and the keys not selected masked, or
`latent_masked_prefill_attention`, the prefill kernel a chunk of keys at a
time with the running softmax carried between chunks.

Elsewhere, and as the parity oracles, jnp compositions of the same
blocking (`fused_dispatch` overrides the choice, as for the other kernels).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.kernels import quantized_matmul as qm

__all__ = ["latent_decode_attention", "latent_prefill_attention",
           "latent_decode_supported", "latent_prefill_supported",
           "index_decode_scores", "index_window_keys", "sortable",
           "kth_largest", "selected_of", "packed",
           "latent_masked_prefill_attention"]

_NEG_INF = -1e30
DECODE_BLOCK_K = 512       # positions of one decode compute block
PREFILL_BLOCK_Q = 512
PREFILL_BLOCK_K = 1024


# ---------------------------------------------------------------------------
# decode: one query a row over the row's pages, absorbed form
# ---------------------------------------------------------------------------

def _page_walk(pos_ref, bt_ref, base_ref, pool_hbm, buf, sems, slot_ref, ps,
               ppb, block, carry):
    """The walk over a grid step's ROW that both decode kernels make: a
    compute block is `ppb` pages, each one async copy of pool[base + bt[row,
    j]] into its rows of a [block, row width] VMEM buffer; the loop's trip
    count follows the row's position. Two buffers: block i + 1 streams
    while block i computes, and a row's last block starts the next row's
    first. `block(i, rows [block, width], carry) -> carry` is the kernel's
    own; returns the last carry."""
    last_table = bt_ref.shape[1] - 1

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

    row, rows = pl.program_id(0), pl.num_programs(0)

    @pl.when(row == 0)
    def _first():
        slot_ref[0] = 0
        start_block(0, 0, 0)

    n_blocks = last_page(row) // ppb + 1
    slot0 = slot_ref[0]

    def body(i, carry):
        slot = (slot0 + i) % 2

        @pl.when(i + 1 < n_blocks)
        def _next_block():
            start_block(row, i + 1, 1 - slot)

        @pl.when(jnp.logical_and(i + 1 == n_blocks, row + 1 < rows))
        def _next_row():
            start_block(row + 1, 0, 1 - slot)

        wait_block(block_pages(row, i), slot)
        return block(i, buf[slot], carry)

    carry = jax.lax.fori_loop(0, n_blocks, body, carry)
    slot_ref[0] = (slot0 + n_blocks) % 2
    return carry


def _decode_kernel(pos_ref, bt_ref, base_ref, ql_ref, qr_ref, *rest,
                   page_size, pages_per_block, sm_scale, v_width,
                   masked=False):
    # grid (b,): one step a ROW, its live pages walked in compute blocks
    # (`_page_walk`). `masked`: one more operand before the pool, the row's
    # additive bias [1, T] (0 where the row selected the key, -1e30
    # elsewhere), a block of it added to every head's scores.
    bias_ref, (pool_hbm, o_ref, buf, sems, slot_ref) = (
        (rest[0], rest[1:]) if masked else (None, rest))
    bk = page_size * pages_per_block
    pos = pos_ref[pl.program_id(0)]
    ql, qr = ql_ref[0], qr_ref[0]            # [H, v_width], [H, rope]
    H = ql.shape[0]

    def block(i, rows, carry):
        acc, m, l = carry
        c = rows[:, :v_width]                # [bk, v_width]: key and value
        r = rows[:, v_width:]                # [bk, rope]
        s = jax.lax.dot_general(ql, c, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s + jax.lax.dot_general(qr, r, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        s = s * sm_scale                                   # [H, bk]
        if masked:
            s = s + bias_ref[0, :, pl.ds(pl.multiple_of(i * bk, bk), bk)]
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

    acc, _, l = _page_walk(
        pos_ref, bt_ref, base_ref, pool_hbm, buf, sems, slot_ref, page_size,
        pages_per_block, block,
        (jnp.zeros((H, v_width), jnp.float32),
         jnp.full((H, 1), _NEG_INF, jnp.float32),
         jnp.zeros((H, 1), jnp.float32)))
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


def _decode_ppb(ps, P):
    """Pages of one decode compute block."""
    return max(1, min(DECODE_BLOCK_K // ps, P))


def _decode_pallas(q, pool, block_tables, pos, sm_scale, v_width, page_base,
                   interpret, bias=None):
    b, H, row = q.shape
    ps, P = pool.shape[1], block_tables.shape[1]
    ppb = _decode_ppb(ps, P)
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
                  pl.BlockSpec((1, H, row - v_width), row_map)]
        + ([] if bias is None else
           [pl.BlockSpec((1, 1, bias.shape[-1]), row_map)])
        + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, H, v_width), row_map),
        scratch_shapes=[pltpu.VMEM((2, ppb * ps, row), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    operands = (ql, qr) + (() if bias is None else (bias[:, None, :],))
    return pl.pallas_call(
        functools.partial(_decode_kernel, page_size=ps, pages_per_block=ppb,
                          sm_scale=sm_scale, v_width=v_width,
                          masked=bias is not None),
        out_shape=jax.ShapeDtypeStruct((b, H, v_width), q.dtype),
        grid_spec=grid_spec,
        # rows in order on one core: a row starts its successor's copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_decode_attention",
    )(*prefetch, *operands, pool)


def _decode_xla(q, pool, block_tables, pos, sm_scale, v_width, page_base,
                bias=None):
    """Gather each row's pages into [b, T, row] and attend under a position
    mask: the oracle (a copy of every table's width, every step)."""
    b, P = block_tables.shape
    pages = block_tables if page_base is None else page_base + block_tables
    rows = pool[pages].reshape(b, P * pool.shape[1], pool.shape[2])
    s = jnp.einsum("bhc,btc->bht", q, rows,
                   preferred_element_type=jnp.float32) * sm_scale
    if bias is not None:
        s = s + bias[:, None, :]
    t = jnp.arange(rows.shape[1], dtype=jnp.int32)
    s = jnp.where(t[None, None, :] <= jnp.asarray(pos)[:, None, None], s,
                  _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bht,btc->bhc", p.astype(q.dtype),
                      rows[..., :v_width],
                      preferred_element_type=jnp.float32).astype(q.dtype)


def latent_decode_attention(q, pool, block_tables, pos, sm_scale, v_width,
                            page_base=None, bias=None):
    """q [b, H, row] (absorbed queries: latent part, then rotary part) over
    the pool [pages, page_size, row] through block tables [b, P], valid
    prefix [0, pos[r]]; `page_base` (a traced scalar) is where the tables'
    page 0 lies in the pool (a layer's run in a stack of layers). `bias`
    [b, P * page_size] float32: added to every head's scores (a selection's
    mask: 0 where the row attends the key, -1e30 elsewhere; a row attends
    at least one). Returns the latent result [b, H, v_width]. Table entries
    past a row's last live page are never read."""
    use_pallas, interpret = qm._mode()
    if use_pallas and latent_decode_supported(
            q.shape, pool.shape, jnp.shape(block_tables), v_width,
            pool.dtype.itemsize) and (
                bias is None or jnp.shape(block_tables)[1] % _decode_ppb(
                    pool.shape[1], jnp.shape(block_tables)[1]) == 0):
        return _decode_pallas(q, pool, block_tables, pos, sm_scale, v_width,
                              page_base, interpret, bias)
    return _decode_xla(q, pool, block_tables, pos, sm_scale, v_width,
                       page_base, bias)


# ---------------------------------------------------------------------------
# the token selector's scores: I(t, s) = sum_j w_j(t) relu(qI_j(t) . kI(s))
# ---------------------------------------------------------------------------

def _index_kernel(pos_ref, bt_ref, base_ref, q_ref, w_ref, pool_hbm, o_ref,
                  buf, sems, slot_ref, *, page_size, pages_per_block):
    # grid (b,): `_decode_kernel`'s walk over the row's live pages of the
    # INDEX pool; a block's scores go to their place in the row's output
    # (blocks past the row's last page are never written: the caller masks
    # by position)
    bk = page_size * pages_per_block
    q, w = q_ref[0], w_ref[0]                # [J, d], [J, 1] float32

    def block(i, keys, carry):
        s = jax.lax.dot_general(q, keys, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.sum(jnp.maximum(s, 0.0) * w, axis=0, keepdims=True)
        o_ref[0, :, pl.ds(pl.multiple_of(i * bk, bk), bk)] = s   # [1, bk]
        return carry

    _page_walk(pos_ref, bt_ref, base_ref, pool_hbm, buf, sems, slot_ref,
               page_size, pages_per_block, block, 0)


def index_decode_supported(q_shape, pool_shape, bt_shape, itemsize=2):
    """True when the Pallas kernel can score qI [b, J, d] against an index
    pool [pages, page_size, d] through tables [b, P]."""
    if len(q_shape) != 3 or len(pool_shape) != 3 or len(bt_shape) != 2:
        return False
    b, J, d = q_shape
    ps, P = pool_shape[1], bt_shape[1]
    if pool_shape[2] != d or bt_shape[0] != b:
        return False
    ppb = _decode_ppb(ps, P)
    if ps % (32 // itemsize) or d % 128 or J % 8 or (ps * ppb) % 128:
        return False
    return P % ppb == 0


def _index_decode_pallas(qi, w, ipool, block_tables, pos, page_base,
                         interpret):
    b, J, d = qi.shape
    ps, P = ipool.shape[1], block_tables.shape[1]
    ppb = _decode_ppb(ps, P)
    prefetch = [jnp.asarray(pos, jnp.int32).reshape(b),
                jnp.asarray(block_tables, jnp.int32),
                jnp.asarray(0 if page_base is None else page_base,
                            jnp.int32).reshape(1)]

    def row_map(bi, *prefetch_refs):
        return (bi, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b,),
        in_specs=[pl.BlockSpec((1, J, d), row_map),
                  pl.BlockSpec((1, J, 1), row_map),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 1, P * ps), row_map),
        scratch_shapes=[pltpu.VMEM((2, ppb * ps, d), ipool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    return pl.pallas_call(
        functools.partial(_index_kernel, page_size=ps, pages_per_block=ppb),
        out_shape=jax.ShapeDtypeStruct((b, 1, P * ps), jnp.float32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="index_decode_scores",
    )(*prefetch, qi, w[..., None], ipool)[:, 0]


def _index_scores(qi, w, keys):
    """qI [n, J, d], w [n, J] float32, keys [.., t, d] (a leading axis of n,
    or none: every query against the same keys) -> I [n, t] float32."""
    eq = "njd,ntd->njt" if keys.ndim == 3 else "njd,td->njt"
    s = jnp.einsum(eq, qi, keys, preferred_element_type=jnp.float32)
    return jnp.sum(jnp.maximum(s, 0.0) * w[..., None], axis=1)


def index_decode_scores(qi, w, ipool, block_tables, pos, page_base=None):
    """The selector's scores of one query a row: qI [b, J, d] and head
    weights w [b, J] (float32) against the index keys of each row's
    positions, read from the index pool [pages, page_size, d] through the
    block tables [b, P]. Returns float32 [b, P * page_size], -inf past
    pos[r]. On TPU a Pallas kernel walks a row's live pages alone (256 B a
    key at the published width); elsewhere every row's table is gathered."""
    use_pallas, interpret = qm._mode()
    if use_pallas and index_decode_supported(
            qi.shape, ipool.shape, jnp.shape(block_tables),
            ipool.dtype.itemsize):
        out = _index_decode_pallas(qi, w, ipool, block_tables, pos,
                                   page_base, interpret)
    else:
        pages = (block_tables if page_base is None
                 else page_base + block_tables)
        keys = ipool[pages].reshape(pages.shape[0], -1, ipool.shape[2])
        out = _index_scores(qi, w, keys)
    t = jnp.arange(out.shape[1], dtype=jnp.int32)
    return jnp.where(t[None, :] <= jnp.asarray(pos)[:, None], out, -jnp.inf)


_KEY_MIN = jnp.iinfo(jnp.int32).min


def sortable(x):
    """float32 -> int32 that orders as the floats do (-0.0 as +0.0)."""
    b = jax.lax.bitcast_convert_type(x.astype(jnp.float32) + 0.0, jnp.int32)
    return b ^ ((b >> 31) & 0x7FFFFFFF)


def index_window_keys(qi, w, ipool, table, base, h, last_idx, block_k):
    """The selector's scores of a prefill window, as SORTABLE KEYS: qI [s,
    J, d], w [s, J] (the queries of positions h .. h + s - 1, real up to
    `last_idx`) against the index keys of the slot's positions (`table`: a
    layer's page numbers, whole blocks of them), a block of `block_k` at a
    time as far as the window's last real position. Returns
    int32 [s, T] (`sortable` of the float32 score; T the table's positions
    in whole blocks), the least int32 where the key is not visible to the
    query or past the last block scored."""
    s, ps = qi.shape[0], ipool.shape[1]
    ppb = block_k // ps
    n_blocks = table.shape[0] // ppb
    qpos = h + jnp.arange(s, dtype=jnp.int32)

    def body(i, keys):
        pages = base + jax.lax.dynamic_slice_in_dim(table, i * ppb, ppb)
        sc = _index_scores(qi, w, ipool[pages].reshape(block_k, -1))
        kpos = i * block_k + jnp.arange(block_k, dtype=jnp.int32)
        blk = jnp.where(kpos[None, :] <= qpos[:, None], sortable(sc),
                        _KEY_MIN)
        return jax.lax.dynamic_update_slice_in_dim(keys, blk, i * block_k, 1)

    live = jnp.minimum((h + last_idx) // block_k + 1, n_blocks)
    return jax.lax.fori_loop(
        0, live, body, jnp.full((s, n_blocks * block_k), _KEY_MIN, jnp.int32))


KTH_ROWS = 8          # rows of keys a grid step of the search holds
KTH_BLOCK = 4096      # and the lanes it counts at once


def _kth_search(count, rows, k):
    """(thr, room) [rows, 1] from `count(trial [rows, 1]) -> [rows, 1]`, the
    entries at or above a trial value: the sign, then 31 bits from the top."""
    def step(i, thr):
        trial = thr | jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(count(trial) >= k, trial, thr)

    zero = jnp.zeros((rows, 1), jnp.int32)
    thr = jnp.where(count(zero) >= k, zero, _KEY_MIN)
    thr = jax.lax.fori_loop(0, 31, step, thr)
    return thr, k - count(thr + 1)


def _kth_kernel(live_ref, keys_ref, thr_ref, room_ref, *, k, block):
    # grid (rows / KTH_ROWS,): the rows' keys [KTH_ROWS, T] lie in VMEM for
    # all 32 counting passes (read from HBM once); a pass counts the blocks
    # up to the step's last live one (`live_ref`), the rest hold the least
    # int32 and count for nothing
    n_blocks = live_ref[pl.program_id(0)]
    rows = keys_ref.shape[0]

    def count(trial):
        def body(j, c):
            blk = keys_ref[:, pl.ds(pl.multiple_of(j * block, block), block)]
            return c + jnp.sum((blk >= trial).astype(jnp.int32), axis=1,
                               keepdims=True)
        return jax.lax.fori_loop(0, n_blocks, body,
                                 jnp.zeros((rows, 1), jnp.int32))

    thr, room = _kth_search(count, rows, k)
    thr_ref[...] = jnp.broadcast_to(thr, thr_ref.shape)
    room_ref[...] = jnp.broadcast_to(room, room_ref.shape)


def _kth_block(T):
    return math.gcd(T, KTH_BLOCK)


def _kth_pallas(keys, k, live, interpret):
    s, T = keys.shape
    block = _kth_block(T)
    out = jax.ShapeDtypeStruct((s, 128), jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s // KTH_ROWS,),
        in_specs=[pl.BlockSpec((KTH_ROWS, T), lambda i, live: (i, 0))],
        out_specs=[pl.BlockSpec((KTH_ROWS, 128), lambda i, live: (i, 0))] * 2,
    )
    thr, room = pl.pallas_call(
        functools.partial(_kth_kernel, k=k, block=block),
        out_shape=[out, out], grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret, name="kth_largest",
    )(-(-jnp.max(live.reshape(-1, KTH_ROWS), axis=1) // block), keys)
    return thr[:, :1], room[:, :1]


def kth_largest(keys, k, live):
    """The k-th largest of each row of `keys` [s, T] (int32), found bit by
    bit as `sparse_attention._largest` finds it, without a sort: (thr [s,
    1], room [s, 1]), `room` the entries EQUAL to thr that are among the k
    largest (counted off from the lower index). `live` [s] int32 (traced):
    the columns of each row that can hold an entry above the least int32
    (at most T); those past it need not be counted. A row with
    fewer than k entries above the least int32 gets that value: everything
    visible is above. On TPU a Pallas kernel holds KTH_ROWS rows in VMEM for
    all 32 passes; elsewhere the passes are jnp over the whole rows."""
    use_pallas, interpret = qm._mode()
    s, T = keys.shape
    if use_pallas and s % KTH_ROWS == 0 and _kth_block(T) % 128 == 0:
        return _kth_pallas(keys, k, live, interpret)

    return _kth_search(lambda trial: jnp.sum(
        keys >= trial, axis=1, keepdims=True, dtype=jnp.int32), s, k)


def packed(mask):
    """bool [.., t] (t a multiple of 8) -> uint8 [.., t / 8], a position's
    bit the (position % 8)-th from the low end (`numpy.unpackbits(...,
    bitorder="little")` gives the mask back)."""
    bits = mask.reshape(mask.shape[:-1] + (-1, 8)).astype(jnp.uint8)
    return jnp.sum(bits << jnp.arange(8, dtype=jnp.uint8), axis=-1,
                   dtype=jnp.uint8)


def selected_of(keys, thr, room, before=0):
    """bool like `keys` [s, t]: the entries above thr, and the first `room`
    of those equal to it, `before` [s, 1] of which lie to the left of these
    columns. (An entry at the least int32 is no entry.)"""
    tie = keys == thr
    ahead = before + jnp.cumsum(tie, axis=1, dtype=jnp.int32)
    return (keys > _KEY_MIN) & ((keys > thr) | (tie & (ahead <= room)))


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


# ---------------------------------------------------------------------------
# prefill under the selector's mask: a chunk of decompressed keys at a time
# ---------------------------------------------------------------------------

MASKED_CHUNK = 4096        # keys decompressed at once under a selection


def _masked_kernel(meta_ref, qn_ref, qr_ref, k_ref, kr_ref, v_ref, bias_ref,
                   m_in, l_in, acc_in, m_out, l_out, acc_out, m_ref, l_ref,
                   acc_ref, *, block_q, block_k, sm_scale):
    # `_prefill_kernel` over ONE CHUNK of the keys, under an additive bias
    # (0 where the query selected the key, -1e30 elsewhere, causality
    # included): the running (max, sum, accumulator) come in from the chunk
    # before and go out unnormalised. meta = [h, last_idx, the chunk's
    # first position]
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = m_in[0]
        l_ref[...] = l_in[0]
        acc_ref[...] = acc_in[0]

    last_q = jnp.minimum((qi + 1) * block_q - 1, meta_ref[1])

    @pl.when(meta_ref[2] + ki * block_k <= meta_ref[0] + last_q)
    def _block():
        s = jax.lax.dot_general(qn_ref[0], k_ref[0],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s + jax.lax.dot_general(qr_ref[0], kr_ref[...],
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        s = s * sm_scale + bias_ref[...].astype(jnp.float32)   # [bq, bk]
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _done():
        m_out[0] = m_ref[...]
        l_out[0] = l_ref[...]
        acc_out[0] = acc_ref[...]


def latent_masked_supported(qn_shape, k_shape):
    """True when the Pallas kernel can take q_nope [H, s, nope] against a
    chunk of keys [H, C, nope]: whole blocks of queries and keys."""
    H, s, _ = qn_shape
    bq, bk = _prefill_blocks(s, k_shape[1])
    return (s % bq == 0 and k_shape[1] % bk == 0 and bq % 16 == 0
            and bk % 128 == 0)


def _masked_chunk_pallas(qn, qr, k, kr, v, bias, carry, meta, sm_scale,
                         interpret):
    H, s, nope = qn.shape
    rope, C, v_dim = qr.shape[-1], k.shape[1], v.shape[-1]
    bq, bk = _prefill_blocks(s, C)
    acc, m, l = carry

    def q_map(hh, qi, ki, meta_ref):
        return (hh, qi, 0)

    def key_block(qi, ki, meta_ref):
        # a block no query of the query block can see is not copied: the
        # index map stays on the last one needed
        last_q = jnp.minimum((qi + 1) * bq - 1, meta_ref[1])
        need = jnp.maximum(meta_ref[0] + last_q - meta_ref[2], 0) // bk
        return jnp.minimum(ki, need)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(H, s // bq, C // bk),
        in_specs=[
            pl.BlockSpec((1, bq, nope), q_map),
            pl.BlockSpec((1, bq, rope), q_map),
            pl.BlockSpec((1, bk, nope), lambda hh, qi, ki, mr: (
                hh, key_block(qi, ki, mr), 0)),
            pl.BlockSpec((bk, rope), lambda hh, qi, ki, mr: (
                key_block(qi, ki, mr), 0)),
            pl.BlockSpec((1, bk, v_dim), lambda hh, qi, ki, mr: (
                hh, key_block(qi, ki, mr), 0)),
            pl.BlockSpec((bq, bk), lambda hh, qi, ki, mr: (
                qi, key_block(qi, ki, mr))),
            pl.BlockSpec((1, bq, 1), q_map),
            pl.BlockSpec((1, bq, 1), q_map),
            pl.BlockSpec((1, bq, v_dim), q_map),
        ],
        out_specs=[pl.BlockSpec((1, bq, 1), q_map),
                   pl.BlockSpec((1, bq, 1), q_map),
                   pl.BlockSpec((1, bq, v_dim), q_map)],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, v_dim), jnp.float32)],
    )
    m, l, acc = pl.pallas_call(
        functools.partial(_masked_kernel, block_q=bq, block_k=bk,
                          sm_scale=sm_scale),
        out_shape=[jax.ShapeDtypeStruct(m.shape, jnp.float32),
                   jax.ShapeDtypeStruct(l.shape, jnp.float32),
                   jax.ShapeDtypeStruct(acc.shape, jnp.float32)],
        grid_spec=grid_spec,
        input_output_aliases={7: 0, 8: 1, 9: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="latent_masked_prefill_attention",
    )(meta, qn, qr, k, kr, v, bias, m, l, acc)
    return acc, m, l


def _masked_chunk_xla(qn, qr, k, kr, v, bias, carry, sm_scale):
    acc, m, l = carry
    sc = jnp.einsum("hsn,htn->hst", qn, k,
                    preferred_element_type=jnp.float32)
    sc = sc + jnp.einsum("hsr,tr->hst", qr, kr,
                         preferred_element_type=jnp.float32)
    sc = sc * sm_scale + bias[None].astype(jnp.float32)
    m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
    p = jnp.exp(sc - m_new)
    alpha = jnp.exp(m - m_new)
    l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc * alpha + jnp.einsum("hst,htv->hsv", p.astype(v.dtype), v,
                                   preferred_element_type=jnp.float32)
    return acc, m_new, l


def latent_masked_prefill_attention(q_nope, q_pe, chunk_kv, keys, thr, room,
                                    h, last_idx, sm_scale, chunk):
    """`latent_prefill_attention` where a query attends the keys it SELECTED
    alone: queries q_nope [s, H, nope], q_pe [s, H, rope] at positions h ..
    h + s - 1 (real up to `last_idx`); `chunk_kv(c)` gives chunk c's
    decompressed keys [H, chunk, nope], values [H, chunk, v] and shared
    rotary keys [chunk, rope] (the caller rebuilds them from the cached
    rows: the whole context's would not fit beside the pools at 64
    heads); the selection is `selected_of(keys, thr, room)` over the
    selector's sortable keys [s, T]. A loop over the chunks as far as the
    window's last real position, the running softmax carried across.
    Returns [s, H, v]; a padded query's row is finite and meaningless."""
    s, H, _ = q_nope.shape
    qn, qr = jnp.swapaxes(q_nope, 0, 1), jnp.swapaxes(q_pe, 0, 1)
    use_pallas, interpret = qm._mode()

    def body(c, carry):
        state, seen = carry
        k, v, kr = chunk_kv(c)
        blk = jax.lax.dynamic_slice_in_dim(keys, c * chunk, chunk, 1)
        # bfloat16: every head's grid steps read the block again
        bias = jnp.where(selected_of(blk, thr, room, seen), 0.0,
                         _NEG_INF).astype(jnp.bfloat16)
        seen = seen + jnp.sum(blk == thr, axis=1, keepdims=True,
                              dtype=jnp.int32)
        if use_pallas and latent_masked_supported(qn.shape, k.shape):
            meta = jnp.stack([jnp.asarray(h, jnp.int32),
                              jnp.asarray(last_idx, jnp.int32),
                              jnp.asarray(c * chunk, jnp.int32)])
            state = _masked_chunk_pallas(qn, qr, k, kr, v, bias, state, meta,
                                         sm_scale, interpret)
        else:
            state = _masked_chunk_xla(qn, qr, k, kr, v, bias, state,
                                      sm_scale)
        return state, seen

    _, v0, _ = jax.eval_shape(chunk_kv, 0)
    n_chunks = jnp.minimum((h + last_idx) // chunk + 1,
                           keys.shape[1] // chunk)
    (acc, _, l), _ = jax.lax.fori_loop(0, n_chunks, body, (
        (jnp.zeros((H, s, v0.shape[-1]), jnp.float32),
         jnp.full((H, s, 1), _NEG_INF, jnp.float32),
         jnp.zeros((H, s, 1), jnp.float32)),
        jnp.zeros((s, 1), jnp.int32)))
    return jnp.swapaxes(acc / l, 0, 1).astype(q_nope.dtype)
