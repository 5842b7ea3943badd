"""A prefill window's causal attention over its slot's pages, through the
block table: the prefill twin of `quantized_matmul.paged_decode_attention`.

The window's own keys are in the pool already (write-before-attend), so the
queries `q [s, nh, hd]` at positions `h .. h + s - 1` attend over the pages
`bt_row [P]` names, as far as the window's last real position `h + last_idx`
and no further: the work follows the context, never the table's width.
Causal by absolute position, online softmax in float32, GQA without
repeating K / V (the `nh / nkv` query heads of a KV head are stacked as rows
of one matmul).

  - Pallas on the TPU: the table and `(h, last_idx, page_base)` are
    scalar-prefetched; the pools stay in HBM and a compute block of
    `pages_per_block` pages is fetched by one async copy a live page and
    KV head, two buffers deep. Grid: KV head x a span of query blocks, which share the
    key blocks the step fetches. The key blocks are a LOOP inside the step
    whose trip count follows the span's last real position, not a third
    grid axis: a grid is static, so an axis over key blocks would be as
    long as the table (`pages_per_slot`), and a skipped step still costs
    its ~0.35 us (what PR 25 found in the decode kernel: a grid of 32 x 8 x
    128 steps, four in five skipped, 2.8% of its roofline). A query block
    skips the key blocks past its own last row (`pl.when`); a step's last
    block starts the next step's first.
  - jnp elsewhere (the CPU, `fused_dispatch(False)`, shapes `_supported`
    refuses), with the same blocking: a `fori_loop` over key blocks whose
    trip count is `(h + last_idx) // block + 1`.

An int8 pool (`k_scale` / `v_scale`, per (page, KV head) absmax) is
dequantised as it is read: in registers in the kernel (the scales fold into
the scores and the probabilities), a key block at a time in the fall-back.

UNDER A SELECTION (a block-sparse layer's window: `sparse_attention`;
STATIC-optional, a call without one traces to the program it always did) the
same walk over the table's entries `0 .. last` applies one more predicate:
`page_bits` packs the per-query mask `sel [nkv, s, P]` by key block (a row's
word i = its bits over key block i's pages, in VMEM) and says for each
(query block, key block) whether any row picked a page of it (SMEM). A score
stands iff its row's bit is set AND the key is at or below the row's limit,
one `[block_q, block_k]` mask for all the heads of the group (they pick
together); a query block none of whose rows has a bit in a key block skips
it as it skips the blocks past its last row. Every page up to the step's
last position is still FETCHED (a list of the picked pages alone is later
work: ROADMAP A4). The span shrinks with the group so that the accumulator
stays in VMEM (`SPAN_ROWS`). No int8 pool and no jnp form under a selection:
the fall-back is `sparse_attention`'s loop.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.kernels import quantized_matmul as qm

__all__ = ["paged_prefill_attention", "Selection", "page_bits",
           "takes_kernel"]

_NEG_INF = -1e30

# window positions a matmul: its rows are these times the query heads a KV
# head, bounded by MAX_ROWS
BLOCK_Q = 128
MAX_ROWS = 1024
# window positions a grid step: SPAN / BLOCK_Q query blocks share every key
# block the step fetches (a copy a page and KV head is 16 KB at the serving
# cell's shapes; my chip runs, PR 38: 554 -> 515 us a call from a span of
# 128 to 512, 256-key blocks, a 512-token window at 2k context)
SPAN = 512
# ... and rows of the accumulator a grid step: the span shrinks with the
# query heads a KV head (at 16 a group, 512 positions are 8,192 rows of q,
# accumulator, m and l: 23 MB of VMEM; 2,048 rows are 5.8 MB and 128
# positions)
SPAN_ROWS = 2048
# keys a compute block, many pages of it: a query block's visit to a key
# block costs as much as ~1,100 more keys whatever the block's width (the
# two cross-lane row reductions and the rescaling of m, l and the
# accumulator), so wide blocks win over the causal waste on the diagonal
# (my chip runs, PR 38: 519 / 318 / 328 us a call at 512 / 1,024 / 2,048
# keys for a 512-token window at 4k context, 32 / 8 heads x 128)
BLOCK_K = 1024
# the fall-back's: XLA fuses a block's whole chain, narrow blocks waste less
XLA_BLOCK_K = 256
# what the kernel may take of the v5e's 128 MiB of VMEM (Mosaic's default is
# 16 MiB), and what the gate's own count of the working set may come to
_VMEM_LIMIT_BYTES = 32 * 1024 * 1024
_VMEM_BUDGET_BYTES = 16 * 1024 * 1024


def _tile_pages(pages, want=8):
    return max(p for p in range(1, min(want, pages) + 1) if pages % p == 0)


def _blocks(s, group, page_size, pages_per_slot):
    """(query positions a matmul, query positions a grid step, pages a key
    block), from the shapes."""
    bq = min(s, BLOCK_Q, max(16, MAX_ROWS // group))
    span = min(s, max(bq, min(SPAN, SPAN_ROWS // group) // bq * bq))
    return bq, span, max(1, min(BLOCK_K // page_size, pages_per_slot))


def _block_scales(scale_ref, pages, head, nkv, page_size):
    """[1, len(pages) * page_size] f32: for each column of a compute block
    the absmax scale of (its page, KV head `head`), from the flattened SMEM
    table (`quantized_matmul._paged_block_scales` for one head)."""
    bk = len(pages) * page_size
    col_page = jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1) // page_size
    r = jnp.zeros((1, bk), jnp.float32)
    for i, page in enumerate(pages):
        r = jnp.where(col_page == i, scale_ref[page * nkv + head], r)
    return r


def _kernel(*refs, page_size, pages_per_block, block_q, span, group, sm_scale,
            quantized, selected):
    # grid (nkv, s // span): one step a KV head and `span` window positions,
    # as span / block_q query blocks that share the key blocks the step
    # fetches. A query block's rows are its positions for each of the KV
    # head's `group` query heads, stacked [group * block_q, hd]. Scalars in
    # SMEM: meta = (h, last_idx, page_base), the slot's block table [P] and,
    # for an int8 pool, the K and V scales of the table's own pages
    # [num_pages * nkv]. `selected` (a `Selection`): in SMEM, for each (KV
    # head, query block, key block), whether any of the block's rows picked
    # a page of it; in VMEM the span's rows' picks, word i of a row = its
    # bits over key block i's pages.
    meta_ref, bt_ref, *refs = refs
    sk_ref, sv_ref = (refs.pop(0), refs.pop(0)) if quantized else (None, None)
    visit_ref = refs.pop(0) if selected else None
    q_ref = refs.pop(0)
    bits_ref = refs.pop(0) if selected else None
    (k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, slot_ref, qs_ref,
     m_ref, l_ref, acc_ref) = refs
    ps, ppb, bq, g = page_size, pages_per_block, block_q, group
    bk, hd = ps * ppb, k_buf.shape[-1]
    nsub, rows = span // bq, g * bq
    head, si = pl.program_id(0), pl.program_id(1)
    heads, steps = pl.num_programs(0), pl.num_programs(1)
    h, last_idx, base = meta_ref[0], meta_ref[1], meta_ref[2]
    last_table = bt_ref.shape[0] - 1
    key_blocks = -(-bt_ref.shape[0] // ppb)

    def last_pos(end):
        # the last position rows up to window row `end` - 1 may see: that
        # row's own, or the window's last real one
        return h + jnp.minimum(end - 1, last_idx)

    def last_page(step):
        return jnp.minimum(last_pos((step + 1) * span) // ps, last_table)

    def live_pages(step, blk):
        # how many of the block's pages the step sees: a table entry past
        # its last page is never read
        return jnp.clip(last_page(step) - blk * ppb + 1, 0, ppb)

    def copies(kv, blk, i, slot):
        page = base + bt_ref[blk * ppb + i]
        dst = (slot, pl.ds(pl.multiple_of(i * ps, ps), ps), slice(None))
        return (pltpu.make_async_copy(k_hbm.at[page, kv], k_buf.at[dst],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[page, kv], v_buf.at[dst],
                                      sems.at[1, slot]))

    # loops over the block's pages, not Python ones: unrolled 16 pages a
    # block, in three places, the kernel's trace costs more than the
    # program's compile-cache hit saves (3.7 s a window bucket on the
    # serving host)
    def start_block(kv, step, blk, slot):
        def start(i, _):
            for c in copies(kv, blk, i, slot):
                c.start()

        jax.lax.fori_loop(0, live_pages(step, blk), start, None)

    def wait_block(blk, slot):
        def wait(i, _):
            for c in copies(head, blk, i, slot):
                c.wait()

        jax.lax.fori_loop(0, live_pages(si, blk), wait, None)
        if not quantized:
            # a key past the step's last position (the tail of the last
            # fetched page holds what the pool held, a page nobody fetched
            # what the buffer did) is masked in the scores below, and its
            # V must be zero as well: a probability of 0 times a NaN is
            # not 0. Only the step's last block holds such keys, and int8
            # codes are finite whatever they are
            @pl.when(blk == last_page(si) // ppb)
            def _tail():
                kpos = blk * bk + jax.lax.broadcasted_iota(
                    jnp.int32, (bk, 1), 0)
                v_buf[slot] = jnp.where(kpos <= last_pos((si + 1) * span),
                                        v_buf[slot], 0).astype(v_buf.dtype)

    @pl.when(jnp.logical_and(head == 0, si == 0))
    def _first():
        slot_ref[0] = 0
        start_block(0, 0, 0, 0)

    # a query block's query heads as rows of ONE matmul: [bq, g * hd] ->
    # [g * bq, hd]; row r of block u is window position si * span + u * bq
    # + r % bq
    for u in range(nsub):
        for j in range(g):
            qs_ref[u * rows + j * bq:u * rows + (j + 1) * bq, :] = q_ref[
                u * bq:(u + 1) * bq, j * hd:(j + 1) * hd]
    m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    def limit(u, heads_stacked=g):
        # the last key a row sees: its own position, and for a padded row
        # the window's last real one (finite, meaningless, in fetched pages)
        return jnp.concatenate(
            [h + jnp.minimum(si * span + u * bq + row, last_idx)]
            * heads_stacked, axis=0)

    # under a selection one [bq, bk] mask serves all the group's heads
    limits = [limit(u, 1 if selected else g) for u in range(nsub)]
    n_blocks = last_page(si) // ppb + 1
    if selected:
        col_page = jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1) // ps
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    slot0 = slot_ref[0]
    last_step = jnp.logical_and(head == heads - 1, si == steps - 1)
    wraps = si + 1 == steps

    def picked(u, i):
        # [bq, bk]: whether the row picked the column's page: bit (column's
        # place in the block) of the row's word i
        words = bits_ref[u * bq:(u + 1) * bq,
                         pl.ds(pl.multiple_of(i // 128 * 128, 128), 128)]
        word = jnp.sum(jnp.where(lane == i % 128, words, 0), axis=1,
                       keepdims=True)
        return jnp.bitwise_and(jnp.right_shift(word, col_page), 1) == 1

    def body(i, _):
        slot = (slot0 + i) % 2

        @pl.when(i + 1 < n_blocks)
        def _next_block():
            start_block(head, si, i + 1, 1 - slot)

        @pl.when(jnp.logical_and(i + 1 == n_blocks,
                                 jnp.logical_not(last_step)))
        def _next_step():
            start_block(jnp.where(wraps, head + 1, head),
                        jnp.where(wraps, 0, si + 1), 0, 1 - slot)

        wait_block(i, slot)
        if quantized:
            lp = last_page(si)
            ids = [bt_ref[jnp.minimum(i * ppb + j, lp)] for j in range(ppb)]
            k_scale = _block_scales(sk_ref, ids, head, heads, ps) * (
                sm_scale / 127.0)
            v_scale = _block_scales(sv_ref, ids, head, heads, ps) / 127.0
        kpos = i * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)

        for u in range(nsub):
            at = slice(u * rows, (u + 1) * rows)
            # query block u sees this key block iff its last row does
            seen = i * bk <= last_pos(si * span + (u + 1) * bq)
            if selected:
                # ... and one of its rows picked one of the block's pages
                seen = jnp.logical_and(seen, visit_ref[
                    ((head * steps + si) * nsub + u) * key_blocks + i] != 0)

            @pl.when(seen)
            def _block():
                k, v = k_buf[slot], v_buf[slot]             # [bk, hd]
                if quantized:                               # exact in bf16
                    k, v = k.astype(qs_ref.dtype), v.astype(qs_ref.dtype)
                s = jax.lax.dot_general(
                    qs_ref[at, :], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)     # [g * bq, bk]
                s = s * (k_scale if quantized else sm_scale)
                if selected:
                    ok = jnp.logical_and(picked(u, i), kpos <= limits[u])
                    s = jnp.where(ok[None], s.reshape(g, bq, bk),
                                  _NEG_INF).reshape(rows, bk)
                else:
                    s = jnp.where(kpos <= limits[u], s, _NEG_INF)
                m = m_ref[at, :]
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m - m_new)
                l_ref[at, :] = l_ref[at, :] * alpha + jnp.sum(
                    p, axis=-1, keepdims=True)
                if quantized:
                    p = p * v_scale
                acc_ref[at, :] = acc_ref[at, :] * alpha + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_ref[at, :] = m_new

    jax.lax.fori_loop(0, n_blocks, body, None)
    slot_ref[0] = (slot0 + n_blocks) % 2
    # under a selection a row no visit reached (a padded row whose picks lie
    # past the window's last position) sums to nothing
    acc, total = acc_ref[...], l_ref[...]
    out = (acc / (jnp.maximum(total, 1e-30) if selected else total)).astype(
        o_ref.dtype)
    for u in range(nsub):
        for j in range(g):
            o_ref[u * bq:(u + 1) * bq, j * hd:(j + 1) * hd] = out[
                u * rows + j * bq:u * rows + (j + 1) * bq]


def _supported(q_shape, pool_shape, bt_shape, q_itemsize=2, pool_itemsize=2,
               selected=False):
    """True when the Pallas kernel can take q [s, nh, hd] against a page
    pool [num_pages, nkv, page_size, hd] through a table [P]: whole query
    heads a KV head, hd lane-aligned (a head is a lane block of the
    window's rows), the page and the query block whole sublane tiles of
    their types (an int8 page needs page_size % 32 == 0), the window whole
    grid steps of whole query blocks, the working set in VMEM. Under a
    selection: the rows' picks beside it, a key block's pages the bits of
    one word, the query block whole float32 tiles (one mask serves the
    group's heads), no int8 pool."""
    if len(q_shape) != 3 or len(pool_shape) != 4 or len(bt_shape) != 1:
        return False
    s, nh, hd = q_shape
    nkv, ps = pool_shape[1], pool_shape[2]
    if pool_shape[3] != hd or hd % 128 or nkv <= 0 or nh % nkv:
        return False
    g = nh // nkv
    bq, span, ppb = _blocks(s, g, ps, bt_shape[0])
    if ps % (32 // max(int(pool_itemsize), 1)) or s % span or span % bq \
            or bq % (32 // max(int(q_itemsize), 1)):
        return False
    rows, bk = g * bq, ppb * ps
    # K and V blocks double-buffered, a query block's f32 scores and
    # probabilities; of the whole span the stacked q, the accumulator, m
    # and l (a column pads to 128 lanes), the q and o blocks double-buffered
    need = (2 * 2 * bk * hd * pool_itemsize + 2 * rows * bk * 4
            + g * span * (hd * (q_itemsize + 4) + 2 * 128 * 4
                          + 2 * 2 * hd * q_itemsize))
    if selected:
        if pool_itemsize == 1 or bq % 8 or ppb > 32:
            return False
        # the span's picks double-buffered, a query block's mask
        need += 2 * span * _words(bt_shape[0], ppb)[1] * 4 + bq * bk * 4
    return need <= _VMEM_BUDGET_BYTES


def takes_kernel(q, pool, bt_row, selected=False):
    """Whether `paged_prefill_attention` runs these operands as the Pallas
    kernel (the mode, then the shapes)."""
    return qm._mode()[0] and _supported(
        q.shape, pool.shape, jnp.shape(bt_row), q.dtype.itemsize,
        pool.dtype.itemsize, selected)


class Selection(NamedTuple):
    """A window's picks as the kernel reads them (`page_bits`)."""
    bits: jax.Array     # [nkv, s, words] int32: a row's picks, by key block
    visit: jax.Array    # [nkv, s // block_q, key blocks] int32


def _words(pages_per_slot, ppb):
    """(key blocks a table holds, those rounded up to whole lane tiles)."""
    blocks = -(-pages_per_slot // ppb)
    return blocks, -(-blocks // 128) * 128


def page_bits(sel, group, page_size):
    """sel [nkv, s, P] bool, the table entries each of a window's queries
    attends (KV heads pick apart) -> the `Selection` the kernel reads: bit j
    of a row's word i is its pick of entry `i * pages_per_block + j`, and a
    (query block, key block) is flagged where any of its rows has a bit."""
    nkv, s, P = sel.shape
    bq, _, ppb = _blocks(s, group, page_size, P)
    blocks, words = _words(P, ppb)
    sel = jnp.pad(sel, ((0, 0), (0, 0), (0, blocks * ppb - P)))
    bits = jnp.sum(
        jnp.where(sel.reshape(nkv, s, blocks, ppb),
                  jnp.left_shift(1, jnp.arange(ppb, dtype=jnp.int32)), 0),
        axis=-1, dtype=jnp.int32)
    visit = jnp.any(bits.reshape(nkv, s // bq, bq, blocks) != 0,
                    axis=2).astype(jnp.int32)
    return Selection(jnp.pad(bits, ((0, 0), (0, 0), (0, words - blocks))),
                     visit)


def _pallas(q, pool_k, pool_v, bt_row, h, last_idx, page_base, k_scale,
            v_scale, sm_scale, interpret, block_q=None, span=None,
            pages_per_block=None, selection=None):
    s, nh, hd = q.shape
    nkv, ps = pool_k.shape[1], pool_k.shape[2]
    g = nh // nkv
    blocks = _blocks(s, g, ps, bt_row.shape[0])
    bq, span, ppb = (given or own for given, own in zip(
        (block_q, span, pages_per_block), blocks))
    bk = ppb * ps
    meta = jnp.stack([jnp.asarray(x, jnp.int32).reshape(()) for x in (
        h, last_idx, 0 if page_base is None else page_base)])
    prefetch = [meta, jnp.asarray(bt_row, jnp.int32)]
    if k_scale is not None:
        prefetch += [k_scale.astype(jnp.float32).reshape(-1),
                     v_scale.astype(jnp.float32).reshape(-1)]

    def q_map(kv, si, *prefetch_refs):
        return (si, kv)

    operands = [q.reshape(s, nh * hd)]
    in_specs = [pl.BlockSpec((span, g * hd), q_map)]
    if selection is not None:
        if blocks != (bq, span, ppb) or k_scale is not None:
            raise ValueError("a selection is laid out for the shapes' own "
                             "blocking, over an unquantized pool")
        prefetch.append(selection.visit.reshape(-1))
        operands.append(selection.bits)
        in_specs.append(pl.BlockSpec(
            (None, span, selection.bits.shape[-1]),
            lambda kv, si, *prefetch_refs: (kv, si, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(nkv, s // span),
        in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY),
                             pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((span, g * hd), q_map),
        scratch_shapes=[pltpu.VMEM((2, bk, hd), pool_k.dtype),
                        pltpu.VMEM((2, bk, hd), pool_v.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.VMEM((g * span, hd), q.dtype),
                        pltpu.VMEM((g * span, 1), jnp.float32),
                        pltpu.VMEM((g * span, 1), jnp.float32),
                        pltpu.VMEM((g * span, hd), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, page_size=ps, pages_per_block=ppb,
                          block_q=bq, span=span, group=g, sm_scale=sm_scale,
                          quantized=k_scale is not None,
                          selected=selection is not None),
        out_shape=jax.ShapeDtypeStruct((s, nh * hd), q.dtype),
        grid_spec=grid_spec,
        # steps in order on one core: a step starts its successor's copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="paged_prefill_attention",
    )(*prefetch, *operands, pool_k, pool_v)
    return out.reshape(s, nh, hd)


def _xla(q, pool_k, pool_v, bt_row, h, last_idx, page_base, k_scale, v_scale,
         sm_scale):
    """The same blocking in jnp: a loop over key blocks of whole pages,
    gathered (and, from an int8 pool, dequantised) a block at a time; its
    trip count follows the window's last real position."""
    s, nh, hd = q.shape
    nkv, ps = pool_k.shape[1], pool_k.shape[2]
    g = nh // nkv
    tp = _tile_pages(bt_row.shape[0], max(1, XLA_BLOCK_K // ps))
    tile = tp * ps
    qg = q.reshape(s, nkv, g, hd)
    last_pos = h + last_idx
    limit = jnp.minimum(h + jnp.arange(s, dtype=jnp.int32), last_pos)

    def block(pool, scale, pages):
        x = pool[pages if page_base is None else page_base + pages]
        if scale is not None:
            x = (x.astype(jnp.float32)
                 * (scale[pages] / 127.0)[..., None, None]).astype(q.dtype)
        return jnp.swapaxes(x, 0, 1).reshape(nkv, tile, hd)

    def body(i, carry):
        acc, m, l = carry
        pages = jax.lax.dynamic_slice_in_dim(bt_row, i * tp, tp)
        kpos = i * tile + jnp.arange(tile, dtype=jnp.int32)
        kt = block(pool_k, k_scale, pages)
        # a key past the window's last position holds anything: masked in
        # the scores, and zero in V (a probability of 0 times it is not 0)
        vt = jnp.where((kpos <= last_pos)[None, :, None],
                       block(pool_v, v_scale, pages), 0)
        sc = jnp.einsum("sngd,ntd->ngst", qg, kt,
                        preferred_element_type=jnp.float32) * sm_scale
        sc = jnp.where(kpos[None, :] <= limit[:, None], sc, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "ngst,ntd->ngsd", p.astype(vt.dtype), vt,
            preferred_element_type=jnp.float32)
        return acc, m_new, l

    acc, _, l = jax.lax.fori_loop(
        0, last_pos // tile + 1, body,
        (jnp.zeros((nkv, g, s, hd), jnp.float32),
         jnp.full((nkv, g, s, 1), _NEG_INF, jnp.float32),
         jnp.zeros((nkv, g, s, 1), jnp.float32)))
    return jnp.moveaxis(acc / l, 2, 0).reshape(s, nh, hd).astype(q.dtype)


@jax.named_scope("pt.paged_attention")
def paged_prefill_attention(q, pool_k, pool_v, bt_row, h, last_idx,
                            page_base=None, k_scale=None, v_scale=None,
                            selection=None):
    """q [s, nh, hd] at positions h .. h + s - 1 (real up to `last_idx`; h
    and last_idx traced) over pool_k / pool_v [num_pages, nkv, page_size,
    hd], which already hold the window's own keys, through the slot's block
    table bt_row [P] (page i holds positions [i * ps, (i + 1) * ps)). A key
    is read iff its position is at or below the query's and at or below `h
    + last_idx`: table entries, and pages, past that one's are never read.
    Returns [s, nh, hd]; a padded query's row is finite and meaningless.

    page_base (a traced scalar): the table indexes a run of pages that
    starts there in the pools: one layer's pages in a stack of layers viewed
    [L * num_pages, nkv, page_size, hd]. k_scale / v_scale [num_pages, nkv]:
    the pools are int8 with per (page, KV head) absmax scales, those of the
    run alone (`paged_decode_attention`'s conventions, both).

    selection (`page_bits` of a per-query mask over the table's entries):
    a key is read iff, besides, its query picked its entry. The kernel's
    alone (ask `takes_kernel(..., selected=True)` first): the jnp form of a
    selection is `sparse_attention`'s loop."""
    sm_scale = 1.0 / math.sqrt(q.shape[-1])
    _, interpret = qm._mode()
    if takes_kernel(q, pool_k, bt_row, selection is not None):
        return _pallas(q, pool_k, pool_v, bt_row, h, last_idx, page_base,
                       k_scale, v_scale, sm_scale, interpret,
                       selection=selection)
    if selection is not None:
        raise ValueError("no jnp form under a selection: ask takes_kernel")
    return _xla(q, pool_k, pool_v, bt_row, h, last_idx, page_base, k_scale,
                v_scale, sm_scale)
