"""Block-sparse attention over a paged KV cache, the blocks picked per query
from a cache of COMPRESSED KEYS (InfLLM v2, as MiniCPM4 publishes it).

A block is a page. The compressed key of kernel j is the mean of the keys
of positions [stride*j, stride*j + kernel_size). A query at position t
(context n = t + 1) with n <= dense_len attends causally over everything;
past that it scores every kernel that lies wholly inside its context
(softmax over the kernels, summed over the query heads of its KV group),
gives a block the best score of the kernels that overlap it, and attends
over `init_blocks` first blocks, the `local_blocks` newest (its own
included) and the best-scored others, `topk` in all.

Where the compressed keys live: `kc` [num_pages, nkv, per, d] beside the
K/V pool, under the same page ids, `per = block_size / kernel_stride`
entries a page. Kernel j is kept IN THE PAGE WHERE IT ENDS (entry
`(end % block_size + 1) / stride - 1` of the page of `end = stride*j +
kernel_size - 1`), so an entry depends only on tokens up to its own place in
its own page: a page shared through the prefix cache carries entries that
are right for every request that shares it, a copy-on-write page copy carries
them along, and an entry is written exactly when the token that completes it
is written, by the page's owner. Laid out flat over a block table, entry f
is kernel `f - overlap` (`overlap = kernel_size / stride - 1`), and block b
is overlapped by the entries [per*b, per*b + per + overlap): its own page's
and the first `overlap` of the next.

Decode reuses the paged decode kernel as it is: the selection IS a block
table (`selected_table`), sorted ascending with the query's own page last,
and because these layers carry no rotary position a page's place in the
table means nothing. The KV heads select apart, so the pool [num_pages, nkv,
B, d] is viewed as [num_pages * nkv, 1, B, d] and every (row, KV head) is a
row of its own with the table `page * nkv + head`. A prefill chunk attends
through `paged_prefill_attention`'s Pallas kernel with the block mask as its
selection: a grid step of 128 queries (at 16 query heads a KV head) walks
the slot's pages up to its last position, masks a score by its own query's
bit and skips a (query block, key block) nobody of the block picked;
accumulator, running maximum and sum stay in VMEM. Off the chip, and for a
shape the kernel refuses, the jnp loop: online softmax over key tiles up to
the chunk's last position. Both visit every page up to there and mask
(fetching the picked pages alone is later work).

Device scopes: `pt.sparse_select` (gathering the compressed keys, scoring,
top-k, the compacted table, a prefill window's bits by key block),
`pt.sparse_attention` (the attention itself; the two kernels keep their own
`pt.paged_attention` inside it), and the compressed keys' writes under
`pt.kv_write` with K's.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import paged_prefill_attention as ppa
from paddle_tpu.kernels import quantized_matmul as qm

__all__ = ["SparseConfig", "entry_of", "compressed_keys_of_window",
           "compressed_key_of_step", "write_compressed", "block_scores",
           "select_blocks", "prefill_selection", "selected_table",
           "prefill_takes_kernel", "sparse_prefill_attention",
           "sparse_decode_attention"]

_NEG = -1e30


class SparseConfig(NamedTuple):
    block_size: int
    kernel_size: int
    kernel_stride: int
    topk: int
    init_blocks: int
    local_blocks: int
    dense_len: int

    @property
    def per(self):
        return self.block_size // self.kernel_stride

    @property
    def overlap(self):
        return self.kernel_size // self.kernel_stride - 1

    @property
    def others(self):
        return self.topk - self.init_blocks - self.local_blocks

    @property
    def table_width(self):
        """Entries of a compacted decode table: a selection, or every page
        of a context that is still attended densely."""
        return max(self.topk, self.dense_len // self.block_size)

    def validate(self):
        B, K, T = self.block_size, self.kernel_size, self.kernel_stride
        if K % T or B % T or not 0 <= self.overlap < self.per:
            raise ValueError(
                f"kernel_size={K} and block_size={B} must be multiples of "
                f"kernel_stride={T}, and a kernel no longer than a block")
        if self.others < 0:
            raise ValueError("topk is smaller than init_blocks + "
                             "local_blocks")
        if self.dense_len % B or self.dense_len < self.topk * B:
            raise ValueError(
                f"dense_len={self.dense_len} must be a multiple of "
                f"block_size={B} and hold topk={self.topk} blocks: past it "
                "a query always has that many blocks to pick")


# ---------------------------------------------------------------------------
# the compressed keys: computed where K is written
# ---------------------------------------------------------------------------

def entry_of(end, cfg):
    """Entry of its page that the kernel ending at position `end` is kept
    in."""
    return (end % cfg.block_size + 1) // cfg.kernel_stride - 1


def compressed_keys_of_window(k, prev_k, h, last_idx, cfg):
    """The kernels that END inside a prefill window. k [s, nkv, d]: the
    window's keys (positions h .. h + s - 1); prev_k [kernel_size, nkv, d]:
    the keys of the positions just before h (rows for positions < 0 are
    never used); last_idx: the window's last real token. Returns (values
    [c, nkv, d] float32, ends [c] absolute end positions, ok [c])."""
    K, T = cfg.kernel_size, cfg.kernel_stride
    s = k.shape[0]
    buf = jnp.concatenate([prev_k, k]).astype(jnp.float32)
    csum = jnp.concatenate([jnp.zeros_like(buf[:1]), jnp.cumsum(buf, 0)])
    ends = h + jnp.mod(T - 1 - h, T) + T * jnp.arange(s // T + 1,
                                                       dtype=jnp.int32)
    ok = (ends <= h + last_idx) & (ends >= K - 1)
    bi = jnp.clip(ends - h + K, K - 1, K + s - 1)   # buf index of `ends`
    values = (csum[bi + 1] - csum[bi + 1 - K]) / K
    return values, ends, ok


def compressed_key_of_step(pool_k, bt, pos, cfg):
    """The kernel (at most one a row) that a decode step's token at `pos`
    [b] completes, from the row's last two pages of `pool_k` [num_pages,
    nkv, B, d] AFTER the token's key was written. Returns (values [b, nkv,
    d] float32, ok [b])."""
    B, K, T = cfg.block_size, cfg.kernel_size, cfg.kernel_stride
    cur = pos // B
    pages = jnp.stack([
        jnp.take_along_axis(bt, jnp.maximum(cur - 1, 0)[:, None], 1)[:, 0],
        jnp.take_along_axis(bt, cur[:, None], 1)[:, 0]], axis=1)
    two = jnp.swapaxes(pool_k[pages], 2, 3)          # [b, 2, B, nkv, d]
    two = two.reshape(two.shape[0], 2 * B, *two.shape[3:])
    at = pos % B + B                                 # the token's place
    idx = jnp.arange(2 * B, dtype=jnp.int32)[None, :]
    w = ((idx > (at - K)[:, None]) & (idx <= at[:, None])) / K
    values = jnp.einsum("bs,bskd->bkd", w.astype(jnp.float32),
                        two.astype(jnp.float32))
    ok = ((pos + 1) % T == 0) & (pos >= K - 1)
    return values, ok


@jax.named_scope("pt.kv_write")
def write_compressed(kc, values, ends, ok, h, new_pages, cfg):
    """A prefill window's kernels into kc [num_pages, nkv, per, d]: values
    [c, nkv, d] of the kernels ending at `ends` [c] (ascending, one a
    stride) go to their entries of the pages `new_pages` (the slot's pages
    from the one that holds position h on), whole pages at a time; an
    entry that is not `ok` keeps what its page holds."""
    B, T, per = cfg.block_size, cfg.kernel_stride, cfg.per
    n_pages = min((values.shape[0] - 1) // per + 2, new_pages.shape[0])
    pages = new_pages[:n_pages]
    flat = jnp.swapaxes(kc[pages], 1, 2)             # [n, per, nkv, d]
    flat = flat.reshape(n_pages * per, *flat.shape[2:])
    at = (ends + 1) // T - 1 - (h // B) * per        # entry, from pages[0]
    sink = n_pages * per                             # a row nobody keeps
    at = jnp.where(ok & (at < sink), at, sink)
    flat = jnp.concatenate([flat, jnp.zeros_like(flat[:1])])
    flat = flat.at[at].set(values.astype(kc.dtype))[:sink]
    flat = flat.reshape(n_pages, per, *flat.shape[1:])
    return kc.at[pages].set(jnp.swapaxes(flat, 1, 2))


# ---------------------------------------------------------------------------
# the selection
# ---------------------------------------------------------------------------

def block_scores(q, kflat, t, cfg):
    """q [b, n, nkv, g, d]: n queries a row at positions t [b, n]; kflat
    [b, F, nkv, d]: the row's compressed keys laid flat over its block
    table (F = pages * per). Returns the blocks' scores [b, nkv, n, pages]
    float32, -1 where no kernel inside the context overlaps the block."""
    T, per, r = cfg.kernel_stride, cfg.per, cfg.overlap
    b, n, nkv, g, d = q.shape
    F = kflat.shape[1]
    logits = jnp.einsum("bnkgd,bfkd->bkgnf", q, kflat,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    f = jnp.arange(F, dtype=jnp.int32)
    valid = (f >= r) & (f[None, None, :] < ((t + 1) // T)[..., None])
    valid = valid[:, None, None]                     # [b, 1, 1, n, F]
    p = jax.nn.softmax(jnp.where(valid, logits, _NEG), axis=-1)
    s = jnp.where(valid[:, :, 0], jnp.sum(jnp.where(valid, p, 0.0), 2), -1.0)
    s = s.reshape(b, nkv, n, F // per, per)
    own = jnp.max(s, axis=-1)
    if not r:
        return own
    nxt = jnp.max(s[..., :r], axis=-1)
    nxt = jnp.concatenate([nxt[..., 1:], jnp.full_like(nxt[..., :1], -1.0)],
                          axis=-1)
    return jnp.maximum(own, nxt)


def _largest(scores, cand, k):
    """bool mask of the k largest `scores` (all >= 0) among `cand` along
    the last axis, the lower index first among equals: what `lax.top_k`
    picks, without its sort (on the chip a top-k over 776 blocks for every
    query of a window was a sixth of the window's device time). Two
    neighbours tie whenever the kernel that straddles them is the best of
    both, so ties are the rule, not an accident. The k-th largest value is
    found bit by bit (a non-negative float32 orders as its bits do), then
    the ties at that value are counted off from the left."""
    bits = jnp.where(cand, jax.lax.bitcast_convert_type(
        jnp.maximum(scores, 0.0), jnp.int32), -1)

    def step(i, thr):
        trial = thr | (1 << (30 - i))
        enough = jnp.sum(bits >= trial, -1, keepdims=True) >= k
        return jnp.where(enough, trial, thr)

    thr = jax.lax.fori_loop(0, 31, step, jnp.zeros_like(bits[..., :1]))
    above = bits > thr
    tie = bits == thr
    room = k - jnp.sum(above, -1, keepdims=True)
    return above | (tie & (jnp.cumsum(tie, -1) <= room))


def select_blocks(scores, t, cfg):
    """scores [b, nkv, n, pages], t [b, n] -> bool [b, nkv, n, pages]: the
    blocks the query at t attends (every block of its context while the
    context is no longer than dense_len)."""
    pages = scores.shape[-1]
    blk = jnp.arange(pages, dtype=jnp.int32)
    cur = (t // cfg.block_size)[:, None, :, None]    # [b, 1, n, 1]
    inside = blk <= cur
    forced = inside & ((blk < cfg.init_blocks)
                       | (blk > cur - cfg.local_blocks))
    sel = forced
    if cfg.others:
        cand = inside & ~forced
        sel = forced | _largest(scores, cand, cfg.others)
    dense = (t + 1 <= cfg.dense_len)[:, None, :, None]
    return jnp.where(dense, inside, sel)


@jax.named_scope("pt.sparse_select")
def prefill_selection(q, kc, bt_row, qpos, cfg, q_tile=512):
    """The block mask of a prefill window: q [s, nkv, g, d] at positions
    qpos [s], over the slot's compressed keys `kc[bt_row]`. Queries go in
    tiles of `q_tile` so that the kernels' score matrix stays small.
    Returns bool [nkv, s, pages]."""
    s = q.shape[0]
    kflat = jnp.swapaxes(kc[bt_row], 1, 2)           # [P, per, nkv, d]
    kflat = kflat.reshape(1, -1, *kflat.shape[2:])
    qt = min(int(q_tile), s)
    if s % qt:
        raise ValueError(f"window of {s} queries is no multiple of {qt}")

    def tile(xs):
        qs, ts = xs
        sc = block_scores(qs[None], kflat, ts[None], cfg)
        return select_blocks(sc, ts[None], cfg)[0]   # [nkv, qt, P]

    sel = jax.lax.map(tile, (q.reshape(s // qt, qt, *q.shape[1:]),
                             qpos.reshape(s // qt, qt)))
    return jnp.swapaxes(sel, 0, 1).reshape(sel.shape[1], s, sel.shape[3])


@jax.named_scope("pt.sparse_select")
def selected_table(q, kc, bt, pos, cfg):
    """A decode step's selection as a block table for the paged kernel. q
    [b, nkv, g, d] at positions pos [b]; kc [num_pages, nkv, per, d]; bt [b,
    P]. Returns (table [b * nkv, W] of indices into the pool viewed as
    [num_pages * nkv, 1, B, d], ascending with the row's own page last;
    pos_eff [b * nkv], the position of the row's token inside that table;
    read [b], the pages a KV head of the row reads)."""
    b, nkv = q.shape[:2]
    P, W = bt.shape[1], cfg.table_width
    kflat = jnp.swapaxes(kc[bt], 2, 3)               # [b, P, per, nkv, d]
    kflat = kflat.reshape(b, -1, *kflat.shape[3:])
    t = pos[:, None]
    sel = select_blocks(block_scores(q[:, None], kflat, t, cfg), t, cfg)
    sel = sel[:, :, 0]                               # [b, nkv, P]
    # the W smallest selected block indices in ascending order: the largest
    # keys P - index first
    key = jnp.where(sel, P - jnp.arange(P, dtype=jnp.int32), 0)
    top = jax.lax.top_k(key, min(W, P))[0]           # [b, nkv, W]
    blocks = jnp.where(top > 0, P - top, 0)
    count = jnp.sum(top > 0, axis=-1)                # [b, nkv]
    phys = jnp.take_along_axis(bt[:, None, :], blocks, axis=2)
    table = phys * nkv + jnp.arange(nkv, dtype=jnp.int32)[None, :, None]
    pos_eff = (count - 1) * cfg.block_size + (pos % cfg.block_size)[:, None]
    return (table.reshape(b * nkv, -1), pos_eff.reshape(b * nkv),
            count[:, 0])


# ---------------------------------------------------------------------------
# the attention
# ---------------------------------------------------------------------------

def prefill_takes_kernel(q, pool_k, bt_row):
    """Whether a window of q [s, nkv, g, d] attends through the Pallas
    kernel (`paged_prefill_attention` under a selection) or the jnp loop:
    the mode and the shapes decide."""
    s, nkv, g, d = q.shape
    return ppa.takes_kernel(jax.ShapeDtypeStruct((s, nkv * g, d), q.dtype),
                            pool_k, bt_row, selected=True)


def sparse_prefill_attention(q, pool_k, pool_v, bt_row, sel, qpos, last_pos,
                             cfg):
    """q [s, nkv, g, d] at positions qpos [s] (consecutive) over the slot's
    pages `bt_row` [P] of pool_k / pool_v [num_pages, nkv, B, d], which
    already hold the window's own keys; sel [nkv, s, P] the per-query block
    mask; last_pos: the window's last position (traced: no page past it is
    read). Online softmax in float32. Returns [s, nkv, g, d].

    On the chip the window goes through `paged_prefill_attention`'s kernel
    with the selection as bits by key block (packed under
    `pt.sparse_select`); elsewhere, and for a shape the kernel refuses
    (`prefill_takes_kernel`), the jnp loop."""
    if prefill_takes_kernel(q, pool_k, bt_row):
        return _prefill_kernel(q, pool_k, pool_v, bt_row, sel, qpos[0],
                               last_pos, cfg.block_size, qm._mode())
    return _prefill_loop(q, pool_k, pool_v, bt_row, sel, qpos, last_pos, cfg)


# a jit of its own: the sparse layers of one window program are traced, and
# lowered to Mosaic, once between them (the layer loop is unrolled, and a
# kernel's trace is a third of a second of every start on the serving host)
@functools.partial(jax.jit, static_argnums=(7, 8))
def _prefill_kernel(q, pool_k, pool_v, bt_row, sel, h, last_pos, block_size,
                    mode):
    s, nkv, g, d = q.shape
    with jax.named_scope("pt.sparse_select"):
        selection = ppa.page_bits(sel, g, block_size)
    with jax.named_scope("pt.sparse_attention"), qm.fused_dispatch(*mode):
        return ppa.paged_prefill_attention(
            q.reshape(s, nkv * g, d), pool_k, pool_v, bt_row, h,
            last_pos - h, selection=selection).reshape(q.shape)


@jax.named_scope("pt.sparse_attention")
def _prefill_loop(q, pool_k, pool_v, bt_row, sel, qpos, last_pos, cfg):
    """The jnp form: a loop over key tiles whose trip count follows
    `last_pos`; every page up to there is visited and masked."""
    s, nkv, g, d = q.shape
    B, P = cfg.block_size, bt_row.shape[0]
    tp = ppa._tile_pages(P)
    tile = tp * B
    scale = 1.0 / math.sqrt(d)

    def body(i, carry):
        acc, m, l = carry
        pages = jax.lax.dynamic_slice_in_dim(bt_row, i * tp, tp)
        kt = jnp.swapaxes(pool_k[pages], 0, 1).reshape(nkv, tile, d)
        vt = jnp.swapaxes(pool_v[pages], 0, 1).reshape(nkv, tile, d)
        sc = jnp.einsum("qkgd,ksd->kgqs", q, kt,
                        preferred_element_type=jnp.float32) * scale
        kpos = i * tile + jnp.arange(tile, dtype=jnp.int32)
        ok = jnp.repeat(jax.lax.dynamic_slice_in_dim(sel, i * tp, tp, 2),
                        B, axis=2)
        ok = (ok & (kpos[None, None, :] <= qpos[None, :, None]))[:, None]
        m_new = jnp.maximum(m, jnp.max(jnp.where(ok, sc, _NEG), -1,
                                       keepdims=True))
        p = jnp.where(ok, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, -1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "kgqs,ksd->kgqd", p.astype(vt.dtype), vt,
            preferred_element_type=jnp.float32)
        return acc, m_new, l

    acc, _, l = jax.lax.fori_loop(
        0, last_pos // tile + 1, body,
        (jnp.zeros((nkv, g, s, d), jnp.float32),
         jnp.full((nkv, g, s, 1), _NEG, jnp.float32),
         jnp.zeros((nkv, g, s, 1), jnp.float32)))
    out = acc / jnp.maximum(l, 1e-30)
    return jnp.transpose(out, (2, 0, 1, 3)).astype(q.dtype)


@jax.named_scope("pt.sparse_attention")
def sparse_decode_attention(q, pool_k, pool_v, table, pos_eff):
    """q [b, nkv, g, d] over the compacted `table` [b * nkv, W] (see
    `selected_table`): the paged decode kernel, a (row, KV head) a row of g
    query heads over a pool of one-head pages. Returns [b, nkv, g, d]."""
    b, nkv, g, d = q.shape
    view = (pool_k.shape[0] * nkv, 1) + pool_k.shape[2:]
    out = qm.paged_decode_attention(
        q.reshape(b * nkv, 1, g, d), pool_k.reshape(view),
        pool_v.reshape(view), table, pos_eff)
    return out.reshape(b, nkv, g, d)
