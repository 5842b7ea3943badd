"""A decoder with LATENT attention (MLA) and routed + shared experts, served
over `PagedEngine`'s paged cache. `LatentMoEArgs` is the static description
that selects this path: `PagedEngine(params, LatentMoEArgs(...))`.

Every layer is `x += Attn(norm(x)); x += FFN(norm(x))`.

  attention  low-rank queries (`c_q = norm(h W_qa)`, `q = c_q W_qb`, a head
             is `[q_nope; q_pe]`) against ONE cached row a token and layer,
             `[c_kv; k_pe]` = `kv_rank + rope_dim` values: the normed latent
             and the rotary key all heads share (YaRN frequencies on the
             rotary slice where `yarn` is given, plain ones where it is
             None). A head's key is `[c_kv W_uk_h; k_pe]`, its
             value `c_kv W_uv_h` (`W_kvb` split by head).
             DECODE takes the ABSORBED form: `q_nope` is carried through
             `W_uk` into the latent space, every head attends the cached
             rows themselves (the value is a row's leading `kv_rank`
             columns), and the result goes through `W_uv`
             (`kernels/latent_attention.latent_decode_attention`).
             PREFILL takes the DECOMPRESSED form: the keys and values of
             the slot's context are rebuilt from its cached rows a block at
             a time and the window attends them in blocks over the keys
             (`latent_prefill_attention`). Rebuilding costs 2 * kv_rank *
             (nope + v) flops a key and head once a window; the absorbed
             form would pay (2 * kv_rank + rope) against (nope + rope + v)
             multiply-adds a (query, key) pair and head, 1,088 against 320
             at the published widths.
  selector   where `indexer` is given (DeepSeek Sparse Attention's learned
             indexer, as GLM-5 publishes it), a query attends the
             `index_topk` keys of largest INDEX SCORE alone, every head the
             same ones: I(t, s) = sum_j w_j(t) ReLU(qI_j(t) . kI(s)) over
             the index heads j, `qI = c_q W_iq`, `kI = LayerNorm(h W_ik)`
             (ONE vector a token), both rotated on their leading `rope_dim`
             columns, `w = h W_iw` times heads^-1/2 dim^-1/2; ties to the
             lower position; a context of at most `index_topk` is attended
             whole. `kI` is cached beside the latent row in a SECOND pool
             `[layers * num_pages, page, index width]` under the same page
             numbers (256 B a token where the latent row is 1,280: the
             selector reads every visible key, and must not read the rows
             to do it), so the cache is the pair (latent pool, index pool).
             ONE selection rule for both steps: a query's scores as sortable
             integer keys, its `index_topk`-th largest found bit by bit
             with no sort and no index list (`kernels/latent_attention.
             kth_largest`: exact, ties counted off from the left), the
             attention under that MASK. DECODE: the scores of a row's live
             pages (`index_decode_scores`), then the absorbed decode kernel
             every step uses with the keys not selected masked: every live
             row of the latent pool is still read (on the chip a sort of
             71,680 scores a row and a gather of the 2,048 selected rows
             cost more than reading all of them at the cell's contexts:
             `kernels/latent_attention.py`'s head). A row whose context is
             no longer than `index_topk` goes the same way and selects all
             of it: no branch a batch could not share. PREFILL: the
             window's keys `[window, context]` (0.6 GB at 2,048 x 71,680),
             the same search, and the decompressed form over
             the context a chunk of keys at a time under that mask
             (`latent_masked_prefill_attention`: the whole context's keys
             and values would be 4 GB at 64 heads).
  FFN        `first_k_dense` leading layers: a SwiGLU. The others: shared
             experts (one SwiGLU of their summed width) plus routed
             experts. The router scores ALL `n_routed_experts` in float32
             (`scoring`: softmax, or sigmoid with the router's correction
             bias added for the CHOICE alone), keeps the `topk_group` best
             of `n_group` groups by each group's best (no group step where
             `n_group` is 1), picks `experts_per_tok` among what stays
             (ties to the lower index) and weighs a pick by
             `routed_scaling_factor` times its score, over the sum of the
             picked scores where `norm_topk` (`route`: one function, the
             rule read from the description). This program HOLDS
             the experts `[first_expert, first_expert + experts_held)`: a
             pick that lands elsewhere adds nothing here (its chip's part
             of an expert-parallel layer), and no code stands in for the
             absent chips. Many rows: picks sorted by expert, one grouped
             matmul a projection; few (a decode step): one pass over the hit
             experts' weights (`_routed_experts`). No capacity, no drops.

The parameter tree is `llama_functional`'s (`embedding`, `layers/*` stacked
on a leading layer axis, `final_norm`, `lm_head`) with the expert layer's
leaves `ln1 ln2 w_qa q_norm w_qb w_kva kv_norm w_kvb wo router ws_gate
ws_up ws_down we_gate we_up we_down` (`router_bias` where the rule has one;
`w_iq w_ik ik_norm ik_bias w_iw` in every layer behind a selector); where
`first_k_dense` > 0 a second
stacked group `dense_layers/*` holds the leading layers (`w_gate w_up
w_down` in the experts' place). The page pool `[layers * num_pages, page,
row_width]` (a row: kv_rank + rope_dim values, padded to whole lane tiles)
is the layer scans' carry: layer l's pages are the run
that starts at `l * num_pages`, written and read where they lie. A request
is its pages and nothing else: the state tree is empty.

What `serving/family.FamilyPath` asks of a family's functional module
(`models/family_protocol.py`) is the last section.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.kernels import grouped_matmul as gm
from paddle_tpu.kernels import latent_attention as la
from paddle_tpu.models import llama_functional as lf
from paddle_tpu.models.family_protocol import StepRiders
from paddle_tpu.models.generation import _wmm, _write_rows
from paddle_tpu.models.hybrid_functional import _write_window_pages

__all__ = ["YarnConfig", "IndexerConfig", "LatentMoEArgs", "rope_tables",
           "softmax_scale", "route", "prefill_window", "decode_step"]

DECOMPRESS_BLOCK = 1024     # keys rebuilt at once in a prefill window
INDEX_BLOCK = 1024          # keys a window's index scores are made for at once
SELECT_ROWS = 8             # consecutive queries of a window whose selection
                            # `record_selection` keeps


class YarnConfig(NamedTuple):
    factor: float
    original_max_position: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float


class IndexerConfig(NamedTuple):
    """The learned token selector in front of the attention (see the
    module's head)."""

    heads: int                  # index heads
    dim: int                    # an index head's (and the index key's) width
    topk: int                   # keys a query attends
    norm_eps: float = 1e-6      # the index key's LayerNorm


class LatentMoEArgs(NamedTuple):
    """Static (hashable) description of the stack."""

    vocab_size: int
    hidden_size: int
    num_layers: int             # dense leading layers + expert layers
    num_heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int               # a head's query / key width without rotary
    rope_dim: int               # the rotary slice all heads share
    v_dim: int
    dense_intermediate: int     # the leading dense layers' FFN width
    expert_intermediate: int    # one routed expert's width
    shared_experts: int         # shared experts, each of a routed one's width
    routed_experts: int         # the router's width: every published expert
    first_expert: int           # the experts held here are
    experts_held: int           # [first_expert, first_expert + experts_held)
    n_group: int
    topk_group: int
    experts_per_tok: int
    routed_scaling: float
    first_k_dense: int
    rope_theta: float
    rms_eps: float
    yarn: YarnConfig | None     # None: plain rotary positions
    # the serving path keeps the experts every token picked, for whoever
    # judges the served tokens (`serving/routing.RoutingTrace`)
    record_routing: bool = False
    indexer: IndexerConfig | None = None
    # "softmax": group-limited greedy over softmax scores; "sigmoid": sigmoid
    # scores, picked by score + the router's correction bias (`route`)
    scoring: str = "softmax"
    norm_topk: bool = False     # a token's picked weights sum to one
    # both step programs also return the positions a few queries selected
    # (`serving/routing.RoutingTrace.selections`)
    record_selection: bool = False
    # every SwiGLU's gate capped above and its up-projection clipped both
    # ways at this value before they meet (`_gate`, `_up`); None: neither
    swiglu_limit: float | None = None

    @property
    def row_width(self):
        """A cached row's width: the latent and the rotary key, padded with
        zeros to whole lane tiles (the TPU lays a 576-wide minor axis out
        as 640 lanes whatever the array says: the pool says it too, so that
        a page is a whole tile and the kernel may copy it)."""
        return -(-(self.kv_rank + self.rope_dim) // 128) * 128

    def validate(self):
        if self.routed_experts % self.n_group:
            raise ValueError("routed_experts must be a multiple of n_group")
        if not 0 < self.topk_group <= self.n_group:
            raise ValueError("topk_group must lie in [1, n_group]")
        if self.experts_per_tok > (self.topk_group * self.routed_experts
                                   // self.n_group):
            raise ValueError("experts_per_tok exceeds the experts of the "
                             "groups that stay")
        if not (0 <= self.first_expert and 0 < self.experts_held
                and self.first_expert + self.experts_held
                <= self.routed_experts):
            raise ValueError("the experts held must lie inside "
                             "[0, routed_experts)")
        if not 0 <= self.first_k_dense < self.num_layers:
            raise ValueError("first_k_dense must leave an expert layer")
        if self.rope_dim % 2:
            raise ValueError("rope_dim must be even")
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring {self.scoring!r}: softmax or sigmoid")
        if self.indexer is not None and not (
                0 < self.rope_dim <= self.indexer.dim
                and self.indexer.topk > 0):
            raise ValueError("the selector's heads hold the rotary slice "
                             "and keep at least one key")
        if self.record_selection and self.indexer is None:
            raise ValueError("record_selection without a selector")


# ---------------------------------------------------------------------------
# rotary positions (YaRN) and the attention scale
# ---------------------------------------------------------------------------

def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(args):
    """The rotary slice's frequencies [rope_dim / 2]: theta^(-2j/d) blended
    with the same over `factor` by a linear ramp between the dimensions
    that make `beta_fast` and `beta_slow` rotations over the original
    context (the published `DeepseekV2YarnRotaryEmbedding`)."""
    y, d, base = args.yarn, args.rope_dim, args.rope_theta
    j = np.arange(0, d, 2, dtype=np.float64) / d
    if y is None:
        return 1.0 / base ** j
    extra, inter = 1.0 / base ** j, 1.0 / (y.factor * base ** j)

    def correction(rotations):
        return (d * math.log(y.original_max_position
                             / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction(y.beta_fast)), 0)
    high = min(math.ceil(correction(y.beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def rope_tables(seq_len, args):
    """cos, sin [seq_len, rope_dim] float32 (both halves alike, the
    rotate-half convention of `lf.apply_rope_bcast`), times YaRN's
    cos / sin multiplier mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)."""
    freqs = np.outer(np.arange(seq_len, dtype=np.float64),
                     yarn_inv_freq(args))
    emb = np.concatenate([freqs, freqs], axis=-1)
    m = 1.0 if args.yarn is None else (
        _yarn_mscale(args.yarn.factor, args.yarn.mscale)
        / _yarn_mscale(args.yarn.factor, args.yarn.mscale_all_dim))
    return (jnp.asarray(np.cos(emb) * m, jnp.float32),
            jnp.asarray(np.sin(emb) * m, jnp.float32))


def softmax_scale(args):
    """(nope + rope)^-1/2 times YaRN's mscale(factor, mscale_all_dim)^2."""
    m = _yarn_mscale(args.yarn.factor, args.yarn.mscale_all_dim) \
        if args.yarn is not None and args.yarn.mscale_all_dim else 1.0
    return (args.nope_dim + args.rope_dim) ** -0.5 * m * m


# ---------------------------------------------------------------------------
# attention: the projections around the two cores
# ---------------------------------------------------------------------------

def _query_latent(lp, hin, args):
    return lf.rms_norm(_wmm(hin, lp["w_qa"]), lp["q_norm"], args.rms_eps)


def _queries_and_row(lp, hin, cos, sin, args, c_q=None):
    """hin [n, h] at rotary rows cos, sin [n, rope_dim] -> q_nope [n, H,
    nope], q_pe [n, H, rope] (rotated) and the row to cache [n, row_width]:
    the normed latent, the rotated shared key, zeros. `c_q`: the normed
    query latent where the caller made it already."""
    n, H = hin.shape[0], args.num_heads
    if c_q is None:
        c_q = _query_latent(lp, hin, args)
    q = _wmm(c_q, lp["w_qb"]).reshape(n, H, args.nope_dim + args.rope_dim)
    q_nope, q_pe = q[..., :args.nope_dim], q[..., args.nope_dim:]
    kv = _wmm(hin, lp["w_kva"])
    c_kv = lf.rms_norm(kv[:, :args.kv_rank], lp["kv_norm"], args.rms_eps)
    q_pe, k_pe = lf.apply_rope_bcast(
        q_pe, kv[:, None, args.kv_rank:], cos[:, None, :], sin[:, None, :])
    pad = jnp.zeros((n, args.row_width - args.kv_rank - args.rope_dim),
                    c_kv.dtype)
    return q_nope, q_pe, jnp.concatenate([c_kv, k_pe[:, 0], pad], axis=-1)


@jax.named_scope("pt.index_scores")
def _index_operands(lp, hin, c_q, cos, sin, args):
    """The selector's side of a token: its index queries qI [n, J, d] (from
    the query latent), the index key to cache kI [n, d] (LayerNorm with
    bias of `hin W_ik`), both rotated on their leading `rope_dim` columns,
    and its head weights w [n, J], float32, times J^-1/2 d^-1/2."""
    ix, r, n = args.indexer, args.rope_dim, hin.shape[0]
    qi = _wmm(c_q, lp["w_iq"]).reshape(n, ix.heads, ix.dim)
    ki = _wmm(hin, lp["w_ik"]).astype(jnp.float32)
    ki = ki - jnp.mean(ki, axis=-1, keepdims=True)
    ki = ki * jax.lax.rsqrt(jnp.mean(ki * ki, axis=-1, keepdims=True)
                            + ix.norm_eps)
    ki = (ki * lp["ik_norm"].astype(jnp.float32)
          + lp["ik_bias"].astype(jnp.float32)).astype(hin.dtype)
    q_r, k_r = lf.apply_rope_bcast(qi[..., :r], ki[:, None, :r],
                                   cos[:, None, :], sin[:, None, :])
    w = _wmm(hin, lp["w_iw"]).astype(jnp.float32) * (
        ix.heads ** -0.5 * ix.dim ** -0.5)
    return (jnp.concatenate([q_r, qi[..., r:]], axis=-1),
            jnp.concatenate([k_r[:, 0], ki[:, r:]], axis=-1), w)


def _w_kvb_by_head(lp, args):
    """W_kvb [kv_rank, H, nope + v]: a head's key and value maps."""
    return lp["w_kvb"].reshape(args.kv_rank, args.num_heads,
                               args.nope_dim + args.v_dim)


def _topk(args, positions):
    """The keys a query attends: `index_topk`, or every position of a table
    that holds fewer."""
    return min(args.indexer.topk, positions)


def _decode_heads(lp, hin, cache, bt, pos, cos, sin, base, record, args):
    """hin [b, h], the block's normed input, one token a row at positions pos
    [b] -> (the heads' outputs [b, H * v] before `wo`, cache, the selection
    of row `record` or None). The absorbed form over the rows' pages; behind
    a selector under each row's selection: its index scores over its live
    pages of the index pool, its `index_topk`-th largest (`la.kth_largest`,
    ties to the lower position; a row whose context is no longer selects all
    of it, through the same code), and the same kernel with the keys not
    selected masked."""
    pool, ipool = cache if args.indexer else (cache, None)
    ps = pool.shape[1]
    with jax.named_scope("pt.attention"):
        c_q = _query_latent(lp, hin, args) if args.indexer else None
        q_nope, q_pe, row = _queries_and_row(lp, hin, cos[pos], sin[pos],
                                             args, c_q)
        w = _w_kvb_by_head(lp, args)
        q_lat = jnp.einsum("bhn,chn->bhc", q_nope, w[..., :args.nope_dim])
        pad = jnp.zeros(q_pe.shape[:2] + (row.shape[1] - args.kv_rank
                                          - args.rope_dim,), q_pe.dtype)
        q = jnp.concatenate([q_lat, q_pe, pad], axis=-1)   # [b, H, row]
    # write before attending; a row that does not decode has a table of
    # null pages, the layer's garbage sink
    page = base + jnp.take_along_axis(bt, (pos // ps)[:, None], axis=1)[:, 0]
    pool = _write_rows(pool[:, None], row[:, None, :], page, pos % ps)[:, 0]
    bias = selected = None
    if args.indexer:
        qi, ki, wi = _index_operands(lp, hin, c_q, cos[pos], sin[pos], args)
        ipool = _write_rows(ipool[:, None], ki[:, None, :], page,
                            pos % ps)[:, 0]
        with jax.named_scope("pt.index_scores"):
            scores = la.index_decode_scores(qi, wi, ipool, bt, pos,
                                            page_base=base)   # [b, T]
        with jax.named_scope("pt.index_select"):
            keys = jnp.where(scores > -jnp.inf, la.sortable(scores),
                             la._KEY_MIN)
            mine = la.selected_of(keys, *la.kth_largest(
                keys, _topk(args, keys.shape[1]), pos + 1))
            bias = jnp.where(mine, 0.0, la._NEG_INF)
            if args.record_selection:
                selected = la.packed(mine[record])
    with jax.named_scope("pt.latent_attention"):
        o_lat = la.latent_decode_attention(
            q, pool, bt, pos, softmax_scale(args), args.kv_rank,
            page_base=base, bias=bias)                     # [b, H, kv_rank]
    with jax.named_scope("pt.attention"):
        o = jnp.einsum("bhc,chv->bhv", o_lat, w[..., args.nope_dim:])
        return (o.reshape(hin.shape[0], -1),
                (pool, ipool) if args.indexer else pool, selected)


def _decode_attention(lp, x, cache, bt, pos, cos, sin, base, record, args):
    """x [b, h] -> (x + attention of its norm, cache, the selection of row
    `record` or None): `_decode_heads` inside this family's block."""
    hin = lf.rms_norm(x, lp["ln1"], args.rms_eps)
    o, cache, selected = _decode_heads(lp, hin, cache, bt, pos, cos, sin,
                                       base, record, args)
    with jax.named_scope("pt.attention"):
        return x + _wmm(o, lp["wo"]), cache, selected


@jax.named_scope("pt.attention")
def _decompress(lp, pool, bt_row, base, n_keys, args):
    """The keys and values of the slot's first `n_keys` positions, rebuilt
    from its cached rows a block of DECOMPRESS_BLOCK keys at a time (the
    loop's trip count follows the traced context, not the table's width):
    kv [T, H * (nope + v)], a head's key then its value, and the shared
    rotary keys k_pe [T, rope_dim], T the table's positions; rows past the
    last block rebuilt are zero."""
    ps, P = pool.shape[1], bt_row.shape[0]
    ppb = max(1, min(DECOMPRESS_BLOCK // ps, P))
    n_blocks = -(-P // ppb)
    table = jnp.zeros(n_blocks * ppb, jnp.int32).at[:P].set(bt_row)
    width = args.num_heads * (args.nope_dim + args.v_dim)
    kv = jnp.zeros((n_blocks * ppb * ps, width), pool.dtype)
    k_pe = jnp.zeros((n_blocks * ppb * ps, args.rope_dim), pool.dtype)

    def body(i, carry):
        kv, k_pe = carry
        pages = base + jax.lax.dynamic_slice_in_dim(table, i * ppb, ppb)
        rows = pool[pages].reshape(ppb * ps, -1)
        kv = jax.lax.dynamic_update_slice_in_dim(
            kv, _wmm(rows[:, :args.kv_rank], lp["w_kvb"]), i * ppb * ps, 0)
        k_pe = jax.lax.dynamic_update_slice_in_dim(
            k_pe, rows[:, args.kv_rank:args.kv_rank + args.rope_dim],
            i * ppb * ps, 0)
        return kv, k_pe

    live = jnp.minimum(-(-n_keys // (ppb * ps)), n_blocks)
    return jax.lax.fori_loop(0, live, body, (kv, k_pe))


def _selected_window(lp, q_nope, q_pe, qi, wi, pool, ipool, h, last_idx,
                     bt_row, base, record, args):
    """A window's attention behind the selector: the index scores of every
    (query, visible key) as sortable keys [s, T], each query's
    `index_topk`-th largest found bit by bit (`la.kth_largest`: no sort, no
    index list; ties to the lower position: the decode step's rule), and
    the decompressed form over the context a chunk of keys at a time under
    that mask. Returns (o [s, H, v], the selections of the SELECT_ROWS
    queries from `record` on as packed bits [SELECT_ROWS, T / 8], or
    None)."""
    ps, P = pool.shape[1], bt_row.shape[0]
    T = P * ps
    chunk = min(la.MASKED_CHUNK, T)
    block = min(INDEX_BLOCK, chunk)
    if chunk % block or block % ps:
        raise ValueError(f"a table of {T} positions in pages of {ps} does "
                         f"not cut into blocks of {block} in chunks of "
                         f"{chunk}")
    n_chunks = -(-T // chunk)
    table = jnp.zeros(n_chunks * chunk // ps, jnp.int32).at[:P].set(bt_row)
    s = qi.shape[0]
    with jax.named_scope("pt.index_scores"):
        keys = la.index_window_keys(qi, wi, ipool, table, base, h, last_idx,
                                    block)
    with jax.named_scope("pt.index_select"):
        thr, room = la.kth_largest(
            keys, _topk(args, T), jnp.full((s,), h + last_idx + 1, jnp.int32))
        selected = None
        if args.record_selection:
            part = [jax.lax.dynamic_slice_in_dim(a, record, min(
                SELECT_ROWS, s)) for a in (keys, thr, room)]
            selected = la.packed(la.selected_of(*part))
    w = _w_kvb_by_head(lp, args)

    def chunk_kv(c):
        with jax.named_scope("pt.attention"):
            pages = base + jax.lax.dynamic_slice_in_dim(
                table, c * (chunk // ps), chunk // ps)
            rows = pool[pages].reshape(chunk, -1)
            latent = rows[:, :args.kv_rank]
            # heads-major as they are made: the kernel's blocks are a head's
            return (jnp.einsum("tc,chn->htn", latent,
                               w[..., :args.nope_dim]),
                    jnp.einsum("tc,chv->htv", latent,
                               w[..., args.nope_dim:]),
                    rows[:, args.kv_rank:args.kv_rank + args.rope_dim])

    with jax.named_scope("pt.latent_attention"):
        o = la.latent_masked_prefill_attention(
            q_nope, q_pe, chunk_kv, keys, thr, room, h, last_idx,
            softmax_scale(args), chunk)
    return o, selected


def _window_rows(a, ps):
    """A window's rows to cache as WHOLE pages of rows, zeros past its own:
    the writer puts `s // ps + 1` pages, which hold a window from any h only
    if s is whole pages, and the engine's `min_bucket` may lie below
    `page_size` (positions no query reads before its own token's write)."""
    pad = -a.shape[0] % ps
    return jnp.pad(a, ((0, pad), (0, 0))) if pad else a


def _window_heads(lp, hin, cache, h, last_idx, bt_row, new_pages, cos, sin,
                  base, record, args):
    """hin [s, h], the block's normed input over a window of one slot at
    positions h .. h + s - 1, real up to `last_idx` -> (the heads' outputs
    [s, H * v] before `wo`, cache, the positions the queries `record`
    selected or None). The decompressed form."""
    pool, ipool = cache if args.indexer else (cache, None)
    s, ps = hin.shape[0], pool.shape[1]
    pos = h + jnp.arange(s, dtype=jnp.int32)
    with jax.named_scope("pt.attention"):
        c_q = _query_latent(lp, hin, args) if args.indexer else None
        q_nope, q_pe, row = _queries_and_row(lp, hin, cos[pos], sin[pos],
                                             args, c_q)
    pool = _write_window_pages(pool[:, None], _window_rows(row, ps)[:, None],
                               h, base + bt_row, base + new_pages, ps)[:, 0]
    selected = None
    if args.indexer:
        qi, ki, wi = _index_operands(lp, hin, c_q, cos[pos], sin[pos], args)
        ipool = _write_window_pages(
            ipool[:, None], _window_rows(ki, ps)[:, None], h, base + bt_row,
            base + new_pages, ps)[:, 0]
        o, selected = _selected_window(lp, q_nope, q_pe, qi, wi, pool, ipool,
                                       h, last_idx, bt_row, base, record,
                                       args)
    else:
        kv, k_pe = _decompress(lp, pool, bt_row, base, h + last_idx + 1,
                               args)
        with jax.named_scope("pt.latent_attention"):
            o = la.latent_prefill_attention(
                q_nope, q_pe, kv, k_pe, h, last_idx, softmax_scale(args),
                args.v_dim)                                # [s, H, v]
    return (o.reshape(s, -1), (pool, ipool) if args.indexer else pool,
            selected)


def _window_attention(lp, x, cache, h, last_idx, bt_row, new_pages, cos, sin,
                      base, record, args):
    """x [s, h] -> (x + attention of its norm, cache, the positions the
    queries `record` selected or None): `_window_heads` inside this
    family's block."""
    hin = lf.rms_norm(x, lp["ln1"], args.rms_eps)
    o, cache, selected = _window_heads(lp, hin, cache, h, last_idx, bt_row,
                                       new_pages, cos, sin, base, record,
                                       args)
    with jax.named_scope("pt.attention"):
        return x + _wmm(o, lp["wo"]), cache, selected


# ---------------------------------------------------------------------------
# the feed-forward half: a SwiGLU, or shared + routed experts
# ---------------------------------------------------------------------------

def _gate(gate, limit=None):
    """A SwiGLU's gate half: silu(gate), the gate capped above at `limit`
    first where one is given."""
    return jax.nn.silu(gate if limit is None else jnp.minimum(gate, limit))


def _up(up, limit=None):
    """A SwiGLU's linear half, clipped both ways at `limit` where one is
    given."""
    return up if limit is None else jnp.clip(up, -limit, limit)


def _swiglu(x, w_gate, w_up, w_down, limit=None):
    return _wmm(_gate(_wmm(x, w_gate), limit) * _up(_wmm(x, w_up), limit),
                w_down)


def route(logits, args, bias=None):
    """Router logits [n, routed_experts] (float32) -> (experts [n, k] int32,
    weights [n, k] float32). Both published rules are a top-k over masked
    scores: an expert scores softmax(logits) (`scoring` "softmax") or
    sigmoid(logits) ("sigmoid"), and is CHOSEN by that score plus the
    router's correction `bias` [routed_experts] where one is given; a group
    scores the best of its experts' choice values; the `topk_group` best
    groups stay (all of them where `n_group` is 1); the `experts_per_tok`
    best among them are picked, each weighing `routed_scaling` times its
    SCORE (never the bias), over the sum of the picked scores where
    `norm_topk`. Ties go to the lower index (`jax.lax.top_k`)."""
    n = logits.shape[0]
    logits = logits.astype(jnp.float32)
    scores = (jax.nn.sigmoid(logits) if args.scoring == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    choice = scores if bias is None else scores + bias.astype(jnp.float32)
    if args.n_group > 1:
        per = args.routed_experts // args.n_group
        group_best = jnp.max(choice.reshape(n, args.n_group, per), axis=-1)
        kept = jax.lax.top_k(group_best, args.topk_group)[1]      # [n, g]
        stays = jnp.any(jax.nn.one_hot(kept, args.n_group, dtype=bool),
                        axis=1)
        # a sigmoid score plus a bias may be negative: what leaves sorts last
        choice = jnp.where(jnp.repeat(stays, per, axis=1), choice,
                           0.0 if bias is None else -jnp.inf)
    w, experts = jax.lax.top_k(choice, args.experts_per_tok)
    if bias is not None:
        w = jnp.take_along_axis(scores, experts, axis=-1)
    if args.norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), w * args.routed_scaling


def experts_fused(rows, args, dtype):
    """Whether a step program of `rows` rows runs its routed experts as the
    fused pass over the hit experts' weights (`gm.fused_expert_ffn`) or as
    grouped matmuls over a row a pick: a fact of the static shapes and of
    where the program runs (`gm.fused_tiles`), nothing else."""
    return gm.fused_tiles(rows, args.hidden_size, args.expert_intermediate,
                          jnp.dtype(dtype).itemsize) is not None


def _routed_experts(lp, stack, first, hin, live, args):
    """hin [n, h] -> (the held experts' part of the routed sum [n, h],
    counts int32 [4]: tokens at the busiest held expert, picks that landed
    on a held expert, picks in all, held experts with a token; rows where
    `live` is False count for nothing; the experts every row picked [n, k],
    of all the published ones). `stack`: the `we_*` leaves of the
    WHOLE stack viewed [layers * held, ..], of which this layer's start at
    group `first` (see `kernels/grouped_matmul`).

    One sum, two forms by the STATIC row count (`experts_fused`). Up to
    `gm.FUSED_ROWS` (128) rows, a decode step: every row against each held
    expert some live row picked, one pass over those experts' weights, the
    routing weights a dense [n, E] matrix that is 0 where a row did not pick
    (`_routed_fused`): `n` times an expert's operations stay hidden behind
    its bytes below the chip's ridge (~240 rows a v5e), and nothing is
    sorted or gathered. More rows, a prefill window: a row a pick sorted by
    expert through three grouped matmuls (`_routed_grouped`), whose
    operations follow the picks."""
    n, k, E = hin.shape[0], args.experts_per_tok, args.experts_held
    with jax.named_scope("pt.moe_route"):
        logits = jnp.matmul(hin.astype(jnp.float32),
                            lp["router"].astype(jnp.float32))
        experts, weights = route(logits, args, lp.get("router_bias"))
        local = experts - args.first_expert
        held = (local >= 0) & (local < E) & live[:, None]
        # what is not held keys past the held experts: no group, no column
        key = jnp.where(held, local, E)
    routed = (_routed_fused
              if experts_fused(n, args, stack["we_gate"].dtype)
              else _routed_grouped)
    out, sizes = routed(stack, first, hin, key, held, weights, args)
    with jax.named_scope("pt.moe_route"):
        counts = jnp.stack([jnp.max(sizes), jnp.sum(sizes),
                            k * jnp.sum(live.astype(jnp.int32)),
                            jnp.sum((sizes > 0).astype(jnp.int32))])
    return out.astype(hin.dtype), counts, experts


def _routed_fused(stack, first, hin, key, held, weights, args):
    """key [n, k]: a pick's held expert, E where it is not `held` (or its
    row not live) -> (the routed sum [n, h] float32, picks a held expert
    [E])."""
    E, limit = args.experts_held, args.swiglu_limit
    with jax.named_scope("pt.moe_route"):
        picked = jax.nn.one_hot(key, E, dtype=jnp.float32)     # [n, k, E]
        c = jnp.sum(picked * weights[:, :, None], axis=1)
        sizes = jnp.sum(picked, axis=(0, 1)).astype(jnp.int32)
    with jax.named_scope("pt.expert_ffn"):
        out = gm.fused_expert_ffn(
            hin, c, sizes > 0, stack["we_gate"], stack["we_up"],
            stack["we_down"], first,
            lambda gate, up: _gate(gate, limit) * _up(up, limit))
    return out, sizes


def _routed_grouped(stack, first, hin, key, held, weights, args):
    """The same of a row a pick, sorted by expert; what is not held sorts
    past the held experts and into no group: it leaves the dispatch."""
    n, k = key.shape
    E, limit = args.experts_held, args.swiglu_limit
    with jax.named_scope("pt.moe_route"):
        key = key.reshape(-1)
        order = jnp.argsort(key, stable=True)
        token = order // k
        sizes = jnp.sum(jax.nn.one_hot(key, E + 1, dtype=jnp.int32),
                        axis=0)[:E]
        xs = hin[token]                                    # [n * k, h]
    with jax.named_scope("pt.expert_ffn"):
        act = (_gate(gm.grouped_matmul(xs, stack["we_gate"], sizes, first),
                     limit)
               * _up(gm.grouped_matmul(xs, stack["we_up"], sizes, first),
                     limit))
        ys = gm.grouped_matmul(act, stack["we_down"], sizes, first)
    with jax.named_scope("pt.moe_route"):
        w = jnp.where(held, weights, 0.0).reshape(-1)[order]
        # a row past the last group holds whatever the grouped matmul left
        ys = jnp.where((w > 0)[:, None], ys.astype(jnp.float32) * w[:, None],
                       0.0)
        # back in token order, a token's k picks side by side: a gather and
        # a sum (a scatter-add of the sorted rows was 7% of the device's
        # busy time at a 2,048-token window)
        out = jnp.sum(ys[jnp.argsort(order)].reshape(n, k, -1), axis=1)
    return out, sizes


def _expert_ffn(lp, stack, first, x, live, args):
    hin = lf.rms_norm(x, lp["ln2"], args.rms_eps)
    with jax.named_scope("pt.mlp"):
        shared = _swiglu(hin, lp["ws_gate"], lp["ws_up"], lp["ws_down"],
                         args.swiglu_limit)
    routed, counts, picks = _routed_experts(lp, stack, first, hin, live,
                                            args)
    return x + shared + routed, counts, picks


def _dense_ffn(lp, x, args):
    hin = lf.rms_norm(x, lp["ln2"], args.rms_eps)
    with jax.named_scope("pt.mlp"):
        return x + _swiglu(hin, lp["w_gate"], lp["w_up"], lp["w_down"],
                           args.swiglu_limit)


# ---------------------------------------------------------------------------
# the two step programs' bodies
# ---------------------------------------------------------------------------

def _stack(params, x, cache, attention, live, args):
    """The dense leading layers, then the expert layers, each group one scan
    whose carry holds the activations and the whole cache (the latent pool,
    or it and the index pool). `attention(lp, x, cache, base)` is the
    step's own and returns (x, cache, what it recorded or None). Returns
    (x, cache, counts [4], picks [expert layers, rows, experts a token],
    the records stacked over ALL layers or None)."""
    pool = jax.tree_util.tree_leaves(cache)[0]
    num_pages = pool.shape[0] // args.num_layers
    kd, E = args.first_k_dense, args.experts_held
    # the experts' leaves stay out of the scan's slices (`_routed_experts`)
    scanned = {k: v for k, v in params["layers"].items()
               if not k.startswith("we_")}
    experts = {k: v.reshape((-1,) + v.shape[2:])
               for k, v in params["layers"].items() if k.startswith("we_")}

    def dense(carry, xs):
        lp, layer = xs
        x, cache, rec = attention(lp, *carry, layer * num_pages)
        return (_dense_ffn(lp, x, args), cache), rec

    def expert(carry, xs):
        lp, layer = xs
        x, cache, rec = attention(lp, *carry, layer * num_pages)
        x, c, picks = _expert_ffn(lp, experts, (layer - kd) * E, x, live,
                                  args)
        return (x, cache), (c, picks, rec)

    recs = None
    if kd:
        (x, cache), recs = jax.lax.scan(
            dense, (x, cache),
            (params["dense_layers"], jnp.arange(kd, dtype=jnp.int32)))
    (x, cache), (per_layer, picks, more) = jax.lax.scan(
        expert, (x, cache),
        (scanned, jnp.arange(kd, args.num_layers, dtype=jnp.int32)))
    if more is not None and recs is not None:
        more = jnp.concatenate([recs, more])
    return x, cache, jnp.sum(per_layer, axis=0), picks, more


def _head(params, x, args):
    x = lf.rms_norm(x, params["final_norm"], args.rms_eps)
    return _wmm(x, params["lm_head"]).astype(jnp.float32)


# ---------------------------------------------------------------------------
# what `serving/family.FamilyPath` asks of a family
# ---------------------------------------------------------------------------

UNSUPPORTED = {
    "model": "a latent-attention expert model",
    "mesh=": "the experts held and the latent pool have no tensor-parallel "
    "placement yet",
    "kv_dtype='int8'": "the latent rows are normed activations that every "
    "head reads; no int8 latent pool exists yet",
    "draft_params=": "no verify program over the latent pool exists yet",
    "hand-off": "a `KVHandoff` ships a K and a V pool, and this family has "
    "one pool of latent rows"}


def pools(args, num_pages, page_size, dtype):
    """The latent pool `[layers * num_pages, page, row_width]` or, behind a
    selector, (latent pool, index pool `[.., index width]`) under the one
    block table: carried, donated, written and copied on write as one."""
    pool = jnp.zeros((args.num_layers * num_pages, page_size,
                      args.row_width), dtype)
    if not args.indexer:
        return pool
    return pool, jnp.zeros((args.num_layers * num_pages, page_size,
                            args.indexer.dim), dtype)


def copy_page(pools, src, dst, args):
    """Page `src` onto page `dst` in every layer's run of every pool."""
    def one(pool):
        num_pages = pool.shape[0] // args.num_layers
        view = pool.reshape((args.num_layers, num_pages) + pool.shape[1:])
        view = jax.lax.dynamic_update_slice_in_dim(
            view, jax.lax.dynamic_slice_in_dim(view, src, 1, axis=1), dst,
            axis=1)
        return view.reshape(pool.shape)

    return jax.tree.map(one, pools)


def slot_state(args, slots, dtype):
    """A request keeps nothing beside its pages."""
    return ()


def tables(args, max_len):
    """(cos, sin). 2 * max_len: a window's padding may pass max_len before
    it is cut."""
    return rope_tables(2 * max_len, args)


def check_engine(args, eng):
    """Nothing of the engine's sizes is this family's to constrain."""


def gauges(args, state, pools):
    if not args.indexer:
        return {}
    return {"index_pool_bytes": pools[1].size * pools[1].dtype.itemsize}


def riders(args):
    """A decode step's four counts (`_routed_experts`) and, behind a
    selector, the keys selected and visible; SELECT_ROWS queries of a window
    where the description records the selection."""
    return (6 if args.indexer else 4,
            SELECT_ROWS if args.record_selection else 0)


def observe_prefill(args, eng, rows):
    """`serve.expert_fused_share`, once a step program of `rows` rows: 1.0
    where its expert layers are the fused pass over the hit experts'
    weights, 0.0 where they are grouped matmuls, by the rule the program
    was traced under (`experts_fused`). Over a deployment's windows and
    decode steps the mean is the decode steps' share of the step programs."""
    dtype = jax.tree_util.tree_leaves(eng.params["embedding"])[0].dtype
    return {"serve.expert_fused_share":
            float(experts_fused(rows, args, dtype))}


def observe_decode(args, eng, active):
    return observe_prefill(args, eng, eng.max_slots)


def _riders(args, counts, picks, selected):
    """What rides a step, each where the description keeps it."""
    return StepRiders(counts, picks if args.record_routing else None,
                      selected if args.record_selection else None)


def prefill_window(params, layer_ids, ids, h, last_idx, bt_row, new_pages,
                   pools, state, tables, args, record=None):
    """One prefill window of one slot (`models/family_protocol.py`). Rides:
    picks [expert layers, s, experts a token] where the description records
    the routing; where it records the selection, that of the SELECT_ROWS
    queries from window row `record` on, as packed bits [layers,
    SELECT_ROWS, table positions / 8] (`la.packed`; a query at position t <
    index_topk selects all t + 1)."""
    s = ids.shape[0]
    live = jnp.arange(s, dtype=jnp.int32) <= last_idx
    cos, sin = tables

    def attention(lp, x, cache, base):
        return _window_attention(lp, x, cache, h, last_idx, bt_row,
                                 new_pages, cos, sin, base, record, args)

    x = jnp.take(params["embedding"], ids, axis=0)
    x, pools, _, picks, selected = _stack(params, x, pools, attention, live,
                                          args)
    return (_head(params, x[last_idx][None], args)[0], pools, state,
            _riders(args, None, picks, selected))


def decode_step(params, layer_ids, tokens, bt, pos, live, pools, state,
                tables, args, record=None):
    """One token a slot (`models/family_protocol.py`). Rides: counts int32
    [4] summed over the expert layers (tokens at the busiest held expert,
    picks on held experts, picks in all, held experts with a token; behind
    a selector two more, over the live rows: the keys they selected and the
    keys they could see); picks [expert layers, b, experts a token]; row
    `record`'s selection as packed bits [layers, table positions / 8]
    (`la.packed`)."""
    cos, sin = tables

    def attention(lp, x, cache, base):
        return _decode_attention(lp, x, cache, bt, pos, cos, sin, base,
                                 record, args)

    x = jnp.take(params["embedding"], tokens, axis=0)
    x, pools, counts, picks, selected = _stack(params, x, pools, attention,
                                               live, args)
    if args.indexer:
        K = _topk(args, bt.shape[1]
                  * jax.tree_util.tree_leaves(pools)[0].shape[1])
        seen = jnp.where(live, pos + 1, 0)
        counts = jnp.concatenate([counts, jnp.stack([
            jnp.sum(jnp.minimum(seen, K)), jnp.sum(seen)])])
    return _head(params, x, args), pools, state, _riders(
        args, counts, picks, selected)
