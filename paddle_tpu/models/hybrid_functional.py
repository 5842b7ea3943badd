"""A decoder whose layers are of two kinds, served over `PagedEngine`'s
paged cache: LIGHTNING layers (linear attention with a decaying recurrent
state, `kernels/lightning_attention.py`) and SPARSE layers (grouped-query
softmax attention without rotary positions that, past `dense_len`, reads only
the blocks a selector picks from a cache of compressed keys,
`kernels/sparse_attention.py`). `HybridArgs` is the static description that
selects this path: `PagedEngine(params, HybridArgs(...))`.

Every layer is `x += a * Mixer(norm(x)); x += a * SwiGLU(norm(x))` with the
muP residual scale `a`; the embedding is scaled by `scale_emb` and the final
norm's output divided by `logit_divisor` before the head. Both mixers norm q
and k per head (learned weight) and gate their output with `sigmoid(h Wg)`;
a lightning layer also norms its output over the whole width and rotates q
and k.

The parameter tree is `llama_functional`'s (`embedding`, `layers/*` stacked
on a leading layer axis, `final_norm`, `lm_head`) with ONE set of layer
leaves, the lightning layer's: `ln1 ln2 wq wk wv wo wg q_norm k_norm o_norm
w_gate w_up w_down`. A sparse layer reads the leading `sparse_kv_heads *
head_dim` columns of the same `wk` / `wv` and no `o_norm`. The layer loop is
unrolled (the kinds differ); the stack is indexed at run time (`_layer`).

Per-request state beside the pages: `state`, one `[slots, heads, d, d]`
float32 array a lightning layer; the pools `(pk, pv, kc)`: `pk`, `pv`
`[num_pages, nkv, B, d]` and the compressed keys `kc` `[num_pages, nkv, per,
d]`, one a sparse layer. All are tuples of per-layer arrays so that a step
updates each in place.

What `serving/family.FamilyPath` asks of a family's functional module
(`models/family_protocol.py`) is the last section.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.kernels import lightning_attention as la
from paddle_tpu.kernels import sparse_attention as sa
from paddle_tpu.models import llama_functional as lf
from paddle_tpu.models.family_protocol import StepRiders, _move_rows
from paddle_tpu.models.generation import _wmm, _write_rows

__all__ = ["HybridArgs", "SPARSE", "LIGHTNING", "prefill_window",
           "decode_step"]

SPARSE, LIGHTNING = "sparse", "lightning"


class HybridArgs(NamedTuple):
    """Static (hashable) description of a hybrid stack."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_heads: int
    head_dim: int
    sparse_kv_heads: int
    layer_kinds: Tuple[str, ...]
    rope_theta: float
    rms_eps: float
    scale_emb: float
    residual_scale: float
    logit_divisor: float
    sparse: sa.SparseConfig

    @property
    def num_layers(self):
        return len(self.layer_kinds)

    @property
    def num_kv_heads(self):
        return self.sparse_kv_heads

    def layers_of(self, kind):
        return [i for i, k in enumerate(self.layer_kinds) if k == kind]

    def validate(self):
        bad = set(self.layer_kinds) - {SPARSE, LIGHTNING}
        if bad or not self.layer_kinds:
            raise ValueError(f"layer_kinds holds {sorted(bad)}; a layer is "
                             f"{SPARSE!r} or {LIGHTNING!r}")
        if self.num_heads % self.sparse_kv_heads:
            raise ValueError("num_heads must be a multiple of "
                             "sparse_kv_heads")
        self.sparse.validate()


def _head_norm(x, w, eps):
    """RMS norm over each head's d, x [..., heads, d], learned w [d]."""
    return lf.rms_norm(x, w, eps)


def _layer(params, index):
    """Layer `index`'s leaves out of the stack. `index` is a TRACED scalar
    (an entry of the `layer_ids` operand, `arange(layers)`): a slice at a
    run-time index fuses into the matmul that reads it, as in a scan over
    the stack, where a slice at a constant index is materialised as a copy
    of the layer's weights in every step."""
    return {k: jax.lax.dynamic_index_in_dim(v, index, 0, keepdims=False)
            for k, v in params["layers"].items()}


def _mlp(lp, x, args):
    hin = lf.rms_norm(x, lp["ln2"], args.rms_eps)
    with jax.named_scope("pt.mlp"):
        act = jax.nn.silu(_wmm(hin, lp["w_gate"])) * _wmm(hin, lp["w_up"])
        return x + args.residual_scale * _wmm(act, lp["w_down"])


def _embed(params, ids, args):
    x = jnp.take(params["embedding"], ids, axis=0)
    return x * jnp.asarray(args.scale_emb, x.dtype)


def _head(params, x, args):
    x = lf.rms_norm(x, params["final_norm"], args.rms_eps)
    x = x / jnp.asarray(args.logit_divisor, x.dtype)
    return _wmm(x, params["lm_head"]).astype(jnp.float32)


def _qkv(lp, hin, args, nkv):
    """q [.., H, d], k, v [.., nkv, d] with the per-head norms on q and k; a
    sparse layer (nkv < H) reads the leading columns of wk / wv."""
    H, d = args.num_heads, args.head_dim
    lead = hin.shape[:-1]
    q = _wmm(hin, lp["wq"]).reshape(*lead, H, d)
    k = _wmm(hin, lp["wk"][:, :nkv * d]).reshape(*lead, nkv, d)
    v = _wmm(hin, lp["wv"][:, :nkv * d]).reshape(*lead, nkv, d)
    return (_head_norm(q, lp["q_norm"], args.rms_eps),
            _head_norm(k, lp["k_norm"], args.rms_eps), v)


def _gated_out(lp, x, hin, attn, args):
    gate = jax.nn.sigmoid(_wmm(hin, lp["wg"]).astype(jnp.float32))
    out = (attn.astype(jnp.float32) * gate).astype(x.dtype)
    return x + args.residual_scale * _wmm(out, lp["wo"])


# ---------------------------------------------------------------------------
# a prefill window of one slot
# ---------------------------------------------------------------------------

def _lightning_window(lp, x, S, pos, valid, cos, sin, args):
    hin = lf.rms_norm(x, lp["ln1"], args.rms_eps)
    with jax.named_scope("pt.attention"):
        q, k, v = _qkv(lp, hin, args, args.num_heads)
        q, k = lf.apply_rope_bcast(q, k, cos[pos][:, None, :],
                                   sin[pos][:, None, :])
        o, S = la.lightning_chunk_scan(q, k, v, S, la.lightning_slopes(
            args.num_heads), valid)
        o = lf.rms_norm(o.reshape(x.shape[0], -1), lp["o_norm"],
                        args.rms_eps)
        x = _gated_out(lp, x, hin, o, args)
    return _mlp(lp, x, args), S


@jax.named_scope("pt.kv_write")
def _write_window_pages(pool, new, h, bt_row, new_pages, B):
    """Write the window's rows `new` [s, nkv, d] (positions h ..) into the
    pages `new_pages` (the slot's pages from the one that holds h on;
    unused entries are the null page). h may sit inside a page: the
    positions of that page below h keep what the page holds."""
    s, nkv, d = new.shape
    # a window as long as the whole table starts at 0 and ends on a page
    n_pages = min(s // B + 1, new_pages.shape[0])
    first = pool[bt_row[h // B]]                      # [nkv, B, d]
    buf = jnp.zeros(((n_pages + 1) * B, nkv, d), pool.dtype)
    buf = jax.lax.dynamic_update_slice_in_dim(
        buf, jnp.swapaxes(first, 0, 1), 0, 0)
    buf = jax.lax.dynamic_update_slice_in_dim(buf, new, h % B, 0)
    pages = jnp.swapaxes(buf[:n_pages * B].reshape(n_pages, B, nkv, d), 1, 2)
    return pool.at[new_pages[:n_pages]].set(pages)


def _sparse_window(lp, x, pk, pv, kc, h, last_idx, pos, bt_row, new_pages,
                   args):
    cfg, nkv = args.sparse, args.sparse_kv_heads
    B, K = cfg.block_size, cfg.kernel_size
    hin = lf.rms_norm(x, lp["ln1"], args.rms_eps)
    with jax.named_scope("pt.attention"):
        q, k, v = _qkv(lp, hin, args, nkv)
        # the keys just before the window, for the kernels it completes
        before = jnp.maximum(h - K + jnp.arange(K, dtype=jnp.int32), 0)
        prev_k = pk[bt_row[before // B], :, before % B]
        pk = _write_window_pages(pk, k, h, bt_row, new_pages, B)
        pv = _write_window_pages(pv, v, h, bt_row, new_pages, B)
        values, ends, ok = sa.compressed_keys_of_window(
            k, prev_k, h, last_idx, cfg)
        kc = sa.write_compressed(kc, values, ends, ok, h, new_pages, cfg)
        s = x.shape[0]
        qg = q.reshape(s, nkv, args.num_heads // nkv, args.head_dim)
        sel = sa.prefill_selection(qg, kc, bt_row, pos, cfg)
        attn = sa.sparse_prefill_attention(qg, pk, pv, bt_row, sel, pos,
                                           h + last_idx, cfg)
        x = _gated_out(lp, x, hin, attn.reshape(s, -1), args)
    return _mlp(lp, x, args), pk, pv, kc


# ---------------------------------------------------------------------------
# what `serving/family.FamilyPath` asks of a family
# ---------------------------------------------------------------------------

# the refusals of every family whose slots keep a recurrent state
UNSUPPORTED = {
    "model": "a hybrid model",
    "mesh=": "the recurrent state has no tensor-parallel placement yet",
    "kv_dtype='int8'": "the hybrid families' pools hold unquantized keys (a "
    "selector's compressed keys are means of them) and have no int8 write "
    "path",
    "draft_params=": "a rejected draft token cannot be taken back out of a "
    "recurrent state",
    "hand-off": "a `KVHandoff` ships pages, and the linear layers' "
    "recurrent state is in none of them"}


def pools(args, num_pages, page_size, dtype):
    """(pk, pv, kc): a tuple of one array a sparse layer each; the page
    axis is axis 0 of every leaf."""
    cfg, nkv, d = args.sparse, args.sparse_kv_heads, args.head_dim
    n = len(args.layers_of(SPARSE))
    page = (num_pages, nkv, page_size, d)
    return (tuple(jnp.zeros(page, dtype) for _ in range(n)),
            tuple(jnp.zeros(page, dtype) for _ in range(n)),
            tuple(jnp.zeros((num_pages, nkv, cfg.per, d), dtype)
                  for _ in range(n)))


def copy_page(pools, src, dst, args):
    return _move_rows(pools, pools, dst, src)


def slot_state(args, slots, dtype):
    """One `[slots, heads, d, d]` float32 array a lightning layer: 2 MiB a
    slot and layer whatever the context's length."""
    H, d = args.num_heads, args.head_dim
    return tuple(jnp.zeros((slots, H, d, d), jnp.float32)
                 for _ in args.layers_of(LIGHTNING))


def tables(args, max_len):
    """(cos, sin) of the lightning layers' rotary positions; 2 * max_len: a
    window's padding may pass max_len before it is cut."""
    return lf.rope_tables(2 * max_len, args.head_dim, args.rope_theta)


def check_engine(args, eng):
    if eng.page_size != args.sparse.block_size:
        raise ValueError(
            f"page_size={eng.page_size} must equal the sparse layers' "
            f"block_size={args.sparse.block_size}: a selection is a block "
            "table")


def gauges(args, state, pools):
    """No record of its own."""
    return {}


def riders(args):
    """No counts, no selection kept."""
    return 0, 0


def observe_prefill(args, eng, rows):
    """Whether the sparse layers of a window program of `rows` rows attend
    through the Pallas kernel (1.0) or the jnp loop (0.0: the kernels off,
    or a shape the kernel refuses, which falls back without a word), by the
    rule the program was traced under (`sa.prefill_takes_kernel`)."""
    pool, nkv = eng.path.pools[0][0], args.num_kv_heads
    q = jax.ShapeDtypeStruct(
        (rows, nkv, args.num_heads // nkv, args.head_dim), pool.dtype)
    table = jax.ShapeDtypeStruct((eng.pages_per_slot,), jnp.int32)
    return {"serve.sparse_prefill_kernel_share":
            float(bool(sa.prefill_takes_kernel(q, pool, table)))}


def observe_decode(args, eng, active):
    """Pages a sparse layer's KV head reads over pages the rows hold: all
    of a context that is still dense, the selection past that."""
    cfg = args.sparse
    held = eng._npos[active] // cfg.block_size + 1
    read = np.where(eng._npos[active] + 1 <= cfg.dense_len, held,
                    np.minimum(held, cfg.topk))
    return {"sparse_read_share": float(read.sum()) / float(held.sum())}


def prefill_window(params, layer_ids, ids, h, last_idx, bt_row, new_pages,
                   pools, state, tables, args, record=None):
    """One prefill window of one slot (`models/family_protocol.py`); `state`
    the SLOT's own recurrent states. Nothing rides."""
    s = ids.shape[0]
    idx = jnp.arange(s, dtype=jnp.int32)
    pos, valid = h + idx, idx <= last_idx
    x = _embed(params, ids, args)
    (pk, pv, kc), (cos, sin) = (list(p) for p in pools), tables
    state = list(state)
    n_sparse = n_light = 0
    for i, kind in enumerate(args.layer_kinds):
        lp = _layer(params, layer_ids[i])
        if kind == LIGHTNING:
            j, n_light = n_light, n_light + 1
            x, state[j] = _lightning_window(lp, x, state[j], pos, valid,
                                            cos, sin, args)
        else:
            j, n_sparse = n_sparse, n_sparse + 1
            x, pk[j], pv[j], kc[j] = _sparse_window(
                lp, x, pk[j], pv[j], kc[j], h, last_idx, pos, bt_row,
                new_pages, args)
    logits = _head(params, x[last_idx][None], args)[0]
    return logits, (tuple(pk), tuple(pv), tuple(kc)), tuple(state), \
        StepRiders()


# ---------------------------------------------------------------------------
# a decode step of every slot
# ---------------------------------------------------------------------------

def _lightning_decode(lp, x, S, pos, live, cos, sin, args):
    hin = lf.rms_norm(x, lp["ln1"], args.rms_eps)
    with jax.named_scope("pt.attention"):
        q, k, v = _qkv(lp, hin, args, args.num_heads)
        q, k = lf.apply_rope_bcast(q, k, cos[pos][:, None, :],
                                   sin[pos][:, None, :])
        o, S = la.lightning_step(q, k, v, S, la.lightning_slopes(
            args.num_heads), live)
        o = lf.rms_norm(o.reshape(x.shape[0], -1), lp["o_norm"],
                        args.rms_eps)
        x = _gated_out(lp, x, hin, o, args)
    return _mlp(lp, x, args), S


def _sparse_decode(lp, x, pk, pv, kc, bt, pos, args):
    cfg, nkv = args.sparse, args.sparse_kv_heads
    B, b = cfg.block_size, x.shape[0]
    hin = lf.rms_norm(x, lp["ln1"], args.rms_eps)
    with jax.named_scope("pt.attention"):
        q, k, v = _qkv(lp, hin, args, nkv)
        # write before attending; a row that is not decoding has a table of
        # null pages, the garbage sink
        page = jnp.take_along_axis(bt, (pos // B)[:, None], axis=1)[:, 0]
        pk = _write_rows(pk, k, page, pos % B)
        pv = _write_rows(pv, v, page, pos % B)
        values, ok = sa.compressed_key_of_step(pk, bt, pos, cfg)
        kc = _write_rows(kc, values, jnp.where(ok, page, 0),
                         jnp.where(ok, sa.entry_of(pos, cfg), 0))
        qg = q.reshape(b, nkv, args.num_heads // nkv, args.head_dim)
        table, pos_eff, _ = sa.selected_table(qg, kc, bt, pos, cfg)
        attn = sa.sparse_decode_attention(qg, pk, pv, table, pos_eff)
        x = _gated_out(lp, x, hin, attn.reshape(b, -1), args)
    return _mlp(lp, x, args), pk, pv, kc


def decode_step(params, layer_ids, tokens, bt, pos, live, pools, state,
                tables, args, record=None):
    """One token a slot (`models/family_protocol.py`). Nothing rides."""
    x = _embed(params, tokens, args)
    (pk, pv, kc), (cos, sin) = (list(p) for p in pools), tables
    state = list(state)
    n_sparse = n_light = 0
    for i, kind in enumerate(args.layer_kinds):
        lp = _layer(params, layer_ids[i])
        if kind == LIGHTNING:
            j, n_light = n_light, n_light + 1
            x, state[j] = _lightning_decode(lp, x, state[j], pos, live, cos,
                                            sin, args)
        else:
            j, n_sparse = n_sparse, n_sparse + 1
            x, pk[j], pv[j], kc[j] = _sparse_decode(
                lp, x, pk[j], pv[j], kc[j], bt, pos, args)
    return _head(params, x, args), (tuple(pk), tuple(pv), tuple(kc)), \
        tuple(state), StepRiders()
