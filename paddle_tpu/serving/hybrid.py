"""The device half of `PagedEngine` for a hybrid stack
(`models/hybrid_functional.HybridArgs`): what a model with lightning and
sparse layers keeps beside the block tables, and the programs over it.

  - pages for the SPARSE layers only: `pk`, `pv` [num_pages, nkv, B, d] and
    the selector's compressed keys `kc` [num_pages, nkv, per, d], one array
    a sparse layer, all under the allocator's page ids (a copy-on-write
    page copy copies the three);
  - the RECURRENT STATE of the lightning layers: one `[slots, heads, d, d]`
    float32 array a layer, 2 MiB a slot and layer whatever the context's
    length. A slot's state restarts from zero in the prefill window that
    starts at position 0, and is kept through a window's padding and
    through decode steps the slot takes no part in;
  - `SNAPSHOTS` buffers of one slot's state, whose ids `BlockAllocator`
    hands out: saved when a prompt's last window ends (the state after the
    whole prompt), hung on the radix tree when the request retires, loaded
    into a slot that hits that prefix.

One prefill program a window bucket and one decode program serve every
context length: block tables, positions, the slot and the page vectors are
traced, the sparse layers' loops follow the traced position, and the dense
rule (context <= dense_len) is a `where` beside the selection.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.models import hybrid_functional as hf
from paddle_tpu.models import llama_functional as lf
from paddle_tpu.serving.sampler import pick as _pick

__all__ = ["HybridPath", "SNAPSHOTS"]

SNAPSHOTS = 8      # snapshot buffers (one slot's state each)


def _prefill_traced(params, layer_ids, ids, h, last_idx, bt_row, new_pages,
                    slot, pk, pv, kc, state, cos, sin, temp, top_p, top_k,
                    seeds, *, args, metrics, sample=False):
    metrics.inc("prefill_compiles")
    logits, pk, pv, kc, state = hf.prefill_window(
        params, layer_ids, ids[0], h, last_idx, bt_row, new_pages, slot, pk,
        pv, kc, state, cos, sin, args)
    first = _pick(logits[None], sample, temp, top_p, top_k, seeds,
                  h + last_idx + 1)[0]
    return pk, pv, kc, state, first


def _decode_traced(params, layer_ids, tokens, bt, pos, live, pk, pv, kc,
                   state, cos, sin, temp, top_p, top_k, seeds, *, args,
                   metrics, sample=False):
    metrics.inc("decode_compiles")
    logits, pk, pv, kc, state = hf.decode_step(
        params, layer_ids, tokens, bt, pos, live, pk, pv, kc, state, cos,
        sin, args)
    return pk, pv, kc, state, _pick(logits, sample, temp, top_p, top_k,
                                    seeds, pos + 1)


@jax.named_scope("pt.kv_write")
def _copy_page_traced(pk, pv, kc, src, dst):
    """Copy-on-write: one page's K, V and compressed keys, every sparse
    layer (the page axis is axis 0 of every leaf)."""
    def cp(a):
        return jax.lax.dynamic_update_slice_in_dim(
            a, jax.lax.dynamic_slice_in_dim(a, src, 1, axis=0), dst, axis=0)

    return jax.tree_util.tree_map(cp, (pk, pv, kc))


def _move_state_traced(dst, src, to, frm):
    """dst[to] = src[frm] in every lightning layer's array."""
    return tuple(jax.lax.dynamic_update_slice_in_dim(
        d, jax.lax.dynamic_slice_in_dim(s, frm, 1, axis=0), to, axis=0)
        for d, s in zip(dst, src))


class HybridPath:
    """Pools, recurrent state, snapshots and step programs of one engine."""

    def __init__(self, eng):
        args, self.eng = eng.args, eng
        for given, what, why in (
                (eng.mesh, "mesh=", "the recurrent state and the selection "
                 "have no tensor-parallel placement yet"),
                (eng.kv_dtype, "kv_dtype='int8'", "the selector's "
                 "compressed keys are means of unquantized keys"),
                (eng.draft_params, "draft_params=", "a rejected draft "
                 "token cannot be taken back out of a recurrent state")):
            if given is not None:
                raise ValueError(f"{what} is not supported for a "
                                 f"hybrid model: {why}")
        args.validate()
        cfg = args.sparse
        if eng.page_size != cfg.block_size:
            raise ValueError(
                f"page_size={eng.page_size} must equal the sparse layers' "
                f"block_size={cfg.block_size}: a selection is a block table")
        if eng.prefix_policy != "radix":
            raise ValueError("a hybrid model needs prefix_policy='radix': "
                             "its state snapshots hang on the radix tree")
        dtype = jax.tree_util.tree_leaves(eng.params["embedding"])[0].dtype
        n_sparse = len(args.layers_of(hf.SPARSE))
        n_light = len(args.layers_of(hf.LIGHTNING))
        nkv, d, H = args.sparse_kv_heads, args.head_dim, args.num_heads
        page = (eng.num_pages, nkv, cfg.block_size, d)
        self.pk = tuple(jnp.zeros(page, dtype) for _ in range(n_sparse))
        self.pv = tuple(jnp.zeros(page, dtype) for _ in range(n_sparse))
        self.kc = tuple(jnp.zeros((eng.num_pages, nkv, cfg.per, d), dtype)
                        for _ in range(n_sparse))
        self.state = tuple(jnp.zeros((eng.max_slots, H, d, d), jnp.float32)
                           for _ in range(n_light))
        self.snaps = tuple(jnp.zeros((self.snapshots, H, d, d), jnp.float32)
                           for _ in range(n_light))
        self.layer_ids = jnp.arange(args.num_layers, dtype=jnp.int32)
        self.cos, self.sin = lf.rope_tables(2 * eng.max_len, d,
                                            args.rope_theta)
        self.reset()

        donate = eng._donate_enabled()
        kw = dict(args=args, metrics=eng.metrics)
        self._prefill, self._decode = {}, {}
        for sample in (False, True):
            self._prefill[sample] = jax.jit(
                functools.partial(_prefill_traced, sample=sample, **kw),
                donate_argnums=(8, 9, 10, 11) if donate else ())
            self._decode[sample] = jax.jit(
                functools.partial(_decode_traced, sample=sample, **kw),
                donate_argnums=(6, 7, 8, 9) if donate else ())
        self._copy = jax.jit(_copy_page_traced,
                             donate_argnums=(0, 1, 2) if donate else ())
        self._move = jax.jit(_move_state_traced,
                             donate_argnums=(0,) if donate else ())

    snapshots = SNAPSHOTS

    def reset(self):
        """An empty engine: no snapshot is waiting (the allocator's ids
        start over with it); the arrays stay, a slot's state restarts at
        position 0 anyway."""
        self.pending = {}     # slot -> snapshot id taken at its prompt's end

        def nbytes(tree):
            return sum(x.size * x.dtype.itemsize
                       for x in jax.tree_util.tree_leaves(tree))

        m = self.eng.metrics
        m.set_gauge("kv_pool_bytes", nbytes((self.pk, self.pv, self.kc)))
        m.set_gauge("recurrent_state_bytes", nbytes(self.state))

    # -- pages ----------------------------------------------------------------
    def copy_page(self, src, dst):
        self.pk, self.pv, self.kc = self._copy(
            self.pk, self.pv, self.kc, jnp.int32(src), jnp.int32(dst))

    def check_handoff(self):
        raise ValueError(
            "disaggregated workers do not serve a hybrid model: a "
            "`KVHandoff` ships pages, and the lightning layers' recurrent "
            "state is in none of them")

    # -- recurrent state --------------------------------------------------------
    def load_snapshot(self, slot, sid):
        self.state = self._move(self.state, self.snaps, jnp.int32(slot),
                                jnp.int32(sid))

    def prompt_done(self, slot):
        """The slot's last prefill window ran: keep its state, now the state
        after its whole prompt, where a snapshot id is to be had; the
        request's retirement hangs it on the radix tree (`attach`)."""
        sid = self.eng._alloc.take_snapshot()
        if sid is None:
            # every id waits for a request that is still decoding: the
            # oldest of them gives its own up (the newest prompt's end is
            # the likeliest to be asked for again)
            sid = self.pending.pop(next(iter(self.pending)))
        self.snaps = self._move(self.snaps, self.state, jnp.int32(sid),
                                jnp.int32(slot))
        self.pending[slot] = sid
        self.eng.metrics.inc("state_snapshots")

    def attach(self, slot, prompt_ids, registered):
        """The slot retires: its prompt's pages are in the tree (or not)."""
        sid = self.pending.pop(slot, None)
        if sid is None:
            return
        if registered:
            self.eng._alloc.attach_state(prompt_ids, sid)
        else:
            self.eng._alloc.release_snapshot(sid)

    def take_state(self, slot):
        """What a preempted slot leaves with: its state, out of the slot's
        row, and the snapshot waiting for the request's retirement."""
        one = tuple(jnp.zeros((1,) + s.shape[1:], s.dtype)
                    for s in self.state)
        return (self._move(one, self.state, jnp.int32(0), jnp.int32(slot)),
                self.pending.pop(slot, None))

    def put_state(self, slot, saved):
        recurrent, sid = saved
        self.state = self._move(self.state, recurrent, jnp.int32(slot),
                                jnp.int32(0))
        if sid is not None:
            self.pending[slot] = sid

    # -- the two step programs ----------------------------------------------------
    def prefill(self, ids, start, last_idx, bt_row, new_vec, slot, req,
                sample):
        self.pk, self.pv, self.kc, self.state, first = self._prefill[sample](
            self.eng.params, self.layer_ids, jnp.asarray(ids),
            jnp.int32(start),
            jnp.int32(last_idx), jnp.asarray(bt_row), jnp.asarray(new_vec),
            jnp.int32(slot), self.pk, self.pv, self.kc, self.state,
            self.cos, self.sin, jnp.float32(req.temperature),
            jnp.float32(req.top_p), jnp.int32(req.top_k),
            jnp.asarray([req.seed], jnp.int32))
        return first

    def decode(self, bt, active, sample, sampling_args):
        eng, cfg = self.eng, self.eng.args.sparse
        live = np.zeros(eng.max_slots, bool)
        live[active] = True
        # pages a sparse layer's KV head reads over pages the rows hold:
        # all of a context that is still dense, the selection past that
        held = eng._npos[active] // cfg.block_size + 1
        read = np.where(eng._npos[active] + 1 <= cfg.dense_len, held,
                        np.minimum(held, cfg.topk))
        eng.metrics.observe("sparse_read_share",
                            float(read.sum()) / float(held.sum()))
        self.pk, self.pv, self.kc, self.state, nxt = self._decode[sample](
            eng.params, self.layer_ids, jnp.asarray(eng._last_tok),
            jnp.asarray(bt),
            jnp.asarray(eng._npos), jnp.asarray(live), self.pk, self.pv,
            self.kc, self.state, self.cos, self.sin, *sampling_args)
        return nxt
