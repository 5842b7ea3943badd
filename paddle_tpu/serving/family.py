"""The device half of `PagedEngine` for every family that keeps TREES: a tree
of page pools and a tree of per-slot state beside them (a recurrent state,
a convolution's last rows; empty where a request is its pages alone). The
path is written once over those trees and names no family: what is a
family's comes from its functional module, `family`, which states
`models/family_protocol.py` and is bound to this class in
`serving/paths.PATHS`.

What is the PATH's:

  - the two step programs, jitted with the pools and the state donated: one
    prefill program a window bucket and one decode program serve every
    context length (block tables, positions, the slot and the page vectors
    are traced). A slot's state restarts from zero in the prefill window
    that starts at position 0 (a recycled slot keeps nothing), and is kept
    through a window's padding and through decode steps the slot takes no
    part in;
  - the TOKEN VECTOR `[slots + counts]`: a decode step's output is the next
    one's operand as it lies on the device (the program reads its first
    `slots` rows, the engine a slot's row alone), and the family's counts
    ride its one read-back behind the rows' tokens (`landed`; their host
    half, the routing traces and the selection sample are
    `serving/routing.RoutingRiders`);
  - where the state tree has leaves, `SNAPSHOTS` buffers of one slot's state
    (every leaf of it), whose ids `BlockAllocator` hands out on its radix
    tree: saved when a prompt's last window ends (the state after the whole
    prompt), hung on the tree when the request retires, loaded into a slot
    that hits that prefix. An empty tree takes none;
  - preempt / resume carry the slot's state out and back in;
  - the refusals, with the family's reasons: `mesh=`, `kv_dtype='int8'`,
    `draft_params=`, a hand-off to a disaggregated worker.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.models.family_protocol import _move_rows
from paddle_tpu.serving.routing import RoutingRiders
from paddle_tpu.serving.sampler import pick as _pick, seat_token, token_vector

__all__ = ["FamilyPath", "SNAPSHOTS"]

SNAPSHOTS = 8      # snapshot buffers (one slot's state each)


def _prefill_traced(params, layer_ids, ids, h, last_idx, bt_row, new_pages,
                    slot, pools, state, tables, temp, top_p, top_k, seeds,
                    record=None, *, family, args, metrics, sample=False):
    metrics.inc("prefill_compiles")
    # the slot's own state; a window that starts at position 0 starts from
    # zero in every leaf (a recycled slot keeps nothing)
    own = jax.tree_util.tree_map(
        lambda a: jnp.where(h == 0, jnp.zeros((), a.dtype), a[slot]), state)
    logits, pools, own, riders = family.prefill_window(
        params, layer_ids, ids[0], h, last_idx, bt_row, new_pages, pools,
        own, tables, args, record)
    state = jax.tree_util.tree_map(
        lambda a, o: jax.lax.dynamic_update_slice_in_dim(a, o[None], slot, 0),
        state, own)
    first = _pick(logits[None], sample, temp, top_p, top_k, seeds,
                  h + last_idx + 1)[0]
    return pools, state, first, riders


def _decode_traced(params, layer_ids, tokens, bt, pos, live, pools, state,
                   tables, temp, top_p, top_k, seeds, record=None, *,
                   family, args, metrics, sample=False):
    metrics.inc("decode_compiles")
    # the token operand is the step before's whole output: the rows' tokens
    # and, behind them, its counts
    logits, pools, state, riders = family.decode_step(
        params, layer_ids, tokens[:pos.shape[0]], bt, pos, live, pools,
        state, tables, args, record)
    nxt = _pick(logits, sample, temp, top_p, top_k, seeds, pos + 1)
    if riders.counts is not None:
        nxt = jnp.concatenate([nxt, riders.counts.astype(nxt.dtype)])
    return pools, state, nxt, riders._replace(counts=None)


class FamilyPath:
    """Pools, per-slot state, snapshots and step programs of one engine."""

    def __init__(self, eng, family):
        args, self.eng, self.family = eng.args, eng, family
        why = family.UNSUPPORTED
        for given, what in ((eng.mesh, "mesh="),
                            (eng.kv_dtype, "kv_dtype='int8'"),
                            (eng.draft_params, "draft_params=")):
            if given is not None:
                raise ValueError(f"{what} is not supported for "
                                 f"{why['model']}: {why[what]}")
        args.validate()
        family.check_engine(args, eng)
        dtype = jax.tree_util.tree_leaves(eng.params["embedding"])[0].dtype
        self.pools = family.pools(args, eng.num_pages, eng.page_size, dtype)
        self.state = family.slot_state(args, eng.max_slots, dtype)
        # snapshot ids the allocator hands out: none for an empty tree
        self.snapshots = SNAPSHOTS if jax.tree_util.tree_leaves(
            self.state) else 0
        if self.snapshots and eng.prefix_policy != "radix":
            raise ValueError(f"{why['model']} needs prefix_policy='radix': "
                             "its state snapshots hang on the radix tree")
        self.snaps = family.slot_state(args, self.snapshots, dtype)
        self.tables = family.tables(args, eng.max_len)
        self.layer_ids = jnp.arange(args.num_layers, dtype=jnp.int32)
        counts, select_rows = family.riders(args)
        # the rows' last tokens (and room for the family's counts behind
        # them): a decode step's output is the next one's operand, a
        # prompt's first token is seated (`seat`)
        self.tokens = token_vector(eng.max_slots + counts, eng.pad_id)
        self.riders = RoutingRiders(eng, select_rows)
        self.reset()

        donate = eng._donate_enabled()
        kw = dict(family=family, args=args, metrics=eng.metrics)
        self._prefill, self._decode = {}, {}
        for sample in (False, True):
            self._prefill[sample] = jax.jit(
                functools.partial(_prefill_traced, sample=sample, **kw),
                donate_argnums=(8, 9) if donate else ())
            self._decode[sample] = jax.jit(
                functools.partial(_decode_traced, sample=sample, **kw),
                donate_argnums=(6, 7) if donate else ())

        @jax.named_scope("pt.kv_write")
        def _copy_page_traced(pools, src, dst):
            """Copy-on-write: one page of every pool."""
            return family.copy_page(pools, src, dst, args)

        self._copy = jax.jit(_copy_page_traced,
                             donate_argnums=(0,) if donate else ())
        self._move = jax.jit(_move_rows,
                             donate_argnums=(0,) if donate else ())
        # never donates: the vector it is given may be a step's output that
        # the host has not read yet
        self._seat = jax.jit(functools.partial(seat_token,
                                               metrics=eng.metrics))

    def reset(self):
        """An empty engine: no snapshot is waiting (the allocator's ids
        start over with it); the arrays stay, a slot's state restarts at
        position 0 anyway."""
        self.pending = {}     # slot -> snapshot id taken at its prompt's end
        self.riders.reset()

        def nbytes(tree):
            return sum(x.size * x.dtype.itemsize
                       for x in jax.tree_util.tree_leaves(tree))

        m = self.eng.metrics
        m.set_gauge("kv_pool_bytes", nbytes(self.pools))
        if self.snapshots:
            m.set_gauge("recurrent_state_bytes", nbytes(self.state))
        for name, value in self.family.gauges(self.eng.args, self.state,
                                              self.pools).items():
            m.set_gauge(name, value)

    def _observe(self, seen):
        for name, value in seen.items():
            self.eng.metrics.observe(name, value)

    # -- pages ----------------------------------------------------------------
    def copy_page(self, src, dst):
        self.pools = self._copy(self.pools, jnp.int32(src), jnp.int32(dst))

    def check_handoff(self):
        why = self.family.UNSUPPORTED
        raise ValueError("disaggregated workers do not serve "
                         f"{why['model']}: {why['hand-off']}")

    # -- per-slot state -----------------------------------------------------------
    def load_snapshot(self, slot, sid):
        self.state = self._move(self.state, self.snaps, jnp.int32(slot),
                                jnp.int32(sid))

    def prompt_done(self, slot):
        """The slot's last prefill window ran: keep its state, now the state
        after its whole prompt, where a snapshot id is to be had; the
        request's retirement hangs it on the radix tree (`attach`)."""
        if not self.snapshots:
            return
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
        self.riders.leave(slot)
        one = jax.tree_util.tree_map(
            lambda a: jnp.zeros((1,) + a.shape[1:], a.dtype), self.state)
        return (self._move(one, self.state, jnp.int32(0), jnp.int32(slot)),
                self.pending.pop(slot, None))

    def put_state(self, slot, saved):
        own, sid = saved
        self.state = self._move(self.state, own, jnp.int32(slot),
                                jnp.int32(0))
        if sid is not None:
            self.pending[slot] = sid
        self.riders.seat(slot)

    def landed(self, out):
        """A decode step's output was read (`out`, the host copy the engine
        made): behind the rows' tokens, the family's counts."""
        if len(out) > self.eng.max_slots:
            self.riders.landed(out[self.eng.max_slots:])

    # -- the token vector and the two step programs -------------------------------
    def seat(self, slot, token):
        self.tokens = self._seat(self.tokens, jnp.int32(slot),
                                 jnp.asarray(token, jnp.int32))

    def prefill(self, ids, start, last_idx, bt_row, new_vec, slot, req,
                sample):
        row = self.riders.window_row(req, start, last_idx)
        self.pools, self.state, first, kept = self._prefill[sample](
            self.eng.params, self.layer_ids, jnp.asarray(ids),
            jnp.int32(start),
            jnp.int32(last_idx), jnp.asarray(bt_row), jnp.asarray(new_vec),
            jnp.int32(slot), self.pools, self.state, self.tables,
            jnp.float32(req.temperature),
            jnp.float32(req.top_p), jnp.int32(req.top_k),
            jnp.asarray([req.seed], jnp.int32), row)
        self._observe(self.family.observe_prefill(
            self.eng.args, self.eng, np.shape(ids)[-1]))
        self.riders.window(req, slot, start, last_idx + 1, kept.picks, row,
                           kept.selection)
        return first

    def decode(self, bt, active, sample, sampling_args):
        eng = self.eng
        live = np.zeros(eng.max_slots, bool)
        live[active] = True
        self._observe(self.family.observe_decode(eng.args, eng, active))
        # a COPY of the positions: the engine moves them on as soon as this
        # returns, and a host array handed to the device may be read later
        pos = eng._npos.copy()
        row, keep = self.riders.step_row(active)
        self.pools, self.state, self.tokens, kept = self._decode[sample](
            eng.params, self.layer_ids, self.tokens, bt, pos, live,
            self.pools, self.state, self.tables, *sampling_args, row)
        self.riders.step(kept.picks, live, pos,
                         kept.selection if keep else None, row)
        return self.tokens
