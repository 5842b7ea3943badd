"""The device half of `PagedEngine` for a HYBRID stack: a model whose
layers keep two kinds of per-request state, pages for its attention layers
and a recurrent state, whatever the context's length, for its linear ones.
One path serves every such family; what is a family's comes from its
functional module (`FAMILIES`, `ROUTED`):

  `models/hybrid_functional` (`HybridArgs`): lightning layers (one `[slots,
      heads, d, d]` float32 state a layer) beside block-sparse layers (pages
      of K, V and the selector's compressed keys);
  `models/gated_delta_functional` (`GatedDeltaArgs`): gated delta-rule
      layers (a `[slots, H, dk, dv]` float32 matrix state AND the last rows
      of a short convolution's input, a layer) beside full multi-head
      attention layers (pages of K and V);
  `models/latent_delta_functional` (`LatentDeltaMoEArgs`): gated delta-rule
      layers (the same two leaves a layer) beside gated latent attention
      layers (pages of one latent row a token), routed experts behind
      either: a ROUTED family.

A family's module gives `pools(args, num_pages, page_size, dtype)` (a tree
whose every leaf has the PAGE axis first: a copy-on-write page copy copies
them all), `slot_state(args, slots, dtype)` (a tree whose every leaf has the
SLOT axis first), `tables(args, max_len)` (constants of the programs),
`check_engine(args, eng)` (what the family needs of the engine's sizes),
`gauges(args, state, pools)` (records of how its step programs are built for
these arrays), `observe_prefill(args, eng, rows)` / `observe_decode(args,
eng, active)` (its own observations of a window / a step, from the host's
numbers) and the two step functions `prefill_window` / `decode_step`.

A ROUTED family's step functions return two things more, and its module
states `RIDERS`: `decode_step` the routing's counts (int32 `[RIDERS]`) and
both the experts every row picked. The counts ride the decode step's
read-back as `serving/latent.LatentPath`'s do (appended to the rows' next
tokens: the token vector is `[slots + RIDERS]`, the step reads its first
`slots` rows, the engine a slot's row alone, and `landed` gets the host
copy), and the picks go to the requests' routing traces where
`args.record_routing` asks for them. The host's half of both is
`serving/latent.RoutingRiders`, the piece the two paths share: the step log,
a trace seated at its windows and carried through preempt and resume beside
the state, the observations.

What is the PATH's is written once over those trees:

  - a slot's state restarts from zero in the prefill window that starts at
    position 0 (a recycled slot keeps nothing), and is kept through a
    window's padding and through decode steps the slot takes no part in;
  - `SNAPSHOTS` buffers of one slot's state (every leaf of it), whose ids
    `BlockAllocator` hands out: saved when a prompt's last window ends (the
    state after the whole prompt), hung on the radix tree when the request
    retires, loaded into a slot that hits that prefix;
  - preempt / resume carry the slot's state out and back in;
  - the refusals: `mesh=`, `kv_dtype='int8'`, `draft_params=`, a hand-off.

One prefill program a window bucket and one decode program serve every
context length: block tables, positions, the slot and the page vectors are
traced.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.models import gated_delta_functional as gdf
from paddle_tpu.models import hybrid_functional as hf
from paddle_tpu.models import latent_delta_functional as ldf
from paddle_tpu.serving.latent import RoutingRiders
from paddle_tpu.serving.sampler import pick as _pick, seat_token, token_vector

__all__ = ["HybridPath", "SNAPSHOTS", "FAMILIES", "ROUTED"]

SNAPSHOTS = 8      # snapshot buffers (one slot's state each)

# type of the model description -> the family's functional module
FAMILIES = {hf.HybridArgs: hf, gdf.GatedDeltaArgs: gdf}
# the same for the families whose step functions also return the routing's
# counts and picks
ROUTED = {ldf.LatentDeltaMoEArgs: ldf}


def _move_rows(dst, src, to, frm):
    """dst[to] = src[frm] along axis 0 of every leaf of two like trees."""
    return jax.tree_util.tree_map(
        lambda d, s: jax.lax.dynamic_update_slice_in_dim(
            d, jax.lax.dynamic_slice_in_dim(s, frm, 1, axis=0), to, axis=0),
        dst, src)


def _prefill_traced(params, layer_ids, ids, h, last_idx, bt_row, new_pages,
                    slot, pools, state, tables, temp, top_p, top_k, seeds, *,
                    family, args, metrics, sample=False):
    metrics.inc("prefill_compiles")
    # the slot's own state; a window that starts at position 0 starts from
    # zero in every leaf (a recycled slot keeps nothing)
    own = jax.tree_util.tree_map(
        lambda a: jnp.where(h == 0, jnp.zeros((), a.dtype), a[slot]), state)
    logits, pools, own, *picks = family.prefill_window(
        params, layer_ids, ids[0], h, last_idx, bt_row, new_pages, pools,
        own, tables, args)
    state = jax.tree_util.tree_map(
        lambda a, o: jax.lax.dynamic_update_slice_in_dim(a, o[None], slot, 0),
        state, own)
    first = _pick(logits[None], sample, temp, top_p, top_k, seeds,
                  h + last_idx + 1)[0]
    return pools, state, first, _recorded(args, picks)


def _recorded(args, picks):
    """The picks a routed family's step returned, where its description
    keeps them; None for any other."""
    return picks[0] if picks and args.record_routing else None


def _decode_traced(params, layer_ids, tokens, bt, pos, live, pools, state,
                   tables, temp, top_p, top_k, seeds, *, family, args,
                   metrics, sample=False):
    metrics.inc("decode_compiles")
    if riders := getattr(family, "RIDERS", 0):
        # the token operand is the step before's whole output: the rows'
        # tokens and, behind them, its counts
        tokens = tokens[:pos.shape[0]]
    logits, pools, state, *routed = family.decode_step(
        params, layer_ids, tokens, bt, pos, live, pools, state, tables, args)
    nxt = _pick(logits, sample, temp, top_p, top_k, seeds, pos + 1)
    if riders:
        nxt = jnp.concatenate([nxt, routed[0].astype(nxt.dtype)])
    return pools, state, nxt, _recorded(args, routed[1:])


@jax.named_scope("pt.kv_write")
def _copy_page_traced(pools, src, dst):
    """Copy-on-write: one page of every pool (the page axis is axis 0 of
    every leaf)."""
    return _move_rows(pools, pools, dst, src)


class HybridPath:
    """Pools, per-slot state, snapshots and step programs of one engine."""

    def __init__(self, eng):
        args, self.eng = eng.args, eng
        family = self.family = {**FAMILIES, **ROUTED}[type(args)]
        for given, what, why in (
                (eng.mesh, "mesh=", "the recurrent state has no "
                 "tensor-parallel placement yet"),
                (eng.kv_dtype, "kv_dtype='int8'", "the hybrid families' "
                 "pools hold unquantized keys (a selector's compressed keys "
                 "are means of them) and have no int8 write path"),
                (eng.draft_params, "draft_params=", "a rejected draft "
                 "token cannot be taken back out of a recurrent state")):
            if given is not None:
                raise ValueError(f"{what} is not supported for a "
                                 f"hybrid model: {why}")
        args.validate()
        family.check_engine(args, eng)
        if eng.prefix_policy != "radix":
            raise ValueError("a hybrid model needs prefix_policy='radix': "
                             "its state snapshots hang on the radix tree")
        dtype = jax.tree_util.tree_leaves(eng.params["embedding"])[0].dtype
        self.pools = family.pools(args, eng.num_pages, eng.page_size, dtype)
        self.state = family.slot_state(args, eng.max_slots, dtype)
        self.snaps = family.slot_state(args, self.snapshots, dtype)
        self.tables = family.tables(args, eng.max_len)
        self.layer_ids = jnp.arange(args.num_layers, dtype=jnp.int32)
        # the rows' last tokens (and room for a routed family's counts
        # behind them): a decode step's output is the next one's operand, a
        # prompt's first token is seated (`seat`)
        self.tokens = token_vector(
            eng.max_slots + getattr(family, "RIDERS", 0), eng.pad_id)
        self.riders = RoutingRiders(eng)
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
        self._copy = jax.jit(_copy_page_traced,
                             donate_argnums=(0,) if donate else ())
        self._move = jax.jit(_move_rows,
                             donate_argnums=(0,) if donate else ())
        # never donates: the vector it is given may be a step's output that
        # the host has not read yet
        self._seat = jax.jit(functools.partial(seat_token,
                                               metrics=eng.metrics))

    snapshots = SNAPSHOTS

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
        m.set_gauge("recurrent_state_bytes", nbytes(self.state))
        for name, value in self.family.gauges(self.eng.args, self.state,
                                              self.pools).items():
            m.set_gauge(name, value)

    # -- pages ----------------------------------------------------------------
    def copy_page(self, src, dst):
        self.pools = self._copy(self.pools, jnp.int32(src), jnp.int32(dst))

    def check_handoff(self):
        raise ValueError(
            "disaggregated workers do not serve a hybrid model: a "
            "`KVHandoff` ships pages, and the linear layers' recurrent "
            "state is in none of them")

    # -- per-slot state -----------------------------------------------------------
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
        made): behind the rows' tokens, a routed family's counts."""
        if len(out) > self.eng.max_slots:
            self.riders.landed(out[self.eng.max_slots:])

    # -- the token vector and the two step programs -------------------------------
    def seat(self, slot, token):
        self.tokens = self._seat(self.tokens, jnp.int32(slot),
                                 jnp.asarray(token, jnp.int32))

    def prefill(self, ids, start, last_idx, bt_row, new_vec, slot, req,
                sample):
        self.pools, self.state, first, picks = self._prefill[sample](
            self.eng.params, self.layer_ids, jnp.asarray(ids),
            jnp.int32(start),
            jnp.int32(last_idx), jnp.asarray(bt_row), jnp.asarray(new_vec),
            jnp.int32(slot), self.pools, self.state, self.tables,
            jnp.float32(req.temperature),
            jnp.float32(req.top_p), jnp.int32(req.top_k),
            jnp.asarray([req.seed], jnp.int32))
        rows = np.shape(ids)[-1]
        self.riders.ran(rows)
        for name, value in self.family.observe_prefill(
                self.eng.args, self.eng, rows).items():
            self.eng.metrics.observe(name, value)
        self.riders.window(req, slot, start, last_idx + 1, picks)
        return first

    def decode(self, bt, active, sample, sampling_args):
        eng = self.eng
        live = np.zeros(eng.max_slots, bool)
        live[active] = True
        for name, value in self.family.observe_decode(
                eng.args, eng, active).items():
            eng.metrics.observe(name, value)
        # a COPY of the positions: the engine moves them on as soon as this
        # returns, and a host array handed to the device may be read later
        pos = eng._npos.copy()
        self.pools, self.state, self.tokens, picks = self._decode[sample](
            eng.params, self.layer_ids, self.tokens, jnp.asarray(bt),
            jnp.asarray(pos), jnp.asarray(live), self.pools,
            self.state, self.tables, *sampling_args)
        self.riders.ran(eng.max_slots)
        self.riders.step(picks, live, pos)
        return self.tokens
