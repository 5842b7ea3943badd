"""The device half of `PagedEngine` for a stack with latent attention and
routed experts (`models/latent_moe_functional.LatentMoEArgs`).

  - ONE page pool, `pool` `[layers * num_pages, page_size, row_width]` in
    the model dtype: a token keeps one row a layer, `[c_kv; k_pe]` (no K
    and V, no KV heads), padded to whole lane tiles. Layer l's pages are the
    run that starts at `l * num_pages` under the allocator's page ids, and
    the pool rides both step programs' layer scans as their carry, written
    and read where it lies; a copy-on-write copies one page of every layer;
  - a request is its pages and nothing else, so the per-request-state half
    of `serving/paths.py`'s interface is the dense path's no-ops;
  - one prefill program a window bucket and one decode program serve every
    context length (block tables, positions and page vectors are traced);
  - once a decode step the program also makes four counts summed over the
    expert layers (tokens at the busiest held expert, picks that landed on
    a held expert, picks in all, held experts with a token). They ride the
    step's existing read-back: the program appends them to the rows' next
    tokens (`[slots + 4]`; the engine reads a slot's row and never the
    tail), so they reach the host in the one transfer a step already
    makes, and the engine hands the path that host copy when it has read it
    (`landed`): no wait and no transfer is added. They become the
    observations `serve.expert_load_max_over_mean`,
    `serve.routed_here_share` and `serve.held_experts_hit` (a layer);
    beside them `serve.expert_fused_share`, once a step program of either
    kind: 1.0 where its expert layers are the fused pass over the hit
    experts' weights, 0.0 where they are grouped matmuls (the decode steps
    and the windows of a deployment: the mean is the decode steps' share of
    the step programs). The
    same `[slots + 4]` vector is the NEXT decode step's token operand, as it
    lies on the device (the program reads its first `slots` rows): the
    path's token vector has that length;

  - where the description asks for it (`args.record_routing`: an operator
    or a judge of the served tokens does, a deployment does not), both step
    programs also return the experts every token picked, and a request
    carries a ROUTING TRACE, `req.routing` (`RoutingTrace`). Routing is
    discrete: a token whose last pick and first miss score alike can go
    either way on rounding, and what the model then computes differs by a
    whole expert; the benchmark's reference so follows the picks the
    program made, after checking each against its own scores. A prefill
    window's picks hang on its request; a decode step's are ONE entry of
    the path's step log for all its rows (the device array as it is, the
    rows that were live, their positions), and a trace finds its rows
    there when it is read: nothing waits for them and no step does work a
    row. The log lives until `reset`: 9 KB a step at 64 rows.

  - behind a TOKEN SELECTOR (`args.indexer`: a learned indexer picks the
    `index_topk` keys a query attends) the cache is TWO pools under the one
    block table and the allocator's one page id: the latent pool and an
    INDEX pool `[layers * num_pages, page_size, index width]` that holds a
    token's index key, 256 B at the published width where the latent row is
    1,280: the selector scores a row's whole context and must not read the
    latent rows to do it. `self.pool` is then the pair, carried, donated,
    written (`pt.kv_write`) and copied on write as one; a prefix hit,
    preempt / resume and `reset` see pages, and a page is a page of both.
    A decode step's counts are six: behind the four, the keys its live rows
    selected and the keys they could see (`serve.selected_keys`,
    `serve.visible_keys`). Where the description asks (`record_selection`),
    a request's trace also holds the selections of a SAMPLE of its queries,
    every layer, as packed bits (a bit a table position: 9 KB a query a
    layer): `lm.SELECT_ROWS` consecutive queries of each prefill window from
    a row drawn from a seed of the window, and one live row of every
    `SELECT_EVERY`-th decode step (the rows in turn), for whoever judges
    the selection itself (`RoutingTrace.selections`);

Refused at construction, with the reason: a mesh, an int8 pool, a draft
model; `check_handoff` refuses the disaggregated workers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.models import latent_moe_functional as lm
from paddle_tpu.serving.sampler import pick as _pick, seat_token, token_vector

__all__ = ["LatentPath", "RoutingTrace", "RoutingRiders"]

SELECT_EVERY = 8     # decode steps between two kept selections


class RoutingTrace:
    """The experts one request's tokens picked: its prefill windows' picks
    `(first position, count, picks [expert layers, rows, experts a
    token])`, and where its decode rows lie in the path's step log:
    `[slot, first step, end step]` for each stay in a slot (one, unless it
    was preempted)."""

    def __init__(self, log):
        self._log, self._windows, self._stays = log, [], []
        # (a window's first position, its count, the first row kept, the
        # kept rows' selections as packed bits [layers, rows, positions / 8])
        self._selected = []

    def window(self, position, count, picks, row=None, selected=None):
        self._windows.append((int(position), int(count), picks))
        if selected is not None:
            self._selected.append((int(position), int(count), int(row),
                                   selected))

    def seat(self, slot):
        """Its decode rows are row `slot` of the steps logged from now on
        (a seat no step was logged under, a window before the last, goes)."""
        if self._stays and self._stays[-1][2] is None:
            self._stays.pop()
        self._stays.append([int(slot), len(self._log), None])

    def leave(self):
        """Preempted: its stay ends with the last step logged."""
        self._stays[-1][2] = len(self._log)

    def _decode_rows(self, positions):
        """(position, the step's log entry, its slot) for the request's
        decode rows below `positions`: one position after the other from its
        last window's end on, in every logged step of its stays that ran
        its row."""
        at = self._windows[-1][0] + self._windows[-1][1]
        for slot, first, end in self._stays:
            for entry in self._log[first:end]:
                live, pos = entry[1], entry[2]
                if at >= positions or (live[slot] and pos[slot] != at):
                    break           # read to the end, or the slot's next owner
                if live[slot]:
                    yield at, entry, slot
                    at += 1

    def table(self, positions):
        """int32 [positions, expert layers, experts a token]: the picks of
        the request's tokens 0 .. positions - 1 (of all the published
        experts); -1 where none was recorded (a position served from the
        prefix cache: its window never ran for this request)."""
        layers, _, k = self._windows[0][2].shape
        out = np.full((positions, layers, k), -1, np.int32)
        for first, count, picks in self._windows:
            count = min(count, positions - first)
            if count > 0:
                out[first:first + count] = np.swapaxes(
                    np.asarray(picks)[:, :count], 0, 1)
        for at, entry, slot in self._decode_rows(positions):
            if not isinstance(entry[0], np.ndarray):
                entry[0] = np.asarray(entry[0])
            out[at] = entry[0][:, slot]
        return out

    def selections(self, positions):
        """[(position, [layers] int arrays)]: the positions that the sampled
        queries below `positions` selected in each layer, lowest first."""
        def unpacked(bits):
            return np.unpackbits(np.asarray(bits), axis=-1,
                                 bitorder="little")

        out = []
        for first, count, row, kept in self._selected:
            kept = unpacked(kept)                   # [layers, rows, T]
            for j in range(min(kept.shape[1], count - row)):
                if first + row + j < positions:
                    out.append((first + row + j, [
                        np.nonzero(layer[j])[0] for layer in kept]))
        for at, entry, slot in self._decode_rows(positions):
            if entry[3] is not None and entry[4] == slot:
                out.append((at, [np.nonzero(layer)[0]
                                 for layer in unpacked(entry[3])]))
        return out


class RoutingRiders:
    """The host's half of what an expert stack's step programs return beside
    their tokens, for whichever path runs them (`LatentPath` here,
    `serving/hybrid.HybridPath` for a family with a state tree as well): the
    step log every request's `RoutingTrace` reads, a request's trace made
    and seated at its prefill windows and moved with it through preempt and
    resume, and the observations made of the counts that ride a decode
    step's read-back."""

    def __init__(self, eng):
        self.eng = eng
        self._fused = {}      # a program's rows -> 1.0 / 0.0 (`ran`)
        self.reset()

    def reset(self):
        # a decode step's [picks, live rows, positions, the selection kept
        # or None, its slot]; a trace made before keeps the log it was
        # made over
        self.log = []

    def window(self, req, slot, start, count, picks, row=None,
               selected=None):
        """A prefill window of `req` in `slot` ran: its picks (and kept
        selection) hang on the request, whose decode rows are the slot's
        from the next logged step on."""
        if picks is None and selected is None:
            return
        if getattr(req, "routing", None) is None:
            req.routing = RoutingTrace(self.log)
        req.routing.window(start, count, picks, row, selected)
        req.routing.seat(slot)

    def step(self, picks, live, pos, selected=None, row=None):
        """A decode step went out: one entry for all its rows."""
        if picks is not None or selected is not None:
            self.log.append([picks, live, pos, selected, row])

    def ran(self, rows):
        """A step program of `rows` rows went out: whether its expert layers
        are the fused pass over the hit experts' weights (1.0) or grouped
        matmuls (0.0), by the rule the program was traced under
        (`lm.experts_fused`); a family without experts records nothing."""
        if not hasattr(self.eng.args, "experts_held"):
            return
        if rows not in self._fused:
            dtype = jax.tree_util.tree_leaves(
                self.eng.params["embedding"])[0].dtype
            self._fused[rows] = float(
                lm.experts_fused(rows, self.eng.args, dtype))
        self.eng.metrics.observe("serve.expert_fused_share",
                                 self._fused[rows])

    def _trace(self, slot):
        return getattr(self.eng.slots.owner(slot), "routing", None)

    def leave(self, slot):
        """The slot's request is preempted: its stay ends here."""
        if (trace := self._trace(slot)) is not None:
            trace.leave()

    def seat(self, slot):
        """A preempted request resumes in `slot`."""
        if (trace := self._trace(slot)) is not None:
            trace.seat(slot)

    def landed(self, counts):
        """The counts behind a decode step's tokens, read: tokens at the
        busiest held expert, picks on held experts, picks in all, held
        experts with a token (summed over the expert layers) and, behind a
        selector, the keys selected and visible."""
        busiest, here, picks, hit, *keys = (int(x) for x in counts)
        m, args = self.eng.metrics, self.eng.args
        if keys:
            m.observe("serve.selected_keys", keys[0])
            m.observe("serve.visible_keys", keys[1])
        if picks:
            m.observe("serve.routed_here_share", here / picks)
            m.observe("serve.held_experts_hit",
                      hit / (args.num_layers - args.first_k_dense))
        if here:
            m.observe("serve.expert_load_max_over_mean",
                      busiest * args.experts_held / here)


def _prefill_traced(params, ids, h, last_idx, bt_row, new_pages, pool, cos,
                    sin, temp, top_p, top_k, seeds, record, *, args, metrics,
                    sample=False):
    metrics.inc("prefill_compiles")
    logits, pool, picks, *selected = lm.prefill_window(
        params, ids[0], h, last_idx, bt_row, new_pages, pool, cos, sin, args,
        record)
    first = _pick(logits[None], sample, temp, top_p, top_k, seeds,
                  h + last_idx + 1)[0]
    return (pool, first, picks if args.record_routing else None,
            selected[0] if selected else None)


def _decode_traced(params, tokens, bt, pos, live, pool, cos, sin, temp,
                   top_p, top_k, seeds, record, *, args, metrics,
                   sample=False):
    metrics.inc("decode_compiles")
    # the token operand is the step before's whole output: the rows' tokens
    # and, behind them, its counts
    logits, pool, counts, picks, *selected = lm.decode_step(
        params, tokens[:pos.shape[0]], bt, pos, live, pool, cos, sin, args,
        record)
    nxt = _pick(logits, sample, temp, top_p, top_k, seeds, pos + 1)
    return (pool, jnp.concatenate([nxt, counts.astype(nxt.dtype)]),
            picks if args.record_routing else None,
            selected[0] if selected else None)


@jax.named_scope("pt.kv_write")
def _copy_page_traced(pool, src, dst, *, layers):
    """Copy-on-write: page `src` onto page `dst` in every layer's run of
    every pool."""
    def one(pool):
        num_pages = pool.shape[0] // layers
        view = pool.reshape((layers, num_pages) + pool.shape[1:])
        view = jax.lax.dynamic_update_slice_in_dim(
            view, jax.lax.dynamic_slice_in_dim(view, src, 1, axis=1), dst,
            axis=1)
        return view.reshape(pool.shape)

    return jax.tree.map(one, pool)


class LatentPath:
    """The pool, the rotary tables and the step programs of one engine."""

    snapshots = 0      # a request keeps nothing beside its pages
    _log = property(lambda self: self.riders.log)   # the decode steps' log

    def __init__(self, eng):
        args, self.eng = eng.args, eng
        for given, what, why in (
                (eng.mesh, "mesh=", "the experts held and the latent pool "
                 "have no tensor-parallel placement yet"),
                (eng.kv_dtype, "kv_dtype='int8'", "the latent rows are "
                 "normed activations that every head reads; no int8 "
                 "latent pool exists yet"),
                (eng.draft_params, "draft_params=", "no verify program "
                 "over the latent pool exists yet")):
            if given is not None:
                raise ValueError(f"{what} is not supported for a latent-"
                                 f"attention expert model: {why}")
        args.validate()
        dtype = jax.tree_util.tree_leaves(eng.params["embedding"])[0].dtype
        self.pool = jnp.zeros((args.num_layers * eng.num_pages,
                               eng.page_size, args.row_width), dtype)
        self.counts = 4
        if args.indexer:
            self.pool = (self.pool, jnp.zeros(
                (args.num_layers * eng.num_pages, eng.page_size,
                 args.indexer.dim), dtype))
            self.counts = 6
        # 2 * max_len: a window's padding may pass max_len before it is cut
        self.cos, self.sin = lm.rope_tables(2 * eng.max_len, args)
        # the rows' last tokens, with room for the four counts behind them:
        # a decode step's output is the next one's operand as it is, a
        # prompt's first token is seated (`seat`)
        self.tokens = token_vector(eng.max_slots + self.counts, eng.pad_id)
        self.riders = RoutingRiders(eng)
        self.reset()

        donate = eng._donate_enabled()
        kw = dict(args=args, metrics=eng.metrics)
        self._prefill, self._decode = {}, {}
        for sample in (False, True):
            self._prefill[sample] = jax.jit(
                functools.partial(_prefill_traced, sample=sample, **kw),
                donate_argnums=(6,) if donate else ())
            self._decode[sample] = jax.jit(
                functools.partial(_decode_traced, sample=sample, **kw),
                donate_argnums=(5,) if donate else ())
        self._copy = jax.jit(
            functools.partial(_copy_page_traced, layers=args.num_layers),
            donate_argnums=(0,) if donate else ())
        # never donates: the vector it is given may be a step's output that
        # the host has not read yet
        self._seat = jax.jit(functools.partial(seat_token,
                                               metrics=eng.metrics))

    def reset(self):
        """An empty engine: the pools stay (and their byte gauges)."""
        self.riders.reset()
        self._steps = 0
        pools = jax.tree_util.tree_leaves(self.pool)
        self.eng.metrics.set_gauge(
            "kv_pool_bytes", sum(p.size * p.dtype.itemsize for p in pools))
        if len(pools) > 1:
            self.eng.metrics.set_gauge(
                "index_pool_bytes", pools[1].size * pools[1].dtype.itemsize)

    # -- pages ----------------------------------------------------------------
    def copy_page(self, src, dst):
        self.pool = self._copy(self.pool, jnp.int32(src), jnp.int32(dst))

    def check_handoff(self):
        raise ValueError(
            "disaggregated workers do not serve a latent-attention expert "
            "model yet: a `KVHandoff` ships a K and a V pool, and this "
            "family has one pool of latent rows")

    # -- per-request state beside the pages: none -----------------------------
    def prompt_done(self, slot):
        pass

    def load_snapshot(self, slot, sid):
        pass

    def attach(self, slot, prompt_ids, registered):
        pass

    def take_state(self, slot):
        self.riders.leave(slot)
        return None

    def put_state(self, slot, saved):
        self.riders.seat(slot)

    # -- the token vector and the two step programs -----------------------------
    def seat(self, slot, token):
        self.tokens = self._seat(self.tokens, jnp.int32(slot),
                                 jnp.asarray(token, jnp.int32))

    def prefill(self, ids, start, last_idx, bt_row, new_vec, slot, req,
                sample):
        # the first of the window's queries whose selection is kept: a
        # draw seeded by the window
        row = None
        if self.eng.args.record_selection:
            row = np.int32(np.random.default_rng(
                [len(req.prompt_ids), start]).integers(
                    0, max(1, last_idx + 2 - lm.SELECT_ROWS)))
        self.pool, first, picks, selected = self._prefill[sample](
            self.eng.params, jnp.asarray(ids), jnp.int32(start),
            jnp.int32(last_idx), jnp.asarray(bt_row), jnp.asarray(new_vec),
            self.pool, self.cos, self.sin, jnp.float32(req.temperature),
            jnp.float32(req.top_p), jnp.int32(req.top_k),
            jnp.asarray([req.seed], jnp.int32), row)
        self.riders.ran(np.shape(ids)[-1])
        self.riders.window(req, slot, start, last_idx + 1, picks, row,
                           selected)
        return first

    def landed(self, out):
        """A decode step's output was read (`out`, the host copy the engine
        made): the routing's four counts behind the rows' tokens and, behind
        a selector, the keys selected and visible."""
        self.riders.landed(out[self.eng.max_slots:])

    def decode(self, bt, active, sample, sampling_args):
        eng = self.eng
        live = np.zeros(eng.max_slots, bool)
        live[active] = True
        # a COPY of the positions: the engine moves them on as soon as this
        # returns, and a host array handed to the device may be read later
        pos = eng._npos.copy()
        # the row whose selection this step returns: the live rows in turn,
        # kept every SELECT_EVERY-th step
        row, keep = None, 0
        if eng.args.record_selection:
            turn, keep = divmod(self._steps, SELECT_EVERY)
            row = np.int32(active[turn % len(active)])
            self._steps += 1
        self.pool, self.tokens, picks, selected = self._decode[sample](
            eng.params, self.tokens, bt, pos, live, self.pool,
            self.cos, self.sin, *sampling_args, row)
        self.riders.ran(eng.max_slots)
        self.riders.step(picks, live, pos, None if keep else selected, row)
        return self.tokens
