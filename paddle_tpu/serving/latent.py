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
    `serve.routed_here_share` and `serve.held_experts_hit` (a layer). The
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

__all__ = ["LatentPath", "RoutingTrace"]


class RoutingTrace:
    """The experts one request's tokens picked: its prefill windows' picks
    `(first position, count, picks [expert layers, rows, experts a
    token])`, and where its decode rows lie in the path's step log:
    `[slot, first step, end step]` for each stay in a slot (one, unless it
    was preempted)."""

    def __init__(self, log):
        self._log, self._windows, self._stays = log, [], []

    def window(self, position, count, picks):
        self._windows.append((int(position), int(count), picks))

    def seat(self, slot):
        """Its decode rows are row `slot` of the steps logged from now on
        (a seat no step was logged under, a window before the last, goes)."""
        if self._stays and self._stays[-1][2] is None:
            self._stays.pop()
        self._stays.append([int(slot), len(self._log), None])

    def leave(self):
        """Preempted: its stay ends with the last step logged."""
        self._stays[-1][2] = len(self._log)

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
        # its decode rows: one position after the other from its last
        # window's end on, in every logged step of its stays that ran its row
        at = self._windows[-1][0] + self._windows[-1][1]
        for slot, first, end in self._stays:
            for entry in self._log[first:end]:
                picks, live, pos = entry
                if at >= positions or (live[slot] and pos[slot] != at):
                    break           # read to the end, or the slot's next owner
                if live[slot]:
                    if not isinstance(picks, np.ndarray):
                        entry[0] = picks = np.asarray(picks)
                    out[at] = picks[:, slot]
                    at += 1
        return out


def _prefill_traced(params, ids, h, last_idx, bt_row, new_pages, pool, cos,
                    sin, temp, top_p, top_k, seeds, *, args, metrics,
                    sample=False):
    metrics.inc("prefill_compiles")
    logits, pool, picks = lm.prefill_window(
        params, ids[0], h, last_idx, bt_row, new_pages, pool, cos, sin, args)
    first = _pick(logits[None], sample, temp, top_p, top_k, seeds,
                  h + last_idx + 1)[0]
    return pool, first, picks if args.record_routing else None


def _decode_traced(params, tokens, bt, pos, live, pool, cos, sin, temp,
                   top_p, top_k, seeds, *, args, metrics, sample=False):
    metrics.inc("decode_compiles")
    # the token operand is the step before's whole output: the rows' tokens
    # and, behind them, its counts
    logits, pool, counts, picks = lm.decode_step(
        params, tokens[:pos.shape[0]], bt, pos, live, pool, cos, sin, args)
    nxt = _pick(logits, sample, temp, top_p, top_k, seeds, pos + 1)
    return (pool, jnp.concatenate([nxt, counts.astype(nxt.dtype)]),
            picks if args.record_routing else None)


@jax.named_scope("pt.kv_write")
def _copy_page_traced(pool, src, dst, *, layers):
    """Copy-on-write: page `src` onto page `dst` in every layer's run."""
    num_pages = pool.shape[0] // layers
    view = pool.reshape((layers, num_pages) + pool.shape[1:])
    view = jax.lax.dynamic_update_slice_in_dim(
        view, jax.lax.dynamic_slice_in_dim(view, src, 1, axis=1), dst, axis=1)
    return view.reshape(pool.shape)


class LatentPath:
    """The pool, the rotary tables and the step programs of one engine."""

    snapshots = 0      # a request keeps nothing beside its pages

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
        # 2 * max_len: a window's padding may pass max_len before it is cut
        self.cos, self.sin = lm.rope_tables(2 * eng.max_len, args)
        # the rows' last tokens, with room for the four counts behind them:
        # a decode step's output is the next one's operand as it is, a
        # prompt's first token is seated (`seat`)
        self.tokens = token_vector(eng.max_slots + 4, eng.pad_id)
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
        """An empty engine: the pool stays (and its byte gauge with it)."""
        self._log = []      # a decode step's [picks, live rows, positions]
        self.eng.metrics.set_gauge(
            "kv_pool_bytes", self.pool.size * self.pool.dtype.itemsize)

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
        trace = getattr(self.eng.slots.owner(slot), "routing", None)
        if trace is not None:
            trace.leave()
        return None

    def put_state(self, slot, saved):
        trace = getattr(self.eng.slots.owner(slot), "routing", None)
        if trace is not None:
            trace.seat(slot)

    # -- the token vector and the two step programs -----------------------------
    def seat(self, slot, token):
        self.tokens = self._seat(self.tokens, jnp.int32(slot),
                                 jnp.asarray(token, jnp.int32))

    def prefill(self, ids, start, last_idx, bt_row, new_vec, slot, req,
                sample):
        self.pool, first, picks = self._prefill[sample](
            self.eng.params, jnp.asarray(ids), jnp.int32(start),
            jnp.int32(last_idx), jnp.asarray(bt_row), jnp.asarray(new_vec),
            self.pool, self.cos, self.sin, jnp.float32(req.temperature),
            jnp.float32(req.top_p), jnp.int32(req.top_k),
            jnp.asarray([req.seed], jnp.int32))
        if picks is not None:
            if getattr(req, "routing", None) is None:
                req.routing = RoutingTrace(self._log)
            req.routing.window(start, last_idx + 1, picks)
            req.routing.seat(slot)
        return first

    def landed(self, out):
        """A decode step's output was read (`out`, the host copy the engine
        made): the four routing counts behind the rows' tokens."""
        busiest, here, picks, hit = (
            int(x) for x in out[self.eng.max_slots:])
        m, args = self.eng.metrics, self.eng.args
        if picks:
            m.observe("serve.routed_here_share", here / picks)
            m.observe("serve.held_experts_hit",
                      hit / (args.num_layers - args.first_k_dense))
        if here:
            m.observe("serve.expert_load_max_over_mean",
                      busiest * args.experts_held / here)

    def decode(self, bt, active, sample, sampling_args):
        eng = self.eng
        live = np.zeros(eng.max_slots, bool)
        live[active] = True
        # a COPY of the positions: the engine moves them on as soon as this
        # returns, and a host array handed to the device may be read later
        pos = eng._npos.copy()
        self.pool, self.tokens, picks = self._decode[sample](
            eng.params, self.tokens, bt, pos, live, self.pool,
            self.cos, self.sin, *sampling_args)
        if picks is not None:
            self._log.append([picks, live, pos])
        return self.tokens
