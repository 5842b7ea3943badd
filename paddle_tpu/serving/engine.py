"""Continuous-batching LLM serving engine with a slot-based KV cache.

The offline `models/generation.generate` path decodes a FIXED batch: one
straggler holds every row, finished rows burn decode FLOPs emitting pads,
and new requests wait for the whole batch to drain. This engine applies
iteration-level scheduling (Orca, OSDI'22) over the slot/block-managed
cache idea (vLLM's PagedAttention, SOSP'23), assembled from the PR-1
decode machinery:

  - ONE fixed KV cache `[L, S, nkv, max_len, hd]` (heads-major, the
    layout the Pallas decode-attention kernel consumes) where the batch
    axis is S SLOTS, each owned by at most one in-flight request;
  - every `step()` either PREFILLS the next queued request into a free
    slot (prompt right-padded to a power-of-two length bucket —
    compilation stays bounded at #buckets prefill programs; the
    next-token logits are gathered at the request's true last token) or
    runs ONE batched decode step across all S slots with a PER-ROW
    position vector (`models/generation.decode_step`'s pos-vector form:
    per-row RoPE, per-row cache writes, per-row valid-prefix masking in
    both the jnp fallback and the Pallas decode kernel);
  - rows that emit their EOS (or hit max_new_tokens) RETIRE immediately:
    the slot returns to the table and the next waiting request is
    admitted on a later step — no drain barrier. Slot caches are never
    cleared: a prefill rewrites the whole slot, and decode's
    write-before-attend order means stale tail positions are always
    overwritten before the position mask ever exposes them.

Greedy decoding by default (the scheduler retires rows on exact token
identity, so continuous-batched output is token-for-token identical to
sequential `generate` — tested); per-request sampling
(temperature/top-p/top-k + per-request seeds, `Request(...)`) rides the
same decode program as traced per-row vectors — greedy rows stay
bit-exact argmax inside a mixed batch, and greedy-only traffic never
compiles the sampling ops. Weight-only int8 trees from
`generation.quantize_params` serve unchanged: every matmul inside the
traced step streams through the fused dequant-matmul dispatch.

Host/device split: the scheduler (queue, slot table, retire/admit,
streaming callbacks, wall-clock metrics) runs in Python between steps;
the two traced programs (per-bucket prefill, one decode) contain no
wall-clock reads and re-compile only when a NEW bucket shape arrives —
compile counts are metered at trace time (`serving/metrics.py`).

The step loop (`Engine._step_action`) keeps ONE PROGRAM IN FLIGHT: a
`step()` schedules, builds and dispatches the next program from the
state that is known at dispatch, then reads the program that was in
flight, emits its tokens and returns its event, so the host's work hides
behind the device's. An engine whose device half reads its own output
(this stripe engine: its tokens are fed from the host mirror `_last_tok`)
makes records that are complete at once and runs the same loop at depth
0; the paged engine's tokens stay on the device and it runs at depth 1.

`serving/paged_engine.PagedEngine` subclasses this scheduler loop but
swaps the per-slot stripes for a paged KV cache (page pool + block
tables + hash-based prefix reuse) — far more concurrent requests per
byte of KV HBM; the stripe engine remains the simple baseline and the
equal-HBM comparison leg in `bench.py --serving`.
"""

from __future__ import annotations

import functools
import itertools
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.models import generation as gen
from paddle_tpu.observability.spans import recording, span
from paddle_tpu.serving.metrics import Metrics
from paddle_tpu.serving.sampler import SlotSampler, pick as _pick
from paddle_tpu.serving.scheduler import AdmissionQueue, SlotTable, bucket_for

__all__ = ["Request", "Engine", "PHASES", "StepPhases"]

_req_ids = itertools.count()


class Request:
    """One generation request.

    stream_cb(request, token_id, finished) fires once per generated token,
    in emission order, from the host scheduler (never inside traced code).
    After completion: `token_ids` (generated tokens, incl. the EOS if one
    was emitted), `finish_reason` ('eos' | 'length'), `ttft_s` (first
    EMITTED token), `prefill_done_s` (prompt fully in the KV cache —
    under chunked prefill the two diverge, see Engine._record_prefill_done),
    `admit_time` (when it was handed a slot: `admit_time - submit_time` is
    the time it waited in the queue, observed once as `queue_wait_s`).

    Sampling: temperature 0 (default) is exactly greedy; temperature > 0
    samples with optional nucleus top_p and top-k cutoffs. `seed` fixes
    the request's own PRNG stream — the sampled tokens depend only on
    (seed, position), not on which other requests share its batch steps
    (default: the request id, so trace replays are deterministic). All
    four are PER-REQUEST and traced: a mixed greedy/sampling batch runs
    one program, greedy rows staying bit-exact argmax.
    """

    def __init__(self, prompt_ids, max_new_tokens=32, eos_token_id=None,
                 stream_cb=None, request_id=None, temperature=0.0,
                 top_p=1.0, top_k=0, seed=None):
        self.prompt_ids = np.asarray(prompt_ids, np.int32).reshape(-1)
        if self.prompt_ids.size == 0:
            raise ValueError("empty prompt")
        self.max_new_tokens = int(max_new_tokens)
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        self.eos_token_id = (None if eos_token_id is None
                             else int(eos_token_id))
        self.stream_cb = stream_cb
        self.request_id = (next(_req_ids) if request_id is None
                           else request_id)
        self.temperature = float(temperature)
        if self.temperature < 0:
            raise ValueError("temperature must be >= 0")
        self.top_p = float(top_p)
        self.top_k = int(top_k)
        if seed is None:
            try:
                seed = int(self.request_id)
            except (TypeError, ValueError):
                # stable across processes (hash() of str is randomized
                # per interpreter — it would break deterministic replays)
                import zlib

                seed = zlib.crc32(str(self.request_id).encode())
        # one normalization point: every consumer (engine programs AND a
        # user passing req.seed to generate(seeds=...)) sees the same
        # non-negative int32
        self.seed = int(seed) & 0x7FFFFFFF
        self.token_ids = []
        # tokens of programs handed to the device whose output the host has
        # not read yet (the engine's: what it schedules ahead by)
        self.in_flight = 0
        self.finished = False
        self.finish_reason = None
        self.submit_time = None
        self.submit_step = None
        self.admit_time = None
        self.first_token_time = None
        self.finish_time = None
        self.ttft_s = None
        self.ttft_steps = None
        self.prefill_done_s = None
        self.prefill_done_steps = None

    def output_ids(self):
        """prompt + generated tokens (the sequential-generate row shape,
        minus its trailing pads)."""
        return np.concatenate(
            [self.prompt_ids, np.asarray(self.token_ids, np.int32)])


def _prefill_traced(params, ids, true_len, ck, cv, slot, cos, sin, temp,
                    top_p, top_k, seeds, *, args, metrics, sample=False,
                    counter="prefill_compiles"):
    # runs once per COMPILE (trace time), not per call — see metrics.py
    metrics.inc(counter)
    L = ck.shape[0]
    sck = jnp.zeros((L, 1) + ck.shape[2:], ck.dtype)
    scv = jnp.zeros_like(sck)
    logits, sck, scv = gen._forward_cached(
        params, ids, sck, scv, 0, cos, sin, args, last_idx=true_len - 1)
    first = _pick(logits, sample, temp, top_p, top_k, seeds, true_len)[0]
    with jax.named_scope("pt.kv_write"):
        ck = jax.lax.dynamic_update_slice_in_dim(ck, sck, slot, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, scv, slot, axis=1)
    return ck, cv, first


def _decode_traced(params, tokens, ck, cv, pos, cos, sin, temp, top_p,
                   top_k, seeds, *, args, metrics, sample=False,
                   counter="decode_compiles"):
    metrics.inc(counter)
    logits, ck, cv = gen._forward_cached(
        params, tokens[:, None], ck, cv, pos, cos, sin, args)
    # the sampled token lands at sequence index pos+1 — the same
    # (seed, position) stream the offline `generate(seeds=...)` draws from
    return ck, cv, _pick(logits, sample, temp, top_p, top_k, seeds, pos + 1)


# the four phases every microsecond of a step()'s action belongs to
PHASES = ("schedule", "stage", "wait", "emit")

# a step that took more than STALL_FACTOR x the running mean of its own kind
# and size, once STALL_MIN_STEPS of them were seen, is a stall
STALL_FACTOR = 4.0
STALL_MIN_STEPS = 16

# the stepping thread's own CPU seconds, read as a step starts and again only
# where it stalled: a stalled step that used none of them waited (a test
# replaces it, as `spans._clock`)
_thread_clock = time.thread_time

_log = logging.getLogger("paddle_tpu.serving")


class StepPhases:
    """One step's accumulator, handed to `span` in a registry's place.

    `with phases("stage", kind="decode", part="build", rows=3):` opens the
    span `pt.serve.stage` (its own `TraceAnnotation`, with the step's number
    and the identifiers) and adds its duration to the phase's total; a phase
    entered inside another one takes its time out of the one around it, so
    the four totals tile the step's action however the phases nest or
    repeat. `Engine.step()` observes each total once when the step ends: an
    observation's count is the number of steps.

    A sub-division of a phase is an IDENTIFIER on the entry, never a span
    nested in it: `part="dispatch"` marks a `stage` entry that is the path's
    call (the argument transfers and the jitted program until it returns),
    and those entries' own time is also summed as `dispatch_s`; `kind` (with
    `bucket`, a prefill window's) says what the step is: the last entry's
    that is no page copy, which `Engine.step()` keeps its running means by."""

    def __init__(self, step):
        self.step = step
        self.totals = {f"serve.{p}_s": 0.0 for p in PHASES}   # by observation
        self.dispatch_s = 0.0     # of `serve.stage_s`: the part="dispatch"
        self.kind = self.bucket = None
        # per open phase: [seconds of phases inside it, is a dispatch entry]
        self._inner = []

    def __call__(self, phase, **ids):
        kind = ids.get("kind")
        if kind is not None and (kind != "copy" or self.kind is None):
            self.kind, self.bucket = kind, ids.get("bucket")
        self._inner.append([0.0, ids.get("part") == "dispatch"])
        return span("serve." + phase, self, step=self.step, **ids)

    def observe(self, name, seconds):
        """A phase's span closed after `seconds` (the call `span` makes)."""
        inside, dispatch = self._inner.pop()
        self.totals[name] += seconds - inside
        if dispatch:
            self.dispatch_s += seconds - inside
        if self._inner:
            self._inner[-1][0] += seconds


class _Flight:
    """One unit of work handed to the device whose output the host has not
    read: `out` is what the program returns for the host (a device value),
    `land(host copy of out)` emits its tokens and returns the step's event,
    `rows` are the `(slot, request)` pairs whose tokens `out` holds (none
    for a prefill window that is not a prompt's last) and `ids` the
    identifiers of the program, which its `wait` entry carries.

    A record is COMPLETE where `out` is on the host already (a device half
    that read its own output, a path without a device, a round that emitted
    its tokens itself: `done`): nothing is dispatched behind it, because
    nothing is left to overlap with."""

    __slots__ = ("ids", "out", "land", "rows")

    def __init__(self, ids, out, land, rows=()):
        self.ids, self.out, self.land, self.rows = ids, out, land, rows

    @classmethod
    def done(cls, event):
        """A unit of work that needs no read-back: `event` is its step's."""
        return cls({}, None, lambda _: event)

    @property
    def complete(self):
        return self.out is None or isinstance(
            self.out, (int, np.integer, np.ndarray))


class Engine:
    """Continuous-batching serving engine over a Llama functional param
    tree (float or `quantize_params` int8).

    max_slots: S — concurrent in-flight requests (the decode batch).
    max_len:   per-slot KV capacity; prompt_len + max_new_tokens must stay
               within it. On TPU pick a multiple of 128 so the Pallas
               decode-attention fast path stays eligible.
    min_bucket: smallest prefill length bucket (power-of-two ladder up to
               max_len).
    """

    def __init__(self, params, args, *, max_slots=4, max_len=256,
                 min_bucket=16, pad_id=0, metrics=None, donate_steps=None):
        self.params = params
        self.args = args
        self.max_slots = int(max_slots)
        self.max_len = int(max_len)
        self.min_bucket = int(min_bucket)
        self.pad_id = int(pad_id)
        self.metrics = metrics if metrics is not None else Metrics()
        # donate_steps: None = auto (donate the KV buffers on TPU only);
        # True/False force it. The static donation audit forces True on
        # CPU so the lowered programs it inspects carry the same aliasing
        # the production TPU programs do.
        self._donate_steps = donate_steps

        self.queue = AdmissionQueue(self.metrics)
        self.slots = SlotTable(self.max_slots)
        self._npos = np.zeros(self.max_slots, np.int32)   # next write pos
        self._last_tok = np.full(self.max_slots, self.pad_id, np.int32)
        # per-slot sampling state (greedy defaults; set at admission)
        self.sampler = SlotSampler(self.max_slots)
        self.step_count = 0
        self._stall_steps = 0     # decode work delayed by a prefill step
        self._phase = StepPhases(0)   # the running step's; step() renews it
        self._flight = None       # the program in flight (`_Flight`)
        self._kept = None         # a settled program's event, not returned yet
        self._recorded = False    # a profiler session recorded the last step
        self._forget_steps()
        self._setup_device_state()

    def _donate_enabled(self):
        """Whether step programs donate their threaded-through buffers."""
        if self._donate_steps is not None:
            return bool(self._donate_steps)
        return jax.default_backend() == "tpu"

    def _setup_device_state(self):
        """Allocate the KV cache buffers + compile wrappers (subclass
        hook: the paged engine replaces the per-slot stripes with a page
        pool here)."""
        args = self.args
        self._ck, self._cv, self._cos, self._sin = gen._init_cache(
            self.params, args, self.max_slots, self.max_len)

        # donate the KV cache buffers: the engine threads ck/cv through
        # every step and immediately drops the old arrays, so XLA aliases
        # input to output instead of materializing a fresh cache copy per
        # step (on the TPU bench shape that copy is ~1 GB/step). CPU/other
        # backends don't implement donation — skip it there to avoid a
        # warning per compile (donate_steps=True forces it for audits).
        donate = self._donate_enabled()
        self._prefill = jax.jit(
            functools.partial(_prefill_traced, args=args,
                              metrics=self.metrics),
            donate_argnums=(3, 4) if donate else (),
            static_argnames=("sample",))
        self._decode = jax.jit(
            functools.partial(_decode_traced, args=args,
                              metrics=self.metrics),
            donate_argnums=(2, 3) if donate else (),
            static_argnames=("sample",))

    # -- admission ----------------------------------------------------------
    def submit(self, req):
        """Queue a Request (or raw prompt ids). Returns the Request."""
        if not isinstance(req, Request):
            req = Request(req)
        n = int(req.prompt_ids.size)
        bucket_for(n, self.min_bucket, self.max_len)  # length must fit
        if n + req.max_new_tokens > self.max_len + 1:
            raise ValueError(
                f"request needs {n} prompt + {req.max_new_tokens} new "
                f"tokens but the slot capacity is max_len={self.max_len}")
        req.submit_time = time.perf_counter()
        req.submit_step = self.step_count
        self.queue.push(req)
        self.metrics.inc("requests_submitted")
        return req

    # -- the iteration-level scheduler --------------------------------------
    def step(self):
        """One engine iteration: the event of ONE program (a prefill window:
        admit-and-prefill if a request is waiting and a slot is free, paged
        engines also require page capacity; else one batched decode step
        over all decodable slots; else idle), with its tokens emitted inside
        the call. The loop keeps one program in flight (`_step_action`): the
        call first hands the device the NEXT program, then reads and emits
        the one it returns the event of.
        Returns a small event dict. The step is the span `pt.serve.step`;
        its action is split over the four `PHASES` (`StepPhases`), each
        observed once a step as `serve.<phase>_s`, and beside them
        `serve.stage_dispatch_s`: the part of `stage` inside the path's
        calls. A step far longer than its kind's mean is counted as a stall
        (`_note_step`)."""
        phase = self._phase = StepPhases(self.step_count)
        with span("serve.step", step=self.step_count):
            # whatever `_step_action` does outside a stage, wait or emit
            # phase of its own is scheduling; the thread's CPU clock is read
            # inside the phase, so the read is host work like the rest
            with phase("schedule"):
                cpu0 = _thread_clock()
                ev = self._step_action()
                with phase("emit"):
                    self.step_count += 1
                    self.metrics.observe("slot_occupancy",
                                         self.slots.occupancy())
                    self.metrics.set_gauge("active_slots",
                                           len(self.slots.active_slots))
                    # every `stage` entry has closed: the total is final
                    self.metrics.observe("serve.stage_dispatch_s",
                                         phase.dispatch_s)
        for name, seconds in phase.totals.items():
            self.metrics.observe(name, seconds)
        self._note_step(phase, cpu0)
        return ev

    def _forget_steps(self):
        """No step seen yet (construction and `reset`): the running means a
        stall is measured against are empty, and the stall counters stand at
        0, so a run without a stall reads 0 and not "no such counter"."""
        self._step_means = {}     # (kind, bucket) -> [steps, mean seconds]
        for name in ("steps", "s", "cpu_s") + tuple(p + "_s" for p in PHASES):
            self.metrics.inc("serve.stalled_" + name, 0)
        # the look-ahead's own counters (`_program`, `settle`, `_land`)
        for name in ("dispatched", "dispatched_ahead", "settled",
                     "discarded_rows"):
            self.metrics.inc("serve." + name, 0)

    def _note_step(self, phase, cpu0):
        """Count a stalled step where it happens. A step's seconds are its
        four totals' sum; its class is its kind and, for a prefill window,
        its bucket. Past `STALL_MIN_STEPS` of a class, a step that took more
        than `STALL_FACTOR` x the class's running mean is a stall: counted,
        charged to the phases it sat in, logged once, and kept out of the
        mean; only then is the thread's CPU clock read a second time (`cpu0`
        is its reading as the step started). Read it so: seconds in `wait` with no CPU = the device or the
        runtime was late; seconds in schedule / stage / emit with CPU about
        equal = host work; the same with CPU far below = the thread was off
        the processor."""
        if phase.kind is None:
            return                  # an idle step
        took = sum(phase.totals.values())
        seen = self._step_means.setdefault((phase.kind, phase.bucket),
                                           [0, 0.0])
        n, mean = seen
        if n < STALL_MIN_STEPS or took <= STALL_FACTOR * mean:
            seen[0], seen[1] = n + 1, mean + (took - mean) / (n + 1)
            return
        cpu_s = _thread_clock() - cpu0
        m = self.metrics
        m.inc("serve.stalled_steps")
        m.inc("serve.stalled_s", took - mean)
        for p in PHASES:
            m.inc(f"serve.stalled_{p}_s", phase.totals[f"serve.{p}_s"])
        m.inc("serve.stalled_cpu_s", cpu_s)
        m.set_gauge("serve.last_stall_step", phase.step)
        m.set_gauge("serve.last_stall_s", took)
        _log.warning(
            "serving step %d stalled: %s%s took %.4f s against a mean of "
            "%.4f s over %d steps (%s; thread cpu %.4f s)",
            phase.step, phase.kind,
            "" if phase.bucket is None else f" bucket {phase.bucket}",
            took, mean, n,
            ", ".join(f"{p} {phase.totals[f'serve.{p}_s']:.4f}"
                      for p in PHASES), cpu_s)

    def _step_action(self):
        """One program ahead. Two kinds of state part here: what the
        scheduler needs to choose and build the next program (`_npos`, a
        paged engine's block tables, reservations and chunk streams, whether
        a row ends by length: `Request.in_flight`) advances when a program
        is DISPATCHED; what needs token values (`token_ids`, `stream_cb`,
        EOS, `_last_tok`, retirement) advances when its output is READ, one
        program later. So with program k in flight this call schedules,
        builds and dispatches k + 1, THEN reads k's output, emits its tokens
        and returns its event; with nothing in flight it dispatches k first.
        The device always has the next program queued behind the one the
        host waits for.

        Nothing is dispatched behind a complete record (`_Flight.complete`)
        nor where `_looks_ahead` says no: the same loop at depth 0. A row
        that hit EOS in k has already run in k + 1: that token is never
        emitted (`_emit_decode`), and a program whose rows all retired is
        dropped unread (`_land`). A slot or pages freed by k's tokens are
        seen by the scheduler one call later than the synchronous loop saw
        them."""
        opened, self._recorded = not self._recorded, recording()
        if opened and self._recorded:
            # a profiler session opened since the last call, with the device
            # still in the program dispatched ahead of it: the recording
            # holds that program's end but not its `stage` entry, and the
            # device's operations before the session's host clock started.
            # Settled, the session's first whole program is one it saw
            # dispatched, after an instant of idle it can attribute
            self.settle()
        if self._kept is not None:
            # `settle` read and emitted what was in flight between two
            # calls: that event is this call's, and the next program goes out
            ev, self._kept = self._kept, None
            self._flight = self._next_program()
            return ev
        flight = self._flight
        if flight is None:
            flight = self._flight = self._next_program()
            if flight is None:
                return {"type": "idle"}
        self._flight = self._next_program() \
            if self._looks_ahead(flight) else None
        return self._land(flight)

    def _looks_ahead(self, flight):
        """Whether the next program may be chosen and dispatched before
        `flight`'s output is read (subclass hook)."""
        return not flight.complete

    def _program(self, ids, out, land, rows=()):
        """The record of a program just handed to the device (`_Flight`);
        the rows' tokens are in flight from here on."""
        self.metrics.inc("serve.dispatched")
        if self._flight is not None:
            self.metrics.inc("serve.dispatched_ahead")
        for _, req in rows:
            req.in_flight += 1
        return _Flight(ids, out, land, rows)

    _done = staticmethod(_Flight.done)

    def _land(self, flight):
        """The emission point: read `flight`'s output (the one host read of
        a device value a step makes), emit its tokens, return its event."""
        out = flight.out
        if not flight.complete:
            with self._phase("wait", **flight.ids):
                out = np.asarray(out)
        for _, req in flight.rows:
            req.in_flight -= 1
        ev = flight.land(out)
        ahead = self._flight
        if ahead is not None and ahead.rows and \
                all(req.finished for _, req in ahead.rows):
            # every row of the program dispatched ahead retired with these
            # tokens (EOS, found a program late): nothing of it is emitted
            for _, req in ahead.rows:
                req.in_flight -= 1
            self.metrics.inc("serve.discarded_rows", len(ahead.rows))
            self._flight = None
        return ev

    def settle(self):
        """Read and emit what is in flight NOW, for a caller that needs the
        emitted state to be the scheduled state (`preempt` / `resume`, a
        hand-off, `reset`): the tokens are emitted here, the event is kept
        for the next `step()` to return. Not to be called from inside
        `_next_program`."""
        flight, self._flight = self._flight, None
        if flight is not None:
            self.metrics.inc("serve.settled")
            self._kept = self._land(flight)

    def _next_program(self):
        """Pick, build and dispatch the next unit of work from the scheduled
        state; returns its `_Flight`, or None where there is none (subclass
        hook: the paged engine interleaves chunked-prefill streams and swaps
        decode for speculate-and-verify here)."""
        if self._can_prefill():
            self._note_prefill_stall()
            return self._prefill_step()
        active = self._decodable_slots()
        if active:
            return self._decode_step(active)
        return None

    def _note_prefill_stall(self):
        """Account one prefill-shaped step taken while decodable slots
        sat waiting — the `prefill_stall_steps` gauge chunked prefill
        exists to flatten (a monolithic long prefill stalls every
        decoding slot for its whole wall time; a chunk stalls them for
        one bounded chunk)."""
        if self._decodable_slots():
            self._stall_steps += 1
            self.metrics.set_gauge("prefill_stall_steps", self._stall_steps)

    def _decodable_slots(self):
        """Slots eligible for a batched decode step: those whose request
        wants a token beyond the ones in flight (a row that ends BY LENGTH
        with a token still on the device is not in the next program).
        Subclass hook: the paged engine excludes slots whose prompt is
        still mid-chunked-prefill."""
        wanting = []
        for slot in self.slots.active_slots:
            req = self.slots.owner(slot)
            if len(req.token_ids) + req.in_flight < req.max_new_tokens:
                wanting.append(slot)
        return wanting

    def _can_prefill(self):
        """True when the next queued request can be admitted this step
        (subclass hook: the paged engine also checks page-pool capacity
        for the queue head)."""
        return bool(self.queue and self.slots.free_count)

    def run_until_idle(self):
        """Drive step() until every queued/active request completes."""
        while self.queue or self.slots.active_slots:
            self.step()

    def serve(self, requests):
        """Convenience: submit all, run to completion, return them."""
        reqs = [self.submit(r) for r in requests]
        self.run_until_idle()
        return reqs

    def replay(self, trace):
        """Replay an arrival trace (tools/serving_trace.py): each entry
        {'arrival_step', 'prompt', 'max_new_tokens'[, 'eos_token_id']} is
        submitted once the engine reaches its arrival step; idle steps
        advance virtual time between sparse arrivals. Returns Requests in
        trace order."""
        pending = sorted(trace, key=lambda t: t["arrival_step"])
        out = {}
        i = 0
        while i < len(pending) or self.queue or self.slots.active_slots:
            while (i < len(pending)
                   and pending[i]["arrival_step"] <= self.step_count):
                t = pending[i]
                req = Request(t["prompt"], t["max_new_tokens"],
                              eos_token_id=t.get("eos_token_id"),
                              request_id=t.get("request_id"),
                              temperature=t.get("temperature", 0.0),
                              top_p=t.get("top_p", 1.0),
                              top_k=t.get("top_k", 0),
                              seed=t.get("seed"))
                out[id(t)] = self.submit(req)
                i += 1
            self.step()
        return [out[id(t)] for t in trace]

    def reset(self):
        """Forget all requests/slots (keeps compiled programs AND compile
        counters; per-run metrics are cleared) — benchmark warmup then
        timed replay on one engine without recompiling."""
        self.settle()
        if self.queue or self.slots.active_slots:
            raise RuntimeError("reset() with requests still in flight")
        self._kept = None         # its tokens were emitted when it settled
        # every trace-time compile counter survives: warm replay compiles,
        # reset, timed replay hits the jit cache — wiping any of these
        # would report 0 programs built for the timed run's artifacts
        self.metrics.reset(keep_counters=("prefill_compiles",
                                          "decode_compiles",
                                          "verify_compiles",
                                          "draft_propose_compiles",
                                          "draft_prefill_compiles"))
        self.queue = AdmissionQueue(self.metrics)
        self.slots = SlotTable(self.max_slots)
        self._npos[:] = 0
        self._last_tok[:] = self.pad_id
        self.sampler.reset()
        self.step_count = 0
        self._stall_steps = 0
        self._forget_steps()

    # -- internals ----------------------------------------------------------
    def _admit(self, req):
        """Hand the queue head a slot and load its sampling params."""
        slot = self.slots.admit(req)
        self.sampler.admit(slot, req)
        if req.admit_time is None and req.submit_time is not None:
            # the first admission (a resumed or handed-over request keeps it)
            req.admit_time = time.perf_counter()
            self.metrics.observe("queue_wait_s",
                                 req.admit_time - req.submit_time)
        return slot

    def _sampling_active(self, active=None):
        """True when any slot in the decode batch samples — selects the
        decode program variant (greedy-only traffic never compiles the
        sampling ops). Scoped to the DECODABLE slots (`active`, where the
        caller has them): a sampling request still mid-chunked-prefill must
        not push the greedy rows' decode steps onto the sampling program."""
        return self.sampler.any_sampling(
            self._decodable_slots() if active is None else active)

    def _record_prefill_done(self, req):
        """The prompt is fully in the target's KV cache. This is NOT
        TTFT: under chunked prefill the final chunk stashes the first
        token but emission waits for the stream to finish (with
        speculation the draft mirror may still be catching up window by
        window), so the two diverge by whole engine steps. Telemetry
        keeps both — `ttft_s` is what a client observes, `prefill_done_s`
        is what the prefill path costs. Idempotent: the monolithic path
        reaches here again via _complete_prefill."""
        if req.prefill_done_s is not None:
            return
        now = time.perf_counter()
        req.prefill_done_s = now - req.submit_time
        req.prefill_done_steps = self.step_count - req.submit_step
        self.metrics.observe("prefill_done_s", req.prefill_done_s)
        self.metrics.observe("prefill_done_steps", req.prefill_done_steps)

    def _record_first_token(self, req):
        now = time.perf_counter()
        req.first_token_time = now
        # TTFT at the first EMITTED token (not prefill completion), in
        # wall-clock seconds AND engine steps: steps are the
        # load-independent scheduling-delay unit arrival traces are written
        # in; seconds are what ROADMAP 2's p99 acceptance is measured in
        req.ttft_s = now - req.submit_time
        req.ttft_steps = self.step_count - req.submit_step
        self.metrics.observe("ttft_s", req.ttft_s)
        self.metrics.observe("ttft_steps", req.ttft_steps)

    def _prefill_step(self):
        req = self.queue.pop()
        slot = self._admit(req)
        n = int(req.prompt_ids.size)
        bucket, first = self._prefill_device(req, slot, n)
        return self._prefill_dispatched(req, slot, bucket, first, n)

    def _prefill_dispatched(self, req, slot, bucket, first, n, start=0):
        """A prompt's LAST window [start, n) was handed to the device: the
        slot decodes from position n on, and `first` (on the device still,
        or read already) is a token in flight (shared by the monolithic path
        and the paged engine's final chunk)."""
        self._npos[slot] = n
        ids = dict(request_id=req.request_id, slot=slot, kind="prefill",
                   tokens=n - start, bucket=bucket, start=start)
        return self._program(
            ids, first,
            lambda tok: self._complete_prefill(req, slot, bucket, int(tok),
                                               n),
            [(slot, req)])

    def _complete_prefill(self, req, slot, bucket, first, n):
        """A finished prompt's first token was read: TTFT, counters, the
        emitted token, retirement where it was the last."""
        with self._phase("emit", request_id=req.request_id, slot=slot):
            self._record_prefill_done(req)
            self._record_first_token(req)
            self.metrics.inc("prefills")
            self.metrics.inc("tokens_generated")
            self._last_tok[slot] = first
            self._emit(req, first)
            if req.finished:
                self._retire(slot)
        return {"type": "prefill", "request_id": req.request_id,
                "slot": slot, "bucket": bucket, "token": first}

    def _prefill_device(self, req, slot, n):
        """Run the device half of a prefill (subclass hook). Returns
        (bucket, first_token): read here, so the record is complete."""
        bucket = bucket_for(n, self.min_bucket, self.max_len)
        ids = dict(request_id=req.request_id, slot=slot, kind="prefill",
                   tokens=n, bucket=bucket, start=0)
        with self._phase("stage", part="build", **ids):
            padded = np.full((1, bucket), self.pad_id, np.int32)
            padded[0, :n] = req.prompt_ids
        with self._phase("stage", part="dispatch", **ids):
            self._ck, self._cv, first = self._prefill(
                self.params, jnp.asarray(padded), jnp.int32(n),
                self._ck, self._cv, jnp.int32(slot), self._cos,
                self._sin, jnp.float32(req.temperature),
                jnp.float32(req.top_p), jnp.int32(req.top_k),
                jnp.asarray([req.seed], jnp.int32),
                sample=req.temperature > 0)
        with self._phase("wait", **ids):
            first = int(first)
        return bucket, first

    def _decode_step(self, active):
        """Hand the device one batched decode step over `active`; every row
        stands one position further from here on."""
        nxt = self._decode_device(active)
        owner = self.slots.owner
        rows = [(slot, owner(slot)) for slot in active]
        self._npos[active] += 1
        return self._program(dict(kind="decode", rows=len(active)), nxt,
                             functools.partial(self._emit_decode, rows),
                             rows)

    def _emit_decode(self, rows, nxt):
        """A decode step's tokens were read (`nxt`, a slot's at its index):
        emit each row's, retire the rows that end."""
        emitted = {}
        with self._phase("emit"):
            nxt = nxt.tolist()
            for slot, req in rows:
                if req.finished:
                    # it hit EOS a program ago, found when that one was
                    # read: this token was never asked for
                    self.metrics.inc("serve.discarded_rows")
                    continue
                tok = nxt[slot]
                self._last_tok[slot] = tok
                self._emit(req, tok)
                emitted[req.request_id] = tok
                if req.finished:
                    self._retire(slot)
            self.metrics.inc("decode_steps")
            self.metrics.inc("tokens_generated", len(emitted))
        return {"type": "decode", "tokens": emitted}

    def _sampling_args(self):
        return self.sampler.device_args()

    def _decode_device(self, active):
        """Run the device half of one batched decode step (subclass
        hook). Returns the next-token array [S]: on the host here (the
        stripe engine feeds its tokens from `_last_tok`, so its record is
        complete and nothing is dispatched behind it), on the device where
        the tokens are fed there (the paged engine's)."""
        ids = dict(kind="decode", rows=len(active))
        with self._phase("stage", part="dispatch", **ids):
            self._ck, self._cv, nxt = self._decode(
                self.params, jnp.asarray(self._last_tok), self._ck,
                self._cv, jnp.asarray(self._npos), self._cos, self._sin,
                *self._sampling_args(),
                sample=self._sampling_active(active))
        with self._phase("wait", **ids):
            return np.asarray(nxt)

    def _emit(self, req, token):
        req.token_ids.append(token)
        finished, reason = False, None
        if req.eos_token_id is not None and token == req.eos_token_id:
            finished, reason = True, "eos"
        elif len(req.token_ids) >= req.max_new_tokens:
            finished, reason = True, "length"
        if req.stream_cb is not None:
            req.stream_cb(req, token, finished)
        if finished:
            req.finished = True
            req.finish_reason = reason
            req.finish_time = time.perf_counter()
            self.metrics.inc("requests_finished")

    def _retire(self, slot):
        self.slots.retire(slot)
        self._npos[slot] = 0
        self._last_tok[slot] = self.pad_id
        self.sampler.clear(slot)
