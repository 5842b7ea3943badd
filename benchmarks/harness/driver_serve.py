"""Drives a serving cell: one `PagedEngine`, load from this one thread.

The loop releases what is due, calls `step()` once, and records a span
around that call: the step loop is host-synchronous, so the generator and
the engine share the thread and how late a release ran is reported. A
request is timed from when it was DUE, through its `stream_cb`.

Traffic time 0 is the opening of the window; arrivals start `ramp_s`
earlier (set-up; a backlog states its ramp in engine steps, `ramp_steps`).
After the window closes no new request is released; an
open-loop cell then steps on until every request that was due inside the
window has its first token (so the tail is the tail of all of them), a
backlog cell stops at once. `correct` is decided after that, on the
engine's served tokens, once the engine is freed.
"""

import gc
import heapq
import time

import numpy as np

from benchmarks.harness import reference
from benchmarks.harness.traffic import ServeTraffic
from benchmarks.harness.weights import make_params

TRACE_S = 3.0            # the traced slice at the end of the window
DRAIN_LIMIT_S = 20.0     # first tokens still missing this long after the
                         # close count as failed
COMPARE_MAX = 32         # finished requests compared at most, and
COMPARE_TOKENS = 65536   # their tokens (prompt + served) at most: the
                         # longest and a seeded draw of the others within
                         # both (`compared`), so the float32 reference's
                         # time is bounded whatever a cell's lengths. Chosen
                         # from chip runs (PR 36, PERF.md section 2): the
                         # references go through a token in 0.43 to 1.0 ms
                         # warm, so a check lasts 16-66 s and every cell's
                         # warm run ends within 180 s of the driver's 360,
                         # a fully cold one within 320; a cell of ~1.7k-token
                         # requests keeps its 32. Requests alone bounded it
                         # before (PR 23), and 30 requests of 9k-49k tokens
                         # took 225 s of a 345 s run

def median_step_ms(spans, kind):
    """Median host time of the `step()` calls of one kind, whole run."""
    d = [b - a for k, a, b, _ in spans if k == kind]
    return 1e3 * float(np.median(d)) if d else None


def compared(sizes, seed, max_requests, max_tokens):
    """Which of the finished requests to compare: indices into `sizes`
    (each request's prompt + served tokens), in their order. The longest
    always; then the others in the order of a draw from the seed, each
    taken if the tokens held with it stay within `max_tokens` and passed
    over if not, until `max_requests` are held. The first drawn is taken
    whatever its size, so two are compared wherever two finished; a
    finished set within both bounds is compared whole."""
    if not len(sizes):
        return []
    longest = int(np.argmax(sizes))          # the first of equals
    rest = [i for i in range(len(sizes)) if i != longest]
    rng = np.random.default_rng([int(seed), 7])
    held, tokens = [longest], int(sizes[longest])
    for j in rng.permutation(len(rest)):
        if len(held) >= max_requests:
            break
        size = int(sizes[rest[j]])
        if len(held) == 1 or tokens + size <= max_tokens:
            held.append(rest[j])
            tokens += size
    return sorted(held)


class _Rec:
    __slots__ = ("rid", "due", "submitted", "times", "prompt", "want",
                 "session", "req")

    def __init__(self, rid, due, prompt, want, session):
        self.rid, self.due, self.prompt, self.want = rid, due, prompt, want
        self.session, self.submitted, self.times, self.req = (
            session, None, [], None)


class ServeRun:
    def __init__(self, cell, seed, devices, log):
        import jax.numpy as jnp

        from paddle_tpu.serving import PagedEngine, Request

        self.Request = Request
        self.cell, self.seed, self.log = cell, seed, log
        arch, family = self.arch, self.family = cell.config, cell.family
        params = make_params(family, arch, seed, jnp.bfloat16)
        self.engine_kw = dict(cell.spec["engine"])
        # the engine is the harness's choice, never the family's
        self.eng = PagedEngine(params, family.serve_args(arch),
                               **self.engine_kw)
        del params
        self.traffic = ServeTraffic(cell.traffic, arch["vocab_size"], seed)
        self.spans = []      # (type, start, end, tokens emitted) per step()
        self._emitted = 0
        self.recs = {}
        self.late = []       # release time - due time, seconds
        self.errors = 0
        self._heap, self._n = [], 0
        self.in_flight = []  # active slots after each step, beside `spans`

    # -- set-up ----------------------------------------------------------------
    def warm_up(self):
        """Every program the window can call: each prefill bucket up to the
        chunk, the chunked stream, decode, and the copy-on-write page copy
        (a prefix hit that ends mid-page). Then the engine is reset."""
        eng, kw = self.eng, self.engine_kw
        rng = np.random.default_rng(0)
        vocab = self.arch["vocab_size"]

        def toks(n):
            return rng.integers(1, vocab, n).astype(np.int32)

        b, sizes = kw["min_bucket"], []
        while b <= kw["prefill_chunk"]:
            sizes.append(b)
            b *= 2
        page = kw["page_size"]
        base = toks(page + page // 2)
        prompts = [toks(n) for n in sizes] + [toks(kw["prefill_chunk"] + 1)]
        for wave in (prompts + [base], [np.concatenate([base, toks(8)])]):
            for p in wave:
                eng.submit(self.Request(p, 3))
            while eng.queue or eng.slots.active_slots:
                eng.step()
        c = eng.metrics.summary()["counters"]
        if not c.get("cow_copies"):
            raise RuntimeError("warm-up did not reach the page copy")
        eng.reset()
        self.compiles_before = self._compiles()

    def _compiles(self):
        """Every program the engine counts the compilations of, whatever
        step programs a family's path adds to prefill and decode."""
        c = self.eng.metrics.summary()["counters"]
        return sum(n for name, n in c.items() if name.endswith("_compiles"))

    # -- the loop ----------------------------------------------------------------
    def _submit(self, session, due, now):
        nxt = session.request()
        if nxt is None:
            return
        prompt, want = nxt
        self._n += 1
        rec = _Rec(self._n, due, prompt, want, session)
        self.recs[rec.rid] = rec
        self.late.append(now - due)

        def cb(req, token, finished, rec=rec):
            t = time.perf_counter()
            rec.times.append(t)
            self._emitted += 1
            if finished:
                due_next = rec.session.answered(rec.prompt, req.token_ids, t)
                if due_next is not None:
                    heapq.heappush(self._heap, (due_next, rec.rid,
                                                rec.session))

        try:
            rec.req = self.eng.submit(self.Request(
                prompt, want, stream_cb=cb, request_id=rec.rid))
            rec.submitted = now
        except ValueError as e:       # refused: counts as failed
            self.errors += 1
            self.log(f"refused request {rec.rid}: {e}")

    def run(self, seconds, trace=None):
        eng, traffic, clock = self.eng, self.traffic, time.perf_counter
        backlog = traffic.arrival["process"] == "backlog"
        depth = int(traffic.arrival.get("queue_depth", 0))
        ramp_steps = int(traffic.mix.get("ramp_steps", 0)) if backlog else 0
        # the window opens here (after `ramp_steps` steps where a backlog
        # states them: far off until that step is reached)
        origin = clock() + (1e9 if ramp_steps else traffic.ramp_s)
        close = origin + seconds
        self.origin, self.close = origin, close
        tracing = False
        self.waiting_at_open = self.waiting_at_close = None  # (queued, active)
        while True:
            now = clock()
            if self.waiting_at_open is None and now >= origin:
                self.waiting_at_open = (len(eng.queue),
                                        len(eng.slots.active_slots))
            if now < close:
                if backlog:
                    while len(eng.queue) < depth:
                        self._submit(traffic.next_session(now), now, now)
                else:
                    while origin + traffic.next_due() <= now:
                        s = traffic.next_session(None)
                        self._submit(s, origin + s.due, now)
                while self._heap and self._heap[0][0] <= now:
                    due, _, s = heapq.heappop(self._heap)
                    self._submit(s, due, now)
            else:
                if tracing:
                    trace.stop()
                    tracing = False
                if self.waiting_at_close is None:
                    self.waiting_at_close = (len(eng.queue),
                                             len(eng.slots.active_slots))
                waiting = [r for r in self.recs.values()
                           if origin <= r.due < close and not r.times
                           and r.submitted is not None]
                if backlog or not waiting or now > close + DRAIN_LIMIT_S:
                    break
            if trace is not None and not tracing and \
                    close - TRACE_S <= now < close:
                trace.start()
                tracing = True
            if not (eng.queue or eng.slots.active_slots):
                nxt = min([close] + ([] if backlog else
                                     [origin + traffic.next_due()])
                          + [h[0] for h in self._heap[:1]])
                time.sleep(max(0.0, min(nxt - clock(), 0.002)))
                continue
            a, before = clock(), self._emitted
            if tracing:
                with trace.span("step"):
                    ev = eng.step()
            else:
                ev = eng.step()
            b = clock()
            self.in_flight.append(len(eng.slots.active_slots))
            self.spans.append((ev["type"], a, b, self._emitted - before))
            if ramp_steps and len(self.spans) == ramp_steps:
                # a backlog's ramp is counted in steps, so the window opens
                # at the same point of the trace whatever the ramp's pace
                origin, close = b, b + seconds
                self.origin, self.close = origin, close
        self.end = clock()
        self.compiles_in_window = self._compiles() - self.compiles_before
        self.counters = eng.metrics.summary()

    # -- results -------------------------------------------------------------------
    def in_flight_thirds(self):
        """Mean active slots after a step, over each third of the window: a
        level population reads three like numbers."""
        inside = [n for n, (_, a, _, _) in zip(self.in_flight, self.spans)
                  if self.origin <= a < self.close]
        return [round(float(np.mean(x)), 1) if len(x) else None
                for x in np.array_split(inside, 3)]

    def in_window(self):
        return [r for r in self.recs.values()
                if self.origin <= r.due < self.close]

    def results(self):
        """The host-clock numbers of the window."""
        recs = self.in_window()
        if self.traffic.arrival["process"] == "backlog":
            # a backlog always has requests waiting; attempted are those
            # the engine served a token to inside the window
            recs = [r for r in self.recs.values() if any(
                self.origin <= t < self.close for t in r.times)]
        ttft = [r.times[0] - r.due for r in recs if r.times]
        # every gap between two tokens of one request that ended inside the
        # window, whenever the request was due: the tail of all of them
        gaps = np.asarray([b - a for r in self.recs.values()
                           for a, b in zip(r.times, r.times[1:])
                           if self.origin <= b < self.close])
        # tokens a step emitted count by the share of the step that lies
        # inside the window, so an edge cuts a step instead of flipping it
        out = sum(n * max(0.0, min(b, self.close) - max(a, self.origin))
                  / (b - a) for _, a, b, n in self.spans if n)
        failed = self.errors + sum(1 for r in recs if not r.times)
        return {"attempted": len(recs) + self.errors, "failed": failed,
                "ttft_s": np.asarray(ttft), "itl_s": gaps,
                "out_tokens_per_s": out / (self.close - self.origin),
                "finished": sum(1 for r in recs
                                if r.req is not None and r.req.finished)}

    def sample(self):
        """The finished requests to compare (`compared`: the longest and a
        draw from the seed of the others, within COMPARE_MAX requests and
        COMPARE_TOKENS tokens). Returns (prompt, tokens, record) and checks
        every finished request has its asked length. `record` is the
        attribute of the finished `Request` that the family's
        `REQUEST_RECORD` names, for the family's own `served_logits` to read
        (None where it names none)."""
        done = [r for r in self.recs.values()
                if r.req is not None and r.req.finished]
        short = [r.rid for r in done if len(r.req.token_ids) != r.want]
        if short:
            raise RuntimeError(f"requests finished short of their asked "
                               f"length: {short[:5]}")
        done.sort(key=lambda r: r.rid)
        self.finished = len(done)
        done = [done[i] for i in compared(
            [len(r.prompt) + r.want for r in done], self.seed, COMPARE_MAX,
            COMPARE_TOKENS)]
        record = getattr(self.family, "REQUEST_RECORD", None)
        return [(r.prompt, np.asarray(r.req.token_ids, np.int32),
                 getattr(r.req, record) if record else None) for r in done]

    def free(self):
        del self.eng
        for r in self.recs.values():
            r.req = None
        gc.collect()

    def reference_logits(self, sample, mm=reference.f32_mm):
        t = time.perf_counter()
        # tokens chosen left to right are judged by one causal pass; a
        # family that chooses them otherwise brings its own rule
        own = getattr(self.family, "served_logits", None)
        out = (own(self.arch, self.seed, sample, mm) if own else
               reference.served_logits(self.family, self.arch, self.seed,
                                       sample, mm))
        self.log(f"correct: the reference ran {len(sample)} requests in "
                 f"{time.perf_counter() - t:.1f} s")
        return out

    def check(self, sample, logits=None, tokens=None):
        """Rows of (name, value, limit). Over the compared requests' served
        tokens, how far each token's reference logit lies below the
        reference's best: the widest gap (a wrong token sits several logits
        down) and the mean gap (steady over thousands of tokens, and grows
        with the square of the arithmetic's noise: the number a lower
        precision fails).
        `tokens` (one array per request) stands in for the served tokens
        when the control is judged."""
        limits = self.cell.spec["limits"]
        if logits is None:
            logits = self.reference_logits(sample)
        gaps = []
        for i, (prompt, served, _) in enumerate(sample):
            gap = reference.served_gap(
                logits[i], served if tokens is None else tokens[i])
            self.log(f"correct: request of {len(prompt)} + {len(served)} "
                     f"tokens: gap below the reference's best, widest "
                     f"{gap.max():.5f} mean {gap.mean():.6f}")
            gaps.append(gap)
        gaps = np.concatenate(gaps) if gaps else np.zeros(1)
        self.log(f"correct: {len(sample)} of {self.finished} finished "
                 f"requests, {len(gaps)} served tokens compared, "
                 f"{sum(len(p) for p, _, _ in sample)} prompt tokens gone "
                 f"through")
        return [("served_gap_widest", float(gaps.max()),
                 limits["served_gap_widest"]),
                ("served_gap_mean", float(gaps.mean()),
                 limits["served_gap_mean"]),
                ("compiles_in_window", float(self.compiles_in_window), 0.0),
                ("sample_requests_missing",
                 float(max(0, 2 - len(sample))), 0.0)]
