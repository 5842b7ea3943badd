"""Paged-KV serving engine, the HOST half: block tables, prefix reuse,
chunked prefill, the step loop, preemption, and the speculative round's
scheduling. What is on the device belongs to the model family's PATH.

`Engine` (serving/engine.py) reserves a full `max_len` KV stripe per
slot, so HBM — not compute — caps concurrency, and identical system
prompts are re-prefilled for every request. `PagedEngine` replaces the
stripes with the vLLM PagedAttention memory model (Kwon et al.,
SOSP'23) plus SGLang-style prefix sharing, on the same iteration-level
scheduler:

  - ONE fixed pool of pages on the device and a per-slot BLOCK TABLE here
    mapping sequence positions to pages. The pools, their layout, whatever
    else a request keeps on the device and the step programs over them are
    `self.path`, one object a family (`serving/paths.py` has the table and
    the interface: `serving/dense.py` for a `LlamaArgs`, `serving/family.py`
    for every other family). This file holds no device array and names no
    family;
  - PREFIX CACHE: every prefilled prompt is registered in
    `BlockAllocator`'s radix tree and REF'd by later requests sharing
    the prefix at TOKEN granularity (refcounted, COW-protected; a
    mid-page divergence shares the straddled page through a
    copy-on-write split — the PR-8 exact-match hash chain survives as
    `prefix_policy="hash"`, the bench baseline);
  - PREFILL = one window of the suffix past the hit at a time through
    `path.prefill` (one program per window-length bucket); DECODE = one
    batched step through the block tables, `path.decode`;
  - ADMISSION reserves the worst-case page count minus hits and defers
    the FIFO head under page pressure;
  - the step loop is `Engine`'s, ONE PROGRAM AHEAD: block tables,
    reservations, chunk streams and positions advance when a program is
    dispatched, token values (EOS, retirement, the tail page's
    registration) when it is read, a call later; the rows' tokens stay on
    the device (`path.tokens`, `path.seat`). `preempt` / `resume` and
    `reset` settle first; a draft model keeps the loop at depth 0.

On top of that scheduler sit the three serving-throughput levers (ROADMAP
item 1). Two are not this file's: TENSOR PARALLELISM (`mesh=`) is the dense
path's placement (`serving/dense.py`, `serving/tp.py`; block tables and the
allocator are untouched by it), SPECULATIVE DECODING (`draft_params=`) is a
round of its own in this engine's decode turn (`serving/spec_decode.py`).

CHUNKED PREFILL (`prefill_chunk=`): a long prompt no longer runs as one
monolithic program that stalls every decoding slot for its whole
duration. The suffix is split into page-aligned chunks and the
scheduler INTERLEAVES: chunk, then a decode step (or a short prefill),
then the next chunk — so TTFT for queued requests stays flat under
long-prompt bursts. Chunks reuse the suffix-bucket prefill program
(each chunk is "a suffix at a deeper h"), composing with prefix hits
unchanged.

Greedy parity with sequential `generate` stays exact under every
combination of the three (and int8 `quantize_params` trees stream
through the same fused dequant-matmul dispatch).

A family may keep a second kind of per-request state beside the pages (a
hybrid stack's recurrent state): a prefix hit is then usable only where a
SNAPSHOT of it was taken, which the path saves at a finished prompt's end
(`path.prompt_done`) and the request's retirement hangs on the radix tree
(`path.attach`, `BlockAllocator(..., state_snapshots=path.snapshots)`);
`preempt` / `resume` carry it (`path.take_state` / `put_state`). What a
family cannot do (a mesh, an int8 pool, a draft model) its path refuses at
construction.
"""

from __future__ import annotations

import numpy as np

from paddle_tpu.serving.block_manager import NULL_PAGE, BlockAllocator
from paddle_tpu.serving.engine import Engine, Request
from paddle_tpu.serving.paths import path_for
from paddle_tpu.serving.scheduler import bucket_for, pages_for
from paddle_tpu.serving.spec_decode import SpecDecoder

__all__ = ["PagedEngine"]


class PagedEngine(Engine):
    """Continuous-batching engine over a paged KV cache with prefix
    reuse, optional tensor parallelism, chunked prefill, and speculative
    decoding.

    page_size: tokens per KV page. On TPU keep it a multiple of 16 (bf16
               sublane tile) with head_dim a multiple of 128 so the Pallas
               paged decode kernel stays eligible. Prefix sharing itself
               is TOKEN-granular (radix cache); page_size only sets the
               COW-copy unit a mid-page divergence pays for.
    num_pages: pool size INCLUDING the reserved null page 0. Defaults to
               max_slots * (max_len/page_size) + 1 — the stripe engine's
               capacity; set it lower to oversubscribe slots against the
               real (sub-max_len, prefix-shared) footprint, which is the
               entire point.
    max_len:   per-REQUEST cap (block tables hold max_len/page_size
               entries); no longer a per-slot HBM reservation.
    mesh:      optional jax Mesh carrying `tp_axis` (default 'mp'):
               weights and the page pool shard over it and every step
               program runs SPMD (serving/tp.py placement). num_kv_heads,
               num_heads and intermediate_size must divide the degree.
    prefill_chunk: optional chunk length (a multiple of page_size).
               Prompt suffixes longer than this prefill in chunks
               interleaved with decode steps — long prompts stop
               stalling in-flight requests.
    draft_params/draft_args: optional draft model (same vocab; e.g.
               `generation.draft_from_params`) enabling speculative
               decoding with `spec_tokens` drafts per round. Greedy
               requests only (exact-match acceptance); sampling requests
               are rejected at submit.
    kv_dtype:  None (pool in the model dtype) or 'int8': the dense path
               quantizes the page pool itself (`serving/dense.py`), so an
               equal-HBM pool holds ~2x the pages. Outputs track the
               bf16 pool to a top-1 agreement bar, not bit-exactly.
    """

    def __init__(self, params, args, *, max_slots=4, max_len=256,
                 page_size=16, num_pages=None, min_bucket=16, pad_id=0,
                 metrics=None, mesh=None, tp_axis="mp", prefill_chunk=None,
                 draft_params=None, draft_args=None, spec_tokens=4,
                 donate_steps=None, prefix_policy="radix", kv_dtype=None):
        if prefix_policy not in ("radix", "hash"):
            raise ValueError(f"prefix_policy={prefix_policy!r} must be "
                             "'radix' or 'hash'")
        self.prefix_policy = prefix_policy
        if kv_dtype not in (None, "int8"):
            raise ValueError(f"kv_dtype={kv_dtype!r} must be None (the "
                             "model dtype) or 'int8'")
        self.kv_dtype = kv_dtype
        if max_len % page_size != 0:
            raise ValueError(
                f"max_len={max_len} must be a multiple of "
                f"page_size={page_size}")
        self.page_size = int(page_size)
        self.pages_per_slot = int(max_len) // self.page_size
        self.num_pages = (int(num_pages) if num_pages is not None
                          else int(max_slots) * self.pages_per_slot + 1)
        self.mesh = mesh
        self.tp_axis = tp_axis
        if prefill_chunk is not None:
            prefill_chunk = int(prefill_chunk)
            if prefill_chunk < 1 or prefill_chunk % self.page_size:
                raise ValueError(
                    f"prefill_chunk={prefill_chunk} must be a positive "
                    f"multiple of page_size={page_size}")
        self.prefill_chunk = prefill_chunk
        if draft_params is not None and draft_args is None:
            raise ValueError("draft_params requires draft_args "
                             "(see generation.draft_from_params)")
        self.draft_params = draft_params
        self.draft_args = draft_args
        self.spec_tokens = int(spec_tokens)
        super().__init__(params, args, max_slots=max_slots, max_len=max_len,
                         min_bucket=min_bucket, pad_id=pad_id,
                         metrics=metrics, donate_steps=donate_steps)

    @property
    def spec_enabled(self):
        return self.draft_params is not None

    # -- construction ------------------------------------------------------
    def _reset_host_state(self):
        """The allocator, block tables and reservations of an empty
        engine (construction and `reset`)."""
        self._alloc = BlockAllocator(
            self.num_pages, self.page_size, metrics=self.metrics,
            policy=self.prefix_policy,
            state_snapshots=self.path.snapshots)
        self._bt = [[] for _ in range(self.max_slots)]   # host block tables
        self._resv = {}            # slot -> pages still reserved for decode
        self._reserved_total = 0
        self._chunk_streams = {}   # slot -> {req, n, done} mid-chunked-prefill
        self._chunk_turn = False
        self._admit_idx = None     # _can_prefill's cached admission scan

    def _setup_device_state(self):
        # everything on the device — pools, per-request state, the step
        # programs — is the model family's (`serving/paths.py`)
        self.path = path_for(self)
        self._reset_host_state()
        # the speculative half (draft cache and programs, the verify program,
        # the propose / verify / accept / roll-back round): spec_decode.py
        self._spec = SpecDecoder(self, self._donate_enabled()) \
            if self.spec_enabled else None

    # -- admission ----------------------------------------------------------
    def submit(self, req):
        if not isinstance(req, Request):
            req = Request(req)
        need = pages_for(req.prompt_ids.size, req.max_new_tokens,
                         self.page_size)
        if need > self._alloc.capacity:
            raise ValueError(
                f"request needs {need} KV pages but the pool only has "
                f"{self._alloc.capacity} (num_pages={self.num_pages}, "
                f"page_size={self.page_size})")
        return super().submit(req)

    def _peek_hits(self, req):
        """Side-effect-free PrefixMatch for a queued request, memoized
        on the allocator's prefix_version: the anti-convoy scan below
        runs every step while a chunk stream is active, and re-walking
        every queued prompt each step is O(queue x prompt_len) host work
        for an answer that only changes when the prefix index does. Any
        registration, split, or eviction bumps prefix_version and
        invalidates the memo — a stale hit set here would skew the
        worst-case page reservation `_can_prefill` gates admission on."""
        ver = self._alloc.prefix_version
        cached = getattr(req, "_hits_memo", None)
        if cached is not None and cached[0] == ver:
            return cached[1]
        peek = self._alloc.match_prefix(req.prompt_ids, commit=False)
        req._hits_memo = (ver, peek)
        return peek

    def _admission_index(self):
        """Queue index to admit next. FIFO — except while a chunk stream
        is in flight, when the first SHORT prompt (suffix fits in one
        chunk) bypasses queued longs: a long prefill already streaming
        must not convoy every cheap prefill behind the NEXT long. Longs
        keep FIFO order among themselves, and the bypass only exists
        while a stream is active, so they cannot starve."""
        if not self.queue:
            return None
        if not (self.prefill_chunk and self._chunk_streams):
            return 0
        for i in range(len(self.queue)):
            req = self.queue.peek_at(i)
            if (req.prompt_ids.size - self._peek_hits(req).matched
                    <= self.prefill_chunk):
                return i
        return 0

    def _can_prefill(self):
        self._admit_idx = None
        if not (self.queue and self.slots.free_count):
            return False
        # cache the scan for the _prefill_step that immediately follows a
        # True answer — the anti-convoy walk match_prefix-hashes every
        # queued prompt, which is too much host work to repeat per step
        self._admit_idx = self._admission_index()
        req = self.queue.peek_at(self._admit_idx)
        peek = self._peek_hits(req)
        # reviving a cached (refcount-0) hit consumes availability just
        # like a fresh alloc; an actively shared hit is free. A mid-page
        # partial hit nets out: its COW copy costs one alloc but saves
        # one page of suffix — so `need` stays pages_for - full_hits.
        hit_pages = list(peek.pages)
        if peek.partial_page is not None:
            hit_pages.append(peek.partial_page)
        revive = sum(1 for p in hit_pages if self._alloc.refcount(p) == 0)
        need = (pages_for(req.prompt_ids.size, req.max_new_tokens,
                          self.page_size) - len(peek.pages) + revive)
        return need <= self._alloc.available - self._reserved_total

    # -- the interleaving scheduler -----------------------------------------
    def _next_program(self):
        """Chunked-prefill interleave: while a prompt is mid-stream, the
        engine alternates one chunk with one unit of other work (admit a
        waiting request or run a decode/speculation step), so queued and
        in-flight requests keep making progress underneath a long
        prefill. Decode becomes speculate-and-verify when a draft model
        is loaded."""
        if self._chunk_streams and self._chunk_turn:
            self._chunk_turn = False
            self._note_prefill_stall()
            return self._chunk_step()
        if self._can_prefill():
            self._chunk_turn = True
            self._note_prefill_stall()
            return self._prefill_step()
        active = self._decodable_slots()
        if active:
            self._chunk_turn = True
            if self.spec_enabled:
                return self._done(self._spec.step())
            return self._decode_step(active)
        if self._chunk_streams:
            return self._chunk_step()
        return None

    def _looks_ahead(self, flight):
        # a verify round's accepted length is data: where the rows stand
        # after it is not known when it is dispatched, so an engine with a
        # draft never dispatches behind a program it has not read
        return not self.spec_enabled and super()._looks_ahead(flight)

    def _decodable_slots(self):
        active = super()._decodable_slots()
        if not self._chunk_streams:
            return active
        return [s for s in active if s not in self._chunk_streams]

    # -- prefill ------------------------------------------------------------
    def _begin_paged_prefill(self, req, slot, n):
        """Match prefix hits, seat the block table, and reserve the
        request's remaining worst-case pages (prompt pages still to be
        written draw from this reservation chunk by chunk; the decode
        tail draws from it at page boundaries). Returns h — the cached
        token count the first window starts at."""
        ps = self.page_size
        hit = self._alloc.match_prefix(req.prompt_ids)   # refs hit pages
        h = hit.matched
        self._bt[slot] = list(hit.pages)
        held = len(hit.pages)
        if hit.partial_page is not None:
            # mid-page hit: the straddled page is frozen (tree-registered),
            # so take a copy-on-write split — ensure_writable swaps our ref
            # for a fresh page and the page-copy program clones the device
            # contents; the first window then overwrites [h, ...) in place
            src = hit.partial_page
            copy, _ = self._alloc.ensure_writable(src)
            with self._phase("stage", kind="copy", part="dispatch",
                             request_id=req.request_id, slot=slot):
                self.path.copy_page(src, copy)
            self._bt[slot].append(copy)
            held += 1
        if hit.state is not None:
            # the match ends at a snapshot of the per-request state kept
            # beside the pages: the slot resumes from it
            with self._phase("stage", kind="copy", part="dispatch",
                             request_id=req.request_id, slot=slot):
                self.path.load_snapshot(slot, hit.state)
        resv = pages_for(n, req.max_new_tokens, ps) - held
        self._resv[slot] = resv
        self._reserved_total += resv
        self.metrics.inc("prompt_tokens", n)
        self.metrics.inc("prefix_tokens_hit", h)
        self.metrics.inc("prefix_pages_hit", len(hit.pages))
        return h

    def _window_ids(self, req, slot, start, end):
        """The identifiers of the prefill window [start, end): what its
        `stage` entries carry and, when it is read, its `wait` entry."""
        return dict(request_id=req.request_id, slot=slot, kind="prefill",
                    tokens=end - start,
                    bucket=bucket_for(end - start, self.min_bucket,
                                      self.max_len), start=start)

    def _window_prefill_device(self, req, slot, start, end, n):
        """Hand the device one prefill window [start, end) of the prompt
        (the whole suffix, or one chunk of it) through the suffix program.
        Returns (bucket, token), the token on the device still — it is
        meaningful only for the final window (end == n), which also
        registers the prompt's full pages in the prefix cache and seats the
        token in the path's token vector, where the slot's first decode step
        finds it."""
        ps, Pn = self.page_size, self.pages_per_slot
        final = end == n
        # pages this window adds beyond those already seated (hits, the
        # partial-hit COW copy, earlier chunks); token-granular `start`
        # makes this ceil(end/ps) minus the seated count
        n_now = -(-end // ps) - len(self._bt[slot])
        new_pages = self._alloc.alloc_many(n_now)
        self._resv[slot] -= n_now
        self._reserved_total -= n_now
        self._bt[slot].extend(new_pages)
        pages = self._bt[slot]

        ids = self._window_ids(req, slot, start, end)
        sb = ids["bucket"]
        with self._phase("stage", part="build", **ids):
            bt_row = np.zeros(Pn, np.int32)
            bt_row[:len(pages)] = pages
            # every page the window touches gets scattered: the
            # straddled page at start//ps (the mid-page-hit COW copy on
            # the first window, the slot's own tail page on later
            # chunks) is rewritten from the gathered stripe plus the
            # new tokens
            touched = pages[start // ps:]
            new_vec = np.full(Pn, NULL_PAGE, np.int32)
            new_vec[:len(touched)] = touched
            padded = np.full((1, sb), self.pad_id, np.int32)
            padded[0, :end - start] = req.prompt_ids[start:end]
            sample = final and req.temperature > 0
            if final:
                # make this prompt's FULL pages hittable right away (a
                # concurrent identical prompt shares them while this one
                # is still decoding): prompt positions only, which the
                # program dispatched below writes before any later one
                # reads them. The partial tail page stays unregistered
                # until _retire — decode keeps writing into it, and
                # freezing it now would force an unreserved COW on the
                # first decode
                self._alloc.register_prefix(req.prompt_ids, pages[:n // ps])
        with self._phase("stage", part="dispatch", **ids):
            first = self.path.prefill(padded, start, end - 1 - start,
                                      bt_row, new_vec, slot, req, sample)
            if final:
                self.path.prompt_done(slot)
                self.path.seat(slot, first)
        # chunk-streamed prompts mirror into the draft window by
        # window instead (see _chunk_step) — one monolithic draft
        # prefill here would reintroduce the stall chunking removes
        if final and self.spec_enabled and slot not in self._chunk_streams:
            self._spec.prefill_slot(req, slot, n)
        return sb, first

    def _prefill_step(self):
        """Admit the queue head; suffixes longer than `prefill_chunk`
        become a chunk STREAM advanced by later steps instead of one
        monolithic program."""
        idx = self._admit_idx if self._admit_idx is not None \
            else self._admission_index()
        req = self.queue.pop_at(idx)
        slot = self._admit(req)
        n = int(req.prompt_ids.size)
        h = self._begin_paged_prefill(req, slot, n)
        if self.prefill_chunk is None or n - h <= self.prefill_chunk:
            bucket, first = self._window_prefill_device(req, slot, h, n, n)
            if self.prefill_chunk is not None:
                self.metrics.observe("chunks_per_prompt", 1)
            return self._prefill_dispatched(req, slot, bucket, first, n, h)
        self._chunk_streams[slot] = {"req": req, "n": n, "done": h,
                                     "ddone": 0, "chunks": 0,
                                     "bucket": None, "first": None,
                                     "start": h}
        self.metrics.inc("chunked_prefills")
        return self._chunk_step()

    def _chunk_step(self):
        """Advance the oldest chunk stream (FIFO: the first admitted long
        prompt finishes first) by ONE bounded unit of prefill work: a
        target chunk, or — when speculation is on and the draft's mirror
        of the prompt lags the target's progress — one draft window of
        the same size, so the draft prefill never runs monolithically
        inside a single scheduler step. A stream's progress is scheduled
        state: it advances as a chunk is dispatched."""
        slot = next(iter(self._chunk_streams))
        st = self._chunk_streams[slot]
        req, n = st["req"], st["n"]
        if self.spec_enabled and st["ddone"] < n and \
                (st["ddone"] < st["done"] or st["done"] == n):
            dstart = st["ddone"]
            dend = min(dstart + self.prefill_chunk, n)
            self._spec.prefill_window(req, slot, dstart, dend)
            st["ddone"] = dend
            self.metrics.inc("draft_prefill_chunks")
            if dend < n or st["done"] < n:
                return self._done({"type": "draft_prefill_chunk",
                                   "request_id": req.request_id,
                                   "slot": slot, "from": dstart, "to": dend})
            return self._finish_stream(slot, st)
        start = st["done"]
        end = min(start + self.prefill_chunk, n)
        bucket, first = self._window_prefill_device(req, slot, start, end, n)
        st["done"] = end
        st["chunks"] += 1
        self.metrics.inc("prefill_chunks")
        self.metrics.inc("prefill_chunk_tokens", end - start)
        if end == n:
            st["bucket"], st["first"], st["start"] = bucket, first, start
            if not (self.spec_enabled and st["ddone"] < n):
                return self._finish_stream(slot, st)
        ev = {"type": "prefill_chunk", "request_id": req.request_id,
              "slot": slot, "from": start, "to": end}

        def land(tok):
            if end == n:
                # the TARGET's prompt KV is complete here; the first token
                # is only emitted at _finish_stream, which waits whole
                # steps for the draft mirror — the prefill_done_s / ttft_s
                # split. The token was read with the window: stash it so
                self._record_prefill_done(req)
                st["first"] = int(tok)
            return ev

        return self._program(self._window_ids(req, slot, start, end), first,
                             land)

    def _finish_stream(self, slot, st):
        """Both the target chunks and (under speculation) the draft
        mirror were dispatched: retire the stream; its last window's token
        is the prompt's first."""
        del self._chunk_streams[slot]
        self.metrics.observe("chunks_per_prompt", st["chunks"])
        req, n = st["req"], st["n"]
        if isinstance(st["first"], int):
            # under a draft the window was read when it went out, steps ago
            # (`_chunk_step`): no program goes out here, its token is emitted
            self._npos[slot] = n
            return self._done(self._complete_prefill(
                req, slot, st["bucket"], st["first"], n))
        return self._prefill_dispatched(req, slot, st["bucket"], st["first"],
                                        n, st["start"])

    # -- decode -------------------------------------------------------------
    def _ensure_tail_pages(self, slot, top):
        """Make the slot's KV positions [npos, top] writable: COW the
        current tail page if it is shared or hash-registered, then draw
        page-boundary allocations from the slot's admission-time
        reservation through `top`. The ONE home of the tail-page
        invariants — plain decode (top == npos) and the speculative
        verify window (top == min(npos + g, limit)) both call it."""
        ps = self.page_size
        pages = self._bt[slot]
        pi = int(self._npos[slot]) // ps
        if pi < len(pages):
            old = pages[pi]
            page, copied = self._alloc.ensure_writable(old)
            if copied:
                with self._phase("stage", kind="copy", part="dispatch",
                                 slot=slot):
                    self.path.copy_page(old, page)
                pages[pi] = page
        while len(pages) * ps <= top:
            pages.append(self._alloc.alloc())
            self._resv[slot] -= 1
            self._reserved_total -= 1

    def _decode_device(self, active):
        Pn = self.pages_per_slot
        for slot in active:
            self._ensure_tail_pages(slot, int(self._npos[slot]))
        ids = dict(kind="decode", rows=len(active))
        with self._phase("stage", part="build", **ids):
            bt = np.full((self.max_slots, Pn), NULL_PAGE, np.int32)
            for slot in active:
                bt[slot, :len(self._bt[slot])] = self._bt[slot]
            # the share of the table's width the step's rows hold: the
            # pages the paged kernel fetches, over slots x pages a slot
            live = int(np.sum(self._npos[active] // self.page_size + 1))
            self.metrics.observe("decode_live_page_share",
                                 live / (self.max_slots * Pn))
        with self._phase("stage", part="dispatch", **ids):
            # with the path's call: the sampler's four per-row operands go
            # to the device here whenever a row was admitted or cleared.
            # The rows' tokens are on the device already (the path's token
            # vector), and the step's output stays there until it is
            # emitted (`Engine._land`)
            return self.path.decode(bt, active,
                                    self._sampling_active(active),
                                    self._sampling_args())

    def _emit_decode(self, rows, nxt):
        # what else rode the step's one read-back is the path's to look at
        self.path.landed(nxt)
        return super()._emit_decode(rows, nxt)

    # -- lifecycle ----------------------------------------------------------
    def _retire(self, slot):
        # the slot stops writing here, so its partial PROMPT tail page is
        # finally frozen: hang it on the radix tree (full pages were
        # registered at prefill; this extends the cached prefix to token
        # granularity — contents beyond the prompt are decode K/V that
        # partial_len keeps unreachable). Only prompt positions are
        # cached: their bytes came from prefill programs, so later hits
        # replay the exact values a fresh prefill would compute.
        req = self.slots.owner(slot)
        whole = req is not None and \
            int(self._npos[slot]) >= req.prompt_ids.size
        if whole:
            n = int(req.prompt_ids.size)
            n_pages = -(-n // self.page_size)
            self._alloc.register_prefix(req.prompt_ids,
                                        self._bt[slot][:n_pages])
        if req is not None:
            # what the path kept of the prompt's end joins the tree with
            # the prompt's last page
            self.path.attach(slot, req.prompt_ids, whole)
        self._alloc.release_many(self._bt[slot])
        self._bt[slot] = []
        self._reserved_total -= self._resv.pop(slot, 0)
        if self.spec_enabled:
            self._spec.retire(slot)
        super()._retire(slot)

    # -- preemption ---------------------------------------------------------
    def preempt(self, slot):
        """Evict a DECODING request from its slot without losing work:
        the returned state is the block table (page ids, refcounts still
        held — the allocator cannot hand the pages out or evict them,
        and prefix hits against the prompt's registered pages stay
        COW-safe), the KV write position, and the last token. `resume`
        re-seats it and the continuation is bit-identical to never
        having been preempted: decode depends only on the held pages'
        contents, the block table, `npos`, the last token, and the
        (seed, pos) sampling stream — all preserved. The slot's
        remaining page reservation is refunded while preempted, which is
        the point: a waiting request can use it."""
        if slot in self._chunk_streams:
            raise ValueError(f"slot {slot} is mid-prefill-stream; only "
                             "decoding slots are preemptible")
        # the slot's last token and position leave with it: what is in
        # flight is read and emitted first
        self.settle()
        if slot not in self.slots.active_slots:
            raise ValueError(f"slot {slot} finished with the tokens that "
                             "were in flight; nothing is left to preempt")
        req = self.slots.owner(slot)
        if self.spec_enabled:
            raise ValueError("preemption with speculative decoding is "
                             "unsupported (the draft's stripe cache is "
                             "not checkpointed)")
        state = {"req": req, "pages": self._bt[slot],
                 "npos": int(self._npos[slot]),
                 "last_tok": int(self._last_tok[slot]),
                 "resv": self._resv.get(slot, 0),
                 # what the path keeps of the slot beside its pages leaves
                 # with the request
                 "path_state": self.path.take_state(slot)}
        self._bt[slot] = []
        self._reserved_total -= self._resv.pop(slot, 0)
        self.slots.retire(slot)
        self._npos[slot] = 0
        self._last_tok[slot] = self.pad_id
        self.sampler.clear(slot)
        self.metrics.inc("preemptions")
        return state

    def can_resume(self, state):
        return bool(self.slots.free_count) and \
            state["resv"] <= self._alloc.available - self._reserved_total

    def resume(self, state):
        """Re-seat a preempted request (see `preempt`); returns its new
        slot. Caller must have checked `can_resume`."""
        self.settle()
        req = state["req"]
        slot = self._admit(req)
        self._bt[slot] = state["pages"]
        self._resv[slot] = state["resv"]
        self._reserved_total += state["resv"]
        self._npos[slot] = state["npos"]
        self._last_tok[slot] = state["last_tok"]
        self.path.seat(slot, state["last_tok"])
        self.path.put_state(slot, state["path_state"])
        self.metrics.inc("resumes")
        return slot

    def reset(self):
        """Forget all requests, block tables, AND the prefix cache (cold
        cache — a warm timed run after reset would be all hits and lie);
        compiled programs and compile counters survive."""
        super().reset()
        self.path.reset()
        self._reset_host_state()
        if self.spec_enabled:
            self._spec.reset()
