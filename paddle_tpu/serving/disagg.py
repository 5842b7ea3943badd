"""Disaggregated prefill/decode serving: KV-page migration between
role-restricted engines.

Prefill and decode have opposite hardware profiles — prefill is one big
compute-bound forward, decode is a memory-bound trickle — and inside one
`PagedEngine` they CONTEND: every long prompt stalls every decoding slot
for whole scheduler steps (the `prefill_stall_steps` gauge chunked
prefill only flattens, never removes). DistServe/Splitwise split the two
roles into separate workers so the interference dies at its root. The
block-table refactor (PR 8) made that split cheap to express here: a
sequence's KV cache IS a list of page ids, so a finished prefill moves
to the decode worker by shipping page CONTENTS + metadata, not by
re-computing anything.

  PrefillWorker (a `PagedEngine` whose decode path is switched off via
  the scheduler hooks) admits requests, runs prefills — prefix cache,
  chunked streaming and length buckets all unchanged — and on prompt
  completion EXTRACTS the slot's pages ([L, P, nkv, ps, hd] gathered
  along the pool's page axis; an int8 `QuantizedKVPage` pool ships its
  codes AND per-(page, kv-head) scales verbatim, no dequant round-trip),
  emits the first token, packs a `KVHandoff`, pushes it on the
  transport, and retires the slot — pages released, prefix registered,
  reservation refunded, exactly as a local retire.

  DecodeWorker (a `PagedEngine` that never prefills) polls the
  transport, and for each handoff allocates fresh pages, RE-SCATTERS the
  shipped contents into its own pool, seats the block table / position /
  last-token state, and decodes on. Because the page bytes are moved
  bit-exact (bf16 pages, or int8 codes + scales), the decode worker's
  continuation is token-for-token the monolithic engine's output.

  Transports: `LocalTransport` is an in-process queue that still
  round-trips every handoff through `KVHandoff.to_bytes()` — the whole
  path is tier-1-testable on CPU, serialization included.
  `StoreTransport` moves the same bytes through the native `TCPStore`
  for the 2-process rig (the CPU backend cannot run cross-process XLA
  programs, so the dryrun rig ships KV host-side; on a real TPU pod the
  same hand-off rides ICI/DCN device-to-device).

  `DisaggServer` wires one of each over a transport for the
  single-process case and mirrors completions back onto the submitted
  Request objects.

Extraction and re-scatter are two tiny jitted programs of the family's path
(`serving/dense.py`: `extract_pages` / `scatter_pages`, which know the
pool's layout) that must stay COLLECTIVE-FREE — pure page-axis data
movement, pinned by the `analysis/presets.py` disagg goldens. A family whose
sequence is more than its pages says so (`path.check_handoff`).
"""

from __future__ import annotations

import io
import json
import time
from collections import deque

import jax
import numpy as np

from paddle_tpu.models import generation as gen
from paddle_tpu.serving.paged_engine import PagedEngine
from paddle_tpu.serving.engine import Request
from paddle_tpu.serving.scheduler import pages_for

__all__ = ["KVHandoff", "LocalTransport", "StoreTransport",
           "PrefillWorker", "DecodeWorker", "DisaggServer"]


def _leaf_dtype(name):
    if name == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(name)


class KVHandoff:
    """One finished prefill, packaged for migration: the request's
    identity + sampling params, the first (already emitted) token, and
    the slot's page contents. `pages_k`/`pages_v` mirror the pool leaf
    structure: plain ndarrays, or `QuantizedKVPage(q, scale)`."""

    def __init__(self, *, request_id, prompt_ids, max_new_tokens,
                 eos_token_id, temperature, top_p, top_k, seed, first,
                 pages_k, pages_v, sent_at=None):
        self.request_id = request_id
        self.prompt_ids = np.asarray(prompt_ids, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = eos_token_id
        self.temperature = float(temperature)
        self.top_p = float(top_p)
        self.top_k = int(top_k)
        self.seed = int(seed)
        self.first = int(first)
        self.pages_k = pages_k
        self.pages_v = pages_v
        # wall-clock (time.time: comparable ACROSS processes, unlike
        # perf_counter) stamped at send; the receiver's admit computes
        # the hand-off latency histogram from it
        self.sent_at = sent_at

    @property
    def num_pages(self):
        leaf = jax.tree_util.tree_leaves(self.pages_k)[0]
        return int(leaf.shape[1])

    def _leaves(self):
        return (jax.tree_util.tree_leaves(self.pages_k)
                + jax.tree_util.tree_leaves(self.pages_v))

    def nbytes(self):
        return sum(x.nbytes for x in self._leaves())

    def to_bytes(self):
        """Self-describing wire format: json header (request metadata +
        per-leaf dtype/shape/length) then the raw leaf buffers. bf16
        rides as raw bytes + a dtype name (numpy cannot npz ml_dtypes
        arrays portably)."""
        leaves = [np.ascontiguousarray(np.asarray(x))
                  for x in self._leaves()]
        meta = {
            "request_id": self.request_id,
            "max_new_tokens": self.max_new_tokens,
            "eos_token_id": self.eos_token_id,
            "temperature": self.temperature,
            "top_p": self.top_p,
            "top_k": self.top_k,
            "seed": self.seed,
            "first": self.first,
            "sent_at": self.sent_at,
            "prompt_len": int(self.prompt_ids.size),
            "quantized": isinstance(self.pages_k, gen.QuantizedKVPage),
            "leaves": [{"dtype": x.dtype.name, "shape": list(x.shape),
                        "nbytes": x.nbytes} for x in leaves],
        }
        head = json.dumps(meta).encode()
        out = io.BytesIO()
        out.write(len(head).to_bytes(8, "little"))
        out.write(head)
        out.write(self.prompt_ids.tobytes())
        for x in leaves:
            out.write(x.tobytes())
        return out.getvalue()

    @classmethod
    def from_bytes(cls, blob):
        hlen = int.from_bytes(blob[:8], "little")
        meta = json.loads(blob[8:8 + hlen].decode())
        off = 8 + hlen
        n = meta["prompt_len"]
        prompt = np.frombuffer(blob, np.int32, count=n, offset=off).copy()
        off += prompt.nbytes
        leaves = []
        for d in meta["leaves"]:
            dt = _leaf_dtype(d["dtype"])
            count = d["nbytes"] // dt.itemsize
            leaves.append(np.frombuffer(blob, dt, count=count, offset=off)
                          .reshape(d["shape"]).copy())
            off += d["nbytes"]
        if meta["quantized"]:
            pages_k = gen.QuantizedKVPage(leaves[0], leaves[1])
            pages_v = gen.QuantizedKVPage(leaves[2], leaves[3])
        else:
            pages_k, pages_v = leaves[0], leaves[1]
        return cls(request_id=meta["request_id"], prompt_ids=prompt,
                   max_new_tokens=meta["max_new_tokens"],
                   eos_token_id=meta["eos_token_id"],
                   temperature=meta["temperature"], top_p=meta["top_p"],
                   top_k=meta["top_k"], seed=meta["seed"],
                   first=meta["first"], pages_k=pages_k, pages_v=pages_v,
                   sent_at=meta["sent_at"])


class LocalTransport:
    """In-process hand-off queue. Every payload still round-trips through
    `KVHandoff.to_bytes()` so tier-1 exercises the exact byte path the
    2-process `StoreTransport` ships."""

    def __init__(self):
        self._q = deque()

    def send(self, blob):
        self._q.append(blob)

    def recv(self):
        return self._q.popleft() if self._q else None

    @property
    def pending(self):
        return len(self._q)


class StoreTransport:
    """TCPStore-backed byte transport for the 2-process dryrun rig: the
    sender publishes numbered messages under `channel/` and bumps a
    counter; the receiver polls the counter non-blockingly (`add(key, 0)`
    creates-or-reads) and fetches in order. One direction per instance."""

    def __init__(self, store, channel="disagg"):
        self.store = store
        self.channel = channel
        self._sent = 0
        self._seen = 0

    def send(self, blob):
        self.store.set(f"{self.channel}/m{self._sent}", blob)
        self._sent += 1
        self.store.add(f"{self.channel}/n", 1)

    def recv(self):
        n = int(self.store.add(f"{self.channel}/n", 0))
        if self._seen >= n:
            return None
        blob = self.store.get(f"{self.channel}/m{self._seen}")
        self._seen += 1
        return blob

    @property
    def pending(self):
        return int(self.store.add(f"{self.channel}/n", 0)) - self._seen


class PrefillWorker(PagedEngine):
    """A `PagedEngine` restricted to the PREFILL role via the scheduler
    hooks: `_decodable_slots` is empty so `_step_action` only ever
    prefills (monolithic or chunk-streamed), and a completed prompt is
    extracted, shipped on the transport, and retired instead of staying
    seated for decode. Prefix cache, chunked prefill, buckets and page
    accounting are all the base engine's."""

    def __init__(self, params, args, *, transport, **kw):
        if kw.get("draft_params") is not None:
            raise ValueError("disaggregated workers do not run "
                             "speculative decoding (the draft mirror "
                             "belongs to the decode role)")
        self.transport = transport
        super().__init__(params, args, **kw)
        self.path.check_handoff()

    def _decodable_slots(self):
        return []

    def _build_handoff(self, req, slot, first):
        pages = np.asarray(self._bt[slot], np.int32)
        ids = dict(kind="copy", request_id=req.request_id, slot=slot)
        with self._phase("stage", part="dispatch", **ids):
            pk, pv = self.path.extract_pages(pages)
        with self._phase("wait", **ids):
            pk = jax.tree_util.tree_map(np.asarray, pk)
            pv = jax.tree_util.tree_map(np.asarray, pv)
        return KVHandoff(
            request_id=req.request_id, prompt_ids=req.prompt_ids,
            max_new_tokens=req.max_new_tokens,
            eos_token_id=req.eos_token_id, temperature=req.temperature,
            top_p=req.top_p, top_k=req.top_k, seed=req.seed, first=first,
            pages_k=pk, pages_v=pv, sent_at=time.time())

    def _complete_prefill(self, req, slot, bucket, first, n):
        ev = super()._complete_prefill(req, slot, bucket, first, n)
        if not req.finished:
            pkg = self._build_handoff(req, slot, first)
            self.transport.send(pkg.to_bytes())
            self.metrics.inc("handoffs_sent")
            self.metrics.inc("handoff_bytes", pkg.nbytes())
            # release the refcounts / refund the reservation on THIS
            # side — the decode worker owns the sequence now. _retire
            # also registers the prompt's pages in the local prefix
            # cache, so a later identical prompt still hits.
            self._retire(slot)
            ev = dict(ev, type="prefill_handoff")
        return ev


class DecodeWorker(PagedEngine):
    """A `PagedEngine` restricted to the DECODE role: it never admits
    from its own queue (`_can_prefill` is False); instead each step
    drains the transport, seating every handoff that fits — fresh pages
    allocated, shipped contents re-scattered, block table / position /
    last-token state restored — then runs the normal batched paged
    decode over all seated slots. `completion_cb(req)` fires at each
    request's retirement (the `DisaggServer` mirror hook)."""

    def __init__(self, params, args, *, transport, completion_cb=None,
                 **kw):
        if kw.get("draft_params") is not None:
            raise ValueError("disaggregated workers do not run "
                             "speculative decoding (the draft has no "
                             "prompt mirror on the decode side)")
        self.transport = transport
        self.completion_cb = completion_cb
        self._inbox = deque()
        super().__init__(params, args, **kw)
        self.path.check_handoff()

    def _can_prefill(self):
        return False

    def _can_admit(self, pkg):
        if not self.slots.free_count:
            return False
        n = int(pkg.prompt_ids.size)
        total = pages_for(n, pkg.max_new_tokens, self.page_size)
        # fresh pages for the shipped contents, plus the same decode-tail
        # reservation a local admission would post
        return total <= self._alloc.available - self._reserved_total

    def admit_handoff(self, pkg):
        """Seat one migrated sequence; returns its (new, local) Request.
        The caller must have checked `_can_admit`."""
        # a token from elsewhere is seated with nothing in flight
        self.settle()
        n = int(pkg.prompt_ids.size)
        req = Request(pkg.prompt_ids, pkg.max_new_tokens,
                      eos_token_id=pkg.eos_token_id,
                      request_id=pkg.request_id,
                      temperature=pkg.temperature, top_p=pkg.top_p,
                      top_k=pkg.top_k, seed=pkg.seed)
        # seated on arrival: this side has no queue for it to wait in
        req.submit_time = req.admit_time = time.perf_counter()
        req.submit_step = self.step_count
        # the first token was emitted on the prefill side; seed the
        # emission count so eos/length accounting continues from it
        req.token_ids = [pkg.first]
        slot = self._admit(req)
        n_pages = pkg.num_pages
        pages = [self._alloc.alloc() for _ in range(n_pages)]
        with self._phase("stage", kind="copy", part="dispatch",
                         request_id=req.request_id, slot=slot):
            self.path.scatter_pages(pages, pkg.pages_k, pkg.pages_v)
        self._bt[slot] = pages
        resv = pages_for(n, pkg.max_new_tokens, self.page_size) - n_pages
        self._resv[slot] = resv
        self._reserved_total += resv
        # npos = next KV write position = the prompt length (the first
        # generated token's KV lands on the next decode step, exactly as
        # after a local prefill)
        self._npos[slot] = n
        self._last_tok[slot] = pkg.first
        self.path.seat(slot, pkg.first)
        self.metrics.inc("handoffs_admitted")
        if pkg.sent_at is not None:
            self.metrics.observe("handoff_latency_s",
                                 max(0.0, time.time() - pkg.sent_at))
        return req

    def _drain_inbox(self):
        while True:
            blob = self.transport.recv()
            if blob is None:
                break
            self._inbox.append(KVHandoff.from_bytes(blob))
        admitted = 0
        while self._inbox and self._can_admit(self._inbox[0]):
            self.admit_handoff(self._inbox.popleft())
            admitted += 1
        if self._inbox:
            self.metrics.inc("handoff_defer_steps")
        return admitted

    def _step_action(self):
        # before anything of this call is dispatched: seating a hand-off
        # settles what is in flight (`admit_handoff`)
        admitted = self._drain_inbox()
        ev = super()._step_action()
        if not admitted:
            return ev
        if ev["type"] == "idle":
            return {"type": "handoff_admit", "count": admitted}
        return dict(ev, admitted=admitted)

    @property
    def busy(self):
        return bool(self.slots.active_slots or self._inbox)

    def _retire(self, slot):
        req = self.slots.owner(slot)
        super()._retire(slot)
        if req is not None and self.completion_cb is not None:
            self.completion_cb(req)


class DisaggServer:
    """Single-process wiring: one PrefillWorker + one DecodeWorker over a
    `LocalTransport` (each with its own page pool, as two hosts would
    have). `submit()` goes to the prefill side; completions are mirrored
    back onto the submitted Request objects, so callers see the same
    surface a monolithic engine gives them."""

    def __init__(self, params, args, *, transport=None, **kw):
        self.transport = transport if transport is not None \
            else LocalTransport()
        self.prefill = PrefillWorker(params, args,
                                     transport=self.transport, **kw)
        self.decode = DecodeWorker(params, args, transport=self.transport,
                                   completion_cb=self._on_complete, **kw)
        self._orig = {}

    def _on_complete(self, twin):
        orig = self._orig.pop(twin.request_id, None)
        if orig is None or orig is twin:
            return
        # twin.token_ids[0] is the first token the prefill side already
        # emitted into orig — mirror the full list, not append
        orig.token_ids = list(twin.token_ids)
        orig.finished = twin.finished
        orig.finish_reason = twin.finish_reason
        orig.finish_time = twin.finish_time

    def submit(self, req):
        if not isinstance(req, Request):
            req = Request(req)
        self._orig[req.request_id] = req
        return self.prefill.submit(req)

    def step(self):
        self.prefill.step()
        self.decode.step()

    @property
    def busy(self):
        return bool(self.prefill.queue or self.prefill.slots.active_slots
                    or self.prefill._chunk_streams or self.transport.pending
                    or self.decode.busy)

    def run_until_idle(self):
        stalled = 0
        while self.busy:
            self.step()
            progressed = (self.prefill.queue
                          or self.prefill.slots.active_slots
                          or self.prefill._chunk_streams
                          or self.transport.pending
                          or self.decode.slots.active_slots)
            stalled = 0 if progressed else stalled + 1
            if stalled > 8 and self.decode._inbox:
                pkg = self.decode._inbox[0]
                raise RuntimeError(
                    f"decode worker cannot seat handoff "
                    f"{pkg.request_id!r}: needs "
                    f"{pages_for(pkg.prompt_ids.size, pkg.max_new_tokens, self.decode.page_size)} "
                    f"pages, pool has {self.decode._alloc.available} "
                    f"available")

    def serve(self, requests):
        reqs = [self.submit(r) for r in requests]
        self.run_until_idle()
        return reqs
