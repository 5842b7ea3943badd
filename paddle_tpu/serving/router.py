"""SLO-aware multi-model router: one front door over many engines.

The engines below this layer serve ONE model each and treat every
request alike. Production traffic is neither: a chat turn (a human
watching tokens appear) and an overnight batch summarization job hit the
same pool, many models share it, and "fair" FIFO is exactly wrong — the
batch job should soak up idle capacity and GET OUT OF THE WAY the moment
an interactive request needs a slot. This module adds that layer:

  - MULTI-MODEL: a `Router` fronts named backends — llama on a
    `PagedEngine`, GPT-2 on the new `GptEngine` (the stripe scheduler
    re-pointed at `_gpt_forward_cached`, per-row learned positions
    instead of RoPE), and BERT on `BertBackend`, a NON-AUTOREGRESSIVE
    model class: no KV cache, no decode loop — pending embedding
    requests batch into one padded forward per step.
  - SLO CLASSES: every request carries `slo="interactive"|"batch"`.
    The router holds its own per-class queues and feeds an engine's
    admission queue interactive-first; arrival order only breaks ties
    within a class.
  - PREEMPTION: when an interactive request is blocked (no slot / no
    pages) and a batch-class request holds a slot, the router calls the
    paged engine's `preempt()` — the victim's state is just its block
    table + page ids (refcounts still held, so the allocator can
    neither reuse nor evict them) and is `resume()`d once no
    interactive work is waiting, continuing BIT-IDENTICALLY to an
    uninterrupted run. Preempted requests outrank new batch admissions
    (no starvation-by-churn); interactive traffic can starve batch by
    design — that is what the class means.
  - PER-TENANT / PER-MODEL TELEMETRY: labeled series on the router's
    own `MetricsRegistry` — `router_requests` / `router_completed` /
    `router_tokens{model, tenant, slo}` counters, `router_ttft_s` and
    `router_tokens_per_s` histograms per model — exported through the
    same `--telemetry-out` artifact as every other subsystem.

The router is a host-side policy layer: it owns no device programs and
never reaches into a traced step — everything it does is queue surgery
between `step()` calls, so engine-level parity guarantees (greedy
token-for-token, seeded sampling) pass through untouched.
"""

from __future__ import annotations

import functools
import itertools
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.models import generation as gen
from paddle_tpu.models import llama_functional as lf
from paddle_tpu.serving.engine import Engine, Request
from paddle_tpu.serving.metrics import Metrics
from paddle_tpu.serving.sampler import pick as _pick
from paddle_tpu.serving.scheduler import bucket_for

__all__ = ["SLO_CLASSES", "GptEngine", "EmbeddingRequest", "BertBackend",
           "Router"]

SLO_CLASSES = ("interactive", "batch")


# -- GPT on the stripe scheduler --------------------------------------------
def _gpt_prefill_traced(params, ids, true_len, ck, cv, slot, temp, top_p,
                        top_k, seeds, *, args, metrics, sample=False):
    # runs once per COMPILE (trace time), not per call
    metrics.inc("prefill_compiles")
    L = ck.shape[0]
    sck = jnp.zeros((L, 1) + ck.shape[2:], ck.dtype)
    scv = jnp.zeros_like(sck)
    logits, sck, scv = gen._gpt_forward_cached(
        params, ids, sck, scv, 0, args, last_idx=true_len - 1)
    first = _pick(logits, sample, temp, top_p, top_k, seeds, true_len)[0]
    ck = jax.lax.dynamic_update_slice_in_dim(ck, sck, slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cv, scv, slot, axis=1)
    return ck, cv, first


def _gpt_decode_traced(params, tokens, ck, cv, pos, temp, top_p, top_k,
                       seeds, *, args, metrics, sample=False):
    metrics.inc("decode_compiles")
    logits, ck, cv = gen._gpt_forward_cached(
        params, tokens[:, None], ck, cv, pos, args)
    return ck, cv, _pick(logits, sample, temp, top_p, top_k, seeds, pos + 1)


class GptEngine(Engine):
    """The continuous-batching stripe scheduler serving GPT-2: same
    queue / slot table / retire-admit loop, with the two device programs
    swapped for `_gpt_forward_cached` (learned positions bound `max_len`
    by the position table; per-row decode positions ride the vmapped
    cache write the llama path uses). `params`/`args` come from
    `generation.gpt_params_from_layer` / `GPTGenArgs`."""

    def _setup_device_state(self):
        args = self.args
        if self.max_len > args.max_position_embeddings:
            raise ValueError(
                f"max_len={self.max_len} exceeds the learned position "
                f"table ({args.max_position_embeddings})")
        hd = lf.head_dim(args)
        self._ck = jnp.zeros((args.num_layers, self.max_slots,
                              args.num_heads, self.max_len, hd),
                             self.params["word_emb"].dtype)
        self._cv = jnp.zeros_like(self._ck)
        donate = self._donate_enabled()
        self._prefill = jax.jit(
            functools.partial(_gpt_prefill_traced, args=args,
                              metrics=self.metrics),
            donate_argnums=(3, 4) if donate else (),
            static_argnames=("sample",))
        self._decode = jax.jit(
            functools.partial(_gpt_decode_traced, args=args,
                              metrics=self.metrics),
            donate_argnums=(2, 3) if donate else (),
            static_argnames=("sample",))

    def _prefill_device(self, req, slot, n):
        bucket = bucket_for(n, self.min_bucket, self.max_len)
        padded = np.full((1, bucket), self.pad_id, np.int32)
        padded[0, :n] = req.prompt_ids
        self._ck, self._cv, first = self._prefill(
            self.params, jnp.asarray(padded), jnp.int32(n),
            self._ck, self._cv, jnp.int32(slot),
            jnp.float32(req.temperature), jnp.float32(req.top_p),
            jnp.int32(req.top_k), jnp.asarray([req.seed], jnp.int32),
            sample=req.temperature > 0)
        return bucket, int(first)

    def _decode_device(self, active):
        self._ck, self._cv, nxt = self._decode(
            self.params, jnp.asarray(self._last_tok), self._ck,
            self._cv, jnp.asarray(self._npos), *self._sampling_args(),
            sample=self._sampling_active())
        return np.asarray(nxt)


# -- BERT as a non-autoregressive model class -------------------------------
_embed_ids = itertools.count()


class EmbeddingRequest:
    """A non-autoregressive request: one forward, result on `.embedding`
    (the pooled [CLS] vector). Mirrors `Request`'s bookkeeping surface
    (submit/finish times, ttft) so the router meters both kinds alike."""

    def __init__(self, prompt_ids, request_id=None):
        self.prompt_ids = np.asarray(prompt_ids, np.int32).reshape(-1)
        if self.prompt_ids.size == 0:
            raise ValueError("empty prompt")
        self.request_id = (next(_embed_ids) if request_id is None
                           else request_id)
        self.max_new_tokens = 0
        self.token_ids = []
        self.embedding = None
        self.finished = False
        self.finish_reason = None
        self.submit_time = None
        self.first_token_time = None
        self.finish_time = None
        self.ttft_s = None


class BertBackend:
    """Serves a BERT encoder (`models/bert.bert_tiny()` or any
    `BertModel`-shaped layer) as embeddings: each `step()` takes up to
    `max_batch` pending requests, right-pads them to one length with a
    0/1 attention mask, and runs ONE eager forward. No KV state, so
    there is nothing to preempt — SLO ordering is feed order."""

    def __init__(self, model, *, max_batch=8, metrics=None):
        self.model = getattr(model, "bert", model)
        if hasattr(self.model, "eval"):
            self.model.eval()
        self.max_batch = int(max_batch)
        self.metrics = metrics if metrics is not None else Metrics()
        self.queue = deque()
        self.step_count = 0

    def submit(self, req):
        if not isinstance(req, EmbeddingRequest):
            req = EmbeddingRequest(req)
        req.submit_time = time.perf_counter()
        self.queue.append(req)
        self.metrics.inc("requests_submitted")
        return req

    @property
    def busy(self):
        return bool(self.queue)

    def step(self):
        self.step_count += 1
        if not self.queue:
            return {"type": "idle"}
        import paddle_tpu as paddle

        k = min(self.max_batch, len(self.queue))
        batch = [self.queue.popleft() for _ in range(k)]
        s = max(int(r.prompt_ids.size) for r in batch)
        ids = np.zeros((k, s), np.int64)
        mask = np.zeros((k, s), np.int64)
        for i, r in enumerate(batch):
            ids[i, :r.prompt_ids.size] = r.prompt_ids
            mask[i, :r.prompt_ids.size] = 1
        with self.metrics.timer("embed_step_s"):
            _, pooled = self.model(paddle.to_tensor(ids),
                                   attention_mask=paddle.to_tensor(mask))
            pooled = np.asarray(pooled.numpy())
        now = time.perf_counter()
        for i, r in enumerate(batch):
            r.embedding = pooled[i]
            r.finished = True
            r.finish_reason = "embedding"
            r.first_token_time = now
            r.finish_time = now
            r.ttft_s = now - r.submit_time
            self.metrics.observe("ttft_s", r.ttft_s)
        self.metrics.inc("requests_finished", k)
        self.metrics.inc("embeds")
        self.metrics.observe("embed_batch_size", k)
        return {"type": "embed", "count": k}

    def run_until_idle(self):
        while self.busy:
            self.step()


# -- the router --------------------------------------------------------------
class Router:
    """Front door over named backends (`Engine`/`PagedEngine`/`GptEngine`
    instances or `BertBackend`s). See the module docstring for policy;
    mechanically, each `step()` per backend does:

      feed      an interactive request whenever the engine's admission
                queue is empty; else resume a preempted batch request if
                nothing interactive waits and capacity allows; else feed
                a batch request (never while preempted work waits);
      preempt   if the blocked queue head is (or is behind) interactive
                work, no admission is possible, and a batch-class slot
                is decoding on a preemption-capable engine;
      step      the backend's own scheduler once.

    Completions are harvested after every sweep into labeled counters
    and histograms on `self.metrics.registry`.
    """

    def __init__(self, backends, *, metrics=None):
        if not backends:
            raise ValueError("router needs at least one backend")
        self.backends = dict(backends)
        self.metrics = metrics if metrics is not None else Metrics()
        self._waiting = {m: {slo: deque() for slo in SLO_CLASSES}
                         for m in self.backends}
        self._preempted = {m: deque() for m in self.backends}
        self._meta = {}        # id(req) -> (model, tenant, slo)
        self._inflight = []
        self.step_count = 0

    # -- admission -----------------------------------------------------------
    def submit(self, model, prompt_ids, *, tenant="default",
               slo="interactive", max_new_tokens=32, **kw):
        if model not in self.backends:
            raise KeyError(f"unknown model {model!r}; have "
                           f"{sorted(self.backends)}")
        if slo not in SLO_CLASSES:
            raise ValueError(f"slo must be one of {SLO_CLASSES}")
        backend = self.backends[model]
        if isinstance(backend, BertBackend):
            req = EmbeddingRequest(prompt_ids,
                                   request_id=kw.get("request_id"))
        else:
            req = Request(prompt_ids, max_new_tokens, **kw)
        self._meta[id(req)] = (model, tenant, slo)
        self._waiting[model][slo].append(req)
        self._inflight.append(req)
        self.metrics.registry.inc(
            "router_requests",
            labels={"model": model, "tenant": tenant, "slo": slo})
        return req

    def _slo_of(self, req):
        return self._meta.get(id(req), (None, None, "interactive"))[2]

    # -- policy --------------------------------------------------------------
    def _feed(self, model, engine):
        waiting = self._waiting[model]
        if len(engine.queue) > 0:
            return
        if waiting["interactive"]:
            engine.submit(waiting["interactive"].popleft())
            return
        pre = self._preempted[model]
        if pre:
            # preempted batch work outranks NEW batch admissions; while
            # it cannot fit, new batch feeds stay blocked too (they
            # would consume the pages the resume is waiting for)
            if engine.can_resume(pre[0]):
                state = pre.popleft()
                engine.resume(state)
                tenant = self._meta[id(state["req"])][1]
                self.metrics.registry.inc(
                    "router_resumes",
                    labels={"model": model, "tenant": tenant})
            return
        if waiting["batch"]:
            engine.submit(waiting["batch"].popleft())

    def _maybe_preempt(self, model, engine):
        if not hasattr(engine, "preempt"):
            return            # stripe engines checkpoint no KV state
        if not len(engine.queue) or engine._can_prefill():
            return
        head_is_interactive = (
            self._slo_of(engine.queue.peek()) == "interactive"
            or bool(self._waiting[model]["interactive"]))
        if not head_is_interactive:
            return
        # a victim is picked from what the rows HAVE emitted: a program in
        # flight is read first (its tokens may finish a row)
        engine.settle()
        streams = getattr(engine, "_chunk_streams", {})
        victims = [s for s in engine.slots.active_slots
                   if self._slo_of(engine.slots.owner(s)) == "batch"
                   and s not in streams]
        if not victims:
            return
        # evict the batch slot with the least decode progress (ties ->
        # highest slot): nothing is lost either way — resume continues
        # bit-identically — but the least-progressed victim frees its
        # reservation refund soonest
        victim = min(victims,
                     key=lambda s: (len(engine.slots.owner(s).token_ids),
                                    -s))
        req = engine.slots.owner(victim)
        state = engine.preempt(victim)
        self._preempted[model].append(state)
        tenant = self._meta[id(req)][1]
        self.metrics.registry.inc(
            "router_preemptions", labels={"model": model, "tenant": tenant})

    # -- the loop ------------------------------------------------------------
    def step(self):
        for model, backend in self.backends.items():
            if isinstance(backend, BertBackend):
                waiting = self._waiting[model]
                for slo in SLO_CLASSES:
                    while waiting[slo]:
                        backend.submit(waiting[slo].popleft())
                backend.step()
                continue
            self._feed(model, backend)
            self._maybe_preempt(model, backend)
            backend.step()
        self.step_count += 1
        self._harvest()
        self._export_depth()

    def _harvest(self):
        reg = self.metrics.registry
        still = []
        for req in self._inflight:
            if not req.finished:
                still.append(req)
                continue
            model, tenant, slo = self._meta.pop(id(req))
            labels = {"model": model, "tenant": tenant, "slo": slo}
            reg.inc("router_completed", labels=labels)
            reg.inc("router_tokens", len(req.token_ids),
                    labels={"model": model, "tenant": tenant})
            if req.ttft_s is not None:
                reg.observe("router_ttft_s", req.ttft_s,
                            labels={"model": model})
            dur = (req.finish_time or 0) - (req.submit_time or 0)
            if req.token_ids and dur > 0:
                reg.observe("router_tokens_per_s",
                            len(req.token_ids) / dur,
                            labels={"model": model})
        self._inflight = still

    def _export_depth(self):
        reg = self.metrics.registry
        for model, waiting in self._waiting.items():
            for slo in SLO_CLASSES:
                reg.set_gauge("router_queue_depth", len(waiting[slo]),
                              labels={"model": model, "slo": slo})
            reg.set_gauge("router_preempted_held",
                          len(self._preempted[model]),
                          labels={"model": model})

    def _backend_busy(self, backend):
        if isinstance(backend, BertBackend):
            return backend.busy
        return bool(len(backend.queue) or backend.slots.active_slots
                    or getattr(backend, "_chunk_streams", None))

    @property
    def busy(self):
        return bool(self._inflight
                    or any(self._backend_busy(b)
                           for b in self.backends.values())
                    or any(self._preempted.values()))

    def run_until_idle(self):
        while self.busy:
            self.step()

    def serve(self, requests):
        """Submit a list of dicts (`model`, `prompt` + Request kwargs +
        optional `tenant`/`slo`), run to completion, return the request
        objects in order."""
        out = [self.submit(r["model"], r["prompt"],
                           tenant=r.get("tenant", "default"),
                           slo=r.get("slo", "interactive"),
                           max_new_tokens=r.get("max_new_tokens", 32),
                           **{k: r[k] for k in ("temperature", "top_p",
                                                "top_k", "seed",
                                                "eos_token_id",
                                                "request_id") if k in r})
               for r in requests]
        self.run_until_idle()
        return out

    def replay(self, trace):
        """Replay an arrival trace: `tools/serving_trace` entries plus
        `model` (+ optional `tenant`/`slo`) keys; arrival steps are
        ROUTER steps. Returns the request objects in trace order."""
        pending = sorted(trace, key=lambda t: t["arrival_step"])
        out = {}
        i = 0
        while i < len(pending) or self.busy:
            while (i < len(pending)
                   and pending[i]["arrival_step"] <= self.step_count):
                t = pending[i]
                kw = {k: t[k] for k in ("temperature", "top_p", "top_k",
                                        "seed", "eos_token_id",
                                        "request_id") if k in t}
                out[id(t)] = self.submit(
                    t["model"], t["prompt"],
                    tenant=t.get("tenant", "default"),
                    slo=t.get("slo", "interactive"),
                    max_new_tokens=t.get("max_new_tokens", 8), **kw)
                i += 1
            self.step()
        return [out[id(t)] for t in trace]
