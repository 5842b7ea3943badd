"""Disaggregated prefill/decode serving + SLO-aware multi-model router.

The serving acceptance bar (ISSUE 20): a `DisaggServer` hand-off over
`LocalTransport` — the exact `KVHandoff.to_bytes()` byte path the
2-process rig ships — must be token-for-token the monolithic
`PagedEngine`'s output (bf16 pools AND int8 `QuantizedKVPage` pools),
a preempted-and-resumed batch request must finish with the IDENTICAL
completion, and the router must meter every request under
per-model/per-tenant labels. The cross-process leg itself lives in
`test_multiprocess.py` (`-m slow`).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import llama_functional as lf
from paddle_tpu.models.generation import generate
from paddle_tpu.serving.dense import (_extract_pages_traced,
                                      _scatter_pages_traced)
from paddle_tpu.serving.disagg import (DisaggServer, KVHandoff,
                                       LocalTransport)
from paddle_tpu.serving.engine import Request
from paddle_tpu.serving.paged_engine import PagedEngine
from paddle_tpu.serving.router import (
    BertBackend, EmbeddingRequest, GptEngine, Router)

ARGS = lf.LlamaArgs(vocab_size=128, hidden_size=64, intermediate_size=176,
                    num_layers=2, num_heads=4, num_kv_heads=2,
                    rope_theta=10000.0, rms_eps=1e-6, use_flash=False)
params = lf.init_params(ARGS, jax.random.key(0))
ENGINE_KW = dict(max_slots=4, max_len=64, page_size=8, min_bucket=8)


def _prompts(lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, ARGS.vocab_size, n).astype(np.int32)
            for n in lengths]


def _serve_monolithic(prompts, max_new=10, engine_kw=None, req_kw=None):
    eng = PagedEngine(params, ARGS, **dict(ENGINE_KW, **(engine_kw or {})))
    reqs = [Request(p, max_new, request_id=f"r{i}", **(req_kw or {}))
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_idle()
    return eng, [list(r.token_ids) for r in reqs]


def _serve_disagg(prompts, max_new=10, engine_kw=None, req_kw=None):
    srv = DisaggServer(params, ARGS, **dict(ENGINE_KW, **(engine_kw or {})))
    reqs = [Request(p, max_new, request_id=f"r{i}", **(req_kw or {}))
            for i, p in enumerate(prompts)]
    srv.serve(reqs)
    return srv, [list(r.token_ids) for r in reqs]


class TestKVHandoffWire:
    def _roundtrip(self, pkg):
        out = KVHandoff.from_bytes(pkg.to_bytes())
        assert out.request_id == pkg.request_id
        np.testing.assert_array_equal(out.prompt_ids, pkg.prompt_ids)
        assert (out.max_new_tokens, out.eos_token_id, out.seed,
                out.first) == (pkg.max_new_tokens, pkg.eos_token_id,
                               pkg.seed, pkg.first)
        assert (out.temperature, out.top_p, out.top_k) == \
            (pkg.temperature, pkg.top_p, pkg.top_k)
        return out

    def test_float_pages_roundtrip_bit_exact(self):
        import ml_dtypes

        rng = np.random.default_rng(0)
        for dt in (np.float32, ml_dtypes.bfloat16):
            pk = rng.standard_normal((2, 3, 2, 8, 16)).astype(dt)
            pv = rng.standard_normal((2, 3, 2, 8, 16)).astype(dt)
            pkg = KVHandoff(request_id="x", prompt_ids=[1, 2, 3],
                            max_new_tokens=4, eos_token_id=None,
                            temperature=0.0, top_p=1.0, top_k=0, seed=0,
                            first=7, pages_k=pk, pages_v=pv, sent_at=1.5)
            out = self._roundtrip(pkg)
            assert out.pages_k.dtype == dt
            np.testing.assert_array_equal(
                out.pages_k.view(np.uint8), pk.view(np.uint8))
            np.testing.assert_array_equal(
                out.pages_v.view(np.uint8), pv.view(np.uint8))
            assert out.sent_at == 1.5 and out.num_pages == 3

    def test_quantized_pages_roundtrip(self):
        from paddle_tpu.models.generation import QuantizedKVPage

        rng = np.random.default_rng(1)
        q = lambda: rng.integers(-128, 128, (2, 3, 2, 8, 16)).astype(np.int8)
        s = lambda: rng.random((2, 3, 2)).astype(np.float32)
        pkg = KVHandoff(request_id="q", prompt_ids=[4, 5],
                        max_new_tokens=2, eos_token_id=9, temperature=0.8,
                        top_p=0.9, top_k=5, seed=11, first=1,
                        pages_k=QuantizedKVPage(q(), s()),
                        pages_v=QuantizedKVPage(q(), s()))
        out = self._roundtrip(pkg)
        assert isinstance(out.pages_k, QuantizedKVPage)
        np.testing.assert_array_equal(out.pages_k.q, pkg.pages_k.q)
        np.testing.assert_array_equal(out.pages_k.scale, pkg.pages_k.scale)
        np.testing.assert_array_equal(out.pages_v.q, pkg.pages_v.q)
        assert out.nbytes() == pkg.nbytes()


class TestMigrationPrograms:
    def test_extract_scatter_roundtrip_oracle(self):
        """extract(pages) then scatter(fresh pool, new ids) lands the
        exact bytes at the new ids and touches nothing else."""
        rng = np.random.default_rng(2)
        pool = lambda: jnp.asarray(
            rng.standard_normal((2, 6, 2, 4, 8)).astype(np.float32))
        pk, pv = pool(), pool()
        src = jnp.asarray([4, 1, 3], jnp.int32)
        dk, dv = _extract_pages_traced(pk, pv, src)
        np.testing.assert_array_equal(np.asarray(dk),
                                      np.asarray(pk)[:, [4, 1, 3]])
        qk, qv = pool(), pool()
        before_k = np.asarray(qk).copy()
        dst = jnp.asarray([0, 5, 2], jnp.int32)
        qk, qv = _scatter_pages_traced(qk, qv, dst, dk, dv)
        np.testing.assert_array_equal(np.asarray(qk)[:, [0, 5, 2]],
                                      np.asarray(pk)[:, [4, 1, 3]])
        np.testing.assert_array_equal(np.asarray(qv)[:, [0, 5, 2]],
                                      np.asarray(pv)[:, [4, 1, 3]])
        untouched = [1, 3, 4]
        np.testing.assert_array_equal(np.asarray(qk)[:, untouched],
                                      before_k[:, untouched])


class TestDisaggParity:
    """LocalTransport hand-off == monolithic engine, token for token."""

    def _check(self, prompts, max_new=10, engine_kw=None, req_kw=None):
        _, ref = _serve_monolithic(prompts, max_new, engine_kw, req_kw)
        srv, got = _serve_disagg(prompts, max_new, engine_kw, req_kw)
        assert got == ref
        return srv

    def test_greedy_parity(self):
        srv = self._check(_prompts([11, 5, 17]))
        m = srv.prefill.metrics
        assert m.counter("handoffs_sent") == 3
        assert srv.decode.metrics.counter("handoffs_admitted") == 3
        assert m.counter("handoff_bytes") > 0
        assert srv.decode.metrics.observation(
            "handoff_latency_s")["count"] == 3

    def test_int8_parity(self):
        self._check(_prompts([11, 5, 17]),
                    engine_kw=dict(kv_dtype="int8"))

    def test_bf16_parity(self):
        global params
        saved = params
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16), saved)
        try:
            srv = self._check(_prompts([9, 13]))
        finally:
            params = saved
        # the pool dtype followed the params: bf16 rode the wire
        leaf = jax.tree_util.tree_leaves(srv.decode.path.pk)[0]
        assert leaf.dtype == jnp.bfloat16

    def test_chunked_prefill_parity(self):
        self._check(_prompts([37, 41]), max_new=8,
                    engine_kw=dict(prefill_chunk=16))

    def test_sampled_parity(self):
        self._check(_prompts([11, 5, 17]),
                    req_kw=dict(temperature=0.9, top_p=0.9, seed=7))

    def test_refcounts_drain_to_zero(self):
        srv = self._check(_prompts([11, 5, 17]))
        for worker in (srv.prefill, srv.decode):
            assert worker._alloc.pages_in_use == 0
            assert worker._reserved_total == 0
            assert worker.slots.free_count == worker.max_slots

    def test_handoff_defers_until_pages_free(self):
        """A decode pool too small for all hand-offs at once defers the
        overflow (metered) and still finishes every request correctly."""
        prompts = _prompts([17, 17, 17], seed=5)
        _, ref = _serve_monolithic(prompts, 12)
        transport = LocalTransport()
        from paddle_tpu.serving.disagg import DecodeWorker, PrefillWorker

        pre = PrefillWorker(params, ARGS, transport=transport, **ENGINE_KW)
        # 8 usable pages: one seated sequence (17+12 -> 4 pages) at a time
        # leaves the rest parked in the inbox
        dec = DecodeWorker(params, ARGS, transport=transport,
                           **dict(ENGINE_KW, max_slots=1, num_pages=9))
        done = {}
        dec.completion_cb = lambda twin: done.setdefault(
            twin.request_id, list(twin.token_ids))
        reqs = [Request(p, 12, request_id=f"r{i}")
                for i, p in enumerate(prompts)]
        for r in reqs:
            pre.submit(r)
        for _ in range(400):
            pre.step()
            dec.step()
            if not (pre.queue or pre.slots.active_slots
                    or transport.pending or dec.busy):
                break
        else:
            pytest.fail("disagg pair never drained")
        assert {rid: toks for rid, toks in done.items()} == \
            {f"r{i}": t for i, t in enumerate(ref)}
        assert dec.metrics.counter("handoff_defer_steps") > 0
        assert dec._alloc.pages_in_use == 0

    def test_prefill_worker_rejects_draft(self):
        from paddle_tpu.serving.disagg import PrefillWorker

        with pytest.raises(ValueError, match="speculative"):
            PrefillWorker(params, ARGS, transport=LocalTransport(),
                          draft_params=params, draft_args=ARGS, **ENGINE_KW)


class TestPreemptResume:
    def test_preempt_resume_identical_completion_and_refcounts(self):
        prompts = _prompts([11, 9])
        _, ref = _serve_monolithic([prompts[0]], 16)

        eng = PagedEngine(params, ARGS, **dict(ENGINE_KW, max_slots=2))
        victim = Request(prompts[0], 16, request_id="victim")
        eng.submit(victim)
        for _ in range(5):            # prefill + 4 decode steps
            eng.step()
        assert len(victim.token_ids) == 5
        slot = next(s for s in eng.slots.active_slots
                    if eng.slots.owner(s) is victim)
        held = list(eng._bt[slot])
        in_use_before = eng._alloc.pages_in_use
        state = eng.preempt(slot)
        # pages stay HELD (refcounts pinned) while preempted; the
        # reservation is refunded
        assert eng._alloc.pages_in_use == in_use_before
        assert all(eng._alloc.refcount(p) >= 1 for p in held)
        assert eng._reserved_total == 0
        assert eng.metrics.counter("preemptions") == 1

        other = Request(prompts[1], 8, request_id="other")
        eng.submit(other)
        eng.run_until_idle()
        assert other.finished and not victim.finished

        assert eng.can_resume(state)
        eng.resume(state)
        eng.run_until_idle()
        assert victim.finished
        assert list(victim.token_ids) == ref[0]
        assert eng.metrics.counter("resumes") == 1
        assert eng._alloc.pages_in_use == 0 and eng._reserved_total == 0

    def test_preempt_rejects_mid_chunk_stream(self):
        eng = PagedEngine(params, ARGS,
                          **dict(ENGINE_KW, prefill_chunk=16))
        req = Request(_prompts([40])[0], 4, request_id="c")
        eng.submit(req)
        eng.step()                    # first chunk only: stream is live
        assert eng._chunk_streams
        slot = next(iter(eng._chunk_streams))
        with pytest.raises(ValueError, match="preemptible"):
            eng.preempt(slot)
        eng.run_until_idle()


def _gpt_setup():
    from paddle_tpu.models.generation import (GPTGenArgs,
                                              gpt_params_from_layer)
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    cfg = GPTConfig(vocab_size=96, hidden_size=48, intermediate_size=96,
                    num_hidden_layers=2, num_attention_heads=4,
                    max_position_embeddings=64)
    model = GPTForCausalLM(cfg)
    return gpt_params_from_layer(model), GPTGenArgs.from_config(cfg)


class TestGptEngine:
    def test_greedy_parity_vs_whole_program(self):
        from paddle_tpu.models.generation import gpt_generate

        gparams, gargs = _gpt_setup()
        eng = GptEngine(gparams, gargs, max_slots=2, max_len=64,
                        min_bucket=8)
        rng = np.random.default_rng(4)
        prompts = [rng.integers(1, 96, n).astype(np.int32)
                   for n in (7, 12, 5)]
        reqs = [eng.submit(Request(p, 8, request_id=f"g{i}"))
                for i, p in enumerate(prompts)]
        eng.run_until_idle()
        for p, r in zip(prompts, reqs):
            ref = np.asarray(gpt_generate(gparams, gargs, p[None],
                                          max_new_tokens=8))[0]
            assert list(r.token_ids) == list(ref[len(p):]), r.request_id

    def test_max_len_bounded_by_position_table(self):
        gparams, gargs = _gpt_setup()
        with pytest.raises(ValueError, match="position"):
            GptEngine(gparams, gargs, max_slots=2, max_len=128,
                      min_bucket=8)


class TestBertBackend:
    def test_pooled_parity_and_batching(self):
        import paddle_tpu as paddle
        from paddle_tpu.models.bert import bert_tiny

        paddle.seed(0)
        model = bert_tiny()
        be = BertBackend(model, max_batch=4)
        rng = np.random.default_rng(6)
        prompts = [rng.integers(1, 1024, n).astype(np.int32)
                   for n in (5, 9, 7)]
        reqs = [be.submit(p) for p in prompts]
        be.run_until_idle()
        assert be.metrics.counter("embeds") == 1   # one padded batch
        for p, r in zip(prompts, reqs):
            assert r.finished and r.embedding is not None
            ids = paddle.to_tensor(p[None].astype(np.int64))
            mask = paddle.to_tensor(np.ones((1, p.size), np.int64))
            _, pooled = be.model(ids, attention_mask=mask)
            np.testing.assert_allclose(r.embedding,
                                       np.asarray(pooled.numpy())[0],
                                       atol=1e-5)

    def test_empty_prompt_rejected(self):
        with pytest.raises(ValueError, match="empty"):
            EmbeddingRequest([])


class TestRouter:
    def _llama_router(self, **engine_kw):
        eng = PagedEngine(params, ARGS, **dict(ENGINE_KW, **engine_kw))
        return Router({"llama": eng}), eng

    def test_slo_admission_ordering(self):
        """Interactive submitted AFTER batch still reaches the engine
        first; batch never feeds while interactive work waits."""
        router, eng = self._llama_router()
        prompts = _prompts([5, 5, 5], seed=8)
        b1 = router.submit("llama", prompts[0], slo="batch",
                           max_new_tokens=4)
        b2 = router.submit("llama", prompts[1], slo="batch",
                           max_new_tokens=4)
        i1 = router.submit("llama", prompts[2], slo="interactive",
                           max_new_tokens=4)
        router.step()
        # one feed per step, interactive-first despite arrival order
        active = eng.slots.active_slots
        assert active and eng.slots.owner(active[0]) is i1
        router.run_until_idle()
        assert all(r.finished for r in (b1, b2, i1))
        assert i1.finish_time <= b1.finish_time
        assert i1.finish_time <= b2.finish_time

    def test_preempt_resume_identical_via_router(self):
        """The acceptance bar: a batch request preempted for an
        interactive arrival resumes to the IDENTICAL completion."""
        _, ref = _serve_monolithic([_prompts([11])[0]], 24)

        router, eng = self._llama_router(max_slots=1, num_pages=9)
        batch = router.submit("llama", _prompts([11])[0], slo="batch",
                              tenant="nightly", max_new_tokens=24)
        for _ in range(6):
            router.step()
        assert not batch.finished
        inter = router.submit("llama", _prompts([11, 5], seed=9)[1],
                              tenant="acme", slo="interactive",
                              max_new_tokens=6)
        router.run_until_idle()
        assert inter.finished and batch.finished
        assert list(batch.token_ids) == ref[0]
        reg = router.metrics.registry
        assert reg.counter("router_preemptions",
                           labels={"model": "llama",
                                   "tenant": "nightly"}) == 1
        assert reg.counter("router_resumes",
                           labels={"model": "llama",
                                   "tenant": "nightly"}) == 1
        assert eng._alloc.pages_in_use == 0

    def test_per_tenant_per_model_labels(self):
        router, _ = self._llama_router()
        p = _prompts([5])[0]
        router.submit("llama", p, tenant="acme", max_new_tokens=3)
        router.submit("llama", p, tenant="acme", max_new_tokens=3)
        router.submit("llama", p, tenant="globex", slo="batch",
                      max_new_tokens=3)
        router.run_until_idle()
        reg = router.metrics.registry
        acme = {"model": "llama", "tenant": "acme", "slo": "interactive"}
        glob = {"model": "llama", "tenant": "globex", "slo": "batch"}
        assert reg.counter("router_requests", labels=acme) == 2
        assert reg.counter("router_completed", labels=acme) == 2
        assert reg.counter("router_requests", labels=glob) == 1
        assert reg.counter("router_tokens",
                           labels={"model": "llama",
                                   "tenant": "acme"}) == 6
        assert reg.observation("router_ttft_s",
                               labels={"model": "llama"})["count"] == 3
        snap = reg.snapshot()["counters"]["router_requests"]
        assert "model=llama,slo=interactive,tenant=acme" in snap

    def test_unknown_model_and_bad_slo(self):
        router, _ = self._llama_router()
        with pytest.raises(KeyError, match="unknown model"):
            router.submit("nope", [1, 2])
        with pytest.raises(ValueError, match="slo"):
            router.submit("llama", [1, 2], slo="bronze")

    def test_mixed_three_model_trace(self):
        import paddle_tpu as paddle
        from paddle_tpu.models.bert import bert_tiny

        gparams, gargs = _gpt_setup()
        paddle.seed(0)
        router = Router({
            "llama": PagedEngine(params, ARGS, **ENGINE_KW),
            "gpt": GptEngine(gparams, gargs, max_slots=2, max_len=64,
                             min_bucket=8),
            "bert": BertBackend(bert_tiny(), max_batch=4),
        })
        rng = np.random.default_rng(12)
        trace = []
        for i in range(4):
            trace.append({"model": "llama", "arrival_step": i,
                          "prompt": rng.integers(1, 128, 7).astype(np.int32),
                          "max_new_tokens": 5,
                          "tenant": ("acme", "globex")[i % 2],
                          "slo": ("interactive", "batch")[i % 2]})
        for i in range(3):
            trace.append({"model": "gpt", "arrival_step": i + 1,
                          "prompt": rng.integers(1, 96, 6).astype(np.int32),
                          "max_new_tokens": 4, "tenant": "acme"})
        for i in range(3):
            trace.append({"model": "bert", "arrival_step": i,
                          "prompt": rng.integers(1, 1024, 8)
                          .astype(np.int32), "tenant": "globex"})
        out = router.replay(trace)
        assert all(r.finished for r in out)
        assert all(r.embedding is not None
                   for r in out if isinstance(r, EmbeddingRequest))
        reg = router.metrics.registry
        for model in ("llama", "gpt", "bert"):
            total = sum(
                v for _k, v in
                reg.snapshot()["counters"]["router_completed"].items()
                if f"model={model}" in _k)
            assert total == {"llama": 4, "gpt": 3, "bert": 3}[model]
        depth = reg.gauge("router_queue_depth",
                          labels={"model": "llama", "slo": "batch"})
        assert depth == 0
