"""Sharded serving: tensor-parallel paged decode, chunked prefill,
speculative decoding, and the per-request sampler (ISSUE 14).

Key properties under test:
  - TP PARITY: the paged engine over a 2-device `mp` mesh (shard_map
    SPMD: Megatron weight shards, pool sharded on nkv, block tables
    replicated) emits token-for-token the sequential `generate` output;
  - sharded paged decode attention: slicing the pool's nkv axis and
    concatenating per-shard kernel outputs reproduces the full-pool
    attention (the kernel-level fact TP relies on), in Pallas interpret
    mode — the tier-1 parity gate for the sharded kernel path;
  - CHUNKED PREFILL: parity on long prompts (chunks compose with prefix
    hits), decode steps interleave between chunks, and short prompts
    bypass queued longs while a stream is in flight (anti-convoy);
  - SPECULATIVE DECODING: draft-propose + batched-verify emits exactly
    the target's greedy sequence (EOS/length retire mid-window included)
    and acceptance counters fill; SAMPLED requests speculate too, via
    Leviathan/Chen rejection sampling (accept draft token w.p.
    min(1, p_target/p_draft), resample the first rejection from the
    normalized positive residual) — seeded-reproducible, greedy rows in
    the same batch stay bit-exact, and a disagreeing draft exercises
    the resample branch;
  - SAMPLER: top-k composes with temperature/top-p, top_k=1 is greedy,
    per-request seeds make a request's tokens deterministic and
    independent of its batch-mates (the engine shares generate(seeds=)'s
    key stream, but bitwise sampled-token equality across cache layouts
    is not asserted — softmax reduces over different padded lengths).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import quantized_matmul as qm
from paddle_tpu.models import llama_functional as lf
from paddle_tpu.models.generation import (draft_from_params, generate,
                                          quantize_params)
from paddle_tpu.serving import PagedEngine, Request
from paddle_tpu.serving.tp import llama_tp_specs, tp_validate

ARGS = lf.LlamaArgs(vocab_size=128, hidden_size=64, intermediate_size=176,
                    num_layers=2, num_heads=4, num_kv_heads=2,
                    rope_theta=10000.0, rms_eps=1e-6, use_flash=False)


@pytest.fixture(scope="module")
def params():
    return lf.init_params(ARGS, jax.random.key(0))


@pytest.fixture(scope="module")
def mesh():
    from paddle_tpu.distributed.mesh_utils import single_axis_mesh

    return single_axis_mesh("mp", 2)


def _prompts(lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, ARGS.vocab_size, size=n).astype(np.int32)
            for n in lengths]


def _sequential(params, prompts, max_new, eos=None, **gen_kw):
    outs = []
    for p in prompts:
        row = np.asarray(generate(params, ARGS, p[None],
                                  max_new_tokens=max_new,
                                  eos_token_id=eos, **gen_kw))[0]
        outs.append(row[len(p):])
    return outs


class TestTPSpecs:
    def test_spec_tree_shapes(self, params):
        from jax.sharding import PartitionSpec as P

        specs = llama_tp_specs(params, "mp")
        assert specs["layers"]["wq"] == P(None, None, "mp")
        assert specs["layers"]["wo"] == P(None, "mp", None)
        assert specs["embedding"] == P()
        q = llama_tp_specs(quantize_params(params), "mp")
        assert q["layers"]["wq"].q == P(None, None, "mp")
        assert q["layers"]["wq"].scale == P(None, "mp")
        assert q["layers"]["w_down"].scale == P()   # out dim unsplit
        assert q["lm_head"].q == P()

    def test_tp_validate(self):
        tp_validate(ARGS, 2)
        with pytest.raises(ValueError, match="num_kv_heads"):
            tp_validate(ARGS, 4)   # nkv=2 does not divide 4

    def test_mesh_requires_divisible_heads(self, params, mesh):
        bad = ARGS._replace(num_kv_heads=1, num_heads=3)
        with pytest.raises(ValueError):
            PagedEngine(params, bad, max_slots=2, max_len=32, page_size=8,
                        min_bucket=8, mesh=mesh)


class TestTensorParallelParity:
    def test_tp2_matches_sequential(self, params, mesh):
        prompts = _prompts([3, 5, 9, 12])
        ref = _sequential(params, prompts, max_new=8)
        eng = PagedEngine(params, ARGS, max_slots=2, max_len=64,
                          page_size=8, min_bucket=8, mesh=mesh)
        assert eng.path.tp_degree == 2
        reqs = eng.serve([Request(p, 8) for p in prompts])
        for r, s in zip(reqs, ref):
            np.testing.assert_array_equal(np.asarray(r.token_ids), s)
        # the pool really is sharded over the mesh
        assert len(eng.path.pk.sharding.device_set) == 2

    @pytest.mark.slow
    def test_tp2_int8_with_prefix_hits(self, params, mesh):
        qp = quantize_params(params)
        rng = np.random.default_rng(11)
        sys_prefix = rng.integers(1, 128, size=16).astype(np.int32)
        prompts = [np.concatenate([sys_prefix,
                                   rng.integers(1, 128, size=k).astype(
                                       np.int32)]) for k in (3, 5, 7)]
        ref = _sequential(qp, prompts, max_new=6)
        eng = PagedEngine(qp, ARGS, max_slots=2, max_len=64, page_size=8,
                          min_bucket=8, mesh=mesh)
        reqs = eng.serve([Request(p, 6) for p in prompts])
        for r, s in zip(reqs, ref):
            np.testing.assert_array_equal(np.asarray(r.token_ids), s)
        assert eng.metrics.summary()["counters"]["prefix_tokens_hit"] > 0


class TestShardedPagedKernel:
    def test_nkv_shard_concat_matches_full(self):
        """Slicing the pool on nkv and concatenating per-shard outputs
        IS the full attention — the invariant that lets the TP engine
        run the paged kernel per-shard with replicated block tables.
        Runs the Pallas kernel in interpret mode (the tier-1 gate)."""
        rng = np.random.default_rng(0)
        b, nh, nkv, ps, hd, npages, P = 2, 4, 2, 8, 128, 9, 3
        pool_k = jnp.asarray(rng.normal(size=(npages, nkv, ps, hd)),
                             jnp.float32)
        pool_v = jnp.asarray(rng.normal(size=(npages, nkv, ps, hd)),
                             jnp.float32)
        q = jnp.asarray(rng.normal(size=(b, 1, nh, hd)), jnp.float32)
        bt = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
        pos = jnp.asarray([13, 20], jnp.int32)
        with qm.fused_dispatch(enabled=True, interpret=True):
            full = qm.paged_decode_attention(q, pool_k, pool_v, bt, pos)
            shards = []
            g = nh // nkv
            for i in range(nkv):
                qi = q.reshape(b, 1, nkv, g, hd)[:, :, i]
                shards.append(qm.paged_decode_attention(
                    qi, pool_k[:, i:i + 1], pool_v[:, i:i + 1], bt, pos))
        stitched = jnp.concatenate(shards, axis=2)
        np.testing.assert_allclose(np.asarray(stitched), np.asarray(full),
                                   rtol=2e-5, atol=2e-5)

    def test_verify_window_matches_stepwise_decode(self):
        """The verify window's attention (paged_gather +
        `_cached_attention`'s vector-pos s>1 branch) == s successive
        single-token paged decode attentions (write-then-attend)."""
        rng = np.random.default_rng(1)
        b, nh, nkv, ps, hd, npages, Pn, s = 2, 4, 2, 4, 16, 8, 4, 3
        pool_k = jnp.asarray(rng.normal(size=(npages, nkv, ps, hd)),
                             jnp.float32)
        pool_v = jnp.asarray(rng.normal(size=(npages, nkv, ps, hd)),
                             jnp.float32)
        bt = jnp.asarray([[1, 2, 3, 7], [4, 5, 6, 7]], jnp.int32)
        pos = np.asarray([5, 9], np.int32)
        q = jnp.asarray(rng.normal(size=(b, s, nh, hd)), jnp.float32)
        k_new = jnp.asarray(rng.normal(size=(b, s, nkv, hd)), jnp.float32)
        v_new = jnp.asarray(rng.normal(size=(b, s, nkv, hd)), jnp.float32)

        # window path: scatter all s tokens, then one verify attention
        pk_w, pv_w = pool_k, pool_v
        for i in range(s):
            pi = (pos + i) // ps
            page = jnp.take_along_axis(bt, pi[:, None], axis=1)[:, 0]
            off = (pos + i) % ps
            pk_w = pk_w.at[page, :, off].set(k_new[:, i])
            pv_w = pv_w.at[page, :, off].set(v_new[:, i])
        from paddle_tpu.models.generation import _cached_attention

        win = _cached_attention(q, qm.paged_gather(pk_w, bt),
                                qm.paged_gather(pv_w, bt),
                                jnp.asarray(pos))

        # step path: write token i then single-query attention at pos+i
        pk_s, pv_s = pool_k, pool_v
        outs = []
        for i in range(s):
            pi = (pos + i) // ps
            page = jnp.take_along_axis(bt, pi[:, None], axis=1)[:, 0]
            off = (pos + i) % ps
            pk_s = pk_s.at[page, :, off].set(k_new[:, i])
            pv_s = pv_s.at[page, :, off].set(v_new[:, i])
            outs.append(qm.paged_decode_attention(
                q[:, i:i + 1], pk_s, pv_s, bt, jnp.asarray(pos + i)))
        np.testing.assert_allclose(np.asarray(win),
                                   np.asarray(jnp.concatenate(outs, 1)),
                                   rtol=2e-5, atol=2e-5)


class TestWindowKernel:
    """`qm.window_decode_attention` — the Pallas fast path for a short
    query window at a traced offset (speculative verify; chunk-offset
    prefill tails) — against the masked-einsum oracle, interpret mode."""

    def _cache(self, rng, b, nkv, max_len, hd):
        k = jnp.asarray(rng.normal(size=(b, nkv, max_len, hd)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, nkv, max_len, hd)), jnp.float32)
        return k, v

    def test_vector_pos_window_matches_reference(self):
        rng = np.random.default_rng(0)
        b, s, nh, nkv, max_len, hd = 2, 4, 4, 2, 256, 16
        ck, cv = self._cache(rng, b, nkv, max_len, hd)
        q = jnp.asarray(rng.normal(size=(b, s, nh, hd)), jnp.float32)
        pos = jnp.asarray([5, 130], jnp.int32)   # spans a 128 block edge
        ref = qm._window_attention_xla(q, ck, cv, pos,
                                       1.0 / np.sqrt(hd))
        with qm.fused_dispatch(enabled=True, interpret=True):
            out = qm.window_decode_attention(q, ck, cv, pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_scalar_pos_chunk_offset_matches_reference(self):
        """The chunk-offset prefill shape: one row, queries at a scalar
        offset h."""
        rng = np.random.default_rng(1)
        b, s, nh, nkv, max_len, hd = 1, 8, 4, 4, 128, 32
        ck, cv = self._cache(rng, b, nkv, max_len, hd)
        q = jnp.asarray(rng.normal(size=(b, s, nh, hd)), jnp.float32)
        for h in (0, 16, 119):                  # incl. the table edge
            ref = qm._window_attention_xla(q, ck, cv, h, 1.0 / np.sqrt(hd))
            with qm.fused_dispatch(enabled=True, interpret=True):
                out = qm.window_decode_attention(q, ck, cv, h)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-5, atol=2e-5,
                                       err_msg=f"h={h}")

    def test_window_overhangs_cache_end(self):
        """A tail speculation window whose watermark lands past max_len:
        the kernel's key-block loop must clamp to the cache instead of
        reading past its end."""
        rng = np.random.default_rng(3)
        b, s, nh, nkv, max_len, hd = 2, 4, 2, 2, 128, 16
        ck = jnp.asarray(rng.normal(size=(b, nkv, max_len, hd)),
                         jnp.float32)
        cv = jnp.asarray(rng.normal(size=(b, nkv, max_len, hd)),
                         jnp.float32)
        q = jnp.asarray(rng.normal(size=(b, s, nh, hd)), jnp.float32)
        pos = jnp.asarray([126, 125], jnp.int32)  # pos + s - 1 >= max_len
        ref = qm._window_attention_xla(q, ck, cv, pos, 1.0 / np.sqrt(hd))
        with qm.fused_dispatch(enabled=True, interpret=True):
            out = qm.window_decode_attention(q, ck, cv, pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_window_supported_gates(self):
        ok = dict(q_shape=(2, 4, 4, 16), cache_shape=(2, 2, 256, 16))
        assert qm.window_supported(**ok)
        assert not qm.window_supported((2, 40, 4, 16), (2, 2, 256, 16)) \
            and 40 * 2 > qm._WINDOW_MAX_ROWS       # window too long
        assert not qm.window_supported((2, 4, 4, 16), (2, 2, 250, 16))
        assert not qm.window_supported((2, 4, 3, 16), (2, 2, 256, 16))

    def test_cached_attention_dispatches_window(self, monkeypatch):
        """`generation._cached_attention`'s s>1 branch rides the window
        kernel when eligible — the verify/chunk fast path."""
        from paddle_tpu.models import generation as gen

        called = {}
        real = qm.window_decode_attention

        def spy(*a, **kw):
            called["yes"] = True
            return real(*a, **kw)

        monkeypatch.setattr(qm, "window_decode_attention", spy)
        rng = np.random.default_rng(2)
        b, s, nh, nkv, max_len, hd = 2, 3, 4, 2, 128, 16
        ck, cv = self._cache(rng, b, nkv, max_len, hd)
        q = jnp.asarray(rng.normal(size=(b, s, nh, hd)), jnp.float32)
        pos = jnp.asarray([3, 60], jnp.int32)
        with qm.fused_dispatch(enabled=True, interpret=True):
            out = gen._cached_attention(q, ck, cv, pos)
        assert called.get("yes")
        ref = qm._window_attention_xla(q, ck, cv, pos, 1.0 / np.sqrt(hd))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestChunkedPrefill:
    def test_long_prompt_parity_with_prefix_hits(self, params):
        prompts = _prompts([29, 31], seed=7)
        ref = _sequential(params, prompts, max_new=6)
        eng = PagedEngine(params, ARGS, max_slots=2, max_len=64,
                          page_size=8, min_bucket=8, prefill_chunk=8)
        reqs = eng.serve([Request(p, 6) for p in prompts])
        for r, s in zip(reqs, ref):
            np.testing.assert_array_equal(np.asarray(r.token_ids), s)
        c = eng.metrics.summary()["counters"]
        assert c["chunked_prefills"] == 2
        assert c["prefill_chunks"] >= 6
        # serve the same prompts again: chunk boundaries must compose
        # with prefix-cache hits (whole pages now cached)
        reqs = eng.serve([Request(p, 6) for p in prompts])
        for r, s in zip(reqs, ref):
            np.testing.assert_array_equal(np.asarray(r.token_ids), s)
        assert eng.metrics.summary()["counters"]["prefix_tokens_hit"] > 0

    def test_decode_interleaves_with_chunks(self, params):
        """While a long prompt streams in chunks, an in-flight request
        keeps emitting tokens between chunks."""
        eng = PagedEngine(params, ARGS, max_slots=2, max_len=64,
                          page_size=8, min_bucket=8, prefill_chunk=8)
        (short,) = _prompts([4], seed=9)
        (longp,) = _prompts([30], seed=10)
        eng.submit(Request(short, 12))
        eng.step()                       # short prefilled, decoding
        eng.submit(Request(longp, 4))
        kinds = []
        while eng.queue or eng.slots.active_slots:
            kinds.append(eng.step()["type"])
        i_chunks = [i for i, k in enumerate(kinds)
                    if k == "prefill_chunk"]
        assert len(i_chunks) >= 2
        # at least one decode ran BETWEEN chunk steps — the interleave
        inner = kinds[i_chunks[0]:i_chunks[-1]]
        assert "decode" in inner

    def test_short_bypasses_queued_long(self, params):
        """Anti-convoy: while a stream is active, a short prompt behind
        a queued long is admitted first."""
        eng = PagedEngine(params, ARGS, max_slots=4, max_len=64,
                          page_size=8, min_bucket=8, prefill_chunk=8)
        long_a, long_b = _prompts([30, 29], seed=12)
        (short,) = _prompts([3], seed=13)
        ra = eng.submit(Request(long_a, 4))
        eng.step()                       # stream A starts
        rb = eng.submit(Request(long_b, 4))
        rs = eng.submit(Request(short, 4))
        eng.run_until_idle()
        assert rs.ttft_steps < rb.ttft_steps
        for r, s in zip([ra, rb, rs],
                        _sequential(params, [long_a, long_b, short],
                                    max_new=4)):
            np.testing.assert_array_equal(np.asarray(r.token_ids), s)

    def test_draft_prefill_chunks_with_target(self, params):
        """With chunking + speculation, the draft's prompt mirror
        advances window-by-window inside the stream's bounded steps (no
        monolithic draft prefill at the final chunk), and parity holds."""
        dp, da = draft_from_params(params, ARGS, 1)
        eng = PagedEngine(params, ARGS, max_slots=2, max_len=64,
                          page_size=8, min_bucket=8, prefill_chunk=8,
                          draft_params=dp, draft_args=da, spec_tokens=3)
        (longp,) = _prompts([30], seed=15)
        ref = _sequential(params, [longp], max_new=6)[0]
        req = eng.submit(Request(longp, 6))
        kinds = []
        while eng.queue or eng.slots.active_slots:
            kinds.append(eng.step()["type"])
        np.testing.assert_array_equal(np.asarray(req.token_ids), ref)
        c = eng.metrics.summary()["counters"]
        assert c["draft_prefill_chunks"] == 4          # ceil(30/8)
        assert "draft_prefill_chunk" in kinds
        assert c.get("draft_prefill_compiles", 0) >= 1

    def test_chunk_must_align_to_pages(self, params):
        with pytest.raises(ValueError, match="prefill_chunk"):
            PagedEngine(params, ARGS, max_slots=2, max_len=64, page_size=8,
                        min_bucket=8, prefill_chunk=12)

    def test_spec_round_preserves_mid_stream_draft_mirror(self, params):
        """A speculation round for the DECODING slot runs the draft scan
        over all stripe rows; the streaming slot's row must take its
        writes at the mirror frontier, not at 0 — otherwise each round
        clobbers the prefix `prefill_window` already mirrored and the
        draft mispredicts for every chunk-streamed prompt (output stays
        correct via exact-match acceptance, so only the KV check sees
        it)."""
        dp, da = draft_from_params(params, ARGS, 1)
        eng = PagedEngine(params, ARGS, max_slots=2, max_len=64,
                          page_size=8, min_bucket=8, prefill_chunk=8,
                          draft_params=dp, draft_args=da, spec_tokens=3)
        short, longp = _prompts([4, 33], seed=77)
        rs = eng.submit(Request(short, 12))
        rl = eng.submit(Request(longp, 4))
        eng.step()                    # short: monolithic prefill + mirror
        eng.step()                    # long: stream starts, target chunk 1
        ev = eng.step()               # draft window [0, 8)
        assert ev["type"] == "draft_prefill_chunk"
        lslot = next(iter(eng._chunk_streams))
        assert int(eng._spec._dpos[lslot]) == 8
        before_k = np.asarray(eng._spec._dck[:, lslot, :, :8])
        before_v = np.asarray(eng._spec._dcv[:, lslot, :, :8])
        ev = eng.step()               # spec round for the short slot
        assert ev["type"] == "spec_decode"
        np.testing.assert_array_equal(
            before_k, np.asarray(eng._spec._dck[:, lslot, :, :8]))
        np.testing.assert_array_equal(
            before_v, np.asarray(eng._spec._dcv[:, lslot, :, :8]))
        eng.run_until_idle()          # and end-to-end parity still holds
        for r, x, mn in ((rs, short, 12), (rl, longp, 4)):
            np.testing.assert_array_equal(
                np.asarray(r.token_ids),
                _sequential(params, [x], max_new=mn)[0])


class TestSpeculativeDecoding:
    @pytest.fixture(scope="class")
    def spec_engine(self, params):
        dp, da = draft_from_params(params, ARGS, 1)
        return PagedEngine(params, ARGS, max_slots=2, max_len=64,
                          page_size=8, min_bucket=8, draft_params=dp,
                          draft_args=da, spec_tokens=3)

    def test_greedy_parity_and_counters(self, params, spec_engine):
        prompts = _prompts([3, 5, 9, 12, 17])
        ref = _sequential(params, prompts, max_new=8)
        reqs = spec_engine.serve([Request(p, 8) for p in prompts])
        for r, s in zip(reqs, ref):
            np.testing.assert_array_equal(np.asarray(r.token_ids), s)
        c = spec_engine.metrics.summary()["counters"]
        assert c["spec_rounds"] > 0
        assert c["draft_tokens_proposed"] >= 3 * c["spec_rounds"]
        assert 0 <= c["draft_tokens_accepted"] <= c["draft_tokens_proposed"]

    def test_eos_retires_mid_window(self, params, spec_engine):
        prompts = _prompts([3, 5, 7], seed=11)
        base = _sequential(params, prompts, max_new=6)
        eos0 = int(base[0][2])
        ref = _sequential(params, prompts, max_new=6, eos=eos0)

        def upto(row):
            idx = np.nonzero(row == eos0)[0]
            return row[: idx[0] + 1] if idx.size else row

        reqs = spec_engine.serve(
            [Request(p, 6, eos_token_id=eos0) for p in prompts])
        for r, s in zip(reqs, ref):
            assert r.finished
            np.testing.assert_array_equal(np.asarray(r.token_ids), upto(s))
        assert spec_engine.slots.free_count == spec_engine.max_slots

    def test_sampled_request_speculates(self, params):
        """A sampling request no longer bounces off a spec engine: the
        round runs rejection-sampling acceptance. With draft == target
        the acceptance ratio is min(1, p/p) = 1, so drafts are accepted
        (up to last-ulp logit drift between the stripe and paged
        forwards) and the request completes through spec rounds."""
        eng = PagedEngine(params, ARGS, max_slots=2, max_len=64,
                          page_size=8, min_bucket=8, draft_params=params,
                          draft_args=ARGS, spec_tokens=3)
        (p,) = _prompts([6], seed=21)
        (req,) = eng.serve([Request(p, 8, temperature=0.7, seed=5)])
        assert req.finished and len(req.token_ids) == 8
        c = eng.metrics.summary()["counters"]
        assert c["spec_rounds"] > 0
        assert c["draft_tokens_accepted"] > 0

    def test_sampled_spec_reproducible(self, params):
        """The accept test and residual resample draw from salted
        branches of the request's (seed, position) stream — the same
        seed on a fresh engine reproduces the tokens exactly, a
        different seed diverges."""
        def run(seed):
            dp, da = draft_from_params(params, ARGS, 1)
            eng = PagedEngine(params, ARGS, max_slots=2, max_len=64,
                              page_size=8, min_bucket=8, draft_params=dp,
                              draft_args=da, spec_tokens=3)
            (p,) = _prompts([5], seed=23)
            (req,) = eng.serve([Request(p, 10, temperature=0.9, top_p=0.95,
                                        seed=seed)])
            return list(req.token_ids)

        assert run(7) == run(7)
        assert run(7) != run(8)

    def test_greedy_row_bit_exact_in_sampled_spec_batch(self, params,
                                                        spec_engine):
        """A greedy request batched with a sampling one keeps exact-match
        acceptance: its output is bit-identical to sequential greedy even
        though the round runs the sampled verify program."""
        gp, sp = _prompts([4, 6], seed=29)
        ref = _sequential(params, [gp], max_new=6)[0]
        greedy, sampled = spec_engine.serve(
            [Request(gp, 6), Request(sp, 6, temperature=0.8, seed=3)])
        np.testing.assert_array_equal(np.asarray(greedy.token_ids), ref)
        assert sampled.finished and len(sampled.token_ids) == 6

    def test_disagreeing_draft_hits_resample_branch(self, params):
        """A 1-layer truncated draft disagrees with the target often
        enough that some accept tests fail — the first rejection in a
        window must commit a residual-resampled token and bump
        `spec_resamples` (the branch an always-agreeing draft never
        takes)."""
        dp, da = draft_from_params(params, ARGS, 1)
        eng = PagedEngine(params, ARGS, max_slots=2, max_len=64,
                          page_size=8, min_bucket=8, draft_params=dp,
                          draft_args=da, spec_tokens=3)
        prompts = _prompts([4, 7], seed=31)
        reqs = eng.serve([Request(p, 12, temperature=1.0, seed=s)
                          for s, p in enumerate(prompts)])
        assert all(r.finished for r in reqs)
        c = eng.metrics.summary()["counters"]
        assert c["spec_resamples"] > 0
        assert c["draft_tokens_accepted"] < c["draft_tokens_proposed"]

    # the worst-case all-rejected rollback test (block tables +
    # refcounts bit-identical to plain decode after every round)
    # lives with the page-level coverage:
    # test_paged_kv.py::TestSpecDecodePaged

    def test_draft_from_params_validates(self, params):
        with pytest.raises(ValueError):
            draft_from_params(params, ARGS, 0)
        dp, da = draft_from_params(quantize_params(params), ARGS, 1)
        assert da.num_layers == 1
        assert dp["layers"]["wq"].q.shape[0] == 1


class TestSamplerMath:
    """Unit tests of the shared sampler math (`generation._sample` via
    `serving.sampler.pick`): greedy == argmax, top-p/top-k mask edges,
    per-request seed reproducibility — on crafted logits, no model."""

    def _logits(self, b=3, vocab=17, seed=0):
        rng = np.random.default_rng(seed)
        return jnp.asarray(rng.normal(size=(b, vocab)), jnp.float32)

    def test_greedy_pick_is_argmax(self):
        from paddle_tpu.serving.sampler import pick

        logits = self._logits()
        out = pick(logits, False, None, None, None, None, None)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(jnp.argmax(logits, axis=-1)))

    def test_top_k_mask_edges(self):
        from paddle_tpu.models import generation as gen

        logits = self._logits(b=1)
        top2 = set(np.asarray(
            jnp.argsort(logits[0])[::-1][:2]).tolist())
        for seed in range(20):
            keys = gen._row_keys(jnp.asarray([seed]), jnp.asarray([0]))
            tok = int(gen._sample(logits, True, jnp.float32(1.0),
                                  jnp.float32(1.0), None,
                                  jnp.int32(2), row_keys=keys)[0])
            assert tok in top2
        # k=1 is greedy; k=0 and k>=vocab are unrestricted (valid range)
        keys = gen._row_keys(jnp.asarray([3]), jnp.asarray([0]))
        k1 = gen._sample(logits, True, jnp.float32(2.0), jnp.float32(1.0),
                         None, jnp.int32(1), row_keys=keys)
        assert int(k1[0]) == int(jnp.argmax(logits[0]))
        for k in (0, 17, 99):
            tok = gen._sample(logits, True, jnp.float32(1.0),
                              jnp.float32(1.0), None, jnp.int32(k),
                              row_keys=keys)
            assert 0 <= int(tok[0]) < logits.shape[1]

    def test_top_p_mask_edges(self):
        from paddle_tpu.models import generation as gen

        logits = self._logits(b=2, seed=1)
        keys = gen._row_keys(jnp.asarray([5, 6]), jnp.asarray([0, 0]))
        # top_p -> 0 keeps only the argmax bucket: sampling == greedy
        tiny = gen._sample(logits, True, jnp.float32(1.0),
                           jnp.float32(1e-9), None, jnp.int32(0),
                           row_keys=keys)
        np.testing.assert_array_equal(
            np.asarray(tiny), np.asarray(jnp.argmax(logits, axis=-1)))
        # top_p = 1.0 is a no-op mask (every token reachable over seeds)
        seen = set()
        for seed in range(40):
            k = gen._row_keys(jnp.asarray([seed, seed + 99]),
                              jnp.asarray([0, 0]))
            toks = gen._sample(logits, True, jnp.float32(3.0),
                               jnp.float32(1.0), None, jnp.int32(0),
                               row_keys=k)
            seen.update(np.asarray(toks).tolist())
        assert len(seen) > 5   # hot temperature + no mask spreads wide

    def test_per_request_seed_reproducibility(self):
        from paddle_tpu.models import generation as gen

        logits = self._logits(b=2, seed=2)
        a = gen._sample(logits, True, jnp.float32(1.0), jnp.float32(0.9),
                        None, jnp.int32(4),
                        row_keys=gen._row_keys(jnp.asarray([7, 8]),
                                               jnp.asarray([3, 3])))
        b = gen._sample(logits, True, jnp.float32(1.0), jnp.float32(0.9),
                        None, jnp.int32(4),
                        row_keys=gen._row_keys(jnp.asarray([7, 8]),
                                               jnp.asarray([3, 3])))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestSampler:
    def test_top_k_one_is_greedy(self, params):
        (p,) = _prompts([5], seed=31)
        greedy = np.asarray(generate(params, ARGS, p[None],
                                     max_new_tokens=6))
        topk1 = np.asarray(generate(params, ARGS, p[None],
                                    max_new_tokens=6, temperature=0.8,
                                    top_k=1, seeds=np.asarray([7])))
        np.testing.assert_array_equal(greedy, topk1)

    def test_seeded_sampling_deterministic_and_seed_sensitive(self, params):
        (p,) = _prompts([5], seed=33)
        a = np.asarray(generate(params, ARGS, p[None], max_new_tokens=8,
                                temperature=1.0, seeds=np.asarray([3])))
        b = np.asarray(generate(params, ARGS, p[None], max_new_tokens=8,
                                temperature=1.0, seeds=np.asarray([3])))
        c = np.asarray(generate(params, ARGS, p[None], max_new_tokens=8,
                                temperature=1.0, seeds=np.asarray([4])))
        np.testing.assert_array_equal(a, b)
        assert not np.array_equal(a, c)

    def test_engine_seed_independent_of_batchmates(self, params):
        """A sampling request's tokens depend only on (seed, position):
        served alone or beside other traffic, the output is identical."""
        (p,) = _prompts([5], seed=35)
        others = _prompts([3, 7], seed=36)

        def serve(extra):
            eng = PagedEngine(params, ARGS, max_slots=2, max_len=64,
                              page_size=8, min_bucket=8)
            reqs = [Request(p, 6, temperature=0.9, top_p=0.9, top_k=8,
                            seed=42)]
            reqs += [Request(o, 6) for o in extra]
            return eng.serve(reqs)[0].token_ids

        alone = serve([])
        crowded = serve(others)
        assert alone == crowded
        assert len(alone) == 6

    def test_seeded_sampling_reproducible_across_engine_instances(
            self, params):
        """A seeded request reproduces its tokens on a FRESH engine of
        the same config (the keys are a pure function of (seed,
        position), and nothing else feeds the draw). NOTE: bitwise
        equality with offline `generate(seeds=...)` is deliberately NOT
        asserted — the key stream is shared, but paged vs stripe caches
        reduce softmax sums over different padded lengths, and a last-ulp
        logit difference can legitimately flip a sampled (never a
        greedy-argmax) token."""
        (p,) = _prompts([6], seed=37)

        def run():
            eng = PagedEngine(params, ARGS, max_slots=2, max_len=64,
                              page_size=8, min_bucket=8)
            (req,) = eng.serve([Request(p, 5, temperature=0.8, top_p=0.95,
                                        seed=9)])
            return req.token_ids

        a, b = run(), run()
        assert a == b and len(a) == 5

    def test_engine_key_stream_positions(self, params, monkeypatch):
        """Pin the shared-key-stream contract structurally: the engine's
        prefill samples with gen._row_keys(seed, n) and its decode with
        gen._row_keys(seed, pos+1) — the exact (seed, position) pairs
        `generate(seeds=...)` derives (rkeys(s) for the first token,
        rkeys(pos+1) in the scan). Bitwise token equality across cache
        layouts is not testable (padded-softmax ulps), but the key
        derivation sites are."""
        import paddle_tpu.serving.engine as eng_mod
        from paddle_tpu.models import generation as gen
        from paddle_tpu.serving.metrics import Metrics

        rec = []
        real = gen._sample

        def spy(logits, sample, temperature, top_p, key, top_k=0,
                row_keys=None):
            rec.append(row_keys)
            return real(logits, sample, temperature, top_p, key, top_k,
                        row_keys)

        monkeypatch.setattr(gen, "_sample", spy)
        n, seed, max_len = 4, 11, 16
        hd = ARGS.hidden_size // ARGS.num_heads
        L = lf.stack_leading_dim(params["layers"])
        ck = jnp.zeros((L, 1, ARGS.num_kv_heads, max_len, hd))
        cv = jnp.zeros_like(ck)
        cos, sin = lf.rope_tables(max_len, hd, ARGS.rope_theta)
        (ids,) = _prompts([n], seed=41)
        common = dict(args=ARGS, metrics=Metrics(), sample=True)
        sampling = (jnp.float32(1.0), jnp.float32(1.0), jnp.int32(0),
                    jnp.asarray([seed], jnp.int32))
        # eager (un-jitted) calls so the spy sees concrete key arrays
        ck, cv, first = eng_mod._prefill_traced(
            params, jnp.asarray(ids[None]), jnp.int32(n), ck, cv,
            jnp.int32(0), cos, sin, *sampling, **common)
        eng_mod._decode_traced(
            params, jnp.asarray([int(first)]), ck, cv,
            jnp.asarray([n], jnp.int32), cos, sin, *sampling, **common)
        assert len(rec) == 2 and all(k is not None for k in rec)
        expect = [gen._row_keys(jnp.asarray([seed]), jnp.asarray([p]))
                  for p in (n, n + 1)]
        for got, want in zip(rec, expect):
            np.testing.assert_array_equal(
                np.asarray(jax.random.key_data(got)),
                np.asarray(jax.random.key_data(want)))

    def test_reset_keeps_all_compile_counters(self, params):
        """Warm -> reset -> timed replay must not zero ANY trace-time
        compile counter (the telemetry contract: counters == programs
        built, and the timed pass hits the jit cache)."""
        from paddle_tpu.models.generation import draft_from_params

        dp, da = draft_from_params(params, ARGS, 1)
        eng = PagedEngine(params, ARGS, max_slots=2, max_len=64,
                          page_size=8, min_bucket=8, draft_params=dp,
                          draft_args=da, spec_tokens=3)
        (p,) = _prompts([5], seed=43)
        eng.serve([Request(p, 4)])
        eng.reset()
        c = eng.metrics.summary()["counters"]
        # (no decode_compiles here: a spec engine's decode IS the
        # propose/verify pair)
        for k in ("prefill_compiles", "verify_compiles",
                  "draft_propose_compiles", "draft_prefill_compiles"):
            assert c.get(k, 0) >= 1, (k, c)

    def test_greedy_rows_unperturbed_in_mixed_batch(self, params):
        """Greedy requests stay bit-exact argmax while sharing decode
        steps with sampling requests."""
        prompts = _prompts([4, 6], seed=39)
        ref = _sequential(params, [prompts[0]], max_new=6)[0]
        eng = PagedEngine(params, ARGS, max_slots=2, max_len=64,
                          page_size=8, min_bucket=8)
        reqs = eng.serve([Request(prompts[0], 6),
                          Request(prompts[1], 6, temperature=1.2,
                                  seed=5)])
        np.testing.assert_array_equal(np.asarray(reqs[0].token_ids), ref)


class TestDtypeParity:
    """Chunked prefill + speculative decoding keep exact greedy parity
    on bf16 and weight-only int8 trees, with and without prefix-cache
    hits (the second serve of each prompt is all hits)."""

    def _engine(self, p, chunk=16):
        dp, da = draft_from_params(p, ARGS, 1)
        return PagedEngine(p, ARGS, max_slots=2, max_len=64, page_size=8,
                           min_bucket=8, prefill_chunk=chunk,
                           draft_params=dp, draft_args=da, spec_tokens=3)

    def _roundtrip(self, p):
        prompts = _prompts([21, 5], seed=61)
        ref = [np.asarray(generate(p, ARGS, x[None],
                                   max_new_tokens=4))[0][len(x):]
               for x in prompts]
        eng = self._engine(p)
        for _ in range(2):    # second pass: prefix-cache hits
            reqs = eng.serve([Request(x, 4) for x in prompts])
            for r, s in zip(reqs, ref):
                np.testing.assert_array_equal(np.asarray(r.token_ids), s)
        assert eng.metrics.summary()["counters"]["prefix_tokens_hit"] > 0

    def test_bf16_chunk_spec_parity(self):
        self._roundtrip(lf.init_params(ARGS, jax.random.key(2),
                                       jnp.bfloat16))

    def test_int8_chunk_spec_parity(self, params):
        self._roundtrip(quantize_params(params))


@pytest.mark.slow
class TestShardedServingSoak:
    def test_all_features_mixed_trace(self, params, mesh):
        """TP x chunked x speculative x prefix hits on a mixed trace —
        full-stack greedy parity."""
        from tools.serving_trace import make_mixed_trace

        dp, da = draft_from_params(params, ARGS, 1)
        trace = make_mixed_trace(seed=5, n_short=10,
                                 short_len_choices=(3, 5, 9),
                                 n_long=2, long_len=40,
                                 mean_interarrival_steps=2.0,
                                 new_tokens_choices=(6,),
                                 long_new_tokens=6,
                                 vocab_size=ARGS.vocab_size)
        eng = PagedEngine(params, ARGS, max_slots=4, max_len=64,
                          page_size=8, min_bucket=8, mesh=mesh,
                          prefill_chunk=16, draft_params=dp,
                          draft_args=da, spec_tokens=3)
        reqs = eng.replay(trace)
        assert all(r.finished for r in reqs)
        for t, r in zip(trace, reqs):
            ref = _sequential(params, [t["prompt"]],
                              max_new=t["max_new_tokens"])[0]
            np.testing.assert_array_equal(np.asarray(r.token_ids), ref)


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-x", "-q"]))
