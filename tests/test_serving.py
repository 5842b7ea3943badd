"""Continuous-batching serving engine (paddle_tpu/serving/).

Key properties under test:
  - PARITY: greedy continuous-batched decode is token-for-token identical
    to sequential `generate` on mixed-length prompts (bf16/f32 and
    weight-only int8 param trees; CPU runs the jnp fallback — the Pallas
    per-row kernel is parity-tested in tests/test_quantized_matmul.py);
  - iteration-level scheduling: EOS rows retire immediately and their
    slot is re-admitted to the next waiting request;
  - streaming callbacks fire in emission order;
  - compilation is BOUNDED: a trace with >= 8 distinct prompt lengths
    compiles at most #length-buckets prefill programs + 1 decode program;
  - the per-row pos-vector decode path matches the scalar path on a
    uniform batch, and inactive slots cannot perturb active rows.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import llama_functional as lf
from paddle_tpu.models.generation import (decode_step, generate, prefill,
                                          quantize_params)
from paddle_tpu.serving import Engine, Request, bucket_for

from phase_ids import (check_identifiers, entries, record_annotations,
                       step_and_check_dispatch)
from step_phases import counting_clock, run_and_collect

ARGS = lf.LlamaArgs(vocab_size=128, hidden_size=64, intermediate_size=176,
                    num_layers=2, num_heads=4, num_kv_heads=2,
                    rope_theta=10000.0, rms_eps=1e-6, use_flash=False)


@pytest.fixture(scope="module")
def params():
    return lf.init_params(ARGS, jax.random.key(0))


@pytest.fixture(scope="module")
def engine(params):
    # ONE engine shared across tests (state fully drains between serves;
    # compiled programs are reused, keeping the tier-1 subset fast)
    return Engine(params, ARGS, max_slots=2, max_len=64, min_bucket=8)


def _prompts(lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, ARGS.vocab_size, size=n).astype(np.int32)
            for n in lengths]


def _sequential(params, prompts, max_new, eos=None):
    """The offline path: one compiled generate per request."""
    outs = []
    for p in prompts:
        row = np.asarray(generate(params, ARGS, p[None],
                                  max_new_tokens=max_new,
                                  eos_token_id=eos))[0]
        outs.append(row[len(p):])
    return outs


def _upto_eos(row, eos):
    """generate() pads after the EOS; the engine stops emitting — compare
    up to and including the first EOS."""
    idx = np.nonzero(row == eos)[0]
    return row[: idx[0] + 1] if idx.size else row


class TestParity:
    def test_greedy_matches_sequential_mixed_lengths(self, params, engine):
        prompts = _prompts([3, 5, 9, 12, 17])
        ref = _sequential(params, prompts, max_new=8)
        reqs = engine.serve([Request(p, 8) for p in prompts])
        for r, s in zip(reqs, ref):
            assert r.finished and r.finish_reason == "length"
            np.testing.assert_array_equal(np.asarray(r.token_ids), s)

    def test_greedy_matches_sequential_int8(self, params):
        qp = quantize_params(params)
        prompts = _prompts([4, 7, 13], seed=5)
        ref = _sequential(qp, prompts, max_new=6)
        eng = Engine(qp, ARGS, max_slots=2, max_len=64, min_bucket=8)
        reqs = eng.serve([Request(p, 6) for p in prompts])
        for r, s in zip(reqs, ref):
            np.testing.assert_array_equal(np.asarray(r.token_ids), s)

    def test_output_ids_prepends_prompt(self, params, engine):
        (p,) = _prompts([6], seed=9)
        (req,) = engine.serve([Request(p, 4)])
        out = req.output_ids()
        np.testing.assert_array_equal(out[:6], p)
        assert out.shape == (10,)


class TestScheduling:
    def test_eos_retires_and_slot_readmits(self, params, engine):
        # 3 requests on 2 slots; a greedy token of request 0 becomes its
        # EOS, freeing a slot mid-flight for the queued third request. The
        # EOS is the first token from the third on that has not occurred
        # before it (the seeded model may repeat itself: [88, 112, 112, ..]),
        # and the request has to end where that token FIRST occurs
        prompts = _prompts([3, 5, 7], seed=11)
        base = _sequential(params, prompts, max_new=6)
        row0 = [int(t) for t in base[0]]
        at = next((i for i in range(2, len(row0)) if row0[i] not in row0[:i]),
                  2)
        eos0 = row0[at]
        stop = row0.index(eos0)
        assert stop >= 1    # it retires mid-flight, not at its prefill
        ref = _sequential(params, prompts, max_new=6, eos=eos0)
        reqs = engine.serve(
            [Request(p, 6, eos_token_id=eos0) for p in prompts])
        for r, s in zip(reqs, ref):
            assert r.finished
            np.testing.assert_array_equal(np.asarray(r.token_ids),
                                          _upto_eos(s, eos0))
        assert reqs[0].finish_reason == "eos"
        assert len(reqs[0].token_ids) == stop + 1
        assert reqs[0].token_ids[-1] == eos0
        # every slot drained back to the table
        assert engine.slots.free_count == engine.max_slots

    def test_eos_on_first_token_retires_at_prefill(self, params, engine):
        (p,) = _prompts([5], seed=13)
        first = int(_sequential(params, [p], max_new=1)[0][0])
        (req,) = engine.serve([Request(p, 8, eos_token_id=first)])
        assert req.finish_reason == "eos"
        assert req.token_ids == [first]

    def test_streaming_callback_order(self, params, engine):
        events = []

        def cb(req, tok, finished):
            events.append((req.request_id, tok, finished))

        prompts = _prompts([3, 8, 11], seed=17)
        reqs = engine.serve([Request(p, 5, stream_cb=cb) for p in prompts])
        for r in reqs:
            mine = [(t, f) for rid, t, f in events if rid == r.request_id]
            assert [t for t, _ in mine] == r.token_ids  # emission order
            assert [f for _, f in mine] == [False] * 4 + [True]

    def test_compile_count_bounded(self, params):
        # >= 8 distinct prompt lengths but only 2 power-of-two buckets:
        # at most #buckets prefill compiles + 1 decode compile
        lengths = [2, 3, 4, 5, 7, 9, 11, 15]
        prompts = _prompts(lengths, seed=19)
        buckets = {bucket_for(n, 8, 32) for n in lengths}
        eng = Engine(params, ARGS, max_slots=2, max_len=32, min_bucket=8)
        eng.serve([Request(p, 2) for p in prompts])
        m = eng.metrics.summary()["counters"]
        assert m["prefill_compiles"] <= len(buckets)
        assert m["decode_compiles"] == 1
        assert m["prefill_compiles"] + m["decode_compiles"] <= \
            len(buckets) + 1

    def test_capacity_validation(self, params, engine):
        (p,) = _prompts([10], seed=23)
        with pytest.raises(ValueError, match="slot capacity"):
            engine.submit(Request(p, engine.max_len))
        with pytest.raises(ValueError, match="largest bucket"):
            engine.submit(Request(np.ones(engine.max_len + 1, np.int32), 1))


class TestPosVector:
    def test_vector_pos_matches_scalar_on_uniform_batch(self, params):
        ids = np.array([[5, 11, 7, 2], [9, 3, 1, 8]], np.int32)
        logits, ck, cv = prefill(params, ARGS, ids, max_len=16)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        l_s, ck_s, cv_s = decode_step(params, ARGS, tok, ck, cv, 4, 16)
        l_v, ck_v, cv_v = decode_step(params, ARGS, tok, ck, cv,
                                      jnp.asarray([4, 4], jnp.int32), 16)
        np.testing.assert_array_equal(np.asarray(l_s), np.asarray(l_v))
        np.testing.assert_array_equal(np.asarray(ck_s), np.asarray(ck_v))
        np.testing.assert_array_equal(np.asarray(cv_s), np.asarray(cv_v))

    def test_inactive_rows_do_not_perturb_active(self, params):
        ids = np.array([[5, 11, 7, 2], [9, 3, 1, 8]], np.int32)
        logits, ck, cv = prefill(params, ARGS, ids, max_len=16)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        pos = jnp.asarray([4, 0], jnp.int32)
        l_a, _, _ = decode_step(params, ARGS, tok, ck, cv, pos, 16)
        # corrupt row 1's cache + token wholesale; row 0 must be bitwise
        # unchanged (rows are independent in the batched decode)
        junk = jax.random.normal(jax.random.key(1), ck.shape, ck.dtype)
        ck_j = ck.at[:, 1].set(junk[:, 1])
        cv_j = cv.at[:, 1].set(-junk[:, 1])
        tok_j = tok.at[1].set(121)
        l_b, _, _ = decode_step(params, ARGS, tok_j, ck_j, cv_j, pos, 16)
        np.testing.assert_array_equal(np.asarray(l_a)[0],
                                      np.asarray(l_b)[0])


class TestMetrics:
    def test_queue_ttft_occupancy_recorded(self, params, engine):
        prompts = _prompts([3, 4, 5, 6], seed=29)
        reqs = engine.serve([Request(p, 3) for p in prompts])
        m = engine.metrics.summary()
        # 4 requests on 2 slots: the queue was visibly non-empty
        assert m["gauges"]["queue_depth"]["max"] >= 1
        assert m["gauges"]["queue_depth"]["value"] == 0
        occ = m["observations"]["slot_occupancy"]
        assert 0 < occ["max"] <= 1
        assert m["observations"]["ttft_s"]["count"] >= len(prompts)
        for r in reqs:
            assert r.ttft_s is not None and r.ttft_s >= 0

    def test_tokens_accounting(self, params):
        prompts = _prompts([3, 9], seed=31)
        eng = Engine(params, ARGS, max_slots=2, max_len=32, min_bucket=8)
        reqs = eng.serve([Request(p, 4) for p in prompts])
        m = eng.metrics.summary()["counters"]
        assert m["tokens_generated"] == sum(len(r.token_ids) for r in reqs)
        assert m["requests_finished"] == len(reqs)


class TestSpeculativeParity:
    """Greedy speculative decoding through the paged engine emits
    token-for-token the sequential `generate` stream — bf16 AND int8
    trees (draft and target quantize together). The page-level
    mechanics (tail pages, rollback) are covered in test_paged_kv.py;
    here the property is pure end-to-end output parity."""

    def _spec_serve(self, p, prompts, max_new):
        from paddle_tpu.models.generation import draft_from_params
        from paddle_tpu.serving import PagedEngine

        dp, da = draft_from_params(p, ARGS, 1)
        eng = PagedEngine(p, ARGS, max_slots=2, max_len=64, page_size=8,
                          min_bucket=8, draft_params=dp, draft_args=da,
                          spec_tokens=3)
        reqs = eng.serve([Request(x, max_new) for x in prompts])
        c = eng.metrics.summary()["counters"]
        assert c["spec_rounds"] > 0   # speculation actually ran
        return reqs

    def test_spec_greedy_matches_sequential_bf16(self):
        bp = lf.init_params(ARGS, jax.random.key(2), jnp.bfloat16)
        prompts = _prompts([5, 12, 21], seed=81)
        ref = [np.asarray(generate(bp, ARGS, x[None],
                                   max_new_tokens=6))[0][len(x):]
               for x in prompts]
        for r, s in zip(self._spec_serve(bp, prompts, 6), ref):
            np.testing.assert_array_equal(np.asarray(r.token_ids), s)

    def test_spec_greedy_matches_sequential_int8(self, params):
        qp = quantize_params(params)
        prompts = _prompts([5, 12, 21], seed=82)
        ref = _sequential(qp, prompts, max_new=6)
        for r, s in zip(self._spec_serve(qp, prompts, 6), ref):
            np.testing.assert_array_equal(np.asarray(r.token_ids), s)


class TestPrefillDoneVsTTFT:
    """`ttft_s` is recorded at the first EMITTED token and
    `prefill_done_s` when the prompt is fully in the target's KV cache.
    On a monolithic prefill they land on the same step; under chunked
    prefill with a speculative draft the emission waits for the draft
    mirror's windows, so the two diverge — telemetry keeps both."""

    def test_monolithic_records_both_same_step(self, params, engine):
        (p,) = _prompts([9], seed=91)
        (r,) = engine.serve([Request(p, 3)])
        assert r.prefill_done_steps == r.ttft_steps
        assert 0 <= r.prefill_done_s <= r.ttft_s
        m = engine.metrics.summary()["observations"]
        assert m["prefill_done_s"]["count"] >= 1
        assert m["ttft_s"]["count"] >= 1

    def test_chunked_spec_first_emit_after_prefill_done(self, params):
        from paddle_tpu.models.generation import draft_from_params
        from paddle_tpu.serving import PagedEngine

        dp, da = draft_from_params(params, ARGS, 1)
        eng = PagedEngine(params, ARGS, max_slots=2, max_len=64,
                          page_size=8, min_bucket=8, prefill_chunk=8,
                          draft_params=dp, draft_args=da, spec_tokens=3)
        (p,) = _prompts([21], seed=92)
        (r,) = eng.serve([Request(p, 3)])
        # the target's final chunk lands while the draft mirror still has
        # windows to stream: prompt-cached and first-emit are different
        # engine steps
        assert r.prefill_done_steps < r.ttft_steps
        assert r.prefill_done_s <= r.ttft_s
        m = eng.metrics.summary()["observations"]
        assert m["prefill_done_steps"]["max"] < m["ttft_steps"]["max"]


class TestProfileWiring:
    def test_predictor_records_wall_time_and_calls(self, tmp_path):
        import paddle_tpu as paddle
        from paddle_tpu import nn
        from paddle_tpu.inference import Config, create_predictor
        from paddle_tpu.static import InputSpec

        lin = nn.Linear(4, 3)
        prefix = str(tmp_path / "m")
        paddle.jit.save(lin, prefix,
                        input_spec=[InputSpec([2, 4], "float32", "x")])
        cfg = Config(prefix)
        cfg.enable_profile()
        pred = create_predictor(cfg)
        for _ in range(3):
            pred.run([np.ones((2, 4), np.float32)])
        s = pred.summary()
        assert s["counters"]["run_calls"] == 3
        wall = s["observations"]["run_wall_s"]
        assert wall["count"] == 3 and wall["sum"] > 0
        # profiling off -> no metrics, summary None
        pred2 = create_predictor(Config(prefix))
        pred2.run([np.ones((2, 4), np.float32)])
        assert pred2.summary() is None


@pytest.mark.slow
class TestSoak:
    def test_arrival_trace_replay_parity(self, params):
        from tools.serving_trace import make_trace, trace_stats

        trace = make_trace(seed=7, n_requests=24,
                           mean_interarrival_steps=2.0,
                           new_tokens_choices=(4, 8, 12),
                           vocab_size=ARGS.vocab_size)
        assert trace_stats(trace)["distinct_prompt_lens"] >= 6
        eng = Engine(params, ARGS, max_slots=4, max_len=64, min_bucket=8)
        reqs = eng.replay(trace)
        assert all(r.finished for r in reqs)
        # spot-check parity on a few requests against sequential generate
        for t, r in list(zip(trace, reqs))[::5]:
            ref = _sequential(params, [t["prompt"]],
                              max_new=t["max_new_tokens"])[0]
            np.testing.assert_array_equal(np.asarray(r.token_ids), ref)
        m = eng.metrics.summary()
        assert m["counters"]["requests_finished"] == len(trace)
        assert m["counters"]["decode_compiles"] == 1


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-x", "-q"]))


class TestStepPhases:
    """Every step() of the stripe engine is one `pt.serve.step` span split
    over schedule / stage / wait / emit, one sample of each per step."""

    @pytest.mark.parametrize("step_type", ["prefill", "decode"])
    def test_four_phases_tile_the_step(self, engine, monkeypatch, step_type):
        counting_clock(monkeypatch)
        for p in _prompts([5, 9, 4], seed=21):
            engine.submit(Request(p, 3))
        seen = run_and_collect(engine, ["prefill", "decode"])
        for phases in seen[step_type]:
            # the dispatch and the read-back each hold at least their own
            # two clock reads' gap; what a prefill or a decode emits is
            # timed as emit
            assert phases["stage"] >= 1 and phases["wait"] >= 1
            assert phases["emit"] >= 1

    def test_dispatch_is_one_sample_a_step_within_stage(self, engine,
                                                        monkeypatch):
        counting_clock(monkeypatch)
        for p in _prompts([5, 9], seed=24):
            engine.submit(Request(p, 3))
        seen = set()
        while engine.queue or engine.slots.active_slots:
            ev, phases, dispatch = step_and_check_dispatch(engine)
            # the jitted call's own two clock reads' gap at the least; a
            # decode step's stage is all dispatch, a prefill pads first
            assert dispatch >= 1
            if ev["type"] == "decode":
                assert dispatch == phases["stage"]
            else:
                assert dispatch < phases["stage"]
            seen.add(ev["type"])
        assert seen == {"prefill", "decode"}

    def test_phase_entries_say_kind_part_and_size(self, engine, monkeypatch):
        seen = record_annotations(monkeypatch)
        (prompt,) = _prompts([11], seed=25)
        engine.serve([Request(prompt, 3, request_id="ids")])
        check_identifiers(seen)
        window = dict(kind="prefill", tokens=11,
                      bucket=bucket_for(11, engine.min_bucket,
                                        engine.max_len),
                      start=0, request_id="ids")
        assert [ids["part"] for ids in entries(seen, "stage", **window)] \
            == ["build", "dispatch"]
        assert len(entries(seen, "wait", **window)) == 1
        # one request in flight: every decode step has one row
        decodes = entries(seen, "stage", kind="decode")
        assert decodes and all(ids["rows"] == 1 and ids["part"] == "dispatch"
                               for ids in decodes)
        assert len(entries(seen, "wait", kind="decode", rows=1)) \
            == len(decodes)

    def test_admit_time_and_queue_wait(self, engine):
        before = engine.metrics.observation("queue_wait_s")
        before = before["count"] if before else 0
        reqs = engine.serve([Request(p, 2)
                             for p in _prompts([4, 6, 8], seed=22)])
        for r in reqs:
            assert r.admit_time >= r.submit_time
            assert r.first_token_time >= r.admit_time
        # two slots, three requests: the third waited for a slot
        assert reqs[2].admit_time > reqs[0].first_token_time
        got = engine.metrics.observation("queue_wait_s")
        assert got["count"] - before == len(reqs)

    def test_removed_observations_stay_removed(self, engine):
        engine.serve([Request(p, 2) for p in _prompts([4], seed=23)])
        obs = engine.metrics.summary()["observations"]
        assert "decode_step_s" not in obs
        assert "tokens_per_decode_step" not in obs
        assert "prefill_s" not in obs
