"""Draft-model speculative decoding over the paged serving engine.

Leviathan-style greedy speculation (Leviathan et al. 2023), organized so
the whole round is two device dispatches regardless of the draft length:

  PROPOSE — a cheap draft model (e.g. `generation.draft_from_params`
  truncation) runs `spec_tokens` greedy decode steps over its OWN stripe
  cache in ONE traced scan. Step j of row r feeds the committed tokens
  the draft hasn't ingested yet (forced-feed catch-up — after a fully
  accepted round the draft is one token behind the target) and its own
  previous output after that.

  VERIFY — the target model scores the whole window [last committed
  token, draft_1..draft_g] in ONE batched paged forward
  (`generation._paged_forward_verify`): token i of row r at position
  pos[r]+i, K/V scattered into the row's tail pages write-before-attend,
  writes past the row's page reservation redirected to the null page.

  ACCEPT — greedy rows commit the longest exactly-matching prefix plus
  the target's own next token: between 1 and g+1 tokens per round, every
  one of them exactly the target's greedy sequence (speculation changes
  the schedule, never the output). Sampling rows use Leviathan rejection
  sampling instead: draft token i is accepted with probability
  min(1, p_target(d)/p_draft(d)); the first rejection commits ONE token
  resampled from the adjusted residual normalize(max(0, p_t - p_d)), a
  fully accepted window commits a bonus token from the target's next
  distribution through the sequential per-request (seed, pos) gumbel
  stream. Every committed token is exactly target-distributed, and when
  draft == target the ratio is 1 so the output is token-for-token the
  sequential seeded sample (parity-tested).

  ROLL BACK — rejected tail tokens are erased by truncating the
  watermark (`_npos`) and the BLOCK TABLE: tail pages allocated for the
  window that end up wholly past the new watermark are released back to
  the pool and their reservation refunded, so after a worst-case
  all-rejected round the block table and page refcounts are bit-identical
  to a plain decode step's (tested). The partially-filled tail page keeps
  its rejected K/V as garbage — the write-before-attend order overwrites
  it before the position mask ever exposes it. Shared/registered tail
  pages are COW'd before the window writes, exactly as plain decode.

The draft stays REPLICATED under a tensor-parallel mesh (its whole point
is being cheap); only the target-side verify shards.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from paddle_tpu.models import generation as gen
from paddle_tpu.models import llama_functional as lf
from paddle_tpu.serving.block_manager import NULL_PAGE
from paddle_tpu.serving.scheduler import bucket_for

__all__ = ["SpecDecoder"]


def _paged_verify_traced(params, ids, pk, pv, bt, pos, limit, cos, sin, *,
                         args, metrics, page_size, tp_axis=None,
                         tp_degree=1):
    """Target-model half of a speculation round: score the whole draft
    window [b, g+1] in one forward (token i of row r at position
    pos[r]+i), writing its K/V into the tail pages (positions past
    limit[r] go to the null page). Returns the target's greedy token at
    every window position — the host accepts the longest exact match."""
    metrics.inc("verify_compiles")
    logits, pk, pv = gen._paged_forward_verify(
        params, ids, pk, pv, bt, pos, limit, cos, sin, args, page_size,
        tp_axis=tp_axis, tp_degree=tp_degree)
    return pk, pv, jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _draft_window_traced(params, ids, h, ck, cv, slot, cos, sin, *, args,
                         metrics):
    """One prefill WINDOW of the draft's stripe cache: forward ids
    [1, sb] at traced offset h, writing KV slots [h, h+sb) of `slot`'s
    stripe (earlier windows' KV below h is already in place — the same
    suffix-at-a-deeper-h trick the target's chunked prefill uses, minus
    the prefix cache: the draft has none, so its windows start at 0).
    Logits are discarded — the draft only needs the KV."""
    metrics.inc("draft_prefill_compiles")
    sb = ids.shape[1]
    max_len = ck.shape[3]
    sck = jax.lax.dynamic_slice_in_dim(ck, slot, 1, axis=1)
    scv = jax.lax.dynamic_slice_in_dim(cv, slot, 1, axis=1)
    # pad the scratch stripe by the bucket so the write at [h, h+sb) can
    # never clamp (the overshoot trick the target's suffix prefill uses);
    # the pad tail is sliced off before writing back
    pad = jnp.zeros(sck.shape[:3] + (sb,) + sck.shape[4:], sck.dtype)
    tk = jnp.concatenate([sck, pad], axis=3)
    tv = jnp.concatenate([scv, pad], axis=3)
    _, tk, tv = gen._forward_cached(params, ids, tk, tv, h, cos, sin,
                                    args, last_idx=0)
    ck = jax.lax.dynamic_update_slice_in_dim(
        ck, jax.lax.slice_in_dim(tk, 0, max_len, axis=3), slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(
        cv, jax.lax.slice_in_dim(tv, 0, max_len, axis=3), slot, axis=1)
    return ck, cv


def _paged_verify_sampled_traced(params, ids, pk, pv, bt, pos, limit, cos,
                                 sin, temp, top_p, top_k, *, args, metrics,
                                 page_size, tp_axis=None, tp_degree=1):
    """Verify variant for rejection-sampling rounds: same paged window
    forward, but alongside the greedy argmax it returns the target's
    WARPED distribution at every window position (softmax over the
    shared `_warp_logits` masking) — the p_target the host acceptance
    test and residual resample consume. Greedy rounds keep the slimmer
    `_paged_verify_traced` program (and its captured golden)."""
    metrics.inc("verify_compiles")
    logits, pk, pv = gen._paged_forward_verify(
        params, ids, pk, pv, bt, pos, limit, cos, sin, args, page_size,
        tp_axis=tp_axis, tp_degree=tp_degree)
    S, W, V = logits.shape
    masked, _ = gen._warp_logits(logits.reshape(S * W, V),
                                 jnp.repeat(temp, W), jnp.repeat(top_p, W),
                                 jnp.repeat(top_k, W))
    probs = jax.nn.softmax(masked, axis=-1).reshape(S, W, V)
    return (pk, pv, jnp.argmax(logits, axis=-1).astype(jnp.int32), probs)


def _draft_propose_traced(params, forced, n_forced, start, ck, cv, cos,
                          sin, temp, top_p, top_k, seeds, *, args, metrics,
                          steps, sample=False):
    """Draft-model propose: `steps` decode steps over the draft's stripe
    cache in ONE traced scan (one device dispatch per round, not per
    token). Step j of row r feeds forced[r, j] while j < n_forced[r] —
    the committed tokens the draft hasn't ingested yet (its own last
    token, plus one catch-up token after a fully-accepted round) — and
    its own previous output after that, at position start[r] + j.

    sample=False (greedy rounds) proposes by argmax. sample=True draws
    step j's token from the draft's WARPED distribution via the
    request's own (seed, position) gumbel stream — the `_row_keys`
    stream sequential `generate(seeds=...)` uses, at the proposed
    token's sequence index start + j + 1 — and additionally returns
    those warped distributions [S, steps, vocab]: the p_draft of the
    host's accept-with-prob-min(1, p_target/p_draft) test. Greedy rows
    (temperature <= 0) inside a mixed batch still propose exact argmax
    (`_sample`'s greedy_rows path)."""
    metrics.inc("draft_propose_compiles")

    def stepf(carry, xs):
        prev, ck, cv = carry
        j, forced_j = xs
        tok = jnp.where(j < n_forced, forced_j, prev)
        logits, ck, cv = gen._forward_cached(
            params, tok[:, None], ck, cv, start + j, cos, sin, args)
        if sample:
            out = gen._sample(logits, True, temp, top_p, None, top_k,
                              row_keys=gen._row_keys(seeds, start + j + 1))
            masked, _ = gen._warp_logits(logits, temp, top_p, top_k)
            probs = jax.nn.softmax(masked, axis=-1)
        else:
            out = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            probs = jnp.zeros((), jnp.float32)
        return (out, ck, cv), (out, probs)

    (_, ck, cv), (outs, probs) = jax.lax.scan(
        stepf, (forced[:, 0], ck, cv),
        (jnp.arange(steps, dtype=jnp.int32), jnp.swapaxes(forced, 0, 1)))
    outs = jnp.swapaxes(outs, 0, 1)            # [S, steps]
    if sample:
        return ck, cv, outs, jnp.swapaxes(probs, 0, 1)  # +[S, steps, V]
    return ck, cv, outs


_ACCEPT_SALT = 0xAC          # acceptance-test uniform branch
_RESAMPLE_SALT = 0x5E        # residual-resample gumbel branch


def _spec_key(seed, pos, salt):
    """Host-side PRNG key for one (request, position) decision in a
    rejection-sampling round: a salted branch of the request's
    `_row_keys` (seed, position) stream — deterministic across
    schedules (batch composition, chunking, preemption never change
    it), and independent of the gumbel draws that CHOSE the draft
    token (reusing those would correlate the accept test with the
    proposal and bias the output distribution)."""
    k = jax.random.fold_in(jax.random.key(0), seed)
    k = jax.random.fold_in(k, pos)
    return jax.random.fold_in(k, salt)


def _residual_draw(residual, seed, pos):
    """Sample from the normalized residual max(0, p_t - p_d)/Z via
    gumbel-max on its log — the adjusted distribution that makes the
    round's committed token EXACTLY target-distributed (Leviathan et
    al. 2023, Thm. 1) regardless of draft quality."""
    gumbel = np.asarray(jax.random.gumbel(
        _spec_key(seed, pos, _RESAMPLE_SALT), residual.shape))
    logp = np.where(residual > 0, np.log(np.maximum(residual, 1e-30)),
                    -np.inf)
    return int(np.argmax(logp + gumbel))


class SpecDecoder:
    """The speculative half of a `PagedEngine`: owns the draft model's
    stripe cache + programs and the target's sharded verify program, and
    runs the propose → verify → accept → roll-back round. Mutates the
    engine's block tables / positions / reservations in place — it IS
    the engine's decode step while a draft model is loaded."""

    def __init__(self, engine, donate):
        from paddle_tpu.serving.engine import _prefill_traced

        self.eng = engine
        self.g = engine.spec_tokens
        dargs = engine.draft_args
        if self.g < 1:
            raise ValueError("spec_tokens must be >= 1")
        if dargs.vocab_size != engine.args.vocab_size:
            raise ValueError("draft and target must share a vocab")
        self.draft_params = engine.draft_params
        self.draft_args = dargs
        Ld = lf.stack_leading_dim(self.draft_params["layers"])
        dhd = lf.head_dim(dargs)
        ddtype = self.draft_params["embedding"].dtype
        self._dck = jnp.zeros(
            (Ld, engine.max_slots, dargs.num_kv_heads, engine.max_len,
             dhd), ddtype)
        self._dcv = jnp.zeros_like(self._dck)
        # 2*max_len tables: window prefills forward a bucket at offset h,
        # and h+bucket can overshoot max_len before masking trims it (the
        # same overshoot the target's suffix prefill pads for)
        self._dcos, self._dsin = lf.rope_tables(2 * engine.max_len, dhd,
                                                dargs.rope_theta)
        self._dpos = np.zeros(engine.max_slots, np.int32)
        self._draft_prefill = jax.jit(
            functools.partial(_prefill_traced, args=dargs,
                              metrics=engine.metrics,
                              counter="draft_prefill_compiles"),
            donate_argnums=(3, 4) if donate else (),
            static_argnames=("sample",))
        self._draft_window = jax.jit(
            functools.partial(_draft_window_traced, args=dargs,
                              metrics=engine.metrics),
            donate_argnums=(3, 4) if donate else ())
        # g+1 draft steps, not g: after a fully-accepted round the draft
        # is one token behind the target (lag 1), and the extra step keeps
        # every verify column backed by a FRESH proposal — lag then
        # stabilizes at <= 1 instead of climbing on repetitive text while
        # clamped duplicate drafts keep matching
        self._draft_propose = jax.jit(
            functools.partial(_draft_propose_traced, args=dargs,
                              metrics=engine.metrics, steps=self.g + 1),
            donate_argnums=(4, 5) if donate else (),
            static_argnames=("sample",))
        # the target's half: over the dense path's pools, in its placement
        rep, path = P(), engine.path
        pool = path.poolspec
        tp_kw = dict(
            args=engine.args, metrics=engine.metrics,
            page_size=engine.page_size,
            tp_axis=engine.tp_axis if engine.mesh is not None else None,
            tp_degree=path.tp_degree)
        self._verify = path.sharded(
            functools.partial(_paged_verify_traced, **tp_kw),
            in_specs=(path.pspecs, rep, pool, pool, rep, rep, rep, rep, rep),
            out_specs=(pool, pool, rep),
            donate=(2, 3) if donate else ())
        # the rejection-sampling verify also returns the warped target
        # distributions; built lazily-adjacent here so greedy-only
        # engines never trace it
        self._verify_sampled = path.sharded(
            functools.partial(_paged_verify_sampled_traced, **tp_kw),
            in_specs=(path.pspecs, rep, pool, pool, rep, rep, rep, rep, rep,
                      rep, rep, rep),
            out_specs=(pool, pool, rep, rep),
            donate=(2, 3) if donate else ())

    # -- lifecycle -----------------------------------------------------------
    def prefill_slot(self, req, slot, n):
        """Mirror the finished prompt into the draft's stripe cache."""
        eng = self.eng
        bucket = bucket_for(n, eng.min_bucket, eng.max_len)
        padded = np.full((1, bucket), eng.pad_id, np.int32)
        padded[0, :n] = req.prompt_ids
        with eng._phase("stage", kind="draft", part="dispatch",
                        request_id=req.request_id, slot=slot, tokens=n,
                        bucket=bucket, start=0):
            self._dck, self._dcv, _ = self._draft_prefill(
                self.draft_params, jnp.asarray(padded), jnp.int32(n),
                self._dck, self._dcv, jnp.int32(slot), self._dcos,
                self._dsin, jnp.float32(0.0), jnp.float32(1.0),
                jnp.int32(0), jnp.asarray([0], jnp.int32), sample=False)
        self._dpos[slot] = n

    def prefill_window(self, req, slot, start, end):
        """Advance the draft's mirror of a chunk-streamed prompt by one
        window [start, end) — the draft prefill rides the same bounded
        scheduler steps as the target's chunks instead of running the
        whole prompt monolithically at the final chunk (which would
        reintroduce exactly the stall chunking removes). Windows start
        at 0: the draft has no prefix cache."""
        eng = self.eng
        n = int(req.prompt_ids.size)
        sb = bucket_for(end - start, eng.min_bucket, eng.max_len)
        padded = np.full((1, sb), eng.pad_id, np.int32)
        padded[0, :end - start] = req.prompt_ids[start:end]
        with eng._phase("stage", kind="draft", part="dispatch",
                        request_id=req.request_id, slot=slot,
                        tokens=end - start, bucket=sb, start=start):
            self._dck, self._dcv = self._draft_window(
                self.draft_params, jnp.asarray(padded), jnp.int32(start),
                self._dck, self._dcv, jnp.int32(slot), self._dcos,
                self._dsin)
        # track the mirror frontier as windows land (not just at end == n):
        # speculation rounds for OTHER slots run the propose scan over all
        # S rows, and a row's scan writes land at _dpos[row] — pointing a
        # mid-stream row's writes at its frontier keeps them on positions
        # the next window rewrites anyway, instead of clobbering the
        # already-mirrored prefix at 0
        self._dpos[slot] = end

    def retire(self, slot):
        self._dpos[slot] = 0

    def reset(self):
        self._dpos[:] = 0

    # -- the round -----------------------------------------------------------
    def _seq_token(self, req, idx):
        """Committed token at sequence index idx (prompt, then outputs)."""
        n = req.prompt_ids.size
        return int(req.prompt_ids[idx]) if idx < n \
            else int(req.token_ids[idx - n])

    def _limit(self, slot):
        """A row's last legal KV write index — the top of its
        admission-time page reservation (`scheduler.pages_for`)."""
        req = self.eng.slots.owner(slot)
        return int(req.prompt_ids.size) + req.max_new_tokens - 2

    def _propose_device(self, forced, n_forced, start, sample=False):
        """One draft-scan dispatch (separate method so tests can stub an
        adversarial draft). Returns (outs, probs) — probs is None on
        greedy rounds."""
        eng = self.eng
        with eng._phase("stage", kind="draft", part="dispatch"):
            out = self._draft_propose(
                self.draft_params, jnp.asarray(forced),
                jnp.asarray(n_forced), jnp.asarray(start), self._dck,
                self._dcv, self._dcos, self._dsin,
                *eng.sampler.device_args(), sample=sample)
        with eng._phase("wait", kind="draft"):
            if sample:
                self._dck, self._dcv, outs, probs = out
                return np.asarray(outs), np.asarray(probs)
            self._dck, self._dcv, outs = out
            return np.asarray(outs), None             # [S, steps]

    def step(self):
        """One speculation round: draft proposes g tokens (one traced
        scan), the target verifies the whole window (one batched paged
        forward), the host commits the longest exactly-matching prefix
        plus the target's next token — between 1 and g+1 tokens per
        round, all of them exactly the target's greedy sequence — then
        rolls the block table back to the new watermark."""
        eng = self.eng
        active = eng._decodable_slots()
        S, g = eng.max_slots, self.g
        steps = g + 1
        Pn = eng.pages_per_slot

        # ---- propose -----------------------------------------------------
        # the scan runs over ALL S rows; non-active rows (free, or a
        # prompt mid-chunked-prefill) still get pad-fed writes at
        # start[r] + j, so start MUST be each row's own frontier (_dpos):
        # writes then hit positions later windows / decode steps rewrite,
        # never the valid mirrored prefix below the frontier
        with eng._phase("stage", kind="draft", part="build"):
            forced = np.zeros((S, steps), np.int32)
            n_forced = np.ones(S, np.int32)
            start = np.asarray(self._dpos, np.int32).copy()
            lag = {}
            for slot in active:
                req = eng.slots.owner(slot)
                lag[slot] = int(eng._npos[slot]) - int(self._dpos[slot])
                start[slot] = self._dpos[slot]
                n_forced[slot] = lag[slot] + 1
                for j in range(min(lag[slot] + 1, steps)):
                    forced[slot, j] = self._seq_token(
                        req, int(self._dpos[slot]) + j)
            sampling = eng._sampling_active()
        outs, dprobs = self._propose_device(forced, n_forced, start,
                                            sampling)

        # ---- tail pages for the verify window ----------------------------
        limit = np.full(S, -1, np.int32)
        for slot in active:
            limit[slot] = self._limit(slot)
            eng._ensure_tail_pages(
                slot, min(int(eng._npos[slot]) + g, int(limit[slot])))

        # ---- verify ------------------------------------------------------
        step_ids = dict(kind="verify", rows=len(active))
        with eng._phase("stage", part="build", **step_ids):
            ids = np.full((S, g + 1), eng.pad_id, np.int32)
            for slot in active:
                ids[slot, 0] = eng._last_tok[slot]
                for i in range(1, g + 1):
                    j = lag[slot] + i - 1        # draft for index npos+i
                    # lag <= 1 keeps j within the proposals (defensive
                    # clamp against an adversarial/stubbed shorter propose)
                    ids[slot, i] = outs[slot, min(j, outs.shape[1] - 1)]
            bt = np.full((S, Pn), NULL_PAGE, np.int32)
            for slot in active:
                bt[slot, :len(eng._bt[slot])] = eng._bt[slot]
        path = eng.path
        tprobs = None
        with eng._phase("stage", part="dispatch", **step_ids):
            if sampling:
                path.pk, path.pv, tgt, tprobs = self._verify_sampled(
                    eng.params, jnp.asarray(ids), path.pk, path.pv,
                    jnp.asarray(bt), jnp.asarray(eng._npos),
                    jnp.asarray(limit), path.cos, path.sin,
                    *eng.sampler.device_args()[:3])
            else:
                path.pk, path.pv, tgt = self._verify(
                    eng.params, jnp.asarray(ids), path.pk, path.pv,
                    jnp.asarray(bt), jnp.asarray(eng._npos),
                    jnp.asarray(limit), path.cos, path.sin)
        with eng._phase("wait", **step_ids):
            if sampling:
                tprobs = np.asarray(tprobs)           # [S, g+1, V]
            tgt = np.asarray(tgt)                     # [S, g+1]

        # ---- accept + roll back ------------------------------------------
        with eng._phase("emit"):
            emitted = self._accept_round(active, ids, tgt, lag, start,
                                         sampling, dprobs, tprobs)
        return {"type": "spec_decode", "tokens": emitted}

    def _accept_round(self, active, ids, tgt, lag, start, sampling, dprobs,
                      tprobs):
        """Commit each row's accepted tokens, emit them, and roll its block
        table back to the new watermark. Returns {request_id: tokens}."""
        eng, g = self.eng, self.g
        steps = g + 1
        emitted = {}
        for slot in active:
            req = eng.slots.owner(slot)
            p = int(eng._npos[slot])
            drafts = [int(ids[slot, i]) for i in range(1, g + 1)]
            if sampling and eng.sampler.any_sampling([slot]):
                a, commit = self._accept_sampled(req, slot, p, drafts,
                                                 lag[slot], dprobs, tprobs)
            else:
                # greedy rows keep EXACT-match acceptance (bit-identical
                # to sequential argmax, even inside a sampling batch)
                a = 0
                while a < g and drafts[a] == int(tgt[slot, a]):
                    a += 1
                commit = drafts[:a] + [int(tgt[slot, a])] if a < g \
                    else drafts + [int(tgt[slot, g])]
            k = 0
            for tok in commit:
                eng._emit(req, tok)
                k += 1
                if req.finished:
                    break
            eng._npos[slot] = p + k
            eng._last_tok[slot] = req.token_ids[-1]
            self._dpos[slot] = min(int(start[slot]) + steps,
                                   p + min(a, k) + 1, p + k)
            emitted[req.request_id] = commit[:k]
            eng.metrics.inc("draft_tokens_proposed", g)
            eng.metrics.inc("draft_tokens_accepted", min(a, k))
            eng.metrics.inc("tokens_generated", k)
            eng.metrics.observe("spec_commit_len", k)
            eng.metrics.observe("spec_acceptance_rate", min(a, k) / g)
            if req.finished:
                eng._retire(slot)
            else:
                self._rollback_tail(slot, p + k)
        eng.metrics.inc("spec_rounds")
        return emitted

    def _accept_sampled(self, req, slot, p, drafts, lag, dprobs, tprobs):
        """Rejection-sampling acceptance for one sampling row: draft
        token i (proposed from warped p_draft) is accepted with
        probability min(1, p_target/p_draft); the first rejection
        commits one token resampled from the adjusted residual
        normalize(max(0, p_target - p_draft)) and ends the round; a
        fully-accepted window commits a bonus token drawn from the
        target's own next distribution via the sequential (seed, pos)
        gumbel stream. Every committed token is exactly
        target-distributed — speculation changes the schedule, never
        the law — and when draft == target the acceptance ratio is 1,
        reducing the round to sequential seeded sampling (the parity
        test)."""
        g = self.g
        commit = []
        a = 0
        for i in range(1, g + 1):
            d = drafts[i - 1]
            j = min(lag + i - 1, dprobs.shape[1] - 1)
            pd = dprobs[slot, j]          # draft dist for index p+i
            pt = tprobs[slot, i - 1]      # target dist for index p+i
            ratio = float(pt[d]) / max(float(pd[d]), 1e-30)
            u = float(jax.random.uniform(
                _spec_key(req.seed, p + i, _ACCEPT_SALT), ()))
            if u < min(1.0, ratio):
                commit.append(d)
                a += 1
                continue
            residual = np.maximum(pt.astype(np.float64)
                                  - pd.astype(np.float64), 0.0)
            tot = float(residual.sum())
            if tot <= 0.0:
                # degenerate (draft dominates everywhere — only possible
                # through float rounding): fall back to the target dist
                residual, tot = pt.astype(np.float64), float(pt.sum())
            commit.append(_residual_draw(residual / tot, req.seed, p + i))
            self.eng.metrics.inc("spec_resamples")
            return a, commit
        # all g drafts accepted: bonus token from the target's next
        # distribution, drawn with the SAME gumbel-max + (seed, pos) key
        # sequential `generate(seeds=...)` would use at index p+g+1 —
        # log p_target is the warped logits up to a per-row constant, so
        # the argmax (hence the token) is identical
        pt = tprobs[slot, g]
        keys = gen._row_keys(np.asarray([req.seed], np.int32), p + g + 1)
        u = np.asarray(jax.vmap(lambda k_: jax.random.uniform(
            k_, pt.shape, jnp.float32, minval=1e-20, maxval=1.0))(keys))[0]
        logp = np.where(pt > 0, np.log(np.maximum(pt, 1e-30)), -np.inf)
        commit.append(int(np.argmax(logp - np.log(-np.log(u)))))
        return a, commit

    def _rollback_tail(self, slot, npos):
        """Truncate the slot's block table to the pages covering the
        committed positions [0, npos): window pages wholly past the new
        watermark return to the pool and their reservation is refunded.
        The rejected K/V inside the kept tail page stays as garbage that
        the next write-before-attend step overwrites."""
        eng = self.eng
        keep = (npos - 1) // eng.page_size + 1
        pages = eng._bt[slot]
        while len(pages) > keep:
            eng._alloc.release(pages.pop())
            eng._resv[slot] += 1
            eng._reserved_total += 1
            eng.metrics.inc("spec_pages_rewound")
