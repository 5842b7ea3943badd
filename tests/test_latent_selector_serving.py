"""Latent attention behind a LEARNED TOKEN SELECTOR, with sigmoid
bias-corrected routing, through `PagedEngine`: GLM-5's stack
(`benchmarks/families/mla_dsa_moe.py`) on the CPU at a tiny preset that
keeps its ratios: 4 index heads of 16 choosing 16 keys a query, pages of 8,
a rotary slice of 8 leading each index head, 16 experts of which 4 are picked
by sigmoid score + bias and weighed over the sum of the picked scores, one
dense leading layer before two expert layers.

Everything is compared with the family's plain float32 reference (indexer,
exact top-k, latent attention over the selection, no kernel, no cache) on
seeded weights. Program and reference are both float32 here: what differs
is the order of sums, 2e-5 on logits of magnitude ~0.1 (measured 5e-7).
"""

import functools
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import reference, weights  # noqa: E402
from paddle_tpu.kernels import latent_attention as la  # noqa: E402
from paddle_tpu.kernels import quantized_matmul as qm  # noqa: E402
from paddle_tpu.models import latent_moe_functional as lm  # noqa: E402
from paddle_tpu.serving import PagedEngine, Request  # noqa: E402

TOL = 2e-5
SEED = 11
ARCH = {
    "family": "mla_dsa_moe", "hidden_size": 64, "intermediate_size": 160,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4, "q_lora_rank": 24,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 24, "n_routed_experts": 16, "n_shared_experts": 1,
    "n_group": 1, "topk_group": 1, "num_experts_per_tok": 4,
    "routed_scaling_factor": 2.5, "vocab_size": 256, "rms_norm_eps": 1e-05,
    "rope_parameters": {"rope_theta": 10000}, "initializer_range": 0.15,
    "router_bias_std": 0.2, "max_position_embeddings": 4096,
    "scoring_func": "sigmoid", "norm_topk_prob": True, "index_n_heads": 4,
    "index_head_dim": 16, "index_topk": 16}
PS, P, NPAGES = 8, 16, 40
ENGINE = dict(max_slots=3, max_len=128, page_size=PS, num_pages=80,
              min_bucket=8, prefill_chunk=16)


def share(first, held=4):
    """ARCH as the chip that holds experts [first, first + held) sees it."""
    return dict(ARCH, n_routed_experts=held,
                published={"n_routed_experts": 16},
                deployment={"first_expert_held": first})


@pytest.fixture(scope="module")
def fam():
    """The family's file, loaded by its path as the harness loads it."""
    path = os.path.join(ROOT, "benchmarks", "families", "mla_dsa_moe.py")
    spec = importlib.util.spec_from_file_location("family_mla_dsa_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def args(fam):
    return fam.serve_args(ARCH)


def _params(fam, arch=ARCH):
    """The seed's weights as the reference makes them (bfloat16 values),
    held in float32 by the program."""
    made = weights.make_params(fam, arch, SEED, jnp.bfloat16)
    return jax.tree.map(lambda a: a.astype(jnp.float32), made)


@pytest.fixture(scope="module")
def params(fam):
    return _params(fam)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(1, 256, n).astype(np.int32)


def _layer(params, arch, i):
    kd = arch["first_k_dense_replace"]
    group = params["dense_layers"] if i < kd else params["layers"]
    return {k: v[i if i < kd else i - kd] for k, v in group.items()}


def _ref_logits(fam, params, ids, arch=ARCH):
    x = fam.forward_hidden(arch, ids, lambda i: _layer(params, arch, i),
                           params["embedding"])
    return np.asarray(fam.head_logits(arch, x, params["final_norm"],
                                      params["lm_head"]))


def _cache(args, dtype=jnp.float32):
    pool = jnp.zeros((args.num_layers * NPAGES, PS, args.row_width), dtype)
    if args.indexer is None:
        return pool
    return pool, jnp.zeros((args.num_layers * NPAGES, PS, args.indexer.dim),
                           dtype)


@functools.lru_cache(maxsize=None)
def _programs(args, fused):
    """The two step programs jitted (run op by op, a test's hundreds of
    small executables exhaust the process's memory maps), one pair a
    description and a dispatch mode."""
    def prefill(params, *a):
        """(params, ids, h, last_idx, bt_row, new_pages, cache, cos, sin,
        record) -> (logits, cache, what rides)"""
        with qm.fused_dispatch(fused, interpret=True):
            logits, cache, _, riders = lm.prefill_window(
                params, None, *a[:6], (), a[6:8], args, a[8])
        return logits, cache, riders

    def decode(params, *a):
        """(params, tokens, bt, pos, live, cache, cos, sin, record) ->
        (logits, cache, what rides)"""
        with qm.fused_dispatch(fused, interpret=True):
            logits, cache, _, riders = lm.decode_step(
                params, None, *a[:5], (), a[5:7], args, a[7])
        return logits, cache, riders

    return jax.jit(prefill), jax.jit(decode)


def _through_the_cache(params, args, ids, chunks, record=0, fused=False):
    """`ids` through `prefill_window` in windows of `chunks` tokens, the
    rest through `decode_step` (row 0 of two). Returns ({position: logits},
    {position: selected [layers, K]} of the decode steps)."""
    prefill, decode = _programs(args, fused)
    cos, sin = lm.rope_tables(256, args)
    cache = _cache(args)
    bt_row = np.zeros(P, np.int32)
    held = -(-len(ids) // PS) + 1
    bt_row[:held] = 1 + np.arange(held)
    got, picked, h = {}, {}, 0
    for c in chunks:
        bucket = 8
        while bucket < c:
            bucket *= 2
        window = np.zeros(bucket, np.int32)
        window[:c] = ids[h:h + c]
        touched = bt_row[h // PS:][:bucket // PS + 1]
        new = np.zeros(P, np.int32)
        new[:len(touched)] = touched
        logits, cache, _ = prefill(
            params, jnp.asarray(window), jnp.int32(h), jnp.int32(c - 1),
            jnp.asarray(bt_row), jnp.asarray(new), cache, cos, sin,
            jnp.int32(0))
        h += c
        got[h - 1] = np.asarray(logits)
    bt = np.zeros((2, P), np.int32)
    bt[0] = bt_row
    for t in range(h, len(ids)):
        logits, cache, riders = decode(
            params, jnp.asarray([ids[t], 0]), jnp.asarray(bt),
            jnp.asarray([t, 0], jnp.int32), jnp.asarray([True, False]),
            cache, cos, sin, jnp.int32(record))
        got[t] = np.asarray(logits[0])
        if riders.selection is not None:
            picked[t] = _positions(riders.selection)
    return got, picked


# ---------------------------------------------------------------------------
# the program against the plain reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("chunks", [[8], [16, 16, 8], [8, 32, 5], [50]])
def test_windows_then_decode_give_the_references_logits(fam, params, args,
                                                        chunks, fused):
    """Contexts on both sides of index_topk = 16: a window that ends below
    it, windows that cross it, decode steps past it; the Pallas kernels
    interpreted (`fused`) and their jnp oracles."""
    ids = _ids(58, 3)
    want = _ref_logits(fam, params, ids)
    got, _ = _through_the_cache(params, args, ids, chunks, fused=fused)
    assert len(got) == len(chunks) + 58 - sum(chunks)
    for t, logits in got.items():
        np.testing.assert_allclose(logits, want[t], atol=TOL)


def _positions(packed):
    """Packed selections [.., T / 8] -> a list (a layer) of position arrays,
    or for a window's rows a list (a layer) of lists (a row)."""
    bits = np.unpackbits(np.asarray(packed), axis=-1, bitorder="little")
    if bits.ndim == 2:
        return [np.nonzero(b)[0] for b in bits]
    return [[np.nonzero(r)[0] for r in layer] for layer in bits]


def _reference_selection(fam, params, ids):
    """{layer: bool [len(ids), len(ids)]}: the keys each position selects
    by the reference's own float32 scores, ties to the lower position."""
    from benchmarks.harness.reference import f32_mm

    out, x = {}, params["embedding"][jnp.asarray(ids)].astype(jnp.float32)
    pos = jnp.arange(len(ids))
    for i, kind in enumerate(fam.layer_kinds(ARCH)):
        w = _layer(params, ARCH, i)
        c_q, _, _, ki, wi = fam.cached(x, w, ARCH, f32_mm, pos)
        sc = np.asarray(fam.index_scores(c_q, wi, pos, ki, w, ARCH, f32_mm))
        sel = np.zeros(sc.shape, bool)
        for t in range(len(ids)):
            order = np.argsort(-sc[t, :t + 1], kind="stable")
            sel[t, order[:ARCH["index_topk"]]] = True
        out[i] = sel
        x = fam.decoder_layer(x[None], w, ARCH, f32_mm, kind)[0]
    return out


def test_the_recorded_selection_is_the_references_top_k(fam, params, args):
    """What a decode row and a window's queries record is, layer by layer,
    the reference's exact top-16 (all of the context below 16): the
    window's eight rows 9 .. 16 lie on both sides of index_topk."""
    ids = _ids(44, 5)
    want = _reference_selection(fam, params, ids)
    _, picked = _through_the_cache(params, args, ids, [16, 8])
    assert sorted(picked) == list(range(24, 44))
    for t, sel in picked.items():
        for layer in range(3):
            assert sorted(sel[layer]) == list(np.nonzero(want[layer][t])[0])
    # a window's sampled queries: on both sides of index_topk
    cos, sin = lm.rope_tables(256, args)
    bt_row = np.zeros(P, np.int32)
    bt_row[:6] = 1 + np.arange(6)
    new = np.zeros(P, np.int32)
    new[:5] = bt_row[:5]
    *_, (_, _, sel) = _programs(args, False)[0](
        params, jnp.asarray(ids[:32]), jnp.int32(0), jnp.int32(31),
        jnp.asarray(bt_row), jnp.asarray(new), _cache(args), cos, sin,
        jnp.int32(9))
    assert sel.shape == (3, lm.SELECT_ROWS, P * PS // 8)
    sel = _positions(sel)
    for layer in range(3):
        for j, t in enumerate(range(9, 9 + lm.SELECT_ROWS)):
            assert list(sel[layer][j]) == list(np.nonzero(want[layer][t])[0])


@pytest.mark.parametrize("k", [1, 5, 16, 40])
def test_the_kth_largest_without_a_sort_is_top_k_ties_included(k):
    """`kth_largest` + `selected_of` pick what a stable sort picks: scores
    of few distinct values (ties are the rule), both signs, both zeros, and
    rows with fewer visible entries than k."""
    rng = np.random.default_rng(k)
    sc = rng.integers(-3, 4, (12, 64)).astype(np.float32) / 4
    sc[0, :8] = -0.0
    visible = np.arange(64)[None, :] <= np.asarray(
        [0, 3, 7, 15, 16, 30, 39, 40, 47, 62, 63, 63])[:, None]
    keys = jnp.where(visible, la.sortable(jnp.asarray(sc)), la._KEY_MIN)
    live = jnp.full((12,), 64, jnp.int32)
    thr, room = la.kth_largest(keys, k, live)
    got = np.asarray(la.selected_of(keys, thr, room))
    # the Pallas search (8 rows a grid step, the first four never past
    # column 47, the last past 63: a pass counts the live blocks alone)
    with qm.fused_dispatch(True, interpret=True):
        wide = jnp.pad(keys[:8], ((0, 0), (0, 64)),
                       constant_values=la._KEY_MIN)
        thr8, room8 = la.kth_largest(
            jnp.concatenate([wide, jnp.pad(keys[4:], ((0, 0), (0, 64)),
                                           constant_values=la._KEY_MIN)]),
            k, jnp.asarray([48] * 8 + [64] * 8, jnp.int32))
    np.testing.assert_array_equal(thr8[:8], thr[:8])
    np.testing.assert_array_equal(room8[8:], room[4:])
    # block by block, the ties counted on from block to block
    seen, parts = jnp.zeros((12, 1), jnp.int32), []
    for a in range(0, 64, 16):
        parts.append(la.selected_of(keys[:, a:a + 16], thr, room, seen))
        seen = seen + jnp.sum(keys[:, a:a + 16] == thr, 1, keepdims=True)
    np.testing.assert_array_equal(np.concatenate(parts, 1), got)
    for r in range(12):
        n = int(visible[r].sum())
        order = np.argsort(-sc[r, :n], kind="stable")[:k]
        assert sorted(np.nonzero(got[r])[0]) == sorted(order)


def test_within_index_topk_the_selector_changes_nothing(fam, params, args):
    """A context of at most index_topk selects everything: the logits are
    those of the same weights served with no selector at all."""
    ids = _ids(40, 7)
    wide = args._replace(indexer=args.indexer._replace(topk=64))
    none = args._replace(indexer=None, record_selection=False)
    got, _ = _through_the_cache(params, wide, ids, [16, 16])
    want, _ = _through_the_cache(params, none, ids, [16, 16])
    assert sorted(got) == sorted(want)
    for t in got:
        np.testing.assert_allclose(got[t], want[t], atol=TOL)
    # and past it the selector does change them
    narrow, _ = _through_the_cache(params, args, ids, [16, 16])
    assert max(np.abs(narrow[t] - want[t]).max() for t in want) > 50 * TOL


def test_index_decode_kernel_equals_its_oracle():
    """The Pallas walk over a row's live pages of the index pool against
    the gather of every row's whole table, at a lane-tile width."""
    rng = np.random.default_rng(2)
    b, J, d, ps, pages = 3, 8, 128, 16, 8
    ipool = jnp.asarray(rng.normal(size=(40, ps, d)), jnp.float32)
    bt = jnp.asarray(rng.permutation(39)[:b * pages].reshape(b, pages) + 1,
                     jnp.int32)
    pos = jnp.asarray([5, 77, 127], jnp.int32)
    qi = jnp.asarray(rng.normal(size=(b, J, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(b, J)), jnp.float32)
    want = la.index_decode_scores(qi, w, ipool, bt, pos, page_base=1)
    assert la.index_decode_supported(qi.shape, ipool.shape, bt.shape, 4)
    with qm.fused_dispatch(True, interpret=True):
        got = la.index_decode_scores(qi, w, ipool, bt, pos, page_base=1)
    np.testing.assert_allclose(got, want, atol=1e-4)
    assert np.isneginf(np.asarray(got)[0, 6:]).all()


# ---------------------------------------------------------------------------
# routing: one function, two rules
# ---------------------------------------------------------------------------

def _group_limited_greedy(logits, args):
    """`route` as it stood for the softmax rule alone (PR 33), kept here as
    the oracle of "bit for bit"."""
    n = logits.shape[0]
    scores = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    per = args.routed_experts // args.n_group
    group_best = jnp.max(scores.reshape(n, args.n_group, per), axis=-1)
    kept = jax.lax.top_k(group_best, args.topk_group)[1]
    stays = jnp.any(jax.nn.one_hot(kept, args.n_group, dtype=bool), axis=1)
    masked = jnp.where(jnp.repeat(stays, per, axis=1), scores, 0.0)
    w, experts = jax.lax.top_k(masked, args.experts_per_tok)
    return experts.astype(jnp.int32), w * args.routed_scaling


@pytest.mark.parametrize("rule", ["softmax", "sigmoid"])
@pytest.mark.parametrize("case", ["random", "ties"])
def test_route_serves_both_published_rules(args, rule, case):
    rng = np.random.default_rng(3)
    logits = rng.normal(size=(40, 16)).astype(np.float32)
    if case == "ties":
        logits = np.round(logits * 2) / 2
    if rule == "softmax":
        soft = args._replace(scoring="softmax", norm_topk=False, n_group=4,
                             topk_group=2, experts_per_tok=3,
                             routed_scaling=16.0)
        experts, w = lm.route(jnp.asarray(logits), soft)
        want_e, want_w = _group_limited_greedy(jnp.asarray(logits), soft)
        np.testing.assert_array_equal(experts, want_e)
        np.testing.assert_array_equal(w, want_w)        # bit for bit
        return
    bias = (rng.normal(size=16) * (0.5 if case == "random" else 0.0)
            ).astype(np.float32)
    experts, w = lm.route(jnp.asarray(logits), args, jnp.asarray(bias))
    s = 1 / (1 + np.exp(-logits.astype(np.float64)))
    for r in range(40):
        order = np.argsort(-(s[r].astype(np.float32) + bias),
                           kind="stable")[:4]
        assert list(np.asarray(experts[r])) == list(order)
        np.testing.assert_allclose(
            np.asarray(w[r]), 2.5 * s[r][order] / s[r][order].sum(),
            rtol=1e-5)
    if case == "random":        # the bias does change picks, never weights
        plain, _ = lm.route(jnp.asarray(logits), args,
                            jnp.zeros(16, jnp.float32))
        assert (np.asarray(plain) != np.asarray(experts)).any()


def test_the_shares_add_up_under_the_sigmoid_rule(fam, params):
    """Four chips of four experts: their parts of the routed sum, and the
    shared expert counted ONCE, add up to the uncut reference's layer; a
    pick's weight is normalised over ALL the picks, held or not."""
    from benchmarks.harness.reference import f32_mm, rms_norm

    w = _layer(params, ARCH, 1)
    x = jnp.asarray(np.random.default_rng(5).normal(
        size=(16, 64)).astype(np.float32))
    whole, _ = fam.finish_noting(x, w, ARCH, f32_mm, False)
    h = rms_norm(x, w["ln2"], 1e-5)
    shared = fam._eq.shared_experts(h, w, ARCH, f32_mm)
    parts_ref, parts_prog = jnp.zeros_like(x), jnp.zeros_like(x)
    for g in range(4):
        arch_g = share(4 * g)
        w_g = dict(w, **{k: w[k][4 * g:4 * g + 4]
                         for k in ("we_gate", "we_up", "we_down")})
        assert fam.experts_held(arch_g) == (4 * g, 4)
        part, _ = fam.finish_noting(x, w_g, arch_g, f32_mm, False)
        parts_ref = parts_ref + (part - x - shared)
        stack = {k: w_g[k] for k in ("we_gate", "we_up", "we_down")}
        got, counts, _ = lm._routed_experts(
            w_g, stack, 0, h, jnp.ones(16, bool), fam.serve_args(arch_g))
        parts_prog = parts_prog + got
        assert int(counts[2]) == 16 * 4
    np.testing.assert_allclose(x + shared + parts_ref, whole, atol=TOL)
    np.testing.assert_allclose(x + shared + parts_prog, whole, atol=TOL)


# ---------------------------------------------------------------------------
# the engine: two pools under one block table
# ---------------------------------------------------------------------------

def _served(fam, params, req, arch=ARCH):
    seq = np.concatenate([req.prompt_ids,
                          np.asarray(req.token_ids[:-1], np.int32)])
    want = _ref_logits(fam, params, seq, arch)[len(req.prompt_ids) - 1:]
    return list(want.argmax(-1))


@pytest.mark.parametrize("chunk", [None, 16])
def test_engine_serves_the_references_tokens(fam, params, args, chunk):
    eng = PagedEngine(params, args, **dict(ENGINE, prefill_chunk=chunk))
    latent, index = eng.path.pools
    assert latent.shape == (3 * 80, PS, 128) and index.shape == (3 * 80, PS,
                                                                  16)
    reqs = [Request(_ids(n, n), 6) for n in (20, 37, 9, 50)]
    eng.serve(reqs)
    for r in reqs:
        assert list(r.token_ids) == _served(fam, params, r)
    found = eng.metrics.summary()
    obs = found["observations"]
    assert 0 < obs["serve.selected_keys"]["mean"] < obs[
        "serve.visible_keys"]["mean"]
    assert found["gauges"]["index_pool_bytes"]["value"] == index.size * 4
    assert found["gauges"]["kv_pool_bytes"]["value"] == (
        latent.size + index.size) * 4


def test_a_request_carries_a_sample_of_its_selections(fam, params, args):
    """`req.routing.selections`: eight consecutive queries of every
    window and, of every eighth decode step, the row whose turn it is; each
    the reference's own top-16 in every layer."""
    eng = PagedEngine(params, args, **ENGINE)
    reqs = [Request(_ids(37, 2), 30), Request(_ids(21, 4), 30)]
    eng.serve(reqs)
    for req in reqs:
        seq = np.concatenate([req.prompt_ids,
                              np.asarray(req.token_ids[:-1], np.int32)])
        want = _reference_selection(fam, params, seq)
        kept = req.routing.selections(len(seq))
        at = [t for t, _ in kept]
        # eight rows of every whole 16-token window, and decode rows on top
        assert len(at) > 8 * (len(req.prompt_ids) // 16)
        assert max(at) >= len(req.prompt_ids)
        for t, sel in kept:
            assert len(sel) == 3
            for layer in range(3):
                assert list(sel[layer]) == list(
                    np.nonzero(want[layer][t])[0])


def test_a_prefix_hit_that_ends_mid_page_copies_both_pools(params, args):
    eng = PagedEngine(params, args, **ENGINE)
    base = _ids(12, 4)                  # a page and a half
    eng.serve([Request(base, 3)])
    longer = np.concatenate([base, _ids(30, 5)])
    warm = Request(longer, 8)
    eng.serve([warm])
    c = eng.metrics.summary()["counters"]
    assert c["cow_copies"] == 1 and c["prefix_tokens_hit"] == 12
    cold_eng = PagedEngine(params, args, **ENGINE)
    cold = Request(longer, 8)
    cold_eng.serve([cold])
    assert list(warm.token_ids) == list(cold.token_ids)
    # the copied page holds the shared positions' rows of BOTH pools
    for pools in (eng.path.pools, cold_eng.path.pools):
        assert all(float(jnp.abs(p).sum()) > 0 for p in pools)
    for t, sel in warm.routing.selections(49):
        if t >= 12:
            want = dict(cold.routing.selections(49))
            if t in want:
                for mine, theirs in zip(sel, want[t]):
                    np.testing.assert_array_equal(mine, theirs)


def test_preempt_and_resume_carry_the_pages_of_both_pools(params, args):
    eng = PagedEngine(params, args, **ENGINE)
    req = eng.submit(Request(_ids(21, 8), 12))
    while len(req.token_ids) < 4:
        eng.step()
    slot = next(s for s in eng.slots.active_slots
                if eng.slots.owner(s) is req)
    state = eng.preempt(slot)
    # nothing beside its pages leaves with it: an empty state tree
    assert not jax.tree_util.tree_leaves(state["path_state"])
    assert state["pages"]
    eng.serve([Request(_ids(15, 9), 4)])    # the slot is used meanwhile
    assert eng.can_resume(state)
    eng.resume(state)
    while not req.finished:
        eng.step()
    straight = Request(_ids(21, 8), 12)
    PagedEngine(params, args, **ENGINE).serve([straight])
    assert list(req.token_ids) == list(straight.token_ids)
    np.testing.assert_array_equal(req.routing.table(32),
                                  straight.routing.table(32))


def test_a_reset_engine_serves_again_with_a_cold_cache(params, args):
    eng = PagedEngine(params, args, **ENGINE)
    first = Request(_ids(29, 3), 4)
    eng.serve([first])
    eng.reset()
    assert eng.path.riders.log == [] and eng.path.riders._steps == 0
    again = Request(_ids(29, 3), 4)
    eng.serve([again])
    assert list(first.token_ids) == list(again.token_ids)
    assert eng.metrics.summary()["counters"].get("prefix_tokens_hit", 0) == 0


@pytest.mark.parametrize("selector", [False, True])
def test_window_bucket_below_a_page_crosses_the_page(fam, params, args,
                                                     selector):
    """ROADMAP C13, `tests/test_paged_kv.py`'s case of the dense path made a
    case of the latent path: `min_bucket` < `page_size`; after a mid-page
    hit (28 of 16-token pages) a 6-token suffix in a bucket of 8 covers
    positions 28..33, the tail of one page and the head of the next. Both
    are written, in every pool: no position is read before its own token
    wrote it."""
    if not selector:
        args = args._replace(indexer=None, record_selection=False)
    base = _ids(28, 131)
    prompts = [np.concatenate([base, _ids(k, k)]) for k in (2, 6)]
    kw = dict(max_slots=1, max_len=64, page_size=16)
    want = []
    for p, n in zip(prompts, (2, 6)):
        r = Request(p, n)
        PagedEngine(params, args, min_bucket=16, **kw).serve([r])
        want.append(list(r.token_ids))
    eng = PagedEngine(params, args, min_bucket=4, **kw)
    # a position nobody wrote holds anything: here, what would win every
    # softmax and every selection it entered
    eng.path.pools = jax.tree.map(lambda a: jnp.full_like(a, 30.0),
                                  eng.path.pools)
    reqs = eng.serve([Request(p, n) for p, n in zip(prompts, (2, 6))])
    assert eng.metrics.summary()["counters"]["prefix_tokens_hit"] == 28
    assert [list(r.token_ids) for r in reqs] == want


def test_a_description_that_cannot_be_is_refused(args):
    with pytest.raises(ValueError, match="softmax or sigmoid"):
        args._replace(scoring="tanh").validate()
    with pytest.raises(ValueError, match="record_selection without"):
        args._replace(indexer=None).validate()


# ---------------------------------------------------------------------------
# `correct`: sound runs pass, planted faults do not
# ---------------------------------------------------------------------------

GAP_LIMIT = 1e-3      # sound: 1e-6 (float32 on both sides)


def _judged(fam, eng, arch=ARCH):
    """Serve two requests, then judge them as the harness does: the widest
    gap of a served token's logit below the reference's best (a request
    the selection check refused reads SELECT_PENALTY there)."""
    reqs = [Request(_ids(n, n), 24) for n in (41, 50)]
    eng.serve(reqs)
    sample = [(r.prompt_ids, np.asarray(r.token_ids, np.int32), r.routing)
              for r in reqs]
    logits = fam.served_logits(arch, SEED, sample)
    return max(reference.served_gap(lg, toks).max()
               for lg, (_, toks, _) in zip(logits, sample))


def test_a_sound_engine_is_correct(fam, params, args):
    assert _judged(fam, PagedEngine(params, args, **ENGINE)) < GAP_LIMIT


def _no_relu(monkeypatch, params, args):
    def scores(qi, w, keys):
        eq = "njd,ntd->njt" if keys.ndim == 3 else "njd,td->njt"
        s = jnp.einsum(eq, qi, keys, preferred_element_type=jnp.float32)
        return jnp.sum(s * w[..., None], axis=1)
    monkeypatch.setattr(la, "_index_scores", scores)
    return args


def _keys_without_rope(monkeypatch, params, args):
    real = lm._index_operands

    def operands(lp, hin, c_q, cos, sin, args):
        qi, _, w = real(lp, hin, c_q, cos, sin, args)
        _, ki, _ = real(lp, hin, c_q, jnp.ones_like(cos),
                        jnp.zeros_like(sin), args)
        return qi, ki, w
    monkeypatch.setattr(lm, "_index_operands", operands)
    return args


def _wrong_layers_selection(monkeypatch, params, args):
    real = lm._index_operands
    first = {k: params["dense_layers"][k][0]
             for k in ("w_iq", "w_ik", "ik_norm", "ik_bias", "w_iw")}

    def operands(lp, hin, c_q, cos, sin, args):
        return real(dict(lp, **first), hin, c_q, cos, sin, args)
    monkeypatch.setattr(lm, "_index_operands", operands)
    return args


def _k_off_by_a_block(monkeypatch, params, args):
    return args._replace(indexer=args.indexer._replace(topk=16 + PS))


def _bias_left_out_of_the_pick(monkeypatch, params, args):
    real = lm.route
    monkeypatch.setattr(lm, "route", lambda logits, args, bias=None: real(
        logits, args, None if bias is None else jnp.zeros_like(bias)))
    return args


def _weights_over_the_held_picks(monkeypatch, params, args):
    real = lm.route

    def route(logits, args, bias=None):
        experts, w = real(logits, args._replace(norm_topk=False), bias)
        held = (experts >= args.first_expert) & (
            experts < args.first_expert + args.experts_held)
        return experts, w / (jnp.sum(jnp.where(held, w, 0.0), -1,
                                     keepdims=True) + 1e-20) * 2.5
    monkeypatch.setattr(lm, "route", route)
    return args


@pytest.mark.parametrize("fault", [
    _k_off_by_a_block, _no_relu, _keys_without_rope, _wrong_layers_selection,
    _bias_left_out_of_the_pick, _weights_over_the_held_picks])
def test_a_planted_fault_reads_not_correct(fam, monkeypatch, fault):
    """Each fault in the PROGRAM; the judgement is the harness's own. The
    selector's faults are caught by the sampled selections (a logit hardly
    moves), the routing's by the logits. The shares' fault needs a share:
    the chip that holds experts 4..11 of 16."""
    arch = share(4, 8) if fault is _weights_over_the_held_picks else ARCH
    params = _params(fam, arch)
    args = fault(monkeypatch, params, fam.serve_args(arch))
    gap = _judged(fam, PagedEngine(params, args, **ENGINE), arch)
    assert gap > GAP_LIMIT
    if fault in (_k_off_by_a_block, _no_relu, _keys_without_rope,
                 _wrong_layers_selection):
        assert gap >= fam.SELECT_PENALTY / 2


# ---------------------------------------------------------------------------
# the configuration's arithmetic
# ---------------------------------------------------------------------------

def test_the_cuts_parameters_and_pools_are_as_stated(fam):
    """benchmarks/configs/glm-5-1chip.json: 3,910 M parameters (7.82 GB in
    bf16), and 8,192 pages of 64 tokens cache 3.36 GB of latent rows + 0.67
    GB of index keys, as ISSUE 41 and the file's `deployment` state."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "glm-5-1chip.json")) as f:
        arch = json.load(f)
    shapes = fam.layer_shapes(arch)
    count = {k: sum(int(np.prod(s)) for s in v.values())
             for k, v in shapes.items()}
    attention = sum(int(np.prod(shapes["layers"][k])) for k in (
        "w_qa", "w_qb", "w_kva", "w_kvb", "wo", "w_iq", "w_ik", "w_iw"))
    assert round(attention / 1e6, 1) == 174.4
    assert round(count["dense_layers"] / 1e6, 1) == 400.9
    assert round(count["layers"] / 1e6, 1) == 817.7
    assert fam.layer_kinds(arch) == ["dense_layers"] + ["layers"] * 4
    total = fam.param_count(arch)
    assert round(total / 1e6) == 3910 and round(2 * total / 1e9, 2) == 7.82
    made = jax.eval_shape(lambda: weights.make_params(fam, arch, 1))
    assert sum(x.size for x in jax.tree.leaves(made)) == total
    latent, index = fam.pool_bytes(arch, 8192 * 64)
    assert round(latent / 1e9, 2) == 3.36 and round(index / 1e9, 2) == 0.67
    assert (latent + index) // (8192 * 64) == 7680
    a = fam.serve_args(arch)
    assert (a.row_width, a.indexer.dim, a.num_layers, a.first_k_dense) == (
        640, 128, 5, 1)
    assert a.yarn is None and lm.softmax_scale(a) == 256 ** -0.5
    for key in arch["reduced"]:
        assert arch["published"][key] != arch[key]
