"""A stack with latent attention (MLA) and routed + shared experts through
`PagedEngine`, on the CPU at a tiny preset with every ratio of the published
model kept: 8 groups of 4 experts, 3 groups stay, 6 experts a token, a
rotary slice of 8 beside 16 plain dimensions, ranks (24, 32) below the width
(64), 2 shared experts, one dense leading layer before two expert layers.

Everything is compared with the plain float32 reference of
`benchmarks/families/mla_moe.py` (written from the equations in the
NON-absorbed form, every expert computed for every token, no kernel, no
cache) on seeded float32 weights. Tolerances: the program and the reference
are both float32 here, so what differs is the order of sums (and the
absorbed form's reassociation of W_uk and W_uv); 2e-5 on logits of
magnitude ~0.5 leaves that twenty times of room and is a hundred times under
what one expert routed wrongly costs (~2e-3 and up, measured by breaking the
group limit).
"""

import importlib.util
import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from paddle_tpu.kernels import grouped_matmul as gm  # noqa: E402
from paddle_tpu.kernels import latent_attention as la  # noqa: E402
from paddle_tpu.kernels import quantized_matmul as qm  # noqa: E402
from paddle_tpu.models import latent_moe_functional as lm  # noqa: E402
from paddle_tpu.serving import PagedEngine, Request  # noqa: E402

TOL = 2e-5

ARCH = {
    "family": "mla_moe", "hidden_size": 64, "intermediate_size": 160,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4, "q_lora_rank": 24,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "n_routed_experts": 32, "n_shared_experts": 2,
    "n_group": 8, "topk_group": 3, "num_experts_per_tok": 6,
    "routed_scaling_factor": 16, "vocab_size": 256, "rms_norm_eps": 1e-06,
    "rope_theta": 10000, "initializer_range": 0.15,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 64, "type": "yarn"}}
PS, P, NPAGES = 8, 16, 40
ENGINE = dict(max_slots=3, max_len=128, page_size=PS, num_pages=80,
              min_bucket=8, prefill_chunk=16)


def share(group):
    """ARCH as the chip that holds `group` of the 8 sees it: 4 experts."""
    return dict(ARCH, n_routed_experts=4,
                published={"n_routed_experts": 32},
                deployment={"expert_group_held": group})


@pytest.fixture(scope="module")
def fam():
    """The family's file, loaded by its path as the harness loads it."""
    path = os.path.join(ROOT, "benchmarks", "families", "mla_moe.py")
    spec = importlib.util.spec_from_file_location("family_mla_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def args(fam):
    return fam.serve_args(ARCH)


@pytest.fixture(scope="module")
def params(fam):
    return fam.make_params(ARCH, 11, jnp.float32)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(1, 256, n).astype(np.int32)


def _layer(params, arch, i):
    kd = arch["first_k_dense_replace"]
    group = params["dense_layers"] if i < kd else params["layers"]
    return {k: v[i if i < kd else i - kd] for k, v in group.items()}


def _ref_logits(fam, params, ids, arch=ARCH):
    """The reference's logits at every position of `ids`."""
    x = fam.forward_hidden(arch, ids, lambda i: _layer(params, arch, i),
                           params["embedding"])
    return np.asarray(fam.head_logits(arch, x, params["final_norm"],
                                      params["lm_head"]))


def _prefill(params, args, ids, chunks, pool=None, bt_row=None):
    """`ids` through `prefill_window` in windows of `chunks` tokens.
    Returns (logits at each window's end by position, pool, bt_row)."""
    cos, sin = lm.rope_tables(256, args)
    if pool is None:
        pool = jnp.zeros((args.num_layers * NPAGES, PS, args.row_width),
                         jnp.float32)
        bt_row = np.zeros(P, np.int32)
        held = -(-len(ids) // PS) + 1
        bt_row[:held] = 1 + np.arange(held)
    got, h = {}, 0
    for c in chunks:
        bucket = 8
        while bucket < c:
            bucket *= 2
        window = np.zeros(bucket, np.int32)
        window[:c] = ids[h:h + c]
        touched = bt_row[h // PS:][:bucket // PS + 1]
        new = np.zeros(P, np.int32)
        new[:len(touched)] = touched
        logits, pool, _, _ = lm.prefill_window(
            params, None, jnp.asarray(window), jnp.int32(h),
            jnp.int32(c - 1), jnp.asarray(bt_row), jnp.asarray(new), pool,
            (), (cos, sin), args)
        h += c
        got[h - 1] = np.asarray(logits)
    return got, pool, bt_row


# ---------------------------------------------------------------------------
# rotary positions and the scale, by hand
# ---------------------------------------------------------------------------

def test_yarn_frequencies_and_scale_by_hand(fam):
    """The published sizes: 64 rotary dimensions, theta 10000, factor 40
    over 4,096 original positions, beta 32 / 1. The correction dimensions
    are 64 ln(4096 / (32 * 2 pi)) / (2 ln 10000) = 10.47 -> 10 and 64
    ln(4096 / (2 pi)) / (2 ln 10000) = 22.51 -> 23: frequencies 0..10 are
    theta^(-2j/64) as they stand, 23..31 the same over 40, between them a
    linear blend, (j - 10) / 13 of the scaled one."""
    real = fam.serve_args(dict(
        ARCH, qk_rope_head_dim=64, qk_nope_head_dim=128,
        rope_scaling=dict(ARCH["rope_scaling"],
                          original_max_position_embeddings=4096)))
    inv = lm.yarn_inv_freq(real)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    assert math.floor(64 * math.log(4096 / (64 * math.pi))
                      / (2 * math.log(10000))) == 10
    assert math.ceil(64 * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(10000))) == 23
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-12)
    j = 17
    np.testing.assert_allclose(
        inv[j], plain[j] / 40 * (7 / 13) + plain[j] * (6 / 13), rtol=1e-12)
    # scale = 192^-1/2 * (0.1 * 0.707 * ln 40 + 1)^2, m = 1.2608
    m = 0.1 * 0.707 * math.log(40) + 1
    assert abs(m - 1.2608) < 1e-4
    assert abs(lm.softmax_scale(real) - m * m / math.sqrt(192)) < 1e-12
    assert abs(lm.softmax_scale(real) - 0.114721) < 1e-6
    # cos / sin multiplier mscale(40, 0.707) / mscale(40, 0.707) = 1
    cos, sin = lm.rope_tables(4, real)
    np.testing.assert_allclose(cos[0], np.ones(64), rtol=1e-6)
    np.testing.assert_allclose(sin[1, :32], np.sin(inv), rtol=1e-6)
    # and the reference's own, written apart, agree
    arch = dict(ARCH, qk_rope_head_dim=64, qk_nope_head_dim=128,
                rope_scaling=dict(ARCH["rope_scaling"],
                                  original_max_position_embeddings=4096))
    np.testing.assert_allclose(fam.yarn_inv_freq(arch), inv, rtol=1e-6)
    assert abs(fam.attention_scale(arch) - lm.softmax_scale(real)) < 1e-12


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

def _route_numpy(scores, n_group, topk_group, k, scaling):
    """The rule written out: a group's score is its best expert's; the
    `topk_group` best groups stay (a tie to the lower index); the k best
    experts among them (a tie to the lower index), each weighing scaling *
    its score."""
    n, e = scores.shape
    per = e // n_group
    experts = np.zeros((n, k), np.int64)
    weights = np.zeros((n, k), np.float64)
    for t in range(n):
        best = scores[t].reshape(n_group, per).max(-1)
        groups = sorted(range(n_group), key=lambda g: (-best[g], g))
        stays = set(groups[:topk_group])
        cand = [i for i in range(e) if i // per in stays]
        picks = sorted(cand, key=lambda i: (-scores[t, i], i))[:k]
        experts[t] = picks
        weights[t] = [scaling * scores[t, i] for i in picks]
    return experts, weights


@pytest.mark.parametrize("case", ["random", "ties"])
def test_group_limited_routing_against_the_written_rule(args, case):
    rng = np.random.default_rng(3)
    logits = rng.normal(size=(40, 32)).astype(np.float32)
    if case == "ties":
        # whole groups tie (equal best scores), and experts tie inside and
        # across the groups that stay: quantised logits
        logits = np.round(logits * 1.5) / 1.5
        logits[0] = 0.0                       # everything ties
        logits[1, 4:8] = logits[1, 0:4]       # two groups alike
    experts, weights = lm.route(jnp.asarray(logits), args)
    scores = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
    want_e, want_w = _route_numpy(scores, 8, 3, 6, 16.0)
    np.testing.assert_array_equal(np.asarray(experts), want_e)
    np.testing.assert_allclose(np.asarray(weights), want_w, rtol=1e-6)
    if case == "ties":
        assert list(want_e[0]) == [0, 1, 2, 3, 4, 5]
    # at most topk_group groups are picked from
    assert all(len({e // 4 for e in row}) <= 3 for row in want_e)


def test_the_references_routing_is_the_same_rule(fam):
    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.normal(size=(24, 64)).astype(np.float32))
    router = np.round(rng.normal(size=(64, 32)) * 2) / 8       # ties happen
    w = {"router": jnp.asarray(router.astype(np.float32))}
    from benchmarks.harness.reference import f32_mm

    weigh = np.asarray(fam.routing(h, w, ARCH, f32_mm))
    scores = np.asarray(jax.nn.softmax(f32_mm(h, w["router"]), axis=-1))
    want_e, want_w = _route_numpy(scores, 8, 3, 6, 16.0)
    for t in range(24):
        assert sorted(np.nonzero(weigh[t])[0]) == sorted(want_e[t])
        np.testing.assert_allclose(weigh[t, want_e[t]], want_w[t], rtol=1e-5)


# ---------------------------------------------------------------------------
# the grouped matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sizes", [[3, 0, 1, 0, 5], [0, 0, 0, 0, 0],
                                   [1, 1, 1, 1, 1], [0, 9, 0, 0, 0]])
@pytest.mark.parametrize("fused", [False, True])
def test_grouped_matmul_with_empty_and_one_token_experts(sizes, fused):
    rng = np.random.default_rng(sum(sizes))
    x = jnp.asarray(rng.normal(size=(12, 16)).astype(np.float32))
    # a stack of two layers' experts, of which the second layer's are used
    w = jnp.asarray(rng.normal(size=(10, 16, 8)).astype(np.float32))
    with qm.fused_dispatch(fused, interpret=True):
        got = np.asarray(gm.grouped_matmul(x, w, jnp.asarray(sizes), 5))
    row = 0
    for g, n in enumerate(sizes):
        for _ in range(n):
            np.testing.assert_allclose(got[row], np.asarray(x[row] @ w[5 + g]),
                                       atol=1e-5)
            row += 1
    assert np.isfinite(got[:row]).all()


# ---------------------------------------------------------------------------
# few rows: the fused pass over the hit experts' weights
# ---------------------------------------------------------------------------

def _expert_args(h, m, E, k, routed, first_expert, limit):
    return lm.LatentMoEArgs(
        vocab_size=64, hidden_size=h, num_layers=2, num_heads=2, q_rank=16,
        kv_rank=16, nope_dim=8, rope_dim=8, v_dim=8, dense_intermediate=32,
        expert_intermediate=m, shared_experts=1, routed_experts=routed,
        first_expert=first_expert, experts_held=E, n_group=1, topk_group=1,
        experts_per_tok=k, routed_scaling=2.5, first_k_dense=0,
        rope_theta=1e4, rms_eps=1e-5, yarn=None, scoring="sigmoid",
        norm_topk=True, swiglu_limit=limit)


def _picks(rng, mix, n, k, E, routed, first_expert):
    """(experts [n, k] of the published ones, live [n]) for a mix of picks;
    held are [first_expert, first_expert + E)."""
    live = np.ones(n, bool)
    if mix == "none_here":          # every pick lands on another chip
        away = np.setdiff1d(np.arange(routed),
                            first_expert + np.arange(E))
        return np.stack([rng.permutation(away)[:k] for _ in range(n)]), live
    if mix == "all_here":           # routed_here_share 1
        return first_expert + np.stack(
            [rng.permutation(E)[:k] for _ in range(n)]), live
    # held expert 0 gets no row, 1 exactly one row, 2 every row; rows
    # 3, 4 are dead and pick held experts that no live row may hit (5, 6)
    others = np.setdiff1d(np.arange(routed), first_expert + np.arange(7))
    experts = np.stack([rng.permutation(others)[:k] for _ in range(n)])
    experts[:, 0] = first_expert + 2
    experts[7, 1] = first_expert + 1
    experts[3:5, 1:3] = first_expert + np.array([5, 6])
    live[3:5] = False
    if mix == "all_dead":
        live[:] = False
    return experts, live


FUSED_CASES = [
    # h : m as each cell's (7,168 : 2,048, 5,120 : 1,536, 6,144 : 2,048)
    dict(n=64, h=896, m=256, E=16, k=8, routed=64, limit=10.0, first=16),
    dict(n=32, h=896, m=256, E=16, k=8, routed=64, limit=None, first=0),
    dict(n=64, h=1280, m=384, E=20, k=6, routed=160, limit=None, first=20),
    dict(n=32, h=1280, m=384, E=20, k=6, routed=160, limit=10.0, first=0),
    dict(n=32, h=768, m=256, E=16, k=8, routed=64, limit=None, first=16),
    dict(n=64, h=768, m=256, E=16, k=8, routed=64, limit=10.0, first=0),
    dict(n=64, h=896, m=256, E=16, k=8, routed=64, limit=10.0, first=16,
         dtype="bfloat16"),
    dict(n=64, h=1280, m=384, E=20, k=6, routed=160, limit=None, first=0,
         dtype="bfloat16"),
    dict(n=64, h=896, m=256, E=16, k=8, routed=64, limit=10.0, first=16,
         mix="all_here"),
    dict(n=32, h=768, m=256, E=16, k=8, routed=16, limit=None, first=0,
         mix="all_here"),
    dict(n=64, h=896, m=256, E=16, k=8, routed=64, limit=10.0, first=16,
         mix="none_here"),
    dict(n=32, h=1280, m=384, E=20, k=6, routed=160, limit=None, first=0,
         mix="none_here"),
    dict(n=64, h=768, m=256, E=16, k=8, routed=64, limit=None, first=16,
         mix="all_dead"),
    dict(n=8, h=896, m=256, E=16, k=8, routed=64, limit=10.0, first=0),
]


@pytest.mark.parametrize(
    "case", FUSED_CASES,
    ids=lambda c: "-".join(f"{k}{v}" for k, v in c.items()))
def test_fused_expert_pass_is_the_grouped_sum(case, monkeypatch):
    """`_routed_experts` at a decode step's row count, the fused Pallas pass
    (interpreted) against `grouped_matmul_reference` composed as the grouped
    form composes it: the sum, the four counts and the picks. A held expert
    no live row picked (and, where nothing lands here, every expert) holds
    NaNs: the pass never reads it. Blocks: several a matrix (a budget of
    1-2 MB), so both kernels accumulate over blocks and walk past the count."""
    n, h, m, E, k = (case[x] for x in "n h m E k".split())
    mix, first = case.get("mix", "mixed"), case["first"]
    dtype = jnp.dtype(case.get("dtype", "float32"))
    first_expert = 0 if case["routed"] == E else E
    args = _expert_args(h, m, E, k, case["routed"], first_expert,
                        case["limit"])
    rng = np.random.default_rng(n + h + E)
    experts, live = _picks(rng, mix, n, k, E, case["routed"], first_expert)
    weights = rng.random((n, k)).astype(np.float32) + 0.05
    hit = np.zeros(E, bool)
    local = experts[live] - first_expert
    hit[local[(local >= 0) & (local < E)]] = True

    def leaf(rows, cols):
        w = rng.normal(size=(2 * E, rows, cols)).astype(np.float32)
        w *= rows ** -0.5
        w[first:first + E][~hit] = np.nan       # never read
        w[:first] = np.nan                      # another layer's
        w[first + E:] = np.nan
        return jnp.asarray(w, dtype)

    stack = {"we_gate": leaf(h, m), "we_up": leaf(h, m),
             "we_down": leaf(m, h)}
    lp = {"router": jnp.zeros((h, case["routed"]), dtype)}
    hin = jnp.asarray(rng.normal(size=(n, h)).astype(np.float32), dtype)
    monkeypatch.setattr(lm, "route", lambda logits, args, bias=None: (
        jnp.asarray(experts, jnp.int32), jnp.asarray(weights)))
    run = jax.jit(lambda *a: lm._routed_experts(*a, args))
    with qm.fused_dispatch(True, interpret=True):
        # the largest budget that leaves neither matrix in one block
        for budget in (2 << 20, 3 << 19, 1 << 20, 3 << 18):
            monkeypatch.setattr(qm, "_VMEM_BUDGET_BYTES", budget)
            th, tm = gm.fused_tiles(n, h, m, dtype.itemsize) or (h, m)
            if th < h and tm < m:
                break
        assert th < h and tm < m and lm.experts_fused(n, args, dtype)
        out, counts, picks = run(lp, stack, first, hin, jnp.asarray(live))
    with qm.fused_dispatch(False):
        assert not lm.experts_fused(n, args, dtype)
        # the reference multiplies every group out: no NaNs for it
        clean = {key: jnp.nan_to_num(w) for key, w in stack.items()}
        want, want_counts, want_picks = jax.jit(
            lambda *a: lm._routed_experts(*a, args))(
                lp, clean, first, hin, jnp.asarray(live))
    assert out.dtype == want.dtype == dtype and out.shape == (n, h)
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(want_counts))
    np.testing.assert_array_equal(np.asarray(picks), np.asarray(want_picks))
    assert int(counts[3]) == hit.sum()
    out, want = (np.asarray(x.astype(jnp.float32)) for x in (out, want))
    assert np.isfinite(out).all()
    if mix in ("none_here", "all_dead"):
        assert int(counts[1]) == 0 and not out.any() and not want.any()
    else:
        assert np.abs(want).max() > 0.1
        # float32: the order of sums; bfloat16: the grouped form rounds the
        # projections, the activation and the down product, the pass the
        # weighted activation alone
        tol = 1e-4 if dtype == jnp.float32 else 0.04
        np.testing.assert_allclose(out, want, atol=tol * np.abs(want).max())
    dead = ~live
    assert not out[dead].any()


@pytest.mark.parametrize("rows, fused", [(32, True), (128, True),
                                         (129, False), (136, False),
                                         (512, False)])
def test_the_row_count_alone_picks_the_experts_form(rows, fused):
    """Up to 128 rows (a decode step) the routed experts are the two Pallas
    calls of the fused pass and no `ragged_dot`; one row more, or a
    512-token window, and they are the three ragged dots, as ever."""
    h, m, E, k = 256, 128, 4, 2
    args = _expert_args(h, m, E, k, 16, 4, None)
    stack = {"we_gate": jnp.zeros((2 * E, h, m)),
             "we_up": jnp.zeros((2 * E, h, m)),
             "we_down": jnp.zeros((2 * E, m, h))}
    lp = {"router": jnp.zeros((h, 16))}
    with qm.fused_dispatch(True, interpret=True):
        assert lm.experts_fused(rows, args, jnp.float32) == fused
        text = str(jax.make_jaxpr(
            lambda *a: lm._routed_experts(*a, args))(
                lp, stack, 0, jnp.zeros((rows, h)), jnp.ones(rows, bool)))
    assert text.count("= ragged_dot_general[") == (0 if fused else 3)
    assert text.count("= pallas_call[") == (2 if fused else 0)
    assert ("expert_gate_up" in text) == ("expert_down" in text) == fused
    assert ("= sort[" in text) != fused
    # the form is the kernels' to refuse too: off the TPU, or where whole
    # tiles do not divide the widths, every row count takes the grouped form
    assert not lm.experts_fused(rows, args, jnp.float32)
    with qm.fused_dispatch(True, interpret=True):
        assert not lm.experts_fused(
            rows, args._replace(hidden_size=192), jnp.float32)
        assert not lm.experts_fused(
            rows, args._replace(expert_intermediate=96), jnp.float32)


def test_expert_fused_share_is_the_decode_programs_share():
    """`serve.expert_fused_share`, one sample a step program: at the
    gigachat cell's widths three decode steps of 64 rows (fused) and one
    2,048-token window (grouped) read 0.75; with the kernels off every
    program reads 0."""
    from types import SimpleNamespace

    from paddle_tpu.models import gated_delta_functional as gdf
    from paddle_tpu.serving.metrics import Metrics

    def share(programs):
        """What the path observes of a decode step (None) or of a window of
        so many rows."""
        eng = SimpleNamespace(
            args=_expert_args(7168, 2048, 16, 8, 256, 0, 10.0),
            params={"embedding": jnp.zeros((2, 2), jnp.bfloat16)},
            metrics=Metrics(), max_slots=64)
        for rows in programs:
            seen = lm.observe_decode(eng.args, eng, [0]) if rows is None \
                else lm.observe_prefill(eng.args, eng, rows)
            for name, value in seen.items():
                eng.metrics.observe(name, value)
        return eng.metrics.summary()["observations"][
            "serve.expert_fused_share"]

    with qm.fused_dispatch(True, interpret=True):
        seen = share([None, 2048, None, None])
    assert seen["count"] == 4 and seen["mean"] == 0.75
    seen = share([None, 2048, None, None])
    assert seen["count"] == 4 and seen["mean"] == 0.0
    # a family without experts has no such observation
    assert "serve.expert_fused_share" not in {
        **gdf.observe_prefill(None, None, 64),
        **gdf.observe_decode(None, None, [0])}


# ---------------------------------------------------------------------------
# the share of an expert-parallel layer
# ---------------------------------------------------------------------------

def test_the_shares_add_up(fam, params):
    """Each of the eight chips computes its own experts' part of the routed
    sum; those parts, and the shared experts counted ONCE, add up to what
    the uncut reference gives for the whole layer: in the reference and in
    the program alike."""
    from benchmarks.harness.reference import f32_mm, rms_norm

    w = _layer(params, ARCH, 1)
    x = jnp.asarray(np.random.default_rng(5).normal(
        size=(16, 64)).astype(np.float32))
    h = rms_norm(x, w["ln2"], 1e-6)
    whole = fam.shared_experts(h, w, ARCH, f32_mm) + fam.routed_experts(
        h, w, ARCH, f32_mm)
    parts_ref = jnp.zeros_like(x)
    parts_prog = jnp.zeros_like(x)
    live = jnp.ones(16, bool)
    for g in range(8):
        arch_g = share(g)
        w_g = dict(w, **{k: w[k][4 * g:4 * g + 4]
                         for k in ("we_gate", "we_up", "we_down")})
        assert fam.experts_held(arch_g) == (4 * g, 4)
        parts_ref = parts_ref + fam.routed_experts(h, w_g, arch_g, f32_mm)
        stack = {k: w_g[k] for k in ("we_gate", "we_up", "we_down")}
        got, counts, _ = lm._routed_experts(w_g, stack, 0, h, live,
                                            fam.serve_args(arch_g))
        parts_prog = parts_prog + got
    total = fam.shared_experts(h, w, ARCH, f32_mm) + parts_ref
    np.testing.assert_allclose(total, whole, atol=TOL)
    np.testing.assert_allclose(
        fam.shared_experts(h, w, ARCH, f32_mm) + parts_prog, whole, atol=TOL)


def test_a_token_with_no_pick_here_still_gets_its_shared_experts(fam, params):
    from benchmarks.harness.reference import f32_mm, rms_norm

    w = _layer(params, ARCH, 1)
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(32, 64)).astype(np.float32))
    h = rms_norm(x, w["ln2"], 1e-6)
    weigh = np.asarray(fam.routing(h, w, ARCH, f32_mm))
    # a group that some token picks nothing from
    group, rows = next(
        (g, r) for g in range(8)
        for r in [np.nonzero(~weigh[:, 4 * g:4 * g + 4].any(-1))[0]]
        if len(r))
    arch_g = share(group)
    w_g = dict(w, **{k: w[k][4 * group:4 * group + 4]
                     for k in ("we_gate", "we_up", "we_down")})
    stack = {k: w_g[k] for k in ("we_gate", "we_up", "we_down")}
    out, counts, picks = lm._expert_ffn(w_g, stack, 0, x, jnp.ones(32, bool),
                                        fam.serve_args(arch_g))
    assert picks.shape == (32, 6)
    shared_only = x + fam.shared_experts(h, w, ARCH, f32_mm)
    np.testing.assert_allclose(np.asarray(out)[rows],
                               np.asarray(shared_only)[rows], atol=TOL)
    assert int(counts[1]) == int((weigh[:, 4 * group:4 * group + 4]
                                  != 0).sum())
    assert int(counts[2]) == 32 * 6


# ---------------------------------------------------------------------------
# attention: the two forms and the kernels
# ---------------------------------------------------------------------------

def test_the_absorbed_form_equals_the_decompressed_form(fam, params, args):
    """One decode step (absorbed) against the window program (decompressed)
    over the same cached rows, and both against the reference."""
    ids = _ids(41, 2)
    want = _ref_logits(fam, params, ids)
    got, pool, bt_row = _prefill(params, args, ids, [40])
    cos, sin = lm.rope_tables(256, args)
    # the 41st token: as a one-token window, and as a decode step
    new = np.zeros(P, np.int32)
    new[:2] = bt_row[40 // PS:][:2]
    window = np.zeros(8, np.int32)
    window[0] = ids[40]
    w_logits, *_ = lm.prefill_window(
        params, None, jnp.asarray(window), jnp.int32(40), jnp.int32(0),
        jnp.asarray(bt_row), jnp.asarray(new), pool, (), (cos, sin), args)
    bt = np.zeros((2, P), np.int32)
    bt[1] = bt_row
    d_logits, *_ = lm.decode_step(
        params, None, jnp.asarray([0, ids[40]]), jnp.asarray(bt),
        jnp.asarray([0, 40]), jnp.asarray([False, True]), pool, (),
        (cos, sin), args)
    np.testing.assert_allclose(d_logits[1], w_logits, atol=TOL)
    np.testing.assert_allclose(d_logits[1], want[40], atol=TOL)


def test_latent_decode_kernel_equals_its_oracle():
    rng = np.random.default_rng(0)
    b, H, vw, row, ps, pages, n = 3, 8, 128, 256, 16, 6, 40
    q = jnp.asarray(rng.normal(size=(b, H, row)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(2 * n, ps, row)), jnp.float32)
    bt = jnp.asarray(rng.integers(1, n, (b, pages)), jnp.int32)
    pos = jnp.asarray([0, 37, 95], jnp.int32)
    assert la.latent_decode_supported(q.shape, pool.shape, bt.shape, vw, 4)
    for base in (None, n):
        want = la._decode_xla(q, pool, bt, pos, 0.1, vw, base)
        with qm.fused_dispatch(True, interpret=True):
            got = la.latent_decode_attention(q, pool, bt, pos, 0.1, vw,
                                             page_base=base)
        np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("h,last", [(0, 63), (100, 40), (301, 63), (448, 63)])
def test_latent_prefill_kernel_equals_full_attention(monkeypatch, h, last):
    monkeypatch.setattr(la, "PREFILL_BLOCK_Q", 32)
    monkeypatch.setattr(la, "PREFILL_BLOCK_K", 128)
    rng = np.random.default_rng(1)
    s, H, nope, rope, T = 64, 4, 128, 64, 512
    qn = jnp.asarray(rng.normal(size=(s, H, nope)), jnp.float32) * 0.3
    qr = jnp.asarray(rng.normal(size=(s, H, rope)), jnp.float32) * 0.3
    kv = jnp.asarray(rng.normal(size=(T, H * 2 * nope)), jnp.float32)
    kr = jnp.asarray(rng.normal(size=(T, rope)), jnp.float32)
    assert la.latent_prefill_supported(qn.shape, qr.shape, kv.shape, nope)
    kvh = kv.reshape(T, H, 2 * nope)
    sc = (jnp.einsum("shn,thn->hst", qn, kvh[..., :nope])
          + jnp.einsum("shr,tr->hst", qr, kr)) * 0.1
    see = jnp.arange(T)[None, :] <= (h + jnp.arange(s))[:, None]
    p = jax.nn.softmax(jnp.where(see[None], sc, -1e30), -1)
    full = jnp.einsum("hst,thv->shv", p, kvh[..., nope:])
    real = last + 1
    for fused in (False, True):
        with qm.fused_dispatch(fused, interpret=True):
            got = la.latent_prefill_attention(
                qn, qr, kv, kr, jnp.int32(h), jnp.int32(last), 0.1, nope)
        np.testing.assert_allclose(got[:real], full[:real], atol=2e-5)
        assert bool(jnp.isfinite(got).all())


# ---------------------------------------------------------------------------
# the two step programs against the reference's full forward pass
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunks", [[50], [16, 16, 16, 2], [8, 32, 10],
                                    [5, 20, 25]])
def test_prefill_in_chunks_gives_the_references_logits(fam, params, args,
                                                       chunks):
    """Windows that start on and inside a page, the dense leading layer and
    the expert layers, the held experts all 32 here."""
    ids = _ids(50, 1)
    want = _ref_logits(fam, params, ids)
    got, _, _ = _prefill(params, args, ids, chunks)
    for pos, logits in got.items():
        np.testing.assert_allclose(logits, want[pos], atol=TOL)


@pytest.mark.parametrize("n_pre", [9, 24, 40])
def test_decode_through_the_cache_gives_the_references_logits(
        fam, params, args, n_pre):
    ids = _ids(50, 7)
    want = _ref_logits(fam, params, ids)
    _, pool, bt_row = _prefill(params, args, ids[:n_pre], [n_pre])
    bt_row[:8] = 1 + np.arange(8)
    cos, sin = lm.rope_tables(256, args)
    bt = np.zeros((3, P), np.int32)
    bt[2] = bt_row
    for t in range(n_pre, 50):
        logits, pool, _, (counts, _, _) = lm.decode_step(
            params, None, jnp.asarray([0, 0, ids[t]]), jnp.asarray(bt),
            jnp.asarray([0, 0, t]), jnp.asarray([False, False, True]), pool,
            (), (cos, sin), args)
        np.testing.assert_allclose(logits[2], want[t], atol=TOL)
    # one live row, two expert layers, every expert held: 6 picks a layer
    assert [int(c) for c in counts[1:3]] == [12, 12]


@pytest.mark.parametrize("group", [None, 3])
def test_decode_with_the_fused_expert_pass_gives_the_references_logits(
        fam, group):
    """The whole decode program at widths whole tiles divide (hidden 128,
    experts 128 wide) and 8 rows, the kernels interpreted: its expert
    layers are the fused pass, and the logits are the reference's, with
    every expert held and as the chip that holds group 3 of 8 (against the
    grouped form there: the reference computes every expert)."""
    arch = dict(ARCH, hidden_size=128, moe_intermediate_size=128)
    params = fam.make_params(arch, 5, jnp.float32)
    ids = _ids(30, 3)
    want = _ref_logits(fam, params, ids)
    if group is not None:
        arch = dict(share(group), hidden_size=128, moe_intermediate_size=128)
        params = dict(params, layers={
            k: v[:, 4 * group:4 * group + 4] if k.startswith("we_") else v
            for k, v in params["layers"].items()})
    args = fam.serve_args(arch)
    _, pool, bt_row = _prefill(params, args, ids[:20], [20])
    cos, sin = lm.rope_tables(256, args)
    bt = np.zeros((8, P), np.int32)
    bt[5] = bt_row
    live = np.arange(8) == 5
    # one jitted program a form: the form is decided as it is traced
    step = {fused: jax.jit(lambda *a: lm.decode_step(*a, (), (cos, sin),
                                                     args))
            for fused in (True, False)}
    pools = {True: pool, False: pool}
    for t in range(20, 30):
        operands = (params, None, jnp.asarray(np.where(live, ids[t], 0)),
                    jnp.asarray(bt), jnp.asarray(np.where(live, t, 0)),
                    jnp.asarray(live))
        got = {}
        for fused in (True, False) if group is not None else (True,):
            with qm.fused_dispatch(fused, interpret=True):
                assert lm.experts_fused(8, args, jnp.float32) == fused
                logits, pools[fused], _, (counts, _, _) = step[fused](
                    *operands, pools[fused])
            got[fused] = np.asarray(logits[5])
        if group is None:
            np.testing.assert_allclose(got[True], want[t], atol=TOL)
            assert [int(c) for c in counts[1:3]] == [12, 12]
        else:
            np.testing.assert_allclose(got[True], got[False], atol=TOL)
            assert np.abs(got[True] - want[t]).max() > 100 * TOL
            assert 0 <= int(counts[1]) <= 8 and int(counts[2]) == 12


def test_the_dense_leading_layer_is_in_the_stack(fam, params, args):
    """`first_k_dense_replace` = 1: layer 0 runs the SwiGLU of `dense_layers`
    in the program and the reference alike; read as an expert layer's
    leaves the stack gives other logits."""
    assert args.first_k_dense == 1 and "dense_layers" in params
    assert params["dense_layers"]["w_gate"].shape == (1, 64, 160)
    ids = _ids(20, 9)
    want = _ref_logits(fam, params, ids)
    got, _, _ = _prefill(params, args, ids, [20])
    np.testing.assert_allclose(got[19], want[19], atol=TOL)
    arch0 = dict(ARCH, first_k_dense_replace=0, num_hidden_layers=2)
    without = _ref_logits(fam, {**params, "dense_layers": None}, ids, arch0)
    assert np.abs(without[19] - want[19]).max() > 100 * TOL


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

def _served(fam, params, req):
    seq = np.concatenate([req.prompt_ids,
                          np.asarray(req.token_ids[:-1], np.int32)])
    return list(_ref_logits(fam, params, seq)[len(req.prompt_ids) - 1:]
                .argmax(-1))


@pytest.mark.parametrize("chunk", [None, 16, 32])
def test_engine_serves_the_references_tokens(fam, params, args, chunk):
    eng = PagedEngine(params, args, **dict(ENGINE, prefill_chunk=chunk))
    reqs = [Request(_ids(n, n), 6) for n in (20, 37, 9, 50)]
    eng.serve(reqs)
    for r in reqs:
        assert list(r.token_ids) == _served(fam, params, r)
    obs = eng.metrics.summary()["observations"]
    assert obs["serve.routed_here_share"]["mean"] == 1.0    # all 32 held
    assert 1.0 <= obs["serve.expert_load_max_over_mean"]["mean"] <= 32
    assert 0 < obs["serve.held_experts_hit"]["mean"] <= 32
    # one sample a step program; on the CPU none is the fused pass
    assert obs["serve.expert_fused_share"]["count"] == eng.metrics.summary()[
        "counters"]["serve.dispatched"]
    assert obs["serve.expert_fused_share"]["max"] == 0.0


def _own_picks(fam, params, seq, scores=None):
    """The reference's own picks [positions, expert layers, k], sorted
    (and, appended to `scores`, each expert layer's router scores)."""
    from benchmarks.harness.reference import f32_mm, rms_norm

    rows = []
    x = params["embedding"][jnp.asarray(seq)].astype(jnp.float32)
    for i in range(ARCH["num_hidden_layers"]):
        w = _layer(params, ARCH, i)
        if i >= ARCH["first_k_dense_replace"]:
            pos = jnp.arange(len(seq))
            qn, qr, kn, kr, v = fam.project(x, w, ARCH, f32_mm, pos)
            mid = x + f32_mm(fam.attend(qn, qr, pos, kn, kr, v, ARCH).reshape(
                len(seq), -1), w["wo"])
            hin = rms_norm(mid, w["ln2"], 1e-6)
            weigh = fam.routing(hin, w, ARCH, f32_mm)
            if scores is not None:
                scores.append(np.asarray(jax.nn.softmax(
                    f32_mm(hin, w["router"]), axis=-1)))
            rows.append(np.stack([np.nonzero(r)[0] for r in
                                  np.asarray(weigh)]))
        x = fam.layer_forward(x, w, ARCH, f32_mm, i)
    return np.stack(rows, axis=1)


def test_a_request_carries_the_experts_its_tokens_picked(fam, params, args):
    """`req.routing`: every position of the prompt and of the served tokens
    fed back, every expert layer, the picks the reference makes itself (in
    float32 nothing ties by rounding)."""
    eng = PagedEngine(params, args, **ENGINE)
    req = Request(_ids(37, 2), 7)       # three windows, six decode steps
    eng.serve([req])
    seq = np.concatenate([req.prompt_ids,
                          np.asarray(req.token_ids[:-1], np.int32)])
    table = req.routing.table(len(seq))
    assert table.shape == (43, 2, 6) and table.min() >= 0
    np.testing.assert_array_equal(np.sort(table, -1),
                                  _own_picks(fam, params, seq))
    # a position whose window never ran for this request reads -1
    hit = Request(np.concatenate([req.prompt_ids[:16], _ids(5, 3)]), 3)
    eng.serve([hit])
    table = hit.routing.table(21)
    assert (table[:16] == -1).all() and (table[16:] >= 0).all()
    # the slot's next owner decodes at positions the first request also
    # had: its rows of the step log are not the first request's
    assert eng.max_slots == 3 and len(eng.path.riders.log) > 6
    np.testing.assert_array_equal(
        np.sort(req.routing.table(len(seq)), -1), _own_picks(fam, params, seq))


def test_the_reference_follows_a_near_tie_and_no_other_pick(fam):
    """`routing(given=)`: a recorded pick set is followed where each pick
    scores within ROUTING_TOL of the reference's own last pick (in a group
    within ROUTING_TOL of the last that stays); any other keeps the
    reference's own picks."""
    from benchmarks.harness.reference import f32_mm

    # one token, logits by hand: groups of 4; the best of groups 0, 1, 2
    # stay; own picks by score: experts 0, 4, 8, 1, 5 and 9 (the sixth);
    # expert 2 scores a quarter of the tolerance under expert 9, expert 6
    # well past the tolerance under it, and group 3's best (expert 12)
    # scores a third of the tolerance under the weakest staying group's
    tol = fam.ROUTING_TOL
    near, far = 1 - tol / 4, (1 - tol) * 0.8
    logit = np.full(32, -9.0)
    logit[[0, 4, 8]] = [3.0, 2.9, 2.8]
    logit[[1, 5, 9]] = [2.0, 1.9, 1.8]
    logit[2] = 1.8 + np.log(near)
    logit[6] = 1.8 + np.log(far)
    logit[12] = 2.8 + np.log(1 - tol / 3)
    logit[13] = 1.8 + np.log(1 - tol / 8)
    h = jnp.eye(1, 64, dtype=jnp.float32)
    w = {"router": jnp.zeros((64, 32)).at[0].set(jnp.asarray(logit))}
    own = [0, 1, 4, 5, 8, 9]

    def picks(given):
        g = None if given is None else jnp.asarray([given], jnp.int32)
        weigh = np.asarray(fam.routing(h, w, ARCH, f32_mm, g))[0]
        return sorted(np.nonzero(weigh)[0])

    assert picks(None) == own and picks([-1] * 6) == own
    assert picks([9, 8, 5, 4, 1, 0]) == own
    assert picks([0, 1, 4, 5, 8, 2]) == [0, 1, 2, 4, 5, 8]     # near
    assert picks([0, 1, 4, 5, 8, 6]) == own                    # far
    # picks from a group that does not stay here (group 3 for group 2):
    # followed where that group's best is within the tolerance of the
    # weakest staying group's, the picks being the best of ITS three groups;
    # picks from four groups are no routing the rule could make
    assert picks([0, 1, 4, 5, 12, 13]) == [0, 1, 4, 5, 12, 13]
    assert picks([0, 1, 4, 5, 8, 13]) == own
    w2 = {"router": w["router"].at[0, 12].set(2.8 + np.log(far))}
    weigh = np.asarray(fam.routing(h, w2, ARCH, f32_mm,
                                   jnp.asarray([[0, 1, 4, 5, 12, 13]])))[0]
    assert sorted(np.nonzero(weigh)[0]) == own
    assert picks([0, 1, 4, 5, 8, 8]) == own                    # five picks
    # the weight of a followed pick is the reference's own score
    weigh = np.asarray(fam.routing(
        h, w, ARCH, f32_mm, jnp.asarray([[0, 1, 4, 5, 8, 2]])))[0]
    score = np.asarray(jax.nn.softmax(jnp.asarray(logit)))
    np.testing.assert_allclose(weigh[2], 16 * score[2], rtol=1e-5)


def test_served_logits_follow_the_recorded_routing(fam, params):
    """With a token's picks swapped for a near-tie the reference's hidden
    state moves with them; with a pick far off it does not."""
    ids = _ids(24, 6)
    scores = []
    own = _own_picks(fam, params, ids, scores)

    def hidden(picks):
        return np.asarray(fam.forward_hidden(
            ARCH, ids, lambda i: _layer(params, ARCH, i),
            params["embedding"], picks=picks))

    base = hidden(None)
    np.testing.assert_allclose(hidden(own.astype(np.int32)), base, atol=1e-6)
    far = own.astype(np.int32).copy()
    far[5, 0, 0] = int(np.argmin(scores[0][5]))     # the worst expert
    np.testing.assert_allclose(hidden(far), base, atol=1e-6)
    # the seventh-best of the experts that stay, made a near-tie by hand:
    # followed, and the token's hidden state moves
    kept = [e for e in np.argsort(-scores[0][5])
            if e // 4 in {p // 4 for p in own[5, 0]}]
    seventh, sixth = int(kept[6]), int(kept[5])
    if scores[0][5][seventh] >= (1 - fam.ROUTING_TOL) * scores[0][5][sixth]:
        near = own.astype(np.int32).copy()
        near[5, 0][list(near[5, 0]).index(sixth)] = seventh
        moved = hidden(near)
        assert np.abs(moved[5] - base[5]).max() > 1e-3
        np.testing.assert_allclose(moved[:5], base[:5], atol=1e-6)


def test_a_share_serves_through_the_engine(fam):
    """One group of eight held: the engine's tokens are the share's
    reference's, and an eighth or so of the picks land here."""
    arch = share(3)
    params = fam.make_params(arch, 11, jnp.float32)
    assert params["layers"]["we_up"].shape == (2, 4, 64, 32)
    eng = PagedEngine(params, fam.serve_args(arch), **ENGINE)
    reqs = [Request(_ids(n, n), 8) for n in (23, 41)]
    eng.serve(reqs)
    for r in reqs:
        seq = np.concatenate([r.prompt_ids,
                              np.asarray(r.token_ids[:-1], np.int32)])
        want = _ref_logits(fam, params, seq, arch)[len(r.prompt_ids) - 1:]
        assert list(r.token_ids) == list(want.argmax(-1))
    here = eng.metrics.summary()["observations"]["serve.routed_here_share"]
    assert 0.0 <= here["mean"] < 0.5


def test_a_prefix_hit_that_ends_mid_page_copies_the_latent_page(params, args):
    eng = PagedEngine(params, args, **ENGINE)
    base = _ids(12, 4)                  # a page and a half
    eng.serve([Request(base, 3)])
    longer = np.concatenate([base, _ids(8, 5)])
    warm = Request(longer, 5)
    eng.serve([warm])
    c = eng.metrics.summary()["counters"]
    assert c["cow_copies"] == 1 and c["prefix_tokens_hit"] == 12
    cold_eng = PagedEngine(params, args, **ENGINE)
    cold = Request(longer, 5)
    cold_eng.serve([cold])
    assert list(warm.token_ids) == list(cold.token_ids)


def test_preempt_and_resume_carry_the_pages(params, args):
    eng = PagedEngine(params, args, **ENGINE)
    req = eng.submit(Request(_ids(21, 8), 10))
    while len(req.token_ids) < 4:
        eng.step()
    slot = next(s for s in eng.slots.active_slots
                if eng.slots.owner(s) is req)
    state = eng.preempt(slot)
    # nothing beside its pages leaves with it: an empty state tree
    assert not jax.tree_util.tree_leaves(state["path_state"])
    assert state["pages"]
    other = Request(_ids(15, 9), 4)
    eng.serve([other])                  # the slot is used meanwhile
    assert eng.can_resume(state)
    eng.resume(state)
    while not req.finished:
        eng.step()
    straight = Request(_ids(21, 8), 10)
    PagedEngine(params, args, **ENGINE).serve([straight])
    assert list(req.token_ids) == list(straight.token_ids)
    # its routing trace finds its rows in both of its stays, and none of
    # the request's that held the slot in between
    np.testing.assert_array_equal(req.routing.table(30),
                                  straight.routing.table(30))
    assert straight.routing.table(30).min() >= 0


def test_routing_is_recorded_only_where_the_description_asks(params, args):
    eng = PagedEngine(params, args._replace(record_routing=False), **ENGINE)
    req = Request(_ids(21, 8), 5)
    eng.serve([req])
    assert getattr(req, "routing", None) is None and eng.path.riders.log == []
    asked = Request(_ids(21, 8), 5)
    eng = PagedEngine(params, args, **ENGINE)
    eng.serve([asked, Request(_ids(9, 1), 3)])
    assert list(asked.token_ids) == list(req.token_ids)
    # one log entry a decode step, whatever the rows
    assert len(eng.path.riders.log) == eng.metrics.summary()["counters"][
        "decode_steps"]


def test_a_reset_engine_serves_again_with_a_cold_cache(params, args):
    eng = PagedEngine(params, args, **ENGINE)
    first = Request(_ids(19, 3), 4)
    eng.serve([first])
    eng.reset()
    again = Request(_ids(19, 3), 4)
    eng.serve([again])
    assert list(first.token_ids) == list(again.token_ids)
    assert eng.metrics.summary()["counters"].get("prefix_tokens_hit", 0) == 0


@pytest.mark.parametrize("what,kw", [
    ("mesh=", {"mesh": object()}),
    ("kv_dtype='int8'", {"kv_dtype": "int8"}),
    ("draft_params=", {"draft_params": {}, "draft_args": object()}),
])
def test_what_is_not_carried_is_refused_with_the_reason(params, args, what,
                                                        kw):
    with pytest.raises(ValueError, match=what + " is not supported for a "
                       "latent-attention expert model"):
        PagedEngine(params, args, **dict(ENGINE, **kw))


def test_disaggregated_hand_off_is_refused(params, args):
    eng = PagedEngine(params, args, **ENGINE)
    with pytest.raises(ValueError, match="one pool of latent rows"):
        eng.path.check_handoff()


@pytest.mark.parametrize("bad,why", [
    ({"topk_group": 9}, "topk_group"),
    ({"first_expert": 30}, "experts held"),
    ({"first_k_dense": 3}, "expert layer"),
    ({"experts_per_tok": 13}, "experts_per_tok"),
])
def test_a_description_that_cannot_be_is_refused(args, bad, why):
    with pytest.raises(ValueError, match=why):
        args._replace(**bad).validate()
