"""Gated delta-rule layers beside gated latent attention, routed + shared
experts behind either mixer, through `PagedEngine` on the CPU at a tiny preset
with the published PATTERN: 1 dense + 1 latent + 3 delta layers (delta +
dense, latent + experts, 3 x delta + experts), hidden 64, 2 key heads serving
4 value heads (8 x 16), a 4-tap convolution, 4 latent heads of 16 + 8 over a
row of 40 values, 32 experts of which 8 are held and 4 picked, page 8.

Everything is compared with the plain float32 reference of
`benchmarks/families/mla_delta_moe.py` (the equations, a scan over tokens, no
kernel, no cache) on seeded float32 weights: the logits of prefill in chunks
and of decode through pool and state, the engine's own tokens with their
recorded routing, a snapshot hit over shared latent pages, preempt / resume,
a recycled slot; the shares of one expert layer against the uncut layer; the
configuration's arithmetic; the decode step's kernel at the published state
shape. Tolerance: both sides are float32, so what differs is the order of
sums, the chunked scan's triangular inverse and the absorbed form of the
latent attention: 1e-4 on logits of magnitude ~1 leaves ten times of room
over the widest seen (8e-6) and is far under what a dropped gate, clamp or
norm costs (each moves a logit by more than 1e-2 here).
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

from paddle_tpu.kernels import gated_delta_rule as gdr  # noqa: E402
from paddle_tpu.kernels import quantized_matmul as qm  # noqa: E402
from paddle_tpu.models import latent_delta_functional as ldf  # noqa: E402
from paddle_tpu.models import latent_moe_functional as lm  # noqa: E402
from paddle_tpu.serving import PagedEngine, Request, paths  # noqa: E402
from paddle_tpu.serving import family, routing  # noqa: E402

TOL = 1e-4

ARCH = {
    "family": "mla_delta_moe", "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 5,
    "first_k_dense_replace": 1, "full_attention_layers": [1],
    "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 8, "n_shared_experts": 1, "n_group": 1,
    "topk_group": 1, "num_experts_per_tok": 4, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "vocab_size": 256, "rms_norm_eps": 1e-06,
    "rope_theta": 10000, "initializer_range": 0.15, "router_bias_std": 0.05,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 8,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 64, "type": "yarn"},
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "linear_attn_o_norm_eps": 1e-06,
    "linear_sigmoid_gate_scale": 2,
    # low enough to bind: a projection's values reach +-4 at this preset
    "swiglu_limit": 1.5,
    "published": {"n_routed_experts": 32},
    "deployment": {"chips_per_layer": 4, "first_expert_held": 8}}
HV, DK, DV, K, C = 4, 8, 16, 4, 2 * 2 * 8 + 4 * 16
B, ROW = 8, 128
ENGINE = dict(max_slots=3, max_len=128, page_size=8, num_pages=80,
              min_bucket=8, prefill_chunk=16)


@pytest.fixture(scope="module")
def fam():
    """The family's file, loaded by its path as the harness loads it."""
    path = os.path.join(ROOT, "benchmarks", "families", "mla_delta_moe.py")
    spec = importlib.util.spec_from_file_location("family_mla_delta", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.T_BLOCK, mod.Q_BLOCK, mod.K_BUCKET = 64, 32, 64   # at test size
    return mod


@pytest.fixture(scope="module")
def args(fam):
    return fam.serve_args(ARCH)


@pytest.fixture(scope="module")
def params(fam):
    from benchmarks.harness import weights

    return weights.make_params(fam, ARCH, 11, jnp.float32)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(1, 256, n).astype(np.int32)


def _ref_logits(fam, params, ids, picks=None, notes=None, arch=ARCH):
    """The reference's logits at every position of `ids`."""
    kinds = fam.layer_kinds(arch)
    place = [kinds[:i].count(k) for i, k in enumerate(kinds)]
    x = fam.forward_hidden(
        arch, ids,
        lambda i: {k: v[place[i]] for k, v in params[kinds[i]].items()},
        params["embedding"], picks=picks, notes=notes)
    return np.asarray(fam.head_logits(arch, x, params["final_norm"],
                                      params["lm_head"]))


def test_the_preset_has_the_published_pattern(fam, args):
    assert fam.layer_kinds(ARCH) == args.layer_kinds == (
        "delta_dense", "latent_experts", "delta_experts", "delta_experts",
        "delta_experts")
    assert args.layers_of(ldf.DELTA) == [0, 2, 3, 4]
    assert (args.first_expert, args.experts_held, args.routed_experts) == (
        8, 8, 32)
    assert args.linear_value_heads == 2 * args.linear_key_heads


# ---------------------------------------------------------------------------
# the two step programs against the reference's full forward: logits
# ---------------------------------------------------------------------------

class Stepper:
    """`ldf.prefill_window` / `ldf.decode_step` over fresh pools: slot 1 of
    2, pages 1 .. 16 (the other slot's states start as garbage)."""

    P, NP, SLOTS = 16, 40, 2

    def __init__(self, params, args):
        self.params, self.args = params, args
        self.pools = ldf.pools(args, self.NP, B, jnp.float32)
        self.state = jax.tree_util.tree_map(
            lambda a: jnp.full(a.shape, 7.0, a.dtype),
            ldf.slot_state(args, self.SLOTS, jnp.float32))
        self.tables = ldf.tables(args, 128)
        self.bt_row = np.arange(1, self.P + 1).astype(np.int32)
        self.layer_ids = jnp.arange(args.num_layers, dtype=jnp.int32)

    def window(self, ids, h, e, sb):
        padded = np.zeros(sb, np.int32)
        padded[:e - h] = ids[h:e]
        new = np.zeros(self.P, np.int32)
        touched = self.bt_row[h // B: -(-e // B)]
        new[:len(touched)] = touched
        # what `serving/family._prefill_traced` does around the family's
        # window: the slot's own state, zero where the window starts at 0
        own = jax.tree_util.tree_map(
            lambda a: jnp.where(h == 0, 0.0, a[1]), self.state)
        logits, self.pools, own, (_, picks, _) = _PREFILL(
            self.params, self.layer_ids, jnp.asarray(padded), jnp.int32(h),
            jnp.int32(e - 1 - h), jnp.asarray(self.bt_row), jnp.asarray(new),
            self.pools, own, self.tables, args=self.args)
        assert picks.shape == (4, sb, 4)
        self.state = jax.tree_util.tree_map(lambda a, o: a.at[1].set(o),
                                            self.state, own)
        return np.asarray(logits)

    def step(self, token, t):
        bt = np.zeros((self.SLOTS, self.P), np.int32)
        bt[1] = self.bt_row
        logits, self.pools, self.state, (counts, picks, _) = _DECODE(
            self.params, self.layer_ids, jnp.asarray([0, token], jnp.int32),
            jnp.asarray(bt), jnp.asarray([0, t], jnp.int32),
            jnp.asarray([False, True]), self.pools, self.state, self.tables,
            args=self.args)
        # one live row: 4 picks a layer in all, 4 expert layers
        assert counts.shape == (4,) and int(counts[2]) == 16
        assert picks.shape == (4, self.SLOTS, 4)
        return np.asarray(logits)[1]


_PREFILL = jax.jit(ldf.prefill_window, static_argnames=("args",))
_DECODE = jax.jit(ldf.decode_step, static_argnames=("args",))


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_prefill_in_chunks_gives_the_references_logits(fam, params, args,
                                                       chunk):
    """Every window's last logits, the windows carrying latent pages, the
    matrix state and the convolution's rows; the last window is padded to
    its bucket."""
    ids, n = _ids(71, chunk), 71
    ref = _ref_logits(fam, params, ids)
    run, h = Stepper(params, args), 0
    while h < n:
        e = min(h + chunk, n)
        np.testing.assert_allclose(run.window(ids, h, e, chunk), ref[e - 1],
                                   atol=TOL, rtol=1e-4)
        h = e


@pytest.mark.parametrize("n_pre", [2, 20, 63])
def test_decode_through_pool_and_state_gives_the_references_logits(
        fam, params, args, n_pre):
    """From a prompt shorter than the convolution's reach (2), one that ends
    inside a page (20) and one that ends on a page's last row (63); the
    other row of the batch is dead and keeps its garbage."""
    n = n_pre + 12
    ids = _ids(n, n_pre)
    ref = _ref_logits(fam, params, ids)
    run = Stepper(params, args)
    for h in range(0, n_pre, 32):
        run.window(ids, h, min(h + 32, n_pre), 32)
    garbage = jax.tree_util.tree_map(lambda a: np.asarray(a[0]), run.state)
    for t in range(n_pre, n):
        np.testing.assert_allclose(run.step(ids[t], t), ref[t], atol=TOL,
                                   rtol=1e-4)
    for kept, now in zip(jax.tree_util.tree_leaves(garbage),
                         jax.tree_util.tree_leaves(run.state)):
        np.testing.assert_array_equal(kept, np.asarray(now[0]))


@pytest.mark.parametrize("h,e", [(5, 8), (5, 9), (7, 16), (8, 17), (3, 23)])
def test_a_window_around_a_page_edge_keeps_what_the_page_holds(
        fam, params, args, h, e):
    """A window that starts inside a page and ends before, on and past the
    next page's edge (buckets of 8 and 16 and 32): the rows below h stay."""
    ids = _ids(e, 100 * h + e)
    ref = _ref_logits(fam, params, ids)
    run = Stepper(params, args)
    run.window(ids, 0, h, 8)
    bucket = 8 if e - h <= 8 else 16 if e - h <= 16 else 32
    np.testing.assert_allclose(run.window(ids, h, e, bucket), ref[e - 1],
                               atol=TOL, rtol=1e-4)


# ---------------------------------------------------------------------------
# through the engine
# ---------------------------------------------------------------------------

_ENGINES = {}


def _engine(params, args, **kw):
    """An EMPTY engine of these arguments: built once (its programs compile
    once a file), handed out again after `reset()`."""
    key = tuple(sorted(kw.items()))
    if key not in _ENGINES:
        _ENGINES[key] = PagedEngine(params, args, **dict(ENGINE, **kw))
    _ENGINES[key].reset()
    return _ENGINES[key]


def _gap(fam, params, req, follow=True):
    """How far each served token's reference logit lies below the best, the
    reference following the routing the request recorded; and the routing's
    notes summed (differing, followed, -)."""
    seq = np.concatenate([req.prompt_ids, np.asarray(req.token_ids)[:-1]])
    notes = []
    picks = req.routing.table(len(seq)) if follow else None
    lg = _ref_logits(fam, params, seq, picks, notes)[len(req.prompt_ids) - 1:]
    toks = np.asarray(req.token_ids)
    return (lg.max(-1) - lg[np.arange(len(toks)), toks],
            np.sum(notes, axis=(0, 1)) if notes else None)


@pytest.mark.parametrize("chunk", [None, 16])
def test_engine_serves_the_references_tokens(fam, params, args, chunk):
    eng = _engine(params, args, prefill_chunk=chunk)
    assert type(eng.path) is family.FamilyPath and eng.path.family is ldf
    reqs = eng.serve([Request(_ids(n, n), 6) for n in (2, 9, 45, 100)])
    for r in reqs:
        assert len(r.token_ids) == 6
        gap, notes = _gap(fam, params, r)
        assert gap.max() < TOL
        # float32 on both sides: the recorded picks are the reference's own
        assert notes[0] == 0
        table = r.routing.table(len(r.prompt_ids) + 5)
        assert table.shape == (len(r.prompt_ids) + 5, 4, 4)
        assert table.min() >= 0 and table.max() < 32
    path = eng.path
    assert path.state[0]["S"].shape == (3, HV // 2, DK, 2 * DV)
    assert [p.shape for p in path.pools] == [(80, B, ROW)]
    assert path.tokens.shape == (3 + ldf.riders(args)[0],)
    obs = eng.metrics.summary()
    state = 4 * (HV * DK * DV + (K - 1) * C) * 4
    gauges = {k: v["value"] for k, v in obs["gauges"].items()}
    assert gauges["serve.slot_state_bytes"] == state
    assert gauges["recurrent_state_bytes"] == 3 * state
    assert gauges["serve.latent_pool_bytes"] == gauges["kv_pool_bytes"] \
        == 80 * B * ROW * 4
    assert gauges["serve.delta_step_pallas"] == 0
    assert obs["counters"]["state_snapshots"] == 4
    seen = obs["observations"]
    # a decode step's rows move their whole state in and out, and the cache
    # of ONE latent layer: 12,800 B a row against 512 B a cached token
    assert seen["serve.state_bytes_step"]["max"] == 2 * 3 * state
    assert seen["serve.state_bytes_step"]["min"] == 2 * state
    assert 0 < seen["serve.cache_bytes_step"]["min"] \
        < seen["serve.cache_bytes_step"]["max"] <= 3 * 106 * ROW * 4
    assert 0 < seen["serve.routed_here_share"]["mean"] < 1
    assert 0 < seen["serve.held_experts_hit"]["mean"] <= 8
    assert seen["serve.expert_load_max_over_mean"]["min"] >= 1


def test_the_step_programs_carry_the_scopes_the_readers_sum(params, args):
    eng = _engine(params, args)
    path, slots = eng.path, ENGINE["max_slots"]
    text = path._decode[False].lower(
        eng.params, path.layer_ids, path.tokens,
        jnp.zeros((slots, eng.pages_per_slot), jnp.int32),
        jnp.zeros(slots, jnp.int32), jnp.zeros(slots, bool), path.pools,
        path.state, path.tables, *eng._sampling_args()
    ).as_text(debug_info=True)
    for scope in ("pt.attention/pt.delta_rule", "pt.attention/pt.short_conv",
                  "pt.latent_attention", "pt.kv_write", "pt.moe_route",
                  "pt.expert_ffn", "pt.mlp", "pt.sample"):
        assert scope in text, scope


def test_a_recycled_slot_starts_from_zero(fam, params, args):
    eng = _engine(params, args, max_slots=1)
    first, second = eng.serve([Request(_ids(50, 1), 5),
                               Request(_ids(44, 2), 5)])
    cold = _engine(params, args, max_slots=1).serve(   # the same, emptied
        [Request(_ids(44, 2), 5)])[0]
    assert second.token_ids == cold.token_ids
    assert _gap(fam, params, second)[0].max() < TOL


@pytest.mark.parametrize("n", [12, 16])
def test_a_prefix_hit_loads_a_snapshot_and_shares_latent_pages(
        fam, params, args, n):
    """The first prompt's state is saved at its end (inside a page for 12
    tokens, at a page's edge for 16) and joins the radix tree with its
    latent pages when the request retires; a longer prompt with that prefix
    starts from both, and its routing trace has no picks for the positions
    it never ran."""
    base, tail = _ids(n, 3), _ids(20, 4)
    longer = np.concatenate([base, tail])
    eng = _engine(params, args)
    eng.serve([Request(base, 4)])
    hit = eng.serve([Request(longer, 6)])[0]
    c = eng.metrics.summary()["counters"]
    assert c["prefix_tokens_hit"] == n
    assert c["prefix_pages_hit"] == n // B
    assert c.get("cow_copies", 0) == (1 if n % B else 0)
    cold = _engine(params, args).serve([Request(longer, 6)])[0]
    assert hit.token_ids == cold.token_ids
    gap, _ = _gap(fam, params, hit)
    assert gap.max() < TOL
    table = hit.routing.table(len(longer) + 5)
    assert (table[:n] == -1).all() and table[n:].min() >= 0


def test_a_snapshot_holds_the_matrix_state_and_the_convolutions_rows(params,
                                                                     args):
    eng = _engine(params, args)
    eng.serve([Request(_ids(12, 3), 4)])
    assert all(np.abs(np.asarray(s["S"])).max() > 0 for s in eng.path.snaps)
    assert all(np.abs(np.asarray(s["conv"])).max() > 0
               for s in eng.path.snaps)


def test_preempt_and_resume_carry_state_pages_and_trace(fam, params, args):
    eng = _engine(params, args)
    req = eng.submit(Request(_ids(30, 5), 10))
    while len(req.token_ids) < 4:
        eng.step()
    slot = next(iter(eng.slots.active_slots))
    saved = eng.preempt(slot)
    # the slot serves another request in between
    other = eng.serve([Request(_ids(25, 6), 5)])[0]
    eng.resume(saved)
    while not req.finished:
        eng.step()
    cold = _engine(params, args).serve([Request(_ids(30, 5), 10)])[0]
    assert req.token_ids == cold.token_ids
    for r in (req, other):
        gap, _ = _gap(fam, params, r)
        assert gap.max() < TOL
    # both stays' decode rows are in the trace, none the other request's
    np.testing.assert_array_equal(req.routing.table(39),
                                  cold.routing.table(39))


# ---------------------------------------------------------------------------
# the share adds up
# ---------------------------------------------------------------------------

def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(fam, params,
                                                                 args):
    """One expert layer (layer 2: delta + experts) on 24 tokens: the routed
    parts that the four shares of the 32 published experts give (the
    PROGRAM's dispatch, each share told which 8 it holds), with the shared
    expert counted once, under the layer's last norm, are the uncut
    reference's layer. The held share's weights are the stack's; the other
    shares' are seeded here."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(24, 64)), jnp.float32)
    w = {k: v[0] for k, v in params["delta_experts"].items()}
    shares = []
    for first in (0, 8, 16, 24):
        if first == 8:
            shares.append({k: w[k] for k in ("we_gate", "we_up", "we_down")})
        else:
            shares.append({k: jnp.asarray(
                rng.normal(scale=0.15, size=w[k].shape), jnp.float32)
                for k in ("we_gate", "we_up", "we_down")})
    whole = dict(w, **{k: jnp.concatenate([s[k] for s in shares])
                       for k in shares[0]})
    uncut = dict(ARCH, n_routed_experts=32,
                 deployment={"first_expert_held": 0})
    want, _ = fam.ffn_noting(x, fam._scaled(whole), uncut, fam.f32_mm, False)

    lp = {k: v for k, v in fam._scaled(w).items() if not k.startswith("we_")}
    hin = fam.rms_norm(x, lp["ln2"], 1e-6)
    live = jnp.ones(24, bool)
    parts, held = [], 0
    for first, share in zip((0, 8, 16, 24), shares):
        part, counts, picks = lm._routed_experts(
            lp, share, 0, hin, live, args._replace(first_expert=first))
        parts.append(part)
        held += int(counts[1])
        assert int(counts[2]) == 24 * 4
    assert held == 24 * 4           # every pick lands on exactly one share
    shared = lm._swiglu(hin, lp["ws_gate"], lp["ws_up"], lp["ws_down"],
                        args.swiglu_limit)
    got = x + fam.rms_norm(shared + sum(parts), lp["ln2_post"], 1e-6)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=1e-4)
    # and one share alone is not the layer
    alone = x + fam.rms_norm(shared + parts[1], lp["ln2_post"], 1e-6)
    assert np.abs(np.asarray(alone - want)).max() > 0.05


# ---------------------------------------------------------------------------
# the configuration's arithmetic
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def real():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "gigachat3.5-1chip.json")) as f:
        return json.load(f)


def test_the_configurations_arithmetic(fam, real):
    n = fam.param_count(real)
    M = lambda x: round(x / 1e6, 1)
    assert [M(x) for x in n["layers"]] == [632.3, 910.4, 986.4, 986.4, 986.4]
    assert M(n["outer"]) == 229.8 and M(n["total"]) == 4731.7
    assert M(n["mixer"]["delta"]) == 235.9
    assert M(n["mixer"]["latent"]) == 159.8
    assert M(n["expert"]) == 44.0 and M(n["dense_ffn"]) == 396.4
    assert round(n["router"] / 1e6, 2) == 1.84
    assert fam.state_bytes(real) == 17170432          # 17.17 MB a request
    assert fam.row_bytes(real) == 1280                # a cached token
    assert fam.layer_kinds(real) == ("delta_dense", "latent_experts",
                                     "delta_experts", "delta_experts",
                                     "delta_experts")
    args = fam.serve_args(real)
    assert (args.linear_key_heads, args.linear_value_heads) == (32, 64)
    assert args.row_width == 640 and args.routed_experts == 256
    assert (args.first_expert, args.experts_held) == (0, 16)
    assert lm.softmax_scale(args) == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(8) + 1) ** 2)
    state = jax.eval_shape(lambda: ldf.slot_state(args, 64, jnp.bfloat16))
    assert state[0]["S"].shape == (64, 64, 128, 128)
    assert sum(x.size * x.dtype.itemsize for x in
               jax.tree_util.tree_leaves(state)) == 64 * 17170432


def test_the_uncut_model_counts_the_published_parameters(fam, real):
    """All 40 layers, 256 experts and the whole vocabulary: 430.5 B without
    the two prediction modules, against the published 432 B."""
    whole = dict(real, **real["published"])
    assert round(fam.param_count(whole)["total"] / 1e9, 1) == 430.5


# ---------------------------------------------------------------------------
# the decode step's kernel at the published state shape
# ---------------------------------------------------------------------------

def test_the_published_state_takes_the_kernel_two_blocks_a_row():
    state = (2, 64, 128, 128)
    assert gdr.heads_per_row(64, 128) == 1
    assert gdr._step_block(state, 64) == 32      # 2 grid steps a row
    with qm.fused_dispatch(True, interpret=True):
        assert gdr.step_is_pallas(state, 64)
    with qm.fused_dispatch(False):
        assert not gdr.step_is_pallas(state, 64)


def test_the_kernel_is_the_jnp_step_at_the_published_shape():
    """[rows, 64, 128, 128] float32, 64 value heads whose k and q fill one
    tile's 128 lanes exactly, p = 1, two blocks of 32 heads a row; key head
    j's k and q repeated over value heads 2j, 2j + 1; one row dead."""
    rng = np.random.default_rng(5)
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    unit = lambda y: y / jnp.linalg.norm(y, axis=-1, keepdims=True)
    r = 2
    q = jnp.repeat(unit(f32(r, 32, 128)) * 128 ** -0.5, 2, axis=1)
    k = jnp.repeat(unit(f32(r, 32, 128)), 2, axis=1)
    a = (q, k, f32(r, 64, 128), -jnp.abs(f32(r, 64)),
         jax.nn.sigmoid(f32(r, 64)), f32(r, 64, 128, 128),
         jnp.asarray([True, False]))

    @functools.partial(jax.jit, static_argnames="kernel")
    def step(*a, kernel):
        with qm.fused_dispatch(kernel, interpret=True):
            return gdr.delta_step(*a)

    assert "pallas_call" in str(jax.make_jaxpr(
        functools.partial(step, kernel=True))(*a))
    (o_k, S_k), (o_j, S_j) = step(*a, kernel=True), step(*a, kernel=False)
    np.testing.assert_allclose(o_k[0], o_j[0], atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(S_k[0], S_j[0], atol=2e-5, rtol=1e-5)
    np.testing.assert_array_equal(S_k[1], a[5][1])    # the dead row's state


# ---------------------------------------------------------------------------
# the seam
# ---------------------------------------------------------------------------

def _bad_mixers(args):
    return args._replace(layer_mixers=("delta", "mamba"))


def _bad_groups(args):
    return args._replace(linear_key_heads=3)


@pytest.mark.parametrize("what,kw,change", [
    ("mesh", {"mesh": object()}, None),
    ("int8", {"kv_dtype": "int8"}, None),
    ("draft_params", {"draft_params": {}, "draft_args": object()}, None),
    ("radix", {"prefix_policy": "hash"}, None),
    ("mixer is", {}, _bad_mixers),
    ("multiple of", {}, _bad_groups),
])
def test_what_is_not_carried_is_refused_with_the_reason(params, args, what,
                                                        kw, change):
    with pytest.raises(ValueError, match=what):
        PagedEngine(params, change(args) if change else args,
                    **dict(ENGINE, **kw))


@pytest.mark.parametrize("worker", ["PrefillWorker", "DecodeWorker"])
def test_disaggregated_workers_refuse_the_model(params, args, worker):
    from paddle_tpu.serving import disagg

    with pytest.raises(ValueError, match="recurrent"):
        getattr(disagg, worker)(params, args,
                                transport=disagg.LocalTransport(), **ENGINE)


def test_the_family_goes_through_the_one_path_and_the_shared_riders(
        params, args):
    """One entry in PATHS, no path of its own: the state tree, snapshots and
    preempt / resume are `FamilyPath`'s, the routing's riders and traces the
    piece the latent-attention expert family uses too."""
    entry = paths.PATHS[ldf.LatentDeltaMoEArgs]
    assert entry.func is paths.PATHS[lm.LatentMoEArgs].func \
        is family.FamilyPath and entry.keywords == {"family": ldf}
    eng = _engine(params, args)
    assert type(eng.path.riders) is routing.RoutingRiders
    assert ldf.riders(args) == (4, 0) and eng.path.riders.select_rows == 0
    # what the path moves is a tree: every leaf of the slot's state has the
    # slot axis first, every leaf of the pools the page axis
    assert {a.shape[0] for a in jax.tree_util.tree_leaves(eng.path.state)} \
        == {ENGINE["max_slots"]}
    assert {a.shape[0] for a in jax.tree_util.tree_leaves(eng.path.snaps)} \
        == {family.SNAPSHOTS}
    assert {a.shape[0] for a in jax.tree_util.tree_leaves(eng.path.pools)} \
        == {ENGINE["num_pages"]}


def test_a_description_that_records_nothing_keeps_no_trace(params, args):
    eng = PagedEngine(params, args._replace(record_routing=False), **ENGINE)
    req = eng.serve([Request(_ids(20, 8), 4)])[0]
    assert getattr(req, "routing", None) is None
    assert eng.path.riders.log == []
    # the counts still ride the read-back
    assert eng.metrics.summary()["observations"][
        "serve.routed_here_share"]["count"] > 0
