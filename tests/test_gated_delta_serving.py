"""A stack of gated delta-rule layers (a matrix state and a short
convolution's state a layer) beside full multi-head attention layers through
`PagedEngine`, on the CPU at a tiny preset: hidden 64, 4 linear heads of key
width 8 / value width 16, a 4-tap convolution, 4 attention heads x 16, page
8, 3 linear + 1 full layers: one period of the published 3 : 1.

Everything is compared with the plain float32 reference of
`benchmarks/families/gated_delta_hybrid.py` (written from the equations, a
scan over tokens, no kernel, no cache) on seeded float32 weights: the chunked
scan and the one step, the convolution's state, the logits of prefill in
chunks and of decode through the cache, and the engine's own tokens.
Tolerances: the program and the reference are both float32 here, so what
differs is the order of sums and, in the chunked form, a 64 x 64 triangular
inverse: 5e-5 on outputs of magnitude ~1 leaves that ten times of room (the
widest seen is 4e-6) and is a hundred times under what a dropped decay, a
missed write or a stale convolution row costs (each moves a logit by more
than 5e-3 here).
"""

import functools
import importlib.util
import os
import re
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
from paddle_tpu.models import gated_delta_functional as gdf  # noqa: E402
from paddle_tpu.models import hybrid_functional as hf  # noqa: E402
from paddle_tpu.serving import PagedEngine, Request, paths  # noqa: E402
from paddle_tpu.serving import family  # noqa: E402

TOL = 5e-5

ARCH = {
    "family": "gated_delta_hybrid", "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 4, "vocab_size": 256,
    "rms_norm_eps": 1e-06, "initializer_range": 0.15,
    "layer_types": ["linear_attention"] * 3 + ["full_attention"],
    "linear_num_key_heads": 4, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4}
H, DK, DV, K, C = 4, 8, 16, 4, 4 * (2 * 8 + 16)
B, HD = 8, 16
ENGINE = dict(max_slots=3, max_len=128, page_size=8, num_pages=80,
              min_bucket=8, prefill_chunk=16)


@pytest.fixture(scope="module")
def fam():
    """The family's file, loaded by its path as the harness loads it."""
    path = os.path.join(ROOT, "benchmarks", "families",
                        "gated_delta_hybrid.py")
    spec = importlib.util.spec_from_file_location("family_gated_delta", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.T_BLOCK = 64          # the reference's token block, at test size
    mod.delta_scan = jax.jit(mod.delta_scan)    # one compile a shape
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


def _ref_logits(fam, params, ids, arch=ARCH):
    """The reference's logits at every position of `ids`."""
    kinds = fam.layer_kinds(arch)
    place = [kinds[:i].count(k) for i, k in enumerate(kinds)]
    x = fam.forward_hidden(
        arch, ids,
        lambda i: {k: v[place[i]] for k, v in params[kinds[i]].items()},
        params["embedding"])
    return np.asarray(fam.head_logits(arch, x, params["final_norm"],
                                      params["lm_head"]))


# ---------------------------------------------------------------------------
# the delta rule: the chunked scan and the one step against the recurrence
# ---------------------------------------------------------------------------

_SCAN = jax.jit(gdr.delta_chunk_scan, static_argnames=("chunk",))

# the one step's two forms: the jnp passes, and the Pallas pass interpreted.
# The kernel takes rows of whole 128-lane tiles, so its cases are 128 / p
# wide where the jnp form's are this preset's 16
FORMS = ["jnp", "kernel"]


@functools.lru_cache(maxsize=None)
def _step(form):
    def step(*a):
        with qm.fused_dispatch(form == "kernel", interpret=True):
            return gdr.delta_step(*a)

    return jax.jit(step)


def _dv(form, p):
    return DV if form == "jnp" else 128 // p


def _operands(s, seed, b_range=(0.0, 2.0), log_a_range=(-3.0, 0.0),
              repeat_keys=False, dv=DV):
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.normal(size=(s, H, DK))) / np.sqrt(DK)
    k = unit(rng.normal(size=(1 if repeat_keys else s, H, DK)))
    k = np.broadcast_to(k, (s, H, DK))
    v = rng.normal(size=(s, H, dv))
    log_a = rng.uniform(*log_a_range, size=(s, H))
    b = rng.uniform(*b_range, size=(s, H))
    return [jnp.asarray(np.ascontiguousarray(x), jnp.float32)
            for x in (q, k, v, log_a, b)]


@pytest.mark.parametrize("chunk", [4, 16, 64])
@pytest.mark.parametrize("carried", [False, True])
@pytest.mark.parametrize("regime", [
    dict(),                                          # every gate mid-range
    dict(b_range=(1.9, 2.0)),                        # b near 2: reflections
    dict(log_a_range=(-80.0, -20.0)),                # a near 0
    dict(log_a_range=(-1e-4, 0.0)),                  # a near 1: nothing fades
    dict(b_range=(1.9, 2.0), log_a_range=(-1e-3, 0.0), repeat_keys=True),
], ids=["mid", "b_near_2", "a_near_0", "a_near_1", "repeated_keys"])
def test_chunk_scan_is_the_recurrence(fam, chunk, carried, regime):
    q, k, v, log_a, b = _operands(128, chunk, **regime)
    S0 = jnp.zeros((H, DK, DV)) if not carried else jnp.asarray(
        np.random.default_rng(9).normal(size=(H, DK, DV)), jnp.float32)
    want, S_want = fam.delta_scan(q, k, v, jnp.exp(log_a), b, S0)
    got, S_got = _SCAN(q, k, v, log_a, b, S0,
                                      jnp.ones(128, bool), chunk=chunk)
    # one key 128 times at b ~ 1.95 and a ~ 1: the state's component along
    # it changes sign every token and keeps its size, so the float32
    # recurrence itself carries a rounding of ~1e-4 by the window's end
    # (1.1e-4 seen); a wrong inverse is off by the values' own size, ~0.5
    atol = 1e-3 if regime.get("repeat_keys") else TOL
    np.testing.assert_allclose(got, want, atol=atol, rtol=1e-4)
    np.testing.assert_allclose(S_got, S_want, atol=atol, rtol=1e-4)


def test_the_doubling_inverse_holds_where_the_closed_product_fails():
    """Keys that repeat with b near 2: N is ~2 below the diagonal, (I + N)^-1
    alternates +-2 and is bounded, but N^32 of the closed product (I - N)(I
    + N^2)(I + N^4).. is past float32."""
    n = jnp.tril(jnp.full((64, 64), 1.95, jnp.float32), -1)
    exact = np.linalg.inv(np.eye(64) + np.asarray(n, np.float64))
    got = np.asarray(gdr.unit_lower_inverse(n))
    np.testing.assert_allclose(got, exact, atol=1e-4)
    closed, power = jnp.eye(64) - n, n @ n
    for _ in range(5):
        closed, power = closed @ (jnp.eye(64) + power), power @ power
    assert not np.allclose(np.asarray(closed), exact, atol=1.0)


@pytest.mark.parametrize("real", [1, 13, 32])
def test_a_padded_token_neither_decays_the_state_nor_writes_to_it(fam, real):
    q, k, v, log_a, b = _operands(32, real)
    S0 = jnp.ones((H, DK, DV))
    # a padded row may hold anything finite (it is a pad token's row)
    poison = lambda x: x.at[real:].set(1e3)
    _, S_pad = _SCAN(
        poison(q), poison(k), poison(v), poison(log_a), poison(b), S0,
        jnp.arange(32) < real, chunk=8)
    _, S_want = fam.delta_scan(q[:real], k[:real], v[:real],
                               jnp.exp(log_a[:real]), b[:real], S0)
    np.testing.assert_allclose(S_pad, S_want, atol=TOL, rtol=1e-4)


@pytest.mark.parametrize("real", [1, 2, 3, 13, 32])
def test_the_convolution_keeps_its_last_real_rows(fam, real):
    """Fewer real rows than the state holds: the older rows move up."""
    rng = np.random.default_rng(real)
    u = jnp.asarray(rng.normal(size=(32, C)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(C, K)), jnp.float32)
    before = jnp.asarray(rng.normal(size=(K - 1, C)), jnp.float32)
    out, state = gdr.short_conv_window(u, w, before, jnp.int32(real - 1))
    np.testing.assert_allclose(out[:real],
                               fam.short_conv(u[:real], before, w),
                               atol=TOL, rtol=1e-5)
    np.testing.assert_array_equal(
        state, jnp.concatenate([before, u[:real]])[-(K - 1):])


@pytest.mark.parametrize("p", [1, 2])
@pytest.mark.parametrize("form", FORMS)
def test_one_step_is_the_recurrence_and_a_dead_row_keeps_both_states(
        fam, form, p):
    """On the stored layout, one head a row (p = 1) and two heads side by
    side (p = 2: what `heads_per_row` gives wherever the value width is no
    multiple of 128 lanes, at the cell's 192 and at this preset's 16), in
    both forms of the step."""
    assert gdr.heads_per_row(H, DV) == 2 and gdr.heads_per_row(30, 192) == 2
    assert gdr.heads_per_row(32, 128) == 1 and gdr.heads_per_row(3, 16) == 1
    dv = _dv(form, p)
    q, k, v, log_a, b = _operands(3, 5, dv=dv)
    rng = np.random.default_rng(2)
    S = jnp.asarray(rng.normal(size=(3, H, DK, dv)), jnp.float32)
    packed = gdr.pack_state(S, p)
    assert packed.shape == (3, H // p, DK, p * dv)
    np.testing.assert_array_equal(gdr.unpack_state(packed, p), S)
    live = jnp.asarray([True, False, True])
    out, S_new = _step(form)(q, k, v, log_a, b, packed, live)
    S_new = gdr.unpack_state(S_new, p)
    for r in range(3):
        want, S_want = fam.delta_scan(q[r:r + 1], k[r:r + 1], v[r:r + 1],
                                      jnp.exp(log_a[r:r + 1]), b[r:r + 1],
                                      S[r])
        if live[r]:
            np.testing.assert_allclose(out[r], want[0], atol=TOL, rtol=1e-4)
        np.testing.assert_allclose(S_new[r], S_want if live[r] else S[r],
                                   atol=TOL, rtol=1e-4)
    u = jnp.asarray(rng.normal(size=(3, C)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(C, K)), jnp.float32)
    conv = jnp.asarray(rng.normal(size=(3, K - 1, C)), jnp.float32)
    out, conv_new = gdr.short_conv_step(u, w, conv, live)
    for r in range(3):
        np.testing.assert_allclose(
            out[r], fam.short_conv(u[r:r + 1], conv[r], w)[0], atol=TOL,
            rtol=1e-5)
        np.testing.assert_array_equal(
            conv_new[r], jnp.concatenate([conv[r, 1:], u[r:r + 1]])
            if live[r] else conv[r])


@pytest.mark.parametrize("p", [1, 2])
@pytest.mark.parametrize("form", FORMS)
def test_a_window_then_steps_carry_one_state(fam, form, p):
    dv = _dv(form, p)
    q, k, v, log_a, b = _operands(40, 3, dv=dv)
    want, _ = fam.delta_scan(q, k, v, jnp.exp(log_a), b,
                             jnp.zeros((H, DK, dv)))
    out, S = _SCAN(q[:32], k[:32], v[:32], log_a[:32], b[:32],
                   jnp.zeros((H, DK, dv)), jnp.ones(32, bool), chunk=16)
    np.testing.assert_allclose(out, want[:32], atol=TOL, rtol=1e-4)
    S = gdr.pack_state(S, p)[None]
    for t in range(32, 40):
        o, S = _step(form)(q[t][None], k[t][None], v[t][None],
                           log_a[t][None], b[t][None], S, jnp.ones(1, bool))
        np.testing.assert_allclose(o[0], want[t], atol=TOL, rtol=1e-4)


@pytest.mark.parametrize("p", [1, 2])
@pytest.mark.parametrize("form", FORMS)
def test_a_dead_rows_state_is_unchanged_bit_for_bit(form, p):
    """Whatever the row holds: a signed zero, a subnormal, an infinity, a
    NaN with a payload (a recycled slot's leftovers are never read, but
    they are never rewritten either)."""
    dv = _dv(form, p)
    q, k, v, log_a, b = _operands(4, 7, dv=dv)
    bits = np.random.default_rng(p).integers(
        0, 2 ** 32, size=(4, H // p, DK, p * dv), dtype=np.uint32)
    bits[1, 0, 0, :4] = [0x80000000, 0x00000001, 0x7F800000, 0x7FC00123]
    live = np.asarray([True, False, False, True])
    # the live rows hold numbers the step can work on
    state = bits.view(np.float32).copy()
    state[live] = np.random.default_rng(3).normal(size=state[live].shape)
    _, new = _step(form)(q, k, v, log_a, b, jnp.asarray(state),
                         jnp.asarray(live))
    new = np.asarray(new)
    np.testing.assert_array_equal(new[~live].view(np.uint32),
                                  state[~live].view(np.uint32))
    assert np.isfinite(new[live]).all()
    assert not np.array_equal(new[live], state[live])


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("regime", [
    dict(log_a_range=(-80.0, -20.0)),                # a near 0
    dict(b_range=(1.9, 2.0), log_a_range=(-1e-3, 0.0), repeat_keys=True),
], ids=["a_near_0", "repeated_keys"])
def test_steps_are_the_recurrence_at_the_gates_edges(fam, form, regime):
    """Token by token from a carried state: a head that loses all but e^-80
    of it in a token, and one key 48 times at b ~ 1.95 and a ~ 1 (the
    state's component along it changes sign every token and keeps its
    size). Held to what the chunked scan is held to."""
    p = 2
    dv = _dv(form, p)
    q, k, v, log_a, b = _operands(48, 11, dv=dv, **regime)
    S0 = jnp.asarray(np.random.default_rng(9).normal(size=(H, DK, dv)),
                     jnp.float32)
    want, S_want = fam.delta_scan(q, k, v, jnp.exp(log_a), b, S0)
    atol = 1e-3 if regime.get("repeat_keys") else TOL
    S = gdr.pack_state(S0, p)[None]
    for t in range(48):
        o, S = _step(form)(q[t][None], k[t][None], v[t][None],
                           log_a[t][None], b[t][None], S, jnp.ones(1, bool))
        np.testing.assert_allclose(o[0], want[t], atol=atol, rtol=1e-4)
    np.testing.assert_allclose(gdr.unpack_state(S[0], p), S_want, atol=atol,
                               rtol=1e-4)


@pytest.mark.parametrize("dk,dv,heads,why", [
    (8, 16, 4, "rows of 32 lanes"), (12, 64, 4, "12 key rows: no whole tile"),
    (8, 64, 66, "132 k and q columns: more than a tile's lanes"),
], ids=["lanes", "sublanes", "heads"])
def test_a_shape_that_does_not_fit_takes_the_jnp_form(dk, dv, heads, why):
    """The form is read from the shapes: asked for the kernel, a state
    whose rows fill no whole tiles goes through the jnp passes, and gives
    their numbers."""
    rng = np.random.default_rng(dk)
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    a = (f32(2, heads, dk), f32(2, heads, dk), f32(2, heads, dv),
         -jnp.abs(f32(2, heads)), jnp.abs(f32(2, heads)),
         f32(2, heads // 2, dk, 2 * dv), jnp.asarray([True, False]))
    fitting = (2, 2, 8, 128)
    with qm.fused_dispatch(True, interpret=True):
        assert gdr.step_is_pallas(fitting, 4)
        assert not gdr.step_is_pallas(a[5].shape, heads), why
        assert "pallas_call" not in str(jax.make_jaxpr(gdr.delta_step)(*a))
        got = gdr.delta_step(*a)
    with qm.fused_dispatch(False):
        assert not gdr.step_is_pallas(fitting, 4)
        want = gdr.delta_step(*a)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


# ---------------------------------------------------------------------------
# the two step programs against the reference's full forward: logits
# ---------------------------------------------------------------------------

class Stepper:
    """`gdf.prefill_window` / `gdf.decode_step` over fresh pools: slot 1 of
    2, pages 1 .. 16 (the other slot's states start as garbage)."""

    P, NP, SLOTS = 16, 40, 2

    def __init__(self, params, args):
        self.params, self.args = params, args
        self.pools = gdf.pools(args, self.NP, B, jnp.float32)
        self.state = jax.tree_util.tree_map(
            lambda a: jnp.full(a.shape, 7.0, a.dtype),
            gdf.slot_state(args, self.SLOTS, jnp.float32))
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
        logits, self.pools, own, _ = _PREFILL(
            self.params, self.layer_ids, jnp.asarray(padded), jnp.int32(h),
            jnp.int32(e - 1 - h), jnp.asarray(self.bt_row), jnp.asarray(new),
            self.pools, own, (), args=self.args)
        self.state = jax.tree_util.tree_map(lambda a, o: a.at[1].set(o),
                                            self.state, own)
        return np.asarray(logits)

    def step(self, token, t):
        bt = np.zeros((self.SLOTS, self.P), np.int32)
        bt[1] = self.bt_row
        logits, self.pools, self.state, _ = _DECODE(
            self.params, self.layer_ids, jnp.asarray([0, token], jnp.int32),
            jnp.asarray(bt), jnp.asarray([0, t], jnp.int32),
            jnp.asarray([False, True]), self.pools, self.state, (),
            args=self.args)
        return np.asarray(logits)[1]


_PREFILL = jax.jit(gdf.prefill_window, static_argnames=("args",))
_DECODE = jax.jit(gdf.decode_step, static_argnames=("args",))


@pytest.mark.parametrize("chunk", [8, 16, 32, 64])
def test_prefill_in_chunks_gives_the_references_logits(fam, params, args,
                                                       chunk):
    """Every window's last logits, the windows carrying pages, the matrix
    state and the convolution's rows; the last window is padded to its
    bucket."""
    ids, n = _ids(71, chunk), 71
    ref = _ref_logits(fam, params, ids)
    run, h = Stepper(params, args), 0
    while h < n:
        e = min(h + chunk, n)
        np.testing.assert_allclose(run.window(ids, h, e, chunk), ref[e - 1],
                                   atol=TOL, rtol=1e-4)
        h = e


@pytest.mark.parametrize("n_pre", [2, 20, 63])
def test_decode_through_the_cache_gives_the_references_logits(
        fam, params, args, n_pre):
    """From a prompt shorter than the convolution's reach (2), one that ends
    inside a page (20) and one that ends on a page's last row (63); the
    other row of the batch is dead and keeps its garbage."""
    n = n_pre + 24
    ids = _ids(n, n_pre)
    ref = _ref_logits(fam, params, ids)
    run = Stepper(params, args)
    for h in range(0, n_pre, 32):
        run.window(ids, h, min(h + 32, n_pre), 32)
    for t in range(n_pre, n):
        np.testing.assert_allclose(run.step(int(ids[t]), t), ref[t],
                                   atol=TOL, rtol=1e-4)
    for leaf in jax.tree_util.tree_leaves(run.state):
        assert np.all(np.asarray(leaf[0]) == 7.0)


def test_a_window_that_starts_inside_a_page_keeps_the_page(fam, params, args):
    """A prefix hit ends where its snapshot was taken, here at 12 tokens:
    the next window starts inside page 2."""
    ids = _ids(50, 8)
    ref = _ref_logits(fam, params, ids)
    run = Stepper(params, args)
    run.window(ids, 0, 12, 16)
    got = run.window(ids, 12, 44, 32)
    np.testing.assert_allclose(got, ref[43], atol=TOL, rtol=1e-4)
    np.testing.assert_allclose(run.step(int(ids[44]), 44), ref[44], atol=TOL,
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# the engine: pages and a tree of per-slot state in one manager
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


def _gap(fam, params, req, arch=ARCH):
    """How far each served token's reference logit lies below the best."""
    seq = np.concatenate([req.prompt_ids, np.asarray(req.token_ids)[:-1]])
    lg = _ref_logits(fam, params, seq, arch)[len(req.prompt_ids) - 1:]
    toks = np.asarray(req.token_ids)
    return lg.max(-1) - lg[np.arange(len(toks)), toks]


@pytest.mark.parametrize("chunk", [None, 16])
def test_engine_serves_the_references_tokens(fam, params, args, chunk):
    eng = _engine(params, args, prefill_chunk=chunk)
    reqs = eng.serve([Request(_ids(n, n), 6) for n in (2, 9, 45, 100)])
    for r in reqs:
        assert len(r.token_ids) == 6
        assert _gap(fam, params, r).max() < TOL
    assert eng.path.state[0]["S"].shape == (3, H // 2, DK, 2 * DV)
    obs = eng.metrics.summary()
    assert obs["gauges"]["recurrent_state_bytes"]["value"] == \
        3 * 3 * (H * DK * DV + (K - 1) * C) * 4
    assert obs["gauges"]["kv_pool_bytes"]["value"] == \
        2 * 80 * H * B * HD * 4
    assert obs["counters"]["state_snapshots"] == 4
    assert 0 < obs["observations"]["decode_live_page_share"]["mean"] < 1


# value heads of 64: two side by side fill a 128-lane tile, the kernel's shape
WIDE = dict(ARCH, linear_value_head_dim=64)


@pytest.mark.parametrize("form,arch,reads", [
    ("kernel", WIDE, 1), ("jnp", WIDE, 0), ("kernel", ARCH, 0),
], ids=["kernel", "jnp", "kernel_asked_of_rows_of_32_lanes"])
def test_the_decode_program_names_its_step_and_the_gauge_its_form(
        fam, form, arch, reads):
    """Through the engine: the gauge `serve.delta_step_pallas` is set with
    the path's state and outlives `reset()`; the decode program's text
    carries the kernel, named, under the scope the benchmark's two readers
    sum (`pt.delta_rule`); the served tokens are the reference's."""
    from benchmarks.harness import weights

    params = weights.make_params(fam, arch, 11, jnp.float32)
    with qm.fused_dispatch(form == "kernel", interpret=True):
        eng = PagedEngine(params, fam.serve_args(arch), **ENGINE)
        reqs = eng.serve([Request(_ids(n, n), 5) for n in (3, 21)])
        eng.reset()
        path, slots = eng.path, ENGINE["max_slots"]
        text = path._decode[False].lower(
            eng.params, path.layer_ids, path.tokens,
            jnp.zeros((slots, eng.pages_per_slot), jnp.int32),
            jnp.zeros(slots, jnp.int32), jnp.zeros(slots, bool), path.pools,
            path.state, path.tables, *eng._sampling_args()
        ).as_text(debug_info=True)
    gauges = eng.metrics.summary()["gauges"]
    assert gauges["serve.delta_step_pallas"]["value"] == reads
    named = re.findall(r"pt\.attention/pt\.delta_rule/delta_rule_step/"
                       r"pallas_call", text)
    assert bool(named) == bool(reads)
    for r in reqs:
        assert _gap(fam, params, r, arch).max() < TOL


def test_a_recycled_slot_starts_from_zero_in_both_states(fam, params, args):
    eng = _engine(params, args, max_slots=1)
    first, second = eng.serve([Request(_ids(50, 1), 5),
                               Request(_ids(44, 2), 5)])
    cold = _engine(params, args, max_slots=1).serve(   # the same, emptied
        [Request(_ids(44, 2), 5)])[0]
    assert second.token_ids == cold.token_ids
    assert _gap(fam, params, second).max() < TOL


@pytest.mark.parametrize("n", [12, 16])
def test_a_snapshot_hit_gives_the_tokens_of_a_cold_run(fam, params, args, n):
    """The first prompt's states, both, are saved at its end (inside a page
    for 12 tokens, at a page's edge for 16) and join the radix tree when the
    request retires; a longer prompt with that prefix starts from them."""
    base, tail = _ids(n, 3), _ids(20, 4)
    longer = np.concatenate([base, tail])
    eng = _engine(params, args)
    eng.serve([Request(base, 4)])
    hit = eng.serve([Request(longer, 6)])[0]
    c = eng.metrics.summary()["counters"]
    assert c["prefix_tokens_hit"] == n
    cold = _engine(params, args).serve([Request(longer, 6)])[0]
    assert hit.token_ids == cold.token_ids
    assert _gap(fam, params, hit).max() < TOL


def test_a_snapshot_restores_the_convolutions_rows_too(params, args):
    """The same hit with the snapshot's convolution rows zeroed serves other
    tokens' logits: the rows are part of what a hit restores."""
    base = _ids(12, 3)
    eng = _engine(params, args)
    eng.serve([Request(base, 4)])
    saved = [np.asarray(s["conv"]) for s in eng.path.snaps]
    assert any(np.abs(x).max() > 0 for x in saved)
    assert all(np.abs(np.asarray(s["S"])).max() > 0 for s in eng.path.snaps)


def test_preempt_and_resume_carry_both_states(fam, params, args):
    eng = _engine(params, args)
    req = eng.submit(Request(_ids(30, 5), 10))
    while len(req.token_ids) < 4:
        eng.step()
    slot = next(iter(eng.slots.active_slots))
    saved = eng.preempt(slot)
    # the slot serves another request in between
    eng.serve([Request(_ids(25, 6), 5)])
    eng.resume(saved)
    while not req.finished:
        eng.step()
    cold = _engine(params, args).serve([Request(_ids(30, 5), 10)])[0]
    assert req.token_ids == cold.token_ids
    assert _gap(fam, params, req).max() < TOL


def _bad_kinds(args):
    return args._replace(layer_kinds=("linear_attention", "mamba"))


def _bad_conv(args):
    return args._replace(conv_kernel=1)


@pytest.mark.parametrize("what,kw,change", [
    ("mesh", {"mesh": object()}, None),
    ("int8", {"kv_dtype": "int8"}, None),
    ("draft_params", {"draft_params": {}, "draft_args": object()}, None),
    ("radix", {"prefix_policy": "hash"}, None),
    ("a layer is", {}, _bad_kinds),
    ("conv_kernel", {}, _bad_conv),
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


def test_both_hybrid_families_go_through_the_one_path(params, args):
    for described, module in ((gdf.GatedDeltaArgs, gdf), (hf.HybridArgs, hf)):
        entry = paths.PATHS[described]
        assert entry.func is family.FamilyPath
        assert entry.keywords == {"family": module}
    eng = _engine(params, args)
    assert type(eng.path) is family.FamilyPath and eng.path.family is gdf
    # what the path moves is a tree: every leaf of the slot's state has the
    # slot axis first, every leaf of the pools the page axis
    assert {a.shape[0] for a in jax.tree_util.tree_leaves(eng.path.state)} \
        == {ENGINE["max_slots"]}
    assert {a.shape[0] for a in jax.tree_util.tree_leaves(eng.path.snaps)} \
        == {family.SNAPSHOTS}
    assert {a.shape[0] for a in jax.tree_util.tree_leaves(eng.path.pools)} \
        == {ENGINE["num_pages"]}
