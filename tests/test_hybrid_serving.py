"""A hybrid stack (lightning linear-attention layers beside block-sparse
attention layers) through `PagedEngine`, on the CPU at a tiny preset: page =
block 8, kernel 4 / stride 2, top-k 4 = 1 init + 2 window + 1 picked,
dense_len 32, 2 sparse + 6 lightning layers of hidden 64.

Everything is compared with the plain float32 reference of
`benchmarks/families/minicpm_sala.py` (written from the equations, no kernel,
no cache) on seeded float32 weights: the chunked scan and the one-step
recurrence, the selection, the logits of prefill in chunks and of decode
through the compacted table, and the engine's own tokens. Tolerances: the
program and the reference are both float32 here, so what differs is the
order of sums; 2e-5 on logits of magnitude ~1 leaves that a hundred times of
room and is a thousand times under what a wrong block in a selection costs.
"""

import importlib.util
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from paddle_tpu.kernels import lightning_attention as la  # noqa: E402
from paddle_tpu.kernels import quantized_matmul as qm  # noqa: E402
from paddle_tpu.kernels import sparse_attention as sa  # noqa: E402
from paddle_tpu.models import hybrid_functional as hf  # noqa: E402
from paddle_tpu.models import llama_functional as lf  # noqa: E402
from paddle_tpu.serving import PagedEngine, Request  # noqa: E402
from paddle_tpu.serving.block_manager import BlockAllocator  # noqa: E402

TOL = 2e-5

ARCH = {
    "family": "minicpm_sala", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
    "rms_norm_eps": 1e-06, "rope_theta": 10000, "scale_emb": 12,
    "scale_depth": 1.4, "dim_model_base": 16, "initializer_range": 0.15,
    "mixer_types": ["minicpm4"] + ["lightning-attn"] * 3
    + ["minicpm4"] + ["lightning-attn"] * 3,
    "sparse_config": {"block_size": 8, "kernel_size": 4, "kernel_stride": 2,
                      "topk": 4, "init_blocks": 1, "window_size": 16,
                      "dense_len": 32}}
CFG = sa.SparseConfig(8, 4, 2, 4, 1, 2, 32)
B, H, NKV, D, L = 8, 4, 2, 16, 8
ENGINE = dict(max_slots=3, max_len=128, page_size=8, num_pages=80,
              min_bucket=8, prefill_chunk=16)


@pytest.fixture(scope="module")
def fam():
    """The family's file, loaded by its path as the harness loads it."""
    path = os.path.join(ROOT, "benchmarks", "families", "minicpm_sala.py")
    spec = importlib.util.spec_from_file_location("family_minicpm_sala", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
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


def _ref_logits(fam, params, ids):
    """The reference's logits at every position of `ids`."""
    x = fam.forward_hidden(
        ARCH, ids, lambda i: {k: v[i] for k, v in params["layers"].items()},
        params["embedding"])
    return np.asarray(fam.head_logits(ARCH, x, params["final_norm"],
                                      params["lm_head"]))


# ---------------------------------------------------------------------------
# lightning layers: the chunked scan and the one step against the recurrence
# ---------------------------------------------------------------------------

def _qkv(s, seed):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.normal(size=(s, H, D)).astype(np.float32))
            for _ in range(3)]


@pytest.mark.parametrize("block", [4, 8, 16, 64])
@pytest.mark.parametrize("carried", [False, True])
def test_chunk_scan_is_the_recurrence(fam, block, carried):
    q, k, v = _qkv(64, block)
    S0 = jnp.zeros((H, D, D)) if not carried else jnp.asarray(
        np.random.default_rng(9).normal(size=(H, D, D)).astype(np.float32))
    valid = jnp.ones(64, bool)
    want, S_want = fam.lightning_scan(q, k, v, S0, valid, ARCH)
    got, S_got = la.lightning_chunk_scan(q, k, v, S0,
                                         la.lightning_slopes(H), valid,
                                         block=block)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=1e-5)
    np.testing.assert_allclose(S_got, S_want, atol=TOL, rtol=1e-5)


@pytest.mark.parametrize("real", [1, 13, 32])
def test_a_padded_token_neither_decays_the_state_nor_adds_to_it(fam, real):
    q, k, v = _qkv(32, real)
    S0 = jnp.ones((H, D, D))
    valid = jnp.arange(32) < real
    _, S_pad = la.lightning_chunk_scan(q, k, v, S0, la.lightning_slopes(H),
                                       valid, block=8)
    _, S_want = fam.lightning_scan(q[:real], k[:real], v[:real], S0,
                                   jnp.ones(real, bool), ARCH)
    np.testing.assert_allclose(S_pad, S_want, atol=TOL, rtol=1e-5)


def test_one_step_is_the_recurrence_and_a_dead_row_keeps_its_state(fam):
    q, k, v = _qkv(3, 5)
    S = jnp.asarray(np.random.default_rng(2).normal(
        size=(3, H, D, D)).astype(np.float32))
    live = jnp.asarray([True, False, True])
    out, S_new = la.lightning_step(q, k, v, S, la.lightning_slopes(H), live)
    for r in range(3):
        want, S_want = fam.lightning_scan(q[r:r + 1], k[r:r + 1], v[r:r + 1],
                                          S[r], jnp.ones(1, bool), ARCH)
        np.testing.assert_allclose(out[r], want[0], atol=TOL, rtol=1e-5)
        np.testing.assert_allclose(S_new[r], S_want if live[r] else S[r],
                                   atol=TOL, rtol=1e-5)


def test_a_window_then_steps_carry_one_state(fam):
    q, k, v = _qkv(40, 3)
    slopes = la.lightning_slopes(H)
    want, _ = fam.lightning_scan(q, k, v, jnp.zeros((H, D, D)),
                                 jnp.ones(40, bool), ARCH)
    out, S = la.lightning_chunk_scan(q[:32], k[:32], v[:32],
                                     jnp.zeros((H, D, D)), slopes,
                                     jnp.ones(32, bool), block=16)
    np.testing.assert_allclose(out, want[:32], atol=TOL, rtol=1e-5)
    for t in range(32, 40):
        o, S1 = la.lightning_step(q[t][None], k[t][None], v[t][None],
                                  S[None], slopes, jnp.ones(1, bool))
        S = S1[0]
        np.testing.assert_allclose(o[0], want[t], atol=TOL, rtol=1e-5)


# ---------------------------------------------------------------------------
# sparse layers: compressed keys, selection, attention over pages
# ---------------------------------------------------------------------------

class Paged:
    """One sequence's keys and values laid into pages 1 .. P of fresh pools,
    with the compressed keys a prefill of the whole sequence leaves."""

    def __init__(self, m, seed, scale=1.0):
        rng = np.random.default_rng(seed)
        self.m, self.P = m, m // B
        self.q = jnp.asarray(rng.normal(size=(m, H, D)).astype(np.float32))
        self.k = jnp.asarray(
            scale * rng.normal(size=(m, NKV, D)).astype(np.float32))
        self.v = jnp.asarray(rng.normal(size=(m, NKV, D)).astype(np.float32))
        self.qg = self.q.reshape(m, NKV, H // NKV, D)
        self.bt = jnp.arange(1, self.P + 1, dtype=jnp.int32)
        NP = self.P + 4

        def pages(x):
            return jnp.zeros((NP, NKV, B, D)).at[self.bt].set(
                jnp.swapaxes(x.reshape(self.P, B, NKV, D), 1, 2))

        self.pk, self.pv = pages(self.k), pages(self.v)
        vals, ends, ok = sa.compressed_keys_of_window(
            self.k, jnp.zeros((CFG.kernel_size, NKV, D)), jnp.int32(0),
            jnp.int32(m - 1), CFG)
        self.kc = sa.write_compressed(
            jnp.zeros((NP, NKV, CFG.per, D)), vals, ends, ok, jnp.int32(0),
            self.bt, CFG)


@pytest.fixture(scope="module")
def paged():
    return Paged(96, 1)


# the per-query switch sits between t = 31 (n = dense_len) and t = 32
@pytest.mark.parametrize("t", [7, 31, 32, 33, 47, 64, 95])
def test_selection_equals_the_references(fam, paged, t):
    want = np.asarray(fam.selected_blocks(
        paged.qg[t:t + 1], jnp.asarray([t]), paged.k, ARCH))[:, 0]
    kflat = jnp.swapaxes(paged.kc[paged.bt], 1, 2).reshape(1, -1, NKV, D)
    pos = jnp.asarray([[t]])
    got = np.asarray(sa.select_blocks(sa.block_scores(
        paged.qg[t][None, None], kflat, pos, CFG), pos, CFG))[0, :, 0]
    np.testing.assert_array_equal(got, want)
    held = t // B + 1
    assert got[:, :held].sum(-1).tolist() == [
        held if t + 1 <= CFG.dense_len else CFG.topk] * NKV
    assert not got[:, held:].any()


@pytest.mark.parametrize("t", [40, 71, 95])
def test_forced_blocks_are_always_selected(paged, t):
    pos = jnp.asarray([[t]])
    # scores that favour the oldest blocks cannot push the forced ones out
    scores = jnp.broadcast_to(-jnp.arange(paged.P, dtype=jnp.float32),
                              (1, NKV, 1, paged.P))
    sel = np.asarray(sa.select_blocks(scores, pos, CFG))[0, :, 0]
    cur = t // B
    assert sel[:, 0].all() and sel[:, cur].all() and sel[:, cur - 1].all()
    assert sel[:, 1].all() and sel.sum(-1).tolist() == [CFG.topk] * NKV


def test_neighbours_that_tie_are_picked_lower_index_first():
    """Two neighbours share the score of the kernel that straddles them
    whenever that kernel is the best of both: exactly one is picked."""
    scores = jnp.asarray([0.1, 0.3, 0.7, 0.7, 0.2, 0.1, 0.1, 0.1])
    sel = np.asarray(sa.select_blocks(scores[None, None, None],
                                      jnp.asarray([[63]]), CFG))[0, 0, 0]
    assert sel.tolist() == [True, False, True, False, False, False, True,
                            True]


@pytest.mark.parametrize("how", ["window", "window_from_mid_page", "steps"])
def test_compressed_keys_live_in_the_page_where_they_end(fam, how):
    """Kernel j = mean of the keys of [2j, 2j + 4); the one that starts at
    offset 6 of a page is finished by the next page's first two tokens and
    is kept there, entry 0."""
    p = Paged(48, 4)
    want = np.zeros((p.P, CFG.per, NKV, D), np.float32)
    for j in range((48 - 4) // 2 + 1):
        end = 2 * j + 3
        want[end // B, (end % B + 1) // 2 - 1] = np.asarray(
            p.k[2 * j:2 * j + 4]).mean(0)
    NP = p.P + 4
    kc = jnp.zeros((NP, NKV, CFG.per, D))
    if how == "window":
        kc = p.kc
    elif how == "window_from_mid_page":
        # two windows, the second starting inside page 2 (h = 19)
        for h, e in ((0, 19), (19, 48)):
            before = np.maximum(h - 4 + np.arange(4), 0)
            vals, ends, ok = sa.compressed_keys_of_window(
                jnp.pad(p.k[h:e], ((0, 32 - (e - h)), (0, 0), (0, 0))),
                p.k[before], jnp.int32(h), jnp.int32(e - 1 - h), CFG)
            kc = sa.write_compressed(kc, vals, ends, ok, jnp.int32(h),
                                     p.bt[h // B:], CFG)
    else:
        bt = p.bt[None]
        for t in range(48):
            vals, ok = sa.compressed_key_of_step(p.pk, bt, jnp.asarray([t]),
                                                 CFG)
            if bool(ok[0]):
                page = int(p.bt[t // B])
                kc = kc.at[page, :, int(sa.entry_of(t, CFG))].set(vals[0])
    got = np.swapaxes(np.asarray(kc[p.bt]), 1, 2)
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_prefill_attention_over_pages_equals_the_references(fam, paged):
    pos = jnp.arange(paged.m)
    want = fam.sparse_attention(paged.q, pos, paged.k, paged.v, ARCH)
    sel = sa.prefill_selection(paged.qg, paged.kc, paged.bt, pos, CFG,
                               q_tile=32)
    got = sa.sparse_prefill_attention(paged.qg, paged.pk, paged.pv, paged.bt,
                                      sel, pos, jnp.int32(paged.m - 1), CFG)
    np.testing.assert_allclose(got.reshape(paged.m, H, D), want, atol=TOL,
                               rtol=1e-5)


def test_one_decode_step_with_dense_and_sparse_rows_side_by_side(fam, paged):
    """Rows at n = 20 (dense), n = 32 (the last dense context), n = 33 and
    n = 96 (selected): one compacted table, one call."""
    ts = [19, 31, 32, 95]
    q = jnp.stack([paged.qg[t] for t in ts])
    bt = jnp.broadcast_to(paged.bt, (len(ts), paged.P))
    pos = jnp.asarray(ts)
    table, pos_eff, read = sa.selected_table(q, paged.kc, bt, pos, CFG)
    assert table.shape == (len(ts) * NKV, CFG.table_width)
    assert read.tolist() == [3, 4, 4, 4]
    got = sa.sparse_decode_attention(q, paged.pk, paged.pv, table, pos_eff)
    want = fam.sparse_attention(paged.q, jnp.arange(paged.m), paged.k,
                                paged.v, ARCH)
    for i, t in enumerate(ts):
        np.testing.assert_allclose(got[i].reshape(H, D), want[t], atol=TOL,
                                   rtol=1e-5)
        # ascending, the row's own page last
        own = int(paged.bt[t // B]) * NKV
        assert int(table[i * NKV, int(read[i]) - 1]) == own


def test_the_compacted_table_goes_through_the_paged_kernel():
    """The Pallas paged decode kernel (interpreted) over the pool viewed as
    one-head pages, against the gather fallback: head size 128 and pages of
    16, shapes the kernel takes."""
    rng = np.random.default_rng(3)
    b, nkv, g, d, ps, NP, W = 3, 2, 4, 128, 16, 12, 4
    q = jnp.asarray(rng.normal(size=(b, nkv, g, d)).astype(np.float32))
    pk = jnp.asarray(rng.normal(size=(NP, nkv, ps, d)).astype(np.float32))
    pv = jnp.asarray(rng.normal(size=(NP, nkv, ps, d)).astype(np.float32))
    table = jnp.asarray(rng.integers(1, NP * nkv, (b * nkv, W)), jnp.int32)
    pos_eff = jnp.asarray([5, 20, 63, 31, 47, 16], jnp.int32)
    want = sa.sparse_decode_attention(q, pk, pv, table, pos_eff)
    assert qm.paged_decode_supported((b * nkv, 1, g, d), (NP * nkv, 1, ps, d),
                                     table.shape, 4)
    with qm.fused_dispatch(True, interpret=True):
        got = sa.sparse_decode_attention(q, pk, pv, table, pos_eff)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=1e-5)


# ---------------------------------------------------------------------------
# the two step programs against the reference's full forward: logits
# ---------------------------------------------------------------------------

class Stepper:
    """`hf.prefill_window` / `hf.decode_step` over fresh pools: slot 1 of 2,
    pages 1 .. 16 (the other slot's state starts as garbage)."""

    P, NP, SLOTS = 16, 40, 2

    def __init__(self, params, args):
        self.params, self.args = params, args
        self.pk = tuple(jnp.zeros((self.NP, NKV, B, D)) for _ in range(2))
        self.pv = tuple(jnp.zeros((self.NP, NKV, B, D)) for _ in range(2))
        self.kc = tuple(jnp.zeros((self.NP, NKV, CFG.per, D))
                        for _ in range(2))
        self.state = tuple(jnp.full((self.SLOTS, H, D, D), 7.0)
                           for _ in range(6))
        self.cos, self.sin = lf.rope_tables(256, D, 10000.0)
        self.bt_row = np.arange(1, self.P + 1).astype(np.int32)
        self.layer_ids = jnp.arange(L, dtype=jnp.int32)

    def window(self, ids, h, e, sb):
        padded = np.zeros(sb, np.int32)
        padded[:e - h] = ids[h:e]
        new = np.zeros(self.P, np.int32)
        touched = self.bt_row[h // B: -(-e // B)]
        new[:len(touched)] = touched
        # what `serving/family._prefill_traced` does around the family's
        # window: the slot's own state, zero where the window starts at 0
        own = tuple(jnp.where(h == 0, 0.0, s[1]) for s in self.state)
        logits, (self.pk, self.pv, self.kc), own, _ = _PREFILL(
            self.params, self.layer_ids, jnp.asarray(padded), jnp.int32(h),
            jnp.int32(e - 1 - h), jnp.asarray(self.bt_row), jnp.asarray(new),
            (self.pk, self.pv, self.kc), own, (self.cos, self.sin),
            args=self.args)
        self.state = tuple(s.at[1].set(o) for s, o in zip(self.state, own))
        return np.asarray(logits)

    def step(self, token, t):
        bt = np.zeros((self.SLOTS, self.P), np.int32)
        bt[1] = self.bt_row
        logits, (self.pk, self.pv, self.kc), self.state, _ = _DECODE(
            self.params, self.layer_ids, jnp.asarray([0, token], jnp.int32),
            jnp.asarray(bt), jnp.asarray([0, t], jnp.int32),
            jnp.asarray([False, True]), (self.pk, self.pv, self.kc),
            self.state, (self.cos, self.sin), args=self.args)
        return np.asarray(logits)[1]


_PREFILL = jax.jit(hf.prefill_window, static_argnames=("args",))
_DECODE = jax.jit(hf.decode_step, static_argnames=("args",))


@pytest.mark.parametrize("chunk", [8, 16, 32, 64])
def test_prefill_in_chunks_gives_the_references_logits(fam, params, args,
                                                       chunk):
    """Every window's last logits, the windows carrying pages, compressed
    keys and the recurrent state; the last window is padded to its bucket."""
    ids, n = _ids(71, chunk), 71
    ref = _ref_logits(fam, params, ids)
    run, h = Stepper(params, args), 0
    while h < n:
        e = min(h + chunk, n)
        np.testing.assert_allclose(run.window(ids, h, e, chunk), ref[e - 1],
                                   atol=TOL, rtol=1e-5)
        h = e


@pytest.mark.parametrize("n_pre", [20, 40, 63])
def test_decode_through_the_cache_gives_the_references_logits(
        fam, params, args, n_pre):
    """From a context that is still dense (20: the switch is crossed while
    decoding), a selected one (40), and a prompt that ends inside a page and
    a kernel (63); page boundaries and straddling kernels on the way."""
    n = n_pre + 24
    ids = _ids(n, n_pre)
    ref = _ref_logits(fam, params, ids)
    run = Stepper(params, args)
    for h in range(0, n_pre, 32):
        run.window(ids, h, min(h + 32, n_pre), 32)
    for t in range(n_pre, n):
        np.testing.assert_allclose(run.step(int(ids[t]), t), ref[t],
                                   atol=TOL, rtol=1e-5)


def test_a_window_that_starts_inside_a_page_keeps_the_page(fam, params, args):
    """A prefix hit ends where its snapshot was taken, here at 12 tokens:
    the next window starts inside page 2."""
    ids = _ids(50, 8)
    ref = _ref_logits(fam, params, ids)
    run = Stepper(params, args)
    run.window(ids, 0, 12, 16)
    got = run.window(ids, 12, 44, 32)
    np.testing.assert_allclose(got, ref[43], atol=TOL, rtol=1e-5)
    np.testing.assert_allclose(run.step(int(ids[44]), 44), ref[44], atol=TOL,
                               rtol=1e-5)


# ---------------------------------------------------------------------------
# the engine: two kinds of per-request state in one manager
# ---------------------------------------------------------------------------

def _engine(params, args, **kw):
    return PagedEngine(params, args, **dict(ENGINE, **kw))


def _gap(fam, params, req):
    """How far each served token's reference logit lies below the best."""
    seq = np.concatenate([req.prompt_ids, np.asarray(req.token_ids)[:-1]])
    lg = _ref_logits(fam, params, seq)[len(req.prompt_ids) - 1:]
    toks = np.asarray(req.token_ids)
    return lg.max(-1) - lg[np.arange(len(toks)), toks]


@pytest.mark.parametrize("chunk", [None, 16, 32])
def test_engine_serves_the_references_tokens(fam, params, args, chunk):
    eng = _engine(params, args, prefill_chunk=chunk)
    reqs = eng.serve([Request(_ids(n, n), 6) for n in (9, 45, 70, 100)])
    for r in reqs:
        assert len(r.token_ids) == 6
        assert _gap(fam, params, r).max() < TOL
    obs = eng.metrics.summary()
    assert 0 < obs["observations"]["sparse_read_share"]["mean"] < 1
    assert obs["gauges"]["recurrent_state_bytes"]["value"] == \
        3 * 6 * H * D * D * 4
    assert obs["gauges"]["kv_pool_bytes"]["value"] > 0


# a head of 128 lanes: what the Pallas prefill kernel takes (in the interpreter
# here), at the tiny preset's every other size
ARCH_LANES = dict(ARCH, head_dim=128)


@pytest.fixture(scope="module")
def lanes(fam):
    from benchmarks.harness import weights

    return (weights.make_params(fam, ARCH_LANES, 11, jnp.float32),
            fam.serve_args(ARCH_LANES))


def _served(params, args, n, kernels):
    """One prompt of n tokens through a fresh engine, the Pallas kernels on
    (interpreted) or off: its tokens and the run's observations."""
    with qm.fused_dispatch(kernels, interpret=True):
        eng = _engine(params, args)
        req, = eng.serve([Request(_ids(n, n), 6)])
    return list(req.token_ids), eng.metrics.summary()["observations"]


@pytest.mark.parametrize("n", [100, 20])
def test_the_prefill_kernel_serves_the_loops_tokens(lanes, n):
    """A prompt past `dense_len` (32) and one within it: the same tokens
    whether a sparse layer's windows attend through the kernel or the jnp
    loop, and every window of the run says which it was."""
    want, loop = _served(*lanes, n, kernels=False)
    got, kernel = _served(*lanes, n, kernels=True)
    assert got == want
    for obs, share in ((loop, 0.0), (kernel, 1.0)):
        said = obs["serve.sparse_prefill_kernel_share"]
        # 16-token chunks
        assert (said["mean"], said["count"]) == (share, -(-n // 16))


def test_a_shape_the_prefill_kernel_refuses_says_so(params, args):
    """Heads of 16 fill no lane block: the windows fall back to the loop
    with the kernels on, and `kernel_share` reads 0."""
    _, obs = _served(params, args, 45, kernels=True)
    assert obs["serve.sparse_prefill_kernel_share"]["mean"] == 0.0


def test_one_program_serves_a_short_and_a_long_context(params, args):
    eng = _engine(params, args)
    eng.serve([Request(_ids(n, n), 3) for n in (8, 16, 17)])
    before = {k: v for k, v in eng.metrics.summary()["counters"].items()
              if k.endswith("_compiles")}
    eng.serve([Request(_ids(120, 1), 8)])
    after = {k: v for k, v in eng.metrics.summary()["counters"].items()
             if k.endswith("_compiles")}
    assert after == before and before["decode_compiles"] == 1


def test_a_recycled_slot_starts_from_a_zero_state(fam, params, args):
    eng = _engine(params, args, max_slots=1)
    first, second = eng.serve([Request(_ids(50, 1), 5),
                               Request(_ids(44, 2), 5)])
    cold = _engine(params, args, max_slots=1).serve(
        [Request(_ids(44, 2), 5)])[0]
    assert second.token_ids == cold.token_ids
    assert _gap(fam, params, second).max() < TOL


@pytest.mark.parametrize("n", [12, 16])
def test_a_snapshot_hit_gives_the_tokens_of_a_cold_run(fam, params, args, n):
    """The first prompt's state is saved at its end (inside a page for 12
    tokens, at a page's edge for 16) and joins the radix tree when the
    request retires; the second prompt starts from it."""
    base = _ids(n, 5)
    longer = np.concatenate([base, _ids(20, 6)])
    eng = _engine(params, args)
    eng.serve([Request(base, 3)])
    hit = eng.serve([Request(longer, 6)])[0]
    c = eng.metrics.summary()["counters"]
    assert c["prefix_tokens_hit"] == n and c["state_snapshots"] == 2
    assert bool(c.get("cow_copies")) == bool(n % 8)
    cold = _engine(params, args).serve([Request(longer, 6)])[0]
    assert hit.token_ids == cold.token_ids
    assert _gap(fam, params, hit).max() < TOL


def test_the_newest_prompt_gets_a_snapshot_when_all_are_waiting(
        fam, params, args, monkeypatch):
    """More finished prompts still decoding than snapshot buffers (the
    serve driver's warm-up at a long chunk): the oldest waiting snapshot
    gives way, and the prompt submitted last is the one that hits."""
    from paddle_tpu.serving import family

    monkeypatch.setattr(family, "SNAPSHOTS", 2)
    base = _ids(12, 5)
    eng = _engine(params, args)
    eng.serve([Request(_ids(n, n), 8) for n in (9, 17, 20)]
              + [Request(base, 3)])
    hit = eng.serve([Request(np.concatenate([base, _ids(8, 6)]), 4)])[0]
    c = eng.metrics.summary()["counters"]
    assert c["prefix_tokens_hit"] == 12 and c["cow_copies"] >= 1
    assert c["state_snapshots"] == 5       # every prompt's end was saved
    assert _gap(fam, params, hit).max() < TOL


def test_a_match_with_no_snapshot_is_a_miss_and_is_counted(fam, params, args):
    """Pages match for 16 tokens, but the only snapshot is at the first
    prompt's end (24): nothing to resume from."""
    first = _ids(24, 7)
    other = np.concatenate([first[:16], _ids(20, 8)])
    eng = _engine(params, args)
    eng.serve([Request(first, 3)])
    got = eng.serve([Request(other, 5)])[0]
    c = eng.metrics.summary()["counters"]
    assert c["prefix_tokens_hit"] == 0
    assert c["prefix_hits_without_state"] == 1
    assert _gap(fam, params, got).max() < TOL


def test_preempt_and_resume_carry_the_state(fam, params, args):
    plain = _engine(params, args).serve([Request(_ids(45, 3), 10)])[0]
    eng = _engine(params, args)
    req = eng.submit(Request(_ids(45, 3), 10))
    while len(req.token_ids) < 4:
        eng.step()
    slot = eng.slots.active_slots[0]
    state = eng.preempt(slot)
    assert state["path_state"] is not None and not eng.slots.active_slots
    # another request takes the slot and leaves its own state behind
    eng.serve([Request(_ids(30, 4), 4)])
    assert eng.can_resume(state)
    eng.resume(state)
    eng.run_until_idle()
    assert req.token_ids == plain.token_ids


def test_preempt_mid_prefill_is_refused(params, args):
    eng = _engine(params, args)
    eng.submit(Request(_ids(60, 3), 4))
    eng.step()
    with pytest.raises(ValueError, match="mid-prefill"):
        eng.preempt(next(iter(eng._chunk_streams)))


def _bad_kinds(args):
    return args._replace(layer_kinds=("sparse", "mamba"))


def _bad_sparse(args):
    return args._replace(sparse=args.sparse._replace(dense_len=16))


@pytest.mark.parametrize("what,kw,change", [
    ("mesh", {"mesh": object()}, None),
    ("int8", {"kv_dtype": "int8"}, None),
    ("draft_params", {"draft_params": {}, "draft_args": object()}, None),
    ("block_size", {"page_size": 16, "prefill_chunk": 16}, None),
    ("radix", {"prefix_policy": "hash"}, None),
    ("a layer is", {}, _bad_kinds),
    ("dense_len", {}, _bad_sparse),
])
def test_what_is_not_carried_is_refused_with_the_reason(params, args, what,
                                                        kw, change):
    with pytest.raises(ValueError, match=what):
        _engine(params, change(args) if change else args, **kw)


@pytest.mark.parametrize("worker", ["PrefillWorker", "DecodeWorker"])
def test_disaggregated_workers_refuse_a_hybrid_model(params, args, worker):
    from paddle_tpu.serving import disagg

    with pytest.raises(ValueError, match="recurrent"):
        getattr(disagg, worker)(params, args,
                                transport=disagg.LocalTransport(), **ENGINE)


# ---------------------------------------------------------------------------
# the allocator: snapshots on the radix tree
# ---------------------------------------------------------------------------

def _alloc(snapshots=2, pages=32):
    return BlockAllocator(pages, 8, policy="radix",
                          state_snapshots=snapshots)


def _cache(a, tokens):
    """A finished request's pages in the tree; returns them released."""
    pages = [a.alloc() for _ in range(-(-len(tokens) // 8))]
    a.register_prefix(tokens, pages)
    for p in pages:
        a.release(p)
    return pages


def test_match_ends_at_the_deepest_snapshot_on_the_path():
    a = _alloc()
    toks = list(range(1, 41))
    _cache(a, toks)
    assert a.match_prefix(toks + [99], commit=False).matched == 0
    s20, s40 = a.take_snapshot(), a.take_snapshot()
    assert a.attach_state(toks[:20], s20) and a.attach_state(toks, s40)
    m = a.match_prefix(toks + [99], commit=False)
    assert (m.matched, m.state, len(m.pages)) == (40, s40, 5)
    m = a.match_prefix(toks[:30] + [99] * 5, commit=False)
    assert (m.matched, m.state, m.partial_len) == (20, s20, 4)
    assert m.partial_page is not None and len(m.pages) == 2
    # with no id free the least recently hit snapshot leaves the tree; a
    # sequence the tree does not hold takes none, and the id goes back
    taken = a.take_snapshot()
    assert taken == s20
    assert a.match_prefix(toks[:30] + [99] * 5, commit=False).matched == 0
    assert not a.attach_state([7] * 9, taken)
    assert a.take_snapshot() == taken


def test_the_least_recently_hit_snapshot_gives_its_id_up():
    a = _alloc(snapshots=2)
    one, two = list(range(1, 17)), list(range(101, 117))
    _cache(a, one)
    _cache(a, two)
    s1, s2 = a.take_snapshot(), a.take_snapshot()
    a.attach_state(one, s1)
    a.attach_state(two, s2)
    hit = a.match_prefix(one + [5])          # touches s1
    for p in hit.pages:
        a.release(p)
    assert a.take_snapshot() == s2           # two's snapshot was the older
    assert a.match_prefix(two + [5], commit=False).matched == 0
    assert a.match_prefix(one + [5], commit=False).state == s1


def test_a_split_keeps_each_snapshot_on_its_side():
    a = _alloc()
    toks = list(range(1, 33))
    _cache(a, toks)
    s12, s32 = a.take_snapshot(), a.take_snapshot()
    a.attach_state(toks[:12], s12)
    a.attach_state(toks, s32)
    _cache(a, toks[:20] + [200 + i for i in range(12)])   # splits at 20
    assert a.match_prefix(toks + [9], commit=False).state == s32
    m = a.match_prefix(toks[:20] + [200, 201, 9], commit=False)
    assert (m.matched, m.state) == (12, s12)


def test_evicting_the_pages_under_a_snapshot_frees_it():
    a = _alloc(snapshots=1, pages=6)         # 5 pages beside the null page
    toks = list(range(1, 41))
    _cache(a, toks)
    sid = a.take_snapshot()
    assert a.attach_state(toks, sid) and a.take_snapshot() == sid
    assert a.attach_state(toks, sid)
    fresh = a.alloc()                        # evicts the leaf's last page
    assert a.match_prefix(toks + [9], commit=False).matched == 0
    assert a.take_snapshot() == sid          # free again, not detached
    a.release(fresh)


def test_snapshots_need_the_radix_tree():
    with pytest.raises(ValueError, match="radix"):
        BlockAllocator(8, 8, policy="hash", state_snapshots=1)
    plain = BlockAllocator(8, 8)
    assert plain.take_snapshot() is None
