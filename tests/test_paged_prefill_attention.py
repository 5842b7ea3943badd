"""`kernels/paged_prefill_attention.py`: a prefill window's causal attention
over its slot's pages through the block table. The Pallas kernel (in the
interpreter) and the jnp fall-back against a plain float32 reference over the
gathered context; the bound by the window's last position is real (pages past
it hold NaN); what the `_supported` gate refuses."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import paged_prefill_attention as ppa
from paddle_tpu.kernels import quantized_matmul as qm


def _quantize(pool):
    """int8 codes and per (page, KV head) absmax scales of a float pool."""
    s = jnp.max(jnp.abs(pool), axis=(2, 3))
    q = jnp.clip(jnp.round(pool / jnp.maximum(s, 1e-9)[..., None, None]
                           * 127.0), -127, 127).astype(jnp.int8)
    return q, s


def _case(seed, s, h, last_idx, nh, nkv, hd=32, ps=8, P=8, layer=None,
          dtype=jnp.float32, poison=False):
    """Operands for one window: a pool of `layers` runs of pages, the slot's
    table a permutation of one run's pages (never the null page 0)."""
    rng = np.random.default_rng(seed)
    NP = P + 3
    runs = 1 if layer is None else layer + 2
    q = jnp.asarray(rng.normal(size=(s, nh, hd)), jnp.float32)
    pool_k = rng.normal(size=(runs * NP, nkv, ps, hd)).astype(np.float32)
    pool_v = rng.normal(size=(runs * NP, nkv, ps, hd)).astype(np.float32)
    bt_row = rng.permutation(np.arange(1, NP))[:P].astype(np.int32)
    base = 0 if layer is None else layer * NP
    if poison:
        # every page past the one that holds the window's last position,
        # and every page of the pool the table does not name
        # ... and in that page the positions past it
        last = h + last_idx
        dead = np.ones(runs * NP, bool)
        dead[base + bt_row[:last // ps + 1]] = False
        for pool in (pool_k, pool_v):
            pool[dead] = np.nan
            pool[base + bt_row[last // ps], :, last % ps + 1:] = np.nan
    return dict(q=q.astype(dtype), pool_k=jnp.asarray(pool_k, dtype),
                pool_v=jnp.asarray(pool_v, dtype), bt_row=jnp.asarray(bt_row),
                h=jnp.int32(h), last_idx=jnp.int32(last_idx),
                page_base=None if layer is None else jnp.int32(base))


def _reference(ops, k_scale=None, v_scale=None):
    """Plain float32 attention over the gathered context, a row at a time:
    row i sees the keys at positions 0 .. min(h + i, h + last_idx)."""
    q = np.asarray(ops["q"], np.float32)
    s, nh, hd = q.shape
    base = 0 if ops["page_base"] is None else int(ops["page_base"])
    pages = base + np.asarray(ops["bt_row"])
    h, last = int(ops["h"]), int(ops["h"]) + int(ops["last_idx"])

    def rows(pool, scale):
        x = np.asarray(pool, np.float32)[pages]              # [P, nkv, ps, hd]
        if scale is not None:
            x = x * (np.asarray(scale)[np.asarray(ops["bt_row"])]
                     / 127.0)[..., None, None]
        return np.moveaxis(x, 1, 0).reshape(x.shape[1], -1, hd)

    k, v = rows(ops["pool_k"], k_scale), rows(ops["pool_v"], v_scale)
    g = nh // k.shape[0]
    out = np.zeros((s, nh, hd), np.float32)
    for i in range(s):
        n = min(h + i, last) + 1
        for a in range(nh):
            sc = k[a // g, :n] @ q[i, a] / np.sqrt(hd)
            p = np.exp(sc - sc.max())
            out[i, a] = (p / p.sum()) @ v[a // g, :n]
    return out


def _run(via, ops, k_scale=None, v_scale=None, **blocks):
    sm_scale = 1.0 / np.sqrt(ops["q"].shape[-1])
    if via == "kernel":
        out = ppa._pallas(ops["q"], ops["pool_k"], ops["pool_v"],
                          ops["bt_row"], ops["h"], ops["last_idx"],
                          ops["page_base"], k_scale, v_scale, sm_scale, True,
                          **blocks)
    else:
        out = ppa._xla(ops["q"], ops["pool_k"], ops["pool_v"], ops["bt_row"],
                       ops["h"], ops["last_idx"], ops["page_base"], k_scale,
                       v_scale, sm_scale)
    return np.asarray(out, np.float32)


# name -> (s, h, last_idx, nh, nkv, layer)
_WINDOWS = {
    "h0_whole_window": (16, 0, 15, 4, 1, None),
    "h_inside_a_page": (16, 5, 15, 4, 1, None),
    "window_crosses_pages": (32, 13, 31, 4, 1, None),
    "padded_window": (32, 19, 6, 4, 1, None),
    "padded_past_its_first_query_block": (32, 3, 11, 4, 2, None),
    "mha": (16, 9, 15, 2, 2, None),
    "gqa_4_to_1_two_kv_heads": (16, 21, 12, 8, 2, None),
    "page_base_of_a_layers_run": (16, 11, 15, 4, 1, 1),
}


# the fall-back, and the kernel with one query block a grid step and with
# the whole window's query blocks sharing a step's key blocks
_VIAS = ["fallback", "kernel_block_a_step", "kernel_window_a_step"]


def _via(via, ops, *scales, pages_per_block=2):
    if via == "fallback":
        return _run(via, ops, *scales)
    span = 16 if via == "kernel_block_a_step" else ops["q"].shape[0]
    return _run("kernel", ops, *scales, block_q=16, span=span,
                pages_per_block=pages_per_block)


@pytest.mark.parametrize("via", _VIAS)
@pytest.mark.parametrize("name", list(_WINDOWS))
def test_matches_the_float32_reference(name, via):
    s, h, last_idx, nh, nkv, layer = _WINDOWS[name]
    ops = _case(1, s, h, last_idx, nh, nkv, layer=layer)
    real = slice(0, last_idx + 1)
    got = _via(via, ops)
    np.testing.assert_allclose(got[real], _reference(ops)[real], atol=2e-5,
                               rtol=2e-5)
    assert np.isfinite(got).all()          # a padded row: finite, meaningless


@pytest.mark.parametrize("via", _VIAS)
@pytest.mark.parametrize("name", ["h_inside_a_page", "padded_window",
                                  "page_base_of_a_layers_run"])
def test_pages_past_the_windows_last_position_are_never_read(name, via):
    """Those pages, every page the table does not name and the last live
    page's positions past the window's last hold NaN: the loop over key
    blocks ends at the window's last position, inside the last block a page
    past it is neither fetched nor summed, and a key past it in a fetched
    page is masked in the scores and in V."""
    s, h, last_idx, nh, nkv, layer = _WINDOWS[name]
    ops = _case(2, s, h, last_idx, nh, nkv, layer=layer, poison=True)
    clean = _case(2, s, h, last_idx, nh, nkv, layer=layer)
    # three pages a block: the last block straddles live and dead pages
    got = _via(via, ops, pages_per_block=3)
    assert np.isfinite(got).all()
    real = slice(0, last_idx + 1)
    np.testing.assert_allclose(got[real], _reference(clean)[real], atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("via", _VIAS)
def test_bf16_pool(via):
    ops = _case(3, 32, 13, 27, 8, 2, dtype=jnp.bfloat16)
    got = _via(via, ops)
    # bf16 probabilities into the second matmul: ~3 decimal digits
    np.testing.assert_allclose(got[:28], _reference(ops)[:28], atol=3e-2,
                               rtol=3e-2)


@pytest.mark.parametrize("via", _VIAS)
@pytest.mark.parametrize("layer", [None, 1])
def test_int8_pool_dequantises_what_it_reads(via, layer):
    """int8 codes with per (page, KV head) scales, those of the table's own
    run of pages: the reference dequantises the gathered context."""
    ops = _case(4, 32, 13, 27, 8, 2, ps=32, P=4, layer=layer)
    NP = 4 + 3
    base = 0 if layer is None else layer * NP
    (kq, ks), (vq, vs) = _quantize(ops["pool_k"]), _quantize(ops["pool_v"])
    ops.update(pool_k=kq, pool_v=vq)
    run_k, run_v = ks[base:base + NP], vs[base:base + NP]
    got = _via(via, ops, run_k, run_v)
    want = _reference(ops, run_k, run_v)
    np.testing.assert_allclose(got[:28], want[:28], atol=2e-4, rtol=2e-4)


def test_public_entry_dispatches_by_mode_and_shape():
    """hd 128 and whole tiles: the kernel under `fused_dispatch(True)`; the
    fall-back under `fused_dispatch(False)` and for a shape the gate
    refuses; all three agree."""
    ops = _case(5, 32, 9, 25, 4, 2, hd=128, ps=16, P=4)
    args = (ops["q"], ops["pool_k"], ops["pool_v"], ops["bt_row"], ops["h"],
            ops["last_idx"])
    assert ppa._supported(ops["q"].shape, ops["pool_k"].shape, (4,), 4, 4)
    def lowered():
        # a fresh function a mode: jit's cache does not know the mode
        return jax.jit(lambda *a: ppa.paged_prefill_attention(*a)).lower(
            *args).as_text(debug_info=True)

    with qm.fused_dispatch(True, interpret=True):
        text = lowered()
        kernel = np.asarray(ppa.paged_prefill_attention(*args))
    assert "pt.paged_attention/paged_prefill_attention" in text
    with qm.fused_dispatch(False):
        plain = lowered()
        fallback = np.asarray(ppa.paged_prefill_attention(*args))
    assert "pt.paged_attention/" in plain
    assert "pt.paged_attention/paged_prefill_attention" not in plain
    np.testing.assert_allclose(kernel[:26], fallback[:26], atol=2e-5,
                               rtol=2e-5)
    np.testing.assert_allclose(kernel[:26], _reference(ops)[:26], atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("why,q,pool,bt,q_item,pool_item,ok", [
    ("the serving cell: 32 / 8 heads x 128, 64-token pages, a 512 window",
     (512, 32, 128), (896, 8, 64, 128), (128,), 2, 2, True),
    ("its smallest bucket", (64, 32, 128), (896, 8, 64, 128), (128,), 2, 2,
     True),
    ("its int8 pool", (512, 32, 128), (1792, 8, 64, 128), (128,), 2, 1,
     True),
    ("one local KV head of a tensor-parallel shard",
     (512, 4, 128), (896, 1, 64, 128), (128,), 2, 2, True),
    ("multi-head attention, 30 heads", (256, 30, 128), (1920, 30, 64, 128),
     (96,), 2, 2, True),
    ("a head that is no lane block", (64, 4, 64), (32, 2, 16, 64), (8,), 2,
     2, False),
    ("query heads that do not divide into KV heads",
     (64, 6, 128), (32, 4, 16, 128), (8,), 2, 2, False),
    ("a bf16 window shorter than a sublane tile",
     (8, 4, 128), (32, 2, 16, 128), (8,), 2, 2, False),
    ("a window of two query blocks", (256, 4, 128), (32, 2, 16, 128), (8,),
     2, 2, True),
    ("a window of 1.5 query blocks", (192, 4, 128), (32, 2, 16, 128), (8,),
     2, 2, False),
    ("an int8 page of 16 tokens", (64, 4, 128), (32, 2, 16, 128), (8,), 2, 1,
     False),
    ("a batch of windows", (2, 64, 4, 128), (32, 2, 16, 128), (8,), 2, 2,
     False),
    ("a table a row", (64, 4, 128), (32, 2, 16, 128), (2, 8), 2, 2, False),
    ("pool and query of different head widths",
     (64, 4, 128), (32, 2, 16, 256), (8,), 2, 2, False),
])
def test_supported_gate(why, q, pool, bt, q_item, pool_item, ok):
    assert ppa._supported(q, pool, bt, q_item, pool_item) is ok, why


@pytest.mark.parametrize("why,q,pool,bt,q_item,pool_item,ok", [
    ("the sala cell's chunk: 2 KV heads x 16 a group, 776 pages a slot",
     (4096, 32, 128), (8192, 2, 64, 128), (776,), 2, 2, True),
    ("its smallest bucket", (64, 32, 128), (8192, 2, 64, 128), (776,), 2, 2,
     True),
    ("float32 operands at those widths: over the VMEM budget",
     (4096, 32, 128), (8192, 2, 64, 128), (776,), 4, 4, False),
    ("an int8 pool under a selection", (512, 32, 128), (1792, 8, 64, 128),
     (128,), 2, 1, False),
    ("the dense cell's shapes under a selection", (512, 32, 128),
     (896, 8, 64, 128), (128,), 2, 2, True),
    ("pages of 16: a key block's 64 are more than a word's bits",
     (512, 32, 128), (896, 8, 16, 128), (128,), 2, 2, False),
])
def test_supported_gate_under_a_selection(why, q, pool, bt, q_item,
                                          pool_item, ok):
    assert ppa._supported(q, pool, bt, q_item, pool_item,
                          selected=True) is ok, why


def test_blocks_follow_the_shapes():
    # a key block is two or more pages where a page is narrower than 128
    # (positions a matmul, positions a grid step, pages a key block)
    assert ppa._blocks(512, 4, 64, 128) == (128, 512, 16)
    assert ppa._blocks(2048, 4, 64, 128) == (128, 512, 16)
    assert ppa._blocks(64, 4, 64, 128) == (64, 64, 16)
    assert ppa._blocks(512, 4, 16, 128) == (128, 512, 64)
    assert ppa._blocks(512, 4, 2048, 4) == (128, 512, 1)
    assert ppa._blocks(512, 4, 64, 2) == (128, 512, 2)    # a two-page table
    # rows bounded: of a matmul, and of a grid step's accumulator
    assert ppa._blocks(512, 16, 64, 128) == (64, 128, 16)
    assert ppa._blocks(4096, 16, 64, 776) == (64, 128, 16)
    assert ppa._blocks(64, 16, 64, 776) == (64, 64, 16)
    assert ppa._blocks(512, 8, 64, 128) == (128, 256, 16)



# name -> ((s, nh, nkv, hd, page, pages a slot, pages in the pool, type),
# sha256 of the dense call's jaxpr): recorded from PR 38's kernel as PR 44
# left it, before the kernel learned a selection. A call without one traces
# to that program, equation for equation; a PR that means to change the
# dense kernel records its own
_DENSE_PROGRAMS = {
    "mistral_chunk": ((512, 32, 8, 128, 64, 128, 2688, jnp.bfloat16),
                      "6a2e7178e2f92642"),
    "mistral_smallest_bucket": ((64, 32, 8, 128, 64, 128, 2688, jnp.bfloat16),
                                "54743322a5376b74"),
    "one_kv_head": ((256, 4, 1, 128, 64, 128, 896, jnp.bfloat16),
                    "2b7410f774455a86"),
    "no_group": ((128, 16, 16, 128, 64, 16, 129, jnp.bfloat16),
                 "16793427b4ef037c"),
    "float32_pages_of_16": ((64, 8, 2, 128, 16, 8, 33, jnp.float32),
                            "ac24f31db128ba66"),
    "int8_pool": ((512, 32, 8, 128, 64, 128, 1792, jnp.int8),
                  "b5e092953cb03229"),
}


@pytest.mark.parametrize("name", list(_DENSE_PROGRAMS))
def test_a_dense_call_traces_to_the_program_it_was(name):
    import hashlib

    (s, nh, nkv, hd, ps, P, NP, dt), want = _DENSE_PROGRAMS[name]
    quant = dt == jnp.int8
    pool = jax.ShapeDtypeStruct((NP, nkv, ps, hd), dt)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    args = [jax.ShapeDtypeStruct((s, nh, hd), jnp.bfloat16 if quant else dt),
            pool, pool, jax.ShapeDtypeStruct((P,), jnp.int32), scalar,
            scalar, scalar]
    args += [jax.ShapeDtypeStruct((NP, nkv), jnp.float32)] * (2 * quant)

    def call(q, k, v, bt, h, last, base, ks=None, vs=None):
        return ppa._pallas(q, k, v, bt, h, last, base, ks, vs, hd ** -0.5,
                           False)

    text = str(jax.make_jaxpr(call)(*args))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == want

# ---------------------------------------------------------------------------
# under a selection: a sparse layer's window (2 KV heads x 16 a group, pages
# of 64), the kernel in the interpreter against `sparse_attention`'s jnp loop
# ---------------------------------------------------------------------------

from paddle_tpu.kernels import sparse_attention as sa  # noqa: E402

SPARSE = sa.SparseConfig(block_size=64, kernel_size=32, kernel_stride=16,
                         topk=64, init_blocks=1, local_blocks=32,
                         dense_len=8192)


def _sparse_case(seed, s, h, last_idx, P, dtype=jnp.float32, nkv=2, g=16,
                 d=128, cfg=SPARSE):
    """A window's operands with the selection `prefill_selection` makes of
    them: pools of random keys, the compressed keys their means."""
    rng = np.random.default_rng(seed)
    B, T, K = cfg.block_size, cfg.kernel_stride, cfg.kernel_size
    NP, n = P + 3, h + last_idx + 1
    q = jnp.asarray(rng.normal(size=(s, nkv, g, d)), dtype)
    pk = rng.normal(size=(NP, nkv, B, d)).astype(np.float32)
    pv = rng.normal(size=(NP, nkv, B, d)).astype(np.float32)
    bt = rng.permutation(np.arange(1, NP))[:P].astype(np.int32)
    keys = np.moveaxis(pk[bt], 1, 0).reshape(nkv, P * B, d)
    csum = np.concatenate([np.zeros((nkv, 1, d), np.float32),
                           np.cumsum(keys, 1)], 1)
    ends = np.arange(K - 1, n, T)
    kc = np.zeros((NP, nkv, cfg.per, d), np.float32)
    kc[bt[ends // B], :, (ends % B + 1) // T - 1] = np.moveaxis(
        (csum[:, ends + 1] - csum[:, ends + 1 - K]) / K, 0, 1)
    qpos = h + jnp.arange(s, dtype=jnp.int32)
    sel = sa.prefill_selection(q.astype(jnp.float32), jnp.asarray(kc),
                               jnp.asarray(bt), qpos, cfg, q_tile=min(s, 512))
    return dict(q=q, pool_k=jnp.asarray(pk, dtype),
                pool_v=jnp.asarray(pv, dtype), bt_row=jnp.asarray(bt),
                sel=sel, qpos=qpos, last_pos=jnp.int32(h + last_idx))


def _selected_kernel(ops, cfg=SPARSE):
    """The kernel under the selection, in the interpreter, whatever the
    gate's count of VMEM says of float32 operands."""
    s, nkv, g, d = ops["q"].shape
    h = ops["qpos"][0]
    out = ppa._pallas(ops["q"].reshape(s, nkv * g, d), ops["pool_k"],
                      ops["pool_v"], ops["bt_row"], h, ops["last_pos"] - h,
                      None, None, None, d ** -0.5, True,
                      selection=ppa.page_bits(ops["sel"], g, cfg.block_size))
    return np.asarray(out, np.float32).reshape(s, nkv, g, d)


# name -> (window, h, last_idx, pages a slot)
_SPARSE_WINDOWS = {
    "wholly_within_dense_len": (128, 1000, 127, 40),
    "straddles_dense_len": (256, 8192 - 100, 255, 140),
    "past_20k_of_context": (128, 20480 + 17, 127, 330),
    "starts_inside_a_page_padded_past_last_idx": (128, 9000 + 13, 70, 150),
    "the_smallest_bucket": (64, 9000, 63, 150),
}


@pytest.mark.parametrize("name", list(_SPARSE_WINDOWS) + ["dense_call"])
def test_selected_window_matches_the_jnp_loop(name):
    if name == "dense_call":
        # no selection: the call it was, which is also every bit set, bit
        # for bit
        ops = _sparse_case(7, 128, 1000, 100, 40)
        s, nkv, g, d = ops["q"].shape
        dense = ppa._pallas(
            ops["q"].reshape(s, nkv * g, d), ops["pool_k"], ops["pool_v"],
            ops["bt_row"], jnp.int32(1000), jnp.int32(100), None, None, None,
            d ** -0.5, True)
        ops["sel"] = jnp.ones_like(ops["sel"])
        np.testing.assert_array_equal(
            np.asarray(dense).reshape(s, nkv, g, d)[:101],
            _selected_kernel(ops)[:101])
        return
    s, h, last_idx, P = _SPARSE_WINDOWS[name]
    ops = _sparse_case(6, s, h, last_idx, P)
    want = np.asarray(sa._prefill_loop(*ops.values(), SPARSE), np.float32)
    got = _selected_kernel(ops)
    assert np.isfinite(got).all()
    real = slice(0, last_idx + 1)
    np.testing.assert_allclose(got[real], want[real], atol=2e-5, rtol=2e-5)


def test_a_key_block_no_query_of_a_block_picked_is_skipped():
    """Two steps of 128 queries past `dense_len` whose queries agree on a few
    pages: every KEY BLOCK none of them picked from holds NaN (a probability
    of 0 times it is not 0: a query block none of whose rows picked a page
    of a key block never multiplies by it), the tail of the window's last
    page too. The padded rows, the last query block among them, pick nothing
    at all and come out finite."""
    s, h, P, real = 256, 9000, 150, 180
    ops = _sparse_case(8, s, h, real - 1, P)
    own = np.asarray(ops["qpos"]) // 64
    picked = np.zeros((2, s, P), bool)
    for kv, first in enumerate(([0, 3, 50], [0, 77])):
        picked[kv][:, first] = True
        picked[kv][np.arange(s), own] = True        # a query's own page
    picked[0, 128:, 90] = True                      # the second step alone
    picked[:, real:] = False
    ops["sel"] = jnp.asarray(picked)
    want = np.asarray(sa._prefill_loop(*ops.values(), SPARSE), np.float32)
    bt = np.asarray(ops["bt_row"])
    last = h + real - 1
    for kv in range(2):
        # key blocks of 16 pages: dead where nobody of the window picked
        dead = np.repeat(~np.pad(picked[kv].any(0), (0, 10)).reshape(
            -1, 16).any(1), 16)[:P]
        assert dead.sum() == (86, 102)[kv]
        for name in ("pool_k", "pool_v"):
            pool = np.array(ops[name])
            pool[bt[dead], kv] = np.nan
            pool[bt[last // 64], kv, last % 64 + 1:] = np.nan
            ops[name] = jnp.asarray(pool)
    got = _selected_kernel(ops)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[:real], want[:real], atol=2e-5, rtol=2e-5)


def test_sparse_prefill_dispatches_by_mode_and_shape():
    """bf16 at the sala widths: the kernel under `fused_dispatch(True)` and
    the scope's name on it; the loop under `fused_dispatch(False)` and for a
    shape the gate refuses (here a head of 64)."""
    ops = _sparse_case(9, 64, 9000, 63, 150, dtype=jnp.bfloat16)
    args = tuple(ops.values())

    def text(*a):
        return jax.jit(lambda *a: sa.sparse_prefill_attention(
            *a, SPARSE)).lower(*a).as_text(debug_info=True)

    kernel = "pt.sparse_attention/pt.paged_attention/paged_prefill_attention"
    with qm.fused_dispatch(True, interpret=True):
        assert sa.prefill_takes_kernel(ops["q"], ops["pool_k"], ops["bt_row"])
        assert kernel in text(*args) and "pt.sparse_select" in text(*args)
        got = sa.sparse_prefill_attention(*args, SPARSE)
        narrow = _sparse_case(9, 64, 9000, 63, 150, dtype=jnp.bfloat16, d=64)
        assert not sa.prefill_takes_kernel(narrow["q"], narrow["pool_k"],
                                           narrow["bt_row"])
        assert kernel not in text(*narrow.values())
    with qm.fused_dispatch(False):
        assert not sa.prefill_takes_kernel(ops["q"], ops["pool_k"],
                                           ops["bt_row"])
        assert kernel not in text(*args)
        want = sa.sparse_prefill_attention(*args, SPARSE)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2,
                               rtol=2e-2)
