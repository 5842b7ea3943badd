"""The paged decode kernel compiled for the chip, without the chip: the TPU's
compiler is installed here and compiles for a v5e that is described, not
attached. Interpret mode cannot see what Mosaic refuses (a slice off the
tiling, a copy it cannot stride, too much VMEM); these compiles can, at the
serving cell's real widths, in about a second each. Nothing runs: a compile
that passes says nothing about results or times.

Only one process at a time may load the TPU's library, so the topology is
described inside a fixture of this ONE file (never at import), and the tests
skip where it cannot be described.
"""

import math
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from paddle_tpu.kernels import quantized_matmul as qm

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def topology():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topology):
    return SingleDeviceSharding(topology.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    # an executable for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


# name -> slots, query heads, kv heads, head dim, page size, pages a slot,
# pages in the pool, pool dtype, pages a block (None: from the shapes)
_SHAPES = {
    "mistral7b_saturated_cell": (32, 32, 8, 128, 64, 128, 896, "bfloat16",
                                 None),
    "mistral7b_int8_pool": (32, 32, 8, 128, 64, 128, 1792, "int8", None),
    "one_local_kv_head_of_a_tp_shard": (32, 4, 1, 128, 64, 128, 896,
                                        "bfloat16", None),
    "chip_smoke_llama_1b": (8, 16, 16, 128, 64, 16, 129, "bfloat16", None),
    "float32_pool_one_page_blocks": (4, 8, 2, 128, 16, 8, 33, "float32", 1),
    # a hybrid model's sparse layers: 32 slots x 2 KV heads as rows of 16
    # query heads over the pool viewed as one-head pages, through a
    # compacted table of a selection (64) or a dense context (128 pages)
    "minicpm_sala_compacted_table": (64, 16, 1, 128, 64, 128, 16384,
                                     "bfloat16", None),
    # a delta hybrid model's full layers: multi-head attention, 30 query
    # heads over 30 KV heads a page (a page of K is 491,520 B)
    "olmo_hybrid_chat_replies_cell": (64, 30, 30, 128, 64, 96, 1920,
                                      "bfloat16", None),
}


@pytest.mark.parametrize("name", list(_SHAPES))
def test_paged_decode_kernel_compiles_for_v5e(one_chip, name):
    b, nh, nkv, hd, ps, P, NP, dtype, ppb = _SHAPES[name]
    pool_dtype = jnp.dtype(dtype)
    q_dtype = jnp.bfloat16 if dtype == "int8" else pool_dtype

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    args = [sds((b, 1, nh, hd), q_dtype), sds((NP, nkv, ps, hd), pool_dtype),
            sds((NP, nkv, ps, hd), pool_dtype), sds((b, P), jnp.int32),
            sds((b,), jnp.int32)]
    if dtype == "int8":
        args += [sds((NP, nkv), jnp.float32)] * 2
    assert qm.paged_decode_supported(args[0].shape, args[1].shape,
                                     args[3].shape, pool_dtype.itemsize)

    def call(q, k, v, bt, pos, ks=None, vs=None):
        return qm._paged_decode_attention_pallas(
            q, k, v, bt, pos, hd ** -0.5, False, ks, vs, pages_per_block=ppb)

    text = jax.jit(call).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("kind", ["bfloat16", "int8"])
def test_decode_program_touches_pages_and_never_a_whole_pool(one_chip, kind):
    """The dense decode program at the serving cell's widths (32 rows, 32 / 8
    heads x 128, FFN 14,336, 896 pages x 64, 128 pages a slot; depth 3), pools
    donated: the stacked pools ride the layer scan as its carry, so nothing
    of a layer's slice of a pool (117 MB in bf16) or more is computed but
    the in-place scatters of the rows' own pages, and the program plans
    no temporary of that size. (A scan over the pools as `xs` / `ys` sliced
    a layer out, re-laid it around the token's write and wrote the stack
    back: five pool-sized operations a layer, 4.46 GB of temporaries.)"""
    import re

    from paddle_tpu.models import generation as gen
    from paddle_tpu.models import llama_functional as lf

    L, b, nkv, hd, ps, P, NP = 3, 32, 8, 128, 64, 128, 896
    args = lf.LlamaArgs(32768, 4096, 14336, L, 32, nkv, 1e6, 1e-5)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: lf.init_params(args, jax.random.key(0),
                                              jnp.bfloat16)))
    pool = sds((L, NP, nkv, ps, hd), jnp.dtype(kind))
    if kind == "int8":
        pool = gen.QuantizedKVPage(pool, sds((L, NP, nkv), jnp.float32))
    table = sds((P * ps, hd), jnp.float32)

    def decode(params, ids, pk, pv, bt, pos, cos, sin):
        with qm.fused_dispatch(True):
            return gen._paged_forward_decode(params, ids, pk, pv, bt, pos,
                                             cos, sin, args, ps)

    compiled = jax.jit(decode, donate_argnums=(2, 3)).lower(
        params, sds((b, 1), jnp.int32), pool, pool, sds((b, P), jnp.int32),
        sds((b,), jnp.int32), table, table).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1

    moved = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
        if not m or m.group(2) in ("parameter", "tuple", "get-tuple-element",
                                   "while", "bitcast"):
            continue
        # results shaped like pages: [.., nkv, ps, hd] (a feed-forward
        # weight of this model happens to have a layer slice's elements)
        pages = [math.prod(map(int, dims.split(","))) for dims in re.findall(
            rf"\[([\d,]+),{nkv},{ps},{hd}\]", m.group(1))]
        if pages and max(pages) >= NP:
            if not re.search(r'op_name="[^"]*pt\.kv_write/scatter"', line):
                moved.append(line.strip()[:200])
    assert not moved, "\n".join(moved)
    layer_bytes = NP * nkv * ps * hd * jnp.dtype(kind).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes


# name -> window, query heads, kv heads, head dim, page size, pages a slot,
# pages in the pool (a stack of layers' runs), pool dtype
_PREFILL_SHAPES = {
    "mistral7b_saturated_cell_chunk": (512, 32, 8, 128, 64, 128, 3 * 896,
                                       "bfloat16"),
    "mistral7b_saturated_cell_smallest_bucket": (64, 32, 8, 128, 64, 128,
                                                 3 * 896, "bfloat16"),
    "mistral7b_int8_pool": (512, 32, 8, 128, 64, 128, 3 * 1792, "int8"),
    "one_local_kv_head_of_a_tp_shard": (256, 4, 1, 128, 64, 128, 896,
                                        "bfloat16"),
    "chip_smoke_llama_1b_mha": (128, 16, 16, 128, 64, 16, 129, "bfloat16"),
    "float32_pool_16_token_pages": (64, 8, 2, 128, 16, 8, 33, "float32"),
}


@pytest.mark.parametrize("name", list(_PREFILL_SHAPES))
def test_paged_prefill_kernel_compiles_for_v5e(one_chip, name):
    from paddle_tpu.kernels import paged_prefill_attention as ppa

    s, nh, nkv, hd, ps, P, NP, dtype = _PREFILL_SHAPES[name]
    pool_dtype = jnp.dtype(dtype)
    q_dtype = jnp.dtype("bfloat16") if dtype == "int8" else pool_dtype

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    args = [sds((s, nh, hd), q_dtype), sds((NP, nkv, ps, hd), pool_dtype),
            sds((NP, nkv, ps, hd), pool_dtype), sds((P,), jnp.int32),
            sds((), jnp.int32), sds((), jnp.int32), sds((), jnp.int32)]
    if dtype == "int8":
        args += [sds((NP // 3, nkv), jnp.float32)] * 2
    assert ppa._supported(args[0].shape, args[1].shape, args[3].shape,
                          q_dtype.itemsize, pool_dtype.itemsize)

    def call(q, k, v, bt, h, last, base, ks=None, vs=None):
        return ppa._pallas(q, k, v, bt, h, last, base, ks, vs, hd ** -0.5,
                           False)

    text = jax.jit(call).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "paged_prefill_attention" in text


# name -> window: a sparse layer's prefill window of the sala cell (2 KV heads
# x 16 query heads a group x 128, 776 pages of 64 a slot, 8,192 in the pool)
_SELECTED_WINDOWS = {"minicpm_sala_chunk": 4096,
                     "minicpm_sala_smallest_bucket": 64}


@pytest.mark.parametrize("name", list(_SELECTED_WINDOWS))
def test_prefill_kernel_under_a_selection_compiles_for_v5e(one_chip, name):
    """The (query block, key block) flags in SMEM (25 KB at the chunk), the
    rows' picks in VMEM, one mask for the group's 16 heads: with the bits'
    packing in front, as `sparse_prefill_attention` calls it."""
    from paddle_tpu.kernels import paged_prefill_attention as ppa

    s, nkv, g, hd, ps, P, NP = _SELECTED_WINDOWS[name], 2, 16, 128, 64, 776, \
        8192

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = sds((NP, nkv, ps, hd), jnp.bfloat16)
    args = [sds((s, nkv * g, hd), jnp.bfloat16), pool, pool,
            sds((P,), jnp.int32), sds((), jnp.int32), sds((), jnp.int32),
            sds((nkv, s, P), jnp.bool_)]
    assert ppa._supported(args[0].shape, pool.shape, (P,), 2, 2,
                          selected=True)

    def call(q, k, v, bt, h, last, sel):
        return ppa._pallas(q, k, v, bt, h, last, None, None, None,
                           hd ** -0.5, False,
                           selection=ppa.page_bits(sel, g, ps))

    text = jax.jit(call).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "paged_prefill_attention" in text


@pytest.mark.parametrize("kind", ["bfloat16", "int8"])
def test_prefill_program_writes_its_pages_and_holds_no_stripe(one_chip, kind):
    """The dense prefill program at the serving cell's widths (a 512-token
    window, 32 / 8 heads x 128, FFN 14,336, 896 pages x 64, 128 pages a
    slot; depth 3), pools donated: the window's K / V goes into the
    window's own pages of the carried pools and one kernel a layer walks
    the table, so the program plans no temporary as large as ONE layer's
    stripe `[nkv, pages_per_slot * page_size, hd]` (16.8 MB in bf16; it
    gathered two of `[L, 1, nkv, 8,704, hd]`, forwarded over them and cut
    128 pages a layer back out), and computes nothing shaped like pages as
    large as a layer's run of the pool but the in-place page writes."""
    import re

    from paddle_tpu.models import generation as gen
    from paddle_tpu.models import llama_functional as lf

    L, sb, nkv, hd, ps, P, NP = 3, 512, 8, 128, 64, 128, 896
    args = lf.LlamaArgs(32768, 4096, 14336, L, 32, nkv, 1e6, 1e-5)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: lf.init_params(args, jax.random.key(0),
                                              jnp.bfloat16)))
    pool = sds((L, NP, nkv, ps, hd), jnp.dtype(kind))
    if kind == "int8":
        pool = gen.QuantizedKVPage(pool, sds((L, NP, nkv), jnp.float32))
    table = sds((2 * P * ps, hd), jnp.float32)

    def prefill(params, ids, pk, pv, h, last_idx, bt_row, new_pages, cos,
                sin):
        with qm.fused_dispatch(True):
            return gen._paged_forward_prefill(
                params, ids, pk, pv, h, last_idx, bt_row, new_pages, cos,
                sin, args, ps)

    compiled = jax.jit(prefill, donate_argnums=(2, 3)).lower(
        params, sds((1, sb), jnp.int32), pool, pool, sds((), jnp.int32),
        sds((), jnp.int32), sds((P,), jnp.int32), sds((P,), jnp.int32),
        table, table).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "paged_prefill_attention" in text

    moved = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
        if not m or m.group(2) in ("parameter", "tuple", "get-tuple-element",
                                   "while", "bitcast"):
            continue
        pages = [math.prod(map(int, dims.split(","))) for dims in re.findall(
            rf"\[([\d,]+),{nkv},{ps},{hd}\]", m.group(1))]
        if pages and max(pages) >= P:
            if not re.search(r'op_name="[^"]*pt\.kv_write/scatter"', line):
                moved.append(line.strip()[:200])
    assert not moved, "\n".join(moved)
    stripe_bytes = nkv * P * ps * hd * 2
    assert compiled.memory_analysis().temp_size_in_bytes < stripe_bytes


# name -> LlamaArgs' widths (vocab, hidden, FFN, heads, KV heads), window,
# page size, pages a slot, pages in the pool
_TP_PREFILL = {
    "chip_smoke_mp4_llama_1b_chunk": ((32000, 2048, 5504, 16, 16), 256, 64,
                                      16, 129),
    "mistral7b_cell_widths_mp4": ((32768, 4096, 14336, 32, 8), 512, 64, 128,
                                  896),
}


@pytest.mark.parametrize("name", list(_TP_PREFILL))
def test_tensor_parallel_prefill_program_compiles_for_four_chips(topology,
                                                                 name):
    """`DensePath`'s prefill program as `mesh=` builds it (one shard_map
    SPMD program over `mp`, the pools sharded on their KV heads), compiled
    for the four described chips: a shard's kernel sees `nkv / 4` KV heads
    and their whole groups of query heads, the table scalar-prefetched."""
    import functools

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.models import llama_functional as lf
    from paddle_tpu.observability import MetricsRegistry
    from paddle_tpu.serving import dense, tp as tp_lib

    (vocab, hidden, ffn, nh, nkv), sb, ps, Pn, NP = _TP_PREFILL[name]
    L, hd = 2, hidden // nh
    args = lf.LlamaArgs(vocab, hidden, ffn, L, nh, nkv, 1e6, 1e-5)
    mesh = Mesh(np.array(topology.devices), ("mp",))
    tp_lib.tp_validate(args, 4)

    def sds(shape, dt, spec=P()):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, spec))

    shapes = jax.eval_shape(
        lambda: lf.init_params(args, jax.random.key(0), jnp.bfloat16))
    pspecs = tp_lib.llama_tp_specs(shapes, "mp")
    params = jax.tree_util.tree_map(
        lambda a, spec: sds(a.shape, a.dtype, spec), shapes, pspecs)
    pool = sds((L, NP, nkv, ps, hd), jnp.bfloat16, tp_lib.pool_spec("mp"))
    i32, f32 = sds((), jnp.int32), sds((), jnp.float32)
    table = sds((2 * Pn * ps, hd), jnp.float32)
    rep, pspec = P(), tp_lib.pool_spec("mp")
    body = functools.partial(
        dense._paged_prefill_traced, args=args, metrics=MetricsRegistry(),
        page_size=ps, sample=False, tp_axis="mp", tp_degree=4)
    program = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspecs, rep, rep, rep, rep, rep, pspec, pspec, rep, rep,
                  rep, rep, rep, rep),
        out_specs=(pspec, pspec, rep), check_vma=False),
        donate_argnums=(6, 7))
    with qm.fused_dispatch(True):
        text = program.lower(
            params, sds((1, sb), jnp.int32), i32, i32, sds((Pn,), jnp.int32),
            sds((Pn,), jnp.int32), pool, pool, table, table, f32, f32, i32,
            sds((1,), jnp.int32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "paged_prefill_attention" in text


# ---------------------------------------------------------------------------
# the latent-attention expert family's kernels and its decode program at the
# cell's widths (`serve_deepseek_v2_long_answers`: 64 rows, 128 heads over a
# cached row of 512 + 64 values laid out as 640 lanes, 256 pages a slot of a
# pool of 6 x 8,192 pages x 64 tokens; 20 experts of 5120 x 1536 held)
# ---------------------------------------------------------------------------

def test_latent_decode_kernel_compiles_for_v5e(one_chip):
    from paddle_tpu.kernels import latent_attention as la

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    args = [sds((64, 128, 640), jnp.bfloat16),
            sds((6 * 8192, 64, 640), jnp.bfloat16),
            sds((64, 256), jnp.int32), sds((64,), jnp.int32),
            sds((), jnp.int32)]
    assert la.latent_decode_supported(args[0].shape, args[1].shape,
                                      args[2].shape, 512)
    # a row of 576 values is no whole lane tile: Mosaic refuses to copy a
    # page of it, which is why the pool states 640
    assert not la.latent_decode_supported((64, 128, 576), (8, 64, 576),
                                          (64, 256), 512)

    def call(q, pool, bt, pos, base):
        return la._decode_pallas(q, pool, bt, pos, 0.1147, 512, base, False)

    text = jax.jit(call).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("window", [64, 2048])
def test_latent_prefill_kernel_compiles_for_v5e(one_chip, window):
    from paddle_tpu.kernels import latent_attention as la

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    args = [sds((window, 128, 128), jnp.bfloat16),
            sds((window, 128, 64), jnp.bfloat16),
            sds((16384, 128 * 256), jnp.bfloat16),
            sds((16384, 64), jnp.bfloat16), sds((), jnp.int32),
            sds((), jnp.int32)]
    assert la.latent_prefill_supported(args[0].shape, args[1].shape,
                                       args[2].shape, 128)

    def call(qn, qr, kv, kr, h, last):
        return la._prefill_pallas(qn, qr, kv, kr, h, last, 0.1147, 128, False)

    text = jax.jit(call).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


def _fused_expert_calls(text):
    """How many of a compiled program's Mosaic calls under the scope
    `pt.expert_ffn` are the fused expert pass's (`expert_gate_up`,
    `expert_down`): what `expert_ffn_time_share` finds them by."""
    import re

    def calls(kernel):
        return len(re.findall(
            rf"%{kernel}[.\d]* = .*custom_call_target=\"tpu_custom_call\""
            rf".*op_name=\"[^\"]*pt\.expert_ffn/{kernel}", text))

    return calls("expert_gate_up"), calls("expert_down")


def test_latent_decode_program_copies_no_experts_and_no_pool(one_chip):
    """The family's decode program at the cell's widths, depth 2, the pool
    donated: the routed experts of its 64 rows are the fused pass's two
    Mosaic calls under `pt.expert_ffn` over the WHOLE stack of experts (a
    layer's slice of it handed to a custom call was a copy of 315 MB a
    projection a step) and no grouped matmul, one latent kernel, and no
    temporary of an expert stack's or the pool's size. A 512-token PREFILL
    window at the same widths still holds XLA's three grouped matmuls."""
    from paddle_tpu.models import latent_moe_functional as lm

    L, b, ps, P, NP = 2, 64, 64, 256, 8192
    args = lm.LatentMoEArgs(
        vocab_size=12800, hidden_size=5120, num_layers=L, num_heads=128,
        q_rank=1536, kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128,
        dense_intermediate=12288, expert_intermediate=1536, shared_experts=2,
        routed_experts=160, first_expert=0, experts_held=20, n_group=8,
        topk_group=3, experts_per_tok=6, routed_scaling=16.0,
        first_k_dense=0, rope_theta=10000.0, rms_eps=1e-6,
        yarn=lm.YarnConfig(40.0, 4096, 32.0, 1.0, 0.707, 0.707))
    h, H, E, m = 5120, 128, 20, 1536

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    layers = {"ln1": (h,), "ln2": (h,), "w_qa": (h, 1536), "q_norm": (1536,),
              "w_qb": (1536, H * 192), "w_kva": (h, 576), "kv_norm": (512,),
              "w_kvb": (512, H * 256), "wo": (H * 128, h),
              "router": (h, 160), "ws_gate": (h, 2 * m), "ws_up": (h, 2 * m),
              "ws_down": (2 * m, h), "we_gate": (E, h, m), "we_up": (E, h, m),
              "we_down": (E, m, h)}
    params = {"embedding": sds((12800, h)), "final_norm": sds((h,)),
              "lm_head": sds((h, 12800)),
              "layers": {k: sds((L,) + s) for k, s in layers.items()}}
    pool = sds((L * NP, ps, args.row_width))
    assert args.row_width == 640
    table = sds((2 * P * ps, 64), jnp.float32)

    def decode(params, tokens, bt, pos, live, pool, cos, sin):
        with qm.fused_dispatch(True):
            return lm.decode_step(params, None, tokens, bt, pos, live, pool,
                                  (), (cos, sin), args)

    compiled = jax.jit(decode, donate_argnums=(5,)).lower(
        params, sds((b,), jnp.int32), sds((b, P), jnp.int32),
        sds((b,), jnp.int32), sds((b,), jnp.bool_), pool, table,
        table).compile()
    text = compiled.as_text()
    # the latent kernel and the fused pass's two (the layers are a scan:
    # one body), no grouped matmul and no sort of the picks
    assert "latent_decode_attention" in text
    assert _fused_expert_calls(text) == (1, 1)
    assert "ragged-dot" not in text
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    # one expert stack of a layer is 315 MB, the pool 1.3 GB here: what the
    # program plans beside its arguments stays far under either
    assert compiled.memory_analysis().temp_size_in_bytes < 150e6

    def prefill(params, ids, at, last, bt_row, new_pages, pool, cos, sin):
        with qm.fused_dispatch(True):
            return lm.prefill_window(params, None, ids, at, last, bt_row,
                                     new_pages, pool, (), (cos, sin), args)

    text = jax.jit(prefill, donate_argnums=(6,)).lower(
        params, sds((512,), jnp.int32), sds((), jnp.int32),
        sds((), jnp.int32), sds((P,), jnp.int32), sds((P,), jnp.int32), pool,
        table, table).compile().as_text()
    # XLA's own grouped-matmul kernels (three projections and their tile
    # schedule), as before the fused pass: 3,072 rows are past the ridge
    assert text.count("ragged-dot-none") >= 3
    assert _fused_expert_calls(text) == (0, 0)


def test_selector_programs_compile_at_the_glm5_widths(one_chip):
    """GLM-5's two step programs (`LatentMoEArgs` with an `IndexerConfig`)
    at the cell's widths, tables and window, depth 2 (one dense leading
    layer, one expert layer), both pools donated: Mosaic takes the index
    pool's decode kernel (`index_decode_scores`: 128-wide pages, a [1,
    71,680] float32 output block a row), the absorbed decode kernel over
    the gathered rows, and the masked prefill kernel (`[H, chunk, 192]`
    keys, the running softmax aliased in and out); neither program plans a
    temporary of a pool's size, and the window's stay within what the chip
    has beside the cell's 11.9 GB of weights and pools."""
    from paddle_tpu.models import latent_moe_functional as lm

    L, b, ps, max_len, NP = 2, 32, 64, 71680, 2048
    P = max_len // ps
    args = lm.LatentMoEArgs(
        vocab_size=19360, hidden_size=6144, num_layers=L, num_heads=64,
        q_rank=2048, kv_rank=512, nope_dim=192, rope_dim=64, v_dim=256,
        dense_intermediate=12288, expert_intermediate=2048, shared_experts=1,
        routed_experts=256, first_expert=0, experts_held=16, n_group=1,
        topk_group=1, experts_per_tok=8, routed_scaling=2.5, first_k_dense=1,
        rope_theta=1e6, rms_eps=1e-5, yarn=None,
        indexer=lm.IndexerConfig(32, 128, 2048), scoring="sigmoid",
        norm_topk=True, record_selection=True)
    h, H, E, m = 6144, 64, 16, 2048

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    both = {"ln1": (h,), "ln2": (h,), "w_qa": (h, 2048), "q_norm": (2048,),
            "w_qb": (2048, H * 256), "w_kva": (h, 576), "kv_norm": (512,),
            "w_kvb": (512, H * 448), "wo": (H * 256, h),
            "w_iq": (2048, 32 * 128), "w_ik": (h, 128), "ik_norm": (128,),
            "ik_bias": (128,), "w_iw": (h, 32)}
    expert = dict(both, router=(h, 256), router_bias=(256,), ws_gate=(h, m),
                  ws_up=(h, m), ws_down=(m, h), we_gate=(E, h, m),
                  we_up=(E, h, m), we_down=(E, m, h))
    dense = dict(both, w_gate=(h, 12288), w_up=(h, 12288),
                 w_down=(12288, h))
    params = {"embedding": sds((19360, h)), "final_norm": sds((h,)),
              "lm_head": sds((h, 19360)),
              "layers": {k: sds((1,) + s) for k, s in expert.items()},
              "dense_layers": {k: sds((1,) + s) for k, s in dense.items()}}
    cache = (sds((L * NP, ps, 640)), sds((L * NP, ps, 128)))
    table = sds((2 * max_len, 64), jnp.float32)

    def decode(params, tokens, bt, pos, live, cache, cos, sin, record):
        with qm.fused_dispatch(True):
            return lm.decode_step(params, None, tokens, bt, pos, live,
                                  cache, (), (cos, sin), args, record)

    compiled = jax.jit(decode, donate_argnums=(5,)).lower(
        params, sds((b,), jnp.int32), sds((b, P), jnp.int32),
        sds((b,), jnp.int32), sds((b,), jnp.bool_), cache, table, table,
        sds((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "index_decode_scores" in text
    assert "latent_decode_attention" in text
    # 32 rows: the one expert layer's fused pass, no grouped matmul
    assert _fused_expert_calls(text) == (1, 1) and "ragged-dot" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 200e6

    def prefill(params, ids, at, last, bt_row, new_pages, cache, cos, sin,
                record):
        with qm.fused_dispatch(True):
            return lm.prefill_window(params, None, ids, at, last, bt_row,
                                     new_pages, cache, (), (cos, sin), args,
                                     record)

    compiled = jax.jit(prefill, donate_argnums=(6,)).lower(
        params, sds((2048,), jnp.int32), sds((), jnp.int32),
        sds((), jnp.int32), sds((P,), jnp.int32), sds((P,), jnp.int32), cache,
        table, table, sds((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "latent_masked_prefill_attention" in text
    # a 2,048-token window keeps the three grouped matmuls
    assert text.count("ragged-dot-none") >= 3
    assert _fused_expert_calls(text) == (0, 0)
    assert compiled.memory_analysis().temp_size_in_bytes < 3.0e9


def _made_of_shape(text, shape):
    """The program's operations whose result is a float32 array of `shape`
    (its parameters and the elements taken out of a kernel's results are
    no operations)."""
    import re

    whole = re.escape("f32[" + ",".join(map(str, shape)) + "]")
    return [line for line in text.splitlines()
            if re.match(rf"\s*(ROOT )?%\S+ = {whole}", line)
            and " parameter(" not in line
            and " get-tuple-element(" not in line]


# name -> slots, heads, key width, value width of a delta hybrid's linear
# layers (the state is stored `heads_per_row` heads a row)
_DELTA_STEP = {
    "olmo_hybrid_chat_replies_cell": (64, 30, 96, 192),   # two heads a row
    "value_heads_of_128": (16, 32, 128, 128),             # one head a row
    "a_row_in_three_blocks": (4, 24, 256, 256),   # 8 of 24 row-groups fit
    # 64 value heads (32 key heads repeated): k and q fill the tile's 128
    # lanes exactly, one head a row, two blocks of 32 heads a row
    "gigachat35_reasoning_traces_cell": (64, 64, 128, 128),
}


@pytest.mark.parametrize("name", list(_DELTA_STEP))
def test_delta_step_kernel_compiles_for_v5e(one_chip, name):
    """The delta rule's decode step over the stored state, donated: Mosaic
    takes the kernel (a lane gather of a head's k / q column, a dynamic row
    of v and of the output), the state goes in and comes out as ONE buffer,
    and nothing else of the state's size is computed or planned."""
    import re

    from paddle_tpu.kernels import gated_delta_rule as gdr

    r, H, dk, dv = _DELTA_STEP[name]
    p = gdr.heads_per_row(H, dv)
    state = (r, H // p, dk, p * dv)

    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def step(*a):
        with qm.fused_dispatch(True):
            assert gdr.step_is_pallas(state, H)
            return gdr.delta_step(*a)

    if name == "a_row_in_three_blocks":
        assert gdr._step_block(state, H) == 8
    if name == "gigachat35_reasoning_traces_cell":
        assert gdr._step_block(state, H) == 32
    compiled = jax.jit(step, donate_argnums=(5,)).lower(
        sds((r, H, dk)), sds((r, H, dk)), sds((r, H, dv)), sds((r, H)),
        sds((r, H)), sds(state), sds((r,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert re.search(r"delta_rule_step[.\d]* = .*output_to_operand_aliasing="
                     r"\{\{1\}: \(3, \{\}\)\}", text)
    assert not _made_of_shape(text, state)
    assert compiled.memory_analysis().temp_size_in_bytes < math.prod(state)


def test_latent_delta_decode_program_at_the_gigachat_widths(one_chip):
    """GigaChat 3.5's decode program at the cell's widths, slots and pools
    (all five layers: delta + dense, latent + experts, 3 x delta + experts),
    pools and state donated as `FamilyPath` donates them: a kernel
    `delta_rule_step` a delta layer under `pt.delta_rule`, its `[64, 64,
    128, 128]` float32 state aliased in and out and nothing of that size
    copied or computed; the latent decode kernel once; the routed experts
    of every expert layer as the fused pass (`expert_gate_up`,
    `expert_down` under `pt.expert_ffn`); the plan's temporaries far under
    one layer's state."""
    import functools
    import importlib.util
    import json
    import re

    from benchmarks.harness import weights
    from paddle_tpu.models import latent_delta_functional as ldf
    from paddle_tpu.serving import family
    from paddle_tpu.serving.metrics import Metrics

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "family_mla_delta_compile",
        os.path.join(root, "benchmarks", "families", "mla_delta_moe.py"))
    fam = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fam)
    with open(os.path.join(root, "benchmarks", "configs",
                           "gigachat3.5-1chip.json")) as f:
        arch = json.load(f)
    args = fam.serve_args(arch)
    slots, pages, ps, max_len = 64, 8192, 64, 10240

    def described(make):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            jax.eval_shape(make))

    def sds(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = described(lambda: weights.make_params(fam, arch, 1))
    pools = described(lambda: ldf.pools(args, pages, ps, jnp.bfloat16))
    state = described(lambda: ldf.slot_state(args, slots, jnp.bfloat16))
    tables = described(lambda: ldf.tables(args, max_len))
    assert state[0]["S"].shape == (64, 64, 128, 128)
    with qm.fused_dispatch(True):
        compiled = jax.jit(
            functools.partial(family._decode_traced, family=ldf, args=args,
                              metrics=Metrics()),
            donate_argnums=(6, 7)).lower(
            params, sds((5,)), sds((slots + ldf.riders(args)[0],)),
            sds((slots, max_len // ps)), sds((slots,)),
            sds((slots,), jnp.bool_), pools, state, tables,
            sds((), jnp.float32), sds((), jnp.float32), sds(()),
            sds((slots,))).compile()
    text = compiled.as_text()
    kernels = re.findall(
        r"%delta_rule_step[.\d]* = .*output_to_operand_aliasing=\{\{1\}: "
        r"\(3, \{\}\)\}.*op_name=\"[^\"]*pt\.delta_rule/delta_rule_step",
        text)
    assert len(kernels) == 4
    assert not _made_of_shape(text, (64, 64, 128, 128))
    assert sum("pt.latent_attention" in line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line) == 1
    # 64 rows: the four expert layers' fused passes, no grouped matmul
    assert _fused_expert_calls(text) == (4, 4) and "ragged-dot" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 28


def test_delta_decode_program_holds_one_copy_of_the_state(one_chip):
    """Olmo-Hybrid's decode program at the cell's widths and slots (one
    period of its layers: 3 linear + 1 full), pools and state donated as
    `FamilyPath` donates them: a kernel `delta_rule_step` a linear layer
    under `pt.delta_rule`, its state aliased in and out, and no operation
    that copies or computes a `[64, 15, 96, 384]` float32 array (the jnp
    step's two fusions a layer were that)."""
    import functools
    import importlib.util
    import json
    import re

    from benchmarks.harness import weights
    from paddle_tpu.models import gated_delta_functional as gdf
    from paddle_tpu.serving import family
    from paddle_tpu.serving.metrics import Metrics

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "family_gated_delta_compile",
        os.path.join(root, "benchmarks", "families", "gated_delta_hybrid.py"))
    fam = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fam)
    with open(os.path.join(root, "benchmarks", "configs",
                           "olmo-hybrid-7b-1chip.json")) as f:
        arch = json.load(f)
    arch = dict(arch, num_hidden_layers=4, layer_types=arch["layer_types"][:4])
    args = fam.serve_args(arch)
    slots, pages, ps, P = 64, 1920, 64, 96

    def described(make):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            jax.eval_shape(make))

    def sds(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = described(lambda: weights.make_params(fam, arch, 1))
    pools = described(lambda: gdf.pools(args, pages, ps, jnp.bfloat16))
    state = described(lambda: gdf.slot_state(args, slots, jnp.bfloat16))
    assert state[0]["S"].shape == (64, 15, 96, 384)
    with qm.fused_dispatch(True):
        compiled = jax.jit(
            functools.partial(family._decode_traced, family=gdf, args=args,
                              metrics=Metrics()),
            donate_argnums=(6, 7)).lower(
            params, sds((4,)), sds((slots,)), sds((slots, P)), sds((slots,)),
            sds((slots,), jnp.bool_), pools, state, (),
            sds((), jnp.float32), sds((), jnp.float32), sds(()),
            sds((slots,))).compile()
    text = compiled.as_text()
    kernels = re.findall(
        r"%delta_rule_step[.\d]* = .*output_to_operand_aliasing=\{\{1\}: "
        r"\(3, \{\}\)\}.*op_name=\"[^\"]*pt\.delta_rule/delta_rule_step",
        text)
    assert len(kernels) == 3
    assert not _made_of_shape(text, (64, 15, 96, 384))
