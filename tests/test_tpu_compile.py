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

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from paddle_tpu.kernels import quantized_matmul as qm

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


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
