"""Tensor-parallel placement for the serving engines.

Serving a model bigger than one chip's HBM means sharding the weights
AND the KV cache over a mesh `mp` axis and running every engine step as
one SPMD program. The placement is the Megatron inference split the
training side already uses (`llama_functional.decoder_layer`,
SNIPPETS [1]/[3] NamedSharding shape):

  - column-parallel: wq/wk/wv/w_gate/w_up sharded on the OUT dim — each
    device owns num_heads/mp query heads, num_kv_heads/mp kv heads and
    intermediate/mp FFN channels;
  - row-parallel: wo/w_down sharded on the IN dim, outputs psum-reduced
    (`generation._tp_reduce`) so the residual stream stays replicated;
  - the PAGED KV POOL `[L, num_pages, nkv, page_size, hd]` shards on the
    nkv axis: a page id means the same thing on every device, so BLOCK
    TABLES STAY REPLICATED — the host-side BlockAllocator (refcounts,
    prefix hash, COW, eviction) is completely sharding-oblivious;
  - embedding / norms / lm_head replicated (tiny next to the layer
    stack; vocab-parallel lm_head would force a cross-device argmax into
    the sampler for marginal bytes).

Weight-only int8 trees shard the same way: a QuantizedWeight's `q`
follows its weight and the per-out-channel `scale` follows the out dim
(replicated for row-parallel shards, whose out dim is unsplit).

Params are placed EAGERLY (`shard_params` -> jax.device_put with
NamedSharding) at engine construction, and the engine's traced step
bodies run under `jax.shard_map` with these specs as
in_specs/out_specs. Everything here is
data (PartitionSpec trees); the collectives live in models/generation.
"""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.models.generation import QuantizedWeight

__all__ = ["tp_validate", "llama_tp_specs", "pool_spec", "shard_params"]

# column-parallel leaves: sharded on the out (last) dim; row-parallel:
# sharded on the in dim with a psum epilogue
_COL = ("wq", "wk", "wv", "w_gate", "w_up")
_ROW = ("wo", "w_down")


def tp_validate(args, degree):
    """The head/FFN divisibility a tp shard needs. Raises ValueError."""
    bad = []
    if args.num_heads % degree:
        bad.append(f"num_heads={args.num_heads}")
    if args.num_kv_heads % degree:
        bad.append(f"num_kv_heads={args.num_kv_heads}")
    if args.intermediate_size % degree:
        bad.append(f"intermediate_size={args.intermediate_size}")
    if bad:
        raise ValueError(
            f"tensor-parallel degree {degree} must divide "
            + ", ".join(bad))


def _leaf_spec(name, leaf, axis):
    """Spec for one stacked [L, ...] layer leaf (or a QuantizedWeight of
    one)."""
    if name in _COL:
        if isinstance(leaf, QuantizedWeight):
            return QuantizedWeight(P(None, None, axis), P(None, axis))
        return P(None, None, axis)
    if name in _ROW:
        if isinstance(leaf, QuantizedWeight):
            # scale is per-OUT-channel; the out dim of a row-parallel
            # shard is unsplit
            return QuantizedWeight(P(None, axis, None), P())
        return P(None, axis, None)
    return QuantizedWeight(P(), P()) if isinstance(leaf, QuantizedWeight) \
        else P()


def llama_tp_specs(params, axis="mp"):
    """PartitionSpec pytree matching a Llama functional param tree (float
    or `quantize_params` int8) for tensor-parallel serving on `axis`."""
    out = {}
    for k, v in params.items():
        if k == "layers":
            out[k] = {n: _leaf_spec(n, leaf, axis) for n, leaf in v.items()}
        elif isinstance(v, QuantizedWeight):
            out[k] = QuantizedWeight(P(), P())
        else:
            out[k] = P()
    return out


def pool_spec(axis="mp"):
    """The paged KV pool `[L, num_pages, nkv, page_size, hd]` shards on
    nkv; stripe caches `[L, S, nkv, max_len, hd]` happen to shard on the
    same axis index."""
    return P(None, None, axis)


def shard_params(params, mesh, axis="mp"):
    """Eagerly place a param tree on `mesh` under the tp specs (the
    sharded arrays are then passed straight into the shard_map'd step
    programs — no resharding on the hot path)."""
    specs = llama_tp_specs(params, axis)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs)
