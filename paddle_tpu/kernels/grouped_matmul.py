"""The held experts' products, in the two forms a step program takes.

**Many rows (a prefill window): a grouped matmul.** Rows sorted by expert,
one weight a group, no capacity and no dropped row.
`grouped_matmul(x, w, group_sizes, first_group)`: x [m, k] whose first
`group_sizes[0]` rows belong to group `first_group`, the next
`group_sizes[1]` to the group after it, ..; w [groups, k, n] may hold more
groups than `group_sizes` names (a stack of layers' experts, of which one
layer's are used: a slice of the stack handed to a custom call would be a
copy of the layer's experts every step, so the stack goes in whole and the
other groups are empty). Rows past `sum(group_sizes)` belong to no group and
their output is unspecified (the caller masks them). On TPU this is `jax.lax.ragged_dot`,
which XLA lowers to its own grouped-matmul custom call (a tile schedule from
the group offsets: an empty group's weights are never read, and the
operations follow the rows, not rows x groups); elsewhere, and as the parity
oracle, `grouped_matmul_reference` (every group's product, masked).

**Few rows (a decode step): one pass over the hit experts' weights.**
`fused_expert_ffn`: EVERY row against each expert some row picked, the
routing weight (0 where the row did not pick it) folded into the row's
activation, the down products summed over the experts in float32. Two Pallas
calls (`expert_gate_up`, `expert_down`) whose grids walk a scalar-prefetched
list of the hit experts, compacted to the front, as groups of the whole
stack: each hit expert's three matrices are read once, in contiguous blocks
of rows, and an expert no row picked is never read. No sort, no gather of a
row a pick and none back. `n` rows against every hit expert are `n x` an
expert's operations where the grouped matmul does a pick's: that is free
while the pass is bound by the weights' bytes, below the chip's ridge of
operations over bytes (a v5e: 197 TFLOP/s over 819 GB/s = 240 rows, and the
MXU takes a weight tile no faster for fewer rows than its 128), so the form
is taken from the STATIC row count: `n <= FUSED_ROWS` (128) rows take the
pass (`fused_tiles`: where the kernels are enabled and whole tiles divide the
widths), anything larger the grouped matmul.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.kernels import quantized_matmul as qm

__all__ = ["grouped_matmul", "grouped_matmul_reference", "FUSED_ROWS",
           "fused_tiles", "fused_expert_ffn"]


def grouped_matmul_reference(x, w, group_sizes):
    """The same product written out: row r times the weight of the group
    whose run holds r; zero for a row in no group."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    r = jnp.arange(x.shape[0])[:, None]
    member = (r >= starts[None, :]) & (r < ends[None, :])        # [m, groups]
    out = jnp.einsum("mk,gkn->gmn", x, w,
                     preferred_element_type=jnp.float32)
    return jnp.sum(jnp.where(member.T[:, :, None], out, 0.0),
                   axis=0).astype(x.dtype)


def grouped_matmul(x, w, group_sizes, first_group=0):
    group_sizes = group_sizes.astype(jnp.int32)
    if qm.fused_enabled():       # the TPU, or forced by `fused_dispatch`
        sizes = jax.lax.dynamic_update_slice_in_dim(
            jnp.zeros(w.shape[0], jnp.int32), group_sizes, first_group, 0)
        return jax.lax.ragged_dot(x, w, sizes)
    return grouped_matmul_reference(
        x, jax.lax.dynamic_slice_in_dim(w, first_group, group_sizes.shape[0]),
        group_sizes)


# ---------------------------------------------------------------------------
# few rows: one pass over the hit experts' weights
# ---------------------------------------------------------------------------

FUSED_ROWS = 128    # rows a program may have and still take the fused pass
_LANES = 128


def _largest_block(size, fits):
    """The largest divisor of `size` in whole lane tiles that `fits`, or 0."""
    return max((t for t in range(_LANES, size + 1, _LANES)
                if size % t == 0 and fits(t)), default=0)


def fused_tiles(n, h, m, itemsize):
    """(th, tm): the rows of `h` a grid step of `expert_gate_up` takes of the
    gate and up matrices and the rows of `m` a step of `expert_down` takes
    of the down matrix: the largest blocks in whole lane tiles whose step,
    double-buffered, fits the kernels' VMEM budget beside what the step
    keeps. None where the fused pass does not apply: the kernels are off
    (`qm.fused_enabled`), more than FUSED_ROWS rows, or widths that whole
    tiles do not divide."""
    if not qm.fused_enabled() or n > FUSED_ROWS or n % 8:
        return None
    budget = qm._VMEM_BUDGET_BYTES
    # gate and up blocks and the rows' slice, twice; the two float32
    # pre-activations and the activation's block, twice
    th = _largest_block(h, lambda t: (
        2 * (2 * t * m + n * t) * itemsize + n * m * (8 + 2 * itemsize)
        <= budget))
    # the down block and the activation's slice, twice; the float32 sum
    tm = _largest_block(m, lambda t: (
        2 * (t * h + n * t) * itemsize + 2 * n * h * 4 <= budget))
    return (th, tm) if th and tm else None


def _gate_up_kernel(ids_ref, count_ref, x_ref, c_ref, wg_ref, wu_ref, act_ref,
                    gate_acc, up_acc, *, activation):
    """Grid step (place e in the list of hit experts, block j of `h`): x [n,
    th]; c [n, 1] the rows' routing weights for this expert; wg, wu [th, m];
    act [n, m], written at the expert's last block."""
    e, j = pl.program_id(0), pl.program_id(1)

    @pl.when(e < count_ref[0])
    def _():
        x = x_ref[...]
        gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)

        @pl.when(j == 0)
        def _():
            gate_acc[...] = gate
            up_acc[...] = up

        @pl.when(j > 0)
        def _():
            gate_acc[...] += gate
            up_acc[...] += up

        @pl.when(j == pl.num_programs(1) - 1)
        def _():
            c = c_ref[...]
            act = activation(gate_acc[...], up_acc[...]) * c
            act_ref[...] = jnp.where(c != 0, act, 0.0).astype(act_ref.dtype)


def _down_kernel(ids_ref, count_ref, act_ref, wd_ref, out_ref):
    """Grid step (place e in the list of hit experts, block k of `m`): act
    [n, tm]; wd [tm, h]; out [n, h] float32, the one block of every step."""
    e, k = pl.program_id(0), pl.program_id(1)

    @pl.when((e == 0) & (k == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(e < count_ref[0])
    def _():
        out_ref[...] += jnp.dot(act_ref[...], wd_ref[...],
                                preferred_element_type=jnp.float32)


def fused_expert_ffn(x, c, hit, w_gate, w_up, w_down, first, activation):
    """sum over the hit experts e of (activation(x Wg_e, x Wu_e) * c[:, e])
    Wd_e -> [n, h] float32. x [n, h]; c [n, E] float32, row r's routing
    weight for held expert e, 0 where it did not pick it; hit [E] bool, the
    experts to read (a superset of c's non-zero columns); w_gate, w_up
    [groups, h, m], w_down [groups, m, h]: the WHOLE stack, of which this
    layer's experts are the E groups from `first` on; `activation(gate, up)`
    on the float32 pre-activations. The operands are the weights' type, every
    sum float32; the activation is rounded once, with its weight. The caller
    has asked `fused_tiles`."""
    n, h = x.shape
    E, m = c.shape[1], w_gate.shape[2]
    th, tm = fused_tiles(n, h, m, w_gate.dtype.itemsize)
    # the hit experts in order at the front of the list; a place past their
    # count repeats the last one's
    place = jnp.arange(E, dtype=jnp.int32)
    count = jnp.sum(hit.astype(jnp.int32))
    at = jnp.cumsum(hit.astype(jnp.int32)) - 1
    order = jnp.sum(jnp.where(hit[None] & (at[None] == place[:, None]),
                              place[None], 0), axis=1)
    order = order[jnp.minimum(place, jnp.maximum(count - 1, 0))]

    def walk(blocks, spec):
        """A grid step's (place, block of the blocked axis) -> the block
        `spec` makes of them; past the count the last hit expert's LAST
        block again, so nothing is copied for such a step."""
        def index(e, j, ids, count):
            past = e >= count[0]
            return spec(ids, jnp.where(past, jnp.maximum(count[0] - 1, 0), e),
                        jnp.where(past, blocks - 1, j))
        return index

    def call(kernel, name, grid, in_specs, out_spec, out_shape, scratch=()):
        return pl.pallas_call(
            kernel, out_shape=out_shape, name=name,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=grid,
                in_specs=[pl.BlockSpec(b, walk(grid[1], f))
                          for b, f in in_specs],
                out_specs=pl.BlockSpec(out_spec[0],
                                       walk(grid[1], out_spec[1])),
                scratch_shapes=scratch),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=qm._mode()[1])

    prefetch = first + order, count[None]
    act = call(
        functools.partial(_gate_up_kernel, activation=activation),
        "expert_gate_up", (E, h // th),
        [((n, th), lambda ids, e, j: (0, j)),
         ((None, n, 1), lambda ids, e, j: (e, 0, 0)),
         ((None, th, m), lambda ids, e, j: (ids[e], j, 0)),
         ((None, th, m), lambda ids, e, j: (ids[e], j, 0))],
        ((None, n, m), lambda ids, e, j: (e, 0, 0)),
        jax.ShapeDtypeStruct((E, n, m), w_down.dtype),
        [pltpu.VMEM((n, m), jnp.float32)] * 2,
    )(*prefetch, x.astype(w_gate.dtype), c.T[order][:, :, None], w_gate, w_up)
    return call(
        _down_kernel, "expert_down", (E, m // tm),
        [((None, n, tm), lambda ids, e, k: (e, 0, k)),
         ((None, tm, h), lambda ids, e, k: (ids[e], k, 0))],
        ((n, h), lambda ids, e, k: (0, 0)),
        jax.ShapeDtypeStruct((n, h), jnp.float32),
    )(*prefetch, act, w_down)
