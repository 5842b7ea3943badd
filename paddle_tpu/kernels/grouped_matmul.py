"""Grouped matmul over the experts a chip holds: rows sorted by expert, one
weight a group, no capacity and no dropped row.

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
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["grouped_matmul", "grouped_matmul_reference"]


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
    from paddle_tpu.kernels import quantized_matmul as qm

    group_sizes = group_sizes.astype(jnp.int32)
    if qm.fused_enabled():       # the TPU, or forced by `fused_dispatch`
        sizes = jax.lax.dynamic_update_slice_in_dim(
            jnp.zeros(w.shape[0], jnp.int32), group_sizes, first_group, 0)
        return jax.lax.ragged_dot(x, w, sizes)
    return grouped_matmul_reference(
        x, jax.lax.dynamic_slice_in_dim(w, first_group, group_sizes.shape[0]),
        group_sizes)
