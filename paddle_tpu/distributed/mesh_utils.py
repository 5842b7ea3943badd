"""Hybrid DCN x ICI device meshes for multi-host / multi-slice training.

The reference scales across hosts by running NCCL over NVLink inside a
node and over IB/ethernet between nodes, with fleet's topology assigning
dp to the slow wires (`fleet/base/topology.py:189`). The TPU equivalent:
a pod SLICE is the fast ICI domain; slices connect over DCN. The standard
layout (scaling-book recipe) is therefore

    dp      -> DCN (gradient all-reduce once a step tolerates latency)
    mp/pp/..-> ICI (per-layer collectives need bandwidth)

`create_hybrid_mesh` builds exactly that: the outermost axis spans
slices, every other axis stays inside a slice, delegating to
`jax.experimental.mesh_utils.create_hybrid_device_mesh` when the runtime
exposes multiple slices and degrading to the plain (single-slice) mesh
builder otherwise — so the same training script runs unchanged from one
chip to a multi-slice pod. Feed the result to `HybridParallelEngine`
(`devices=`) or any `shard_map`/`pjit` program.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

__all__ = ["create_hybrid_mesh", "single_axis_mesh", "slice_count"]


def slice_count(devices=None):
    """Number of DCN-connected slices among `devices` (1 on single-slice
    or CPU platforms, whose devices carry no slice_index)."""
    devices = list(devices if devices is not None else jax.devices())
    return len({getattr(d, "slice_index", 0) for d in devices})


def single_axis_mesh(axis, degree, devices=None):
    """A one-axis Mesh over the first `degree` devices — the
    tensor-parallel serving mesh (`serving.PagedEngine(mesh=...)`), and
    the degenerate case of `create_hybrid_mesh` that doesn't require the
    axes product to cover every device on the host."""
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < degree:
        raise ValueError(
            f"axis {axis!r} needs {degree} devices, got {len(devices)}")
    return create_hybrid_mesh({axis: int(degree)}, devices[:int(degree)])


def create_hybrid_mesh(axes, devices=None, dcn_axis=None):
    """Build a Mesh whose `dcn_axis` (default: the first axis with degree
    > 1) spans slices over DCN and whose remaining axes stay inside a
    slice on ICI.

    axes: dict name -> degree, e.g. {"dp": 2, "pp": 2, "mp": 2}. The
    product must equal the device count. Returns jax.sharding.Mesh with
    the axes in the given order.

    On a single slice (or CPU) this is the ordinary row-major mesh — the
    function is safe to call unconditionally."""
    devices = list(devices if devices is not None else jax.devices())
    names = list(axes)
    degrees = [int(axes[n]) for n in names]
    total = int(np.prod(degrees))
    if total != len(devices):
        raise ValueError(
            f"axes {axes} need {total} devices, got {len(devices)}")
    if dcn_axis is not None and dcn_axis not in axes:
        # validate regardless of slice count: a typo here would otherwise
        # only surface as a KeyError on the real multi-slice pod
        raise ValueError(f"dcn_axis {dcn_axis!r} is not one of {names}")

    n_slices = slice_count(devices)
    if n_slices > 1:
        from jax.experimental import mesh_utils

        dcn_name = dcn_axis or next(
            (n for n, d in zip(names, degrees) if d > 1), names[0])
        if axes[dcn_name] % n_slices != 0:
            raise ValueError(
                f"DCN axis {dcn_name!r} degree {axes[dcn_name]} must be "
                f"divisible by the slice count {n_slices}")
        # the dcn axis splits as (n_slices over DCN) x (remainder on ICI);
        # every other axis lives wholly inside a slice
        ici_parallelism = [axes[n] // n_slices if n == dcn_name else axes[n]
                           for n in names]
        dcn_parallelism = [n_slices if n == dcn_name else 1 for n in names]
        dev_array = mesh_utils.create_hybrid_device_mesh(
            ici_parallelism, dcn_parallelism, devices=devices)
        return Mesh(dev_array, names)

    dev_array = np.asarray(devices).reshape(degrees)
    return Mesh(dev_array, names)
