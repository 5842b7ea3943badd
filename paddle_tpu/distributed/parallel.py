"""Parallel environment bootstrap + DataParallel.

Reference: `python/paddle/distributed/parallel.py:978` (init_parallel_env:
read PADDLE_TRAINER_* env -> TCPStore -> ProcessGroupNCCL) and `:219`
(DataParallel: broadcast params + EagerReducer bucketed allreduce overlap,
`paddle/fluid/distributed/collective/reducer.cc:1089`).

TPU-native design: the runtime is single-controller SPMD. One Python process
drives every chip; `jax.distributed.initialize` extends the same model to
multi-host (each host holds its local chips, XLA runs collectives over
ICI/DCN). Consequences:

- "rank" for API parity = `jax.process_index()`; the *device* mesh carries
  the parallel axes. world_size = total chips.
- DataParallel needs no reducer: inputs are sharded over the 'dp' mesh axis
  (batch dim), parameters are replicated; grads of replicated params are
  globally correct by construction — under jit, XLA emits exactly the fused
  all-reduce the reference's EagerReducer schedules by hand, overlapped by
  the scheduler. The bucket-size knob therefore disappears.
"""

from __future__ import annotations

import os

import numpy as np

import jax

from paddle_tpu.distributed import collective as _collective
from paddle_tpu.distributed.api import shard_tensor
from paddle_tpu.distributed.placement import Replicate, Shard
from paddle_tpu.distributed.process_mesh import ProcessMesh

__all__ = ["init_parallel_env", "get_rank", "get_world_size", "ParallelEnv",
           "DataParallel", "is_initialized"]

_env = None


class ParallelEnv:
    """Reference: parallel.py ParallelEnv reading PADDLE_TRAINER_* env."""

    def __init__(self):
        self.device_type = jax.default_backend()
        self.rank = jax.process_index()
        self.world_size = jax.device_count()
        self.local_rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
        self.nranks = self.world_size
        self.dev_id = 0
        self.trainer_endpoints = os.environ.get(
            "PADDLE_TRAINER_ENDPOINTS", "").split(",")
        self.current_endpoint = os.environ.get("PADDLE_CURRENT_ENDPOINT", "")

    @property
    def device_id(self):
        return self.dev_id


def init_parallel_env():
    """Initialize the distributed environment (reference parallel.py:978).

    Multi-host: if the launch CLI set PADDLE_MASTER + PADDLE_TRAINERS_NUM and
    more than one process is requested, bring up the JAX coordination service
    (the TCPStore equivalent — reference parallel.py:1134) before building
    the global group.
    """
    global _env
    if _env is not None:
        return _env

    master = os.environ.get("PADDLE_MASTER", "")
    nprocs = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    proc_id = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    if master and nprocs > 1 and not jax.distributed.is_initialized():
        # native TCPStore rendezvous (reference parallel.py:1134): rank 0
        # hosts the store; everyone barriers so jax.distributed.initialize
        # only starts once all hosts are up (clearer failures than a
        # coordination-service connect timeout)
        global _store
        try:
            from paddle_tpu.core import native

            if native.available():
                host, port = master.rsplit(":", 1)
                _store = native.TCPStore(host, int(port) + 1,
                                         is_master=proc_id == 0,
                                         world_size=nprocs)
                _store.barrier("init_parallel_env", proc_id, nprocs,
                               timeout=300.0)
        except Exception:
            _store = None  # fall through to the coordination service alone
        if _store is not None:
            # the rendezvous store becomes the default store (reference
            # parallel.py:1134) and feeds the heartbeat failure detector
            # (reference CommTaskManager + launch watcher)
            _collective._set_default_store(_store)
            from paddle_tpu.distributed import comm_monitor

            comm_monitor.start_comm_monitor(_store, proc_id, nprocs)
        jax.distributed.initialize(
            coordinator_address=master, num_processes=nprocs,
            process_id=proc_id)

    _env = ParallelEnv()
    world = list(range(jax.device_count()))
    mesh = ProcessMesh(np.asarray(world), ["world"])
    g = _collective.Group(_env.rank, 0, world, name="_default_pg0",
                          axis_name="world", mesh=mesh)
    _collective._register_global_group(g)
    return _env


def is_initialized():
    return _collective.is_initialized()


def get_rank(group=None):
    if group is not None:
        return group.rank
    return jax.process_index()


def get_world_size(group=None):
    if group is not None:
        return group.nranks
    return jax.device_count()


class DataParallel:
    """Reference: parallel.py:219 + reducer.cc.

    TPU-native: wraps the layer, shards the input batch over a 1-D 'dp' mesh;
    parameters stay replicated. No reducer: XLA inserts (and overlaps) the
    grad all-reduce when the train step is jitted; in eager mode the sharded
    forward/backward is globally correct by construction.
    """

    def __init__(self, layers, strategy=None, comm_buffer_size=25,
                 last_comm_buffer_size=1, find_unused_parameters=False,
                 group=None, mesh=None):
        init_parallel_env()
        self._layers = layers
        if mesh is None:
            n = jax.device_count()
            mesh = ProcessMesh(np.arange(n), ["dp"])
        self._mesh = mesh
        # replicate parameters onto the dp mesh (reference broadcasts from
        # rank 0, parallel.py sync_params_buffers)
        for p in layers.parameters():
            p._data = shard_tensor(p, mesh, [Replicate()])._data

    def _shard_input(self, x):
        from paddle_tpu.core.tensor import Tensor

        if isinstance(x, Tensor) and x.ndim >= 1 and \
                x.shape[0] % self._mesh.shape[0] == 0:
            return shard_tensor(x, self._mesh, [Shard(0)],
                                stop_gradient=x.stop_gradient)
        return x

    def forward(self, *inputs, **kwargs):
        inputs = tuple(self._shard_input(x) for x in inputs)
        kwargs = {k: self._shard_input(v) for k, v in kwargs.items()}
        return self._layers(*inputs, **kwargs)

    __call__ = forward

    def scale_loss(self, loss):
        return loss  # grads are exact means already

    def no_sync(self):
        import contextlib

        return contextlib.nullcontext()

    # delegate the Layer surface
    def __getattr__(self, name):
        return getattr(self.__dict__["_layers"], name)

    def parameters(self, *a, **k):
        return self._layers.parameters(*a, **k)

    def named_parameters(self, *a, **k):
        return self._layers.named_parameters(*a, **k)

    def state_dict(self, *a, **k):
        return self._layers.state_dict(*a, **k)

    def set_state_dict(self, *a, **k):
        return self._layers.set_state_dict(*a, **k)

    def train(self):
        self._layers.train()

    def eval(self):
        self._layers.eval()
