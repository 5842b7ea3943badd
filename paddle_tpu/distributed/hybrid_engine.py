"""Hybrid-parallel compiled training engine: dp x pp x mp (+sp) in ONE jit.

This is the TPU-native equivalent of the reference's fleet hybrid-parallel
runtime (`fleet/meta_parallel/pipeline_parallel.py:684` forward_backward_pipeline,
`fleet/base/topology.py:189` HybridCommunicateGroup, TP layers
`fleet/layers/mpu/mp_layers.py`, ZeRO `sharding/group_sharded_stage2.py`):
instead of Python schedulers issuing NCCL ops per micro-step, the whole
train step — pipeline schedule, TP collectives, DP grad sync, optimizer —
is a single `shard_map`-partitioned XLA program over a
`jax.sharding.Mesh(['dp','pp','mp'])`:

  - TP:  Megatron column/row sharding with explicit `psum` over 'mp'
         (the collectives the reference hand-writes in mp_ops.py:259).
  - SP:  sequence dim sharded over 'mp' between blocks; `all_gather` /
         `psum_scatter` at block boundaries (sequence_parallel_utils.py:85-147).
  - PP:  layer stack sharded over 'pp'; GPipe schedule as a `lax.scan` over
         micro-steps with `ppermute` moving activations stage->stage (the
         reference's batched isend/irecv, p2p_communication.py:573). XLA
         overlaps the ppermute with the next micro-batch's compute.
  - DP:  batch sharded over 'dp'; gradient `pmean` over 'dp' (the
         reference's EagerReducer fused allreduce, reducer.cc:1089).
  - ZeRO-1/2: AdamW moments sharded over 'dp' via NamedSharding on the
         optimizer update (optimizer-state partition of
         group_sharded_optimizer_stage2.py:53); XLA inserts the
         reduce-scatter/all-gather pair.
  - ZeRO-3 (zero_stage=3): layer params live dp-SHARDED; each scan step
         all-gathers just its layer's weights right before use (the
         stage-3 pre-forward hook, group_sharded_stage3.py:85,560) and the
         gather's AD transpose reduce-scatters grads to their owner
         shards — no hand-written reducer, parity-tested against
         single-device autodiff.
"""

from __future__ import annotations

import functools
import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.models import llama_functional as lf
from paddle_tpu.observability.spans import span

__all__ = ["HybridParallelEngine"]


# --------------------------------------------------------------------------
# AdamW (functional, pytree)
# --------------------------------------------------------------------------


from paddle_tpu.core.numerics import \
    stochastic_round_bf16 as _stochastic_round_bf16


def _factored_leaf(shape):
    return len(shape) >= 2


def adamw_init(params, moments="f32", master_weights=False):
    """AdamW state with selectable moment storage (the memory knob that
    decides how much HBM is left for activations — reference keeps f32
    moments unconditionally, `python/paddle/optimizer/adamw.py` moment1/2
    accumulators):

      - 'f32':      full-precision m and v (2 x 4 bytes/param).
      - 'bf16':     m and v stored bf16, stochastic-rounding write-back
                    (2 x 2 bytes/param).
      - 'factored': m stored bf16; v replaced by Adafactor-style f32
                    row/col EMAs of g^2 over the last two axes
                    (~2 bytes/param total). Rank<2 leaves keep full f32 v.

    master_weights: keep an f32 master copy of each param in the state and
    apply updates to IT (bf16 params are then a pure down-cast view) —
    the mixed-precision recipe when per-step updates underflow bf16's
    8 mantissa bits. Costs 4 bytes/param; off by default to preserve the
    bench configs' HBM headroom.
    """
    if moments not in ("f32", "bf16", "factored"):
        raise ValueError(f"moments must be f32|bf16|factored, got {moments!r}")
    mdt = jnp.float32 if moments == "f32" else jnp.bfloat16

    def mk_v(p):
        if moments == "factored" and _factored_leaf(p.shape):
            return {"r": jnp.zeros(p.shape[:-1], jnp.float32),
                    "c": jnp.zeros(p.shape[:-2] + p.shape[-1:], jnp.float32)}
        return jnp.zeros(p.shape, jnp.float32 if moments != "bf16"
                         else jnp.bfloat16)

    state = {"m": jax.tree.map(lambda p: jnp.zeros(p.shape, mdt), params),
             "v": jax.tree.map(mk_v, params),
             "step": jnp.zeros((), jnp.int32)}
    if master_weights:
        state["master"] = jax.tree.map(
            lambda p: p.astype(jnp.float32), params)
    return state


@jax.named_scope("pt.adamw")
def adamw_update(params, grads, state, lr=3e-4, beta1=0.9, beta2=0.999,
                 eps=1e-8, weight_decay=0.01, moments="f32"):
    step = state["step"] + 1
    b1t = 1.0 - beta1 ** step.astype(jnp.float32)
    b2t = 1.0 - beta2 ** step.astype(jnp.float32)
    # all math runs in f32; `moments` only selects the *storage* format
    # written back each step. Stochastic rounding keys are derived from the
    # step so the noise sequence is reproducible and state stays a pure
    # function of (params, grads, step).
    base_key = (jax.random.key(step.astype(jnp.uint32))
                if moments != "f32" else None)

    def store(x32, leaf_idx, slot):
        if moments == "f32":
            return x32
        return _stochastic_round_bf16(
            jax.random.fold_in(base_key, 2 * leaf_idx + slot), x32)

    def upd(i, p, g, m, v, master):
        g32 = g.astype(jnp.float32)
        m32 = beta1 * m.astype(jnp.float32) + (1 - beta1) * g32
        if isinstance(v, dict):  # factored second moment
            g2 = g32 * g32
            r = beta2 * v["r"] + (1 - beta2) * g2.mean(axis=-1)
            c = beta2 * v["c"] + (1 - beta2) * g2.mean(axis=-2)
            # v_ij ~= r_i * c_j / mean(r): exact when g^2 is rank-1
            denom = jnp.maximum(r.mean(axis=-1, keepdims=True), 1e-30)
            vhat = (r / denom)[..., :, None] * c[..., None, :] / b2t
            new_v = {"r": r, "c": c}
        else:
            v32 = beta2 * v.astype(jnp.float32) + (1 - beta2) * (g32 * g32)
            vhat = v32 / b2t
            # factored mode keeps full-f32 v on its rank<2 leaves (tiny);
            # only the 'bf16' mode rounds the second moment down
            new_v = store(v32, i, 1) if moments == "bf16" else v32
        mhat = m32 / b1t
        # master weights: the f32 copy in the state is the source of truth;
        # the (possibly bf16) param is just its down-cast
        p32 = master if master is not None else p.astype(jnp.float32)
        p32 = p32 - lr * (mhat / (jnp.sqrt(vhat) + eps) + weight_decay * p32)
        return p32.astype(p.dtype), store(m32, i, 0), new_v, p32

    flat_p, tdef = jax.tree.flatten(params)
    flat_g = tdef.flatten_up_to(grads)
    flat_m = tdef.flatten_up_to(state["m"])
    flat_v = tdef.flatten_up_to(state["v"])
    flat_mw = (tdef.flatten_up_to(state["master"])
               if "master" in state else [None] * len(flat_p))
    out = [upd(i, p, g, m, v, mw)
           for i, (p, g, m, v, mw)
           in enumerate(zip(flat_p, flat_g, flat_m, flat_v, flat_mw))]
    new_p = tdef.unflatten([o[0] for o in out])
    new_m = tdef.unflatten([o[1] for o in out])
    new_v = tdef.unflatten([o[2] for o in out])
    new_state = {"m": new_m, "v": new_v, "step": step}
    if "master" in state:
        new_state["master"] = tdef.unflatten([o[3] for o in out])
    return new_p, new_state


# --------------------------------------------------------------------------
# engine
# --------------------------------------------------------------------------


class HybridParallelEngine:
    """Compile-and-run Llama training with dp/pp/mp/sp over a device mesh.

    Mirrors the role of the reference auto-parallel `Engine`
    (`distributed/auto_parallel/static/engine.py:99`) + fleet's dygraph
    hybrid wrappers, but produces one compiled XLA step.
    """

    def __init__(self, config, dp=1, pp=1, mp=1, micro_batches=None, sp=False,
                 devices=None, dtype=jnp.float32, remat=True, lr=3e-4,
                 schedule="gpipe", num_virtual_stages=2, zero_stage=1,
                 loss_chunk=None, moments="f32", cp=1, cp_mode="ring",
                 unroll=None, monitor=None, master_weights=False,
                 save_every=None, checkpoint=None, resume=False,
                 keep_last_k=3):
        from paddle_tpu.models.llama import LlamaConfig  # noqa: F401 (type)

        self.config = config
        self.args = lf.LlamaArgs.from_config(config)
        self.dp, self.pp, self.mp = dp, pp, mp
        self.sp = sp and mp > 1
        # CP: context parallelism as a 4th mesh axis — sequences arrive
        # seq-sharded over 'cp'; attention runs ring_attention (kv ring)
        # or ulysses (all_to_all) per layer (SURVEY §5 long context; the
        # reference snapshot has neither)
        if cp_mode not in ("ring", "ulysses"):
            raise ValueError("cp_mode must be 'ring' or 'ulysses'")
        self.cp, self.cp_mode = cp, cp_mode
        self._cp_axis = "cp" if cp > 1 else None
        # cp-derived pieces shared by all four schedule paths
        self._cp_vary = ("cp",) if cp > 1 else ()
        self._loss_axes = ("dp", "cp") if cp > 1 else "dp"
        self._data_spec = P(None, "dp", "cp" if cp > 1 else None)
        if cp > 1 and cp_mode == "ulysses":
            local_heads = self.args.num_heads // max(mp, 1)
            local_kv = max(1, self.args.num_kv_heads // max(mp, 1))
            if local_heads % cp != 0 or local_kv % cp != 0:
                raise ValueError(
                    f"cp_mode='ulysses' needs local q heads ({local_heads}) "
                    f"AND kv heads ({local_kv}) divisible by cp={cp}; use "
                    "cp_mode='ring'")
        self.micro_batches = micro_batches or max(pp, 1)
        self.dtype = dtype
        self.remat = remat
        # unroll the layer loop whenever layers are NOT sharded (pp == 1):
        # lax.scan must stack every layer's remat residuals into [L, ...]
        # buffers with dynamic-update-slice and re-slice them in backward —
        # profiled at ~17% of the h2048 train step on TPU v5e. Applies to
        # the degenerate mesh AND dp/mp/cp-parallel meshes; the pipeline
        # paths keep the scan (pp shards its leading dim). ACTIVE ZeRO-3
        # (zero_stage=3 with dp>1) keeps the scan too by default: its
        # per-layer all-gather dominates the DUS cost and the scan form
        # keeps the gathered layer's liveness tight.
        if unroll is None:
            self.unroll = pp == 1 and (zero_stage < 3 or dp == 1)
        else:
            if unroll and pp > 1:
                raise ValueError(
                    "unroll=True requires pp == 1: pipeline parallelism "
                    "shards the layer stack's leading dim, which only the "
                    "scan form supports")
            self.unroll = unroll
        self.lr = lr
        # fused lm_head + CE on every path (lf.fused_linear_cross_entropy, a
        # custom_vjp): the [b, s, vocab] logits never materialize; the live
        # logits block is bounded at b * loss_chunk * vocab_local elements
        self.loss_chunk = loss_chunk
        # f32 master copies of the params inside the opt state (see
        # adamw_init); off by default — costs 4 bytes/param of HBM
        self.master_weights = bool(master_weights)
        # moment storage: 'f32' | 'bf16' (stochastic-rounded) | 'factored'
        # (Adafactor-style second moment). On a 16G chip the f32 moments of
        # a ~1B model (7.5GB) are what force remat in the first place.
        if moments not in ("f32", "bf16", "factored"):
            raise ValueError("moments must be 'f32', 'bf16' or 'factored'")
        self.moments = moments
        # ZeRO: stage 1/2 = dp-sharded AdamW moments (in ONE compiled step
        # the stage-1/2 distinction collapses — XLA frees grads inside the
        # program); stage 3 additionally shards the LAYER params over 'dp':
        # each scan step all-gathers its layer pre-use and the AD transpose
        # reduce-scatters the grads (reference group_sharded_stage3.py:85;
        # embedding/head/final_norm stay moment-sharded only)
        if zero_stage not in (1, 2, 3):
            raise ValueError("zero_stage must be 1, 2, or 3")
        self.zero_stage = zero_stage
        self._zero3 = zero_stage >= 3 and dp > 1
        self._zero_axis = "dp" if self._zero3 else None
        # zero_stage=3 divisibility is handled per-leaf in
        # _build_param_specs: leaves whose first param axis doesn't divide
        # dp (x mp) stay moment-sharded only, with a warning — a graceful
        # fallback instead of r2's hard rejection (VERDICT item 10)
        if schedule not in ("gpipe", "1f1b", "interleave", "zb", "auto"):
            raise ValueError(f"unknown pipeline schedule {schedule!r} "
                             "(gpipe | 1f1b | interleave | zb | auto)")
        if schedule == "auto":
            # cost model (validated by the dryrun's repeated-median sweep):
            # both run M+2S-1 ticks; 1f1b's tick is F + full backward (~3F),
            # zb's is F + activation-grad (~2F) plus a deferred weight-grad
            # phase ~M unit-backwards => zb wins iff M < 2S-1 — the
            # fill/drain-dominated deep-pipeline regime zero-bubble targets
            # (reference pipeline_zero_bubble.py:62 schedules it
            # unconditionally; we pick by regime)
            M = self.micro_batches
            schedule = "zb" if pp > 1 and M < 2 * pp - 1 else "1f1b"
        self.schedule = schedule if pp > 1 else "gpipe"
        self.num_virtual_stages = num_virtual_stages
        if self.schedule == "interleave":
            V = num_virtual_stages
            if V < 2:
                raise ValueError("interleave needs num_virtual_stages >= 2")
            if config.num_hidden_layers % (pp * V) != 0:
                raise ValueError("num_hidden_layers must divide pp * "
                                 "num_virtual_stages")
            # M > pp runs as ceil(M/pp) groups of pp micro-batches, each
            # riding the ring V times (the reference's large-M interleave,
            # pipeline_parallel.py:1308) — no M <= pp restriction.

        if config.num_hidden_layers % max(pp, 1) != 0:
            raise ValueError("num_hidden_layers must divide pp")
        if config.num_attention_heads % max(mp, 1) != 0:
            raise ValueError("num_attention_heads must divide mp")

        devices = devices if devices is not None else jax.devices()
        n = dp * pp * mp * cp
        if len(devices) < n:
            raise ValueError(f"need {n} devices, have {len(devices)}")
        dev_array = np.asarray(devices[:n]).reshape(dp, pp, mp, cp)
        self.mesh = Mesh(dev_array, ("dp", "pp", "mp", "cp"))

        self._zero_skip = frozenset()  # zero-3 leaves left unsharded
        self._param_specs = self._build_param_specs()
        self._train_step = None
        self._opt_shardings = None
        self._param_shardings = None

        # per-step telemetry into the shared registry. The default monitor
        # uses nan_action='none': train_batch stays sync-free (no device->
        # host loss readback in the step path — the benchmark times through
        # here), so it records the step counter and `train/dispatch_s` only;
        # pass a TrainingMonitor with nan_action='raise'/'warn' for a
        # loss-checked (synced) loop with step time, tokens/sec and MFU.
        if monitor is None:
            from paddle_tpu.observability import TrainingMonitor

            monitor = TrainingMonitor(source="hybrid_engine",
                                      nan_action="none")
        self.monitor = monitor
        if monitor.peak_flops == "auto":
            # train_batch reports GLOBAL tokens/sec across the whole mesh,
            # so the MFU denominator must be the whole mesh's peak — a
            # single-chip peak would inflate MFU by the device count
            from paddle_tpu.observability.hardware import detect_peak_flops

            per_chip = detect_peak_flops()
            monitor.peak_flops = (per_chip * self.mesh.devices.size
                                  if per_chip else None)
        # auto-fill MFU flops only when the monitor didn't come with a
        # user-supplied flops_per_token (a custom model's FLOPs may not
        # follow the llama formula)
        self._fpt_auto = monitor.flops_per_token is None
        self._fpt_seq = None  # seq len the monitor's flops_per_token is for

        # -- fault tolerance: periodic atomic checkpoints + resume ----------
        # save_every=N commits {"params", "opt"} every N completed steps
        # through CheckpointManager (async single-process; the manager
        # degrades to sync under multi-process). `checkpoint` is a root dir
        # or a CheckpointManager; with neither, the manager falls back to
        # $PADDLE_CHECKPOINT_DIR — which the elastic supervisor exports, so
        # a supervisor-restarted trainer with resume=True continues from
        # the newest COMMITTED step via maybe_resume().
        self._save_every = int(save_every) if save_every else None
        self._resume = bool(resume)
        self._global_step = 0  # completed train_batch calls (resume-aware)
        self.checkpoint_manager = None
        if (self._save_every or resume or checkpoint is not None):
            from paddle_tpu.distributed.checkpoint import CheckpointManager

            if isinstance(checkpoint, CheckpointManager):
                self.checkpoint_manager = checkpoint
            else:
                self.checkpoint_manager = CheckpointManager(
                    root=checkpoint, keep_last_k=keep_last_k)

    # -- sharding specs -----------------------------------------------------
    def _build_param_specs(self):
        """PartitionSpec per leaf. layers.* have leading 'pp' (stacked stage
        dim); TP dims over 'mp'."""
        layer_specs = {
            "wq": P("pp", None, "mp"),
            "wk": P("pp", None, "mp"),
            "wv": P("pp", None, "mp"),
            "wo": P("pp", "mp", None),
            "w_gate": P("pp", None, "mp"),
            "w_up": P("pp", None, "mp"),
            "w_down": P("pp", "mp", None),
            "ln1": P("pp", None),
            "ln2": P("pp", None),
        }
        if self.mp == 1:
            layer_specs = {k: P("pp", *([None] * (len(v) - 1)))
                           for k, v in layer_specs.items()}
        if self._zero3:
            # stage 3: shard the first PARAM axis (post-stack axis 0) over
            # 'dp' — composed with 'mp' when that axis is already
            # tensor-parallel ('mp' outer, 'dp' inner, so the tiled dp
            # all_gather reassembles each mp block contiguously). Leaves
            # whose axis doesn't divide stay moment-sharded only (graceful
            # fallback for real model dims on non-power-of-two meshes).
            cfg = self.config
            hd = cfg.hidden_size // cfg.num_attention_heads
            axis0 = {
                "wq": cfg.hidden_size, "wk": cfg.hidden_size,
                "wv": cfg.hidden_size,
                "wo": cfg.num_attention_heads * hd,
                "w_gate": cfg.hidden_size, "w_up": cfg.hidden_size,
                "w_down": cfg.intermediate_size,
                "ln1": cfg.hidden_size, "ln2": cfg.hidden_size,
            }

            skipped = []

            def z3(name, spec):
                parts = list(spec)
                need = self.dp * (self.mp if parts[1] == "mp" else 1)
                if axis0[name] % need != 0:
                    skipped.append(name)
                    return spec
                parts[1] = ("mp", "dp") if parts[1] == "mp" else "dp"
                return P(*parts)

            layer_specs = {k: z3(k, v) for k, v in layer_specs.items()}
            self._zero_skip = frozenset(skipped)
            if skipped:
                import warnings

                warnings.warn(
                    "zero_stage=3: first param axis of "
                    f"{sorted(set(skipped))} does not divide dp"
                    f"{'*mp' if self.mp > 1 else ''}={self.dp * self.mp}; "
                    "these leaves stay replicated over 'dp' (ZeRO-1 "
                    "moment-sharding still applies)")
        emb = P("mp", None) if self.mp > 1 else P(None, None)
        head = P(None, "mp") if self.mp > 1 else P(None, None)
        return {
            "embedding": emb,
            "layers": layer_specs,
            "final_norm": P(None),
            "lm_head": head,
        }

    def _zero_spec(self, spec, shape):
        """ZeRO-1: additionally shard optimizer moments over 'dp' along the
        first free, divisible axis (group_sharded_optimizer_stage2.py:53).
        Stage-3 leaves already carry 'dp' in the param spec — moments
        inherit it."""
        if self.dp == 1:
            return spec
        present = set()
        for p in spec:
            present.update(p if isinstance(p, tuple) else (p,))
        if "dp" in present:
            return spec
        parts = list(spec)
        for i, (p, d) in enumerate(zip(parts, shape)):
            if p is None and d % self.dp == 0:
                parts[i] = "dp"
                return P(*parts)
        return spec

    def _sharding(self, spec):
        return NamedSharding(self.mesh, spec)

    def param_shardings(self):
        return jax.tree.map(self._sharding, self._param_specs,
                            is_leaf=lambda x: isinstance(x, P))

    def _ensure_shardings(self):
        if self._param_shardings is not None:
            return
        args, dtype = self.args, self.dtype
        shapes = jax.eval_shape(
            lambda k: lf.init_params(args, k, dtype), jax.random.key(0))
        self._param_shardings = jax.tree.map(
            self._sharding, self._param_specs, is_leaf=lambda x: isinstance(x, P))
        specs_tree = self._spec_tree(shapes)

        def v_shard(sp, sh):
            if self.moments == "factored" and _factored_leaf(sh.shape):
                # r/c inherit the param's sharding minus the factored axis
                # (keeps e.g. the stacked-layer 'pp' axis sharded); they're
                # tiny either way
                parts = list(sp) + [None] * (len(sh.shape) - len(sp))
                return {"r": self._sharding(P(*parts[:-1])),
                        "c": self._sharding(P(*(parts[:-2] + parts[-1:])))}
            return self._sharding(self._zero_spec(sp, sh.shape))

        self._opt_shardings = {
            "m": jax.tree.map(lambda sp, sh: self._sharding(
                self._zero_spec(sp, sh.shape)), specs_tree, shapes),
            "v": jax.tree.map(v_shard, specs_tree, shapes),
            "step": self._sharding(P()),
        }
        if self.master_weights:
            self._opt_shardings["master"] = jax.tree.map(
                lambda sp, sh: self._sharding(
                    self._zero_spec(sp, sh.shape)), specs_tree, shapes)

    def _vpp_perm(self):
        """Leading-dim permutation of the stacked layers for the interleaved
        schedule: stage s's pp-shard holds its V chunks contiguously
        ([chunk v=0..V-1], each L/(S·V) layers), chunk v being global virtual
        stage v*S + s (reference pp_layers.py:264 chunked segmentation)."""
        L, S, V = self.config.num_hidden_layers, self.pp, self.num_virtual_stages
        lc = L // (S * V)
        perm = [
            (v * S + s) * lc + k
            for s in range(S) for v in range(V) for k in range(lc)
        ]
        return np.asarray(perm)

    # -- init ---------------------------------------------------------------
    def init_state(self, seed=0):
        """Sharded params + ZeRO-sharded AdamW state, initialised on-device."""
        self._ensure_shardings()
        key = jax.random.key(seed)
        args, dtype = self.args, self.dtype
        if self.schedule == "interleave":
            perm = jnp.asarray(self._vpp_perm())

            def make(k):
                p = lf.init_params(args, k, dtype)
                p["layers"] = jax.tree.map(lambda a: a[perm], p["layers"])
                return p
        else:
            make = lambda k: lf.init_params(args, k, dtype)  # noqa: E731
        init_fn = jax.jit(make, out_shardings=self._param_shardings)
        params = init_fn(key)
        opt_init = jax.jit(functools.partial(
            adamw_init, moments=self.moments,
            master_weights=self.master_weights),
            out_shardings=self._opt_shardings)
        opt_state = opt_init(params)
        return params, opt_state

    def maybe_resume(self, params, opt_state):
        """(params, opt_state, start_step): restored from the newest
        COMMITTED checkpoint when resume=True was requested and one
        exists, otherwise passed through with start_step=0. Restore is
        in place into the freshly initialised (correctly sharded) state,
        so the trainer loop is identical either way:

            params, opt = engine.init_state(seed)
            params, opt, start = engine.maybe_resume(params, opt)
            for step in range(start, total_steps): ...
        """
        if self.checkpoint_manager is None or not self._resume:
            return params, opt_state, 0
        state = {"params": params, "opt": opt_state}
        extras = self.checkpoint_manager.resume(state)
        if extras is None:
            return params, opt_state, 0
        self._global_step = int(extras.get("step", 0))
        return state["params"], state["opt"], self._global_step

    def _spec_tree(self, like):
        """Expand self._param_specs (with P leaves) to match `like`'s tree."""
        flat_like, tdef = jax.tree.flatten(like)
        flat_specs = tdef.flatten_up_to(
            jax.tree.map(lambda x: x, self._param_specs,
                         is_leaf=lambda x: isinstance(x, P)))
        return tdef.unflatten(flat_specs)


    def _rope_local(self, s_len):
        """RoPE tables for THIS device's seq chunk: under cp the position
        ids are global (chunk r covers [r*s_local, (r+1)*s_local))."""
        hd = self.args.hidden_size // self.args.num_heads
        if self.cp == 1:
            return lf.rope_tables(s_len, hd, self.args.rope_theta)
        cos, sin = lf.rope_tables(s_len * self.cp, hd, self.args.rope_theta)
        r = jax.lax.axis_index("cp")
        cos = jax.lax.dynamic_slice_in_dim(cos, r * s_len, s_len, axis=0)
        sin = jax.lax.dynamic_slice_in_dim(sin, r * s_len, s_len, axis=0)
        return cos, sin

    # -- the pipelined local step (runs inside shard_map) --------------------
    def _mk_stage_helpers(self, ids, labels, s_len):
        """The per-stage pieces every schedule shares, parameterized on the
        (pvary'd) param tree: embed a micro-batch, run the head+loss, and
        build a vma-typed zero loss for non-owning stages."""
        args = self.args
        mp_axis = "mp" if self.mp > 1 else None
        mp, sp = self.mp, self.sp

        def embed_mb(lp, idx):
            idm = jax.lax.dynamic_index_in_dim(ids, idx, 0, keepdims=False)
            h = lf.embed_lookup(lp["embedding"], idm, args, mp_axis, mp)
            h = h.astype(self.dtype)
            if sp and mp_axis:
                loc = s_len // mp
                r = jax.lax.axis_index(mp_axis)
                h = jax.lax.dynamic_slice_in_dim(h, r * loc, loc, axis=1)
            return h

        def head_loss(lp, h, idx):
            h = lf.rms_norm(h, lp["final_norm"], args.rms_eps)
            if sp and mp_axis:
                h = jax.lax.all_gather(h, mp_axis, axis=1, tiled=True)
            labm = jax.lax.dynamic_index_in_dim(labels, idx, 0, keepdims=False)
            if self.loss_chunk:
                # fused streamed lm_head+CE: no [mb, s, vocab] logits buffer
                # even on the vocab-parallel path
                return lf.fused_linear_cross_entropy(
                    h, lp["lm_head"], labm, args, mp_axis, mp,
                    int(self.loss_chunk))
            logits = h @ lp["lm_head"]
            return lf.parallel_cross_entropy(logits, labm, args, mp_axis, mp)

        def zero_loss(ref):
            z = jnp.sum(ref).astype(jnp.float32) * 0
            if sp and mp_axis:
                z = jax.lax.psum(z, mp_axis)
            return z

        return embed_mb, head_loss, zero_loss

    def _pipeline_loss(self, lp, ids, labels):
        """Per-device GPipe loss. ids/labels local: [M, mb_local, s]."""
        args, S, M = self.args, self.pp, self.micro_batches
        mp_axis = "mp" if self.mp > 1 else None
        mp, sp = self.mp, self.sp
        stage = jax.lax.axis_index("pp")
        s_len = ids.shape[-1]
        cos, sin = self._rope_local(s_len)

        # embedding/lm_head/final_norm are replicated over 'pp' but used only
        # inside stage-gated conds. pvary them HERE (outside the conds) so the
        # vjp's cotangent psum over 'pp' — which sums the real grad from the
        # owning stage with zeros from the others — runs uniformly on every
        # stage instead of deadlocking inside a divergent branch.
        lp = dict(lp)
        for k in ("embedding", "lm_head", "final_norm"):
            lp[k] = jax.lax.pcast(lp[k], ("pp",), to="varying")

        embed_mb, head_loss, zero_loss = self._mk_stage_helpers(
            ids, labels, s_len)

        za = self._zero_axis

        def stage_fn(h):
            return lf.run_layers(lp["layers"], h, cos, sin, args, mp_axis, mp,
                                 sp, self.remat, zero_axis=za,
                                 zero_skip=self._zero_skip,
                                 cp_axis=self._cp_axis, cp_mode=self.cp_mode,
                                 unroll=self.unroll)

        perm = [(i, i + 1) for i in range(S - 1)]

        def step(carry, t):
            h_prev = carry
            if S > 1:
                h_recv = jax.lax.ppermute(h_prev, "pp", perm)
            else:
                h_recv = h_prev
            in_idx = jnp.clip(t, 0, M - 1)
            # Gate embed/head on the owning stage with lax.cond so the other
            # stages skip the vocab-sized matmuls entirely. The predicate is
            # pp-varying, so branches must not contain 'pp' collectives (their
            # participants would diverge and deadlock) — 'dp'/'mp' collectives
            # are safe because those groups share the stage index. The
            # zero-scaled adds tie the branch outputs to h_recv/h_out's vma
            # type without introducing a collective in forward or vjp.
            h_in = jax.lax.cond(stage == 0,
                                lambda op: embed_mb(lp, op[1]) + op[0] * 0,
                                lambda op: op[0], (h_recv, in_idx))
            h_out = stage_fn(h_in)
            out_idx = t - (S - 1)
            contrib = jax.lax.cond(
                (stage == S - 1) & (out_idx >= 0),
                lambda op: head_loss(lp, op[0], jnp.clip(op[1], 0, M - 1)),
                lambda op: zero_loss(op[0]), (h_out, out_idx))
            return h_out, contrib

        mb_local = ids.shape[1]
        seq_local = s_len // mp if (sp and mp_axis) else s_len
        h0 = jnp.zeros((mb_local, seq_local, args.hidden_size), self.dtype)
        # the scan carry becomes device-varying after one step (data over
        # 'dp', stage-gated compute over 'pp', seq shards over 'mp' under
        # SP); pvary the zero carry up-front so the vma type is stable
        vary_axes = (("dp", "pp") + self._cp_vary
                     + (("mp",) if (sp and mp_axis) else ()))
        h0 = jax.lax.pcast(h0, vary_axes, to="varying")
        _, losses = jax.lax.scan(step, h0, jnp.arange(M + S - 1))
        # Scale by 1/dp so this is each rank's *contribution to the global
        # mean* loss. Params arrive dp-invariant, so their implicit pvary at
        # first use transposes to a psum over 'dp' — the vjp therefore SUMS
        # grads across dp ranks (the reference's EagerReducer allreduce,
        # reducer.cc:1089); with the 1/dp here that sum is the global-mean
        # gradient, no post-hoc pmean (which would double-scale) needed.
        total = jnp.sum(losses) / (M * self.dp * self.cp)
        # stage-gated cond makes the loss pp-varying even at pp=1; psum
        # collapses it (only the last stage contributed non-zeros)
        total = jax.lax.psum(total, "pp")
        return total

    # -- interleaved / virtual pipeline (reference
    #    pipeline_parallel.py:1308 PipelineParallelWithInterleave) ----------
    def _pipeline_loss_vpp(self, lp, ids, labels):
        """Chunked-ring interleaved schedule: the model is S·V virtual
        stages; each physical stage hosts V chunks and micro-batches ride a
        RING ppermute V times around the mesh. Each tick moves every
        micro-batch one virtual stage (1/V of a stage's layers), so the
        pipeline fill costs (S·V-1) chunk-times ≈ (S-1)/V stage-times —
        the V-fold bubble reduction that is VPP's point. M > S runs as
        ceil(M/S) GROUPS of S micro-batches, each group riding the ring V
        times back-to-back (collision-free: tick t, stage s handles the
        unique unit a = t - s; group = a // (S*V), chunk v = (a mod S*V)
        // S, micro-batch = group*S + a mod S). Backward is AD over the
        scan, GPipe-memory like the reference's interleaved mode."""
        args, S, M, V = self.args, self.pp, self.micro_batches, \
            self.num_virtual_stages
        mp_axis = "mp" if self.mp > 1 else None
        mp, sp = self.mp, self.sp
        stage = jax.lax.axis_index("pp")
        s_len = ids.shape[-1]
        cos, sin = self._rope_local(s_len)
        lc = args.num_layers // (S * V)  # layers per chunk

        lp = dict(lp)
        for k in ("embedding", "lm_head", "final_norm"):
            lp[k] = jax.lax.pcast(lp[k], ("pp",), to="varying")

        za = self._zero_axis

        def chunk_fn(v_idx, h):
            chunk = jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, v_idx * lc, lc, 0),
                lp["layers"])
            return lf.run_layers(chunk, h, cos, sin, args, mp_axis, mp, sp,
                                 self.remat, zero_axis=za,
                                 zero_skip=self._zero_skip,
                                 cp_axis=self._cp_axis, cp_mode=self.cp_mode)

        embed_mb, head_loss, zero_loss = self._mk_stage_helpers(
            ids, labels, s_len)
        ring = [(i, (i + 1) % S) for i in range(S)]

        def step(carry, t):
            h_prev = carry
            h_recv = jax.lax.ppermute(h_prev, "pp", ring) if S > 1 else h_prev
            a = t - stage
            grp = a // (S * V)
            r = jnp.mod(a, S * V)
            v = r // S
            f = grp * S + jnp.mod(r, S)
            valid = (a >= 0) & (f < M) & (v < V)
            f_idx = jnp.clip(f, 0, M - 1)
            v_idx = jnp.clip(v, 0, V - 1)
            h_in = jax.lax.cond(
                (stage == 0) & (v_idx == 0) & (a >= 0),
                lambda op: embed_mb(lp, op[1]) + op[0] * 0,
                lambda op: op[0], (h_recv, f_idx))
            h_out = chunk_fn(v_idx, h_in)
            contrib = jax.lax.cond(
                (stage == S - 1) & (v_idx == V - 1) & valid,
                lambda op: head_loss(lp, op[0], op[1]),
                lambda op: zero_loss(op[0]), (h_out, f_idx))
            return h_out, contrib

        mb_local = ids.shape[1]
        seq_local = s_len // mp if (sp and mp_axis) else s_len
        h0 = jnp.zeros((mb_local, seq_local, args.hidden_size), self.dtype)
        vary_axes = (("dp", "pp") + self._cp_vary
                     + (("mp",) if (sp and mp_axis) else ()))
        h0 = jax.lax.pcast(h0, vary_axes, to="varying")
        G = -(-M // S)  # groups of S micro-batches
        a_max = (G - 1) * S * V + (V - 1) * S + (M - 1) % S
        T = a_max + S  # last unit finishes at stage S-1, tick a_max + S - 1
        _, losses = jax.lax.scan(step, h0, jnp.arange(T))
        total = jnp.sum(losses) / (M * self.dp * self.cp)
        total = jax.lax.psum(total, "pp")
        return total

    # -- 1F1B: hand-scheduled forward/backward (reference
    #    pipeline_parallel.py:242 PipelineParallel 1F1B) --------------------
    def _missing_axes(self, spec):
        """Mesh axes a leaf's grad must be psum'd over in the 1F1B path:
        'dp' (params replicated over data ranks) and 'pp' for the leaves
        shared across stages. 'mp' is intentionally absent — the vma type
        system transposes the mp collectives inside each per-micro-batch vjp
        (psum for mp-replicated leaves like the norms), exactly as in the
        AD'd GPipe path."""
        present = set()
        for ax in spec:
            if isinstance(ax, (tuple, list)):
                present.update(ax)
            elif ax is not None:
                present.add(ax)
        cands = ("dp", "pp") + self._cp_vary
        return tuple(ax for ax in cands if ax not in present)

    def _grads_1f1b(self, lp, ids, labels):
        """Per-device 1F1B loss+grads. Unlike the GPipe path (AD over the
        whole micro-step scan, which saves every tick's carry — M+S-1
        activations), this hand-rolls the schedule: each tick runs at most
        one forward and one backward micro-batch, backward re-derives the
        stage vjp from a saved *input* activation (micro-batch-level remat),
        and the only activation storage is a fixed ring of 2S-1 slots.
        Param grads accumulate in the scan carry.

        Tick timetable (stage s, micro-batch m):
          forward(s, m)  at t = s + m
          backward(s, m) at t = (2S-1-s) + m
        so a forward activation's lifetime is 2S-1-2s ticks (max 2S-1), and
        the backward edge from stage s+1 arrives exactly when due.
        """
        args, S, M = self.args, self.pp, self.micro_batches
        mp_axis = "mp" if self.mp > 1 else None
        mp, sp = self.mp, self.sp
        stage = jax.lax.axis_index("pp")
        s_len = ids.shape[-1]
        cos, sin = self._rope_local(s_len)

        # pvary every param over the mesh axes missing from its spec: the
        # per-micro-batch vjps then stay collective-free on those axes
        # (grads come out as *partials*), and ONE final psum per leaf over
        # the same axes restores the full gradient — instead of a psum per
        # micro-batch that AD's transpose would otherwise insert.
        spec_tree = self._spec_tree(lp)
        lp = jax.tree.map(
            lambda x, sp_: jax.lax.pcast(x, self._missing_axes(sp_),
                                         to="varying"),
            lp, spec_tree, is_leaf=lambda x: isinstance(x, P))

        za = self._zero_axis

        def stage_layers(lp_, h):
            return lf.run_layers(lp_["layers"], h, cos, sin, args, mp_axis,
                                 mp, sp, self.remat, zero_axis=za,
                                 zero_skip=self._zero_skip,
                                 cp_axis=self._cp_axis, cp_mode=self.cp_mode)

        embed_mb, head_loss, zero_loss = self._mk_stage_helpers(
            ids, labels, s_len)
        down = [(i, i + 1) for i in range(S - 1)]
        up = [(i + 1, i) for i in range(S - 1)]
        B = 2 * S - 1  # max in-flight forwards at stage 0
        mb_local = ids.shape[1]
        seq_local = s_len // mp if (sp and mp_axis) else s_len
        h_shape = (mb_local, seq_local, args.hidden_size)
        vary_axes = (("dp", "pp") + self._cp_vary
                     + (("mp",) if (sp and mp_axis) else ()))

        def vary(x):
            return jax.lax.pcast(x, vary_axes, to="varying")

        def step(carry, t):
            h_prev, g_prev, slots, gacc, lacc = carry
            h_recv = jax.lax.ppermute(h_prev, "pp", down) if S > 1 else h_prev
            g_recv = jax.lax.ppermute(g_prev, "pp", up) if S > 1 else g_prev

            # ---- forward tick ----
            f = t - stage
            f_valid = (f >= 0) & (f < M)
            f_idx = jnp.clip(f, 0, M - 1)
            h_in = jax.lax.cond(stage == 0,
                                lambda op: embed_mb(lp, op[1]) + op[0] * 0,
                                lambda op: op[0], (h_recv, f_idx))
            slot = jnp.where(f_valid, f_idx % B, B)  # slot B is the trash can
            slots = jax.lax.dynamic_update_index_in_dim(slots, h_in, slot, 0)
            h_out = stage_layers(lp, h_in)

            # ---- backward tick ----
            b = t - (2 * S - 1 - stage)
            b_valid = (b >= 0) & (b < M)
            b_idx = jnp.clip(b, 0, M - 1)
            h_saved = jax.lax.dynamic_index_in_dim(slots, b_idx % B, 0,
                                                   keepdims=False)

            def bwd_first(op):
                g_in, bi, h_sv = op

                def f_(lp_):
                    return stage_layers(lp_, embed_mb(lp_, bi))

                _, vjp = jax.vjp(f_, lp)
                (g_lp,) = vjp(g_in)
                return zero_loss(h_sv), g_lp, g_in * 0

            def bwd_mid(op):
                g_in, bi, h_sv = op
                _, vjp = jax.vjp(stage_layers, lp, h_sv)
                g_lp, g_h = vjp(g_in)
                return zero_loss(h_sv), g_lp, g_h

            def bwd_last(op):
                g_in, bi, h_sv = op

                def f_(lp_, h):
                    return head_loss(lp_, stage_layers(lp_, h), bi)

                loss_mb, vjp = jax.vjp(f_, lp, h_sv)
                g_lp, g_h = vjp(loss_mb * 0 + 1)  # cotangent with loss's vma
                return loss_mb + zero_loss(h_sv), g_lp, g_h + g_in * 0

            role = jnp.where(stage == 0, 0, jnp.where(stage == S - 1, 2, 1))
            loss_mb, g_lp, g_out = jax.lax.switch(
                role, [bwd_first, bwd_mid, bwd_last],
                (g_recv, b_idx, h_saved))

            w = b_valid.astype(jnp.float32)
            gacc = jax.tree.map(lambda a, g: a + w.astype(g.dtype) * g,
                                gacc, g_lp)
            lacc = lacc + w * loss_mb
            return (h_out, g_out, slots, gacc, lacc), None

        h0 = vary(jnp.zeros(h_shape, self.dtype))
        g0 = vary(jnp.zeros(h_shape, self.dtype))
        slots0 = vary(jnp.zeros((B + 1,) + h_shape, self.dtype))
        gacc0 = jax.tree.map(jnp.zeros_like, lp)
        lacc0 = jax.lax.pcast(jnp.zeros((), jnp.float32),
                              ("dp", "pp") + self._cp_vary,
                              to="varying")
        T = M + 2 * S - 1
        (_, _, _, gacc, lacc), _ = jax.lax.scan(
            step, (h0, g0, slots0, gacc0, lacc0), jnp.arange(T))

        c = 1.0 / (M * self.dp * self.cp)
        loss = jax.lax.psum(lacc, "pp") * c
        loss = jax.lax.psum(loss, self._loss_axes)
        grads = jax.tree.map(
            lambda g, sp_: jax.lax.psum(
                (g.astype(jnp.float32) * c).astype(g.dtype),
                self._missing_axes(sp_))
            if self._missing_axes(sp_) else (g.astype(jnp.float32)
                                             * c).astype(g.dtype),
            gacc, spec_tree, is_leaf=lambda x: isinstance(x, P))
        return loss, grads

    # -- zero-bubble (ZB-H1 family): B/W split (reference static-graph pass
    #    pipeline_scheduler_pass/pipeline_zero_bubble.py:62) -----------------
    def _grads_zb(self, lp, ids, labels):
        """1F1B timetable with the backward SPLIT into activation-grad (B)
        and weight-grad (W) phases — the zero-bubble decomposition:

          - B ticks compute ONLY the activation cotangent (params are
            closed over in the vjp, so XLA dead-code-eliminates the weight
            -grad half) — the tick's critical-path work shrinks, and the
            cotangent chain drains the pipeline at the same tick rate.
          - Every micro-batch's stage-input activation and arriving output
            cotangent are stored ([M] slots); after the scan, ALL weight
            grads run in one batched, bubble-free W phase (no cross-stage
            dependency — each stage sweeps its stored pairs).

        vs _grads_1f1b the scan ticks do less work at an unchanged tick
        count (M + 2S - 1) — the (S-1)-tick fill/drain bubble wastes cheap
        ticks, and the deferred W work runs at 100% utilization. Cost of
        the split under micro-batch remat: the stage forward runs 3x per
        (stage, micro-batch) (F tick, B-tick vjp, W-phase vjp) vs 2x for
        1f1b, and memory holds 2(M+1) boundary h/g buffers vs the 2S-1
        ring — zb wins when the bubble saving (~(S-1)/(M+S-1) of step
        time) exceeds that extra recompute, i.e. small M relative to S;
        benchmark both on the target config.
        """
        args, S, M = self.args, self.pp, self.micro_batches
        mp_axis = "mp" if self.mp > 1 else None
        mp, sp = self.mp, self.sp
        stage = jax.lax.axis_index("pp")
        s_len = ids.shape[-1]
        cos, sin = self._rope_local(s_len)

        spec_tree = self._spec_tree(lp)
        lp = jax.tree.map(
            lambda x, sp_: jax.lax.pcast(x, self._missing_axes(sp_),
                                         to="varying"),
            lp, spec_tree, is_leaf=lambda x: isinstance(x, P))

        za = self._zero_axis

        def stage_layers(lp_, h):
            return lf.run_layers(lp_["layers"], h, cos, sin, args, mp_axis,
                                 mp, sp, self.remat, zero_axis=za,
                                 zero_skip=self._zero_skip,
                                 cp_axis=self._cp_axis, cp_mode=self.cp_mode)

        embed_mb, head_loss, zero_loss = self._mk_stage_helpers(
            ids, labels, s_len)
        down = [(i, i + 1) for i in range(S - 1)]
        up = [(i + 1, i) for i in range(S - 1)]
        mb_local = ids.shape[1]
        seq_local = s_len // mp if (sp and mp_axis) else s_len
        h_shape = (mb_local, seq_local, args.hidden_size)
        vary_axes = (("dp", "pp") + self._cp_vary
                     + (("mp",) if (sp and mp_axis) else ()))

        def vary(x):
            return jax.lax.pcast(x, vary_axes, to="varying")

        role = jnp.where(stage == 0, 0, jnp.where(stage == S - 1, 2, 1))

        def step(carry, t):
            h_prev, g_prev, h_store, g_store, lacc = carry
            h_recv = jax.lax.ppermute(h_prev, "pp", down) if S > 1 else h_prev
            g_recv = jax.lax.ppermute(g_prev, "pp", up) if S > 1 else g_prev

            # ---- forward tick (same timetable as 1F1B) ----
            f = t - stage
            f_valid = (f >= 0) & (f < M)
            f_idx = jnp.clip(f, 0, M - 1)
            h_in = jax.lax.cond(stage == 0,
                                lambda op: embed_mb(lp, op[1]) + op[0] * 0,
                                lambda op: op[0], (h_recv, f_idx))
            slot = jnp.where(f_valid, f_idx, M)  # slot M is the trash can
            h_store = jax.lax.dynamic_update_index_in_dim(
                h_store, h_in, slot, 0)
            h_out = stage_layers(lp, h_in)

            # ---- backward tick: ACTIVATION grad only ----
            b = t - (2 * S - 1 - stage)
            b_valid = (b >= 0) & (b < M)
            b_idx = jnp.clip(b, 0, M - 1)
            h_saved = jax.lax.dynamic_index_in_dim(h_store, b_idx, 0,
                                                   keepdims=False)

            def bwd_first(op):
                g_in, bi, h_sv = op
                # nothing upstream to send; W-phase reads the stored g
                return zero_loss(h_sv), g_in * 0

            def bwd_mid(op):
                g_in, bi, h_sv = op
                # lp closed over => vjp computes d/dh only (wgrad DCE'd)
                _, vjp = jax.vjp(lambda h: stage_layers(lp, h), h_sv)
                (g_h,) = vjp(g_in)
                return zero_loss(h_sv), g_h

            def bwd_last(op):
                g_in, bi, h_sv = op

                def f_(h):
                    return head_loss(lp, stage_layers(lp, h), bi)

                loss_mb, vjp = jax.vjp(f_, h_sv)
                (g_h,) = vjp(loss_mb * 0 + 1)
                return loss_mb + zero_loss(h_sv), g_h + g_in * 0

            loss_mb, g_out = jax.lax.switch(
                role, [bwd_first, bwd_mid, bwd_last],
                (g_recv, b_idx, h_saved))
            bslot = jnp.where(b_valid, b_idx, M)
            g_store = jax.lax.dynamic_update_index_in_dim(
                g_store, g_recv, bslot, 0)

            w = b_valid.astype(jnp.float32)
            lacc = lacc + w * loss_mb
            return (h_out, g_out, h_store, g_store, lacc), None

        h0 = vary(jnp.zeros(h_shape, self.dtype))
        g0 = vary(jnp.zeros(h_shape, self.dtype))
        h_store0 = vary(jnp.zeros((M + 1,) + h_shape, self.dtype))
        g_store0 = vary(jnp.zeros((M + 1,) + h_shape, self.dtype))
        lacc0 = jax.lax.pcast(jnp.zeros((), jnp.float32),
                              ("dp", "pp") + self._cp_vary,
                              to="varying")
        T = M + 2 * S - 1
        (_, _, h_store, g_store, lacc), _ = jax.lax.scan(
            step, (h0, g0, h_store0, g_store0, lacc0), jnp.arange(T))

        # ---- deferred W phase: all weight grads, bubble-free ----
        def w_step(gacc, xs):
            h_sv, g_sv, midx = xs

            def w_first(op):
                g_o, mi, _h = op

                def f_(lp_):
                    return stage_layers(lp_, embed_mb(lp_, mi))

                _, vjp = jax.vjp(f_, lp)
                (g_lp,) = vjp(g_o)
                return g_lp

            def w_mid(op):
                g_o, mi, h_ = op
                _, vjp = jax.vjp(lambda lp_: stage_layers(lp_, h_), lp)
                (g_lp,) = vjp(g_o)
                return g_lp

            def w_last(op):
                g_o, mi, h_ = op

                def f_(lp_):
                    return head_loss(lp_, stage_layers(lp_, h_), mi)

                loss_mb, vjp = jax.vjp(f_, lp)
                (g_lp,) = vjp(loss_mb * 0 + 1)
                return g_lp

            g_lp = jax.lax.switch(role, [w_first, w_mid, w_last],
                                  (g_sv, midx, h_sv))
            gacc = jax.tree.map(lambda a, g: a + g, gacc, g_lp)
            return gacc, None

        gacc0 = jax.tree.map(jnp.zeros_like, lp)
        gacc, _ = jax.lax.scan(
            w_step, gacc0,
            (h_store[:M], g_store[:M], jnp.arange(M)))

        c = 1.0 / (M * self.dp * self.cp)
        loss = jax.lax.psum(lacc, "pp") * c
        loss = jax.lax.psum(loss, self._loss_axes)
        grads = jax.tree.map(
            lambda g, sp_: jax.lax.psum(
                (g.astype(jnp.float32) * c).astype(g.dtype),
                self._missing_axes(sp_))
            if self._missing_axes(sp_) else (g.astype(jnp.float32)
                                             * c).astype(g.dtype),
            gacc, spec_tree, is_leaf=lambda x: isinstance(x, P))
        return loss, grads

    # -- trivial-mesh fast path (dp=pp=mp=1) --------------------------------
    def _grads_trivial(self, params, ids, labels):
        """Single-device loss+grads: plain `value_and_grad` over the
        functional model, no shard_map / pcast / psum / pipeline-scan
        machinery. On a 1x1x1 mesh those constructs are semantically inert
        but not free — the M=1 GPipe scan, the stage-gating `lax.cond`s and
        the vma-typed zero carries measured as a ~15% dispatch tax vs the
        bare-jax program at identical math. The degenerate mesh must compile
        to the *same* XLA program a hand-written jit would produce; this
        path guarantees that. M>1 accumulates micro-batch grads in a scan
        (plain gradient accumulation — pipelining is meaningless at pp=1)."""
        args, M = self.args, self.micro_batches

        def mb_loss(p, i, l):
            return lf.forward_and_loss(p, i, l, args, remat=self.remat,
                                       loss_chunk=self.loss_chunk,
                                       unroll=self.unroll)

        if M == 1:
            return jax.value_and_grad(mb_loss)(params, ids[0], labels[0])

        def step(carry, xs):
            lacc, gacc = carry
            i, l = xs
            loss, g = jax.value_and_grad(mb_loss)(params, i, l)
            gacc = jax.tree.map(jnp.add, gacc, g)
            return (lacc + loss, gacc), None

        g0 = jax.tree.map(jnp.zeros_like, params)
        (lacc, gacc), _ = jax.lax.scan(
            step, (jnp.zeros((), jnp.float32), g0), (ids, labels))
        inv = 1.0 / M
        grads = jax.tree.map(
            lambda g: (g.astype(jnp.float32) * inv).astype(g.dtype), gacc)
        return lacc * inv, grads

    def _local_grads(self, lp, ids, labels):
        """Loss + grads with collective transposition handled by the vma type
        system (check_vma=True): forward psum/all_gather/psum_scatter
        transpose to pvary/psum_scatter/all_gather, so TP/SP weight grads come
        out correct with no manual fix-ups (the pvary transposes even cover
        the stage-gated embedding/head/final-norm psum over 'pp'). The only
        reduction left for us is dp grad averaging (the reference's
        EagerReducer allreduce, reducer.cc:1089)."""
        loss_fn = (self._pipeline_loss_vpp if self.schedule == "interleave"
                   else self._pipeline_loss)
        loss, grads = jax.value_and_grad(loss_fn)(lp, ids, labels)
        # loss is this rank's 1/dp-scaled contribution: psum = global mean
        loss = jax.lax.psum(loss, self._loss_axes)
        return loss, grads

    # -- public API ----------------------------------------------------------
    def _record_ce_blocking(self, ids_shape):
        """Gauges that say how the step being built blocks its fused CE.
        The blocking is static (`lf.ce_blocking` of one device's
        micro-batch), so it is a record, not a rate."""
        if not self.loss_chunk:
            return
        b, s = ids_shape[1] // self.dp, ids_shape[2] // self.cp
        tile, block, _ = lf.ce_blocking(
            b, s, self.args.vocab_size // self.mp, self.loss_chunk)
        for name, value in (
                ("train.ce_token_tile", tile),
                ("train.ce_vocab_block", block),
                ("train.ce_head_grad_passes_per_microbatch", b * s // tile)):
            self.monitor.registry.set_gauge(
                name, value, labels={"source": self.monitor.source})

    def build_train_step(self):
        if self._train_step is not None:
            return self._train_step
        mesh = self.mesh
        param_specs = self._param_specs
        data_spec = self._data_spec  # [M, batch, seq]

        flat_specs_tree = param_specs

        if self.dp == self.pp == self.mp == 1 and self.cp == 1:
            # degenerate mesh: the fast path IS the reference program
            shard_mapped = self._grads_trivial
        else:
            # 1f1b/zb hand-roll their backward; gpipe and interleave AD
            # through their respective schedule loss via _local_grads
            local = functools.partial(
                {"1f1b": self._grads_1f1b, "zb": self._grads_zb}.get(
                    self.schedule, self._local_grads))
            shard_mapped = jax.shard_map(
                local, mesh=mesh,
                in_specs=(flat_specs_tree, data_spec, data_spec),
                out_specs=(P(), flat_specs_tree),
                check_vma=True)

        lr, moments = self.lr, self.moments
        monitor = self.monitor

        def train_step(params, opt_state, ids, labels):
            # trace-time side effect: runs exactly once per XLA compilation
            # (a cached call never re-enters the traced Python), so this
            # counter is precisely "train-step programs built"
            monitor.record_compile("train_step")
            self._record_ce_blocking(ids.shape)
            loss, grads = shard_mapped(params, ids, labels)
            new_params, new_opt = adamw_update(params, grads, opt_state,
                                               lr=lr, moments=moments)
            return loss, new_params, new_opt

        self._ensure_shardings()
        self._train_step = jax.jit(
            train_step,
            donate_argnums=(0, 1),
            out_shardings=(None, self._param_shardings, self._opt_shardings),
        )
        return self._train_step

    def shard_batch(self, ids, labels):
        """[B, s] host arrays -> [M, B/M, s] device arrays sharded over dp.

        Already-placed [M, mb, s] jax.Arrays pass through untouched, so an
        input pipeline can stage the next batch to device while the current
        step runs (the reference DataLoader's pinned-memory prefetch,
        `io/dataloader/dataloader_iter.py`) and train_batch won't re-pay
        the h2d."""
        M = self.micro_batches

        def placed(a):
            return (isinstance(a, jax.Array) and a.ndim == 3
                    and a.shape[0] == M)

        if placed(ids) and placed(labels):
            expect = self._sharding(self._data_spec)
            for name, a in (("ids", ids), ("labels", labels)):
                if a.shape[1] % self.dp != 0:
                    raise ValueError(
                        f"pre-placed {name}: micro-batch dim {a.shape[1]} "
                        f"must be divisible by dp={self.dp}")
                if not a.sharding.is_equivalent_to(expect, a.ndim):
                    raise ValueError(
                        f"pre-placed {name} has sharding {a.sharding}, "
                        f"expected {expect} (batch dim over 'dp'); pass host "
                        "arrays to let shard_batch place them")
            return ids, labels
        B = ids.shape[0]
        if B % (M * self.dp) != 0:
            raise ValueError(f"batch {B} must divide micro_batches*dp={M * self.dp}")
        if ids.shape[-1] % self.cp != 0:
            raise ValueError(f"seq len {ids.shape[-1]} must divide "
                             f"cp={self.cp}")
        ids = np.asarray(ids).reshape(M, B // M, -1)
        labels = np.asarray(labels).reshape(M, B // M, -1)
        sharding = self._sharding(self._data_spec)
        return (jax.device_put(ids, sharding), jax.device_put(labels, sharding))

    def train_batch(self, params, opt_state, ids, labels):
        from paddle_tpu.distributed import comm_monitor as _cm

        step = self.build_train_step()
        with span("train.shard_batch", step=self._global_step):
            ids, labels = self.shard_batch(ids, labels)
        mon = _cm.get_comm_monitor()
        if mon is not None:
            mon.check_peers()  # fail fast if a rank died between steps
        if self._fpt_auto and self._fpt_seq != ids.shape[-1]:
            from paddle_tpu.observability.hardware import llama_flops_per_token

            # attention FLOPs/token scale with seq, so refresh on change
            # (mixed-length training would otherwise skew MFU)
            self.monitor.flops_per_token = llama_flops_per_token(
                self.args, ids.shape[-1])
            self._fpt_seq = ids.shape[-1]
        self.monitor.start_step()
        with _cm.guard("compiled_train_step"), \
                span("train.dispatch", step=self._global_step):
            out = step(params, opt_state, ids, labels)
        # ids is [M, mb, s] global, so .size is the whole-batch token count
        self.monitor.end_step(loss=out[0], tokens=ids.size)
        from paddle_tpu.amp import debugging as _dbg

        if _dbg.checking_enabled():  # FLAGS_check_nan_inf post-step scan
            _dbg.assert_finite(out[0], where="HybridParallelEngine loss")
        self._global_step += 1
        if (self.checkpoint_manager is not None and self._save_every
                and self._global_step % self._save_every == 0):
            # out = (loss, new_params, new_opt): the POST-step state is what
            # gets committed as step N ("N completed steps"); the manager
            # snapshots device->host before returning, so the caller may
            # immediately feed these (donated) arrays back into the next
            # step. Writer errors surface on the handle / next save's wait.
            self.checkpoint_manager.save(
                {"params": out[1], "opt": out[2]}, self._global_step)
        if os.environ.get("PADDLE_CHAOS"):
            from paddle_tpu.distributed.checkpoint.integrity import (
                chaos_point)

            chaos_point("step_end", step=self._global_step)
        return out
