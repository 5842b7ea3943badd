"""Drives a training cell: one engine, one compiled step, one state.

Set-up builds the engine and its state from the seed, takes the first
steps through `train_batch` (the call and the feed the window uses) while
reading what `correct` compares, and hands the same objects to the window.
The window enqueues steps, a fresh seeded batch each, and is fenced by
`block_until_ready` on the last loss. The reference runs after the state
is freed.
"""

import gc
import math
import time

import numpy as np

from benchmarks.harness import reference
from benchmarks.harness.traffic import TrainTraffic
from benchmarks.harness.weights import leaves, make_params

CHECK_STEPS = 3          # steps the reference follows
RUN_AHEAD = 2            # steps enqueued ahead of the one being waited for
TRACE_STEPS = 4


def _leaf_names(tree, prefix=""):
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(_leaf_names(v, f"{prefix}{k}/"))
        else:
            out[f"{prefix}{k}"] = v
    return out


class TrainRun:
    def __init__(self, cell, seed, devices, log):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.distributed.hybrid_engine import HybridParallelEngine

        self.jax, self.jnp = jax, jnp
        self.cell, self.seed, self.log = cell, seed, log
        arch, eng_kw = cell.config, dict(cell.spec["engine"])
        self.arch, self.family = arch, cell.family
        self.traffic = TrainTraffic(cell.traffic, arch["vocab_size"], seed)
        dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            eng_kw.pop("dtype")]
        self.hp = cell.spec["optimizer"]
        # the engine is the harness's choice, never the family's
        self.eng = HybridParallelEngine(
            self.family.train_config(arch),
            micro_batches=self.traffic.micro_batches, dtype=dtype,
            lr=self.hp["lr"], devices=devices, **eng_kw)
        # the program's own (zero) optimizer state; its seeded weights are
        # dropped for the benchmark's, which the reference can make too
        p0, self.opt = self.eng.init_state(0)
        del p0
        self.params = make_params(self.family, arch, seed, dtype,
                                  out_shardings=self.eng.param_shardings())
        self.dtype = dtype
        self.spans = []          # (name, start, end) on the host clock
        self.readings = {}

    # -- set-up: the first steps, read for `correct` -------------------------
    def first_steps(self):
        jax, jnp = self.jax, self.jnp
        sq = jax.jit(lambda t: jax.tree.map(
            lambda a: jnp.sum(jnp.square(a.astype(jnp.float32))), t))
        losses = []
        for step in range(CHECK_STEPS):
            ids, labels = self.traffic.batch(step)
            loss, self.params, self.opt = self.eng.train_batch(
                self.params, self.opt, ids, labels)
            losses.append(float(loss))
            if step == 0:
                # m = (1 - beta1) * g after the first step from zero moments
                scale = 1.0 / (1.0 - self.hp["beta1"])
                self.readings["grad_norms"] = {
                    k: scale * math.sqrt(float(v))
                    for k, v in _leaf_names(sq(self.opt["m"])).items()}
        self.readings["losses"] = losses
        # against the seeded weights made anew, a leaf at a time: it keeps
        # the peak the program's, and each leaf is materialized in its type
        dsq = jax.jit(lambda a, b: jnp.sum(jnp.square(
            a.astype(jnp.float32) - b.astype(jnp.float32))))
        now = _leaf_names(self.params)
        self.readings["delta_norms"] = {
            k: math.sqrt(float(dsq(now[k], p0)))
            for k, p0 in leaves(self.family, self.arch, self.seed,
                                self.dtype)}
        self.next_step = CHECK_STEPS

    # -- the measured window ---------------------------------------------------
    def window(self, seconds, trace=None):
        """Returns (tokens_per_s, steps, losses). With `trace` (a tracer
        object with start()/stop()), the last TRACE_STEPS steps are traced and
        left out of the rate."""
        jax = self.jax
        pending, losses = [], []
        tokens = self.traffic.tokens_per_step
        t0 = time.perf_counter()
        steps = 0
        while True:
            ids, labels = self.traffic.batch(self.next_step)
            a = time.perf_counter()
            loss, self.params, self.opt = self.eng.train_batch(
                self.params, self.opt, ids, labels)
            self.spans.append(("train_batch", a, time.perf_counter()))
            self.next_step += 1
            steps += 1
            pending.append(loss)
            if len(pending) > RUN_AHEAD:
                a = time.perf_counter()
                losses.append(float(pending.pop(0)))
                self.spans.append(("wait_loss", a, time.perf_counter()))
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready(pending[-1])
        t1 = time.perf_counter()
        losses.extend(float(x) for x in pending)
        rate = steps * tokens / (t1 - t0)
        if trace is not None:
            trace.start()
            for _ in range(TRACE_STEPS):
                ids, labels = self.traffic.batch(self.next_step)
                with trace.span("train_batch"):
                    loss, self.params, self.opt = self.eng.train_batch(
                        self.params, self.opt, ids, labels)
                self.next_step += 1
            with trace.span("wait_loss"):
                losses.append(float(loss))
            trace.stop()
        return rate, steps, losses

    def free(self):
        """Drop the program's state so the reference has the device."""
        del self.params, self.opt, self.eng
        gc.collect()

    # -- correct ----------------------------------------------------------------
    def reference_numbers(self, mm=reference.f32_mm):
        """What the reference reads over the same first steps: losses, the
        first gradient's norms and the parameters' change, leaf by leaf."""
        hp = self.hp
        ref = reference.TrainReference(
            self.family, self.arch, self.seed,
            (hp["lr"], hp["beta1"], hp["beta2"], hp["eps"],
             hp["weight_decay"]), mm=mm, dtype=self.dtype)
        losses, grads = [], None
        for step in range(CHECK_STEPS):
            losses.append(ref.train_step(*self.traffic.batch(step)))
            if step == 0:
                grads = ref.grad_norms()
        out = {"losses": losses, "grad_norms": grads,
               "delta_norms": ref.delta_norms()}
        del ref
        gc.collect()
        return out

    def check(self):
        """Rows of (name, value, limit): the readings of the program's first
        steps against the reference's."""
        return compare(self.readings, self.reference_numbers(),
                       self.cell.spec["limits"], self.log)


def worst_leaf_gap(got, want):
    """Largest |got - want| over the leaves, each against the larger of the
    reference's norm of that leaf and of the median leaf (some gradients
    are all but zero)."""
    floor = float(np.median(list(want.values())))
    worst, at = 0.0, None
    for k, w in want.items():
        gap = abs(got[k] - w) / max(w, floor)
        if gap > worst:
            worst, at = gap, k
    return worst, at


def compare(got, want, limits, log):
    rows = []
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"])):
        rows.append((f"loss_step{i + 1}_gap", abs(a - b),
                     limits["loss_gap"]))
        log(f"correct: loss step {i + 1}: program {a:.6f} reference {b:.6f}")
    # steadier than one step's gap, and the number a lower precision fails:
    # fp8 moves the loss at seeded weights ten times further than bf16 does
    rows.append(("loss_gap_mean", float(np.mean(
        [abs(a - b) for a, b in zip(got["losses"], want["losses"])])),
        limits["loss_gap_mean"]))
    g, at = worst_leaf_gap(got["grad_norms"], want["grad_norms"])
    rows.append(("grad_norm_worst_leaf_gap", g, limits["grad_norm_gap"]))
    log(f"correct: first gradient norm, worst leaf {at}: program "
        f"{got['grad_norms'][at]:.6g} reference {want['grad_norms'][at]:.6g}")
    d, at = worst_leaf_gap(got["delta_norms"], want["delta_norms"])
    rows.append(("param_change_worst_leaf_gap", d,
                 limits["param_change_gap"]))
    log(f"correct: parameter change norm, worst leaf {at}: program "
        f"{got['delta_norms'][at]:.6g} reference "
        f"{want['delta_norms'][at]:.6g}")
    return rows
