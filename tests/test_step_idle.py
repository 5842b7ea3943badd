"""What the serving step says of itself (PR 39), on the CPU:

  - the stall rule of `Engine.step()` under a fake clock: a scripted engine
    whose steps are phase entries of a stated kind, size and length;
  - `benchmarks/harness/step_idle.py` on a synthetic extract: two steps,
    operations and gaps placed by hand, each of the five causes getting
    exactly its instants.

The identifiers on the real engines' phase entries are checked where those
engines are built (`test_serving.py`, `test_paged_kv.py`).
"""

import logging
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import program_scopes, step_idle  # noqa: E402
from paddle_tpu.observability import spans  # noqa: E402
from paddle_tpu.serving import engine as engine_mod  # noqa: E402
from paddle_tpu.serving.engine import PHASES, Engine  # noqa: E402


# -- the stall rule ----------------------------------------------------------------

class _Clock:
    """A clock that stands still until the script moves it."""
    t = 0.0

    def __call__(self):
        return self.t


class Scripted(Engine):
    """The step loop with no device: a step is the script's next entry,
    (kind, bucket, phase, seconds, cpu seconds), spent inside one phase
    entry of that kind."""

    def __init__(self, clock, cpu):
        self.script, self.clock, self.cpu = [], clock, cpu
        super().__init__(None, None, max_slots=1, max_len=16, min_bucket=8)

    def _setup_device_state(self):
        pass

    def _step_action(self):
        kind, bucket, phase, seconds, cpu_s = self.script.pop(0)
        ids = {} if kind is None else {"kind": kind}
        if bucket is not None:
            ids["bucket"] = bucket
        if phase != "schedule":
            with self._phase(phase, **ids):
                self.clock.t += seconds
        else:                    # scheduling is what lies outside the three
            with self._phase("stage", **ids):
                pass
            self.clock.t += seconds
        self.cpu.t += cpu_s
        return {"type": kind}

    def run(self, *steps):
        self.script = list(steps)
        while self.script:
            self.step()
        return self.metrics.summary()


@pytest.fixture
def scripted(monkeypatch):
    clock, cpu = _Clock(), _Clock()
    monkeypatch.setattr(spans, "_clock", clock)
    monkeypatch.setattr(engine_mod, "_thread_clock", cpu)
    return Scripted(clock, cpu)


def _stall_counters(summary):
    return {k[len("serve.stalled_"):]: v
            for k, v in summary["counters"].items()
            if k.startswith("serve.stalled_")}


def test_a_run_without_a_stall_reads_zero_not_nothing(scripted):
    got = _stall_counters(scripted.run(("decode", None, "wait", 1.0, 0.0)))
    assert got == dict.fromkeys(
        ("steps", "s", "cpu_s") + tuple(p + "_s" for p in PHASES), 0)


@pytest.mark.parametrize("phase", ["wait", "stage", "schedule"])
def test_one_long_step_among_twenty_is_one_stall(scripted, caplog, phase):
    """20 decode steps of 1 s and one of 50: one stall of 49 past the mean,
    charged to the phase that held it, absent from the mean, logged once."""
    short = ("decode", None, "wait", 1.0, 0.25)
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.serving"):
        summary = scripted.run(*[short] * 20,
                               ("decode", None, phase, 50.0, 0.5),
                               *[short] * 3)
    got = _stall_counters(summary)
    assert got["steps"] == 1 and got["s"] == 49.0
    assert got[phase + "_s"] == 50.0 and got["cpu_s"] == 0.5
    assert sum(got[p + "_s"] for p in PHASES) == 50.0
    assert summary["gauges"]["serve.last_stall_step"]["value"] == 20
    assert summary["gauges"]["serve.last_stall_s"]["value"] == 50.0
    # the stalled step is no part of the mean: 23 steps of 1 s
    assert scripted._step_means == {("decode", None): [23, 1.0]}
    said = [r.getMessage() for r in caplog.records
            if r.name == "paddle_tpu.serving"]
    assert len(said) == 1
    assert "step 20 stalled" in said[0] and "decode" in said[0]
    assert f"{phase} 50.0000" in said[0] and "cpu 0.5000" in said[0]
    # every step, stalled or not, is one sample of each phase
    assert summary["observations"]["serve.wait_s"]["count"] == 24


def test_a_step_is_judged_by_its_own_kind_and_size(scripted, caplog):
    """A first long prefill bucket among short ones is no stall, nor is a
    long step among fewer than 16 of its class; an idle step is no class."""
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.serving"):
        summary = scripted.run(
            *[("prefill", 64, "wait", 1.0, 0.0)] * 20,
            ("prefill", 2048, "wait", 50.0, 0.0),     # its bucket's first
            *[("decode", None, "wait", 1.0, 0.0)] * 15,
            ("decode", None, "wait", 50.0, 0.0),      # the 16th decode
            (None, None, "emit", 50.0, 0.0),          # nothing staged
            ("prefill", 64, "wait", 4.0, 0.0))        # 4 x the mean: no more
    assert _stall_counters(summary)["steps"] == 0
    assert not caplog.records
    assert set(scripted._step_means) == {
        ("prefill", 64), ("prefill", 2048), ("decode", None)}
    assert scripted._step_means[("prefill", 2048)] == [1, 50.0]


def test_a_page_copy_does_not_rename_the_step(scripted):
    phases = engine_mod.StepPhases(0)
    for kind, bucket in (("copy", None), ("prefill", 64), ("copy", None)):
        with phases("stage", kind=kind, bucket=bucket):
            pass
    assert (phases.kind, phases.bucket) == ("prefill", 64)
    alone = engine_mod.StepPhases(1)
    with alone("stage", kind="copy", part="dispatch"):
        pass
    assert alone.kind == "copy"


def test_reset_clears_the_means(scripted):
    short = ("decode", None, "wait", 1.0, 0.0)
    scripted.run(*[short] * 20)
    assert scripted._step_means[("decode", None)][0] == 20
    scripted.reset()
    assert scripted._step_means == {}
    # sixteen more have to be seen before a long one counts again
    got = _stall_counters(scripted.run(
        *[short] * 15, ("decode", None, "wait", 50.0, 0.0)))
    assert got["steps"] == 0
    got = _stall_counters(scripted.run(("decode", None, "wait", 50.0, 0.0)))
    assert got["steps"] == 1


# -- step_idle on a synthetic extract ------------------------------------------------

def _op(start, end):
    return ("fusion.1", start, end - start, "")


def _extract(identifiers=True):
    """Two steps on a clock in nanoseconds. Step 7 (a decode, 0..100):
    operations [40, 50) and [60, 70); step 8 (a prefill window after a page
    copy's call, 100..200): operations [150, 170) and [170, 180). The
    device's idle gaps: [50, 60) and [70, 150)."""
    ops = [_op(40, 50), _op(60, 70), _op(150, 170), _op(170, 180)]
    dec = dict(step=7, kind="decode", rows=3)
    pre = dict(step=8, kind="prefill", tokens=40, bucket=64, start=0,
               request_id=5, slot=1)
    events = [
        ("serve.step", 0, 100, dict(step=7)),
        ("serve.schedule", 1, 99, dict(step=7)),
        ("serve.stage", 5, 20, dict(dec, part="build")),
        ("serve.stage", 20, 35, dict(dec, part="dispatch")),
        ("serve.wait", 35, 80, dec),
        ("serve.emit", 80, 98, dict(step=7)),
        ("serve.step", 100, 200, dict(step=8)),
        ("serve.schedule", 101, 199, dict(step=8)),
        ("serve.stage", 110, 114, dict(step=8, kind="copy",
                                       part="dispatch")),
        ("serve.stage", 118, 130, dict(pre, part="build")),
        ("serve.stage", 130, 140, dict(pre, part="dispatch")),
        ("serve.wait", 140, 185, pre),
        ("serve.emit", 185, 198, dict(step=8))]
    if not identifiers:
        events = [(n, s, e, {k: v for k, v in ids.items()
                             if k in ("step", "request_id", "slot")})
                  for n, s, e, ids in events]
    return ops, events


def test_each_cause_gets_exactly_its_instants():
    ops, events = _extract()
    got = step_idle.split(ops, events)
    ns = {c: round(s * 1e9) for c, s in got["causes"].items()}
    assert ns == {
        # [80, 98) emit, [98, 99) + [101, 110) + [114, 118) schedule,
        # [118, 130) the window's build
        "host_work": 18 + 1 + 9 + 4 + 12,
        # [110, 114) the page copy's call, [130, 140) the window's call
        "dispatch": 4 + 10,
        # [140, 150): the host waits, the window's first operation has not
        # started
        "launch": 10,
        # [50, 60): between the decode step's two operations
        "in_program": 10,
        # [70, 80): after the decode step's last operation
        "readback": 10}
    assert round(got["idle_s"] * 1e9) == 10 + 80
    # [99, 101) lies in the step spans' own bookkeeping: under no phase, so
    # in none of the five
    assert sum(ns.values()) == 90 - 2
    assert got["decode_steps"] == 1
    assert round(got["decode_idle_s"] * 1e9) == 10 + 30   # [50,60) [70,100)


def test_the_five_sum_to_the_attributed_idle():
    """`idle_attributed_share` reads `program_scopes.idle_by_span` over the
    same planes: the same gaps by the same rule."""
    ops, events = _extract()
    planes = [("/device:TPU:0", [("XLA Ops", ops)]),
              ("/host:CPU", [("python", [("pt." + n, s, e - s, "")
                                         for n, s, e, _ in events])])]
    gaps = program_scopes.idle_by_span(planes)
    attributed = sum(gaps.get(p, 0.0) for p in program_scopes.PHASES)
    got = step_idle.split(ops, events)
    assert sum(got["causes"].values()) == pytest.approx(attributed, abs=1e-12)
    assert sum(gaps.values()) == pytest.approx(got["idle_s"], abs=1e-12)


def test_a_step_without_operations_waits_for_its_launch():
    """A step whose span holds no device operation (an idle step, a page
    copy that ran on): what idles under its `wait` is launch."""
    ops = [_op(0, 10), _op(90, 100)]
    events = [("serve.step", 20, 80, dict(step=1)),
              ("serve.stage", 25, 30, dict(step=1, kind="copy",
                                           part="dispatch")),
              ("serve.wait", 30, 70, dict(step=1, kind="copy"))]
    got = step_idle.split(ops, events)
    ns = {c: round(s * 1e9) for c, s in got["causes"].items()}
    assert ns == {"host_work": 0, "dispatch": 5, "launch": 40,
                  "in_program": 0, "readback": 0}
    assert got["decode_steps"] == 0


def test_the_steps_first_operation_may_be_a_page_copys():
    """`f` is the first operation inside the step span, whatever program it
    belongs to: once a page copy has run, what idles under `wait` until the
    window's program starts lies between the step's operations."""
    ops = [_op(0, 10), _op(32, 36), _op(60, 90)]
    events = [("serve.step", 20, 100, dict(step=1)),
              ("serve.stage", 25, 30, dict(step=1, kind="copy",
                                           part="dispatch")),
              ("serve.stage", 36, 40, dict(step=1, kind="prefill",
                                           part="dispatch")),
              ("serve.wait", 40, 95, dict(step=1, kind="prefill"))]
    got = step_idle.split(ops, events)
    ns = {c: round(s * 1e9) for c, s in got["causes"].items()}
    assert ns == {"host_work": 0, "dispatch": 5 + 4, "launch": 0,
                  "in_program": 20, "readback": 0}


def test_a_program_without_identifiers_reads_nothing():
    """The parent of the PR that added `kind` and `part`."""
    ops, events = _extract(identifiers=False)
    assert step_idle.split(ops, events) is None
    assert step_idle.split(ops, []) is None
