"""Shared by test_serving.py and test_paged_kv.py: drive an engine one
`step()` at a time under a counting clock and check the step's accounting.

With `spans._clock` replaced by a counter that advances by one per read, a
span's duration is the number of reads made inside it, so "the four phases
tile the step" is arithmetic: every read inside `serve.step` belongs to
exactly one phase, except the step span's own entry and exit reads. The
step span itself is observed nowhere (nothing reads it), so its length is
taken from the counter: `step()` reads the clock for its spans alone, first
on entering `serve.step` and last on leaving it.
"""

from paddle_tpu.observability import spans
from paddle_tpu.serving.engine import PHASES

# the step span's entry and exit each put one tick outside any phase
# (entry read -> first phase's entry read; last phase's exit read -> exit
# read)
READS_OUTSIDE_PHASES = 2


class _Ticks:
    reads = 0

    def __call__(self):
        self.reads += 1
        return float(self.reads)


def counting_clock(monkeypatch):
    monkeypatch.setattr(spans, "_clock", _Ticks())


def _sums(engine):
    obs = engine.metrics.summary()["observations"]
    names = [f"serve.{p}_s" for p in PHASES]
    return {n: ((obs.get(n) or {}).get("count", 0),
                (obs.get(n) or {}).get("sum", 0.0)) for n in names}


def synchronous(engine):
    """`engine` with its loop at depth 0: every `step()` reads the program it
    dispatched before it returns, so the scheduled state (`_npos`, block
    tables, reservations) is the emitted state after every call, as it was
    before the loop looked ahead. For a test that compares those internals
    step by step; no engine has a switch for it."""
    engine._looks_ahead = lambda flight: False
    return engine


def step_and_check(engine):
    """One `step()`: each phase gains exactly one sample, and the four
    samples sum to the step span less the reads outside any phase.
    Returns (event, {phase: seconds})."""
    before, first = _sums(engine), spans._clock.reads + 1
    ev = engine.step()
    after, step_span = _sums(engine), spans._clock.reads - first
    gained = {n: after[n][0] - before[n][0] for n in after}
    assert gained == dict.fromkeys(after, 1), gained
    took = {n: after[n][1] - before[n][1] for n in after}
    phases = {p: took[f"serve.{p}_s"] for p in PHASES}
    assert all(v >= 0 for v in phases.values()), phases
    assert sum(phases.values()) == (step_span
                                    - READS_OUTSIDE_PHASES), (ev, took)
    return ev, phases


def run_and_collect(engine, want_types, limit=200):
    """Step until idle; returns {step type: [phases of each such step]} and
    asserts every type in `want_types` occurred."""
    seen = {}
    for _ in range(limit):
        if not (engine.queue or engine.slots.active_slots):
            break
        ev, phases = step_and_check(engine)
        seen.setdefault(ev["type"], []).append(phases)
    assert not (engine.queue or engine.slots.active_slots)
    assert set(want_types) <= set(seen), sorted(seen)
    return seen
