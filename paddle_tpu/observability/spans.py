"""The program's one span primitive.

`span(name, metrics=None, **ids)` brackets host work twice over:

  - as a `jax.profiler.TraceAnnotation("pt.<name>", **ids)`, so while a
    profiler session runs the span lands in the same `.xplane.pb`, on the
    same clock, as the device's operations (an idle gap of the device can
    then be put down to what the host was doing in it). Outside a session
    the annotation records nothing;
  - as one observation `"<name>_s"` (unlabeled, seconds) in the registry it
    was given: a `MetricsRegistry`, the serving `Metrics` facade, or any
    object with `observe(name, value)`. A caller that wants one sample per
    step and not one per entry hands in the step's own accumulator and
    observes the totals itself (`serving/engine.py::StepPhases`).

Nothing is kept per span: a span on a serving loop costs two clock reads and
one locked histogram update, whatever the run's length.

`ids` are the identifiers that tie spans together: `step` (the engine's step
count; a phase's parent is the step span of the same `step`), `request_id`,
`slot`, and what a serving phase's entry says of its step: `kind`, `part`,
`rows`, `tokens`, `bucket`, `start` (a sub-division of a phase is an
identifier, never a span nested in it). They become the event's stats in the
trace, each under its key.

Device-side names are `jax.named_scope("pt.<what>")` in the traced code
itself (ARCHITECTURE.md "Observability" has both tables).
"""

from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

__all__ = ["PREFIX", "recording", "span"]

PREFIX = "pt."


# whether a profiler session records annotations now: what a `TraceAnnotation`
# itself asks before it records anything (one atomic read)
recording = TraceAnnotation.is_enabled


# the one clock every span reads, once on entry and once on exit (a test
# replaces it with a counter to make a step's accounting exact arithmetic)
_clock = time.perf_counter


class span:
    __slots__ = ("name", "_metrics", "_ann", "_t0")

    # what the trace event's name starts with: the program's own spans are
    # found by it; a user's annotation (`profiler.RecordEvent`) has none
    prefix = PREFIX

    def __init__(self, name, metrics=None, **ids):
        self.name = name
        self._metrics = metrics
        self._ann = TraceAnnotation(self.prefix + name, **ids)
        self._t0 = None

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = _clock()
        return self

    def __exit__(self, *exc):
        seconds = _clock() - self._t0
        self._ann.__exit__(*exc)
        if self._metrics is not None:
            self._metrics.observe(self.name + "_s", seconds)
        return False
