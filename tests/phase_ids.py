"""Shared by test_serving.py and test_paged_kv.py: what a step's phase
entries say of themselves (PR 39).

`record_annotations` puts a recorder in `TraceAnnotation`'s place (as
`test_observability.py` does), so every phase entry's identifiers are seen
as the profiler would get them; `check_identifiers` holds every `stage`
entry to a `kind` and a `part` and every `wait` entry to a `kind`;
`step_and_check_dispatch` is `step_phases.step_and_check` plus the fifth
observation, `serve.stage_dispatch_s`: one sample a step, within
`serve.stage_s`.
"""

from paddle_tpu.observability import spans

from step_phases import step_and_check

KINDS = {"decode", "prefill", "verify", "draft", "copy"}
PARTS = {"build", "dispatch"}


def record_annotations(monkeypatch):
    """[(span name, identifiers)] of every annotation opened from here on."""
    seen = []

    class Ann:
        def __init__(self, name, **ids):
            seen.append((name, ids))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(spans, "TraceAnnotation", Ann)
    return seen


def entries(seen, phase, **having):
    """The identifiers of the `pt.serve.<phase>` entries that carry
    `having`."""
    return [ids for name, ids in seen if name == "pt.serve." + phase
            and all(ids.get(k) == v for k, v in having.items())]


def check_identifiers(seen):
    stage, wait = entries(seen, "stage"), entries(seen, "wait")
    assert stage and wait
    for ids in stage:
        assert ids.get("kind") in KINDS and ids.get("part") in PARTS, ids
    for ids in wait:
        assert ids.get("kind") in KINDS and "part" not in ids, ids
    for ids in stage + wait + entries(seen, "schedule") + entries(seen, "emit"):
        assert "step" in ids, ids


def _dispatch(engine):
    obs = engine.metrics.summary()["observations"].get(
        "serve.stage_dispatch_s") or {}
    return obs.get("count", 0), obs.get("sum", 0.0)


def step_and_check_dispatch(engine):
    """One `step()` under the counting clock: the four phases tile it
    (`step_and_check`), `serve.stage_dispatch_s` gains one sample, and the
    sample lies within the step's `stage`. Returns (event, phases,
    dispatch seconds)."""
    before = _dispatch(engine)
    ev, phases = step_and_check(engine)
    after = _dispatch(engine)
    assert after[0] - before[0] == 1
    dispatch = after[1] - before[1]
    assert 0 <= dispatch <= phases["stage"], (ev, phases, dispatch)
    return ev, phases, dispatch
