"""Reusable jaxpr walker: one home for "visit every equation, including
the nested ones", so audit rules (and tests) stop hand-rolling partial
traversals.

Handles every place jax hides a subjaxpr:
  - pjit / closed_call / custom_jvp_call / custom_vjp_call_jaxpr carry a
    ClosedJaxpr under params["jaxpr"] / ["call_jaxpr"] / ["fun_jaxpr"];
  - scan / while carry ClosedJaxprs ("jaxpr", "cond_jaxpr", "body_jaxpr");
  - cond carries a TUPLE of ClosedJaxprs under "branches";
  - shard_map carries an OPEN Jaxpr under "jaxpr".

The walker doesn't enumerate those keys — it scans every param value for
anything jaxpr-shaped (has `.eqns`, or wraps something that does), so new
primitives with new param names keep working.

Provenance: every equation carries `source_info`; `provenance(eqn)`
resolves it to the first non-jax user frame ("file.py:line (function)"),
which is what audit violations print so a finding names the line of
framework code that built the offending op.
"""

from __future__ import annotations

import os

__all__ = ["subjaxprs", "iter_eqns", "iter_shaped_values", "provenance",
           "user_frame", "format_eqn"]


def _as_open_jaxpr(item):
    """Jaxpr | ClosedJaxpr | anything -> open Jaxpr or None."""
    if hasattr(item, "eqns"):
        return item
    inner = getattr(item, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        return inner
    return None


def subjaxprs(params):
    """Yield every open Jaxpr nested in an eqn's params dict (scalars,
    tuples and lists of jaxprs all handled; non-jaxpr values skipped)."""
    for v in params.values():
        items = v if isinstance(v, (tuple, list)) else (v,)
        for item in items:
            jx = _as_open_jaxpr(item)
            if jx is not None:
                yield jx


def iter_eqns(jaxpr):
    """DFS over (eqn, path) pairs of a Jaxpr/ClosedJaxpr and every nested
    subjaxpr. `path` is the tuple of enclosing primitive names, e.g.
    ("pjit", "shard_map", "scan") — the breadcrumb a violation message
    shows so "inside which program half" is never a guess. Cycles (shared
    subjaxpr objects) are visited once."""
    root = _as_open_jaxpr(jaxpr)
    if root is None:
        raise TypeError(f"not a jaxpr: {type(jaxpr).__name__}")
    seen = set()

    def walk(jx, path):
        if id(jx) in seen:
            return
        seen.add(id(jx))
        for eqn in jx.eqns:
            yield eqn, path
            sub_path = path + (eqn.primitive.name,)
            for sub in subjaxprs(eqn.params):
                yield from walk(sub, sub_path)

    yield from walk(root, ())


def iter_shaped_values(jaxpr):
    """Yield (aval, eqn, path, role) for every array-shaped value an
    equation reads ("in") or writes ("out"), across all subjaxprs.
    Literals are included (their avals carry shape/dtype too)."""
    for eqn, path in iter_eqns(jaxpr):
        for role, vs in (("in", eqn.invars), ("out", eqn.outvars)):
            for v in vs:
                aval = getattr(v, "aval", None)
                if aval is not None and hasattr(aval, "shape"):
                    yield aval, eqn, path, role


def user_frame(eqn):
    """Best-effort first user (non-jax-internal) frame of an equation's
    source_info. Returns an object with file_name / start_line /
    function_name, or None."""
    from jax._src import source_info_util as siu

    tb = eqn.source_info.traceback
    if tb is None:
        return None
    fr = siu.user_frame(tb)
    if fr is not None:
        return fr
    # fall back to the raw traceback's innermost frame (user_frame filters
    # to non-jax code and can come up empty for ops built by jax-internal
    # helpers)
    frames = list(tb.frames)
    return frames[0] if frames else None


def provenance(eqn):
    """Equation -> "file.py:line (function)" or "" when unavailable."""
    fr = user_frame(eqn)
    if fr is None:
        return ""
    fname = os.path.basename(getattr(fr, "file_name", "") or "")
    line = getattr(fr, "start_line", 0)
    func = getattr(fr, "function_name", "")
    return f"{fname}:{line} ({func})" if fname else ""


def format_eqn(eqn, path=()):
    """Short human label for an equation in a violation message."""
    shapes = ",".join(str(tuple(getattr(v.aval, "shape", ())))
                      for v in eqn.outvars if hasattr(v, "aval"))
    where = "/".join(path) if path else "top"
    return f"{eqn.primitive.name} -> {shapes} [{where}]"
