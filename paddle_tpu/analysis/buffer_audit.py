"""Buffer audit: what the compiled program materializes.

Four rules over the jaxpr's intermediate values:

  top_intermediates    the k largest buffers any equation writes — the
                       report half (what would an HBM profile blame?).
  check_byte_ceiling   no single intermediate may exceed a per-program
                       byte budget (buffer.byte-ceiling). Budgets are
                       pinned per program family in analysis.presets.
  check_forbidden_shape  the generalized no-[b, s, vocab] rule from the
                       fused-CE work (buffer.forbidden-shape): the given
                       shape must not appear anywhere in the program,
                       forward or backward, including every subjaxpr.
  check_forbidden_carry  no scan or while loop may carry a value of the
                       given shape and dtype from one iteration to the
                       next (buffer.forbidden-carry): a carry is read and
                       written every iteration, so one the size of a weight
                       is that weight's traffic times the trip count.

`has_shape` is the predicate form (used by tests/test_fused_ce.py — the
traversal that used to live there as a private helper now has one home).
"""

from __future__ import annotations

import numpy as np

from paddle_tpu.analysis.base import Violation
from paddle_tpu.analysis.jaxpr_walk import (format_eqn, iter_eqns,
                                            iter_shaped_values, provenance)

__all__ = ["intermediates", "top_intermediates", "has_shape",
           "loop_carries", "check_forbidden_shape", "check_forbidden_carry",
           "check_byte_ceiling"]


def _nbytes(aval):
    try:
        return int(np.prod(aval.shape, dtype=np.int64)) * aval.dtype.itemsize
    except Exception:
        return 0


def intermediates(jaxpr):
    """Every buffer an equation writes: [(nbytes, aval, eqn, path)],
    deduped (an outvar read downstream is still one buffer), sorted
    largest-first."""
    out, seen = [], set()
    for eqn, path in iter_eqns(jaxpr):
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is None or not hasattr(aval, "shape") or id(v) in seen:
                continue
            seen.add(id(v))
            out.append((_nbytes(aval), aval, eqn, path))
    out.sort(key=lambda t: t[0], reverse=True)
    return out


def top_intermediates(jaxpr, k=10):
    """Top-k largest intermediates as report rows
    {nbytes, shape, dtype, op, provenance}."""
    return [{
        "nbytes": nb,
        "shape": tuple(aval.shape),
        "dtype": str(aval.dtype),
        "op": format_eqn(eqn, path),
        "provenance": provenance(eqn),
    } for nb, aval, eqn, path in intermediates(jaxpr)[:k]]


def has_shape(jaxpr, shape):
    """True iff any value (read or written, any subjaxpr) has exactly
    `shape`."""
    shape = tuple(shape)
    return any(tuple(aval.shape) == shape
               for aval, _, _, _ in iter_shaped_values(jaxpr))


def check_forbidden_shape(jaxpr, shape, program, what="buffer"):
    """No value of exactly `shape` may exist anywhere in the program.
    This is the standing form of the fused-CE no-[b, s, vocab] guarantee:
    pass shape=(b, s, vocab) and a rematerialized logits buffer — forward
    OR backward — fails the audit with the eqn that built it."""
    shape = tuple(shape)
    out = []
    seen_eqns = set()
    for aval, eqn, path, role in iter_shaped_values(jaxpr):
        if tuple(aval.shape) != shape or id(eqn) in seen_eqns:
            continue
        seen_eqns.add(id(eqn))
        out.append(Violation(
            rule="buffer.forbidden-shape",
            program=program,
            message=(f"forbidden {what} shape {shape} ({str(aval.dtype)}) "
                     f"{'read' if role == 'in' else 'written'} by "
                     f"{format_eqn(eqn, path)}"),
            provenance=provenance(eqn)))
        if len(out) >= 5:  # the first few sites identify the leak
            break
    return out


def loop_carries(jaxpr):
    """Yield (aval, eqn, path) for every value a scan or a while loop
    carries from one iteration to the next, in every subjaxpr."""
    for eqn, path in iter_eqns(jaxpr):
        if eqn.primitive.name == "scan":
            lo = eqn.params["num_consts"]
            carried = eqn.invars[lo:lo + eqn.params["num_carry"]]
        elif eqn.primitive.name == "while":
            carried = eqn.invars[eqn.params["cond_nconsts"]
                                 + eqn.params["body_nconsts"]:]
        else:
            continue
        for v in carried:
            yield v.aval, eqn, path


def check_forbidden_carry(jaxpr, shape, dtype, program, what="buffer"):
    """No loop may carry a value of exactly `shape` and `dtype`. The
    fused CE's standing form: no float32 [hidden, vocab_local] head
    gradient accumulated across a scan (it was, once a 128-token chunk:
    a read and a write of 758 MB 128 times a step)."""
    shape, dtype = tuple(shape), np.dtype(dtype)
    return [Violation(
        rule="buffer.forbidden-carry",
        program=program,
        message=(f"forbidden {what} {shape} ({dtype}) carried by "
                 f"{format_eqn(eqn, path)}"),
        provenance=provenance(eqn))
        for aval, eqn, path in loop_carries(jaxpr)
        if tuple(aval.shape) == shape and aval.dtype == dtype]


def check_byte_ceiling(jaxpr, ceiling_bytes, program):
    """No single intermediate may exceed `ceiling_bytes`. The budget is
    the audit's teeth against "a refactor quietly re-materialized the big
    buffer": presets pins one per program family at the landed program's
    high-water mark plus headroom."""
    out = []
    for nb, aval, eqn, path in intermediates(jaxpr):
        if nb <= ceiling_bytes:
            break  # sorted descending
        out.append(Violation(
            rule="buffer.byte-ceiling",
            program=program,
            message=(f"intermediate {tuple(aval.shape)} {str(aval.dtype)} "
                     f"is {nb} bytes > ceiling {ceiling_bytes} "
                     f"({format_eqn(eqn, path)})"),
            provenance=provenance(eqn)))
        if len(out) >= 5:
            break
    return out
