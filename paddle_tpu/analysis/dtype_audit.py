"""Dtype-policy audit: no stray f32 matmuls under a bf16 policy.

Under bf16 training every dot_general should take bf16 operands — an f32
dot runs the MXU at half rate and usually means a cast crept in upstream
(the classic silent 2x). The few *intentional* f32 sites (loss math, the
normalization stack, optimizer master-weight math) are allowlisted BY
PROVENANCE — file + function of the equation's source_info — so the
allowlist survives refactors that move lines but not functions.

Rule id: dtype.f32-dot-under-bf16.
"""

from __future__ import annotations

import numpy as np

from paddle_tpu.analysis.base import Violation
from paddle_tpu.analysis.jaxpr_walk import iter_eqns, provenance, user_frame

__all__ = ["DEFAULT_F32_DOT_ALLOWLIST", "check_dtype_policy"]

# "file.py::function" sites allowed to run f32 dot_generals under bf16:
# the loss epilogue accumulates in f32 by design, rms_norm's statistics
# are f32, and the optimizer's master-weight update is the entire point
# of keeping f32 around. Everything else must justify itself here.
DEFAULT_F32_DOT_ALLOWLIST = (
    "llama_functional.py::parallel_cross_entropy",
    "llama_functional.py::_ce_logits",
    "llama_functional.py::_ce_block_grads",
    "llama_functional.py::rms_norm",
    "llama_functional.py::apply_rope_bcast",
    "llama_functional.py::apply_rope",
    "hybrid_engine.py::upd",          # adamw master-weight math
    "hybrid_engine.py::adamw_update",
)


def _allowed(eqn, allowlist):
    fr = user_frame(eqn)
    if fr is None:
        return False
    fname = str(getattr(fr, "file_name", "") or "")
    # frames carry the qualified name ("Outer.<locals>.fn"); the allowlist
    # names the function itself
    func = str(getattr(fr, "function_name", "") or "").rsplit(".", 1)[-1]
    for entry in allowlist:
        efile, _, efunc = entry.partition("::")
        if fname.endswith(efile) and (not efunc or efunc == func):
            return True
    return False


def check_dtype_policy(jaxpr, program, policy="bf16",
                       allowlist=DEFAULT_F32_DOT_ALLOWLIST):
    """Flag f32-operand dot_generals when the program's compute policy is
    bf16. `policy` other than "bf16" disables the rule (f32 training is
    allowed to be f32)."""
    if policy != "bf16":
        return []
    out = []
    for eqn, path in iter_eqns(jaxpr):
        if eqn.primitive.name != "dot_general":
            continue
        op_dtypes = [getattr(getattr(v, "aval", None), "dtype", None)
                     for v in eqn.invars]
        if not any(d is not None and np.dtype(d) == np.dtype(np.float32)
                   for d in op_dtypes):
            continue
        if _allowed(eqn, allowlist):
            continue
        shapes = [tuple(getattr(getattr(v, "aval", None), "shape", ()))
                  for v in eqn.invars]
        out.append(Violation(
            rule="dtype.f32-dot-under-bf16",
            program=program,
            message=(f"f32 dot_general {shapes[0]} x {shapes[1]} under "
                     "bf16 policy (half MXU rate); cast operands to bf16 "
                     "or allowlist the site with a justification"),
            provenance=provenance(eqn)))
    return out
