"""Share of the traced slice's idle seconds of device 0 in which
the host already waits (`pt.serve.wait`) and the step's
first operation has not started: the transfers and the launch still under
way; one transfer a step, inputs that stay on the device
(`harness/step_idle.py`; the five shares sum to `idle_attributed_share`)."""

from benchmarks.harness import step_idle


def read(ctx):
    return step_idle.share(ctx, "launch")
