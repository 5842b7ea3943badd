"""Share of the traced slice's idle seconds of device 0 in which
the host waits (`pt.serve.wait`) between the step's first
and last operation: the program's own gaps (operation count), which no
host loop removes
(`harness/step_idle.py`; the five shares sum to `idle_attributed_share`)."""

from benchmarks.harness import step_idle


def read(ctx):
    return step_idle.share(ctx, "in_program")
