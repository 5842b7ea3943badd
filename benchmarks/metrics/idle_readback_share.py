"""Share of the traced slice's idle seconds of device 0 in which
the host still waits (`pt.serve.wait`) after the step's last
operation: the tokens' copy back and the wake of the blocked thread; tokens
fed on the device and a read-back that trails by a step remove it
(`harness/step_idle.py`; the five shares sum to `idle_attributed_share`)."""

from benchmarks.harness import step_idle


def read(ctx):
    return step_idle.share(ctx, "readback")
