"""Share of the traced slice's idle seconds of device 0 in which
the path's call runs (a `pt.serve.stage` entry with
part="dispatch": the argument transfers and the jitted program until it
returns): fewer and lighter arguments, inputs that stay on the device
(`harness/step_idle.py`; the five shares sum to `idle_attributed_share`)."""

from benchmarks.harness import step_idle


def read(ctx):
    return step_idle.share(ctx, "dispatch")
