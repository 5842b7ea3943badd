"""Share of the traced slice's idle seconds of device 0 in which
the host schedules, emits or builds a step's own arrays
(`pt.serve.schedule`, `pt.serve.emit`, a `pt.serve.stage` entry with
part="build" or none): what a loop that dispatches ahead hides
(`harness/step_idle.py`; the five shares sum to `idle_attributed_share`)."""

from benchmarks.harness import step_idle


def read(ctx):
    return step_idle.share(ctx, "host_work")
