"""Idle time of device 0 inside a decode step (the `pt.serve.step` spans
whose phase entries say kind="decode"), mean over the traced slice's decode
steps: what a decode step loses, apart from a prefill window's."""

from benchmarks.harness import step_idle


def read(ctx):
    return step_idle.decode_idle_ms(ctx)
