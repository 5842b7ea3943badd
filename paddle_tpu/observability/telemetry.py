"""Structured per-run telemetry artifacts.

`bench.py --telemetry-out PATH` and the hybrid-engine dryrun
(`__graft_entry__.dryrun_multichip`, env `PADDLE_TELEMETRY_OUT`) both call
`write_run_telemetry` so every run leaves a diffable JSON record: the
bench/record payload plus a full registry snapshot (step-time histograms,
MFU, compile counters, heartbeat gauges). Perf regressions become a JSON
diff instead of a scrollback hunt.
"""

from __future__ import annotations

import json
import os
import time

__all__ = ["SCHEMA", "write_run_telemetry"]

SCHEMA = "paddle_tpu.telemetry/v1"


def write_run_telemetry(path, *, record=None, registry=None, meta=None,
                        legs=None):
    """Atomically write one run's telemetry JSON; returns the payload.

    `legs` carries per-subprocess registry snapshots ({name: metrics}) for
    drivers like `bench.py main()` that run each leg in a child process —
    the parent's own registry never saw those runs."""
    payload = {"schema": SCHEMA, "unix_time": time.time(), "meta": meta or {}}
    if record is not None:
        payload["record"] = record
    if registry is not None:
        payload["metrics"] = registry.snapshot()
    if legs:
        payload["metrics_by_leg"] = legs
    path = os.fspath(path)
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True, default=str)
        f.write("\n")
    os.replace(tmp, path)
    return payload
