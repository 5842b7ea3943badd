"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it holds the chip and starts no child. It finds the cell's
files by name (`harness/spec.py`), makes weights and traffic from the seed,
warms up the cell's own programs (set-up), measures for `--seconds`,
decides `correct` against the plain reference, and prints one JSON object
as its last line. Without the chips the cell asks for it exits 2 and
prints no result: a CPU number is never a device number.

Every number compared stands beside its limit in the result's last key,
`compared`, and in the last lines of standard error. Before the result a
run says where its time went (`phases:`, see `Phases`).
"""

import argparse
import json
import os
import re
import sys
import time

T_START = time.perf_counter()

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACE_DIR = os.path.join(".bench_out", "trace")


def log(msg):
    print(msg, flush=True)


class Phases:
    """Where a run's time went, in seconds on the process's own clock.
    `mark(name)` ends the phase `name` (now, or at a time the driver noted)
    and says so at once, so a run that is stopped has said how far it got;
    `line()` is the summary that goes before the result:

        phases: start-up 19.1 s, warm-up 18.2, ramp 52.4, window 30.0,
        check 224.6, whole run 345.0          (on one line)
    """

    def __init__(self):
        self.ends = [("", T_START)]

    def mark(self, name, at=None):
        at = time.perf_counter() if at is None else at
        self.ends.append((name, at))
        log(f"phase: {name} ended {at - T_START:.1f} s after the start")

    def line(self):
        parts = [f"{name} {b - a:.1f}" for (_, a), (name, b)
                 in zip(self.ends, self.ends[1:])]
        parts[0] += " s"
        whole = self.ends[-1][1] - T_START
        return "phases: " + ", ".join(parts) + f", whole run {whole:.1f}"


def parse_phases(text):
    """{phase: seconds} from the `phases:` line of a run's output, the
    last one where `text` holds several; None where it holds none."""
    lines = [ln for ln in text.splitlines() if ln.startswith("phases: ")]
    if not lines:
        return None
    return {name: float(value) for name, value in re.findall(
        r"([^,]+?) (\d+(?:\.\d+)?)(?: s)?(?:, |$)", lines[-1][8:])}


class Context:
    """What a per-layer metric's reader may look at."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def _device_info(devices):
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def _verdict(rows):
    """(every number within its limit, the lines that say so)."""
    ok, lines = True, []
    for name, value, limit in rows:
        good = value <= limit
        ok = ok and good
        lines.append(f"compare: {name} = {value:.6g}  limit {limit:.6g}  "
                     f"{'ok' if good else 'NOT OK'}")
    return ok, lines


def run_train(cell, opts, devices, tracer, phases):
    import numpy as np

    from benchmarks.harness.driver_train import TrainRun

    run = TrainRun(cell, opts.seed, devices, log)
    phases.mark("start-up")
    run.first_steps()
    setup_s = time.perf_counter() - T_START
    phases.mark("first steps", T_START + setup_s)
    rate, steps, losses = run.window(opts.seconds, tracer)
    phases.mark("window")
    device = _device_info(devices)
    bad = int(np.sum(~np.isfinite(losses)))
    log(f"window: {steps} steps, {rate:.1f} tokens/s, first loss "
        f"{losses[0]:.4f} last {losses[-1]:.4f}")
    run.free()
    rows = run.check()
    rows.append(("nonfinite_losses", float(bad), 0.0))
    e2e = {"train_tokens_per_s": rate / len(devices), "setup_s": setup_s}
    ctx = Context(kind="train", spans=run.spans, counters=None, run=run,
                  seq_len=run.traffic.seq_len, rows=run.traffic.rows)
    return e2e, rows, steps, bad, device, ctx


def run_serve(cell, opts, devices, tracer, phases):
    from benchmarks.harness.driver_serve import ServeRun

    run = ServeRun(cell, opts.seed, devices, log)
    phases.mark("start-up")
    run.warm_up()
    phases.mark("warm-up")
    run.run(opts.seconds, tracer)
    phases.mark("ramp", run.origin)
    phases.mark("window", run.end)
    setup_s = run.origin - T_START
    device = _device_info(devices)
    res = run.results()
    sample = run.sample()
    log(f"window: {res['attempted']} requests due, {res['finished']} "
        f"finished, {len(res['ttft_s'])} first tokens, {len(res['itl_s'])} "
        f"token gaps, {len(run.spans)} engine steps; requests in flight "
        f"over the window's thirds {run.in_flight_thirds()}")
    run.free()
    rows = run.check(sample)
    e2e = {"serve_out_tokens_per_s": res["out_tokens_per_s"],
           "setup_s": setup_s}
    if len(res["itl_s"]):
        import numpy as np

        gaps = 1e3 * res["itl_s"]
        e2e["itl_mean_ms"] = float(gaps.mean())
        log("window: token gaps ms: " + " ".join(
            f"p{q}={np.percentile(gaps, q):.2f}" for q in (50, 90, 95, 99))
            + f" mean={gaps.mean():.3f} n={len(gaps)}")
    ctx = Context(kind="serve", spans=run.spans, counters=run.counters,
                  run=run, results=res, late_s=run.late,
                  engine_kw=run.engine_kw)
    return e2e, rows, res["attempted"], res["failed"], device, ctx


def main(argv=None, require_chip=True, root=ROOT):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    opts = ap.parse_args(argv)

    from benchmarks.harness import peaks, reduce_trace
    from benchmarks.harness.spec import Cell

    cell = Cell(opts.workload, root)
    from _platform_setup import configure_compile_cache

    configure_compile_cache()
    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        print(f"benchmark: {cell.name} needs {cell.chips} TPU chip(s); "
              f"jax.devices() = {devs}", file=sys.stderr)
        return 2
    devices = devs[:cell.chips]
    log(f"benchmark: {cell.name} seed {opts.seed} on {len(devices)} x "
        f"{devices[0].device_kind}")

    tracer = None
    if opts.trace:
        import shutil

        trace_dir = os.path.join(root, TRACE_DIR)
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracer = reduce_trace.Tracer(trace_dir)
    runner = {"train": run_train, "serve": run_serve}[cell.kind]
    phases = Phases()
    e2e, rows, attempted, failed, device, ctx = runner(
        cell, opts, devices, tracer, phases)
    phases.mark("check")
    correct, said = _verdict(rows)
    for line in said:
        log(line)

    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": {}, "device": device}
    if not opts.trace:
        for m in cell.end_to_end:
            if e2e.get(m["name"]) is not None:
                result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                "unit": m["unit"]}
    else:
        files = tracer.files()
        summary = reduce_trace.reduce(reduce_trace.load(files[-1]),
                                      tracer.window_s) if files else None
        if summary is None and require_chip:
            print("benchmark: the traced slice holds no device operation",
                  file=sys.stderr)
            return 3
        if summary is not None:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            result["breakdown"] = reduce_trace.breakdown(summary)
        ctx.__dict__.update(
            cell=cell, arch=cell.config, family=cell.family, e2e=e2e,
            trace=summary,
            device=device, chips=len(devices), seconds=opts.seconds,
            trace_host_window=tracer.host_window,
            peaks=(peaks.peaks_for(device["kind"])
                   if device["platform"] == "tpu" else None))
        for m in cell.per_layer:
            value = cell.reader(m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": float(value),
                                                "unit": m["unit"]}
        phases.mark("readers")
    result["compared"] = {name: {"value": value, "limit": limit}
                          for name, value, limit in rows}
    log(phases.line())
    print(json.dumps(result), flush=True)
    print("\n".join(said), file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
