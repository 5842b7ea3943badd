"""Reads, on the chip and at a cell's own size, the two numbers every limit
of `correct` is set from: what sound runs of the program give over many
seeds, and what the control gives — the reference put in the program's
place and computed in fp8, the precision below bfloat16.

    python3 benchmarks/control.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 2 --seconds 8

One process for all seeds (set-up is long). A serving cell runs a short
window at the cell's own load for each seed; a training cell needs none.
The benchmark's own runs never call this; `benchmarks/tests` keeps the
control at a size a test run can hold. One JSON line per seed.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def log(msg):
    print(msg, flush=True)


def one_seed(cell, seed, seconds, devices, with_control):
    from benchmarks.harness import reference

    if cell.kind == "train":
        from benchmarks.harness import driver_train

        run = driver_train.TrainRun(cell, seed, devices, log)
        run.first_steps()
        run.free()
        ref = run.reference_numbers()
        rows = driver_train.compare(run.readings, ref, cell.spec["limits"],
                                    log)
        control = None
        if with_control:
            low = run.reference_numbers(reference.fp8_mm)
            control = driver_train.compare(low, ref, cell.spec["limits"], log)
    else:
        from benchmarks.harness import driver_serve

        run = driver_serve.ServeRun(cell, seed, devices, log)
        run.warm_up()
        run.run(seconds)
        sample = run.sample()
        run.free()
        logits = run.reference_logits(sample)
        rows = run.check(sample, logits)
        control = None
        if with_control:
            low = run.reference_logits(sample, reference.fp8_mm)
            control = run.check(sample, logits,
                                [x.argmax(-1) for x in low])
    out = {"seed": seed, "sound": {n: v for n, v, _ in rows}}
    if control is not None:
        out["control"] = {n: v for n, v, _ in control}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=8.0)
    opts = ap.parse_args(argv)

    from _platform_setup import configure_compile_cache
    from benchmarks.harness.spec import Cell

    cell = Cell(opts.workload)
    configure_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"control: needs {cell.chips} TPU chip(s)", file=sys.stderr)
        return 2
    for i, seed in enumerate(int(s) for s in opts.seeds.split(",")):
        res = one_seed(cell, seed, opts.seconds, devs[:cell.chips],
                       i < opts.control_seeds)
        print("CONTROL " + json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
