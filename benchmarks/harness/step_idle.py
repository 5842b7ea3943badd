"""Every idle instant of a serving step gets a cause.

The engine's phases (`pt.serve.schedule / stage / wait / emit` inside
`pt.serve.step`) say what kind of step they belong to (`kind`) and which
half of `stage` they are (`part`: the host's own arrays or the path's call).
For each step span this takes the device operations inside it (the first
one's start `f`, the last one's end `l`) and sends every idle instant of
device 0 to exactly one cause, named by its cure:

  host_work    under `schedule`, `emit`, or a `stage` entry with
               part="build" (or none): hidden by dispatching ahead
  dispatch     under a `stage` entry with part="dispatch": fewer and lighter
               arguments, inputs that stay on the device
  launch       under `wait`, before `f`: the same, and one transfer a step
  in_program   under `wait`, between `f` and `l`: the program's own gaps;
               no host loop removes them
  readback     under `wait`, after `l`: tokens fed on the device, the
               read-back trailing by a step

An instant goes to the INNERMOST span open at it, `program_scopes.
idle_by_span`'s own rule over the same gaps, so the five add up to the idle
inside the four phases: `idle_attributed_share` of the same run. A step that
holds no device operation (an idle step, a page copy that ran on) sends its
`wait` instants to launch.

`reduce_trace.load` keeps a string stat's value without its key and drops
the integers, so `host_events` reads `jax.profiler.ProfileData` for the host
planes itself: an identifier BY KEY. Everything below it works on plain
tuples, and a synthetic extract tests it on a CPU. A program without the
identifiers (the parent of the PR that added them) reads None, never an
error.
"""

import bisect
import functools
import os

from benchmarks.harness import program_scopes, reduce_trace

CAUSES = ("host_work", "dispatch", "launch", "in_program", "readback")
STEP = "serve.step"
PREFIX = "pt."


def host_events(trace_file):
    """[(name without the prefix, start_ns, end_ns, {identifier: value})]
    of the program's own spans on the host planes."""
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(trace_file).planes:
        if reduce_trace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    start = int(ev.start_ns)
                    events.append((ev.name[len(PREFIX):], start,
                                   start + int(ev.duration_ns),
                                   dict(ev.stats)))
    return events


def _cause(name, ids):
    """The cause of an idle instant under the phase entry (`name`, `ids`),
    or None where it lies under no phase; `wait` is split by its caller."""
    if name in ("serve.schedule", "serve.emit"):
        return "host_work"
    if name == "serve.stage":
        return "dispatch" if ids.get("part") == "dispatch" else "host_work"
    return "wait" if name == "serve.wait" else None


def _first_last(merged):
    """(f, l) of the operations that touch [lo, hi), cut to it, over the
    merged busy intervals of a device; None where none does."""
    starts = [a for a, _ in merged]
    ends = [b for _, b in merged]

    def first_last(lo, hi):
        i, j = bisect.bisect_right(ends, lo), bisect.bisect_left(starts, hi)
        if i >= j:
            return None
        return max(lo, starts[i]), min(hi, ends[j - 1])
    return first_last


def split(ops, events):
    """Device 0's operations [(name, start_ns, dur_ns, label)] and the host
    events above -> {"idle_s": every idle second of the device between its
    first and its last operation, "causes": {cause: seconds},
    "decode_steps": step spans with an entry of kind="decode",
    "decode_idle_s": idle seconds inside those}, or None where no `stage`
    entry carries `kind` and `part`."""
    if not any(name == "serve.stage" and "kind" in ids and "part" in ids
               for name, _, _, ids in events):
        return None
    merged = reduce_trace._union((e[1], e[1] + e[2]) for e in ops)
    first_last = _first_last(merged)
    steps = {ids["step"]: (s, e) for name, s, e, ids in events
             if name == STEP and "step" in ids}
    decode = {ids["step"] for name, _, _, ids in events
              if ids.get("kind") == "decode" and ids.get("step") in steps}
    program = {}               # step -> (f, l) or None, at first use

    def wait_causes(step, lo, hi):
        """[(cause, seconds)] of the idle piece [lo, hi) under a `wait`."""
        if step not in program:
            program[step] = first_last(*steps[step]) if step in steps \
                else None
        if program[step] is None:
            return [("launch", hi - lo)]
        f, l = program[step]
        cuts = [lo] + [t for t in (f, l) if lo < t < hi] + [hi]
        return [("launch" if b <= f else "readback" if a >= l
                 else "in_program", b - a) for a, b in zip(cuts, cuts[1:])]

    spans = sorted(events, key=lambda s: (s[1], -s[2]))
    causes = dict.fromkeys(CAUSES, 0.0)
    idle = decode_idle = 0.0
    open_, nxt = [], 0
    for (_, a), (b, _) in zip(merged, merged[1:]):
        idle += b - a
        # spans by start, gaps by start: those that can touch this gap
        while nxt < len(spans) and spans[nxt][1] < b:
            open_.append(spans[nxt])
            nxt += 1
        open_ = [s for s in open_ if s[2] > a]
        cuts = sorted({a, b} | {t for _, s, e, _ in open_ for t in (s, e)
                               if a < t < b})
        for lo, hi in zip(cuts, cuts[1:]):
            over = [(e - s, i) for i, (_, s, e, _) in enumerate(open_)
                    if s <= lo and e >= hi]
            if not over:
                continue
            if any(open_[i][0] == STEP and open_[i][3].get("step") in decode
                   for _, i in over):
                decode_idle += hi - lo
            name, _, _, ids = open_[min(over)[1]]      # the innermost
            cause = _cause(name, ids)
            if cause == "wait":
                for cause, ns in wait_causes(ids.get("step"), lo, hi):
                    causes[cause] += ns
            elif cause is not None:
                causes[cause] += hi - lo
    return {"idle_s": idle / 1e9,
            "causes": {c: ns / 1e9 for c, ns in causes.items()},
            "decode_steps": len(decode), "decode_idle_s": decode_idle / 1e9}


@functools.lru_cache(maxsize=1)
def _of_file(trace_file):
    return split(program_scopes._device0_ops(
        program_scopes._loaded(trace_file)), host_events(trace_file))


def of_run(ctx):
    """`split` of the run's traced slice (`program_scopes.traced_planes`'
    own file, read once for all the readers), or None where the run has no
    device trace (a CPU rehearsal), left no file, or the program has no
    identifiers."""
    if not ctx.trace:
        return None
    files = reduce_trace.Tracer(
        os.path.join(ctx.cell.root, ".bench_out", "trace")).files()
    return _of_file(files[-1]) if files else None


def share(ctx, cause):
    """100 x the idle seconds of one cause over ALL the slice's idle
    seconds of device 0 (the denominator of `idle_attributed_share`)."""
    found = of_run(ctx)
    if found is None or not found["idle_s"]:
        return None
    return 100.0 * found["causes"][cause] / found["idle_s"]


def decode_idle_ms(ctx):
    """Idle milliseconds of device 0 inside the step spans whose entries say
    kind="decode", a step: what a decode step loses, apart from a window's."""
    found = of_run(ctx)
    if found is None or not found["decode_steps"]:
        return None
    return 1e3 * found["decode_idle_s"] / found["decode_steps"]


# -- how far the file's one clock can be trusted ---------------------------------

def clock_slack(ops, events):
    """(lo, hi) in seconds: how far the device plane's clock could be moved
    against the host planes' before an operation would start ahead of its
    step's first `dispatch` entry (lo: the largest such lead) or end after
    its step's last `wait` (hi: the smallest room left). The launch /
    in-program / read-back split stands on the two planes sharing a clock:
    lo <= 0 <= hi says the file is consistent as it is, lo > 0 that the
    device's clock runs EARLY by at least lo, and hi - lo is the room
    within which launch and read-back can be traded for one another (a
    round trip's two legs cannot be told apart without a second clock)."""
    first_last = _first_last(
        reduce_trace._union((e[1], e[1] + e[2]) for e in ops))
    steps, dispatch, wait = {}, {}, {}
    for name, s, e, ids in events:
        step = ids.get("step")
        if name == STEP:
            steps[step] = (s, e)
        elif name == "serve.stage" and ids.get("part") == "dispatch":
            dispatch[step] = min(s, dispatch.get(step, s))
        elif name == "serve.wait":
            wait[step] = max(e, wait.get(step, e))
    lo, hi = [], []
    for step, (s, e) in steps.items():
        found = first_last(s, e)
        if found and step in dispatch and step in wait:
            lo.append(dispatch[step] - found[0])
            hi.append(wait[step] - found[1])
    return (max(lo) / 1e9, min(hi) / 1e9) if lo else None


def main(argv):
    """python -m benchmarks.harness.step_idle <file.xplane.pb>: the split of
    a traced slice brought back from the chip, on the file's clock and with
    the device plane moved to either end of its slack."""
    (trace_file,) = argv
    ops = program_scopes._device0_ops(program_scopes.load(trace_file))
    events = host_events(trace_file)
    slack = clock_slack(ops, events)
    print(f"clock slack (lo, hi) ms: {slack and [1e3 * x for x in slack]}")
    for shift in sorted({0.0} | set(slack or ())):
        moved = [(n, s + int(shift * 1e9), d, label)
                 for n, s, d, label in ops]
        found = split(moved, events)
        if found is None:
            print("no identifiers on the phase entries")
            return 1
        idle = found["idle_s"]
        print(f"device clock {1e3 * shift:+.3f} ms: idle {idle:.4f} s; "
              + ", ".join(f"{c} {100 * x / idle:.2f}%"
                          for c, x in found["causes"].items())
              + f"; decode_idle_ms "
              f"{1e3 * found['decode_idle_s'] / max(1, found['decode_steps']):.3f}"
              f" over {found['decode_steps']} decode steps")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main(sys.argv[1:]))
