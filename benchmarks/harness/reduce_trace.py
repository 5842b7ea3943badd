"""From the profiler's trace to numbers: device busy time, time per
operation, and what the host was doing in the device's longest idle gaps.

`Tracer` wraps `jax.profiler` around a slice of the window and names the
benchmark's own host spans (`bench:<name>`), which land in the same file
on the same clock. `load` turns the `.xplane.pb` into plain tuples, and
everything below works on those, so a recorded extract (JSON) tests it.

A plane is (name, [line]); a line is (name, [event]); an event is
(name, start_ns, duration_ns, label). On the TPU's "XLA Ops" line the
profiler names an event by its whole HLO instruction
(`%fusion.12 = bf16[..] fusion(..), kind=..`): `name` is the part before
` = ` without the `%`, `label` the rest plus the event's string stats.
"""

import contextlib
import glob
import os
import re
import time

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_SPAN = "bench:"


class Tracer:
    def __init__(self, out_dir):
        self.out_dir = out_dir
        self.window_s = 0.0
        self.host_window = None      # (start, stop) on time.perf_counter
        self._t0 = None

    def start(self):
        import jax

        jax.profiler.start_trace(self.out_dir)
        self._t0 = time.perf_counter()

    def stop(self):
        import jax

        now = time.perf_counter()
        self.window_s += now - self._t0
        self.host_window = (self._t0, now)
        jax.profiler.stop_trace()

    @contextlib.contextmanager
    def span(self, name):
        import jax

        with jax.profiler.TraceAnnotation(HOST_SPAN + name):
            yield

    def files(self):
        return sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))


def load(path):
    """[(plane name, [(line name, [(name, start_ns, dur_ns, label)])])]."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                name, _, rest = ev.name.partition(" = ")
                label = " ".join([rest] + [str(v) for _, v in ev.stats
                                           if isinstance(v, str)])
                events.append((name.lstrip("%"), int(ev.start_ns),
                               int(ev.duration_ns), label.strip()))
            lines.append((line.name, events))
        planes.append((plane.name, lines))
    return planes


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def self_times(events):
    """(name, label, self_ns) per event of one line: its duration less what
    its nested events cover (a `while` holds its body's operations)."""
    out, stack = [], []      # stack of [end, index into out]
    for name, start, dur, label in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack and end <= stack[-1][0]:
            out[stack[-1][1]][2] -= dur
        out.append([name, label, dur])
        stack.append([end, len(out) - 1])
    return [(n, l, max(0, d)) for n, l, d in out]


def reduce(planes, window_s=None):
    """A summary dict:
      devices      how many device planes had operations
      busy_s       seconds an operation ran, averaged over those devices
      span_s       first operation's start to the last one's end (device 0)
      ops          {operation name: self seconds}, device 0
      labels       {operation name: label}
      gaps         [(host span name or 'untracked', seconds)] idle gaps of
                   device 0 summed by what the host was in, longest first
    """
    device, host_spans = {}, []
    for pname, lines in planes:
        m = DEVICE_PLANE.match(pname)
        for lname, events in lines:
            if m and lname == OPS_LINE and events:
                device[int(m.group(1))] = events
            if not m:
                host_spans += [(e[1], e[1] + e[2], e[0][len(HOST_SPAN):])
                               for e in events if e[0].startswith(HOST_SPAN)]
    if not device:
        return None
    busy = []
    for events in device.values():
        busy.append(sum(b - a for a, b in _union(
            (e[1], e[1] + e[2]) for e in events)) / 1e9)
    first = device[min(device)]
    merged = _union((e[1], e[1] + e[2]) for e in first)
    ops, labels = {}, {}
    for name, label, ns in self_times(first):
        ops[name] = ops.get(name, 0.0) + ns / 1e9
        labels.setdefault(name, label)
    gaps = {}
    host_spans.sort()
    for (_, a), (b, _) in zip(merged, merged[1:]):
        inside = [(min(b, e) - max(a, s), n) for s, e, n in host_spans
                  if s < b and e > a]
        name = max(inside)[1] if inside else "untracked"
        gaps[name] = gaps.get(name, 0.0) + (b - a) / 1e9
    return {"devices": len(device), "busy_s": sum(busy) / len(busy),
            "span_s": (merged[-1][1] - merged[0][0]) / 1e9,
            "window_s": window_s, "ops": ops, "labels": labels,
            "gaps": sorted(gaps.items(), key=lambda kv: -kv[1])}


# how the trace names the Pallas calls (by HLO instruction, not by the
# kernel's Python name): `pallas_call.N` is flash attention's forward kernel
# (_fwd_kernel), `transpose_jvp___.N` its two backward ones (_bwd_dq_kernel,
# _bwd_dkv_kernel); the train step has no other tpu custom call.
# `closed_call.N` is the paged decode kernel (_paged_decode_kernel).
FLASH_KERNELS = r"^(pallas_call|transpose_jvp_+)[.\d]*$|tpu_custom_call"
PAGED_DECODE_KERNEL = r"^closed_call[.\d]*$"


def idle_share_pct(summary):
    """Share of the traced slice in which no operation ran on the device."""
    if not summary or not summary["window_s"]:
        return None
    return 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])


def kernel_seconds(summary, pattern):
    """Self seconds of the operations whose name or label matches."""
    rx = re.compile(pattern)
    return sum(s for n, s in summary["ops"].items()
               if rx.search(n) or rx.search(summary["labels"].get(n, "")))


def group_key(name, label):
    """`fusion.2825` + `bf16[92544,2048]{..} fusion(..)` -> `fusion
    bf16[92544,2048]`: the instruction's kind and what it produces, so the
    ten layers' copies of one operation add up."""
    base = re.sub(r"[.\d]+$", "", name)
    shape = re.match(r"\(?([a-z0-9]+\[[\d,]*\])", label)
    return f"{base} {shape.group(1)}" if shape else base


def breakdown(summary, top=10):
    groups = {}
    for n, s in summary["ops"].items():
        k = group_key(n, summary["labels"].get(n, ""))
        groups[k] = groups.get(k, 0.0) + s
    ops = sorted(groups.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in summary["gaps"][:top]]}
