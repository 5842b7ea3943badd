"""xprof device-time attribution report (ROADMAP 3's method, as a CLI).

Classifies the HLO events of an xprof dump into matmul / collective /
vector / copy-infeed / other and prints, per class, the top-k consumers
with their % of device time, plus device-busy % and the comm-compute
overlap fraction — the artifact "xprof the champion, name the top
non-matmul consumer" asks for, without hand-reading gzipped trace JSON.

Input is any of:
  - an xprof log dir (what `jax.profiler.start_trace(log_dir)` /
    `paddle_tpu.profiler.Profiler(log_dir=...)` writes): the latest
    `plugins/profile/<run>/*.trace.json.gz` is parsed;
  - a single `*.trace.json.gz` or plain `*.json` chrome trace (including
    the synthetic test fixture).

Built on `paddle_tpu.profiler._parse_trace_data` — the same parser that
fills the Profiler's Operator DevTotal column, so the numbers agree.

Usage:
  python tools/xprof_report.py LOGDIR_OR_TRACE [--top K] [--json OUT]

The --json payload carries the per-class device-time shares.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import sys

CLASSES = ("matmul", "collective", "vector", "copy-infeed", "other")

# substring patterns over the normalized HLO event name, checked in order
# (first hit wins): collectives before matmul so "all-reduce.1" never
# matches a fused dot's name, matmul before vector so fused dots count as
# MXU work.
_COLLECTIVE = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast", "send", "recv",
               "partition-id", "replica-id")
# "convolution"/"conv2d" rather than bare "conv": HLO `convert` (dtype
# casts) must stay out of the MXU class
_MATMUL = ("dot", "convolution", "conv2d", "gemm", "matmul", "einsum",
           "cublas", "mxu")
_COPY = ("copy", "infeed", "outfeed", "transfer", "host-to-device",
         "device-to-host")


def classify(name):
    """HLO event name -> one of CLASSES. Names arrive like `fusion.123`,
    `%dot.5`, `loop_add_fusion.2`, `all-reduce-start.1`."""
    n = str(name).lower().lstrip("%")
    for pat in _COLLECTIVE:
        if pat in n:
            return "collective"
    for pat in _MATMUL:
        if pat in n:
            return "matmul"
    for pat in _COPY:
        if pat in n:
            return "copy-infeed"
    # the remaining XLA op events are vector/VPU work (fusions, elementwise,
    # reductions, layout ops); non-op lanes (XLA Modules spans) are "other"
    return "vector"


def load_events(path):
    """Path (xprof logdir | trace.json | trace.json.gz) -> raw device-lane
    event list [{name, ts, dur, lane, pid}] (ts/dur in microseconds)."""
    from paddle_tpu.profiler import _parse_device_trace, _parse_trace_data

    if os.path.isdir(path):
        _, _, raw = _parse_device_trace(path)
        return raw
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        data = json.loads(f.read())
    _, _, raw = _parse_trace_data(data)
    return raw


def _merge_intervals(iv):
    """[(start, end)] -> disjoint sorted union."""
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _intersect_total(a, b):
    """Total overlap (same unit as inputs) of two disjoint interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if lo < hi:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def build_report(events, top_k=5):
    """Raw device events -> the attribution report dict.

    - device_busy_pct: per-device op time / per-device trace span, summed
      over devices (module spans excluded from busy — they bracket ops).
    - classes: per-class seconds, % of device time, and top-k consumers.
    - comm_compute_overlap_pct: fraction of collective time whose wall
      interval overlaps compute (matmul/vector) intervals on the SAME
      device — how much comm the schedule actually hides.
    """
    def lane_kind(e):
        lane = e.get("lane", "")
        if "Modules" in lane:
            return "module"  # whole-program spans: bracket ops, skip
        if "XLA Ops" in lane or "/device:" in lane or lane.startswith("TPU"):
            return "op"
        return "misc"  # device-side step/framework lanes -> "other"

    op_events = [e for e in events if lane_kind(e) == "op"]
    per_class = {c: {} for c in CLASSES}
    for e in events:
        kind = lane_kind(e)
        if kind == "module":
            continue  # counting module spans AND their ops double-books
        cls = classify(e["name"]) if kind == "op" else "other"
        agg = per_class[cls].setdefault(e["name"], {"seconds": 0.0,
                                                    "count": 0})
        agg["seconds"] += float(e["dur"]) / 1e6
        agg["count"] += 1

    device_total = sum(float(e["dur"]) for e in op_events) / 1e6

    # per-device busy % + comm/compute interval sets
    by_dev = {}
    for e in op_events:
        by_dev.setdefault(e.get("pid", 0), []).append(e)
    busy_s = span_s = 0.0
    comm_total = comm_overlap = 0.0
    for evs in by_dev.values():
        t0 = min(float(e["ts"]) for e in evs)
        t1 = max(float(e["ts"]) + float(e["dur"]) for e in evs)
        span_s += (t1 - t0) / 1e6
        busy_iv = _merge_intervals(
            [(float(e["ts"]), float(e["ts"]) + float(e["dur"]))
             for e in evs])
        busy_s += sum(e - s for s, e in busy_iv) / 1e6
        comm_iv = _merge_intervals(
            [(float(e["ts"]), float(e["ts"]) + float(e["dur"]))
             for e in evs if classify(e["name"]) == "collective"])
        compute_iv = _merge_intervals(
            [(float(e["ts"]), float(e["ts"]) + float(e["dur"]))
             for e in evs
             if classify(e["name"]) in ("matmul", "vector")])
        comm_total += sum(e - s for s, e in comm_iv) / 1e6
        comm_overlap += _intersect_total(comm_iv, compute_iv) / 1e6

    def top(cls, denom, pct_key):
        rows = sorted(per_class[cls].items(),
                      key=lambda kv: kv[1]["seconds"], reverse=True)[:top_k]
        return [{"name": n, "seconds": round(v["seconds"], 6),
                 "count": v["count"],
                 pct_key: round(100 * v["seconds"] / denom, 2)
                 if denom else 0.0}
                for n, v in rows]

    classes = {}
    for cls in CLASSES:
        sec = sum(v["seconds"] for v in per_class[cls].values())
        if cls == "other":
            # step/framework lanes BRACKET the ops, so an op-time ratio
            # would exceed 100%; their honest denominator is the trace span
            classes[cls] = {
                "seconds": round(sec, 6),
                "pct_of_span": (round(100 * sec / span_s, 2)
                                if span_s else 0.0),
                "top": top(cls, span_s, "pct_of_span"),
            }
        else:
            classes[cls] = {
                "seconds": round(sec, 6),
                "pct_of_device": (round(100 * sec / device_total, 2)
                                  if device_total else 0.0),
                "top": top(cls, device_total, "pct_of_device"),
            }

    # "other" excluded: those are step/framework lanes, not HLO consumers
    non_matmul = sorted(
        ((n, v, cls) for cls in ("collective", "vector", "copy-infeed")
         for n, v in per_class[cls].items()),
        key=lambda x: x[1]["seconds"], reverse=True)[:top_k]

    return {
        "devices": len(by_dev),
        "device_time_s": round(device_total, 6),
        "span_s": round(span_s, 6),
        "device_busy_pct": (round(100 * busy_s / span_s, 2)
                            if span_s else 0.0),
        "classes": classes,
        "top_non_matmul": [
            {"name": n, "class": cls, "seconds": round(v["seconds"], 6),
             "pct_of_device": round(100 * v["seconds"] / device_total, 2)
             if device_total else 0.0}
            for n, v, cls in non_matmul],
        "comm_total_s": round(comm_total, 6),
        "comm_compute_overlap_pct": (round(100 * comm_overlap / comm_total,
                                           2) if comm_total else 0.0),
    }


def format_report(rep, top_k=5):
    lines = []
    lines.append(
        f"device-busy: {rep['device_busy_pct']:.1f}%  "
        f"({rep['device_time_s']:.4f}s op time over {rep['span_s']:.4f}s "
        f"span, {rep['devices']} device lane(s))")
    share = "  |  ".join(
        f"{cls} {rep['classes'][cls]['pct_of_device']:.1f}%"
        for cls in CLASSES if cls != "other")
    lines.append(f"device-time share: {share}")
    other = rep["classes"]["other"]
    if other["seconds"]:
        lines.append(
            f"non-op lanes (steps/framework): {other['seconds']:.4f}s = "
            f"{other['pct_of_span']:.1f}% of span (bracket ops; not part "
            "of the device-time share)")
    lines.append(
        f"comm-compute overlap: {rep['comm_compute_overlap_pct']:.1f}% of "
        f"{rep['comm_total_s']:.4f}s collective time hidden under compute")
    for cls in CLASSES:
        rows = rep["classes"][cls]["top"]
        if not rows:
            continue
        lines.append(f"top-{min(top_k, len(rows))} {cls}:")
        pct_key = "pct_of_span" if cls == "other" else "pct_of_device"
        for i, r in enumerate(rows, 1):
            lines.append(f"  {i}. {r['name']:<40} {r['seconds']:.6f}s  "
                         f"{r[pct_key]:5.2f}%  x{r['count']}")
    lines.append(f"top-{min(top_k, len(rep['top_non_matmul']))} non-matmul "
                 "consumers (ROADMAP 3's 'name the top non-matmul "
                 "consumer'):")
    for i, r in enumerate(rep["top_non_matmul"], 1):
        lines.append(f"  {i}. {r['name']:<40} [{r['class']}] "
                     f"{r['seconds']:.6f}s  {r['pct_of_device']:5.2f}%")
    return "\n".join(lines)


def check_gates(rep, min_busy_pct=None, max_non_matmul_pct=None,
                min_overlap_pct=None):
    """CI gates over a report dict -> list of failure strings. Exposed
    for tests and for CI scripts that already hold the --json payload."""
    failures = []
    if min_busy_pct is not None and rep["device_busy_pct"] < min_busy_pct:
        failures.append(
            f"GATE device-busy {rep['device_busy_pct']:.2f}% < floor "
            f"{min_busy_pct:.2f}%")
    if max_non_matmul_pct is not None and rep["top_non_matmul"]:
        top = rep["top_non_matmul"][0]
        if top["pct_of_device"] > max_non_matmul_pct:
            failures.append(
                f"GATE top non-matmul consumer {top['name']} "
                f"[{top['class']}] at {top['pct_of_device']:.2f}% of "
                f"device time > ceiling {max_non_matmul_pct:.2f}%")
    if min_overlap_pct is not None and rep["comm_total_s"] \
            and rep["comm_compute_overlap_pct"] < min_overlap_pct:
        failures.append(
            f"GATE comm-compute overlap "
            f"{rep['comm_compute_overlap_pct']:.2f}% < floor "
            f"{min_overlap_pct:.2f}%")
    return failures


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Classify xprof device time into matmul / collective / "
                    "vector / copy-infeed / other")
    ap.add_argument("trace", help="xprof log dir, trace.json, or "
                                  "trace.json.gz")
    ap.add_argument("--top", type=int, default=5, metavar="K",
                    help="top-K consumers per class (default 5)")
    ap.add_argument("--json", metavar="OUT", default=None,
                    help="also write the report dict as JSON "
                         "('-' = stdout, for piping into jq/CI)")
    ap.add_argument("--min-busy-pct", type=float, default=None,
                    metavar="PCT",
                    help="CI gate: exit 2 if device-busy %% is below PCT")
    ap.add_argument("--max-non-matmul-pct", type=float, default=None,
                    metavar="PCT",
                    help="CI gate: exit 2 if the top non-matmul consumer "
                         "takes more than PCT%% of device time")
    ap.add_argument("--min-overlap-pct", type=float, default=None,
                    metavar="PCT",
                    help="CI gate: exit 2 if comm-compute overlap %% is "
                         "below PCT (ignored when the trace has no "
                         "collectives)")
    args = ap.parse_args(argv)

    events = load_events(args.trace)
    if not events:
        print(f"no device-lane events found in {args.trace!r} (host-only "
              "trace? XLA:CPU compute runs in host threads and has no "
              "device lanes)", file=sys.stderr)
        return 1
    rep = build_report(events, top_k=args.top)
    if args.json == "-":
        # machine-readable stdout: the human report moves to stderr
        print(format_report(rep, top_k=args.top), file=sys.stderr)
        json.dump(rep, sys.stdout, indent=2, sort_keys=True)
        sys.stdout.write("\n")
    else:
        print(format_report(rep, top_k=args.top))
        if args.json:
            with open(args.json, "w") as f:
                json.dump(rep, f, indent=2, sort_keys=True)
                f.write("\n")
            print(f"json report -> {args.json}")
    failures = check_gates(rep, args.min_busy_pct,
                           args.max_non_matmul_pct, args.min_overlap_pct)
    for msg in failures:
        print(msg, file=sys.stderr)
    if failures:
        return 2
    return 0


if __name__ == "__main__":
    # running as `python tools/xprof_report.py` puts tools/ (not the repo
    # root) on sys.path; fix that so paddle_tpu imports
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.exit(main())
