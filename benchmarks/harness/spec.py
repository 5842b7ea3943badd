"""Finds a cell's files by the names `BENCHMARK.json` gives.

A cell is one entry of `workloads`. Its files:

  benchmarks/configs/<config>.json     the model configuration as it is run
  benchmarks/traffic/<traffic>.json    the traffic mix: parameters only
  benchmarks/workloads/<cell>.json     kind, engine arguments, limits
  benchmarks/metrics/<metric>.py       one per-layer metric's reader; a
                                       quantity split by the end-to-end
                                       metric it moves (`<quantity>.<split>`)
                                       has one reader, `<quantity>.py`

A later PR adds a cell, a configuration, a mix or a metric by adding files
and entries; nothing here is edited for it.
"""

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _read_json(path):
    with open(path) as f:
        return json.load(f)


class Cell:
    """One cell with everything the harness needs, read from data files."""

    def __init__(self, name, root=ROOT):
        self.root = root
        self.bench_dir = os.path.join(root, "benchmarks")
        bench = _read_json(os.path.join(root, "BENCHMARK.json"))
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                             f"{[w['name'] for w in bench['workloads']]}")
        self.name = name
        self.chips = int(entry["chips"])
        self.config_name = entry["config"]
        self.traffic_name = entry["traffic"]
        conf = next(c for c in bench["configs"]
                    if c["name"] == self.config_name)
        self.config = _read_json(os.path.join(root, conf["file"]))
        self.traffic = _read_json(os.path.join(
            self.bench_dir, "traffic", self.traffic_name + ".json"))
        self.spec = _read_json(os.path.join(
            self.bench_dir, "workloads", name + ".json"))
        self.kind = self.spec["kind"]

        # an end-to-end metric applies to every cell unless it lists cells;
        # a per-layer metric applies to the cells it lists or, listing none,
        # to every cell that reports the end-to-end metric it moves
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        e2e_names = {m["name"] for m in self.end_to_end}
        self.per_layer = [
            m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in e2e_names)]

    def reader(self, metric_name):
        """The `read(ctx)` function of benchmarks/metrics/<metric>.py or,
        where that file is not there, of <name before the first dot>.py."""
        mdir = os.path.join(self.bench_dir, "metrics")
        path = os.path.join(mdir, metric_name + ".py")
        if not os.path.isfile(path):
            path = os.path.join(mdir, metric_name.split(".")[0] + ".py")
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric_name.replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
