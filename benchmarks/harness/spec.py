"""Finds a cell's files by the names `BENCHMARK.json` gives.

A cell is one entry of `workloads`. Its files:

  benchmarks/configs/<config>.json     the model configuration as it is run;
                                       its key `family` names the next file
  benchmarks/families/<family>.py      what the harness knows of the model's
                                       block: the program's static arguments
                                       (`serve_args`, `train_config`), one
                                       layer's leaves (`layer_shapes`), the
                                       plain float32 layer (`decoder_layer`)
                                       and, where the generic ones do not
                                       serve, `leaf_init`, `served_logits`
                                       with `REQUEST_RECORD`, and the counts
                                       of its own readers
  benchmarks/traffic/<traffic>.json    the traffic mix: parameters only
  benchmarks/workloads/<cell>.json     kind, engine arguments, limits
  benchmarks/metrics/<metric>.py       one per-layer metric's reader; a
                                       quantity split by the end-to-end
                                       metric it moves (`<quantity>.<split>`)
                                       has one reader, `<quantity>.py`

A later PR adds a cell, a configuration, a family, a mix or a metric by
adding files and entries; nothing here is edited for it.
"""

import functools
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _read_json(path):
    with open(path) as f:
        return json.load(f)


def _load(name, path):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


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
        family, fdir = self.config.get("family"), os.path.join(
            self.bench_dir, "families")
        self._family_path = os.path.join(fdir, f"{family}.py")
        if not os.path.isfile(self._family_path):
            there = sorted(f[:-3] for f in os.listdir(fdir)
                           if f.endswith(".py"))
            raise SystemExit(f"{conf['file']} names the family {family!r}; "
                             f"{fdir} has {there}")
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

    @functools.cached_property
    def family(self):
        """The module benchmarks/families/<the configuration's `family`>.py,
        loaded at its first use: it imports jax, which `run.py` configures
        after it has read the cell."""
        return _load("bench_family_" + self.config["family"],
                     self._family_path)

    def reader(self, metric_name):
        """The `read(ctx)` function of benchmarks/metrics/<metric>.py or,
        where that file is not there, of <name before the first dot>.py."""
        mdir = os.path.join(self.bench_dir, "metrics")
        path = os.path.join(mdir, metric_name + ".py")
        if not os.path.isfile(path):
            path = os.path.join(mdir, metric_name.split(".")[0] + ".py")
        return _load("bench_metric_" + metric_name, path).read
