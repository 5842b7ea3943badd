"""CPU tests of the `decode_live_page_share` reader (`pytest benchmarks/tests`;
not part of tier-1): its entry in BENCHMARK.json, what it reads in a rehearsed
run of the toy backlog cell, and that a program without the observation (the
parent of the PR that added it) reads nothing.
"""

import json
import os
import sys
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from _platform_setup import force_cpu_platform  # noqa: E402

force_cpu_platform(1)

from benchmarks import run  # noqa: E402
from benchmarks.harness.spec import Cell  # noqa: E402
from benchmarks.tests import tiny  # noqa: E402

NAME = "decode_live_page_share.saturated"


def test_the_entry_is_the_serve_kernels_counter():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "serve kernels",
        "moves": "serve_out_tokens_per_s",
        "workloads": ["serve_mistral7b_saturated"]}
    # the layer's other metric is the kernel's roofline share
    assert [m["name"] for m in bench["per_layer"]
            if m["layer"] == "serve kernels"] == [
                "paged_decode_roofline.saturated", NAME]


def test_backlog_cell_reports_the_share_of_the_tables_held(tmp_path, capsys):
    root = tiny.tiny_root(tmp_path)
    rc = run.main(["--workload", "tiny_backlog", "--seed", str(2**31 + 25),
                   "--seconds", "2", "--trace", "1"],
                  require_chip=False, root=root)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True
    # 3 slots x 16 pages of 16: contexts of 4-92 positions hold 1-6 pages
    assert 0 < res["metrics"][NAME]["value"] <= 100
    assert res["metrics"][NAME]["value"] < 3 * 6 / 48 * 100


def test_a_program_without_the_observation_reads_nothing():
    read = Cell("serve_mistral7b_saturated", ROOT).reader(NAME)
    assert read(SimpleNamespace(counters={"observations": {}})) is None
    ctx = SimpleNamespace(counters={"observations": {
        "decode_live_page_share": {"mean": 0.175, "count": 40}}})
    assert read(ctx) == 17.5
