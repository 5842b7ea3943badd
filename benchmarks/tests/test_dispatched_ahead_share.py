"""CPU tests of the `dispatched_ahead_share` reader (`pytest benchmarks/tests`;
not part of tier-1): its entry in BENCHMARK.json, what it reads from the
engine's two counters, that a program without them (the parent of the PR that
added them) reads nothing, and a rehearsed run of the toy backlog cell.
"""

import json
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from _platform_setup import force_cpu_platform  # noqa: E402

force_cpu_platform(1)

from benchmarks import run  # noqa: E402
from benchmarks.harness.spec import Cell  # noqa: E402
from benchmarks.tests import tiny  # noqa: E402

NAME = "dispatched_ahead_share"
SERVING = ["serve_mistral7b_saturated", "serve_minicpm_sala_long_documents",
           "serve_deepseek_v2_long_answers",
           "serve_olmo_hybrid_chat_replies"]


def _ctx(**counters):
    return SimpleNamespace(counters={"counters": counters,
                                     "observations": {}})


def test_the_entry_is_the_server_entrys_counter_in_the_serving_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "server entry",
        "moves": "serve_out_tokens_per_s", "workloads": SERVING}
    assert bench["per_layer"][-1] == entry
    for cell in SERVING:
        c = Cell(cell)
        assert NAME in [m["name"] for m in c.per_layer]
        assert callable(c.reader(NAME))
    assert NAME not in [m["name"]
                        for m in Cell("train_internlm2_s4096").per_layer]


@pytest.mark.parametrize("counters,want", [
    ({}, None),                                    # the parent: no counter
    ({"serve.dispatched": 40}, None),              # one of the two only
    ({"serve.dispatched": 0, "serve.dispatched_ahead": 0}, None),
    ({"serve.dispatched": 40, "serve.dispatched_ahead": 0}, 0.0),
    ({"serve.dispatched": 40, "serve.dispatched_ahead": 39}, 97.5),
    ({"serve.dispatched": 8, "serve.dispatched_ahead": 8}, 100.0)])
def test_the_reader_on_a_counters_dict(counters, want):
    read = Cell(SERVING[0], ROOT).reader(NAME)
    got = read(_ctx(**counters))
    assert got is None if want is None else got == pytest.approx(want)


def test_backlog_cell_reports_the_share(tmp_path, capsys):
    root = tiny.tiny_root(tmp_path)
    rc = run.main(["--workload", "tiny_backlog", "--seed", str(2**31 + 40),
                   "--seconds", "2", "--trace", "1"],
                  require_chip=False, root=root)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True
    assert res["metrics"][NAME]["unit"] == "%"
    # a backlog keeps the loop fed: nearly every program goes out behind
    # another (the first of the run and one a pipeline bubble do not)
    assert 80.0 < res["metrics"][NAME]["value"] <= 100.0
