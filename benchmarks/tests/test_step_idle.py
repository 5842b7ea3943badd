"""CPU tests of PR 39's seven readers (`pytest benchmarks/tests`; not part
of tier-1; `harness/step_idle.py`'s own rules are tested in
`tests/test_step_idle.py`): the entries resolve to readers and list the four
serving cells, a run without a device trace or without the engine's stall
counters reads nothing, and a rehearsed run of the toy backlog cell reports
`stalled_time_share`.
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
from benchmarks.harness import step_idle  # noqa: E402
from benchmarks.harness.spec import Cell  # noqa: E402
from benchmarks.tests import tiny  # noqa: E402

SHARES = ["idle_host_work_share", "idle_dispatch_share", "idle_launch_share",
          "idle_in_program_share", "idle_readback_share"]
SEVEN = SHARES + ["decode_idle_ms", "stalled_time_share"]
SERVING = ["serve_mistral7b_saturated", "serve_minicpm_sala_long_documents",
           "serve_deepseek_v2_long_answers",
           "serve_olmo_hybrid_chat_replies"]


def _entries():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return {m["name"]: m for m in json.load(f)["per_layer"]}


@pytest.mark.parametrize("name", SEVEN)
def test_the_entry_lists_the_serving_cells_and_resolves(name):
    entry = _entries()[name]
    assert entry["workloads"] == SERVING
    assert entry["layer"] == "server entry"
    assert entry["moves"] == "serve_out_tokens_per_s"
    assert entry["better"] == "lower"
    assert entry["unit"] == ("ms" if name == "decode_idle_ms" else "%")
    assert entry["source"] == ("program_counter"
                               if name == "stalled_time_share"
                               else "program_span")
    for cell in SERVING:
        c = Cell(cell)
        assert name in [m["name"] for m in c.per_layer]
        assert callable(c.reader(name))
    assert name not in [m["name"]
                        for m in Cell("train_internlm2_s4096").per_layer]


def test_the_seven_are_the_files_last_entries_in_order():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    assert names[-7:] == SEVEN


def test_each_share_reads_its_own_cause(monkeypatch):
    found = {"idle_s": 2.0, "decode_steps": 4, "decode_idle_s": 0.01,
             "causes": dict(zip(step_idle.CAUSES,
                                (0.2, 0.4, 0.6, 0.1, 0.5)))}
    monkeypatch.setattr(step_idle, "of_run", lambda ctx: found)
    cell = Cell(SERVING[0])
    got = [cell.reader(name)(None) for name in SHARES]
    assert got == pytest.approx([10.0, 20.0, 30.0, 5.0, 25.0])
    assert cell.reader("decode_idle_ms")(None) == pytest.approx(2.5)
    # a slice without idle, or without a decode step, has no share to give
    monkeypatch.setattr(step_idle, "of_run", lambda ctx: dict(
        found, idle_s=0.0, decode_steps=0))
    assert cell.reader(SHARES[0])(None) is None
    assert cell.reader("decode_idle_ms")(None) is None


def test_a_run_without_a_trace_or_without_the_counter_reads_nothing():
    """A CPU rehearsal has no device trace; the parent of the PR that added
    the stall counters has no `serve.stalled_s`."""
    obs = {f"serve.{p}_s": {"sum": 1.0, "count": 3}
           for p in ("schedule", "stage", "wait", "emit")}
    none = SimpleNamespace(trace=None, cell=SimpleNamespace(root=ROOT),
                           counters={"counters": {}, "observations": obs})
    cell = Cell(SERVING[0])
    for name in SEVEN:
        assert cell.reader(name)(none) is None
    none.counters["counters"]["serve.stalled_s"] = 0.0
    assert cell.reader("stalled_time_share")(none) == 0.0
    none.counters["counters"]["serve.stalled_s"] = 0.4
    assert cell.reader("stalled_time_share")(none) == pytest.approx(10.0)


def test_backlog_cell_reports_the_stalled_time_share(tmp_path, capsys):
    root = tiny.tiny_root(tmp_path)
    rc = run.main(["--workload", "tiny_backlog", "--seed", str(2**31 + 39),
                   "--seconds", "2", "--trace", "1"],
                  require_chip=False, root=root)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True
    m = res["metrics"]
    assert m["stalled_time_share"]["unit"] == "%"
    assert 0.0 <= m["stalled_time_share"]["value"] < 100.0
    # no device plane on the CPU: the trace's readers return nothing
    for name in SHARES + ["decode_idle_ms"]:
        assert name not in m
