"""CPU tests of the benchmark's harness. Run with `pytest benchmarks/tests`
(not part of tier-1). They rehearse the drivers end to end at a toy size,
check the yardstick's arithmetic by hand, and keep the two proofs that
`correct` can fail: the lower-precision control and a broken timed path.
"""

import filecmp
import functools
import hashlib
import json
import os
import re
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from _platform_setup import force_cpu_platform  # noqa: E402

force_cpu_platform(1)

from benchmarks import run  # noqa: E402
from benchmarks.harness import (counts, peaks, reduce_trace,  # noqa: E402
                                reference, traffic, weights)
from benchmarks.harness.spec import Cell  # noqa: E402
from benchmarks.tests import tiny  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name)) as f:
        return json.load(f)


# -- the contract's shape ------------------------------------------------------

def test_benchmark_json_names_files_that_exist():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    bdir = os.path.join(ROOT, "benchmarks")
    for c in b["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        conf = _config(os.path.basename(c["file"]))
        assert conf["reduced"] == c["reduced"]
        assert conf["source"] == c["source"]
        assert NAME.match(conf["family"]) and os.path.isfile(os.path.join(
            bdir, "families", conf["family"] + ".py"))
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in b["workloads"]}
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(bdir, "workloads",
                                           w["name"] + ".json"))
        assert os.path.isfile(os.path.join(bdir, "traffic",
                                           w["traffic"] + ".json"))
    for m in b["end_to_end"]:
        assert NAME.match(m["name"]) and 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", [])) <= cells
    for m in b["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert any(os.path.isfile(os.path.join(bdir, "metrics", n + ".py"))
                   for n in (m["name"], m["name"].split(".")[0]))
        assert set(m.get("workloads", [])) <= cells
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(b["workloads"]) // 4)


def test_every_cell_resolves_to_a_family_with_the_names_its_kind_calls():
    """No jit and no engine: what a tier-1 test of the seam would check."""
    for w in _bench()["workloads"]:
        cell = Cell(w["name"])
        fam = cell.family
        assert fam.__file__ == os.path.join(
            ROOT, "benchmarks", "families", cell.config["family"] + ".py")
        entry = {"serve": "serve_args", "train": "train_config"}[cell.kind]
        for name in (entry, "layer_shapes", "decoder_layer"):
            assert callable(getattr(fam, name)), (w["name"], name)
        shapes = fam.layer_shapes(cell.config)
        assert shapes and all(isinstance(n, int) for shape in shapes.values()
                              for n in shape)
        assert set(getattr(fam, "leaf_init", lambda a: {})(cell.config)) \
            <= set(shapes)


def test_a_family_that_is_not_there_stops_the_run_with_those_that_are(
        tmp_path):
    root = tiny.tiny_root(tmp_path)
    path = os.path.join(root, "benchmarks", "configs", "tiny.json")
    with open(path, "w") as f:
        json.dump(dict(tiny.TINY_ARCH, family="no_such_block"), f)
    with pytest.raises(SystemExit) as e:
        Cell("tiny_train", root)
    assert "no_such_block" in str(e.value)
    assert "'dense_gqa_swiglu'" in str(e.value)
    assert "'tiny_alt'" in str(e.value)


def test_run_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "train_internlm2_s4096", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout


# -- counts and peaks, by hand ---------------------------------------------------

def test_counts_by_hand():
    il = _config("internlm2-1.8b-1chip.json")
    mi = _config("mistral-7b-v0.3-1chip.json")
    # InternLM2 layer: q 2048x2048, k and v 2048x1024, o 2048x2048,
    # three 2048x8192 feed-forward matrices
    assert counts.layer_matmul_params(il) == (
        2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192) == 62_914_560
    assert counts.layer_matmul_params(mi) == (
        2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336) == 218_103_808
    # K and V of one token in one layer: 2 x 8 heads x 128 x 2 bytes = 4 KB
    assert counts.kv_bytes_per_token(mi) == 4096 * mi["num_hidden_layers"]
    assert counts.kv_bytes_per_token(il) == 4096 * il["num_hidden_layers"]
    # the embedding table is a lookup: not in the matmul count
    assert counts.matmul_params(il) == (
        il["num_hidden_layers"] * 62_914_560 + 2048 * 92544)
    assert counts.total_params(il) - counts.matmul_params(il) == (
        92544 * 2048 + il["num_hidden_layers"] * 4096 + 2048)
    # causal attention at s = 4: 10 visible pairs a head, 4 * hd flops each
    toy = dict(il, num_hidden_layers=1, num_attention_heads=1, head_dim=8)
    assert counts.attn_flops_fwd(toy, 4) == 10 * 4 * 8
    assert counts.flash_train_flops_per_seq(toy, 4) == 10 * 14 * 8
    s = 4096
    per_tok = counts.train_flops_per_token(il, s)
    assert per_tok == pytest.approx(
        6 * counts.matmul_params(il)
        + 3 * il["num_hidden_layers"] * 16 * 4 * 128 * (s + 1) / 2)


def test_peaks_known_and_unknown():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9")


# -- traffic -----------------------------------------------------------------------

def _sessions_mix():
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "sessions_mix.json")) as f:
        return json.load(f)


def _draw(mix, seed, n):
    t = traffic.ServeTraffic(mix, 32768, seed)
    return [t.next_session(None) for _ in range(n)]


def test_traffic_same_seed_same_sessions():
    mix = _sessions_mix()
    a, b = _draw(mix, 2**31 + 11, 40), _draw(mix, 2**31 + 11, 40)
    for x, y in zip(a, b):
        assert x.due == y.due and len(x.turns) == len(y.turns)
        assert (x.history == y.history).all()
        assert all((u == v).all() and p == q
                   for (u, p), (v, q) in zip(x.turns, y.turns))
    # another seed: other tokens, the same sizes and gaps in the same order
    c = _draw(mix, 2**31 + 12, 40)
    assert not all((x.history == y.history).all() for x, y in zip(a, c))
    assert all(x.due == y.due and [len(u) for u, _ in x.turns]
               == [len(u) for u, _ in y.turns] for x, y in zip(a, c))


def test_traffic_every_seed_draws_the_same_sizes():
    mix = _sessions_mix()
    pool = mix["pool"]

    def sizes(seed):
        t = traffic.ServeTraffic(mix, 32768, seed)
        return (sorted(t._user.next() for _ in range(pool)),
                sorted(t._gap.next() for _ in range(pool)))

    assert sizes(1) == sizes(99)
    users, gaps = sizes(1)
    assert users[0] >= 16 and users[-1] <= 1024
    assert np.median(users) == pytest.approx(128, rel=0.05)
    assert np.mean(gaps) == pytest.approx(
        1.0 / mix["arrival"]["rate_per_s"], rel=0.03)
    drawn = _draw(mix, 5, 60)
    for i, s in enumerate(drawn):
        used = len(s.history) + sum(len(u) + a for u, a in s.turns)
        assert used <= mix["max_context"] and 1 <= len(s.turns) <= 5
        if i < mix["initial_sessions"]:
            # the steady population: due together, some in mid-conversation
            assert s.due == -mix["ramp_s"] and len(s.history) >= 2048
        else:
            assert s.due > -mix["ramp_s"] or i == mix["initial_sessions"]
            assert len(s.history) == 2048
    assert any(len(s.history) > 2048 for s in drawn[:mix["initial_sessions"]])


def test_train_batches_are_seeded_and_shifted():
    mix = {"rows": 2, "seq_len": 8, "micro_batches": 1}
    t = traffic.TrainTraffic(mix, 100, 2**31 + 3)
    ids, labels = t.batch(0)
    assert ids.shape == (2, 8) and (ids[:, 1:] == labels[:, :-1]).all()
    ids2, _ = traffic.TrainTraffic(mix, 100, 2**31 + 3).batch(0)
    assert (ids == ids2).all() and not (ids == t.batch(1)[0]).all()


# -- trace reduction -----------------------------------------------------------------

def test_reduce_trace_nesting_union_and_gaps():
    ops = [("while.1", 0, 100, ""), ("fusion.a", 10, 30, "scope/_fwd_kernel"),
           ("fusion.b", 50, 40, ""), ("copy.1", 200, 50, "")]
    host = [("bench:step", 90, 120, ""), ("other", 0, 10, "")]
    planes = [("/device:TPU:0", [("XLA Ops", ops), ("Steps", [])]),
              ("/host:CPU", [("main", host)])]
    s = reduce_trace.reduce(planes, window_s=1e-6)
    assert s["devices"] == 1
    assert s["busy_s"] == pytest.approx(150e-9)
    assert s["ops"]["while.1"] == pytest.approx(30e-9)   # 100 - 30 - 40
    assert s["gaps"] == [("step", pytest.approx(100e-9))]
    assert reduce_trace.kernel_seconds(s, "_fwd_kernel") == \
        pytest.approx(30e-9)
    b = reduce_trace.breakdown(s)
    assert b["device_ops"][0][0] == "copy" and len(b["idle_gaps"]) == 1
    assert reduce_trace.reduce([("/host:CPU", [("main", host)])]) is None


def test_reduce_trace_on_the_recorded_extract():
    path = os.path.join(os.path.dirname(__file__), "data",
                        "trace_extract.json")
    if not os.path.isfile(path):
        pytest.skip("no extract recorded on the chip yet")
    with open(path) as f:
        rec = json.load(f)
    s = reduce_trace.reduce(rec["planes"], rec["window_s"])
    assert s["devices"] == rec["expect"]["devices"]
    assert s["busy_s"] == pytest.approx(rec["expect"]["busy_s"], rel=1e-9)
    assert 0 < s["busy_s"] <= rec["window_s"]


# -- the reference against the program, toy size ---------------------------------------

@functools.cache
def _dense():
    """The committed dense family, found as a run finds it (once: a module
    loaded anew is a new `decoder_layer`, which the reference jits anew)."""
    return Cell("train_internlm2_s4096").family


def test_reference_forward_matches_llama_functional():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import llama_functional as lf

    arch, fam = tiny.TINY_ARCH, _dense()
    args = fam.serve_args(arch)
    assert args == lf.LlamaArgs(8192, 128, 256, 2, 4, 2, 10000.0, 1e-05)
    params = weights.make_params(fam, arch, 7, jnp.float32)
    rng = np.random.default_rng(0)
    prompt, toks = rng.integers(1, 8192, 37), rng.integers(1, 8192, 9)
    seq = np.concatenate([prompt, toks[:-1]])[None]
    want = lf.forward(params, jnp.asarray(seq), args, remat=False)[0, 36:]
    got = reference.served_logits(fam, arch, 7, [(prompt, toks)])[0]
    # the reference makes bf16 weights; the program got the same seed in f32
    params16 = weights.make_params(fam, arch, 7, jnp.bfloat16)
    want16 = lf.forward(jax.tree.map(lambda a: a.astype(jnp.float32),
                                     params16),
                        jnp.asarray(seq), args, remat=False)[0, 36:]
    assert np.abs(np.asarray(got) - np.asarray(want16)).max() < 2e-5
    assert np.abs(np.asarray(want) - np.asarray(want16)).max() > 1e-4


# -- the seeded weights did not move ---------------------------------------------------

# sha256, first 16 hex digits, of each leaf's bytes for TINY_ARCH at seed
# 2**31 + 17 on the CPU, taken on PR 25's commit (before the family seam):
# the dense cells' weights, and with them every limit of `correct` that PR 23
# measured, keep their meaning
PINNED = {
    "make_params/bfloat16": {
        "embedding": "3b87218fe64b5424", "final_norm": "e8f2deae18dc7d31",
        "layers/ln1": "712abf736882fb9e", "layers/ln2": "50812d5544ea735d",
        "layers/w_down": "bcca87ff7df45c1a",
        "layers/w_gate": "966a4b84f21a0f20",
        "layers/w_up": "cfa3053c535fa496", "layers/wk": "c6ab4be5686c4aad",
        "layers/wo": "7b35370a19244ef1", "layers/wq": "720ce1fa22b23b9d",
        "layers/wv": "f4e3efb5b0fead87", "lm_head": "09768780812b4b7b"},
    "make_params/float32": {
        "embedding": "6097995b0a284c5f", "final_norm": "ab80832d8b53aa77",
        "layers/ln1": "caacae945ec63668", "layers/ln2": "a0da556b22337508",
        "layers/w_down": "45618fd35cba1ebe",
        "layers/w_gate": "f98dd1752fcda0eb",
        "layers/w_up": "f17fa5385bf7f230", "layers/wk": "058e791bcd380298",
        "layers/wo": "d779d707b37c80ae", "layers/wq": "c728f724e29d173f",
        "layers/wv": "0ccbdfee3f1a23ff", "lm_head": "ba777248acc65fdb"},
    "layer_params/1/bfloat16": {
        "ln1": "71683180dbbb4f9f", "ln2": "9c47c55ecc2e0361",
        "w_down": "58eb39d03e85901d", "w_gate": "dbdc9e98434934a1",
        "w_up": "bfa2beb7bcd05d6d", "wk": "7c53fe105e916fa6",
        "wo": "e1b638d8bb952ba5", "wq": "7569f1f094914cb0",
        "wv": "30a7b7015ce116e4"}}


def _digests(tree):
    from benchmarks.harness.driver_train import _leaf_names

    out = {}
    for name, leaf in _leaf_names(tree).items():
        a = np.asarray(leaf)
        a = a.view(np.uint16) if a.dtype.itemsize == 2 else a
        out[name] = hashlib.sha256(a.tobytes()).hexdigest()[:16]
    return out


@pytest.mark.parametrize("which", sorted(PINNED))
def test_seeded_weights_are_bit_identical_to_pr25(which):
    import jax.numpy as jnp

    fam, arch, seed = _dense(), tiny.TINY_ARCH, 2**31 + 17
    if which.startswith("make_params"):
        dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            which.split("/")[1]]
        tree = weights.make_params(fam, arch, seed, dtype)
        # a leaf alone, as the train driver reads it back, is the same leaf
        for name, leaf in weights.leaves(fam, arch, seed, dtype):
            assert _digests({name: leaf})[name] == PINNED[which][name]
    else:
        tree = weights.layer_params(fam, arch, seed, 1)
    assert _digests(tree) == PINNED[which]


# -- the harness holds no leaf name and no head size ------------------------------------

def test_weights_and_reference_take_any_leaves_and_any_head_size(root):
    """The rehearsal family under leaf names of its own and a head size that
    is NOT hidden // heads (4 heads of 48 at hidden 128), through the
    weights and the reference alone: the program cannot run either today."""
    import jax.numpy as jnp

    alt = _cell(root, "alt_train").family
    arch = dict(tiny.ALT_ARCH, head_size=48)
    with pytest.raises(ValueError):
        alt.serve_args(arch)
    mine = {n: "blk_" + n for n in alt.layer_shapes(arch)}
    back = {v: k for k, v in mine.items()}
    fam = types.SimpleNamespace(
        layer_shapes=lambda a: {mine[n]: s
                                for n, s in alt.layer_shapes(a).items()},
        leaf_init=lambda a: {mine[n]: i for n, i in alt.leaf_init(a).items()},
        decoder_layer=lambda x, w, a, mm: alt.decoder_layer(
            x, {back[n]: v for n, v in w.items()}, a, mm))
    params = weights.make_params(fam, arch, 5, jnp.float32)
    assert set(params["layers"]) == set(back)
    assert params["layers"]["blk_wq"].shape == (2, 128, 4 * 48)
    assert params["layers"]["blk_wo"].shape == (2, 4 * 48, 128)
    # the stated init of one leaf (std 0.01) beside the rule's (0.02)
    assert float(params["layers"]["blk_wo"].std()) == pytest.approx(
        0.01, rel=0.02)
    assert float(params["layers"]["blk_wq"].std()) == pytest.approx(
        0.02, rel=0.02)
    rng = np.random.default_rng(0)
    prompt, toks = rng.integers(1, 8192, 30), rng.integers(1, 8192, 5)
    logits, = reference.served_logits(fam, arch, 5, [(prompt, toks)])
    assert logits.shape == (5, 8192) and np.isfinite(logits).all()
    ref = reference.TrainReference(fam, arch, 5, (3e-4, 0.9, 0.999, 1e-8,
                                                  0.01), dtype=jnp.float32)
    ids = rng.integers(1, 8192, (2, 1025)).astype(np.int32)
    loss = ref.train_step(ids[:, :-1], ids[:, 1:])
    assert np.isfinite(loss)
    grads = ref.grad_norms()
    assert {k for k in grads if k.startswith("layers/")} == {
        "layers/" + n for n in back}
    assert all(g > 0 for g in grads.values())
    assert all(d > 0 for d in ref.delta_norms().values())


def test_compiles_counts_every_program_the_engine_counts():
    from benchmarks.harness.driver_serve import ServeRun

    run = ServeRun.__new__(ServeRun)
    counters = {"prefill_compiles": 2, "decode_compiles": 1,
                "block_step_compiles": 4, "cow_copies": 9}
    run.eng = types.SimpleNamespace(metrics=types.SimpleNamespace(
        summary=lambda: {"counters": counters}))
    assert run._compiles() == 7


# -- the drivers end to end, and adding a cell or a family needs no edit ---------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.tiny_root(tmp_path_factory.mktemp("bench"))


def _run(root, cell, trace, capsys, seed=2**31 + 17, seconds=2):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)],
                  require_chip=False, root=root)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), out


@pytest.mark.parametrize("cell,metric", [
    ("tiny_train", "train_tokens_per_s"),
    ("tiny_sessions", "itl_mean_ms"),
    ("tiny_backlog", "serve_out_tokens_per_s")])
def test_added_cell_runs_end_to_end(root, cell, metric, capsys):
    rc, res, out = _run(root, cell, 0, capsys)
    assert rc == 0 and res["correct"] is True and res["failed"] == 0
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "compared"]
    # every number compared beside its limit, as the `compare:` lines say
    rows = [line.split() for line in out if line.startswith("compare:")]
    assert [r[1] for r in rows] == list(res["compared"])
    for r in rows:
        got = res["compared"][r[1]]
        assert float(r[3]) == pytest.approx(got["value"], rel=1e-5)
        assert float(r[5]) == pytest.approx(got["limit"], rel=1e-5)
    # and where the run's time went, before the result
    assert out[-2].startswith("phases: ")
    phases = run.parse_phases("\n".join(out))
    first = "first steps" if cell == "tiny_train" else "warm-up"
    assert list(phases)[:2] == ["start-up", first]
    assert list(phases)[-2:] == ["check", "whole run"]
    assert phases["window"] >= 2.0
    assert sum(v for k, v in phases.items() if k != "whole run") == \
        pytest.approx(phases["whole run"], abs=0.4)
    assert res["metrics"][metric]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0
    assert res["attempted"] > 0
    assert any(line.startswith("compare:") for line in out)


@pytest.mark.parametrize("cell,correct", [
    ("alt_train", True), ("alt_backlog", True),
    ("altwrong_train", False), ("altwrong_backlog", False)])
def test_added_family_runs_end_to_end_and_its_reference_decides(
        root, cell, correct, capsys):
    """A family that came as files: its cells are correct by ITS reference,
    and not correct where that reference's first norm lost its weight, so
    the run read the added file."""
    rc, res, out = _run(root, cell, 0, capsys)
    assert rc == 0 and res["failed"] == 0 and res["attempted"] > 0
    assert res["correct"] is correct
    rows = [line for line in out if line.startswith("compare:")]
    assert rows and any("NOT OK" in r for r in rows) is not correct


def test_a_family_of_two_kinds_of_layer_is_served_and_correct(root, capsys):
    """`data/tiny_kinds.py`, added by files alone: a dense leading layer and
    expert layers whose leaves differ in name and shape, each kind stacked
    apart by the harness, served through `PagedEngine` and judged by the
    generic reference, which hands each layer its kind's leaves."""
    import jax.numpy as jnp

    cell = _cell(root, "kinds_backlog")
    tree = weights.make_params(cell.family, cell.config, 3, jnp.bfloat16)
    assert set(tree) == {"dense_layers", "layers", "embedding", "final_norm",
                         "lm_head"}
    assert tree["dense_layers"]["w_gate"].shape == (1, 64, 160)
    assert tree["layers"]["we_gate"].shape == (2, 4, 64, 32)
    assert "router" not in tree["dense_layers"]
    assert "w_gate" not in tree["layers"]
    rc, res, out = _run(root, "kinds_backlog", 0, capsys)
    rows = [line for line in out if line.startswith("compare:")]
    assert rc == 0 and res["correct"] is True and res["failed"] == 0, rows
    assert res["attempted"] > 0
    # the reference's DENSE layer alone made wrong: not correct, so the
    # dense kind's leaves went to the layer they belong to
    rc, res, out = _run(root, "kindswrong_backlog", 0, capsys)
    rows = [line for line in out if line.startswith("compare:")]
    assert rc == 0 and res["correct"] is False and res["failed"] == 0, rows


def test_the_added_family_and_cells_needed_no_edit(root):
    theirs, mine = (os.path.join(r, "benchmarks") for r in (root, ROOT))
    same = ["run.py", "control.py",
            os.path.join("families", "dense_gqa_swiglu.py")]
    same += [os.path.join("harness", f)
             for f in os.listdir(os.path.join(mine, "harness"))
             if f.endswith(".py")]
    same += [os.path.join(d, f) for d in ("configs", "traffic", "workloads",
                                          "metrics")
             for f in os.listdir(os.path.join(mine, d))
             if not f.startswith("__")]
    assert len(same) > 30
    match, mismatch, errors = filecmp.cmpfiles(mine, theirs, same,
                                               shallow=False)
    assert (mismatch, errors) == ([], [])


def test_traced_run_reports_per_layer_and_the_added_metric(root, capsys):
    rc, res, _ = _run(root, "tiny_backlog", 1, capsys)
    assert rc == 0 and res["correct"] is True
    m = res["metrics"]
    assert "setup_s" not in m and m["tiny_steps"]["value"] > 0
    assert m["decode_step_ms.saturated"]["value"] > 0
    assert 0 < m["slot_occupancy.saturated"]["value"] <= 100
    assert m["prefill_tokens_per_s.saturated"]["value"] > 0
    # no device plane on the CPU: the device-trace readers return nothing
    assert "device_idle_share.saturated" not in m


# -- `correct` can fail: the control, and a broken timed path ---------------------------

@functools.cache
def _cell(root, name):
    return Cell(name, root)


@pytest.mark.parametrize("seed", [3, 2**31 + 4, 55])
def test_control_fp8_fails_the_served_limit(root, seed):
    """The reference in fp8, put in the program's place: at each position of
    the same prompt and tokens, the gap of the token it puts first."""
    cell = _cell(root, "tiny_sessions")
    rng = np.random.default_rng(seed)
    prompt, toks = rng.integers(1, 8192, 512), rng.integers(1, 8192, 513)
    ref, = reference.served_logits(cell.family, cell.config, seed,
                                   [(prompt, toks)])
    low, = reference.served_logits(cell.family, cell.config, seed,
                                   [(prompt, toks)], reference.fp8_mm)
    gap = reference.served_gap(ref, np.asarray(low).argmax(-1))
    assert gap.mean() > cell.spec["limits"]["served_gap_mean"]


@pytest.mark.parametrize("seed", [3, 2**31 + 4, 55])
def test_control_fp8_fails_a_training_limit(root, seed):
    from benchmarks.harness import driver_train

    cell = _cell(root, "tiny_train")
    hp = cell.spec["optimizer"]
    hp = (hp["lr"], hp["beta1"], hp["beta2"], hp["eps"], hp["weight_decay"])
    data = traffic.TrainTraffic(cell.traffic, 8192, seed)
    out = {}
    for name, mm in (("ref", reference.f32_mm), ("low", reference.fp8_mm)):
        import jax.numpy as jnp

        r = reference.TrainReference(cell.family, cell.config, seed, hp, mm,
                                     jnp.float32)
        losses = [r.train_step(*data.batch(0))]
        out[name] = {"losses": losses, "grad_norms": r.grad_norms(),
                     "delta_norms": r.delta_norms()}
    rows = driver_train.compare(out["low"], out["ref"], cell.spec["limits"],
                                lambda m: None)
    assert any(v > lim for _, v, lim in rows)


def test_broken_train_step_is_not_correct(root, capsys, monkeypatch):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.distributed.hybrid_engine import HybridParallelEngine

    real = HybridParallelEngine.train_batch

    def unchanged(self, params, opt, ids, labels):
        copy = jax.tree.map(jnp.copy, (params, opt))
        loss, _, _ = real(self, *copy, ids, labels)
        return loss, params, opt

    monkeypatch.setattr(HybridParallelEngine, "train_batch", unchanged)
    rc, res, _ = _run(root, "tiny_train", 0, capsys)
    assert rc == 0 and res["correct"] is False


@pytest.mark.parametrize("which", ["every_fifth_token", "one_request"])
def test_altered_served_token_is_not_correct(root, capsys, monkeypatch, which):
    """A token altered where it is emitted: one in five of all, or the
    tokens of ONE request among those the run finishes (every finished
    request is compared while they are within COMPARE_MAX requests and
    COMPARE_TOKENS tokens, so a fault in one slot fails the run; the toy
    finishes hundreds, so both bounds are lifted for that case and stand
    for the other)."""
    from benchmarks.harness import driver_serve
    from paddle_tpu.serving.engine import Engine

    if which == "one_request":
        monkeypatch.setattr(driver_serve, "COMPARE_MAX", 10**6)
        monkeypatch.setattr(driver_serve, "COMPARE_TOKENS", 10**9)

    real, n = Engine._emit, [0]

    def altered(self, req, token):
        n[0] += 1
        hit = (n[0] % 5 == 0 if which == "every_fifth_token"
               else req.request_id == 3)
        return real(self, req, (token + 1) % 8192 if hit else token)

    monkeypatch.setattr(Engine, "_emit", altered)
    rc, res, out = _run(root, "tiny_backlog", 0, capsys)
    assert rc == 0 and res["correct"] is False
    compared = [line for line in out if "finished requests" in line][0]
    n_compared, n_finished = map(int, re.search(
        r"correct: (\d+) of (\d+) finished", compared).groups())
    assert n_compared == (n_finished if which == "one_request"
                          else driver_serve.COMPARE_MAX)


@pytest.mark.xfail(reason="engine fault found by PR 23, not the benchmark's to "
                   "repair: with prefix_policy='radix' the third turn of a "
                   "session (a prefix hit through a page that an earlier "
                   "turn's hit copied on write) is served from wrong K/V; "
                   "'hash' agrees with the reference exactly", strict=False)
def test_third_turn_of_a_session_agrees_with_the_reference():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.serving import PagedEngine, Request

    arch, fam = tiny.TINY_ARCH, _dense()
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          weights.make_params(fam, arch, 11, jnp.bfloat16))
    eng = PagedEngine(params, fam.serve_args(arch), max_slots=3, max_len=512,
                      page_size=16, num_pages=120, min_bucket=16,
                      prefill_chunk=32, prefix_policy="radix")
    rng = np.random.default_rng(0)
    hist = rng.integers(1, 8192, 70).astype(np.int32)
    worst = 0.0
    for user, answer in [(23, 9), (19, 11), (27, 12)]:
        prompt = np.concatenate(
            [hist, rng.integers(1, 8192, user).astype(np.int32)])
        req = eng.submit(Request(prompt, answer))
        eng.run_until_idle()
        toks = np.asarray(req.token_ids, np.int32)
        gap = reference.served_gap(
            reference.served_logits(fam, arch, 11, [(prompt, toks)])[0],
            toks)
        worst = max(worst, float(gap.max()))
        hist = np.concatenate([prompt, toks])
    assert worst < 1e-3      # float32 program against the float32 reference
