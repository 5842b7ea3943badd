"""CPU tests of the `mla_delta_moe` family (GigaChat 3.5) under the committed
harness: a tiny cell of the family runs end to end through the unedited
serve driver (its warm-up reaches the copy-on-write of a latent page beside a
loaded snapshot) and is `correct`; with the PROGRAM made wrong (one line of
`serve_args` each) the same run is not; the configuration's cuts and the
readers' counts are pinned to numbers worked by hand. Run with `pytest
benchmarks/tests` (not tier-1; the tier-1 file is
`tests/test_latent_delta_serving.py`)."""

import json
import os
import re
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
from benchmarks.harness.spec import Cell  # noqa: E402
from benchmarks.tests import tiny  # noqa: E402

CELL = "serve_gigachat35_reasoning_traces"
# the published PATTERN at toy widths: 1 dense + 1 latent + 3 delta layers, 2
# key heads serving 4 value heads, 32 experts of which 8 are held and 4 picked
# by sigmoid score + bias, a clamp low enough to bind
TOY_ARCH = {
    "source": "none: a toy for the CPU tests", "family": "mla_delta_moe",
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 5, "first_k_dense_replace": 1,
    "full_attention_layers": [1], "num_attention_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 8,
    "n_shared_experts": 1, "n_group": 1, "topk_group": 1,
    "num_experts_per_tok": 4, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "vocab_size": 256, "rms_norm_eps": 1e-06,
    "rope_theta": 10000, "initializer_range": 0.05, "router_bias_std": 0.05,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 8,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 64, "type": "yarn"},
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "linear_attn_o_norm_eps": 1e-06,
    "linear_sigmoid_gate_scale": 2, "swiglu_limit": 0.5,
    "published": {"n_routed_experts": 32},
    "deployment": {"chips_per_layer": 4, "first_expert_held": 8},
    "reduced": [], "assumed": {}}
TOY_MIX = {
    "kind": "serve", "arrival": {"process": "backlog", "queue_depth": 3},
    "ramp_steps": 10, "pool": 8, "tenants": 0, "system_prompt_tokens": 0,
    "turns": {"min": 1, "max": 1},
    "user_tokens": {"dist": "lognormal", "median": 32, "sigma": 0.5,
                    "min": 8, "max": 80},
    "answer_tokens": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                      "min": 6, "max": 40},
    "think_s": {"dist": "const", "value": 0.0}, "max_context": 126}
# the driver serves bfloat16 on the CPU too: see the limits' note in
# `test_tiny_cell_of_the_family_is_correct`
TOY_CELL = {
    "kind": "serve",
    "engine": {"max_slots": 3, "max_len": 128, "page_size": 8,
               "num_pages": 80, "min_bucket": 8, "prefill_chunk": 16,
               "kv_dtype": None, "prefix_policy": "radix"},
    "limits": {"served_gap_widest": 0.2, "served_gap_mean": 0.008}}

FAMILY = os.path.join(ROOT, "benchmarks", "families", "mla_delta_moe.py")
# the toy routes 4 of 32 over a width of 64 in bfloat16: its scores move by a
# tenth of a score (sound runs on the CPU: the widest shortfall 0.06-0.12, 6.6-
# 7.9% of the token-layers followed), where the published widths' move by
# under a hundredth. The toy family's files state the toy's tolerance
TOY_TOL = (r"ROUTING_TOL = [0-9.]+", "ROUTING_TOL = 0.25")
# the PROGRAM made wrong, one line of `serve_args` each (the reference left
# alone): no clamp in the feed-forwards; the picked weights left
# unnormalised; the share told it holds experts 0..7 where its weights are
# those of 8..15
WRONG = {
    "noclamp": (r'swiglu_limit=float\(arch\["swiglu_limit"\]\)',
                "swiglu_limit=None"),
    "nonorm": (r'norm_topk=bool\(arch\["norm_topk_prob"\]\)',
               "norm_topk=False"),
    "share": (r"first_expert=first", "first_expert=0"),
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = tiny.tiny_root(tmp_path_factory.mktemp("gc35"))
    b = os.path.join(tmp, "benchmarks")
    with open(FAMILY) as f:
        text = f.read()
    text, n = re.subn(*TOY_TOL, text)
    assert n == 1
    with open(os.path.join(b, "families", "gc35_toy.py"), "w") as f:
        f.write(text)
    names = {"gc35": "gc35_toy"}
    for name, (pattern, repl) in WRONG.items():
        wrong, n = re.subn(pattern, repl, text)
        assert n == 1, f"serve_args lost the line to break for {name}"
        with open(os.path.join(b, "families", f"gc35_{name}.py"), "w") as f:
            f.write(wrong)
        names[f"gc35_{name}"] = f"gc35_{name}"
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(b, "traffic", "tiny_traces.json"), "w") as f:
        json.dump(TOY_MIX, f)
    for cell, family in names.items():
        with open(os.path.join(b, "configs", cell + ".json"), "w") as f:
            json.dump(dict(TOY_ARCH, family=family), f)
        with open(os.path.join(b, "workloads", cell + ".json"), "w") as f:
            json.dump(TOY_CELL, f)
        bench["configs"].append({"name": cell, "source": "none",
                                 "file": f"benchmarks/configs/{cell}.json",
                                 "reduced": [], "why": "toy"})
        bench["workloads"].append({"name": cell, "config": cell,
                                   "traffic": "tiny_traces", "chips": 1,
                                   "why": "toy"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if CELL in m.get("workloads", []):
                m["workloads"].append(cell)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp


@pytest.fixture(scope="module")
def fam():
    return Cell(CELL).family


@pytest.fixture(scope="module")
def arch():
    return Cell(CELL).config


def _run(root, cell, capsys, seed, trace=0):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds", "2",
                   "--trace", str(trace)], require_chip=False, root=root)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), out


def _said(out):
    return [line for line in out if line.startswith(("compare:",
                                                     "correct: "))]


@pytest.mark.parametrize("seed", [5, 2**31 + 23])
def test_tiny_cell_of_the_family_is_correct(root, capsys, seed):
    """Through `PagedEngine.submit` / `step` under the unedited driver: the
    warm-up raises unless a prefix hit that ends mid-page copied the page on
    write; the routing's check reports. The limits: sound runs over seeds 5,
    7, 11 and 2**31 + 23 read a widest gap of at most 0.049 and a mean of at
    most 0.00081 (6.6-7.9% of their token-layers followed, the widest
    shortfall 0.12 of a score); the wrong programs below read 0.69-1.08 and
    0.092-0.21: each limit near the geometric mean of its two readings."""
    rc, res, out = _run(root, "gc35", capsys, seed)
    assert rc == 0 and res["correct"] is True and res["failed"] == 0, \
        _said(out)
    assert res["attempted"] > 0
    assert res["metrics"]["serve_out_tokens_per_s"]["value"] > 0
    assert any(line.startswith("correct: routing:") for line in out)


@pytest.mark.parametrize("which", sorted(WRONG))
def test_a_wrong_program_is_not_correct(root, capsys, which):
    rc, res, out = _run(root, f"gc35_{which}", capsys, 5)
    assert rc == 0 and res["failed"] == 0 and res["attempted"] > 0
    assert res["correct"] is False, _said(out)


def test_traced_run_reads_the_engines_observations(root, capsys):
    """On the CPU there is no device plane: the device-trace readers give
    nothing (and do not raise); the engine's own observations read."""
    rc, res, out = _run(root, "gc35", capsys, 7, trace=1)
    assert rc == 0 and res["correct"] is True, _said(out)
    m = res["metrics"]
    # 12,800 B of state a row in and out against 512 B a cached token at
    # contexts of tens of tokens
    assert 40 < m["recurrent_byte_share"]["value"] < 100
    assert 0 < m["routed_here_share.reasoning_traces"]["value"] < 100
    assert m["expert_load_max_over_mean.reasoning_traces"]["value"] >= 1
    assert m["decode_step_ms.reasoning_traces"]["value"] > 0
    assert 0 < m["decode_live_page_share.reasoning_traces"]["value"] <= 100
    assert 0 < m["slot_occupancy.reasoning_traces"]["value"] <= 100
    for name in ("delta_rule_roofline.reasoning_traces",
                 "latent_attn_roofline.reasoning_traces",
                 "expert_ffn_roofline.reasoning_traces",
                 "delta_rule_time_share.reasoning_traces",
                 "unscoped_time_share.reasoning_traces"):
        assert name not in m


def test_the_reader_gives_nothing_on_a_program_without_the_observations():
    read = Cell(CELL).reader("recurrent_byte_share")
    assert read(types.SimpleNamespace(counters={"observations": {}})) is None
    assert read(types.SimpleNamespace(counters={"observations": {
        "serve.state_bytes_step": {"sum": 300.0},
        "serve.cache_bytes_step": {"sum": 100.0}}})) == 75.0


def test_a_reduced_key_is_listed_and_no_width_changed(arch):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "gigachat3.5-1chip")
    assert sorted(entry["reduced"]) == sorted(arch["reduced"]) == sorted(
        arch["published"])
    for key, value in arch["published"].items():
        assert arch[key] != value
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if os.path.isfile(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "GigaChat3.5-432B-A28B")
        assert entry["source"] == arch["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert arch["published"].get(key, arch[key]) == value, key
    assert set(arch["not_served"]) == {"num_nextn_predict_layers"}
    assert arch["deployment"]["chips_per_layer"] == 16
    assert [k[:2] for k in sorted(arch["assumed"])[:8]] == [
        f"A{i}" for i in range(1, 9)]


def test_the_readers_counts_by_hand(arch, fam):
    """One prefill window of 2,048 tokens at 4,096 and one decode token at a
    context of 2,000, on a described chip of 1 flop/s and 1 byte/s."""
    assert fam.delta_state_bytes(arch) == 8388608
    C = 64
    assert fam.delta_flops_per_token(arch) == (
        32 * 4 * C * 128 + 64 * (C * 256 + 6 * 128 * 128 + 2 * C * 128))
    long_prompt = types.SimpleNamespace(
        rid=1, submitted=0.0, prompt=np.zeros(6144), times=[1.5])
    decoding = types.SimpleNamespace(
        rid=2, submitted=0.0, prompt=np.zeros(1999), times=[0.1, 2.5])
    ctx = types.SimpleNamespace(
        trace={"busy_s": 1.0}, peaks={"bf16_flops": 1.0,
                                      "hbm_bytes_per_s": 1.0},
        arch=arch, engine_kw={"prefill_chunk": 2048},
        trace_host_window=(0.5, 10.0),
        counters={"observations": {
            "serve.held_experts_hit": {"mean": 7.0},
            "serve.routed_here_share": {"mean": 0.0625}}},
        run=types.SimpleNamespace(recs={1: long_prompt, 2: decoding}),
        spans=[("prefill", 0.05, 0.1, 1),       # before the traced slice
               ("prefill_chunk", 0.6, 0.7, 0), ("prefill_chunk", 0.8, 0.9, 0),
               ("prefill", 1.0, 1.5, 1), ("decode", 2.0, 2.5, 1)])
    need = fam.traced_work(ctx)
    # windows [0, 2048), [2048, 4096), [4096, 6144) and the decode token, in
    # each of the 4 delta layers
    assert need["delta"] == pytest.approx(4 * (
        3 * (2048 * fam.delta_flops_per_token(arch) + 8388608) + 8388608))
    # ONE latent layer: the windows' (query, key) pairs in the form the
    # equations state, the decode token's keys in the absorbed form
    pairs = sum((lo + 1 + lo + 2048) * 2048 / 2 for lo in (0, 2048, 4096))
    assert need["latent"] == pytest.approx(
        pairs * 64 * 2 * (128 + 64 + 128) + 2000 * 64 * 2 * (576 + 512))
    # 4 expert layers: a window's pairs' flops, a decode step's 7 experts hit
    expert = 3 * 7168 * 2048
    assert need["experts"] == pytest.approx(4 * (
        3 * 2048 * 8 * 0.0625 * 2 * expert + 7.0 * 2 * expert))
    ctx.counters = {"observations": {}}       # a program without the counts
    assert fam.traced_work(ctx) is None
