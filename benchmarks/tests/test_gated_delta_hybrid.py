"""CPU tests of the `gated_delta_hybrid` family (Olmo-Hybrid) under the
committed harness: a tiny cell of the family runs end to end through the
unedited serve driver (its leaves stacked BY KIND, its warm-up reaching the
copy-on-write over the full layers' pages) and is `correct`; the reference
computed in fp8 in the program's place is not; the seeded weights, the
configuration's sizes and the readers' counts are pinned to numbers worked
by hand. Run with `pytest benchmarks/tests` (not tier-1; the tier-1 file is
`tests/test_gated_delta_serving.py`)."""

import json
import os
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

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks import run  # noqa: E402
from benchmarks.harness import driver_serve, reference, weights  # noqa: E402
from benchmarks.harness.spec import Cell  # noqa: E402
from benchmarks.tests import tiny  # noqa: E402

CELL = "serve_olmo_hybrid_chat_replies"
# every ratio of the published model kept: key width half the value width, a
# 4-tap convolution, heads = KV heads, one period of 3 linear + 1 full
GDH_ARCH = {
    "source": "none: a toy for the CPU tests", "family": "gated_delta_hybrid",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 4, "vocab_size": 256,
    "rms_norm_eps": 1e-06, "initializer_range": 0.15,
    "layer_types": ["linear_attention"] * 3 + ["full_attention"],
    "linear_num_key_heads": 4, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}, "reduced": [], "assumed": {}}
GDH_MIX = {
    "kind": "serve", "arrival": {"process": "backlog", "queue_depth": 3},
    "ramp_steps": 10, "pool": 8, "tenants": 0, "system_prompt_tokens": 0,
    "turns": {"min": 1, "max": 1},
    "user_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                    "min": 12, "max": 90},
    "answer_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                      "min": 4, "max": 24},
    "think_s": {"dist": "const", "value": 0.0}, "max_context": 126}
# the driver serves bfloat16 on the CPU too. Over seeds 5, 7, 11 and 2**31 +
# 23 sound runs read a widest gap of at most 0.056 and a mean of at most
# 5.8e-4 (which requests a 2 s window holds follows the machine's pace), the
# fp8 control of seed 5 0.63 and 0.071: each limit near the geometric mean
# of its two readings
GDH_CELL = {
    "kind": "serve",
    "engine": {"max_slots": 3, "max_len": 128, "page_size": 8,
               "num_pages": 80, "min_bucket": 8, "prefill_chunk": 16,
               "kv_dtype": None, "prefix_policy": "radix"},
    "limits": {"served_gap_widest": 0.2, "served_gap_mean": 0.006}}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = tiny.tiny_root(tmp_path_factory.mktemp("gdh"))
    b = os.path.join(tmp, "benchmarks")
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(b, "traffic", "tiny_replies.json"), "w") as f:
        json.dump(GDH_MIX, f)
    with open(os.path.join(b, "configs", "gdh.json"), "w") as f:
        json.dump(GDH_ARCH, f)
    with open(os.path.join(b, "workloads", "gdh.json"), "w") as f:
        json.dump(GDH_CELL, f)
    bench["configs"].append({"name": "gdh", "source": "none",
                             "file": "benchmarks/configs/gdh.json",
                             "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": "gdh", "config": "gdh",
                               "traffic": "tiny_replies", "chips": 1,
                               "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("gdh")
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp


@pytest.fixture(scope="module")
def fam():
    return Cell(CELL).family


@pytest.fixture(scope="module")
def arch():
    return Cell(CELL).config


def _run(root, capsys, seed, trace=0):
    rc = run.main(["--workload", "gdh", "--seed", str(seed), "--seconds", "2",
                   "--trace", str(trace)], require_chip=False, root=root)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), out


@pytest.mark.parametrize("seed", [5, 2**31 + 23])
def test_tiny_cell_of_the_family_is_correct(root, capsys, seed):
    """Through `PagedEngine.submit` / `step` under the unedited driver: the
    warm-up raises unless a prefix hit that ends mid-page copied the full
    layers' page on write (and loaded a snapshot of both states)."""
    rc, res, out = _run(root, capsys, seed)
    rows = [line for line in out if line.startswith("compare:")]
    assert rc == 0 and res["correct"] is True and res["failed"] == 0, rows
    assert res["attempted"] > 0
    assert res["metrics"]["serve_out_tokens_per_s"]["value"] > 0
    assert res["compared"]["compiles_in_window"]["value"] == 0


def test_the_reference_in_fp8_is_not_correct(root):
    """`benchmarks/control.py` at test size: the reference computed in fp8
    put in the program's place fails BOTH limits; the sound run beside it
    passes both."""
    cell = Cell("gdh", root)
    run_ = driver_serve.ServeRun(cell, 5, jax.devices()[:1], lambda m: None)
    run_.warm_up()
    run_.run(2.0)
    sample = run_.sample()
    run_.free()
    logits = run_.reference_logits(sample)
    sound = {n: (v, lim) for n, v, lim in run_.check(sample, logits)}
    low = run_.reference_logits(sample, reference.fp8_mm)
    control = {n: (v, lim) for n, v, lim in run_.check(
        sample, logits, [x.argmax(-1) for x in low])}
    for name in ("served_gap_widest", "served_gap_mean"):
        assert sound[name][0] <= sound[name][1], (name, sound[name])
        assert control[name][0] > control[name][1], (name, control[name])


def test_traced_run_reads_the_engines_observations(root, capsys):
    """On the CPU there is no device plane: the device-trace readers give
    nothing (and do not raise); the engine's own observations read."""
    rc, res, out = _run(root, capsys, 7, trace=1)
    assert rc == 0 and res["correct"] is True, [
        line for line in out if line.startswith("compare:")]
    m = res["metrics"]
    assert m["decode_step_ms.chat_replies"]["value"] > 0
    assert m["prefill_tokens_per_s.chat_replies"]["value"] > 0
    assert m["schedule_ms.chat_replies"]["value"] > 0
    assert 0 < m["slot_occupancy.chat_replies"]["value"] <= 100
    assert 0 < m["pool_fill_peak_share.chat_replies"]["value"] <= 100
    assert 0 < m["decode_live_page_share.chat_replies"]["value"] <= 100
    for name in ("delta_rule_time_share", "delta_rule_roofline",
                 "short_conv_time_share", "full_attn_time_share",
                 "device_idle_share.chat_replies"):
        assert name not in m


def test_the_seeded_weights_are_pinned(fam):
    """The same (seed, layer, leaf) gives the same numbers on every
    machine: the served model is a function of the seed alone. The two kinds
    are stacked apart, in layer order."""
    tree = weights.make_params(fam, GDH_ARCH, 11, jnp.float32)
    shapes = fam.layer_shapes(GDH_ARCH)
    assert sorted(tree["linear_attention"]) == sorted(
        shapes["linear_attention"])
    assert sorted(tree["full_attention"]) == sorted(shapes["full_attention"])
    assert tree["linear_attention"]["conv_w"].shape == (3, 128, 4)
    assert tree["full_attention"]["wq"].shape == (1, 64, 64)
    for i, (kind, at) in enumerate([("linear_attention", 0),
                                    ("linear_attention", 1),
                                    ("linear_attention", 2),
                                    ("full_attention", 0)]):
        one = weights.layer_params(fam, GDH_ARCH, 11, i, jnp.float32)
        for name in one:
            np.testing.assert_array_equal(one[name], tree[kind][name][at])
    lin, full = tree["linear_attention"], tree["full_attention"]
    got = [float(lin["wq"][1, 0, 0]), float(lin["A_log"][2, 1]),
           float(lin["dt_bias"][0, 3]), float(lin["conv_w"][1, 5, 2]),
           float(full["q_norm"][0, 2])]
    np.testing.assert_allclose(got, PINNED, rtol=1e-6)


PINNED = [-0.2916118800640106, 1.8302555084228516, -3.358168363571167,
          -0.00010947773262159899, 0.8796067237854004]


def test_the_configurations_sizes_from_its_keys(arch, fam):
    """3,268 M parameters, 46,080 bytes a cached token, 19.9 MB of matrix
    state and 0.6 MB of convolution state a request, recomputed from the
    configuration's keys: the arithmetic of PERF.md section 4."""
    shapes = fam.layer_shapes(arch)
    linear = sum(int(np.prod(s)) for s in shapes["linear_attention"].values())
    full = sum(int(np.prod(s)) for s in shapes["full_attention"].values())
    assert round(linear / 1e6, 1) == 215.6 and round(full / 1e6, 1) == 185.8
    assert round((3 * linear + full) / 1e6, 1) == 832.5     # one period
    assert 2 * arch["vocab_size"] * arch["hidden_size"] == 770_703_360
    assert fam.param_count(arch) == 9 * linear + 3 * full + 770_703_360 + 3840
    assert round(fam.param_count(arch) / 1e6) == 3268
    assert round(2 * fam.param_count(arch) / 1e9, 2) == 6.54
    args = fam.serve_args(arch)
    assert (args.num_heads, args.head_dim) == (30, 128)
    assert (args.linear_heads, args.linear_key_dim, args.linear_value_dim,
            args.conv_kernel, args.conv_channels) == (30, 96, 192, 4, 11520)
    kv_token = len(args.layers_of("full_attention")) * 30 * 128 * 2 * 2
    assert kv_token == 46080
    n_lin = len(args.layers_of("linear_attention"))
    assert n_lin * 30 * 96 * 192 * 4 == 19_906_560           # 19.9 MB
    assert n_lin * 3 * 11520 * 2 == 622_080                  # 0.6 MB
    assert fam.delta_state_bytes(arch) == n_lin * 4_423_680
    spec = Cell(CELL).spec["engine"]
    assert spec["num_pages"] * spec["page_size"] * kv_token == 5_662_310_400


def test_a_reduced_key_is_listed_and_no_width_changed(arch):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "olmo-hybrid-7b-1chip")
    assert sorted(entry["reduced"]) == sorted(arch["reduced"]) == [
        "layer_types", "num_hidden_layers"]
    for key, value in arch["published"].items():
        assert key in arch["reduced"] and arch[key] != value
    # the cut keeps the published order: its first 12 layers, 3 : 1
    assert arch["layer_types"] == arch["published"]["layer_types"][:12]
    assert arch["layer_types"].count("linear_attention") == 9
    for key, value in (("hidden_size", 3840), ("intermediate_size", 11008),
                       ("num_attention_heads", 30),
                       ("num_key_value_heads", 30),
                       ("linear_key_head_dim", 96),
                       ("linear_value_head_dim", 192),
                       ("linear_conv_kernel_dim", 4),
                       ("vocab_size", 100352)):
        assert arch[key] == value and key not in arch["reduced"]
    assert sorted(arch["assumed"]) == sorted(
        ["block_shape", "qk_norm", "no_rotary", "leaf_init", "head_dim",
         "mechanism"])


def test_traced_works_counts_by_hand(arch, fam):
    """One prefill window of 2,048 tokens at 0, one decode step of one row,
    on a described chip of 1 flop/s and 1 byte/s."""
    # a token, head and layer at a chunk of 64: K K^T and Q K^T 2 x 2 x 64 x
    # 96, the solve 64 x 288, three state products 3 x 2 x 96 x 192, A U 2 x
    # 64 x 192
    per_head = 24576 + 18432 + 110592 + 24576
    assert fam.delta_flops_per_token(arch) == 9 * 30 * per_head
    assert fam.delta_state_bytes(arch) == 9 * 30 * 96 * 192 * 4 * 2
    rec = types.SimpleNamespace(rid=1, submitted=0.0, prompt=np.zeros(2048),
                                times=[1.5, 2.5])
    ctx = types.SimpleNamespace(
        trace={"busy_s": 1.0}, peaks={"bf16_flops": 1.0,
                                      "hbm_bytes_per_s": 1.0},
        arch=arch, engine_kw={"prefill_chunk": 2048},
        trace_host_window=(0.0, 10.0),
        run=types.SimpleNamespace(recs={1: rec}),
        spans=[("prefill", 1.0, 1.5, 1), ("decode", 2.0, 2.5, 1)])
    need = fam.traced_work(ctx)
    assert need == {"delta": pytest.approx(
        2048 * 9 * 30 * per_head + 2 * 9 * 30 * 96 * 192 * 4 * 2)}
    ctx.trace = None                          # a run with no device trace
    assert fam.traced_work(ctx) is None


def test_keys_padded_to_whole_buckets_change_no_logit(fam, monkeypatch):
    """Past one key bucket the reference pads a full layer's keys and values
    up to whole buckets, so that a request's own length compiles nothing.
    With the token block cut to 32 and the bucket to 64, 90 tokens (96
    positions) take that path; at the real sizes they are attended in one
    block: the same hidden state to float32 rounding (values up to 4 after
    eight normed sublayers; 7e-5 seen, a dropped key moves them by 1e-2)."""
    ids = np.random.default_rng(3).integers(1, 256, 90)
    emb = weights.outer_params(GDH_ARCH, 5, jnp.float32)["embedding"]

    def hidden():
        return np.asarray(fam.forward_hidden(
            GDH_ARCH, ids, lambda i: weights.layer_params(
                fam, GDH_ARCH, 5, i, jnp.float32), emb))

    whole = hidden()
    monkeypatch.setattr(fam, "T_BLOCK", 32)
    monkeypatch.setattr(fam, "K_BUCKET", 64)
    blocked = hidden()
    assert np.abs(whole).max() > 1
    np.testing.assert_allclose(blocked, whole, rtol=0, atol=3e-4)
