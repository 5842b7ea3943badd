"""CPU tests of the `mla_moe` family (DeepSeek-V2) under the committed
harness: a tiny cell of the family runs end to end through the unedited
serve driver (its warm-up reaches the copy-on-write over the latent pool)
and is `correct`; with the family's REFERENCE made wrong the same run is
not; the seeded weights, the configuration's sizes and the readers' counts
are pinned to numbers worked by hand. Run with `pytest benchmarks/tests`
(not tier-1; the tier-1 file is `tests/test_latent_moe_serving.py`)."""

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

import jax.numpy as jnp  # noqa: E402

from benchmarks import run  # noqa: E402
from benchmarks.harness import weights  # noqa: E402
from benchmarks.harness.spec import Cell  # noqa: E402
from benchmarks.tests import tiny  # noqa: E402

CELL = "serve_deepseek_v2_long_answers"
# every ratio of the published model kept: 8 groups of 4, 3 of them stay, 6
# experts a token, one group of 4 held (group 1 of 8), a rotary slice, ranks
# below the width; no dense leading layer (the harness stacks one kind)
MLA_ARCH = {
    "source": "none: a toy for the CPU tests", "family": "mla_moe",
    "hidden_size": 64, "intermediate_size": 160,
    "moe_intermediate_size": 32, "num_hidden_layers": 2,
    "first_k_dense_replace": 0, "num_attention_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 4,
    "n_shared_experts": 2, "n_group": 8, "topk_group": 3,
    "num_experts_per_tok": 6, "routed_scaling_factor": 16,
    "vocab_size": 256, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "initializer_range": 0.15,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 64,
                     "type": "yarn"},
    "published": {"n_routed_experts": 32},
    "deployment": {"chips_per_layer": 8, "expert_group_held": 1},
    "reduced": [], "assumed": {}}
MLA_MIX = {
    "kind": "serve", "arrival": {"process": "backlog", "queue_depth": 3},
    "ramp_steps": 10, "pool": 8, "tenants": 0, "system_prompt_tokens": 0,
    "turns": {"min": 1, "max": 1},
    "user_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                    "min": 12, "max": 90},
    "answer_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                      "min": 4, "max": 24},
    "think_s": {"dist": "const", "value": 0.0}, "max_context": 126}
# the driver serves bfloat16 on the CPU too. Sound over 3 seeds (with the
# recorded routing followed): mean 1.1e-5, 5.2e-5 and 1.1e-3, widest 0.003,
# 0.007 and 0.18; wrong references: mean 0.085 (YaRN's factor of the scale
# dropped), 0.110 (the routed weights' scaling dropped), widest 0.48-0.60
# (the shared experts dropped: not read, it fails the mean too). The mean
# decides: its limit lies 3.6x over the sound runs' largest and 21x under
# those two
MLA_CELL = {
    "kind": "serve",
    "engine": {"max_slots": 3, "max_len": 128, "page_size": 8,
               "num_pages": 80, "min_bucket": 8, "prefill_chunk": 16,
               "kv_dtype": None, "prefix_policy": "radix"},
    "limits": {"served_gap_widest": 0.5, "served_gap_mean": 4e-3}}

FAMILY = os.path.join(ROOT, "benchmarks", "families", "mla_moe.py")
# the reference made wrong, one line each (the program's side, `serve_args`,
# is left alone): YaRN's factor of the scale dropped; the routed weights'
# scaling factor dropped; the shared experts dropped. (A reference whose OWN
# routing is wrong, say with no group limit, is not among them: it follows
# the routing the program recorded wherever that stands its check, and a
# looser rule of its own lets more stand. The routing rule itself is held
# to a written-out numpy rule in `tests/test_latent_moe_serving.py`.)
WRONG = {
    "noscale": (r"\*\* -0\.5 \\\n        \* m \* m", "** -0.5"),
    "noweight": (r'jnp\.where\(picked, arch\["routed_scaling_factor"\] '
                 r"\* scores,\n\s+0\.0\)", "jnp.where(picked, scores, 0.0)"),
    "noshared": (r"return x \+ shared_experts\(h, w, arch, mm\) \+ routed,",
                 "return x + routed,"),
    # the PROGRAM made wrong (`serve_args`), the reference left alone: no
    # group limit (picks from more groups than stay are no routing the rule
    # could make: refused); two groups kept for three (every pick among the
    # best of groups that do stay, so each stands the check: what holds
    # this one is the share of a request that may be followed)
    "nogroups": (r'topk_group=arch\["topk_group"\]',
                 'topk_group=arch["n_group"]'),
    "twogroups": (r'topk_group=arch\["topk_group"\]',
                  'topk_group=arch["topk_group"] - 1'),
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = tiny.tiny_root(tmp_path_factory.mktemp("mla"))
    b = os.path.join(tmp, "benchmarks")
    with open(FAMILY) as f:
        text = f.read()
    names = {"mla": "mla_moe"}
    for name, (pattern, repl) in WRONG.items():
        wrong, n = re.subn(pattern, repl, text)
        assert n == 1, f"the reference lost the line to break for {name}"
        with open(os.path.join(b, "families", f"mla_{name}.py"), "w") as f:
            f.write(wrong)
        names[f"mla_{name}"] = f"mla_{name}"
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(b, "traffic", "tiny_answers.json"), "w") as f:
        json.dump(MLA_MIX, f)
    for cell, family in names.items():
        with open(os.path.join(b, "configs", cell + ".json"), "w") as f:
            json.dump(dict(MLA_ARCH, family=family), f)
        with open(os.path.join(b, "workloads", cell + ".json"), "w") as f:
            json.dump(MLA_CELL, f)
        bench["configs"].append({"name": cell, "source": "none",
                                 "file": f"benchmarks/configs/{cell}.json",
                                 "reduced": [], "why": "toy"})
        bench["workloads"].append({"name": cell, "config": cell,
                                   "traffic": "tiny_answers", "chips": 1,
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


def _compared(out, name):
    row = next(line for line in out
               if line.startswith(f"compare: {name} "))
    return float(row.split("=")[1].split()[0])


@pytest.mark.parametrize("seed", [5, 2**31 + 23])
def test_tiny_cell_of_the_family_is_correct(root, capsys, seed):
    """Through `PagedEngine.submit` / `step` under the unedited driver: the
    warm-up raises unless a prefix hit that ends mid-page copied the latent
    page on write."""
    rc, res, out = _run(root, "mla", capsys, seed)
    rows = [line for line in out if line.startswith("compare:")]
    assert rc == 0 and res["correct"] is True and res["failed"] == 0, rows
    assert res["attempted"] > 0
    assert res["metrics"]["serve_out_tokens_per_s"]["value"] > 0
    assert _compared(out, "compiles_in_window") == 0
    assert any(line.startswith("correct: routing:") for line in out)


@pytest.mark.parametrize("which", sorted(WRONG))
def test_a_wrong_reference_is_not_correct(root, capsys, which):
    rc, res, out = _run(root, f"mla_{which}", capsys, 5)
    assert rc == 0 and res["failed"] == 0 and res["attempted"] > 0
    rows = [line for line in out if line.startswith("compare:")]
    assert res["correct"] is False, rows
    assert any("NOT OK" in r and "served_gap_mean" in r for r in rows), rows
    found = next(line for line in out if line.startswith("correct: routing:"))
    if which == "nogroups":         # refused pick by pick
        assert float(found.split("refused ")[1].split("%")[0]) > 50, found
    if which == "twogroups":        # stands pick by pick: held by the share
        assert int(found.split("; ")[-1].split()[0]) > 0, found


def test_traced_run_reads_the_engines_observations(root, capsys):
    """On the CPU there is no device plane: the device-trace readers give
    nothing (and do not raise); the engine's own observations read."""
    rc, res, out = _run(root, "mla", capsys, 7, trace=1)
    assert rc == 0 and res["correct"] is True, [
        line for line in out if line.startswith(("compare:", "correct: r"))]
    m = res["metrics"]
    # one group of eight held: 12.5% where routing is level; a toy's is not
    assert 0 < m["routed_here_share"]["value"] < 60
    assert m["expert_load_max_over_mean"]["value"] >= 1
    assert m["decode_step_ms.long_answers"]["value"] > 0
    assert m["prefill_tokens_per_s.long_answers"]["value"] > 0
    assert m["schedule_ms.long_answers"]["value"] > 0
    assert 0 < m["decode_live_page_share.long_answers"]["value"] <= 100
    for name in ("expert_ffn_time_share", "latent_attn_roofline",
                 "expert_ffn_roofline", "device_idle_share.long_answers"):
        assert name not in m


def test_the_seeded_weights_are_pinned(fam):
    """The same (seed, layer, leaf) gives the same numbers on every
    machine: the served model is a function of the seed alone."""
    w = fam.layer_weights(MLA_ARCH, 11, 1, jnp.float32)
    assert sorted(w) == sorted(fam.layer_shapes(MLA_ARCH))
    assert w["we_gate"].shape == (4, 64, 32) and w["router"].shape == (64, 32)
    again = weights.layer_params(fam, MLA_ARCH, 11, 1, jnp.float32)
    for name in w:
        np.testing.assert_array_equal(w[name], again[name])
    got = [float(w["w_qa"][0, 0]), float(w["router"][3, 5]),
           float(w["kv_norm"][2])]
    np.testing.assert_allclose(got, PINNED, rtol=1e-6)


PINNED = [0.1254502534866333, 0.1599140167236328, 0.9700249433517456]


def test_served_logits_equal_the_programs(fam):
    """The family's `served_logits` (blocks, the share, the recorded
    routing followed) judges the program's own prefill-then-decode on the
    same seeded bfloat16 weights: the served tokens are its best or next
    to it."""
    from paddle_tpu.serving import PagedEngine, Request

    arch = MLA_ARCH
    prompt = np.random.default_rng(1).integers(1, 256, 29).astype(np.int32)
    # the reference makes its weights from the seed in bfloat16, as the
    # benchmark serves them: the program gets the same values
    params = weights.make_params(fam, arch, 3, jnp.bfloat16)
    eng = PagedEngine(params, fam.serve_args(arch), **MLA_CELL["engine"])
    req = Request(prompt, 9)
    eng.serve([req])
    served = np.asarray(req.token_ids, np.int32)
    logits = fam.served_logits(arch, 3, [(prompt, served, req.routing)])[0]
    assert logits.shape == (9, 256)
    gap = logits.max(-1) - logits[np.arange(9), served]
    assert gap.max() < 0.05 and gap.mean() < 4e-3


def test_the_configurations_sizes_from_its_keys(arch, fam):
    """4,146 M parameters and 6,912 bytes a token, recomputed from the
    configuration's keys: the table of PERF.md section 4."""
    count = sum(int(np.prod(s)) for s in fam.layer_shapes(arch).values())
    assert round(count / 1e6, 1) == 669.1            # one layer, M
    total = (arch["num_hidden_layers"] * count
             + 2 * arch["vocab_size"] * arch["hidden_size"]
             + arch["hidden_size"])
    assert round(total / 1e6) == 4146
    assert arch["num_hidden_layers"] * fam.row_bytes(arch) == 6912
    assert fam.router_width(arch) == 160
    assert fam.experts_held(arch) == (0, 20)
    args = fam.serve_args(arch)
    assert args.row_width == 640 and args.routed_experts == 160


def test_a_reduced_key_is_listed_and_no_width_changed(arch):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "deepseek-v2-1chip")
    assert sorted(entry["reduced"]) == sorted(arch["reduced"])
    for key, value in arch["published"].items():
        assert key in arch["reduced"] and arch[key] != value
    for key in ("hidden_size", "kv_lora_rank", "q_lora_rank",
                "moe_intermediate_size", "num_experts_per_tok", "n_group",
                "topk_group", "qk_rope_head_dim", "v_head_dim"):
        assert key not in arch["reduced"]


def test_traced_works_counts_by_hand(arch, fam):
    """One prefill window of 2,048 tokens at 0, one decode step of one row
    at context 3,000, on a described chip of 1 flop/s and 1 byte/s."""
    assert fam.expert_bytes(arch) == 3 * 5120 * 1536 * 2
    assert fam.expert_flops_per_pair(arch) == 6 * 5120 * 1536
    assert fam.latent_decode_flops_per_pair(arch) == 278528      # 278.5 k
    assert fam.latent_prefill_flops_per_pair(arch) == 81920      # 81.9 k
    rec = types.SimpleNamespace(rid=1, submitted=0.0, prompt=np.zeros(2048),
                                times=[1.5, 2.5])
    ctx = types.SimpleNamespace(
        trace={"busy_s": 1.0}, peaks={"bf16_flops": 1.0,
                                      "hbm_bytes_per_s": 1.0},
        arch=arch, engine_kw={"prefill_chunk": 2048},
        trace_host_window=(0.0, 10.0),
        counters={"observations": {
            "serve.held_experts_hit": {"mean": 18.0},
            "serve.routed_here_share": {"mean": 0.125}}},
        run=types.SimpleNamespace(recs={1: rec}),
        spans=[("prefill", 1.0, 1.5, 1), ("decode", 2.0, 2.5, 1)])
    need = fam.traced_work(ctx)
    pairs = 2048 * 6 * 0.125
    experts = 6 * (max(20 * 47185920, pairs * 47185920) + 18 * 47185920)
    latent = 6 * (2049 * 2048 / 2 * 81920 + 2049 * max(1152, 278528))
    assert need["experts"] == pytest.approx(experts)
    assert need["latent"] == pytest.approx(latent)
    ctx.counters = {"observations": {}}       # the parent's program
    assert fam.traced_work(ctx) is None


def test_keys_padded_to_whole_buckets_change_no_logit(fam, monkeypatch):
    """Past one token block the reference pads the decompressed keys and
    values up to whole buckets, so that a request's own length compiles
    nothing (PR 36). With the token block cut to 32 and the bucket to 64, 90
    tokens (96 positions) take that path, their keys padded to 128; at the
    real sizes they are attended unpadded: the same hidden state to float32
    rounding."""
    ids = np.random.default_rng(3).integers(1, 256, 90)
    emb = weights.outer_params(MLA_ARCH, 5, jnp.float32)["embedding"]

    def hidden():
        return np.asarray(fam.forward_hidden(
            MLA_ARCH, ids, lambda i: fam.layer_weights(
                MLA_ARCH, 5, i, jnp.float32), emb))

    whole = hidden()
    shapes, real = [], fam._attend_fn

    def noting(fz):
        fn = real(fz)
        return lambda qn, qr, qpos, kn, kr, v: (
            shapes.append((kn.shape[0], kr.shape[0], v.shape[0])),
            fn(qn, qr, qpos, kn, kr, v))[1]

    monkeypatch.setattr(fam, "T_BLOCK", 32)
    monkeypatch.setattr(fam, "K_BUCKET", 64)
    monkeypatch.setattr(fam, "_attend_fn", noting)
    padded = hidden()
    assert set(shapes) == {(128, 128, 128)}        # whole buckets alone
    assert np.abs(whole).max() > 1
    np.testing.assert_allclose(padded, whole, rtol=0, atol=2e-5)
