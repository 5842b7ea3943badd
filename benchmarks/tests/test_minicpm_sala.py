"""CPU tests of the MiniCPM-SALA family under the committed harness: a tiny
cell of the family runs end to end through the unedited serve driver (its
warm-up reaches the copy-on-write through a state-snapshot hit) and is
`correct`; with the family's REFERENCE made wrong in each of three ways the
same run is not; and nothing of the harness was edited for it. Run with
`pytest benchmarks/tests` (not tier-1; the tier-1 file is
`tests/test_hybrid_serving.py`)."""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from _platform_setup import force_cpu_platform  # noqa: E402

force_cpu_platform(1)

from benchmarks import run  # noqa: E402
from benchmarks.tests import tiny  # noqa: E402

# the tier-1 tests' preset: page = block 8, kernel 4 / stride 2, top-k 4 = 1
# init + 2 window + 1 picked, dense_len 32; 2 sparse + 6 lightning layers
SALA_ARCH = {
    "source": "none: a toy for the CPU tests", "family": "minicpm_sala",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "scale_emb": 12, "scale_depth": 1.4, "dim_model_base": 16,
    "initializer_range": 0.15,
    "mixer_types": ["minicpm4"] + ["lightning-attn"] * 3
    + ["minicpm4"] + ["lightning-attn"] * 3,
    "sparse_config": {"block_size": 8, "kernel_size": 4, "kernel_stride": 2,
                      "topk": 4, "init_blocks": 1, "window_size": 16,
                      "dense_len": 32},
    "reduced": [], "assumed": {}}
# every prompt is past dense_len; a backlog, unshared, as the real mix
SALA_MIX = {
    "kind": "serve", "arrival": {"process": "backlog", "queue_depth": 3},
    "ramp_steps": 10, "pool": 8, "tenants": 0, "system_prompt_tokens": 0,
    "turns": {"min": 1, "max": 1},
    "user_tokens": {"dist": "lognormal", "median": 80, "sigma": 0.3,
                    "min": 48, "max": 112},
    "answer_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                      "min": 3, "max": 12},
    "think_s": {"dist": "const", "value": 0.0}, "max_context": 126}
# the driver serves bfloat16 on the CPU too. Sound over 2 seeds: mean 6.1e-5
# and 4.9e-4, widest 0.005 and 0.053 (bf16 against the float32 reference);
# the three wrong references: mean 0.0186 (scale_depth dropped), 0.036
# (decay dropped), 0.042 (dense attention past dense_len), widest 0.10-0.17.
# The mean decides: its limit lies 6x over the sound runs' largest and 6x
# under the wrong references' smallest
SALA_CELL = {
    "kind": "serve",
    "engine": {"max_slots": 3, "max_len": 128, "page_size": 8,
               "num_pages": 80, "min_bucket": 8, "prefill_chunk": 16,
               "kv_dtype": None, "prefix_policy": "radix"},
    "limits": {"served_gap_widest": 0.5, "served_gap_mean": 3e-3}}

FAMILY = os.path.join(ROOT, "benchmarks", "families", "minicpm_sala.py")
# the reference made wrong, one line each (the program's side of the file,
# `serve_args`, is left alone): the decay dropped from the recurrence; dense
# attention where the equations select; the residual scale's scale_depth
# dropped
WRONG = {
    "nodecay": (r"new = lam \* S \+", "new = S +"),
    "dense": (r"return jnp\.where\(\(ctx <= sp\[\"dense_len\"\]\)"
              r"\[None, :, None\], held,\s+forced \| picked\)",
              "return held"),
    "noscale": (r"a = arch\[\"scale_depth\"\] / math\.sqrt\(",
                "a = 1.0 / math.sqrt("),
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = tiny.tiny_root(tmp_path_factory.mktemp("sala"))
    b = os.path.join(tmp, "benchmarks")
    with open(FAMILY) as f:
        text = f.read()
    names = {"sala": "minicpm_sala"}
    for name, (pattern, repl) in WRONG.items():
        wrong, n = re.subn(pattern, repl, text)
        assert n == 1, f"the reference lost the line to break for {name}"
        with open(os.path.join(b, "families", f"sala_{name}.py"), "w") as f:
            f.write(wrong)
        names[f"sala_{name}"] = f"sala_{name}"
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(b, "traffic", "tiny_long.json"), "w") as f:
        json.dump(SALA_MIX, f)
    for cell, family in names.items():
        with open(os.path.join(b, "configs", cell + ".json"), "w") as f:
            json.dump(dict(SALA_ARCH, family=family), f)
        with open(os.path.join(b, "workloads", cell + ".json"), "w") as f:
            json.dump(SALA_CELL, f)
        bench["configs"].append({"name": cell, "source": "none",
                                 "file": f"benchmarks/configs/{cell}.json",
                                 "reduced": [], "why": "toy"})
        bench["workloads"].append({"name": cell, "config": cell,
                                   "traffic": "tiny_long", "chips": 1,
                                   "why": "toy"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if "serve_minicpm_sala_long_documents" in m.get("workloads", []):
                m["workloads"].append(cell)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp


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
    warm-up raises unless a 12-token prompt's snapshot serves the next
    prompt's hit and the straddled page is copied on write."""
    rc, res, out = _run(root, "sala", capsys, seed)
    assert rc == 0 and res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["metrics"]["serve_out_tokens_per_s"]["value"] > 0
    assert _compared(out, "compiles_in_window") == 0


@pytest.mark.parametrize("which", sorted(WRONG))
def test_a_wrong_reference_is_not_correct(root, capsys, which):
    rc, res, out = _run(root, f"sala_{which}", capsys, 5)
    assert rc == 0 and res["failed"] == 0 and res["attempted"] > 0
    assert res["correct"] is False
    rows = [line for line in out if line.startswith("compare:")]
    assert any("NOT OK" in r and "served_gap_mean" in r for r in rows)


def test_traced_run_reads_the_engines_observations(root, capsys):
    """On the CPU there is no device plane: the device-trace readers give
    nothing (and do not raise); the engine's own observations read."""
    rc, res, _ = _run(root, "sala", capsys, 7, trace=1)
    assert rc == 0 and res["correct"] is True
    m = res["metrics"]
    assert 0 < m["sparse_read_share"]["value"] < 100
    assert m["decode_step_ms.long_documents"]["value"] > 0
    assert m["prefill_tokens_per_s.long_documents"]["value"] > 0
    for name in ("lightning_attn_time_share", "sparse_attn_roofline",
                 "device_idle_share.long_documents"):
        assert name not in m


def test_the_harness_names_nothing_of_this_family():
    """`run.py`, `control.py` and every file of `harness/` were the parent's
    when this family came (PR 28). PR 36 edited them for every family alike,
    so what holds since is what that stood for: none of them names this
    family, its kinds of layer or its leaves."""
    bdir = os.path.join(ROOT, "benchmarks")
    names = ["run.py", "control.py"] + [
        os.path.join("harness", f)
        for f in sorted(os.listdir(os.path.join(bdir, "harness")))
        if f.endswith(".py")]
    assert len(names) > 8
    for name in names:
        with open(os.path.join(bdir, name)) as f:
            text = f.read().lower()
        for word in ("minicpm", "sala", "lightning", "sparse", "mixer_types",
                     "o_norm", "q_norm"):
            assert word not in text, (name, word)


def test_keys_padded_to_whole_buckets_change_no_logit(monkeypatch):
    """Past one token block the reference pads a sparse layer's keys and
    values up to whole buckets, so that a request's own length compiles
    nothing (PR 36). With the token block and the bucket cut to 64 a toy
    sequence of 150 tokens (192 positions) takes that path, its keys padded
    to 256 (64 rows of padding past the sequence's own 42); at the real
    sizes it is attended unpadded: the same hidden state to float32
    rounding, at a length where selection is on."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import weights
    from benchmarks.harness.spec import Cell

    fam = Cell("serve_minicpm_sala_long_documents").family
    ids = np.random.default_rng(3).integers(1, 256, 150)
    emb = weights.outer_params(SALA_ARCH, 5, jnp.float32)["embedding"]

    def hidden():
        return np.asarray(fam.forward_hidden(
            SALA_ARCH, ids, lambda i: weights.layer_params(
                fam, SALA_ARCH, 5, i, jnp.float32), emb))

    whole = hidden()
    shapes = []
    real = fam._sparse_fn

    def noting(fz):
        fn = real(fz)
        return lambda q, qpos, k, v: (shapes.append(k.shape[0]),
                                      fn(q, qpos, k, v))[1]

    monkeypatch.setattr(fam, "T_BLOCK", 64)
    monkeypatch.setattr(fam, "K_BUCKET", 128)
    monkeypatch.setattr(fam, "_sparse_fn", noting)
    padded = hidden()
    assert set(shapes) == {128, 256}           # whole buckets alone
    assert np.abs(whole).max() > 1
    np.testing.assert_allclose(padded, whole, rtol=0, atol=2e-5)
