"""A tiny benchmark in a temporary root: the committed harness, readers
and BENCHMARK.json's metrics, with toy configurations, toy mixes, toy
cells, a second model family and a family of two kinds of layer ADDED as
new files and entries. It is how the tests rehearse the drivers on the CPU,
and it shows that a new cell or a new family needs no edit."""

import json
import os
import re
import shutil

from benchmarks.harness.spec import ROOT

TINY_ARCH = {
    "source": "none: a toy for the CPU tests", "family": "dense_gqa_swiglu",
    "hidden_size": 128,
    "intermediate_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "vocab_size": 8192, "rms_norm_eps": 1e-05, "rope_theta": 10000.0,
    "reduced": [], "assumed": {}}
TINY_TRAIN_MIX = {"kind": "train", "rows": 4, "seq_len": 1024,
                  "micro_batches": 2}
TINY_SERVE_MIX = {
    "kind": "serve", "arrival": {"process": "poisson", "rate_per_s": 4.0},
    "ramp_s": 0.5, "pool": 16, "tenants": 2, "system_prompt_tokens": 40,
    "turns": {"min": 1, "max": 2},
    "user_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                    "min": 4, "max": 80},
    "answer_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                      "min": 2, "max": 12},
    "think_s": {"dist": "exponential", "mean": 0.05}, "max_context": 200}
TINY_BACKLOG_MIX = dict(TINY_SERVE_MIX, tenants=0, system_prompt_tokens=0,
                        arrival={"process": "backlog", "queue_depth": 4},
                        turns={"min": 1, "max": 1})
TINY_TRAIN_CELL = {
    "kind": "train",
    "engine": {"dp": 1, "pp": 1, "mp": 1, "dtype": "float32", "remat": False,
               "moments": "f32", "loss_chunk": 128},
    "optimizer": {"lr": 0.0003, "beta1": 0.9, "beta2": 0.999, "eps": 1e-08,
                  "weight_decay": 0.01},
    "limits": {"loss_gap": 5e-5, "loss_gap_mean": 3e-5, "grad_norm_gap": 1e-3,
               "param_change_gap": 1e-3}}
TINY_SERVE_CELL = {
    "kind": "serve",
    "engine": {"max_slots": 3, "max_len": 256, "page_size": 16,
               "num_pages": 40, "min_bucket": 16, "prefill_chunk": 32,
               "kv_dtype": None, "prefix_policy": "radix"},
    "limits": {"served_gap_widest": 0.02, "served_gap_mean": 0.0003}}


# the second family (`data/tiny_alt.py`), and the same with the norms'
# weights dropped from its `decoder_layer`: a run over that one has to come
# out not correct, which shows that a run reads the added file
ALT_FAMILY = os.path.join(os.path.dirname(__file__), "data", "tiny_alt.py")
ALT_WRONG = re.compile(r'_norm\(x, w\["ln[12]"\], eps\)'), "_norm(x, 1.0, eps)"
ALT_ARCH = {
    "source": "none: a toy for the CPU tests", "family": "tiny_alt",
    "hidden_size": 128, "ffn_size": 256, "num_hidden_layers": 2, "heads": 4,
    "kv_heads": 2, "head_size": 32, "vocab_size": 8192,
    "rms_norm_eps": 1e-05, "rope_theta": 10000.0, "reduced": [],
    "assumed": {}}
# the mean gap over 4 seeds on the CPU (PR 26): sound 0 .. 1.7e-5, with the
# norms' weights dropped 4.7e-4 .. 9.6e-4
ALT_SERVE_CELL = dict(TINY_SERVE_CELL, limits={"served_gap_widest": 0.02,
                                               "served_gap_mean": 1e-4})

# the family of two kinds of layer (`data/tiny_kinds.py`): one dense leading
# layer and two expert layers whose leaves differ in name and shape, every
# ratio small; both groups stay and all four experts are picked for every
# token, so nothing discrete stands between a bfloat16 program and the
# float32 reference
KINDS_FAMILY = os.path.join(os.path.dirname(__file__), "data",
                            "tiny_kinds.py")
KINDS_ARCH = {
    "source": "none: a toy for the CPU tests", "family": "tiny_kinds",
    "hidden_size": 64, "intermediate_size": 160,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 4,
    "n_shared_experts": 2, "n_group": 2, "topk_group": 2,
    "num_experts_per_tok": 4, "routed_scaling_factor": 4,
    "vocab_size": 256, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "initializer_range": 0.15,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 64,
                     "type": "yarn"},
    "reduced": [], "assumed": {}}
# the same with the second norm's weight dropped from the REFERENCE's dense
# layer alone: a run over that one has to come out not correct, which shows
# that the dense kind's leaves reach the layer they belong to
KINDS_WRONG = (re.compile(r"return _eq\.decoder_layer\(x, w, arch, mm, index\)"),
               'return _eq.decoder_layer(x, dict(w, ln2=1.0 + 0 * w["ln2"]) '
               'if kind == DENSE else w, arch, mm, index)')
# over 5 seeds on the CPU (PR 36): sound mean 1.6e-5 .. 1.0e-4, widest up to
# 0.0073; with that norm's weight dropped mean 2.4e-3 .. 5.3e-3, widest
# 0.044 .. 0.135 (4 seeds)
KINDS_SERVE_CELL = dict(TINY_SERVE_CELL, limits={"served_gap_widest": 0.02,
                                                 "served_gap_mean": 5e-4})

# (cell, configuration, traffic, the cell's file, its end-to-end metric)
CELLS = [
    ("tiny_train", "tiny", "tiny_train", TINY_TRAIN_CELL,
     "train_tokens_per_s"),
    ("tiny_sessions", "tiny", "tiny_sessions", TINY_SERVE_CELL,
     "itl_mean_ms"),
    ("tiny_backlog", "tiny", "tiny_backlog", TINY_SERVE_CELL,
     "serve_out_tokens_per_s"),
    ("alt_train", "tiny_alt", "tiny_train", TINY_TRAIN_CELL,
     "train_tokens_per_s"),
    ("alt_backlog", "tiny_alt", "tiny_backlog", ALT_SERVE_CELL,
     "serve_out_tokens_per_s"),
    ("altwrong_train", "tiny_alt_wrong", "tiny_train", TINY_TRAIN_CELL,
     "train_tokens_per_s"),
    ("altwrong_backlog", "tiny_alt_wrong", "tiny_backlog", ALT_SERVE_CELL,
     "serve_out_tokens_per_s"),
    ("kinds_backlog", "tiny_kinds", "tiny_backlog", KINDS_SERVE_CELL,
     "serve_out_tokens_per_s"),
    ("kindswrong_backlog", "tiny_kinds_wrong", "tiny_backlog",
     KINDS_SERVE_CELL, "serve_out_tokens_per_s")]
E2E = {"train_tokens_per_s": ("tokens/s/chip", "higher"),
       "itl_mean_ms": ("ms", "lower"),
       "serve_out_tokens_per_s": ("tokens/s", "higher")}


def _dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


def tiny_root(tmp):
    """Copy the committed benchmark into `tmp` and add the toy files."""
    tmp = str(tmp)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(tmp, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    b = os.path.join(tmp, "benchmarks")
    for path, name, (pattern, repl), times in (
            (ALT_FAMILY, "tiny_alt", ALT_WRONG, 2),
            (KINDS_FAMILY, "tiny_kinds", KINDS_WRONG, 1)):
        with open(path) as f:
            text = f.read()
        wrong, n = pattern.subn(repl, text)
        if n != times:
            raise RuntimeError(f"{path} lost the {times} line(s) to break")
        for fname, body in ((name, text), (name + "_wrong", wrong)):
            with open(os.path.join(b, "families", fname + ".py"), "w") as f:
                f.write(body)
    configs = {"tiny": TINY_ARCH, "tiny_alt": ALT_ARCH,
               "tiny_alt_wrong": dict(ALT_ARCH, family="tiny_alt_wrong"),
               "tiny_kinds": KINDS_ARCH,
               "tiny_kinds_wrong": dict(KINDS_ARCH,
                                        family="tiny_kinds_wrong")}
    for name, arch in configs.items():
        _dump(os.path.join(b, "configs", name + ".json"), arch)
        bench["configs"].append({"name": name, "source": "none",
                                 "file": f"benchmarks/configs/{name}.json",
                                 "reduced": [], "why": "toy"})
    _dump(os.path.join(b, "traffic", "tiny_train.json"), TINY_TRAIN_MIX)
    _dump(os.path.join(b, "traffic", "tiny_sessions.json"), TINY_SERVE_MIX)
    _dump(os.path.join(b, "traffic", "tiny_backlog.json"), TINY_BACKLOG_MIX)
    with open(os.path.join(b, "metrics", "tiny_steps.py"), "w") as f:
        f.write("def read(ctx):\n    return float(len(ctx.spans))\n")
    # the toy cells report the end-to-end metric of their kind (an entry
    # the committed file lacks is added, as a later PR would add it), and a
    # cell that wants an existing per-layer metric joins its `workloads`
    have = {m["name"]: m for m in bench["end_to_end"]}
    for cell, config, traffic, spec, metric in CELLS:
        _dump(os.path.join(b, "workloads", cell + ".json"), spec)
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": traffic, "chips": 1,
                                   "why": "toy"})
        if metric not in have:
            unit, better = E2E[metric]
            have[metric] = {"name": metric, "unit": unit, "better": better,
                            "bound": 0.05, "source": "host_clock",
                            "workloads": []}
            bench["end_to_end"].append(have[metric])
        have[metric]["workloads"].append(cell)
        for m in bench["per_layer"]:
            if "workloads" in m and m["moves"] == metric:
                m["workloads"].append(cell)
    bench["per_layer"].append({
        "name": "tiny_steps", "unit": "steps", "better": "higher",
        "source": "program_span", "layer": "server entry",
        "moves": "setup_s"})
    _dump(os.path.join(tmp, "BENCHMARK.json"), bench)
    return tmp
