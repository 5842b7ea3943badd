"""A tiny benchmark in a temporary root: the committed harness, readers
and BENCHMARK.json's metrics, with a toy configuration, toy mixes and toy
cells ADDED as new files and entries. It is how the tests rehearse the
drivers on the CPU, and it shows that a new cell needs no edit."""

import json
import os
import shutil

from benchmarks.harness.spec import ROOT

TINY_ARCH = {
    "source": "none: a toy for the CPU tests", "hidden_size": 128,
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


def _dump(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def tiny_root(tmp):
    """Copy the committed benchmark into `tmp` and add the toy cells."""
    tmp = str(tmp)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(tmp, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    b = os.path.join(tmp, "benchmarks")
    _dump(os.path.join(b, "configs", "tiny.json"), TINY_ARCH)
    _dump(os.path.join(b, "traffic", "tiny_train.json"), TINY_TRAIN_MIX)
    _dump(os.path.join(b, "traffic", "tiny_sessions.json"), TINY_SERVE_MIX)
    _dump(os.path.join(b, "traffic", "tiny_backlog.json"), TINY_BACKLOG_MIX)
    _dump(os.path.join(b, "workloads", "tiny_train.json"), TINY_TRAIN_CELL)
    _dump(os.path.join(b, "workloads", "tiny_sessions.json"), TINY_SERVE_CELL)
    _dump(os.path.join(b, "workloads", "tiny_backlog.json"), TINY_SERVE_CELL)
    with open(os.path.join(b, "metrics", "tiny_steps.py"), "w") as f:
        f.write("def read(ctx):\n    return float(len(ctx.spans))\n")
    bench["configs"].append({"name": "tiny", "source": "none",
                             "file": "benchmarks/configs/tiny.json",
                             "reduced": [], "why": "toy"})
    for name, traffic in (("tiny_train", "tiny_train"),
                          ("tiny_sessions", "tiny_sessions"),
                          ("tiny_backlog", "tiny_backlog")):
        bench["workloads"].append({"name": name, "config": "tiny",
                                   "traffic": traffic, "chips": 1,
                                   "why": "toy"})
    # the toy cells report the end-to-end metrics of their kind (an entry
    # the committed file lacks is added, as a later PR would add it)
    kinds = {"train_tokens_per_s": ("tiny_train", "tokens/s/chip", "higher"),
             "itl_mean_ms": ("tiny_sessions", "ms", "lower"),
             "serve_out_tokens_per_s": ("tiny_backlog", "tokens/s", "higher")}
    have = {m["name"]: m for m in bench["end_to_end"]}
    for name, (cell, unit, better) in kinds.items():
        if name in have:
            have[name]["workloads"].append(cell)
        else:
            bench["end_to_end"].append({
                "name": name, "unit": unit, "better": better, "bound": 0.05,
                "source": "host_clock", "workloads": [cell]})
    # a cell that wants an existing per-layer metric joins its `workloads`
    for m in bench["per_layer"]:
        if "workloads" in m and m["moves"] in kinds:
            m["workloads"].append(kinds[m["moves"]][0])
    bench["per_layer"].append({
        "name": "tiny_steps", "unit": "steps", "better": "higher",
        "source": "program_span", "layer": "server entry",
        "moves": "setup_s"})
    _dump(os.path.join(tmp, "BENCHMARK.json"), bench)
    return tmp
