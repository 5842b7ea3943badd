"""A family of TWO KINDS of layer for the CPU tests, whose leaves differ in
name and in shape: a dense leading layer (`dense_layers`: attention and one
SwiGLU, `w_gate w_up w_down`) before expert layers (`layers`: the same
attention, a router, shared and routed experts, `router ws_* we_*`).
`tiny.py` copies this file to `<temporary root>/benchmarks/families/`, as a
later PR would add a family's file. It states `layer_kinds`, so the harness
stacks each kind apart (`harness/weights.py`) and tells `decoder_layer` the
kind of the layer it is handed; its tokens are chosen left to right, so the
generic `reference.served_logits` serves.

The equations are the committed `mla_moe` family's (loaded from the file
beside this one); the program's side is its `serve_args`, whose tree has
just these two stacks. The toy configuration keeps every expert of every
group for every token, so that routing is continuous and a bfloat16 program
can be held to a float32 reference without a record of its picks.
"""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_family_tiny_kinds_equations",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "mla_moe.py"))
_eq = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_eq)

DENSE, EXPERT = "dense_layers", "layers"

serve_args = _eq.serve_args


def layer_kinds(arch):
    kd = arch["first_k_dense_replace"]
    return [DENSE] * kd + [EXPERT] * (arch["num_hidden_layers"] - kd)


def layer_shapes(arch):
    return {DENSE: _eq.dense_layer_shapes(arch),
            EXPERT: _eq.layer_shapes(arch)}


def leaf_init(arch):
    init = _eq.leaf_init(arch)
    return {kind: {name: init[name] for name in leaves if name in init}
            for kind, leaves in layer_shapes(arch).items()}


def decoder_layer(x, w, arch, mm, kind):
    # the committed layer takes its kind from an index against the count of
    # dense leading layers
    index = 0 if kind == DENSE else arch["first_k_dense_replace"]
    return _eq.decoder_layer(x, w, arch, mm, index)
