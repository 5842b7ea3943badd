"""Unit tests of the harness's own rules, seconds each on the CPU: which
finished requests a serving run compares (`driver_serve.compared`), the
weights of a family of several kinds of layer (`harness/weights.py`), and
the line in which a run says where its time went (`run.Phases`,
`run.parse_phases`). `benchmarks/tests/test_harness_units.py` copied into
tier-1 (PERF.md section 7 (i), PR 36: written so that this file can be that
file copied): the by-kind weights are what the `gated_delta_hybrid` family
and the hybrid serving path's tests stand on. `tests/conftest.py` has
already held jax to the CPU."""

import hashlib
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax.numpy as jnp  # noqa: E402

from benchmarks import run  # noqa: E402
from benchmarks.harness import reference, weights  # noqa: E402
from benchmarks.harness.driver_serve import (COMPARE_MAX,  # noqa: E402
                                             COMPARE_TOKENS, compared)

SEEDS = [0, 7, 2**31 + 23, 2350000101]


# -- the compared sample ---------------------------------------------------------

def _long_documents(n=40, seed=1):
    """Sizes like the sala cell's finished requests: 9k-49k tokens."""
    rng = np.random.default_rng(seed)
    return list(rng.integers(9_300, 49_600, n))


@pytest.mark.parametrize("seed", SEEDS)
def test_the_longest_is_always_compared(seed):
    sizes = _long_documents()
    assert int(np.argmax(sizes)) in compared(sizes, seed, COMPARE_MAX,
                                             COMPARE_TOKENS)


@pytest.mark.parametrize("seed", SEEDS)
def test_never_fewer_than_two_while_two_finished(seed):
    # every request alone is over the bound
    sizes = [COMPARE_TOKENS + 5, COMPARE_TOKENS + 9, COMPARE_TOKENS + 1]
    held = compared(sizes, seed, COMPARE_MAX, COMPARE_TOKENS)
    assert len(held) == 2 and 1 in held
    assert compared([12], seed, COMPARE_MAX, COMPARE_TOKENS) == [0]
    assert compared([], seed, COMPARE_MAX, COMPARE_TOKENS) == []


@pytest.mark.parametrize("seed", SEEDS)
def test_never_over_the_bound_but_by_the_first_drawn(seed):
    sizes = _long_documents(seed=seed % 1000)
    held = compared(sizes, seed, COMPARE_MAX, COMPARE_TOKENS)
    total = sum(sizes[i] for i in held)
    # over the bound only as the longest with the first drawn
    assert total <= COMPARE_TOKENS or len(held) == 2
    assert len(held) >= 2
    assert len(held) == len(set(held)) and held == sorted(held)


def test_the_same_for_one_seed_and_another_for_two():
    sizes = _long_documents()
    a = compared(sizes, 5, COMPARE_MAX, COMPARE_TOKENS)
    assert a == compared(list(sizes), 5, COMPARE_MAX, COMPARE_TOKENS)
    others = [compared(sizes, s, COMPARE_MAX, COMPARE_TOKENS)
              for s in range(6, 12)]
    assert any(o != a for o in others)


@pytest.mark.parametrize("n", [1, 2, 17, COMPARE_MAX])
def test_a_finished_set_within_both_bounds_is_compared_whole(n):
    sizes = [COMPARE_TOKENS // COMPARE_MAX - 3] * n
    assert compared(sizes, 3, COMPARE_MAX, COMPARE_TOKENS) == list(range(n))


@pytest.mark.parametrize("seed", SEEDS)
def test_the_request_bound_still_holds(seed):
    sizes = [40 + i % 7 for i in range(500)]       # the toy cells' sizes
    held = compared(sizes, seed, COMPARE_MAX, COMPARE_TOKENS)
    assert len(held) == COMPARE_MAX and int(np.argmax(sizes)) in held


def test_a_request_that_does_not_fit_is_passed_over_not_the_end():
    # after the longest (50) and the first drawn, only the 1s still fit
    sizes = [50] + [30] * 6 + [1] * 6
    held = compared(sizes, 4, 10, 85)
    assert 0 in held and len(held) == 2 + 5
    assert sum(sizes[i] for i in held) == 50 + 30 + 5


def test_the_dense_cell_keeps_what_it_compared():
    """Requests of the mistral cell's lengths (mean ~1.7k, a tail to 8k):
    at least 28 of them stay in the sample."""
    rng = np.random.default_rng(0)
    for seed in SEEDS:
        sizes = np.clip(rng.lognormal(np.log(1200), 0.9, 60), 40,
                        8192).astype(int)
        assert len(compared(sizes, seed, COMPARE_MAX, COMPARE_TOKENS)) >= 28


# -- weights by layer kind ---------------------------------------------------------

ARCH = {"hidden_size": 16, "vocab_size": 64, "num_hidden_layers": 5}
KINDS = ["thin", "wide", "wide", "thin", "wide"]
TWO = types.SimpleNamespace(
    layer_kinds=lambda a: KINDS,
    layer_shapes=lambda a: {
        "thin": {"wa": (a["hidden_size"], 8), "norm": (a["hidden_size"],)},
        "wide": {"wb": (a["hidden_size"], 24), "wc": (24, a["hidden_size"]),
                 "norm": (24,)}},
    leaf_init=lambda a: {"wide": {"wc": (0.0, 0.01)}})
ONE = types.SimpleNamespace(
    layer_shapes=lambda a: {"wa": (a["hidden_size"], 24),
                            "wb": (24, a["hidden_size"]),
                            "norm": (a["hidden_size"],)},
    leaf_init=lambda a: {"wb": (0.0, 0.01)})


def test_each_kind_is_stacked_apart_in_layer_order():
    tree = weights.make_params(TWO, ARCH, 9, jnp.float32)
    assert set(tree) == {"thin", "wide", "embedding", "final_norm",
                         "lm_head"}
    assert {k: v.shape for k, v in tree["thin"].items()} == {
        "wa": (2, 16, 8), "norm": (2, 16)}
    assert {k: v.shape for k, v in tree["wide"].items()} == {
        "wb": (3, 16, 24), "wc": (3, 24, 16), "norm": (3, 24)}
    assert float(tree["wide"]["wc"].std()) == pytest.approx(0.01, rel=0.1)
    assert float(tree["wide"]["wb"].std()) == pytest.approx(0.02, rel=0.1)


@pytest.mark.parametrize("index", range(5))
def test_a_layer_alone_is_its_row_of_its_kinds_stack(index):
    tree = weights.make_params(TWO, ARCH, 9)
    kind = KINDS[index]
    row = KINDS[:index].count(kind)
    layer = weights.layer_params(TWO, ARCH, 9, index)
    assert sorted(layer) == sorted(TWO.layer_shapes(ARCH)[kind])
    for name, leaf in layer.items():
        np.testing.assert_array_equal(leaf, tree[kind][name][row])


def test_leaves_are_named_by_kind_and_equal_the_trees():
    tree = weights.make_params(TWO, ARCH, 9)
    got = dict(weights.leaves(TWO, ARCH, 9))
    assert set(got) == {"thin/wa", "thin/norm", "wide/wb", "wide/wc",
                        "wide/norm", "embedding", "final_norm", "lm_head"}
    for name, leaf in got.items():
        kind, _, leaf_name = name.rpartition("/")
        np.testing.assert_array_equal(
            leaf, tree[kind][leaf_name] if kind else tree[name])


def test_a_layers_values_do_not_depend_on_the_other_kinds_count():
    """Layer 4 (`wide`) under another order of kinds before it: the same
    (seed, layer index, leaf's place) gives the same numbers."""
    other = types.SimpleNamespace(
        layer_kinds=lambda a: ["wide", "thin", "thin", "thin", "wide"],
        layer_shapes=TWO.layer_shapes, leaf_init=TWO.leaf_init)
    a = weights.layer_params(TWO, ARCH, 9, 4)
    b = weights.layer_params(other, ARCH, 9, 4)
    for name in a:
        np.testing.assert_array_equal(a[name], b[name])


def test_kinds_that_miss_a_layer_are_refused():
    short = types.SimpleNamespace(layer_kinds=lambda a: KINDS[:4],
                                  layer_shapes=TWO.layer_shapes)
    with pytest.raises(ValueError, match="layer_kinds names 4 layers"):
        weights.make_params(short, ARCH, 9)


def _digest(a):
    a = np.asarray(a)
    a = a.view(np.uint16) if a.dtype.itemsize == 2 else a
    return hashlib.sha256(a.tobytes()).hexdigest()[:16]


# made by the parent's `weights.py` (commit 105b4c0, before kinds), seed
# 2**31 + 5, three layers
ONE_PINNED = {
    jnp.bfloat16: {"layers/norm": "ec45e0d6e2d89e68",
                   "layers/wa": "1b0b3baa745582e7",
                   "layers/wb": "3cbd7f94bf93cb62",
                   "embedding": "858b3cdb3fe5e881",
                   "final_norm": "52292c6427a1a6a2",
                   "lm_head": "0a0d299d518ca672"},
    jnp.float32: {"layers/norm": "67610bb83c361e18",
                  "layers/wa": "51c68f5275a7dcac",
                  "layers/wb": "88fd3407de97612c",
                  "embedding": "2a010d863bf2cff9",
                  "final_norm": "696419613953ec28",
                  "lm_head": "a4070f332230e14f"}}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_a_family_of_one_kind_keeps_its_tree_and_its_bits(dtype):
    arch = dict(ARCH, num_hidden_layers=3)
    tree = weights.make_params(ONE, arch, 2**31 + 5, dtype)
    assert set(tree) == {"layers", "embedding", "final_norm", "lm_head"}
    got = {f"layers/{k}": _digest(v) for k, v in tree["layers"].items()}
    got.update((k, _digest(tree[k])) for k in ("embedding", "final_norm",
                                               "lm_head"))
    assert got == ONE_PINNED[dtype]
    assert {k: _digest(v) for k, v in weights.leaves(
        ONE, arch, 2**31 + 5, dtype)} == ONE_PINNED[dtype]
    assert weights.layer_kinds(ONE, arch) == ("layers",) * 3


def test_the_reference_tells_a_layer_its_kind_and_keeps_nested_groups():
    """Through `reference.served_logits`: a family of kinds is handed the
    kind of each layer with that kind's leaves, and the configuration's
    nested groups reach it whole."""
    seen = []

    def layer(x, w, arch, mm, kind):
        seen.append((kind, sorted(w), arch["group"]["scale"]))
        name = "wa" if kind == "thin" else "wb"
        return x + arch["group"]["scale"] * mm(mm(x, w[name]), w[name].T)

    fam = types.SimpleNamespace(
        layer_kinds=TWO.layer_kinds, layer_shapes=TWO.layer_shapes,
        leaf_init=TWO.leaf_init, decoder_layer=layer)
    arch = dict(ARCH, rms_norm_eps=1e-5, group={"scale": 0.5})
    rng = np.random.default_rng(0)
    logits, = reference.served_logits(
        fam, arch, 9, [(rng.integers(1, 64, 20), rng.integers(1, 64, 4))])
    assert logits.shape == (4, 64) and np.isfinite(logits).all()
    assert [k for k, _, _ in seen] == ["thin", "wide"]   # traced once a kind
    assert seen[0][1:] == (["norm", "wa"], 0.5)
    assert seen[1][1:] == (["norm", "wb", "wc"], 0.5)


# -- the phase clock ---------------------------------------------------------------

LINES = {
    "serve": ("phases: start-up 19.1 s, warm-up 18.2, ramp 52.4, window "
              "30.0, check 224.6, whole run 345.0",
              {"start-up": 19.1, "warm-up": 18.2, "ramp": 52.4,
               "window": 30.0, "check": 224.6, "whole run": 345.0}),
    "train": ("phases: start-up 12.0 s, first steps 31.5, window 30.1, "
              "check 40.2, readers 3.0, whole run 116.8",
              {"start-up": 12.0, "first steps": 31.5, "window": 30.1,
               "check": 40.2, "readers": 3.0, "whole run": 116.8}),
    "whole": ("phases: start-up 7 s, whole run 7", {"start-up": 7.0,
                                                    "whole run": 7.0}),
}


@pytest.mark.parametrize("which", sorted(LINES))
def test_the_phase_line_is_parsed(which):
    line, want = LINES[which]
    assert run.parse_phases(f"benchmark: x\n{line}\n{{}}") == want


def test_no_phase_line_parses_to_none_and_the_last_of_two_counts():
    assert run.parse_phases("compare: a = 1  limit 2  ok\n") is None
    two = LINES["serve"][0] + "\n" + LINES["whole"][0]
    assert run.parse_phases(two) == LINES["whole"][1]


def test_the_clock_writes_what_the_parser_reads(capsys):
    clock = run.Phases()
    t0 = clock.ends[0][1]
    clock.mark("start-up", t0 + 19.14)
    clock.mark("warm-up", t0 + 37.3)
    clock.mark("check", t0 + 100.0)
    said = capsys.readouterr().out
    assert "phase: warm-up ended 37.3 s after the start" in said
    assert run.parse_phases(clock.line()) == {
        "start-up": 19.1, "warm-up": 18.2, "check": 62.7,
        "whole run": 100.0}
