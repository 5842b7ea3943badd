"""The seam between `PagedEngine` (the host half of serving) and a model
family's device half (`serving/paths.py`): a family the engine has never
heard of is served by registering its path, and the engine's source names
no family. Below it, the seam between the one path that keeps trees
(`serving/family.py`) and a family's functional module
(`models/family_protocol.py`)."""

import ast
import functools
import glob
import os
from types import SimpleNamespace
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.sparse_attention import SparseConfig
from paddle_tpu.models import family_protocol
from paddle_tpu.models import gated_delta_functional as gdf
from paddle_tpu.models import hybrid_functional as hf
from paddle_tpu.models import latent_delta_functional as ldf
from paddle_tpu.models import latent_moe_functional as lm
from paddle_tpu.serving import PagedEngine, Request, paths
from paddle_tpu.serving.family import FamilyPath

VOCAB = 50


class ToyArgs(NamedTuple):
    vocab_size: int = VOCAB


class ToyPath:
    """A family with no device: the token after t is t + 1. Every call the
    engine makes is recorded; what is not written out does nothing."""
    snapshots = 0

    def __init__(self, eng):
        self.eng, self.calls = eng, []

    def __getattr__(self, name):
        return lambda *a: self.calls.append(name)

    def prefill(self, ids, start, last_idx, bt_row, new_vec, slot, req,
                sample):
        self.calls.append("prefill")
        return (int(ids[0, last_idx]) + 1) % VOCAB

    def decode(self, bt, active, sample, sampling_args):
        self.calls.append("decode")
        return (self.eng._last_tok + 1) % VOCAB


def _after(prompt, n):
    return [(int(prompt[-1]) + 1 + i) % VOCAB for i in range(n)]


def test_a_family_registered_in_the_table_is_served(monkeypatch):
    """Chunked prefill, a copy-on-write, a preempt / resume and a reset,
    through `submit` / `step`, with nothing of the engine edited."""
    monkeypatch.setitem(paths.PATHS, ToyArgs, ToyPath)
    eng = PagedEngine(None, ToyArgs(), max_slots=2, max_len=64, page_size=8,
                      min_bucket=8, prefill_chunk=8)
    calls = eng.path.calls
    first = np.arange(1, 21, dtype=np.int32)          # 20 tokens: 3 chunks
    a = eng.submit(Request(first, 5))
    eng.run_until_idle()
    assert a.token_ids == _after(first, 5)
    assert calls.count("prefill") == 3 and "prompt_done" in calls
    assert "attach" in calls and "copy_page" not in calls

    # the same 20 tokens and six more: the hit ends inside the third page,
    # which is frozen in the tree, so the slot takes a copy of it
    second = np.concatenate([first, np.arange(30, 36, dtype=np.int32)])
    b = eng.submit(Request(second, 12))
    while len(b.token_ids) < 3:
        eng.step()
    assert "copy_page" in calls
    assert eng.metrics.summary()["counters"]["prefix_tokens_hit"] == 20

    slot = eng.slots.active_slots[0]
    state = eng.preempt(slot)
    assert "take_state" in calls and not eng.slots.active_slots
    c = eng.submit(Request(np.arange(40, 47, dtype=np.int32), 4))
    eng.run_until_idle()
    assert c.token_ids == _after(np.arange(40, 47), 4)
    assert eng.can_resume(state)
    eng.resume(state)
    assert "put_state" in calls
    eng.run_until_idle()
    assert b.token_ids == _after(second, 12)

    calls.clear()
    eng.reset()
    assert calls == ["reset"] and eng._alloc.pages_in_use == 0
    again = eng.serve([Request(first, 2)])[0]
    assert again.token_ids == _after(first, 2)
    assert eng.metrics.summary()["counters"]["prefix_tokens_hit"] == 0


def test_an_unknown_description_is_refused_and_the_table_is_named():
    with pytest.raises(TypeError, match=r"serving\.paths\.PATHS"):
        PagedEngine(None, ToyArgs(), max_slots=2, max_len=64, page_size=8)


def test_the_engine_names_no_family_and_holds_no_device_code():
    """`paged_engine.py` imports neither jax nor a model nor a family's
    path, and no name in it speaks of one family."""
    import paddle_tpu.serving.paged_engine as mod

    with open(mod.__file__) as f:
        tree = ast.parse(f.read())
    imported, names = set(), set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            imported.add(node.module)
            imported.update(f"{node.module}.{a.name}" for a in node.names)
        elif isinstance(node, ast.Attribute):
            names.add(node.attr)
        elif isinstance(node, ast.Name):
            names.add(node.id)
    assert not [m for m in imported
                if m.split(".")[0] == "jax" or ".models" in m
                or m.endswith((".hybrid", ".dense"))], sorted(imported)
    for word in ("hybrid", "layer_kinds", "generation", "dense", "_hy",
                 "_pk", "_pv", "shard_map", "jit"):
        assert not [n for n in names if word in n.lower()], word
    assert os.path.basename(mod.__file__) == "paged_engine.py"


# ---------------------------------------------------------------------------
# the one path over trees and the protocol a family states
# ---------------------------------------------------------------------------

_EXPERTS = dict(
    num_heads=2, q_rank=12, kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8,
    dense_intermediate=48, expert_intermediate=16, shared_experts=1,
    routed_experts=8, first_expert=0, experts_held=8, n_group=1,
    topk_group=1, experts_per_tok=2, routed_scaling=2.5, first_k_dense=1,
    rope_theta=10000.0, rms_eps=1e-6, yarn=None, scoring="sigmoid",
    norm_topk=True)
MODULES = {
    "hybrid": (hf, hf.HybridArgs(
        vocab_size=VOCAB, hidden_size=32, intermediate_size=48, num_heads=2,
        head_dim=16, sparse_kv_heads=1,
        layer_kinds=(hf.SPARSE, hf.LIGHTNING, hf.LIGHTNING), rope_theta=1e4,
        rms_eps=1e-6, scale_emb=1.0, residual_scale=1.0, logit_divisor=1.0,
        sparse=SparseConfig(8, 4, 2, 4, 1, 2, 32))),
    "gated_delta": (gdf, gdf.GatedDeltaArgs(
        vocab_size=VOCAB, hidden_size=32, intermediate_size=48, num_heads=2,
        head_dim=16, linear_heads=2, linear_key_dim=8, linear_value_dim=16,
        conv_kernel=4, layer_kinds=(gdf.LINEAR,) * 3 + (gdf.FULL,),
        rms_eps=1e-6)),
    "latent_selector": (lm, lm.LatentMoEArgs(
        vocab_size=VOCAB, hidden_size=32, num_layers=3,
        indexer=lm.IndexerConfig(2, 8, 16), record_selection=True,
        **_EXPERTS)),
    "latent_delta": (ldf, ldf.LatentDeltaMoEArgs(
        vocab_size=VOCAB, hidden_size=32,
        layer_mixers=(ldf.DELTA, ldf.LATENT, ldf.DELTA),
        linear_key_heads=2, linear_value_heads=4, linear_key_dim=8,
        linear_value_dim=16, conv_kernel=4, swiglu_limit=None, **_EXPERTS)),
}


@pytest.mark.parametrize("name", sorted(MODULES))
def test_a_family_module_states_the_whole_protocol(name):
    """Every name of `family_protocol.PROTOCOL`; a state tree whose every
    leaf has the slot axis first; a page copy that copies page `src` of
    every layer and every pool and nothing else; and what rides, in the
    one named value."""
    family, args = MODULES[name]
    args.validate()
    assert not [n for n in family_protocol.PROTOCOL
                if not hasattr(family, n)]
    assert {"model", "mesh=", "kv_dtype='int8'", "draft_params=",
            "hand-off"} <= set(family.UNSUPPORTED)
    counts, select_rows = family.riders(args)
    assert (counts, select_rows) == {
        "hybrid": (0, 0), "gated_delta": (0, 0),
        "latent_selector": (6, lm.SELECT_ROWS), "latent_delta": (4, 0)}[name]
    slots, pages, page = 3, 7, 8
    state = family.slot_state(args, slots, jnp.float32)
    assert {a.shape[0] for a in jax.tree_util.tree_leaves(state)} <= {slots}
    assert bool(jax.tree_util.tree_leaves(state)) == (family is not lm)

    def runs(a):
        """A pool leaf as [runs of pages (a layer each, or one), page, ..]."""
        return np.asarray(a).reshape((-1, pages) + a.shape[1:])

    pools = jax.tree_util.tree_map(
        lambda a: jnp.arange(a.size, dtype=a.dtype).reshape(a.shape),
        family.pools(args, pages, page, jnp.float32))
    assert jax.tree_util.tree_leaves(pools)
    copied = family.copy_page(pools, jnp.int32(2), jnp.int32(5), args)
    for old, new in zip(jax.tree_util.tree_leaves(pools),
                        jax.tree_util.tree_leaves(copied)):
        old, new = runs(old), runs(new)
        np.testing.assert_array_equal(new[:, 5], old[:, 2])
        np.testing.assert_array_equal(np.delete(new, 5, 1),
                                      np.delete(old, 5, 1))


def test_the_one_path_imports_no_family_and_paths_holds_the_only_table():
    """`serving/family.py` imports no `models/*_functional`, and the one
    dict keyed by an `*Args` type under `paddle_tpu/serving/` is `PATHS`,
    whose every family entry binds its module to the one class."""
    import paddle_tpu.serving.family as mod

    with open(mod.__file__) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            imported.update(f"{node.module}.{a.name}" for a in node.names)
    assert not [m for m in imported if "_functional" in m], sorted(imported)

    tables = set()
    for path in glob.glob(os.path.join(os.path.dirname(mod.__file__),
                                       "*.py")):
        with open(path) as f:
            for node in ast.walk(ast.parse(f.read())):
                if isinstance(node, ast.Dict) and any(
                        getattr(k, "attr", getattr(k, "id", "")).endswith(
                            "Args") for k in node.keys):
                    tables.add(os.path.basename(path))
    assert tables == {"paths.py"}
    bound = {t: e.keywords["family"] for t, e in paths.PATHS.items()
             if isinstance(e, functools.partial)}
    assert all(e.func is FamilyPath for e in paths.PATHS.values()
               if isinstance(e, functools.partial))
    assert bound == {hf.HybridArgs: hf, gdf.GatedDeltaArgs: gdf,
                     lm.LatentMoEArgs: lm, ldf.LatentDeltaMoEArgs: ldf}
    assert len(paths.PATHS) == len(bound) + 1         # and the dense path


def _toy_family(state_rows):
    """A family that states the protocol with no model: the token after t is
    t + 1, one pool of one value a page, and `state_rows` values a slot."""
    def logits(tokens):
        return jax.nn.one_hot((tokens + 1) % VOCAB, VOCAB)

    def prefill_window(params, layer_ids, ids, h, last_idx, bt_row,
                       new_pages, pools, state, tables, args, record=None):
        return (logits(ids[last_idx]), pools, state,
                family_protocol.StepRiders())

    def decode_step(params, layer_ids, tokens, bt, pos, live, pools, state,
                    tables, args, record=None):
        return logits(tokens), pools, state, family_protocol.StepRiders()

    return SimpleNamespace(
        UNSUPPORTED=dict(hf.UNSUPPORTED, model="a toy model"),
        pools=lambda args, num_pages, page_size, dtype: jnp.zeros(num_pages),
        copy_page=lambda pools, src, dst, args: family_protocol._move_rows(
            pools, pools, dst, src),
        slot_state=lambda args, slots, dtype: tuple(
            jnp.zeros((slots, state_rows)) for _ in range(bool(state_rows))),
        tables=lambda args, max_len: (),
        check_engine=lambda args, eng: None,
        gauges=lambda args, state, pools: {},
        riders=lambda args: (0, 0),
        observe_prefill=lambda args, eng, rows: {},
        observe_decode=lambda args, eng, active: {},
        prefill_window=prefill_window, decode_step=decode_step)


class ToyTreeArgs(NamedTuple):
    vocab_size: int = VOCAB
    num_layers: int = 1

    def validate(self):
        pass


def test_a_family_with_an_empty_state_tree_takes_no_snapshot(monkeypatch):
    """One line in `PATHS` serves a module that states the protocol. An
    empty state tree takes no snapshot id, counts no `state_snapshots`,
    sets no state gauge and serves under `prefix_policy='hash'`; a tree
    with a leaf needs the radix tree, for its snapshots."""
    monkeypatch.setitem(paths.PATHS, ToyTreeArgs, functools.partial(
        FamilyPath, family=_toy_family(0)))
    kw = dict(max_slots=2, max_len=64, page_size=8, min_bucket=8,
              prefill_chunk=8)
    params = {"embedding": jnp.zeros((VOCAB, 1))}
    eng = PagedEngine(params, ToyTreeArgs(), prefix_policy="hash", **kw)
    assert eng.path.snapshots == 0 and eng._alloc.take_snapshot() is None
    first = np.arange(1, 21, dtype=np.int32)
    a, b = eng.serve([Request(first, 5), Request(first[:7], 3)])
    assert a.token_ids == _after(first, 5) and b.token_ids == _after(
        first[:7], 3)
    req = eng.submit(Request(first, 9))
    while len(req.token_ids) < 2:
        eng.step()
    saved = eng.preempt(eng.slots.active_slots[0])
    assert not jax.tree_util.tree_leaves(saved["path_state"])
    eng.resume(saved)
    eng.run_until_idle()
    assert req.token_ids == _after(first, 9)
    seen = eng.metrics.summary()
    assert "state_snapshots" not in seen["counters"]
    assert "recurrent_state_bytes" not in seen["gauges"]
    assert seen["gauges"]["kv_pool_bytes"]["value"] > 0
    assert eng.path.pending == {} and eng.path.tokens.shape == (2,)

    monkeypatch.setitem(paths.PATHS, ToyTreeArgs, functools.partial(
        FamilyPath, family=_toy_family(4)))
    with pytest.raises(ValueError, match="a toy model needs "
                       "prefix_policy='radix'"):
        PagedEngine(params, ToyTreeArgs(), prefix_policy="hash", **kw)
    eng = PagedEngine(params, ToyTreeArgs(), **kw)
    assert eng.serve([Request(first, 5)])[0].token_ids == _after(first, 5)
    seen = eng.metrics.summary()
    assert seen["counters"]["state_snapshots"] == 1
    assert seen["gauges"]["recurrent_state_bytes"]["value"] == 2 * 4 * 4
