"""The seam between `PagedEngine` (the host half of serving) and a model
family's device half (`serving/paths.py`): a family the engine has never
heard of is served by registering its path, and the engine's source names
no family."""

import ast
import os
from typing import NamedTuple

import numpy as np
import pytest

from paddle_tpu.serving import PagedEngine, Request, paths

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
