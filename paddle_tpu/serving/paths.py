"""The seam between `PagedEngine` and a model family.

`PagedEngine` is the HOST half of serving: admission, the allocator and the
block tables, reservations, chunk streams, the step loop, copy-on-write
bookkeeping, retire / preempt / resume, counters and spans. Everything on the
DEVICE (the pools and their layout, what a request keeps beside its pages,
the step programs) is one object a family, `eng.path`, built here from the
type of the model description the engine was given. The engine keeps one
program in flight (`Engine._step_action`): it reads what `prefill` and
`decode` return one call later, after the next program went out, and nothing
here may read a device value back itself. The engine calls:

  prefill(ids, start, last_idx, bt_row, new_vec, slot, req, sample) -> first
      one prefill window [start, start + last_idx] of a slot's prompt, `ids`
      [1, bucket] padded; the block-table row and the pages to write are
      host arrays. Returns the token after the window (a device scalar).
  decode(bt, active, sample, sampling_args) -> next
      one batched step over the block tables `bt` [slots, pages a slot].
      Its token operand is the path's TOKEN VECTOR, on the device (`tokens`:
      the step before's output as it is, with the rows `seat` set since),
      and its output becomes the vector; the positions are a copy of
      `eng._npos`. Returns the next tokens, a slot's at its index (a path
      may append what else should ride the one read-back a step makes: the
      engine reads a slot's row only, and hands the path the host copy it
      made: `landed`).
  seat(slot, token)         one row of the token vector set on the device:
                            a prompt's first token (`first`, a device
                            scalar) or a host token from elsewhere (a
                            resumed or handed-over request)
  landed(out)               the host copy of a decode step's output, when
                            the engine has read it
  copy_page(src, dst)       device half of a copy-on-write
  prompt_done(slot)         the slot's last prefill window ran
  load_snapshot(slot, sid)  a prefix hit that ends at snapshot `sid`
  attach(slot, prompt_ids, registered)   the slot retires
  take_state(slot) -> saved / put_state(slot, saved)   preempt / resume
  reset()                   an empty engine (arrays and programs stay)
  snapshots                 how many snapshot ids the allocator hands out
  check_handoff()           raises where a sequence is more than its pages;
                            else `extract_pages(pages)` / `scatter_pages(
                            pages, data_k, data_v)` move them (`disagg.py`)

A path is built as `Path(eng)` once the engine's own arguments are stored
(`eng.args`, `params`, `page_size`, `num_pages`, `max_slots`, `mesh`, ..)
and refuses there what its family cannot do. Two classes fill the interface:
`dense.DensePath` (a mesh, an int8 pool, the verify programs, the page mover)
and `family.FamilyPath`, written once over a tree of pools and a tree of
per-slot state for every other family. Adding a family: a functional module
under `models/` that states the protocol (`models/family_protocol.py`), one
entry in `PATHS`.
"""

from __future__ import annotations

import functools

from paddle_tpu.models import gated_delta_functional as gdf
from paddle_tpu.models import hybrid_functional as hf
from paddle_tpu.models import latent_delta_functional as ldf
from paddle_tpu.models import latent_moe_functional as lm
from paddle_tpu.models.llama_functional import LlamaArgs
from paddle_tpu.serving.dense import DensePath
from paddle_tpu.serving.family import FamilyPath

__all__ = ["PATHS", "path_for"]

# type of the model description -> the family's device half (the ONE table)
PATHS = {LlamaArgs: DensePath,
         hf.HybridArgs: functools.partial(FamilyPath, family=hf),
         gdf.GatedDeltaArgs: functools.partial(FamilyPath, family=gdf),
         lm.LatentMoEArgs: functools.partial(FamilyPath, family=lm),
         ldf.LatentDeltaMoEArgs: functools.partial(FamilyPath, family=ldf)}


def path_for(eng):
    """The device half of `eng` for the family `eng.args` describes."""
    try:
        cls = PATHS[type(eng.args)]
    except KeyError:
        raise TypeError(
            f"no serving path for a {type(eng.args).__name__}: "
            "paddle_tpu.serving.paths.PATHS holds "
            f"{sorted(t.__name__ for t in PATHS)}") from None
    return cls(eng)
