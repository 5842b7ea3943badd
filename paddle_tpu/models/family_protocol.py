"""What `serving/family.FamilyPath` asks of a model family, stated once. A
family is one functional module (`hybrid_functional`,
`gated_delta_functional`, `latent_delta_functional`, `latent_moe_functional`)
that defines every name in `PROTOCOL`, and one entry in `serving/paths.PATHS`:

  UNSUPPORTED   {"model": what the refusals call the family, "mesh=",
                "kv_dtype='int8'", "draft_params=", "hand-off": why each is
                refused}
  pools(args, num_pages, page_size, dtype)   the paged cache, a tree
  copy_page(pools, src, dst, args)   page `src` onto page `dst` in every
                leaf and layer (the one fact of the pools' layout the path
                needs: `_move_rows` where the page axis is a leaf's axis 0)
  slot_state(args, slots, dtype)   what a request keeps beside its pages, a
                tree whose every leaf has the SLOT axis first; empty where a
                request is its pages alone (no snapshot is then taken)
  tables(args, max_len)   constants of the step programs
  check_engine(args, eng)   raises where the engine's sizes do not fit
  gauges(args, state, pools)   {gauge: value} of how its programs are built
  riders(args)  (counts a decode step appends to its tokens, consecutive
                queries of a prefill window whose selection is kept); 0: none
  observe_prefill(args, eng, rows) / observe_decode(args, eng, active)
                {observation: value} of a window / a step, from the host's
                numbers
  prefill_window(params, layer_ids, ids, h, last_idx, bt_row, new_pages,
                 pools, state, tables, args, record=None)
                one window of one slot: ids [s] at positions h .. h + s - 1,
                real up to `last_idx`; bt_row [P] the slot's block table,
                new_pages the pages the window writes from the one that holds
                h on; `state` the SLOT's own (no slot axis), zero where h ==
                0; `record` the first window row whose selection is kept, or
                None -> (logits [vocab] at last_idx, pools, state, StepRiders)
  decode_step(params, layer_ids, tokens, bt, pos, live, pools, state,
              tables, args, record=None)
                one token a slot: tokens [b] at positions pos [b] through
                block tables bt [b, P]; live [b] the rows that decode (the
                others keep their state, write to the null page and count
                for nothing); `record` the row whose selection is returned,
                or None -> (logits [b, vocab], pools, state, StepRiders)

`layer_ids` is `arange(layers)` as an operand (a stack indexed by it is
indexed at run time); a family that scans its layers ignores it.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax

__all__ = ["PROTOCOL", "StepRiders"]

PROTOCOL = ("UNSUPPORTED", "pools", "copy_page", "slot_state", "tables",
            "check_engine", "gauges", "riders", "observe_prefill",
            "observe_decode", "prefill_window", "decode_step")


class StepRiders(NamedTuple):
    """What a step returns beside logits, pools and state; None where the
    family (or its description: `record_routing`, `record_selection`) has
    none."""

    counts: Any = None      # int32 [riders(args)[0]], a decode step's
    picks: Any = None       # [expert layers, rows, experts a token]
    selection: Any = None   # packed bits of the kept queries' selected keys


def _move_rows(dst, src, to, frm):
    """dst[to] = src[frm] along axis 0 of every leaf of two like trees."""
    return jax.tree_util.tree_map(
        lambda d, s: jax.lax.dynamic_update_slice_in_dim(
            d, jax.lax.dynamic_slice_in_dim(s, frm, 1, axis=0), to, axis=0),
        dst, src)
