"""The host's half of what an expert stack's step programs return beside
their tokens (`models/family_protocol.StepRiders`), for `serving/family.py`.

  - COUNTS. Once a decode step the program makes four counts summed over the
    expert layers (tokens at the busiest held expert, picks that landed on a
    held expert, picks in all, held experts with a token) and, behind a
    token selector, two more over the live rows (the keys they selected, the
    keys they could see). They ride the step's one read-back behind the
    rows' next tokens, and `landed` turns the host copy into the
    observations `serve.expert_load_max_over_mean`,
    `serve.routed_here_share`, `serve.held_experts_hit` (a layer),
    `serve.selected_keys` and `serve.visible_keys`.
  - PICKS. Where the description asks (`record_routing`: an operator or a
    judge of the served tokens does, a deployment does not), both step
    programs also return the experts every token picked, and a request
    carries a ROUTING TRACE, `req.routing` (`RoutingTrace`). Routing is
    discrete: a token whose last pick and first miss score alike can go
    either way on rounding, and what the model then computes differs by a
    whole expert; the benchmark's reference so follows the picks the
    program made, after checking each against its own scores. A prefill
    window's picks hang on its request; a decode step's are ONE entry of
    the step log for all its rows (the device array as it is, the rows that
    were live, their positions), and a trace finds its rows there when it
    is read: nothing waits for them and no step does work a row. The log
    lives until `reset`: 9 KB a step at 64 rows.
  - SELECTIONS. Where the description asks (`record_selection`), a request's
    trace also holds the selections of a SAMPLE of its queries, every layer,
    as packed bits (a bit a table position: 9 KB a query a layer):
    `select_rows` consecutive queries of each prefill window from a row
    drawn from a seed of the window (`window_row`), and one live row of
    every `SELECT_EVERY`-th decode step, the rows in turn (`step_row`), for
    whoever judges the selection itself (`RoutingTrace.selections`).
"""

from __future__ import annotations

import numpy as np

__all__ = ["RoutingTrace", "RoutingRiders", "SELECT_EVERY"]

SELECT_EVERY = 8     # decode steps between two kept selections


class RoutingTrace:
    """The experts one request's tokens picked: its prefill windows' picks
    `(first position, count, picks [expert layers, rows, experts a
    token])`, and where its decode rows lie in the path's step log:
    `[slot, first step, end step]` for each stay in a slot (one, unless it
    was preempted)."""

    def __init__(self, log):
        self._log, self._windows, self._stays = log, [], []
        # (a window's first position, its count, the first row kept, the
        # kept rows' selections as packed bits [layers, rows, positions / 8])
        self._selected = []

    def window(self, position, count, picks, row=None, selected=None):
        self._windows.append((int(position), int(count), picks))
        if selected is not None:
            self._selected.append((int(position), int(count), int(row),
                                   selected))

    def seat(self, slot):
        """Its decode rows are row `slot` of the steps logged from now on
        (a seat no step was logged under, a window before the last, goes)."""
        if self._stays and self._stays[-1][2] is None:
            self._stays.pop()
        self._stays.append([int(slot), len(self._log), None])

    def leave(self):
        """Preempted: its stay ends with the last step logged."""
        self._stays[-1][2] = len(self._log)

    def _decode_rows(self, positions):
        """(position, the step's log entry, its slot) for the request's
        decode rows below `positions`: one position after the other from its
        last window's end on, in every logged step of its stays that ran
        its row."""
        at = self._windows[-1][0] + self._windows[-1][1]
        for slot, first, end in self._stays:
            for entry in self._log[first:end]:
                live, pos = entry[1], entry[2]
                if at >= positions or (live[slot] and pos[slot] != at):
                    break           # read to the end, or the slot's next owner
                if live[slot]:
                    yield at, entry, slot
                    at += 1

    def table(self, positions):
        """int32 [positions, expert layers, experts a token]: the picks of
        the request's tokens 0 .. positions - 1 (of all the published
        experts); -1 where none was recorded (a position served from the
        prefix cache: its window never ran for this request)."""
        layers, _, k = self._windows[0][2].shape
        out = np.full((positions, layers, k), -1, np.int32)
        for first, count, picks in self._windows:
            count = min(count, positions - first)
            if count > 0:
                out[first:first + count] = np.swapaxes(
                    np.asarray(picks)[:, :count], 0, 1)
        for at, entry, slot in self._decode_rows(positions):
            if not isinstance(entry[0], np.ndarray):
                entry[0] = np.asarray(entry[0])
            out[at] = entry[0][:, slot]
        return out

    def selections(self, positions):
        """[(position, [layers] int arrays)]: the positions that the sampled
        queries below `positions` selected in each layer, lowest first."""
        def unpacked(bits):
            return np.unpackbits(np.asarray(bits), axis=-1,
                                 bitorder="little")

        out = []
        for first, count, row, kept in self._selected:
            kept = unpacked(kept)                   # [layers, rows, T]
            for j in range(min(kept.shape[1], count - row)):
                if first + row + j < positions:
                    out.append((first + row + j, [
                        np.nonzero(layer[j])[0] for layer in kept]))
        for at, entry, slot in self._decode_rows(positions):
            if entry[3] is not None and entry[4] == slot:
                out.append((at, [np.nonzero(layer)[0]
                                 for layer in unpacked(entry[3])]))
        return out



class RoutingRiders:
    """The step log every request's `RoutingTrace` reads, a request's trace
    made and seated at its prefill windows and moved with it through preempt
    and resume, the rows whose selection is kept, and the observations made
    of the counts that ride a decode step's read-back. `select_rows`: the
    second of the family's `riders(args)`."""

    def __init__(self, eng, select_rows):
        self.eng, self.select_rows = eng, select_rows
        self.reset()

    def reset(self):
        # a decode step's [picks, live rows, positions, the selection kept
        # or None, its slot]; a trace made before keeps the log it was
        # made over
        self.log = []
        self._steps = 0

    def window_row(self, req, start, last_idx):
        """The first of a window's queries whose selection is kept, a draw
        seeded by the window; None where none is."""
        if not self.select_rows:
            return None
        return np.int32(np.random.default_rng(
            [len(req.prompt_ids), start]).integers(
                0, max(1, last_idx + 2 - self.select_rows)))

    def step_row(self, active):
        """(the row whose selection this decode step returns: the live rows
        in turn; whether it is kept: every SELECT_EVERY-th step's is);
        (None, False) where none is."""
        if not self.select_rows:
            return None, False
        turn, skipped = divmod(self._steps, SELECT_EVERY)
        self._steps += 1
        return np.int32(active[turn % len(active)]), not skipped

    def window(self, req, slot, start, count, picks, row=None,
               selected=None):
        """A prefill window of `req` in `slot` ran: its picks (and kept
        selection) hang on the request, whose decode rows are the slot's
        from the next logged step on."""
        if picks is None and selected is None:
            return
        if getattr(req, "routing", None) is None:
            req.routing = RoutingTrace(self.log)
        req.routing.window(start, count, picks, row, selected)
        req.routing.seat(slot)

    def step(self, picks, live, pos, selected=None, row=None):
        """A decode step went out: one entry for all its rows."""
        if picks is not None or selected is not None:
            self.log.append([picks, live, pos, selected, row])

    def _trace(self, slot):
        return getattr(self.eng.slots.owner(slot), "routing", None)

    def leave(self, slot):
        """The slot's request is preempted: its stay ends here."""
        if (trace := self._trace(slot)) is not None:
            trace.leave()

    def seat(self, slot):
        """A preempted request resumes in `slot`."""
        if (trace := self._trace(slot)) is not None:
            trace.seat(slot)

    def landed(self, counts):
        """The counts behind a decode step's tokens, read: tokens at the
        busiest held expert, picks on held experts, picks in all, held
        experts with a token (summed over the expert layers) and, behind a
        selector, the keys selected and visible."""
        busiest, here, picks, hit, *keys = (int(x) for x in counts)
        m, args = self.eng.metrics, self.eng.args
        if keys:
            m.observe("serve.selected_keys", keys[0])
            m.observe("serve.visible_keys", keys[1])
        if picks:
            m.observe("serve.routed_here_share", here / picks)
            m.observe("serve.held_experts_hit",
                      hit / (args.num_layers - args.first_k_dense))
        if here:
            m.observe("serve.expert_load_max_over_mean",
                      busiest * args.experts_held / here)
