"""The serving loop keeps one program in flight (`Engine._step_action`): with
program k on the device a `step()` schedules, builds and dispatches k + 1,
THEN reads k's output, emits its tokens and returns its event. On the CPU,
at the four families' tiny presets (the dense one of `test_paged_kv.py`, the
three of the families' own suites):

  ORDER   every token output of the step programs is wrapped so that its
          conversion to the host is logged: k's is read after k + 1 went
          out, each exactly once, and nothing else is read in between (the
          latent path's routing counts ride that one read);
  PARITY  the tokens are bit for bit the synchronous loop's (the same
          engine at depth 0: `step_phases.synchronous`), and sequential
          `generate`'s where there is one, under chunked prefill, a prefix
          hit that ends mid-page (a copy-on-write split, a state snapshot),
          sampling rows and an EOS;
  EOS     found a program late: the extra token is never emitted and is
          counted, pages go back once, the slot's next request is right;
  SETTLE  `preempt` / `resume`, a hand-off, `reset()` read what is in
          flight first; so does the first step a profiler session records;
          an engine with a draft never dispatches ahead;
  DRAIN   `run_until_idle` and `replay` leave nothing in flight, and the
          events are the synchronous loop's on a trace without EOS;
  and the counters and the phases' identifiers of a look-ahead step.
"""

import functools
import importlib.util
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from paddle_tpu.models import llama_functional as lf  # noqa: E402
from paddle_tpu.models.generation import (draft_from_params,  # noqa: E402
                                          generate)
from paddle_tpu.serving import (DisaggServer, PagedEngine,  # noqa: E402
                                Request)
from paddle_tpu.serving import engine as engine_mod  # noqa: E402

from phase_ids import (check_identifiers, entries,  # noqa: E402
                       record_annotations, step_and_check_dispatch)
from step_phases import counting_clock, synchronous  # noqa: E402
import test_gated_delta_serving as gated  # noqa: E402
import test_hybrid_serving as hybrid  # noqa: E402
import test_latent_moe_serving as latent  # noqa: E402

DENSE = lf.LlamaArgs(vocab_size=128, hidden_size=64, intermediate_size=176,
                     num_layers=2, num_heads=4, num_kv_heads=2,
                     rope_theta=10000.0, rms_eps=1e-6, use_flash=False)
ENGINE = dict(max_slots=3, max_len=128, page_size=8, num_pages=80,
              min_bucket=8, prefill_chunk=16)
PATH_KINDS = ["dense", "hybrid", "gated_delta", "latent"]
COUNTERS = ("serve.dispatched", "serve.dispatched_ahead", "serve.settled",
            "serve.discarded_rows")


def _family_file(name):
    path = os.path.join(ROOT, "benchmarks", "families", name + ".py")
    spec = importlib.util.spec_from_file_location("ahead_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def _model(family):
    """(params, args, vocab) of a family's tiny preset."""
    from benchmarks.harness import weights

    if family == "dense":
        return lf.init_params(DENSE, jax.random.key(0)), DENSE, 128
    if family == "latent":
        fam = _family_file("mla_moe")
        return (fam.make_params(latent.ARCH, 11, jnp.float32),
                fam.serve_args(latent.ARCH), 256)
    name, arch = {"hybrid": ("minicpm_sala", hybrid.ARCH),
                  "gated_delta": ("gated_delta_hybrid", gated.ARCH)}[family]
    fam = _family_file(name)
    return (weights.make_params(fam, arch, 11, jnp.float32),
            fam.serve_args(arch), 256)


@functools.lru_cache(maxsize=None)
def _engine(family):
    """ONE engine a family for the whole file (its compiled programs are
    the cost); every test leaves it idle and resets it first."""
    params, args, _ = _model(family)
    return PagedEngine(params, args, **ENGINE)


def fresh(family):
    eng = _engine(family)
    eng.__dict__.pop("_looks_ahead", None)     # `synchronous` undone
    eng.reset()
    return eng


def _ids(family, n, seed):
    vocab = _model(family)[2]
    return np.random.default_rng(seed).integers(1, vocab, n).astype(np.int32)


def _first_unique(toks, at):
    return next(i for i in range(at, len(toks)) if toks[i] not in toks[:i])


def _counters(eng):
    return {n: eng.metrics.counter(n) for n in COUNTERS}


def _idle(eng):
    return eng._flight is None and eng._kept is None \
        and not eng.queue and not eng.slots.active_slots


# ---------------------------------------------------------------------------
# (a) ORDER
# ---------------------------------------------------------------------------

class Out:
    """A token output of a step program, as it lies on the device: it goes
    into the next program as it is, and every conversion to the host is
    logged under the program's number."""

    def __init__(self, raw, tag, log):
        self.raw, self.tag, self.log = raw, tag, log
        self.shape, self.dtype = raw.shape, raw.dtype

    def __jax_array__(self):
        return self.raw

    def _host(self):
        self.log.append(("read",) + self.tag)
        return np.asarray(self.raw)

    def __array__(self, dtype=None, copy=None):
        return self._host()

    def __int__(self):
        return int(self._host())

    __index__ = __int__

    def tolist(self):
        return self._host().tolist()

    def item(self):
        return self._host().item()


def _spy_programs(path, log):
    """Every jitted program of `path` that makes or takes the tokens gives
    them out wrapped (`Out`) and takes them unwrapped, so a conversion
    ANYWHERE (the engine's or the path's own) is logged."""
    count = {"n": 0}

    def wrap(program, kind):
        def call(*args, **kw):
            args = [a.raw if isinstance(a, Out) else a for a in args]
            got = program(*args, **kw)
            if kind == "seat":
                log.append(("seat",))
                return Out(got, ("seat",), log)
            tag = (kind, count["n"])
            count["n"] += 1
            log.append(("dispatch",) + tag)
            return tuple(
                Out(x, tag, log) if isinstance(x, jax.Array)
                and x.dtype == jnp.int32 and x.ndim <= 1 else x
                for x in got)
        return call

    path._prefill = {s: wrap(f, "prefill") for s, f in path._prefill.items()}
    path._decode = {s: wrap(f, "decode") for s, f in path._decode.items()}
    path._seat = wrap(path._seat, "seat")


@pytest.mark.parametrize("family", PATH_KINDS)
def test_a_programs_output_is_read_after_the_next_one_went_out(family,
                                                               monkeypatch):
    eng = fresh(family)
    path, log = eng.path, []
    saved = path._prefill, path._decode, path._seat
    landed = []
    monkeypatch.setattr(path, "landed", lambda out, real=path.landed: (
        landed.append(type(out)), log.append(("landed",)), real(out))[-1])
    _spy_programs(path, log)
    try:
        # two prompts of one window each (three slots: no one waits for a
        # slot), then decode steps for both until both end by length
        reqs = [eng.submit(Request(_ids(family, n, 100 + n), 6))
                for n in (9, 13)]
        eng.run_until_idle()
    finally:
        path._prefill, path._decode, path._seat = saved
        path.tokens = getattr(path.tokens, "raw", path.tokens)
    assert all(r.finished and len(r.token_ids) == 6 for r in reqs)

    steps = [e for e in log if e[0] in ("dispatch", "read")]
    programs = [e[1:] for e in steps if e[0] == "dispatch"]
    assert [k for k, _ in programs] == ["prefill"] * 2 + ["decode"] * 5
    # k + 1 goes out, THEN k is read: d0 d1 r0 d2 r1 .. d6 r5 r6; each
    # output is read once, in order, and nothing else is ever converted
    want = [("dispatch",) + programs[0]]
    for k, nxt in zip(programs, programs[1:]):
        want += [("dispatch",) + nxt, ("read",) + k]
    want.append(("read",) + programs[-1])
    assert steps == want
    # a prompt's first token is seated on the device right behind its window
    assert [log[i + 1] for i, e in enumerate(log)
            if e[:2] == ("dispatch", "prefill")] == [("seat",)] * 2
    # what else rides a decode step's read-back reaches the path as the host
    # copy the engine made, right after that read
    assert landed == [np.ndarray] * 5
    assert [log[i - 1][:2] for i, e in enumerate(log)
            if e == ("landed",)] == [("read", "decode")] * 5
    if family == "latent":
        obs = eng.metrics.summary()["observations"]
        assert obs["serve.routed_here_share"]["count"] == 5
    assert _counters(eng) == {"serve.dispatched": 7,
                              "serve.dispatched_ahead": 6,
                              "serve.settled": 0, "serve.discarded_rows": 0}


# ---------------------------------------------------------------------------
# (b) PARITY
# ---------------------------------------------------------------------------

def _traffic(family, eos=None):
    """Two waves. First a 37-token prompt (three chunks) beside a sampling
    row; then, with the first prompt's pages and the state at its end in the
    tree, a prompt that continues it (the hit ends mid-page: a copy-on-write
    split, and a snapshot where the family keeps one), a sampling row that
    may hit EOS, and a second sampling row."""
    base = _ids(family, 37, 1)
    first = [Request(base, 5, request_id="base"),
             Request(_ids(family, 11, 2), 7, request_id="sampled",
                     temperature=0.8, top_p=0.9, top_k=20, seed=5)]
    second = [Request(np.concatenate([base, _ids(family, 6, 3)]), 6,
                      request_id="continues"),
              Request(_ids(family, 9, 4), 12, request_id="eos",
                      eos_token_id=eos, temperature=0.9, seed=11),
              Request(_ids(family, 21, 6), 6, request_id="sampled2",
                      temperature=1.1, seed=9)]
    return first, second


def _serve(eng, family, eos=None):
    waves = _traffic(family, eos)
    for wave in waves:
        for r in wave:
            eng.submit(r)
        eng.run_until_idle()
        assert _idle(eng)
    return {r.request_id: (list(r.token_ids), r.finish_reason)
            for wave in waves for r in wave}


@pytest.mark.parametrize("family", PATH_KINDS)
def test_tokens_are_the_synchronous_loops(family):
    eng = synchronous(fresh(family))
    free = _serve(eng, family)
    # a token of the row past its second, the first of its kind, ends it
    toks = free["eos"][0]
    eos = toks[_first_unique(toks[:10], 2)]
    want = _serve(synchronous(fresh(family)), family, eos)
    assert want["eos"][1] == "eos" and len(want["eos"][0]) < 12
    sync = _counters(eng)
    assert sync["serve.dispatched_ahead"] == sync["serve.discarded_rows"] == 0

    eng = fresh(family)
    got = _serve(eng, family, eos)
    assert got == want
    c = eng.metrics.summary()["counters"]
    assert c["cow_copies"] >= 1 and c["prefix_tokens_hit"] >= 37
    assert c["chunked_prefills"] >= 1
    if eng.path.snapshots:
        assert c["state_snapshots"] >= 1
    assert c["serve.dispatched_ahead"] > 0.8 * c["serve.dispatched"]
    assert c["serve.discarded_rows"] == 1      # the EOS row's extra step
    assert eng._alloc.pages_in_use == 0 and eng._reserved_total == 0

    if family == "dense":
        params = _model(family)[0]
        for wave in _traffic(family, eos):
            for r in wave:
                if r.temperature == 0:
                    row = np.asarray(generate(
                        params, DENSE, r.prompt_ids[None],
                        max_new_tokens=r.max_new_tokens))[0]
                    seq = row[r.prompt_ids.size:].tolist()
                    n = len(got[r.request_id][0])
                    assert got[r.request_id][0] == seq[:n]


# ---------------------------------------------------------------------------
# (c) EOS found late
# ---------------------------------------------------------------------------

def _reference(prompt, new):
    row = np.asarray(generate(_model("dense")[0], DENSE, prompt[None],
                              max_new_tokens=new))[0]
    return row[prompt.size:].tolist()


def test_a_row_that_hit_eos_has_already_run_once_more():
    eng = fresh("dense")
    prompt = _ids("dense", 10, 21)
    ref = _reference(prompt, 12)
    stop = _first_unique(ref, 2)
    req = eng.submit(Request(prompt, 12, eos_token_id=ref[stop]))
    emitted = []
    req.stream_cb = lambda r, tok, done: emitted.append((tok, done))
    eng.run_until_idle()
    # the step after the EOS was on the device when the EOS was read: it is
    # dropped unread, its token never emitted
    assert req.token_ids == ref[:stop + 1] and req.finish_reason == "eos"
    assert emitted == [(t, False) for t in ref[:stop]] + [(ref[stop], True)]
    c = _counters(eng)
    assert c["serve.discarded_rows"] == 1 and _idle(eng)
    # programs: the prompt, `stop` decode steps read, one dropped
    assert c["serve.dispatched"] == stop + 2
    assert eng.metrics.counter("decode_steps") == stop
    # its pages went back once (a second release would raise), the
    # reservation with them
    assert eng._alloc.pages_in_use == 0 and eng._reserved_total == 0
    assert eng._alloc.available == eng._alloc.capacity
    assert not eng._npos.any() and req.in_flight == 0
    # and the slot's next request is right
    other = _ids("dense", 14, 22)
    nxt = eng.serve([Request(other, 9)])[0]
    assert nxt.token_ids == _reference(other, 9)


def test_a_late_eos_beside_a_live_row_and_a_waiting_request():
    """Two slots taken, a third request waiting: the row that hits EOS is in
    the next step beside the other row (that step is read, the dead row's
    token skipped), its slot then goes to the waiting request."""
    params, args, _ = _model("dense")
    eng = PagedEngine(params, args, **dict(ENGINE, max_slots=2))
    a, b, c = (_ids("dense", n, 30 + n) for n in (9, 12, 7))
    ref = _reference(a, 10)
    stop = _first_unique(ref, 2)
    reqs = eng.serve([Request(a, 10, eos_token_id=ref[stop]),
                      Request(b, 14), Request(c, 8)])
    assert reqs[0].token_ids == ref[:stop + 1]
    assert reqs[1].token_ids == _reference(b, 14)
    assert reqs[2].token_ids == _reference(c, 8)
    assert _counters(eng)["serve.discarded_rows"] == 1 and _idle(eng)
    assert eng._alloc.pages_in_use == 0 and eng._reserved_total == 0


# ---------------------------------------------------------------------------
# (d) SETTLE
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", PATH_KINDS)
def test_preempt_and_resume_with_a_program_in_flight(family):
    prompt, other = _ids(family, 13, 41), _ids(family, 9, 42)
    eng = synchronous(fresh(family))
    want = eng.serve([Request(prompt, 14, temperature=0.7, seed=3)])[0]

    eng = fresh(family)
    victim = eng.submit(Request(prompt, 14, temperature=0.7, seed=3))
    while len(victim.token_ids) < 4:
        eng.step()
    assert eng._flight is not None and victim.in_flight == 1
    slot = eng.slots.active_slots[0]
    state = eng.preempt(slot)
    # what was in flight was read and emitted first: the state that leaves
    # holds the token the device had made
    assert eng._flight is None and victim.in_flight == 0
    assert len(victim.token_ids) == 5
    assert state["last_tok"] == victim.token_ids[-1]
    assert state["npos"] == prompt.size + 4
    assert _counters(eng)["serve.settled"] == 1
    # the settled program's event is the next call's
    between = eng.submit(Request(other, 5))
    ev = eng.step()
    assert ev["type"] == "decode" and \
        ev["tokens"] == {victim.request_id: victim.token_ids[-1]}
    eng.run_until_idle()
    assert between.finished and not victim.finished
    eng.resume(state)
    eng.run_until_idle()
    assert victim.token_ids == want.token_ids and _idle(eng)
    assert eng._alloc.pages_in_use == 0 and eng._reserved_total == 0


def test_a_hand_off_is_seated_with_nothing_in_flight():
    params, args, _ = _model("dense")
    kw = dict(max_slots=3, max_len=64, page_size=8, min_bucket=8)
    prompts = [_ids("dense", n, 50 + n) for n in (7, 12, 18, 5)]
    want = [_reference(p, 9) for p in prompts]
    srv = DisaggServer(params, args, **kw)
    reqs = [srv.submit(Request(prompts[0], 9))]
    for _ in range(4):           # the first decodes before the others arrive
        srv.step()
    reqs += [srv.submit(Request(p, 9)) for p in prompts[1:]]
    srv.run_until_idle()
    assert [r.token_ids for r in reqs] == want
    dec, pre = srv.decode.metrics, srv.prefill.metrics
    assert dec.counter("handoffs_admitted") == 4
    # a hand-off that found a decode step in flight read it first
    assert dec.counter("serve.settled") >= 1
    assert dec.counter("serve.dispatched_ahead") > 0
    assert pre.counter("serve.dispatched_ahead") > 0
    for eng in (srv.prefill, srv.decode):
        assert _idle(eng) and eng._alloc.pages_in_use == 0


def test_reset_reads_what_is_in_flight_first():
    eng = fresh("dense")
    req = eng.submit(Request(_ids("dense", 9, 61), 6))
    eng.step()
    assert eng._flight is not None
    with pytest.raises(RuntimeError, match="still in flight"):
        eng.reset()
    # it settled before it refused: nothing is on the device unread
    assert eng._flight is None and _counters(eng)["serve.settled"] == 1
    eng.run_until_idle()
    assert req.token_ids == _reference(req.prompt_ids, 6)
    eng.reset()
    assert _idle(eng) and _counters(eng) == dict.fromkeys(COUNTERS, 0)


@pytest.mark.parametrize("family", PATH_KINDS)
def test_a_recording_opens_on_a_program_it_saw_dispatched(family,
                                                          monkeypatch):
    """A profiler session that opens between two calls finds a program in
    flight whose dispatch it did not see: the first recorded step reads and
    emits it BEFORE it dispatches the next one, once; the events and tokens
    are those of a run nobody recorded."""
    def reqs():
        return [Request(_ids(family, 21, 81), 9, temperature=0.8, seed=5,
                        request_id="a"),
                Request(_ids(family, 6, 82), 7, request_id="b")]

    eng = fresh(family)
    plain = [eng.submit(r) for r in reqs()]
    want = []
    while not _idle(eng):
        want.append(eng.step())

    eng = fresh(family)
    on = [False]
    monkeypatch.setattr(engine_mod, "recording", lambda: on[0])
    seen = record_annotations(monkeypatch)
    got, mine = [], [eng.submit(r) for r in reqs()]
    while len(mine[0].token_ids) < 3:
        got.append(eng.step())
    assert eng._flight is not None and _counters(eng)["serve.settled"] == 0
    on[0], ahead = True, _counters(eng)["serve.dispatched_ahead"]
    del seen[:]
    got.append(eng.step())
    # the program that was in flight is read and emitted first, the next one
    # goes out behind it with nothing in flight: not counted as ahead
    order = [name.rsplit(".", 1)[1] for name, ids in seen
             if name.startswith("pt.serve.") and name != "pt.serve.step"
             and ids.get("part") != "build"]
    assert order.index("wait") < order.index("emit") < order.index("stage")
    c = _counters(eng)
    assert c["serve.settled"] == 1 and c["serve.dispatched_ahead"] == ahead
    assert eng._flight is not None and eng._kept is None
    # the session goes on: nothing more settles, the loop looks ahead again
    got.append(eng.step())
    assert _counters(eng)["serve.dispatched_ahead"] == ahead + 1
    on[0] = False
    while not _idle(eng):
        got.append(eng.step())
    assert _counters(eng)["serve.settled"] == 1
    assert got == want
    assert [r.token_ids for r in mine] == [r.token_ids for r in plain]


def test_a_real_profiler_session_settles_its_first_step_once(tmp_path):
    eng = fresh("dense")
    prompt = _ids("dense", 9, 83)
    req = eng.submit(Request(prompt, 12))
    for _ in range(3):
        eng.step()
    assert eng._flight is not None
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            eng.step()
        assert _counters(eng)["serve.settled"] == 1 and \
            eng._flight is not None
    eng.run_until_idle()
    assert _counters(eng)["serve.settled"] == 1
    assert req.token_ids == _reference(prompt, 12)
    # a session that finds nothing in flight has nothing to read
    eng.reset()
    with jax.profiler.trace(str(tmp_path)):
        assert eng.serve([Request(prompt, 4)])[0].token_ids == \
            _reference(prompt, 4)
    assert _counters(eng)["serve.settled"] == 0


def test_an_engine_with_a_draft_never_dispatches_ahead():
    params, args, _ = _model("dense")
    dp, da = draft_from_params(params, args, 1)
    eng = PagedEngine(params, args, max_slots=2, max_len=64, page_size=8,
                      min_bucket=8, prefill_chunk=16, draft_params=dp,
                      draft_args=da, spec_tokens=3)
    prompts = [_ids("dense", n, 70 + n) for n in (20, 6)]
    reqs = eng.serve([Request(p, 8) for p in prompts])
    assert [r.token_ids for r in reqs] == [_reference(p, 8) for p in prompts]
    c = _counters(eng)
    # three windows (a stream of two, a short prompt): each was read before
    # anything else went out
    assert c == {"serve.dispatched": 3, "serve.dispatched_ahead": 0,
                 "serve.settled": 0, "serve.discarded_rows": 0}
    assert eng.metrics.counter("spec_rounds") >= 1 and _idle(eng)


# ---------------------------------------------------------------------------
# (e) DRAIN
# ---------------------------------------------------------------------------

def _events(eng, drive):
    """The events of every `step()` call `drive` makes."""
    seen, step = [], eng.step

    def spy():
        seen.append(step())
        return seen[-1]

    eng.step = spy
    try:
        out = drive()
    finally:
        del eng.step
    return seen, out


def test_run_until_idle_ends_with_nothing_in_flight_and_the_same_events():
    """Three requests on three slots, one a chunk stream, no EOS: nothing
    arrives and no slot is waited for between two calls, so the sequence of
    events is the synchronous loop's, event for event."""
    prompts = [_ids("dense", n, 80 + n) for n in (40, 6, 11)]

    def drive(eng):
        return _events(eng, lambda: eng.serve(
            [Request(p, 5 + i, request_id=f"r{i}")
             for i, p in enumerate(prompts)]))

    want, _ = drive(synchronous(fresh("dense")))
    eng = fresh("dense")
    got, reqs = drive(eng)
    assert got == want and _idle(eng)
    assert {e["type"] for e in got} == {"prefill", "prefill_chunk", "decode"}
    assert all(r.in_flight == 0 and r.finished for r in reqs)


def test_replay_ends_with_nothing_in_flight_and_the_same_events():
    trace = [{"arrival_step": 0, "prompt": _ids("dense", n, 90 + n),
              "max_new_tokens": 4 + i, "request_id": f"t{i}",
              "temperature": 0.6 * (i == 1), "seed": 7}
             for i, n in enumerate((19, 8, 5))]

    def drive(eng):
        return _events(eng, lambda: eng.replay(trace))

    want, ref = drive(synchronous(fresh("dense")))
    eng = fresh("dense")
    got, reqs = drive(eng)
    assert got == want and _idle(eng)
    assert [r.token_ids for r in reqs] == [r.token_ids for r in ref]
    assert [r.ttft_steps for r in reqs] == [r.ttft_steps for r in ref]


# ---------------------------------------------------------------------------
# (f) the counters and the phases' identifiers of a look-ahead step
# ---------------------------------------------------------------------------

def test_a_look_ahead_steps_entries_name_the_program_each_belongs_to(
        monkeypatch):
    eng = fresh("dense")
    first, second = _ids("dense", 9, 95), _ids("dense", 20, 96)
    a = eng.submit(Request(first, 6, request_id="a"))
    b = eng.submit(Request(second, 6, request_id="b"))
    seen = record_annotations(monkeypatch)
    ev = eng.step()
    # the first call: a's window goes out, b's first chunk goes out behind
    # it, a's window is read
    assert ev["type"] == "prefill" and ev["request_id"] == "a"
    stage = [(i["request_id"], i["part"]) for i in entries(seen, "stage")]
    assert stage == [("a", "build"), ("a", "dispatch"),
                     ("b", "build"), ("b", "dispatch")]
    (wait,) = entries(seen, "wait")
    assert wait["request_id"] == "a" and wait["kind"] == "prefill" \
        and wait["tokens"] == 9 and wait["bucket"] == 16 \
        and wait["start"] == 0
    names = [n for n, _ in seen]
    assert names.index("pt.serve.wait") > max(
        i for i, n in enumerate(names) if n == "pt.serve.stage")
    del seen[:]
    ev = eng.step()
    # the second: b's last window [16, 20) goes out, its first chunk is read
    assert ev == {"type": "prefill_chunk", "request_id": "b",
                  "slot": 1, "from": 0, "to": 16}
    assert [(i["kind"], i["part"], i["start"], i["tokens"])
            for i in entries(seen, "stage")] == [
                ("prefill", "build", 16, 4), ("prefill", "dispatch", 16, 4)]
    (wait,) = entries(seen, "wait")
    assert (wait["kind"], wait["request_id"], wait["tokens"],
            wait["start"]) == ("prefill", "b", 16, 0)
    del seen[:]
    ev = eng.step()
    # the third: a decode step of both rows goes out (b's first token is on
    # the device, seated), b's last window is read and its token emitted
    assert ev["type"] == "prefill" and ev["request_id"] == "b" \
        and b.token_ids == [ev["token"]]
    assert [(i["kind"], i["part"], i["rows"])
            for i in entries(seen, "stage")] == [
                ("decode", "build", 2), ("decode", "dispatch", 2)]
    (wait,) = entries(seen, "wait")
    assert (wait["kind"], wait["start"], wait["bucket"]) == ("prefill", 16, 8)
    # to the stall rule the step is the program it read: a window of bucket 8
    assert (eng._phase.kind, eng._phase.bucket) == ("prefill", 8)
    eng.run_until_idle()
    check_identifiers(seen)
    assert a.finished and b.finished
    c = _counters(eng)
    programs = eng.metrics.counter("decode_steps") + 3
    assert c == {"serve.dispatched": programs,
                 "serve.dispatched_ahead": programs - 1,
                 "serve.settled": 0, "serve.discarded_rows": 0}


def test_the_four_phases_tile_a_look_ahead_step(monkeypatch):
    eng = fresh("dense")
    counting_clock(monkeypatch)
    for n in (30, 7):
        eng.submit(Request(_ids("dense", n, 97 + n), 5))
    kinds = set()
    while eng.queue or eng.slots.active_slots:
        ahead = eng.metrics.counter("serve.dispatched_ahead")
        ev, phases, dispatch = step_and_check_dispatch(eng)
        kinds.add(ev["type"])
        assert phases["wait"] >= 1 and phases["emit"] >= 1
        if eng.metrics.counter("serve.dispatched_ahead") > ahead:
            # the next program's build and dispatch are this call's stage
            assert 1 <= dispatch < phases["stage"]
    assert kinds == {"prefill", "prefill_chunk", "decode"} and _idle(eng)
