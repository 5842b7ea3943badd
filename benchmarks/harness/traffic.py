"""The one traffic generator: it reads a mix's parameters from a data file
and makes, from the seed, either training batches or a schedule of serving
sessions. A new mix is a new file of parameters, never new code.

Every seed gets the SAME sizes and arrival gaps in the SAME order: they are
the evenly spaced quantiles of the mix's distributions (a pool of `pool`
values) laid out once in a base order that the mix file fixes
(`schedule_seed`), and walked again and again; any `pool` consecutive draws
from a pass's start hold the whole pool. The seed draws the tokens (and the
weights). A request lives about as long as a window on this system, so
which sizes a window holds, and in which order, decides its work: with
sizes reordered by the seed, seeds differed by 7% in tokens/s while two
runs of one seed agreed to the digit (chip runs, PR 23).

Serving mix (`"kind": "serve"`):
  arrival      {"process": "poisson", "rate_per_s": r}: sessions arrive in an
               open loop; or {"process": "backlog", "queue_depth": n}: the
               generator keeps n requests waiting at all times
  pool, schedule_seed
               see above
  ramp_s       seconds of the same traffic before the window opens (set-up);
               a backlog may state `ramp_steps` instead: engine steps before
               the window opens, so that it opens at one point of the trace
  initial_sessions
               sessions that arrive together at the start of the ramp: the
               population a steady state would hold, so that a short ramp
               starts near it instead of at an empty system. They are in
               mid-conversation: the i-th has i mod (its turns) turns behind
               it, random tokens standing for the answers it once got
  tenants, system_prompt_tokens
               each session belongs to a tenant and starts with its
               tenant's system prompt (0 tenants: no shared prefix)
  turns        {"min", "max"}: requests of one session, each appending a
               user message to the whole history; closed loop inside a
               session (the next turn waits for the answer plus think time)
  user_tokens, answer_tokens, think_s
               distributions: {"dist": "lognormal", "median", "sigma", "min",
               "max"} | {"dist": "exponential", "mean"} | {"dist": "const",
               "value"}
  max_context  a session ends before a turn whose prompt plus answer passes it

Training mix (`"kind": "train"`): rows, seq_len, micro_batches, tokens
uniform over the vocabulary, a new batch every step.
"""

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def quantile_pool(dist, n):
    """n evenly spaced quantiles of `dist`, ascending."""
    qs = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "const":
        vals = np.full(n, float(dist["value"]))
    elif kind == "exponential":
        vals = -float(dist["mean"]) * np.log1p(-qs)
    elif kind == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(q)) for q in qs])
        vals = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    elif kind == "uniform_int":
        vals = np.floor(dist["min"] + qs * (dist["max"] + 1 - dist["min"]))
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in dist or "max" in dist:
        vals = np.clip(vals, dist.get("min", -math.inf),
                       dist.get("max", math.inf))
    return vals


class _Cycle:
    """An endless walk over a fixed pool in its base order (fixed by
    `base_rng`)."""

    def __init__(self, pool, base_rng):
        self.pool = np.asarray(pool)[base_rng.permutation(len(pool))]
        self.i = 0

    def next(self):
        self.i += 1
        return self.pool[(self.i - 1) % len(self.pool)]


def _rng(seed, stream):
    return np.random.default_rng([int(seed), stream])


class Session:
    """One client: a system prompt and a few turns, each a request."""

    __slots__ = ("sid", "due", "history", "turns", "turn", "think")

    def __init__(self, sid, due, history, turns, think):
        self.sid, self.due, self.history = sid, due, history
        self.turns, self.turn, self.think = turns, 0, think

    def request(self):
        """(prompt, max_new_tokens) of the next turn, or None when done."""
        if self.turn >= len(self.turns):
            return None
        user, answer = self.turns[self.turn]
        return np.concatenate([self.history, user]), answer

    def answered(self, prompt, tokens, now):
        """The answer came: it joins the history; the next turn is due after
        the think time. Returns that due time or None."""
        self.history = np.concatenate([prompt, np.asarray(tokens, np.int32)])
        think = self.think[self.turn]
        self.turn += 1
        if self.turn >= len(self.turns):
            return None
        self.due = now + think
        return self.due


class ServeTraffic:
    """Sessions in arrival order. `next_session(t)` hands out the next one,
    due at the time the arrival process gives (or at `t` for a backlog)."""

    def __init__(self, mix, vocab_size, seed):
        self.mix, self.vocab = mix, int(vocab_size)
        n = int(mix.get("pool", 256))
        self.arrival = mix["arrival"]
        self.ramp_s = float(mix.get("ramp_s", 0.0))
        self._tok = _rng(seed, 2)
        base = _rng(mix.get("schedule_seed", 0), 3)

        def stream(dist):
            return _Cycle(quantile_pool(dist, n), base)

        self._user = stream(mix["user_tokens"])
        self._answer = stream(mix["answer_tokens"])
        self._think = stream(mix["think_s"])
        turns = mix["turns"]
        self._turns = stream({"dist": "uniform_int", "min": turns["min"],
                              "max": turns["max"]})
        self.tenants = int(mix.get("tenants", 0))
        self._tenant = _Cycle(np.arange(max(self.tenants, 1)), base)
        sys_len = int(mix.get("system_prompt_tokens", 0))
        self._system = [self._tokens(sys_len) for _ in range(self.tenants)]
        if self.arrival["process"] == "poisson":
            gap = {"dist": "exponential",
                   "mean": 1.0 / float(self.arrival["rate_per_s"])}
            self._gap = stream(gap)
        elif self.arrival["process"] != "backlog":
            raise ValueError(f"unknown arrival {self.arrival['process']!r}")
        self._clock = -self.ramp_s     # due time of the next poisson arrival
        self._initial = int(mix.get("initial_sessions", 0))
        self._sid = 0
        self.max_context = int(mix["max_context"])

    def _tokens(self, n):
        # token 0 is the engine's pad id; keep it out of prompts
        return self._tok.integers(1, self.vocab, int(n)).astype(np.int32)

    def next_due(self):
        """When the next session is due (poisson); None for a backlog."""
        if self.arrival["process"] != "poisson":
            return None
        return -self.ramp_s if self._initial > 0 else self._clock

    def next_session(self, now):
        behind = 0
        if self.arrival["process"] != "poisson":
            due = now
        elif self._initial > 0:
            behind = self._initial
            self._initial -= 1
            due = -self.ramp_s
        else:
            due = self._clock
            self._clock += float(self._gap.next())
        tenant = int(self._tenant.next())
        history = (self._system[tenant] if self.tenants
                   else np.zeros(0, np.int32))
        turns, think, used = [], [], len(history)
        for _ in range(int(self._turns.next())):
            user, answer = int(self._user.next()), int(self._answer.next())
            if used + user + answer > self.max_context:
                break
            turns.append((self._tokens(user), answer))
            think.append(float(self._think.next()))
            used += user + answer
        if not turns:    # even one turn does not fit: a minimal one does
            turns, think = [(self._tokens(16), 8)], [0.0]
        for _ in range(behind % len(turns)):
            user, answer = turns.pop(0)
            think.pop(0)
            history = np.concatenate([history, user, self._tokens(answer)])
        self._sid += 1
        return Session(self._sid, due, history, turns, think)


class TrainTraffic:
    """A new batch every step: rows x (seq_len + 1) uniform tokens; inputs
    are all but the last column, labels all but the first."""

    def __init__(self, mix, vocab_size, seed):
        self.rows, self.seq_len = int(mix["rows"]), int(mix["seq_len"])
        self.micro_batches = int(mix["micro_batches"])
        self.vocab, self.seed = int(vocab_size), int(seed)

    @property
    def tokens_per_step(self):
        return self.rows * self.seq_len

    def batch(self, step):
        rng = _rng(self.seed, 1000 + step)
        t = rng.integers(0, self.vocab, (self.rows, self.seq_len + 1),
                         dtype=np.int32)
        return np.ascontiguousarray(t[:, :-1]), np.ascontiguousarray(t[:, 1:])
