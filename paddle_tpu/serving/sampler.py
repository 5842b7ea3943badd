"""Per-request sampler shared by the serving engines.

One next-token selection waist for every traced engine step (stripe
prefill/decode, paged prefill/decode — `pick`), plus the host-side
per-slot sampling state both engines carry (`SlotSampler`). The math
itself lives in `models/generation._sample` (temperature, nucleus
top-p, top-k, gumbel-max per-row draws) so the OFFLINE
`generate(temperature=, top_p=, top_k=, seeds=)` path and the serving
engines share one implementation; keys come from
`generation._row_keys` — the one (seed, position) derivation, so a
request's randomness is a pure function of its own seed and the
position being sampled, never of its batch-mates.

Greedy is the default and stays the fast path: `pick(sample=False)`
compiles to a bare argmax (no sampling ops in the program), and inside
a mixed batch greedy rows (temperature 0) remain bit-exact argmax.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.models import generation as gen

__all__ = ["pick", "seat_token", "token_vector", "SlotSampler"]


@jax.named_scope("pt.sample")
def pick(logits, sample, temp, top_p, top_k, seeds, pos):
    """Next-token selection shared by every traced engine step: exact
    argmax for the greedy program (sample=False — the default, whose
    program contains no sampling ops at all), the per-row `_sample`
    machinery otherwise. Keys come from `generation._row_keys` — the ONE
    (seed, position) derivation `generate(seeds=...)` also uses."""
    if not sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return gen._sample(logits, True, temp, top_p, None, top_k,
                       row_keys=gen._row_keys(seeds, pos))


def token_vector(rows, pad_id):
    """The rows' last tokens, on the device: a paged path's decode program
    takes it as its token operand and its output IS the next one's, so a
    decoding row's token never goes through the host."""
    return jnp.full((rows,), pad_id, jnp.int32)


def seat_token(tokens, slot, token, *, metrics):
    """`tokens` with row `slot` set to `token`: how a token that no decode
    step made gets into the token vector (a prompt's first, a device scalar
    as its last window returned it; a host token of a resumed or handed-over
    request). One tiny program an engine, compiled with its first prompt."""
    metrics.inc("seat_compiles")
    return tokens.at[slot].set(token)


class SlotSampler:
    """Host-side per-slot sampling parameters (greedy defaults; loaded
    at admission, cleared at retire). The arrays feed the traced step
    programs as per-row operands, so changing a request's sampling
    settings never recompiles."""

    def __init__(self, max_slots):
        self.max_slots = int(max_slots)
        self._temp = np.zeros(self.max_slots, np.float32)
        self._top_p = np.ones(self.max_slots, np.float32)
        self._top_k = np.zeros(self.max_slots, np.int32)
        self._seed = np.zeros(self.max_slots, np.int32)
        self._device = None     # `device_args` of the rows as they stand

    def admit(self, slot, req):
        self._device = None
        self._temp[slot] = req.temperature
        self._top_p[slot] = req.top_p
        self._top_k[slot] = req.top_k
        self._seed[slot] = np.int32(req.seed)

    def clear(self, slot):
        self._device = None
        self._temp[slot] = 0.0
        self._top_p[slot] = 1.0
        self._top_k[slot] = 0
        self._seed[slot] = 0

    def reset(self):
        for slot in range(self.max_slots):
            self.clear(slot)

    def any_sampling(self, slots):
        """True when any of `slots` samples — selects the step-program
        variant (greedy-only traffic never compiles the sampling ops)."""
        return any(self._temp[s] > 0 for s in slots)

    def device_args(self):
        """The per-row operands the traced `pick` consumes: moved to the
        device when a row was admitted or cleared since, not every step.
        Copies: a row is admitted or cleared while the program that was
        given these may still be waiting for the device."""
        if self._device is None:
            self._device = tuple(jnp.asarray(x.copy()) for x in (
                self._temp, self._top_p, self._top_k, self._seed))
        return self._device
