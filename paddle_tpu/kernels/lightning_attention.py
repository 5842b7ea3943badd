"""Lightning (linear) attention with a per-head scalar decay: the recurrence

    S_t = lambda_h * S_{t-1} + k_t v_t^T        o_t = d^-1/2 * q_t^T S_t

as a CHUNKED SCAN for a prefill window and as ONE STEP for a decode batch.
The state S [heads, d, d] is float32 and is the layer's whole memory of the
context: it does not grow with the context's length.

`lightning_chunk_scan` splits a window into blocks of `block` tokens: inside
a block the causal product (Q K^T . D) V with the decay matrix D[t, s] =
lambda^(t-s); across blocks the carried state, read with the decay since
the block's start and advanced by the block's keys and values (Lightning
Attention-2, Qin et al. 2024). A window is right-padded to a length bucket:
`valid` marks its real tokens, a padded token neither decays the state nor
adds to it, so the state the window leaves is the state at its last real
token. Decays are formed as exp of a difference of cumulative log-decays,
never as a ratio of two powers: head 0 loses 0.57 of its state a token, and
lambda^-128 is past float32.

Plain `jax.numpy`: XLA fuses the elementwise decay into the two block
matmuls, and the state's read-modify-write is one pass over 2 MiB a request
and layer. Both entry points carry the device scope `pt.lightning_attention`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["lightning_slopes", "lightning_chunk_scan", "lightning_step"]

_HIGHEST = jax.lax.Precision.HIGHEST


def lightning_slopes(num_heads):
    """Per-head log-decay s_h = 2^(-8 (h + 1) / H); lambda_h = exp(-s_h)."""
    h = jnp.arange(1, num_heads + 1, dtype=jnp.float32)
    return jnp.exp2(-8.0 * h / num_heads)


@jax.named_scope("pt.lightning_attention")
def lightning_chunk_scan(q, k, v, state, slopes, valid, block=128):
    """q, k, v [s, H, d]; state [H, d, d] float32 (S before the window);
    slopes [H]; valid [s] bool. Returns (out [s, H, d] in q's type, the
    state after the window's last valid token). s must be a multiple of
    min(block, s)."""
    s, H, d = q.shape
    C = min(int(block), s)
    if s % C:
        raise ValueError(f"window of {s} tokens is no multiple of {C}")
    nb = s // C
    tril = jnp.tril(jnp.ones((C, C), bool))

    def body(S, xs):
        qb, kb, vb, gb = xs                       # [C, H, d] x3, [C] f32
        # a[t]: real tokens of the block up to and with t, so -slope * a is
        # the log-decay from the block's start to t
        a = jnp.cumsum(gb)
        A = -slopes[:, None] * a[None, :]                         # [H, C]
        kb = kb * gb[:, None, None].astype(kb.dtype)
        sc = jnp.einsum("thd,shd->hts", qb, kb,
                        preferred_element_type=jnp.float32)
        D = jnp.exp(jnp.where(tril[None], A[:, :, None] - A[:, None, :],
                              -jnp.inf))
        o = jnp.einsum("hts,shd->thd", (sc * D).astype(vb.dtype), vb,
                       preferred_element_type=jnp.float32)
        q32, k32, v32 = (x.astype(jnp.float32) for x in (qb, kb, vb))
        o = o + jnp.einsum("thd,hde->the", q32 * jnp.exp(A).T[:, :, None],
                           S, precision=_HIGHEST)
        kw = k32 * jnp.exp(A[:, -1:] - A).T[:, :, None]
        S = (jnp.exp(A[:, -1])[:, None, None] * S
             + jnp.einsum("shd,she->hde", kw, v32, precision=_HIGHEST))
        return S, o

    split = lambda x: x.reshape((nb, C) + x.shape[1:])
    state, out = jax.lax.scan(
        body, state.astype(jnp.float32),
        (split(q), split(k), split(v), split(valid.astype(jnp.float32))))
    out = out.reshape(s, H, d) * (d ** -0.5)
    return out.astype(q.dtype), state


@jax.named_scope("pt.lightning_attention")
def lightning_step(q, k, v, state, slopes, live):
    """One token a row: q, k, v [b, H, d]; state [b, H, d, d] float32; live
    [b] bool (a row that is not decoding keeps its state untouched).
    Returns (out [b, H, d] in q's type, new state)."""
    d = q.shape[-1]
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    lam = jnp.exp(-slopes)[None, :, None, None]
    new = lam * state + k32[..., :, None] * v32[..., None, :]
    out = jnp.sum(q32[..., :, None] * new, axis=-2) * (d ** -0.5)
    state = jnp.where(live[:, None, None, None], new, state)
    return out.astype(q.dtype), state
