"""The gated delta rule (Gated Delta Networks, arXiv:2412.06464): a linear
attention whose state forgets by a data-dependent decay `a_t` in (0, 1) and
is corrected, not only added to, by a data-dependent write strength `b_t`
in (0, 2):

    S_t = a_t (I - b_t k_t k_t^T) S_{t-1} + b_t k_t v_t^T      o_t = S_t^T q_t

with S [heads, dk, dv] float32, the layer's whole memory of the context, and
the SHORT CONVOLUTION that feeds it: a causal depthwise filter of `K` taps
over the projections' channels, whose state is the last `K - 1` rows of its
input.

`delta_chunk_scan` is the rule over a prefill window in chunks of C tokens.
With the pseudo-values u_t = b_t (v_t - a_t S_{t-1}^T k_t) the rule is
S_t = a_t S_{t-1} + k_t u_t^T, a decayed SUM, and inside a chunk that
starts from S (G the chunk's cumulative log-decay, D[t, i] = exp(G_t - G_i))

    (I + N) U = diag(b) (V - diag(e^G) K S),   N[t, i] = b_t D[t, i] k_t.k_i
                                                 for i < t, else 0
    O = diag(e^G) Q S + tril(Q K^T . D) U
    S' = e^{G_C} S + (K . e^{G_C - G})^T U

(the WY / UT transform, section 3.3 of the paper). T = (I + N)^-1 diag(b)
does not hold S, so every chunk's T, T V and T (e^G K) are formed at once
before the scan; the scan carries S alone. `(I + N)^-1` is formed by
RECURSIVE DOUBLING of its diagonal blocks: the inverse of [[A, 0], [L, B]]
is [[A^-1, 0], [-B^-1 L A^-1, B^-1]], six levels from 1 x 1 to 64 x 64,
each two 64 x 64 matmuls a chunk and head over the whole block-diagonal
matrix (`unit_lower_inverse`). It is block forward substitution, so its intermediates are
inverses of sub-blocks, bounded as the whole is; the closed product (I -
N)(I + N^2)(I + N^4).. forms N^32, whose entries pass 1e20 where keys
repeat and b is near 2 and cancel to nothing in float32
(`tests/test_gated_delta_serving.py` holds that case), and XLA expands
`solve_triangular` on a 64 x 64 block into 64 sequential row updates.

Decays are `exp` of differences of cumulative logs, never ratios of
products: a head may lose all but e^-80 of its state in a token. A window is
right-padded to a length bucket: a padded token has log a = 0 and b = 0, so
it neither decays the state nor writes to it, and the convolution keeps the
last REAL rows of its input.

`delta_step` is one token a row for a decode batch. With kS = k^T S and qS
= q^T S of the OLD state: u = b (v - a kS), o = a qS + (q.k) u (no read of
the new state), S' = a S + k u^T. The equations need the state once in and
once out. On a TPU, where the shape fits, that is what the step does: ONE
Pallas pass (`delta_rule_step`), a grid step a row (and block of its
row-groups) that holds the block in VMEM, forms both reductions, the
pseudo-value and the output from it and writes the new block over the one
it read (`input_output_aliases`: the program holds one copy of the state; a
row that is not decoding gets its block back bit for bit). Every product
and sum is float32 on the VPU; the MXU stays out (at `highest` it would take
the state in three splits). The jnp form (`_step_jnp`) makes TWO passes,
because `u` depends on the first reduction's result and XLA cannot fuse
across that: 0.79 ms a layer at the Olmo cell's `[64, 15, 96, 384]` where
the kernel takes 0.43, the time of its copies (PERF.md, PR 42). It stays as the CPU's form and
as the fallback for a shape the kernel does not take (rows that fill no
whole 128-lane tiles, a key width off the 8 sublanes, more than 64 heads):
the choice is read from the shapes and the backend (`qm._mode()`), never
from a flag. The chunked scan stays jnp: its work is matmuls.

THE STATE'S LAYOUT between steps is `[.., H / p, dk, p * dv]`: `p` heads
side by side in a row (`heads_per_row`: 2 where dv is no multiple of the
TPU's 128 lanes and H is even, else 1). The TPU pads an array's last axis to
whole lanes: a `[.., 96, 192]` float32 state is stored and moved as `[.., 96,
256]`, a third more bytes in every pass of every decode step, and two heads
of 192 fill 384 = 3 x 128 lanes exactly. `delta_step` works on that layout
as it is, in both forms (a head's k and q are spread over its own lanes,
per-head scalars repeated over them: no reshape of the state; the kernel
takes a head's k / q column and its three gates out of one `[dk + 8, 128]`
tile a row, a head a lane, by a lane gather, where spread over the state's
lanes in HBM they would be a second state's worth of bytes); a prefill window unpacks its one slot's state
before the scan and packs it after (`unpack_state` / `pack_state`).

Float32 at matmul precision `highest` wherever the state is touched. Device
scopes: `pt.delta_rule` (both entry points), `pt.short_conv` (the
convolution's two).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.kernels import quantized_matmul as qm

__all__ = ["unit_lower_inverse", "delta_chunk_scan", "delta_step",
           "heads_per_row", "pack_state", "unpack_state",
           "short_conv_window", "short_conv_step"]

_HIGHEST = jax.lax.Precision.HIGHEST


def unit_lower_inverse(n):
    """(I + n)^-1 for n [..., C, C] (only its strictly lower part is read;
    C a power of two), by recursive doubling of the diagonal blocks. X holds
    the inverses of the diagonal s x s blocks (block diagonal); with L the
    lower-left s x s corners of the diagonal 2s x 2s blocks of n, X - X L X
    holds the inverses of those: two C x C matmuls a level and batch entry,
    whatever s, and no gather (the first form took the corners out with an
    indexed gather and multiplied [s, s] blocks; this one compiles in half
    the time and read +10% prefill tokens/s on the chip: PERF.md, PR 37)."""
    C = n.shape[-1]
    if C & (C - 1):
        raise ValueError(f"chunk of {C} tokens is no power of two")
    i = jnp.arange(C)
    row, col = i[:, None], i[None, :]
    # blocks of 1: each inverse is 1, and X L X = L
    X = jnp.eye(C, dtype=n.dtype) - jnp.where(
        (row // 2 == col // 2) & (row > col), n, 0.0)
    s = 2
    while s < C:
        corner = ((row // (2 * s) == col // (2 * s))
                  & (row // s % 2 == 1) & (col // s % 2 == 0))
        L = jnp.where(corner, n, 0.0)
        X = X - jnp.matmul(jnp.matmul(X, L, precision=_HIGHEST), X,
                           precision=_HIGHEST)
        s *= 2
    return X


@jax.named_scope("pt.delta_rule")
def delta_chunk_scan(q, k, v, log_a, b, state, valid, chunk=64):
    """q, k [s, H, dk] (k of unit norm a head), v [s, H, dv]; log_a, b [s,
    H] float32 (log of the decay, the write strength); state [H, dk, dv]
    float32 (S before the window); valid [s] bool. Returns (out [s, H, dv]
    float32, the state after the window's last valid token). s must be a
    multiple of min(chunk, s)."""
    s, H, dk = q.shape
    C = min(int(chunk), s)
    if s % C:
        raise ValueError(f"window of {s} tokens is no multiple of {C}")
    nb = s // C
    f32 = jnp.float32
    # [nb, H, C, ..]: a chunk and head a matrix problem
    split = lambda x: jnp.swapaxes(
        x.astype(f32).reshape((nb, C) + x.shape[1:]), 1, 2)
    q, k, v = split(q), split(k), split(v)
    # a padded token: log a = 0 and b = 0 (its row holds a pad token's
    # values, finite)
    g = split(jnp.where(valid[:, None], log_a, 0.0)[..., None])[..., 0]
    b = split(jnp.where(valid[:, None], b, 0.0)[..., None])[..., 0]
    G = jnp.cumsum(g, axis=-1)
    tril = jnp.tril(jnp.ones((C, C), bool))
    D = jnp.exp(jnp.where(tril, G[..., :, None] - G[..., None, :], -jnp.inf))
    kk = jnp.einsum("nhtd,nhsd->nhts", k, k, precision=_HIGHEST)
    N = jnp.where(jnp.tril(tril, -1), b[..., :, None] * D * kk, 0.0)
    T = unit_lower_inverse(N) * b[..., None, :]            # (I + N)^-1 diag(b)
    eG = jnp.exp(G)[..., None]
    Wv = jnp.matmul(T, v, precision=_HIGHEST)                    # T V
    Wk = jnp.matmul(T, eG * k, precision=_HIGHEST)               # T (e^G K)
    A = jnp.einsum("nhtd,nhsd->nhts", q, k, precision=_HIGHEST) * D
    q_in = eG * q                                  # reads the chunk's S
    k_out = jnp.exp(G[..., -1:] - G)[..., None] * k  # carries to its end
    decay = jnp.exp(G[..., -1])[..., None, None]

    def body(S, xs):
        Wv_c, Wk_c, A_c, q_c, k_c, d_c = xs
        U = Wv_c - jnp.matmul(Wk_c, S, precision=_HIGHEST)       # [H, C, dv]
        o = (jnp.matmul(q_c, S, precision=_HIGHEST)
             + jnp.matmul(A_c, U, precision=_HIGHEST))
        S = d_c * S + jnp.einsum("htk,htv->hkv", k_c, U, precision=_HIGHEST)
        return S, o

    state, out = jax.lax.scan(body, state.astype(f32),
                              (Wv, Wk, A, q_in, k_out, decay))
    return jnp.swapaxes(out, 1, 2).reshape(s, H, -1), state


def heads_per_row(heads, dv):
    """Heads laid side by side in a row of the stored state."""
    return 2 if dv % 128 and heads % 2 == 0 else 1


def pack_state(S, p):
    """[.., H, dk, dv] -> [.., H / p, dk, p * dv]: head p * i + j's values
    in lanes [j * dv, (j + 1) * dv) of row-group i."""
    *lead, H, dk, dv = S.shape
    S = S.reshape(*lead, H // p, p, dk, dv)
    return jnp.swapaxes(S, -3, -2).reshape(*lead, H // p, dk, p * dv)


def unpack_state(S, p):
    """The inverse of `pack_state`."""
    *lead, Hp, dk, width = S.shape
    S = S.reshape(*lead, Hp, dk, p, width // p)
    return jnp.swapaxes(S, -3, -2).reshape(*lead, Hp * p, dk, width // p)


def _over_lanes(x, p, dv):
    """x [r, H, n] (a value a head and row of the state) -> [r, H / p, n, p *
    dv]: each head's value over its own dv lanes."""
    r, H, n = x.shape
    x = x.reshape(r, H // p, p, n)
    if p == 1:
        return jnp.broadcast_to(x[:, :, 0, :, None], (r, H, n, dv))
    first = jnp.arange(2 * dv) < dv
    return jnp.where(first, x[:, :, 0, :, None], x[:, :, 1, :, None])


def _step_jnp(q, k, v, a, b, qk, state, live):
    """The step in plain `jax.numpy`: two passes over the state (XLA cannot
    make one of them: `u` needs the first's result). The CPU's form, and
    the TPU's where the shape does not fit the kernel."""
    r, H, dv = v.shape
    p = H // state.shape[1]
    # per-head scalars over the state's lanes
    lanes = lambda x: jnp.repeat(x.reshape(r, H // p, p), dv, -1)
    a, b, qk = lanes(a), lanes(b), lanes(qk)
    v = v.reshape(r, H // p, p * dv)
    # k^T S and q^T S in one read of the state (k and q stacked while they
    # are small: spread over the lanes they stay a broadcast inside the
    # reduction, where a stack of two spread operands is written out)
    kq = _over_lanes(jnp.concatenate([k, q], -1), p, dv)
    red = jnp.sum(kq.reshape(r, H // p, 2, -1, p * dv) * state[:, :, None],
                  axis=-2)
    u = b * (v - a * red[:, :, 0])                      # [r, H / p, p * dv]
    out = a * red[:, :, 1] + qk * u
    new = a[:, :, None] * state + _over_lanes(k, p, dv) * u[:, :, None]
    return (out.reshape(r, H, dv),
            jnp.where(live[:, None, None, None], new, state))


_LANES = 128


def _step_block(state_shape, heads):
    """Row-groups of the state a grid step of the kernel holds (a divisor of
    H / p: the largest whose four blocks, in and out, each double-buffered,
    fit the budget), or 0 where the shape does not fit the kernel: the rows
    must fill whole lanes and whole sublanes, and every head's k and q
    column must find a lane in ONE tile."""
    _, G, dk, width = state_shape
    if width % _LANES or dk % 8 or 2 * heads > _LANES:
        return 0
    fit = qm._VMEM_BUDGET_BYTES // (4 * dk * width * 4)
    return max((g for g in range(1, G + 1) if G % g == 0 and g <= fit),
               default=0)


def _by_head(per_head, dv, width):
    """per_head: one [n, 128] tile a head of the row-group, each constant
    over its lanes -> [n, width] that holds head j's values in lanes [j *
    dv, (j + 1) * dv), a 128-lane tile at a time: a tile inside one head is
    that head's as it is, only a tile that straddles two heads is a
    select."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    tiles = []
    for t in range(width // _LANES):
        first, last = t * _LANES // dv, ((t + 1) * _LANES - 1) // dv
        tile = per_head[last]
        for j in range(last - 1, first - 1, -1):
            tile = jnp.where(lane + t * _LANES < (j + 1) * dv, per_head[j],
                             tile)
        tiles.append(tile)
    return jnp.concatenate(tiles, axis=-1)


def _column(tile, lane):
    """Every lane of the [n, 128] tile takes its lane `lane`: a head's
    column, as wide as a tile."""
    return jnp.take_along_axis(tile, jnp.full(tile.shape, lane, jnp.int32),
                               axis=1)


def _step_kernel(live_ref, cols_ref, v_ref, s_ref, o_ref, new_ref, *, heads,
                 dv):
    """Grid step (row i, block j of its row-groups). live [r] int32 in SMEM;
    cols [dk + 8, 128], what the row's heads bring, a head a LANE: head h's k
    down lane h and its q down lane H + h of the first dk rows, its a, b and
    q.k in lane h of the next three; v, o [gb, p * dv]; s, new [gb, dk, p *
    dv], ONE array outside (`input_output_aliases`): what is read here is
    written here, by this step alone."""
    i, j = pl.program_id(0), pl.program_id(1)
    gb, dk, width = s_ref.shape
    p = width // dv

    @pl.when(live_ref[i] == 0)
    def _():
        new_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live_ref[i] != 0)
    def _():
        kq, gates = cols_ref[:dk], cols_ref[dk:]

        def group(g, carry):
            h0 = (j * gb + g) * p
            over = lambda tile, first: _by_head(
                [_column(tile, first + m) for m in range(p)], dv, width)
            k, q, abq = over(kq, h0), over(kq, heads + h0), over(gates, h0)
            a, b, qk = abq[0:1], abq[1:2], abq[2:3]
            S = s_ref[g]
            u = b * (v_ref[pl.ds(g, 1), :]
                     - a * jnp.sum(k * S, axis=0, keepdims=True))
            o_ref[pl.ds(g, 1), :] = (
                a * jnp.sum(q * S, axis=0, keepdims=True) + qk * u)
            new_ref[g] = a * S + k * u
            return carry

        # unrolled (traced once): the compiler's schedule for a v5e is 172
        # bundles a head pair against 305 as a loop, a pair's copy 338
        jax.lax.fori_loop(0, gb, group, 0, unroll=True)


def _step_pallas(q, k, v, a, b, qk, state, live):
    r, H, dv = v.shape
    _, G, dk, width = state.shape
    gb = _step_block(state.shape, H)
    # a head a lane of one 128-lane tile a row: its k and q as columns over
    # dk, its three gates under them. 53 KB a row, where k and q spread
    # over the state's lanes (what the jnp form fuses into its passes)
    # would be a second state's worth
    kq = jnp.pad(jnp.concatenate([k, q], 1),
                 ((0, 0), (0, _LANES - 2 * H), (0, 0)))
    gates = jnp.pad(jnp.stack([a, b, qk], 1),
                    ((0, 0), (0, 5), (0, _LANES - H)))
    cols = jnp.concatenate([jnp.swapaxes(kq, 1, 2), gates], 1)
    rows = lambda i, j, _: (i, j, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(r, G // gb),
        in_specs=[
            pl.BlockSpec((None, dk + 8, _LANES), lambda i, j, _: (i, 0, 0)),
            pl.BlockSpec((None, None, gb, width), rows),
            pl.BlockSpec((None, gb, dk, width), rows),
        ],
        out_specs=[
            pl.BlockSpec((None, None, gb, width), rows),
            pl.BlockSpec((None, gb, dk, width), rows),
        ],
    )
    out, new = pl.pallas_call(
        functools.partial(_step_kernel, heads=H, dv=dv),
        out_shape=[jax.ShapeDtypeStruct((r, G // gb, gb, width), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        grid_spec=grid_spec,
        # operand 3 (after the prefetched one) is the state, result 1 its
        # successor: one buffer
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="delta_rule_step",
        interpret=qm._mode()[1],
    )(live.astype(jnp.int32), cols, v.reshape(r, G // gb, gb, width), state)
    return out.reshape(r, H, dv), new


def step_is_pallas(state_shape, heads):
    """Whether `delta_step` on a state of this shape is the Pallas kernel
    (a TPU, or `fused_dispatch`, and a shape that fits) or the jnp form."""
    return bool(qm._mode()[0] and _step_block(state_shape, heads))


@jax.named_scope("pt.delta_rule")
def delta_step(q, k, v, log_a, b, state, live):
    """One token a row: q, k [r, H, dk], v [r, H, dv], log_a, b [r, H];
    state [r, H / p, dk, p * dv] float32 (`pack_state`'s layout); live [r]
    bool (a row that is not decoding keeps its state untouched). Returns
    (out [r, H, dv] float32, state)."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    a, b, qk = jnp.exp(log_a.astype(f32)), b.astype(f32), jnp.sum(q * k, -1)
    step = (_step_pallas if step_is_pallas(state.shape, v.shape[1])
            else _step_jnp)
    return step(q, k, v, a, b, qk, state, live)


def _taps(u_ext, w, s):
    """sum_i w[:, i] * u_ext[i : i + s] in float32; w [channels, K]."""
    w = w.astype(jnp.float32)
    return sum(w[:, i] * u_ext[..., i:i + s, :].astype(jnp.float32)
               for i in range(w.shape[1]))


@jax.named_scope("pt.short_conv")
def short_conv_window(u, w, state, last_idx):
    """The causal depthwise convolution of a window: u [s, channels] its
    input rows, w [channels, K] the filter, state [K - 1, channels] the
    input rows just before the window (zeros before position 0), last_idx
    the window's last real row. Returns (silu(conv) [s, channels] float32,
    the last K - 1 REAL rows of the input, in the state's type)."""
    s, K = u.shape[0], w.shape[1]
    ext = jnp.concatenate([state.astype(u.dtype), u], axis=0)
    out = jax.nn.silu(_taps(ext, w, s))
    keep = jax.lax.dynamic_slice_in_dim(ext, last_idx + 1, K - 1, axis=0)
    return out, keep.astype(state.dtype)


@jax.named_scope("pt.short_conv")
def short_conv_step(u, w, state, live):
    """One row a slot: u [r, channels], state [r, K - 1, channels], live
    [r] bool (a dead row keeps its state). Returns (silu(conv) [r,
    channels] float32, state)."""
    ext = jnp.concatenate([state, u[:, None].astype(state.dtype)], axis=1)
    out = jax.nn.silu(_taps(ext, w, 1)[:, 0])
    return out, jnp.where(live[:, None, None], ext[:, 1:], state)
