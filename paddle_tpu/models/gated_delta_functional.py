"""A decoder whose layers are of two kinds WITH DIFFERENT LEAVES, served over
`PagedEngine`'s paged cache: LINEAR layers (the gated delta rule behind a
short convolution, `kernels/gated_delta_rule.py`) and FULL layers
(multi-head softmax attention over pages, every head its own K and V, no
rotary positions). `GatedDeltaArgs` is the static description that selects
this path: `PagedEngine(params, GatedDeltaArgs(...))`.

Every layer is NORM-AFTER: `x += RMSNorm(Mixer(x)); x += RMSNorm(SwiGLU(x))`,
nothing is normed before a sublayer. Input `embedding[ids]`, output
`lm_head(RMSNorm(x))`.

  linear  u = [x Wq, x Wk, x Wv]; c = silu(causal depthwise conv of K taps
          over u); per head q = c_q / |c_q| * dk^-1/2, k = c_k / |c_k|, v =
          c_v; b = 2 sigmoid(x Wb), log a = -exp(A_log) softplus(x Wa +
          dt_bias); the delta rule gives o; out = (RMSNorm_head(o) *
          silu(x Wg)) Wo.
  full    q = RMSNorm(x Wq), k = RMSNorm(x Wk) over the whole width, v = x
          Wv; causal softmax(q k^T / sqrt(d)) v a head; Wo.

The parameter tree holds ONE STACK A KIND beside `embedding`, `final_norm`,
`lm_head`: `linear_attention/*` (`wq wk wv wg wo wa wb conv_w A_log dt_bias
o_norm ln1 ln2 w_gate w_up w_down`) and `full_attention/*` (`wq wk wv wo
q_norm k_norm ln1 ln2 w_gate w_up w_down`), each stacked on a leading axis
in layer order and indexed at run time (`_layer`). The layer loop is
unrolled.

Per-request state beside the pages, a tuple of one entry a linear layer:
`{"S": [slots, H / p, dk, p * dv] float32, "conv": [slots, K - 1,
channels]}` (the matrix state, `p` heads side by side in a row so that the
rows fill whole lanes: `gated_delta_rule.pack_state`; and the last K - 1
rows of the convolution's input, in the model's type). The pools are `(pk, pv)`, one `[num_pages, heads, page, d]`
array a full layer each.

What `serving/family.FamilyPath` asks of a family's functional module
(`models/family_protocol.py`) is the last section.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import gated_delta_rule as gdr
from paddle_tpu.kernels import quantized_matmul as qm
from paddle_tpu.kernels.paged_prefill_attention import _tile_pages
from paddle_tpu.models import llama_functional as lf
from paddle_tpu.models.family_protocol import StepRiders, _move_rows
from paddle_tpu.models.generation import _wmm, _write_rows
from paddle_tpu.models.hybrid_functional import (UNSUPPORTED,
                                                 _write_window_pages)

__all__ = ["GatedDeltaArgs", "LINEAR", "FULL", "UNSUPPORTED",
           "prefill_window", "decode_step"]

LINEAR, FULL = "linear_attention", "full_attention"
_NEG = -1e30


class GatedDeltaArgs(NamedTuple):
    """Static (hashable) description of the stack."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_heads: int            # full layers: query heads = KV heads
    head_dim: int
    linear_heads: int
    linear_key_dim: int
    linear_value_dim: int
    conv_kernel: int
    layer_kinds: Tuple[str, ...]
    rms_eps: float

    @property
    def num_layers(self):
        return len(self.layer_kinds)

    @property
    def num_kv_heads(self):
        return self.num_heads

    @property
    def conv_channels(self):
        return self.linear_heads * (2 * self.linear_key_dim
                                    + self.linear_value_dim)

    def layers_of(self, kind):
        return [i for i, k in enumerate(self.layer_kinds) if k == kind]

    def validate(self):
        bad = set(self.layer_kinds) - {LINEAR, FULL}
        if bad or not self.layer_kinds:
            raise ValueError(f"layer_kinds holds {sorted(bad)}; a layer is "
                             f"{LINEAR!r} or {FULL!r}")
        if self.conv_kernel < 2:
            raise ValueError("conv_kernel must be at least 2: the "
                             "convolution's state is its last K - 1 rows")


def _layer(params, kind, index):
    """Layer `index` of the stack of `kind` (a TRACED scalar: see
    `hybrid_functional._layer`)."""
    return {k: jax.lax.dynamic_index_in_dim(v, index, 0, keepdims=False)
            for k, v in params[kind].items()}


def _after(lp, x, mixed, args):
    """The residual around the mixer's output and the feed-forward, each
    normed AFTER the sublayer."""
    x = x + lf.rms_norm(mixed.astype(x.dtype), lp["ln1"], args.rms_eps)
    with jax.named_scope("pt.mlp"):
        act = jax.nn.silu(_wmm(x, lp["w_gate"])) * _wmm(x, lp["w_up"])
        out = _wmm(act, lp["w_down"])
    return x + lf.rms_norm(out, lp["ln2"], args.rms_eps)


def _head(params, x, args):
    x = lf.rms_norm(x, params["final_norm"], args.rms_eps)
    return _wmm(x, params["lm_head"]).astype(jnp.float32)


# ---------------------------------------------------------------------------
# a linear layer
# ---------------------------------------------------------------------------

def _conv_input(lp, x):
    """u = [x Wq, x Wk, x Wv], the convolution's input rows."""
    return jnp.concatenate([_wmm(x, lp["wq"]), _wmm(x, lp["wk"]),
                            _wmm(x, lp["wv"])], axis=-1)


def _delta_operands(lp, x, c, args):
    """From the convolution's output c [.., channels] float32: q, k [.., H,
    dk] (unit norm a head, q scaled by dk^-1/2), v [.., H, dv], log a and
    b [.., H] float32."""
    H, dk, dv = args.linear_heads, args.linear_key_dim, args.linear_value_dim
    lead = c.shape[:-1]
    q = c[..., :H * dk].reshape(*lead, H, dk)
    k = c[..., H * dk:2 * H * dk].reshape(*lead, H, dk)
    v = c[..., 2 * H * dk:].reshape(*lead, H, dv)
    unit = lambda y: y * jax.lax.rsqrt(
        jnp.sum(y * y, -1, keepdims=True) + 1e-12)
    b = 2.0 * jax.nn.sigmoid(_wmm(x, lp["wb"]).astype(jnp.float32))
    dt = jax.nn.softplus(_wmm(x, lp["wa"]).astype(jnp.float32)
                         + lp["dt_bias"].astype(jnp.float32))
    log_a = -jnp.exp(lp["A_log"].astype(jnp.float32)) * dt
    return unit(q) * dk ** -0.5, unit(k), v, log_a, b


def _linear_out(lp, x, o, args):
    """(RMSNorm a head of o [.., H, dv] * silu(x Wg)) Wo."""
    o = lf.rms_norm(o, lp["o_norm"].astype(jnp.float32), args.rms_eps)
    gate = jax.nn.silu(_wmm(x, lp["wg"]).astype(jnp.float32))
    out = (o.reshape(gate.shape) * gate).astype(x.dtype)
    return _wmm(out, lp["wo"])


def _linear_window(lp, x, st, last_idx, valid, args):
    with jax.named_scope("pt.attention"):
        c, conv = gdr.short_conv_window(_conv_input(lp, x), lp["conv_w"],
                                        st["conv"], last_idx)
        q, k, v, log_a, b = _delta_operands(lp, x, c, args)
        p = gdr.heads_per_row(args.linear_heads, args.linear_value_dim)
        o, S = gdr.delta_chunk_scan(q, k, v, log_a, b,
                                    gdr.unpack_state(st["S"], p), valid)
        mixed = _linear_out(lp, x, o, args)
    return _after(lp, x, mixed, args), {"S": gdr.pack_state(S, p),
                                        "conv": conv}


def _linear_decode(lp, x, st, live, args):
    with jax.named_scope("pt.attention"):
        c, conv = gdr.short_conv_step(_conv_input(lp, x), lp["conv_w"],
                                      st["conv"], live)
        q, k, v, log_a, b = _delta_operands(lp, x, c, args)
        o, S = gdr.delta_step(q, k, v, log_a, b, st["S"], live)
        mixed = _linear_out(lp, x, o, args)
    return _after(lp, x, mixed, args), {"S": S, "conv": conv}


# ---------------------------------------------------------------------------
# a full layer
# ---------------------------------------------------------------------------

def _qkv(lp, x, args):
    """q, k, v [.., H, d]; q and k normed over the whole projection."""
    H, d = args.num_heads, args.head_dim
    lead = x.shape[:-1]
    q = lf.rms_norm(_wmm(x, lp["wq"]), lp["q_norm"], args.rms_eps)
    k = lf.rms_norm(_wmm(x, lp["wk"]), lp["k_norm"], args.rms_eps)
    v = _wmm(x, lp["wv"])
    return (q.reshape(*lead, H, d), k.reshape(*lead, H, d),
            v.reshape(*lead, H, d))


@jax.named_scope("pt.paged_attention")
def window_attention(q, pool_k, pool_v, bt_row, qpos, last_pos):
    """A prefill window's causal attention over its slot's pages: q [s, H,
    d] at positions qpos [s]; pool_k / pool_v [num_pages, H, B, d] already
    hold the window's own keys; bt_row [P] the slot's pages; last_pos the
    window's last position (traced: it bounds the loop over key tiles).
    Online softmax in float32. Returns [s, H, d] in q's type."""
    s, H, d = q.shape
    B, P = pool_k.shape[2], bt_row.shape[0]
    tp = _tile_pages(P)
    tile = tp * B
    scale = 1.0 / math.sqrt(d)

    def body(i, carry):
        acc, m, l = carry
        pages = jax.lax.dynamic_slice_in_dim(bt_row, i * tp, tp)
        kt = jnp.swapaxes(pool_k[pages], 0, 1).reshape(H, tile, d)
        vt = jnp.swapaxes(pool_v[pages], 0, 1).reshape(H, tile, d)
        sc = jnp.einsum("qhd,hsd->hqs", q, kt,
                        preferred_element_type=jnp.float32) * scale
        kpos = i * tile + jnp.arange(tile, dtype=jnp.int32)
        ok = (kpos[None, :] <= qpos[:, None])[None]
        m_new = jnp.maximum(m, jnp.max(jnp.where(ok, sc, _NEG), -1,
                                       keepdims=True))
        p = jnp.where(ok, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, -1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "hqs,hsd->hqd", p.astype(vt.dtype), vt,
            preferred_element_type=jnp.float32)
        return acc, m_new, l

    acc, _, l = jax.lax.fori_loop(
        0, last_pos // tile + 1, body,
        (jnp.zeros((H, s, d), jnp.float32),
         jnp.full((H, s, 1), _NEG, jnp.float32),
         jnp.zeros((H, s, 1), jnp.float32)))
    return jnp.swapaxes(acc / jnp.maximum(l, 1e-30), 0, 1).astype(q.dtype)


def _full_window(lp, x, pk, pv, h, last_idx, pos, bt_row, new_pages, args):
    B = pk.shape[2]
    with jax.named_scope("pt.attention"):
        q, k, v = _qkv(lp, x, args)
        pk = _write_window_pages(pk, k, h, bt_row, new_pages, B)
        pv = _write_window_pages(pv, v, h, bt_row, new_pages, B)
        attn = window_attention(q, pk, pv, bt_row, pos, h + last_idx)
        mixed = _wmm(attn.reshape(x.shape[0], -1), lp["wo"])
    return _after(lp, x, mixed, args), pk, pv


def _full_decode(lp, x, pk, pv, bt, pos, args):
    B = pk.shape[2]
    with jax.named_scope("pt.attention"):
        q, k, v = _qkv(lp, x, args)
        # write before attending; a row that is not decoding has a table of
        # null pages, the garbage sink
        page = jnp.take_along_axis(bt, (pos // B)[:, None], axis=1)[:, 0]
        pk = _write_rows(pk, k, page, pos % B)
        pv = _write_rows(pv, v, page, pos % B)
        with jax.named_scope("pt.paged_attention"):
            attn = qm.paged_decode_attention(q[:, None], pk, pv, bt, pos)
        mixed = _wmm(attn.reshape(x.shape[0], -1), lp["wo"])
    return _after(lp, x, mixed, args), pk, pv


# ---------------------------------------------------------------------------
# what `serving/family.FamilyPath` asks of a family; `UNSUPPORTED`, the
# refusals of a recurrent state, is `hybrid_functional`'s
# ---------------------------------------------------------------------------

def pools(args, num_pages, page_size, dtype):
    """(pk, pv): a tuple of one page pool a full layer each; the page axis
    is axis 0 of every leaf."""
    shape = (num_pages, args.num_heads, page_size, args.head_dim)
    n = len(args.layers_of(FULL))
    return (tuple(jnp.zeros(shape, dtype) for _ in range(n)),
            tuple(jnp.zeros(shape, dtype) for _ in range(n)))


def copy_page(pools, src, dst, args):
    return _move_rows(pools, pools, dst, src)


def slot_state(args, slots, dtype):
    """One entry a linear layer; the slot axis is axis 0 of every leaf."""
    H, dk, dv = args.linear_heads, args.linear_key_dim, args.linear_value_dim
    p = gdr.heads_per_row(H, dv)
    return tuple(
        {"S": jnp.zeros((slots, H // p, dk, p * dv), jnp.float32),
         "conv": jnp.zeros((slots, args.conv_kernel - 1,
                            args.conv_channels), dtype)}
        for _ in args.layers_of(LINEAR))


def tables(args, max_len):
    """No rotary table: no layer rotates."""
    return ()


def check_engine(args, eng):
    """Nothing of the engine's sizes is this family's to constrain."""


def gauges(args, state, pools):
    """Which form the decode program's delta-rule step takes for this
    state: 1 the Pallas pass (a TPU and a shape that fits), 0 the jnp one."""
    return {"serve.delta_step_pallas": int(gdr.step_is_pallas(
        state[0]["S"].shape, args.linear_heads))}


def riders(args):
    """No counts, no selection kept."""
    return 0, 0


def observe_prefill(args, eng, rows):
    """No observation of its own."""
    return {}


def observe_decode(args, eng, active):
    """No observation of its own: the engine's `decode_live_page_share`
    already counts the pages the full layers' decode kernel fetches."""
    return {}


def prefill_window(params, layer_ids, ids, h, last_idx, bt_row, new_pages,
                   pools, state, tables, args, record=None):
    """One prefill window of one slot (`models/family_protocol.py`); `state`
    the SLOT's own entries. Nothing rides."""
    s = ids.shape[0]
    idx = jnp.arange(s, dtype=jnp.int32)
    pos, valid = h + idx, idx <= last_idx
    x = jnp.take(params["embedding"], ids, axis=0)
    pk, pv, state = list(pools[0]), list(pools[1]), list(state)
    n_full = n_lin = 0
    for kind in args.layer_kinds:
        if kind == LINEAR:
            j, n_lin = n_lin, n_lin + 1
            x, state[j] = _linear_window(
                _layer(params, kind, layer_ids[j]), x, state[j], last_idx,
                valid, args)
        else:
            j, n_full = n_full, n_full + 1
            x, pk[j], pv[j] = _full_window(
                _layer(params, kind, layer_ids[j]), x, pk[j], pv[j], h,
                last_idx, pos, bt_row, new_pages, args)
    logits = _head(params, x[last_idx][None], args)[0]
    return logits, (tuple(pk), tuple(pv)), tuple(state), StepRiders()


def decode_step(params, layer_ids, tokens, bt, pos, live, pools, state,
                tables, args, record=None):
    """One token a slot (`models/family_protocol.py`): a row that is not
    live keeps both of its states. Nothing rides."""
    x = jnp.take(params["embedding"], tokens, axis=0)
    pk, pv, state = list(pools[0]), list(pools[1]), list(state)
    n_full = n_lin = 0
    for kind in args.layer_kinds:
        if kind == LINEAR:
            j, n_lin = n_lin, n_lin + 1
            x, state[j] = _linear_decode(
                _layer(params, kind, layer_ids[j]), x, state[j], live, args)
        else:
            j, n_full = n_full, n_full + 1
            x, pk[j], pv[j] = _full_decode(
                _layer(params, kind, layer_ids[j]), x, pk[j], pv[j], bt,
                pos, args)
    return _head(params, x, args), (tuple(pk), tuple(pv)), tuple(state), \
        StepRiders()
