"""A decoder whose layers pair one of two MIXERS with one of two
FEED-FORWARDS, served over `PagedEngine`'s paged cache: gated delta-rule
layers (a float32 matrix state and a short convolution's rows a request,
`kernels/gated_delta_rule.py`) beside gated LATENT attention layers (one
cached row a token, `kernels/latent_attention.py`), each followed by a dense
SwiGLU or by shared + routed experts of which this program holds a share.
`LatentDeltaMoEArgs` is the static description that selects this path:
`PagedEngine(params, LatentDeltaMoEArgs(...))`.

Every layer is normed BEFORE AND AFTER each sublayer: `h = x + N(Mix(N(x)))`,
`y = h + N(FFN(N(h)))`, `N(x; w) = x / sqrt(mean(x^2) + eps) * 2 sigmoid(w)`
(a zero-centred gated norm: the scale is 1 at `w = 0`); the two norms inside
the latent mixer and the final norm are of the same kind. Input
`embedding[ids]`, output `lm_head(N(x))`.

  delta    [q; k; v] = silu(conv_K(x W_qkv)), a causal depthwise convolution
           of K taps over all the projected channels; q, k per KEY head of
           unit norm, q times dk^-1/2, key head j serving the value heads
           j * r .. j * r + r - 1 (r = value heads / key heads: q and k are
           repeated); b = sigmoid(x W_b), log a = -exp(A_log) softplus(x W_a
           + dt_bias), a value head each; the delta rule over S [value
           heads, dk, dv] gives o; out = (RMSNorm_head(o; 1 + w) * 2
           sigmoid(x W_z)) W_o.
  latent   `latent_moe_functional`'s heads (`_window_heads`, the
           decompressed form; `_decode_heads`, the absorbed form over the
           pages) with YaRN's scale, then an elementwise gate from the
           block's normed input: out = (heads * sigmoid(x W_g)) W_o.
  FFN      E(x) = (silu(min(x W_gate, limit)) * clip(x W_up, -limit, limit))
           W_down (`lm._swiglu`) in the dense layers (the `first_k_dense`
           leading ones), the shared expert and the routed experts alike;
           routing, the share of experts held and the dispatch are
           `latent_moe_functional`'s (`route`, `_routed_experts`).

The parameter tree holds ONE STACK A KIND OF LAYER beside `embedding`,
`final_norm`, `lm_head`; a kind is its mixer and its feed-forward
(`delta_dense`, `latent_experts`, `delta_experts`, `latent_dense`), each
stacked on a leading axis in layer order and indexed at run time (`_layer`;
the experts' `we_*` leaves stay whole: `lm._routed_experts` takes the stack
and where this layer's experts start). The layer loop is unrolled: every
delta layer's state is a leaf of its own, updated where it lies.

Per-request state beside the pages, a tuple of one entry a delta layer:
`{"S": [slots, Hv / p, dk, p * dv] float32, "conv": [slots, K - 1,
channels]}` (`gated_delta_rule.pack_state`'s layout). The pools are a tuple
of one `[num_pages, page, row_width]` latent pool a latent layer.

What `serving/family.FamilyPath` asks of a family's functional module
(`models/family_protocol.py`) is the last section.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import gated_delta_rule as gdr
from paddle_tpu.models import latent_moe_functional as lm
from paddle_tpu.models import llama_functional as lf
from paddle_tpu.models.family_protocol import _move_rows
from paddle_tpu.models.generation import _wmm
from paddle_tpu.models.hybrid_functional import UNSUPPORTED

__all__ = ["LatentDeltaMoEArgs", "DELTA", "LATENT", "UNSUPPORTED",
           "prefill_window", "decode_step"]

DELTA, LATENT = "delta", "latent"
DENSE, EXPERTS = "dense", "experts"
_NORMS = ("ln1", "ln1_post", "ln2", "ln2_post", "q_norm", "kv_norm")


class LatentDeltaMoEArgs(NamedTuple):
    """Static (hashable) description of the stack. The latent mixer's and the
    experts' fields carry `LatentMoEArgs`'s names: that module's functions
    read them from either description."""

    vocab_size: int
    hidden_size: int
    layer_mixers: Tuple[str, ...]   # a layer's mixer: DELTA or LATENT
    first_k_dense: int              # leading layers whose FFN is dense
    # the latent mixer
    num_heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_theta: float
    yarn: lm.YarnConfig | None
    # the delta mixer
    linear_key_heads: int
    linear_value_heads: int
    linear_key_dim: int
    linear_value_dim: int
    conv_kernel: int
    # the feed-forwards
    dense_intermediate: int
    expert_intermediate: int
    shared_experts: int
    routed_experts: int         # the router's width: every published expert
    first_expert: int           # the experts held here are
    experts_held: int           # [first_expert, first_expert + experts_held)
    n_group: int
    topk_group: int
    experts_per_tok: int
    routed_scaling: float
    scoring: str
    norm_topk: bool
    swiglu_limit: float | None
    rms_eps: float
    # both step programs also return the experts every token picked, for
    # whoever judges the served tokens (`serving/routing.RoutingTrace`)
    record_routing: bool = False

    # what `latent_moe_functional` asks of a description and this family
    # does not have
    indexer = None
    record_selection = False

    @property
    def num_layers(self):
        return len(self.layer_mixers)

    @property
    def row_width(self):
        """A cached row's width in whole lane tiles (`LatentMoEArgs`)."""
        return -(-(self.kv_rank + self.rope_dim) // 128) * 128

    @property
    def conv_channels(self):
        return (2 * self.linear_key_heads * self.linear_key_dim
                + self.linear_value_heads * self.linear_value_dim)

    @property
    def layer_kinds(self):
        """A layer's kind, the key of its stack: `<mixer>_<feed-forward>`."""
        return tuple(f"{m}_{DENSE if i < self.first_k_dense else EXPERTS}"
                     for i, m in enumerate(self.layer_mixers))

    def layers_of(self, mixer):
        return [i for i, m in enumerate(self.layer_mixers) if m == mixer]

    def validate(self):
        bad = set(self.layer_mixers) - {DELTA, LATENT}
        if bad or not self.layer_mixers:
            raise ValueError(f"layer_mixers holds {sorted(bad)}; a layer's "
                             f"mixer is {DELTA!r} or {LATENT!r}")
        if self.conv_kernel < 2:
            raise ValueError("conv_kernel must be at least 2: the "
                             "convolution's state is its last K - 1 rows")
        if self.linear_value_heads % self.linear_key_heads:
            raise ValueError("linear_value_heads must be a multiple of "
                             "linear_key_heads: a key head serves a whole "
                             "group of value heads")
        if not 0 <= self.first_k_dense < self.num_layers:
            raise ValueError("first_k_dense must leave an expert layer")
        if self.routed_experts % self.n_group:
            raise ValueError("routed_experts must be a multiple of n_group")
        if not (0 <= self.first_expert and 0 < self.experts_held
                and self.first_expert + self.experts_held
                <= self.routed_experts):
            raise ValueError("the experts held must lie inside "
                             "[0, routed_experts)")
        if self.rope_dim % 2:
            raise ValueError("rope_dim must be even")
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring {self.scoring!r}: softmax or sigmoid")


def _layer(params, kind, index):
    """Layer `index` of the stack of `kind` (a TRACED scalar: see
    `hybrid_functional._layer`), its norm weights as the scales they stand
    for (2 sigmoid(w)); the experts' leaves are not sliced
    (`lm._routed_experts` reads them from the whole stack)."""
    lp = {k: jax.lax.dynamic_index_in_dim(v, index, 0, keepdims=False)
          for k, v in params[kind].items() if not k.startswith("we_")}
    for name in _NORMS:
        if name in lp:
            lp[name] = _scale(lp[name])
    return lp


def _scale(w):
    """A zero-centred gated norm's scale: 2 sigmoid(w), 1 at w = 0."""
    return (2.0 * jax.nn.sigmoid(w.astype(jnp.float32))).astype(w.dtype)


def _head(params, x, args):
    x = lf.rms_norm(x, _scale(params["final_norm"]), args.rms_eps)
    return _wmm(x, params["lm_head"]).astype(jnp.float32)


# ---------------------------------------------------------------------------
# the delta mixer
# ---------------------------------------------------------------------------

def _delta_operands(lp, hin, c, args):
    """From the convolution's output c [.., channels] float32: q, k [.., Hv,
    dk] (unit norm a key head, q scaled by dk^-1/2, each key head repeated
    over the value heads it serves), v [.., Hv, dv], log a and b [.., Hv]
    float32."""
    Hk, Hv = args.linear_key_heads, args.linear_value_heads
    dk, dv = args.linear_key_dim, args.linear_value_dim
    lead = c.shape[:-1]
    q = c[..., :Hk * dk].reshape(*lead, Hk, dk)
    k = c[..., Hk * dk:2 * Hk * dk].reshape(*lead, Hk, dk)
    v = c[..., 2 * Hk * dk:].reshape(*lead, Hv, dv)
    unit = lambda y: y * jax.lax.rsqrt(
        jnp.sum(y * y, -1, keepdims=True) + 1e-12)
    over = lambda y: jnp.repeat(y, Hv // Hk, axis=-2)
    b = jax.nn.sigmoid(_wmm(hin, lp["wb"]).astype(jnp.float32))
    dt = jax.nn.softplus(_wmm(hin, lp["wa"]).astype(jnp.float32)
                         + lp["dt_bias"].astype(jnp.float32))
    log_a = -jnp.exp(lp["A_log"].astype(jnp.float32)) * dt
    return over(unit(q) * dk ** -0.5), over(unit(k)), v, log_a, b


def _delta_out(lp, hin, o, args):
    """(RMSNorm a head of o [.., Hv, dv], weight 1 + w, * 2 sigmoid(x Wz))
    Wo."""
    o = lf.rms_norm(o, 1.0 + lp["o_norm"].astype(jnp.float32), args.rms_eps)
    gate = 2.0 * jax.nn.sigmoid(_wmm(hin, lp["wz"]).astype(jnp.float32))
    return _wmm((o.reshape(gate.shape) * gate).astype(hin.dtype), lp["wo"])


def _delta_window(lp, hin, st, last_idx, valid, args):
    with jax.named_scope("pt.attention"):
        c, conv = gdr.short_conv_window(_wmm(hin, lp["w_qkv"]), lp["conv_w"],
                                        st["conv"], last_idx)
        q, k, v, log_a, b = _delta_operands(lp, hin, c, args)
        p = gdr.heads_per_row(args.linear_value_heads, args.linear_value_dim)
        o, S = gdr.delta_chunk_scan(q, k, v, log_a, b,
                                    gdr.unpack_state(st["S"], p), valid)
        return _delta_out(lp, hin, o, args), {"S": gdr.pack_state(S, p),
                                              "conv": conv}


def _delta_decode(lp, hin, st, live, args):
    with jax.named_scope("pt.attention"):
        c, conv = gdr.short_conv_step(_wmm(hin, lp["w_qkv"]), lp["conv_w"],
                                      st["conv"], live)
        q, k, v, log_a, b = _delta_operands(lp, hin, c, args)
        o, S = gdr.delta_step(q, k, v, log_a, b, st["S"], live)
        return _delta_out(lp, hin, o, args), {"S": S, "conv": conv}


# ---------------------------------------------------------------------------
# the latent mixer's gate, and the block around either mixer
# ---------------------------------------------------------------------------

def _gated_out(lp, hin, heads):
    """(heads [n, H * v] * sigmoid(x Wg)) Wo."""
    with jax.named_scope("pt.attention"):
        gate = jax.nn.sigmoid(_wmm(hin, lp["wg"]).astype(jnp.float32))
        return _wmm((heads.astype(jnp.float32) * gate).astype(hin.dtype),
                    lp["wo"])


def _feed_forward(params, lp, kind, first, x, live, args):
    """y = x + N(FFN(N(x))) -> (y, counts [4] or None, picks [n, k] or
    None): a dense layer's SwiGLU, or the shared expert plus the held
    experts' part of the routed sum."""
    hin = lf.rms_norm(x, lp["ln2"], args.rms_eps)
    if kind.endswith(DENSE):
        with jax.named_scope("pt.mlp"):
            out = lm._swiglu(hin, lp["w_gate"], lp["w_up"], lp["w_down"],
                             args.swiglu_limit)
        counts = picks = None
    else:
        with jax.named_scope("pt.mlp"):
            out = lm._swiglu(hin, lp["ws_gate"], lp["ws_up"], lp["ws_down"],
                             args.swiglu_limit)
        stack = {k: v.reshape((-1,) + v.shape[2:])
                 for k, v in params[kind].items() if k.startswith("we_")}
        routed, counts, picks = lm._routed_experts(lp, stack, first, hin,
                                                   live, args)
        out = out + routed
    return x + lf.rms_norm(out, lp["ln2_post"], args.rms_eps), counts, picks


def _layers(params, layer_ids, x, mix, live, args):
    """The unrolled layer loop of both step programs. `mix(mixer, j, lp,
    hin)` is the step's own: the j-th layer of that mixer on the block's
    normed input -> the mixer's output. Returns (x, counts [4] summed over
    the expert layers, picks [expert layers, rows, experts a token])."""
    seen, n_mix, counts, picks = {}, {DELTA: 0, LATENT: 0}, [], []
    for mixer, kind in zip(args.layer_mixers, args.layer_kinds):
        i = seen[kind] = seen.get(kind, -1) + 1      # its place in its stack
        j, n_mix[mixer] = n_mix[mixer], n_mix[mixer] + 1
        lp = _layer(params, kind, layer_ids[i])
        hin = lf.rms_norm(x, lp["ln1"], args.rms_eps)
        mixed = mix(mixer, j, lp, hin)
        x = x + lf.rms_norm(mixed.astype(x.dtype), lp["ln1_post"],
                            args.rms_eps)
        x, c, p = _feed_forward(params, lp, kind,
                                layer_ids[i] * args.experts_held, x, live,
                                args)
        if c is not None:
            counts.append(c)
            picks.append(p)
    return x, sum(counts), jnp.stack(picks)


# ---------------------------------------------------------------------------
# what `serving/family.FamilyPath` asks of a family; `UNSUPPORTED`, the
# refusals of a recurrent state, is `hybrid_functional`'s
# ---------------------------------------------------------------------------

def pools(args, num_pages, page_size, dtype):
    """One latent pool a latent layer; the page axis is axis 0 of every
    leaf."""
    return tuple(jnp.zeros((num_pages, page_size, args.row_width), dtype)
                 for _ in args.layers_of(LATENT))


def copy_page(pools, src, dst, args):
    return _move_rows(pools, pools, dst, src)


def slot_state(args, slots, dtype):
    """One entry a delta layer; the slot axis is axis 0 of every leaf."""
    H, dk, dv = (args.linear_value_heads, args.linear_key_dim,
                 args.linear_value_dim)
    p = gdr.heads_per_row(H, dv)
    return tuple(
        {"S": jnp.zeros((slots, H // p, dk, p * dv), jnp.float32),
         "conv": jnp.zeros((slots, args.conv_kernel - 1,
                            args.conv_channels), dtype)}
        for _ in args.layers_of(DELTA))


def tables(args, max_len):
    """(cos, sin) of the latent layers' rotary slice. 2 * max_len: a
    window's padding may pass max_len before it is cut."""
    return lm.rope_tables(2 * max_len, args)


def check_engine(args, eng):
    """Nothing of the engine's sizes is this family's to constrain."""


def _nbytes(tree):
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


def gauges(args, state, pools):
    """How the step programs are built for this state: what ONE slot keeps
    beside its pages, the latent pools' bytes, and which form the decode
    program's delta-rule step takes (1 the Pallas pass, 0 the jnp one)."""
    slots = state[0]["S"].shape[0]
    return {"serve.slot_state_bytes": _nbytes(state) // slots,
            "serve.latent_pool_bytes": _nbytes(pools),
            "serve.delta_step_pallas": int(gdr.step_is_pallas(
                state[0]["S"].shape, args.linear_value_heads))}


def riders(args):
    """A decode step's four counts (`lm._routed_experts`); no selector."""
    return 4, 0


def observe_prefill(args, eng, rows):
    """Which form the window's experts took (`lm.observe_prefill`)."""
    return lm.observe_prefill(args, eng, rows)


def observe_decode(args, eng, active):
    """Which form the step's experts took, and what a decode step must move
    of the two kinds of per-request memory, from the host's own numbers:
    every live row's state read and written once, and every live row's
    cached tokens' rows in every latent layer (the token the step writes
    among them)."""
    path = eng.path
    state = _nbytes(path.state) // eng.max_slots
    row = args.row_width * path.pools[0].dtype.itemsize
    cached = int(eng._npos[active].sum()) + len(active)
    return {**lm.observe_decode(args, eng, active),
            "serve.state_bytes_step": 2 * len(active) * state,
            "serve.cache_bytes_step":
                cached * row * len(args.layers_of(LATENT))}


def prefill_window(params, layer_ids, ids, h, last_idx, bt_row, new_pages,
                   pools, state, tables, args, record=None):
    """One prefill window of one slot (`models/family_protocol.py`); `state`
    the SLOT's own entries. Rides: picks [expert layers, s, experts a
    token] where the description records the routing."""
    s = ids.shape[0]
    valid = jnp.arange(s, dtype=jnp.int32) <= last_idx
    cos, sin = tables
    pools, state = list(pools), list(state)

    def mix(mixer, j, lp, hin):
        if mixer == DELTA:
            out, state[j] = _delta_window(lp, hin, state[j], last_idx, valid,
                                          args)
            return out
        heads, pools[j], _ = lm._window_heads(
            lp, hin, pools[j], h, last_idx, bt_row, new_pages, cos, sin, 0,
            None, args)
        return _gated_out(lp, hin, heads)

    x = jnp.take(params["embedding"], ids, axis=0)
    x, _, picks = _layers(params, layer_ids, x, mix, valid, args)
    logits = _head(params, x[last_idx][None], args)[0]
    return logits, tuple(pools), tuple(state), lm._riders(
        args, None, picks, None)


def decode_step(params, layer_ids, tokens, bt, pos, live, pools, state,
                tables, args, record=None):
    """One token a slot (`models/family_protocol.py`). Rides: counts int32
    [4] summed over the expert layers (`lm._routed_experts`); picks [expert
    layers, b, experts a token] where the description records the
    routing."""
    cos, sin = tables
    pools, state = list(pools), list(state)

    def mix(mixer, j, lp, hin):
        if mixer == DELTA:
            out, state[j] = _delta_decode(lp, hin, state[j], live, args)
            return out
        heads, pools[j], _ = lm._decode_heads(
            lp, hin, pools[j], bt, pos, cos, sin, 0, None, args)
        return _gated_out(lp, hin, heads)

    x = jnp.take(params["embedding"], tokens, axis=0)
    x, counts, picks = _layers(params, layer_ids, x, mix, live, args)
    return _head(params, x, args), tuple(pools), tuple(state), lm._riders(
        args, counts, picks, None)
