"""GigaChat 3.5's stack (`gigachat3_5`): gated DELTA-RULE layers (Gated
DeltaNet behind a short causal convolution, grouped value heads) three in
four, gated LATENT attention (MLA with an output gate) one in four, every
sublayer normed before and after, and behind every mixer past the leading
dense layers one shared expert plus routed experts under sigmoid,
bias-corrected, renormalised routing, of which this chip HOLDS sixteen. What
the harness knows of it (`harness/spec.py`):

  serve_args      the program's static description (`LatentDeltaMoEArgs`)
  layer_kinds     a layer's mixer and feed-forward, `delta_dense`,
                  `latent_experts`, `delta_experts` (`latent_dense`): the
                  kinds differ in their leaves, one stack a kind
  layer_shapes / leaf_init   by kind
  decoder_layer   the plain layer, told its kind
  served_logits   the plain float32 forward of each served request, in
                  blocks of fixed shapes, following the routing the program
                  recorded where it stands this reference's check
  the counts of its readers (`traced_work`, `param_count`, `state_bytes`,
  `row_bytes`)

The latent attention's projections, rotary rotation (YaRN) and scale are
`mla_moe.py`'s, the convolution and the token-by-token recurrence
`gated_delta_hybrid.py`'s, the routing rule with its check of recorded picks
`mla_dsa_moe.py`'s (the files beside this one, loaded by their paths and not
edited).

THE EQUATIONS the reference is written from (config keys in backticks; A1-A8
are the readings no key settles, listed with their reasons in the
configuration file's `assumed`). N(x; w) = x / sqrt(mean(x^2) + eps) * 2
sigmoid(w) (A1: `norm_type` ZeroCenteredGatedNorm, `layernorm_gating_weight`
2; the scale is 1 at w = 0). Every layer (`layernorm_type` pre_post): h = x +
N(Mix(N(x))); y = h + N(FFN(N(h))): four norms a layer. Input embedding[ids];
output lm_head(N(x)), untied.

  delta    (the layers not in `full_attention_layers`) x_n = N(x). [q~; k~;
           v] = silu(conv_K(x_n W_qkv)): depthwise, causal, K taps
           (`linear_conv_kernel_dim`), zeros before position 0; q~, k~ in
           `linear_num_key_heads` heads of `linear_key_head_dim`, v in
           `linear_num_value_heads` heads of `linear_value_head_dim`. Per
           key head q = q~ / |q~| * dk^-1/2, k = k~ / |k~| (A2); key head j
           serves the value heads j r .. j r + r - 1 (r = value heads / key
           heads). Per value head b = sigmoid(x_n W_b), a = exp(-exp(A_log)
           softplus(x_n W_a + dt_bias)), S_0 = 0 in R^{dk x dv}:
               S_t = a_t (I - b_t k_t k_t^T) S_{t-1} + b_t k_t v_t^T
               o_t = S_t^T q_t
           out = (RMSNorm_head(o; 1 + w_o) * 2 sigmoid(x_n W_z)) W_o (A3:
           `linear_gating_type` gated_rmsnorm_sigmoid_zero_centered,
           `linear_sigmoid_gate_scale` 2, eps `linear_attn_o_norm_eps`).
  latent   (the layers in `full_attention_layers`) `mla_moe.py`'s: c_q =
           N(x_n W_qa), [q_nope_i; q_pe_i] = c_q W_qb a head, [c_kv; k_pe] =
           x_n W_kva, c_kv = N(c_kv), RoPE with YaRN on q_pe_i and k_pe,
           [k_nope_i; v_i] = c_kv W_kvb, causal softmax at scale (nope +
           rope)^-1/2 mscale(factor, mscale_all_dim)^2 (A4), then the gate
           (A5: `gated_attention`): out = (concat_i(o_i) * sigmoid(x_n W_g))
           W_o, elementwise over the H * v values.
  FFN      E(x) = (silu(min(x W_gate, L)) * clip(x W_up, -L, L)) W_down, L =
           `swiglu_limit` (A6), in the dense layers (the leading
           `first_k_dense_replace`, width `intermediate_size`), the shared
           expert and the routed experts alike. Expert layers: s = sigmoid(h
           W_r) over ALL published experts, float32; the pick is the
           `num_experts_per_tok` largest of s + b (A7; no group step), ties
           to the lower index; a pick weighs `routed_scaling_factor` s_e /
           (sum of the picked s + 1e-20). FFN = E_shared + sum over the
           picked of w_e E_e.
  the share  experts [first, first + n_routed_experts) of the published count
           are held (`deployment.first_expert_held`); a pick elsewhere adds
           nothing; mixers, shared expert and router are whole.

ROUTING IS DISCRETE, as `mla_moe.py`'s head says: the program records every
token's picks in every expert layer (`Request.routing`, `REQUEST_RECORD`)
and this reference FOLLOWS them where each pick's s + b is at least (1 -
ROUTING_TOL) of its own k-th best (`mla_dsa_moe.routing_noting`, told this
family's tolerance); a request more than `FOLLOW_MAX` of whose token-layers
had to be followed is judged on the reference's own routing. Both are set
from chip runs at the cell's size (PERF.md section 6, PR 43: 7 sound runs on 6
seeds at the seeded weights as they stand, 1.8 million token-layers, and the
reference in fp8 on one): a pick's shortfall reads at most 0.0187 of a score
sound and 0.498 in fp8; 20.2-20.6% of a sound run's token-layers are
followed (a request at most 21.8%: level picks leave more near-ties than
lopsided ones did), 68% in fp8.

Nothing here is the program's but `serve_args`: `jax.numpy`, float32, matmul
precision `highest`, no kernel, no cache, no batching; the recurrence is a
scan over tokens; an expert held is computed for the tokens that picked it.
Every weight matrix goes through `mm` (the control swaps it for fp8).
"""

import functools
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import weights
from benchmarks.harness.reference import (f32_mm, pad_rows, rms_norm,
                                          served_rows)


def _beside(name):
    """The family file `name` beside this one, loaded by its path."""
    spec = importlib.util.spec_from_file_location(
        f"bench_family_mla_delta_moe_{name}",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_mla, _gd, _dsa = (_beside(n) for n in ("mla_moe", "gated_delta_hybrid",
                                        "mla_dsa_moe"))

DELTA, LATENT = "delta", "latent"
DENSE, EXPERTS = "dense", "experts"
T_BLOCK = 1024      # tokens a call takes: a sequence comes padded to whole
                    # blocks, so every call has one shape
Q_BLOCK = 256       # queries attended at once: [heads, Q_BLOCK, keys] scores
K_BUCKET = 4096     # a query block sees its keys padded up to a multiple: at
                    # most three key counts at the cell's 10,240 positions
REQUEST_RECORD = "routing"      # `serving.latent.RoutingTrace`: the picks
ROUTING_TOL = 0.04  # see ROUTING IS DISCRETE above: twice the largest sound
FOLLOW_MAX = 0.4    # shortfall, a twelfth of the control's; between 22% and 68%
_dsa.ROUTING_TOL = ROUTING_TOL      # this file's own copy of that module
_NORMS = ("ln1", "ln1_post", "ln2", "ln2_post", "q_norm", "kv_norm")
# The convolution's seeded taps. At `gated_delta_hybrid.py`'s 0.5 the four
# taps pass the projection's scale on (x_n W_qkv reads ~1.7 at the published
# hidden size under the harness's normal(0, 0.02)) and SiLU RECTIFIES there:
# every q, k and v channel of every token gets the same positive mean, q . k
# is positive on average, a delta mixer's output has a direction common to
# all tokens, and the latent layer, whose seeded softmax spreads over
# hundreds of keys, averages everything else away and keeps that direction.
# The top 8 of 256 router scores then land on the same few experts for every
# row (`expert_load_max_over_mean` 6.5 on the chip), and how many of those
# are among the 16 held is the seed's draw: a seed decided how much a step
# reads, the cell's rate stood in groups by seed and the driver refused its
# spread (PERF.md section 6, PR 43). The common part of a mixer's output
# falls with the CUBE of the convolution's output scale s (SiLU's mean over
# its spread is s / 2; once from v, twice from q . k): at 0.125 (s ~ 0.4) the
# chip still read 5.2, at 0.03 (s ~ 0.1) it reads 2.65, which is what level
# picks give (34 picks on 16 experts), and six seeds' rates lie within 1.6%.
# SiLU is nearly linear there: by its series a dropped one turns q and k by
# ~5 degrees, which this cell's check has not been shown to catch; tier-1
# compares the program's convolution and SiLU with this file's at 1e-4.
CONV_STD = 0.03


# -- the program's side: imported here and nowhere in the reference ----------

def serve_args(arch):
    from paddle_tpu.models import latent_delta_functional as ldf
    from paddle_tpu.models.latent_moe_functional import YarnConfig

    y = arch["rope_scaling"]
    first, held = experts_held(arch)
    return ldf.LatentDeltaMoEArgs(
        vocab_size=arch["vocab_size"], hidden_size=arch["hidden_size"],
        layer_mixers=mixers(arch),
        first_k_dense=arch["first_k_dense_replace"],
        num_heads=arch["num_attention_heads"], q_rank=arch["q_lora_rank"],
        kv_rank=arch["kv_lora_rank"], nope_dim=arch["qk_nope_head_dim"],
        rope_dim=arch["qk_rope_head_dim"], v_dim=arch["v_head_dim"],
        rope_theta=float(arch["rope_theta"]),
        yarn=YarnConfig(
            factor=float(y["factor"]),
            original_max_position=y["original_max_position_embeddings"],
            beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
            mscale=float(y["mscale"]),
            mscale_all_dim=float(y["mscale_all_dim"])),
        linear_key_heads=arch["linear_num_key_heads"],
        linear_value_heads=arch["linear_num_value_heads"],
        linear_key_dim=arch["linear_key_head_dim"],
        linear_value_dim=arch["linear_value_head_dim"],
        conv_kernel=arch["linear_conv_kernel_dim"],
        dense_intermediate=arch["intermediate_size"],
        expert_intermediate=arch["moe_intermediate_size"],
        shared_experts=arch["n_shared_experts"],
        routed_experts=_mla.router_width(arch), first_expert=first,
        experts_held=held, n_group=arch["n_group"],
        topk_group=arch["topk_group"],
        experts_per_tok=arch["num_experts_per_tok"],
        routed_scaling=float(arch["routed_scaling_factor"]),
        scoring="sigmoid", norm_topk=bool(arch["norm_topk_prob"]),
        swiglu_limit=float(arch["swiglu_limit"]),
        rms_eps=arch["rms_norm_eps"],
        record_routing=True)        # `served_logits` reads `Request.routing`


# -- the layers' kinds and the share --------------------------------------------

def mixers(arch):
    full = set(arch["full_attention_layers"])
    return tuple(LATENT if i in full else DELTA
                 for i in range(arch["num_hidden_layers"]))


def layer_kinds(arch):
    kd = arch["first_k_dense_replace"]
    return tuple(f"{m}_{DENSE if i < kd else EXPERTS}"
                 for i, m in enumerate(mixers(arch)))


def experts_held(arch):
    """(first, count): the experts this chip holds."""
    return _dsa.experts_held(arch)


# -- the weights ---------------------------------------------------------------

def _delta_widths(arch):
    """(key heads, value heads, dk, dv, K, conv channels)."""
    Hk, Hv = arch["linear_num_key_heads"], arch["linear_num_value_heads"]
    dk, dv = arch["linear_key_head_dim"], arch["linear_value_head_dim"]
    return Hk, Hv, dk, dv, arch["linear_conv_kernel_dim"], \
        2 * Hk * dk + Hv * dv


def _mixer_shapes(arch, mixer):
    h = arch["hidden_size"]
    if mixer == DELTA:
        Hk, Hv, dk, dv, K, C = _delta_widths(arch)
        return {"w_qkv": (h, C), "conv_w": (C, K), "wa": (h, Hv),
                "wb": (h, Hv), "A_log": (Hv,), "dt_bias": (Hv,),
                "wz": (h, Hv * dv), "o_norm": (dv,), "wo": (Hv * dv, h)}
    shapes = {k: v for k, v in _mla._attention_shapes(arch).items()
              if k not in ("ln1", "ln2")}
    H, v = arch["num_attention_heads"], arch["v_head_dim"]
    return dict(shapes, wg=(h, H * v))


def _ffn_shapes(arch, ffn):
    h, i, m = (arch["hidden_size"], arch["intermediate_size"],
               arch["moe_intermediate_size"])
    if ffn == DENSE:
        return {"w_gate": (h, i), "w_up": (h, i), "w_down": (i, h)}
    sh, E = arch["n_shared_experts"] * m, arch["n_routed_experts"]
    n = _mla.router_width(arch)
    return {"router": (h, n), "router_bias": (n,), "ws_gate": (h, sh),
            "ws_up": (h, sh), "ws_down": (sh, h), "we_gate": (E, h, m),
            "we_up": (E, h, m), "we_down": (E, m, h)}


def layer_shapes(arch):
    h = arch["hidden_size"]
    norms = {n: (h,) for n in ("ln1", "ln1_post", "ln2", "ln2_post")}
    return {f"{mixer}_{ffn}": dict(norms, **_mixer_shapes(arch, mixer),
                                   **_ffn_shapes(arch, ffn))
            for mixer in (DELTA, LATENT) for ffn in (DENSE, EXPERTS)}


def leaf_init(arch):
    """Every norm weight w ~ normal(0, 0.05) around the zero its scale is 1
    at (the block's four and the latent mixer's two through 2 sigmoid(w), the
    delta output's through 1 + w); the decay's leaves as
    `gated_delta_hybrid.py` seeds them (the mechanism's paper's draws); the
    convolution's taps normal(0, CONV_STD), see there; the router's
    correction bias normal(0, `router_bias_std` or 0.01), so that it changes
    picks; the matrices `initializer_range` where the configuration states
    one (the tests' toy presets), else the harness's rules."""
    std = arch.get("initializer_range")
    out = {}
    for kind, shapes in layer_shapes(arch).items():
        init = {n: (0.0, std) for n, s in shapes.items()
                if std is not None and len(s) >= 2}
        init.update({n: (0.0, 0.05) for n in _NORMS + ("o_norm",)
                     if n in shapes})
        if kind.startswith(DELTA):
            init.update(A_log=(1.96, 0.67), dt_bias=(-4.6, 1.33),
                        conv_w=(0.0, CONV_STD))
        if kind.endswith(EXPERTS):
            init["router_bias"] = (0.0, arch.get("router_bias_std", 0.01))
        out[kind] = init
    return out


def _count(shapes):
    return sum(math.prod(s) for s in shapes.values())


def param_count(arch):
    """Parameters of the configuration as it is run, by part: every layer by
    its kind, the embedding with the head, and their sum; `mixer` and `ffn`
    hold one of each kind's count, `expert` one routed expert's."""
    h = arch["hidden_size"]
    layers = [_count(layer_shapes(arch)[k]) for k in layer_kinds(arch)]
    outer = 2 * arch["vocab_size"] * h + h
    return {"layers": layers, "outer": outer, "total": sum(layers) + outer,
            "mixer": {m: _count(_mixer_shapes(arch, m))
                      for m in (DELTA, LATENT)},
            "dense_ffn": _count(_ffn_shapes(arch, DENSE)),
            "router": h * _mla.router_width(arch),
            "expert": 3 * h * arch["moe_intermediate_size"]}


def state_bytes(arch, itemsize=2):
    """What ONE request keeps beside its pages, whatever its length: the
    float32 matrix state and the convolution's last K - 1 input rows, every
    delta layer."""
    Hk, Hv, dk, dv, K, C = _delta_widths(arch)
    return mixers(arch).count(DELTA) * (Hv * dk * dv * 4
                                        + (K - 1) * C * itemsize)


def row_bytes(arch, itemsize=2):
    """What one token caches in a latent layer as it is laid out: [c_kv;
    k_pe] padded to whole 128-lane tiles."""
    lanes = -(-(arch["kv_lora_rank"] + arch["qk_rope_head_dim"]) // 128) * 128
    return lanes * itemsize


# -- the plain layer -------------------------------------------------------------

def scale(w):
    """A zero-centred gated norm's scale (A1)."""
    return 2.0 * jax.nn.sigmoid(w)


def _scaled(w):
    """The layer's leaves with every norm weight of the A1 kind replaced by
    the scale it stands for: `rms_norm(x, scale, eps)` is then N."""
    return dict(w, **{n: scale(w[n]) for n in _NORMS if n in w})


def glu(x, w_gate, w_up, w_down, arch, mm):
    L = arch["swiglu_limit"]
    return mm(jax.nn.silu(jnp.minimum(mm(x, w_gate), L))
              * jnp.clip(mm(x, w_up), -L, L), w_down)


def delta_operands(xn, c, w, arch, mm):
    """q, k [s, Hv, dk], v [s, Hv, dv], a, b [s, Hv] of the equations (q and
    k repeated over the value heads their key head serves)."""
    Hk, Hv, dk, dv, _, _ = _delta_widths(arch)
    s = xn.shape[0]
    q = c[:, :Hk * dk].reshape(s, Hk, dk)
    k = c[:, Hk * dk:2 * Hk * dk].reshape(s, Hk, dk)
    v = c[:, 2 * Hk * dk:].reshape(s, Hv, dv)
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / math.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    b = jax.nn.sigmoid(mm(xn, w["wb"]))
    a = jnp.exp(-jnp.exp(w["A_log"])
                * jax.nn.softplus(mm(xn, w["wa"]) + w["dt_bias"]))
    r = Hv // Hk
    return jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1), v, a, b


def delta_mix(xn, w, arch, mm, before, S):
    """The delta mixer over a block of normed inputs xn [s, h], carrying the
    convolution's last K - 1 input rows and the matrix state. Returns (out
    [s, h], the rows to carry, S)."""
    u = mm(xn, w["w_qkv"])
    c = _gd.short_conv(u, before, w["conv_w"])
    q, k, v, a, b = delta_operands(xn, c, w, arch, mm)
    o, S = _gd.delta_scan(q, k, v, a, b, S)
    o = rms_norm(o, 1.0 + w["o_norm"], arch["linear_attn_o_norm_eps"])
    gate = arch["linear_sigmoid_gate_scale"] * jax.nn.sigmoid(
        mm(xn, w["wz"]))
    return (mm(o.reshape(xn.shape[0], -1) * gate, w["wo"]),
            u[-before.shape[0]:], S)


def latent_out(xn, heads, w, mm):
    """(heads [s, H, v] * sigmoid(x_n W_g)) W_o."""
    return mm(heads.reshape(xn.shape[0], -1)
              * jax.nn.sigmoid(mm(xn, w["wg"])), w["wo"])


def ffn_noting(x, w, arch, mm, dense, given=None):
    """y = x + N(FFN(N(x))) on top of x [s, h] (the mixer and its residual
    already in), and the routing's notes [s, 3] (`mla_dsa_moe.
    routing_noting`; zeros for a dense layer). `w` holds scales."""
    eps = arch["rms_norm_eps"]
    h = rms_norm(x, w["ln2"], eps)
    if dense:
        out = glu(h, w["w_gate"], w["w_up"], w["w_down"], arch, mm)
        return (x + rms_norm(out, w["ln2_post"], eps),
                jnp.zeros((x.shape[0], 3), jnp.float32))
    first, held = experts_held(arch)
    weigh, notes = _dsa.routing_noting(h, w, arch, mm, given)
    weigh = weigh[:, first:first + held]
    # an expert is computed for the tokens that picked it, up to a sixteenth
    # of the rows; an expert more tokens than that picked is computed for
    # all (`mla_dsa_moe.finish_noting`'s rule)
    cap = max(1, h.shape[0] // 16)

    def one(acc, xs):
        w_gate, w_up, w_down, we = xs

        def every(acc):
            return acc + we[:, None] * glu(h, w_gate, w_up, w_down, arch, mm)

        def its_own(acc):
            rows = jnp.nonzero(we != 0, size=cap, fill_value=0)[0]
            real = jnp.arange(cap) < jnp.sum(we != 0)
            out = we[rows][:, None] * glu(h[rows], w_gate, w_up, w_down,
                                          arch, mm)
            return acc.at[rows].add(jnp.where(real[:, None], out, 0.0))

        return jax.lax.cond(jnp.sum(we != 0) > cap, every, its_own, acc), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        w["we_gate"], w["we_up"], w["we_down"], weigh.T))
    out = glu(h, w["ws_gate"], w["ws_up"], w["ws_down"], arch, mm) + routed
    return x + rms_norm(out, w["ln2_post"], eps), notes


def decoder_layer(x, w, arch, mm, kind):
    """One whole layer over sequences x [b, s, h] from position 0."""
    Hk, Hv, dk, dv, K, C = _delta_widths(arch)
    eps, w = arch["rms_norm_eps"], _scaled(w)

    def one(x1):
        s = x1.shape[0]
        xn = rms_norm(x1, w["ln1"], eps)
        if kind.startswith(DELTA):
            mixed, _, _ = delta_mix(xn, w, arch, mm, jnp.zeros((K - 1, C)),
                                    jnp.zeros((Hv, dk, dv)))
        else:
            pos = jnp.arange(s)
            q_nope, q_pe, k_nope, k_pe, v = _mla.project(
                x1, w, arch, mm, pos)
            mixed = latent_out(xn, _mla.attend(q_nope, q_pe, pos, k_nope,
                                               k_pe, v, arch), w, mm)
        x1 = x1 + rms_norm(mixed, w["ln1_post"], eps)
        return ffn_noting(x1, w, arch, mm, kind.endswith(DENSE))[0]

    return jax.lax.map(one, x)


# -- a served model: logits at the served positions ----------------------------

_KEYS = ("hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "rms_norm_eps", "rope_theta", "rope_scaling", "n_routed_experts",
         "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
         "swiglu_limit", "linear_num_key_heads", "linear_num_value_heads",
         "linear_key_head_dim", "linear_value_head_dim",
         "linear_conv_kernel_dim", "linear_attn_o_norm_eps",
         "linear_sigmoid_gate_scale")


def _frozen(arch):
    first, _ = experts_held(arch)
    return json.dumps(dict(
        {k: arch[k] for k in _KEYS},
        published={"n_routed_experts": _mla.router_width(arch)},
        deployment={"first_expert_held": first}), sort_keys=True)


def _f32(w):
    return jax.tree.map(lambda a: a.astype(jnp.float32), w)


@functools.lru_cache(maxsize=None)
def _delta_block_fn(frozen, mm):
    """One block of tokens through a delta layer's mixer and its residual."""
    arch = json.loads(frozen)

    def block(x, w, before, S):
        w = _scaled(_f32(w))
        xn = rms_norm(x, w["ln1"], arch["rms_norm_eps"])
        mixed, before, S = delta_mix(xn, w, arch, mm, before, S)
        return (x + rms_norm(mixed, w["ln1_post"], arch["rms_norm_eps"]),
                before, S)

    return jax.jit(block)


@functools.lru_cache(maxsize=None)
def _project_fn(frozen, mm):
    arch = json.loads(frozen)
    return jax.jit(lambda x, w, pos: _mla.project(x, _scaled(_f32(w)), arch,
                                                  mm, pos))


@functools.lru_cache(maxsize=None)
def _attend_fn(frozen, mm):
    """A block of queries over the keys before them, the gate, the output
    projection and the mixer's residual."""
    arch = json.loads(frozen)

    def block(x, w, qn, qr, qpos, kn, kr, v):
        w = _scaled(_f32(w))
        xn = rms_norm(x, w["ln1"], arch["rms_norm_eps"])
        mixed = latent_out(xn, _mla.attend(qn, qr, qpos, kn, kr, v, arch), w,
                           mm)
        return x + rms_norm(mixed, w["ln1_post"], arch["rms_norm_eps"])

    return jax.jit(block)


@functools.lru_cache(maxsize=None)
def _ffn_fn(frozen, mm, dense):
    arch = json.loads(frozen)
    return jax.jit(lambda x, w, given: ffn_noting(
        x, _scaled(_f32(w)), arch, mm, dense, given))


def _blocks(n, size):
    return [(a, min(a + size, n)) for a in range(0, n, size)]


def layer_forward(x, w, arch, mm, kind, given=None, notes=None):
    """One layer over one sequence x [s, h] (s whole token blocks), block by
    block: every jitted call has one of a few fixed shapes whatever the
    sequence's length. Rows past the sequence's tokens sit after everything
    they could influence. `given` [s, k]: the picks a served program
    recorded for this layer (-1: none), whose notes [s, 3] are appended to
    the list `notes`."""
    fz, s = _frozen(arch), x.shape[0]
    tb, qb = min(T_BLOCK, s), min(Q_BLOCK, s)
    Hk, Hv, dk, dv, K, C = _delta_widths(arch)
    out = []
    if kind.startswith(DELTA):
        before = jnp.zeros((K - 1, C), jnp.float32)
        S = jnp.zeros((Hv, dk, dv), jnp.float32)
        for a, b in _blocks(s, tb):
            y, before, S = _delta_block_fn(fz, mm)(x[a:b], w, before, S)
            out.append(y)
    else:
        parts = [_project_fn(fz, mm)(x[a:b], w, jnp.arange(a, b))
                 for a, b in _blocks(s, tb)]
        q_nope, q_pe, k_nope, k_pe, v = (jnp.concatenate(p)
                                         for p in zip(*parts))
        # rows past a query's position are never read: the key counts are
        # the buckets' alone
        bucket = K_BUCKET if s > T_BLOCK else s
        k_nope, k_pe, v = pad_rows(bucket, k_nope, k_pe, v)
        for a, b in _blocks(s, qb):
            m = -(-b // bucket) * bucket
            out.append(_attend_fn(fz, mm)(
                x[a:b], w, q_nope[a:b], q_pe[a:b], jnp.arange(a, b),
                k_nope[:m], k_pe[:m], v[:m]))
    x = jnp.concatenate(out)
    fin = _ffn_fn(fz, mm, kind.endswith(DENSE))
    if given is None:
        given = np.full((s, arch["num_experts_per_tok"]), -1, np.int32)
    out = [fin(x[a:b], w, jnp.asarray(given[a:b])) for a, b in _blocks(s, tb)]
    if notes is not None:
        notes.append(np.concatenate([np.asarray(n) for _, n in out]))
    return jnp.concatenate([y for y, _ in out])


def _embedded(ids, embedding):
    """embedding[ids] in float32, ids padded with zeros to whole token blocks
    (a short sequence to a whole eight rows)."""
    n = len(ids)
    tb = T_BLOCK if n > T_BLOCK else -(-n // 8) * 8
    padded = np.zeros(-(-n // tb) * tb, np.int32)
    padded[:n] = ids
    return embedding[jnp.asarray(padded)].astype(jnp.float32)


def _layer_of(arch, x, n, w, mm, i, picks, notes):
    """Layer i over one padded sequence x [size, h] of n tokens. `picks` [n,
    expert layers, k]: a served program's recorded routing; `notes`: a list
    that gets each expert layer's routing notes [n, 3]."""
    kind, kd = layer_kinds(arch)[i], arch["first_k_dense_replace"]
    given = noted = None
    if picks is not None and i >= kd:
        given = np.full((x.shape[0], picks.shape[-1]), -1, np.int32)
        given[:n] = picks[:, i - kd]
        noted = [] if notes is not None else None
    x = layer_forward(x, w, arch, mm, kind, given, noted)
    if noted:
        notes.append(noted[0][:n])
    return x


def forward_hidden(arch, ids, weights_of, embedding, mm=f32_mm, picks=None,
                   notes=None):
    """ids [n] -> the last layer's output [n, h]: the whole model's plain
    forward over one sequence. `weights_of(i)` gives layer i's leaves."""
    x = _embedded(ids, embedding)
    for i in range(arch["num_hidden_layers"]):
        x = _layer_of(arch, x, len(ids), weights_of(i), mm, i, picks, notes)
    return x[:len(ids)]


@functools.lru_cache(maxsize=None)
def _head_fn(eps, mm):
    def head(x, norm_w, head_w):
        return mm(rms_norm(x, scale(norm_w.astype(jnp.float32)), eps),
                  head_w.astype(jnp.float32))

    return jax.jit(head)


def head_logits(arch, x, final_norm, lm_head, mm=f32_mm):
    return _head_fn(arch["rms_norm_eps"], mm)(x, final_norm, lm_head)


def served_logits(arch, seed, requests, mm=f32_mm):
    """For each (prompt, tokens, record) of `requests`, the reference logits
    [len(tokens), vocab] (the chip's slice of the vocabulary) at the
    positions where the server chose `tokens` after `prompt`, following the
    routing the server recorded (`record`, a `RoutingTrace`) where it stands
    this reference's check, unless more than FOLLOW_MAX of the request's
    token-layers had to be followed: then the request is judged on this
    reference's own routing. Layer by layer, every request one at a time
    through the layer, so a layer's weights are made from the seed once.
    Prints what the routing check found over all the requests."""
    outer = weights.outer_params(arch, seed)
    seqs = [np.concatenate([np.asarray(prompt), np.asarray(tokens)[:-1]])
            for prompt, tokens, *_ in requests]
    tables = [record[0].table(len(seq)) if record and record[0] is not None
              else None for (_, _, *record), seq in zip(requests, seqs)]
    xs = [_embedded(seq, outer["embedding"]) for seq in seqs]
    notes = [None if t is None else [] for t in tables]

    def weights_of(i):
        return weights.layer_params(_family, arch, seed, i)

    for i in range(arch["num_hidden_layers"]):
        w = weights_of(i)
        for r, seq in enumerate(seqs):
            xs[r] = _layer_of(arch, xs[r], len(seq), w, mm, i, tables[r],
                              notes[r])
    # token-layers recorded, differing, followed; requests judged on the
    # reference's own routing; the widest shortfall; the most a request had
    # followed
    out, found = [], np.zeros(6)
    for r, (prompt, tokens, *_) in enumerate(requests):
        x = xs[r][:len(seqs[r])]
        if tables[r] is not None:
            recorded = int(np.sum(tables[r][:, :, 0] >= 0))
            differ, followed, _ = np.sum(notes[r], axis=(0, 1))
            own = followed > FOLLOW_MAX * recorded
            if own:
                x = forward_hidden(arch, seqs[r], weights_of,
                                   outer["embedding"], mm)
            found[:4] += [recorded, differ, followed, own]
            found[4] = max(found[4], np.max(np.asarray(notes[r])[:, :, 2]))
            found[5] = max(found[5], followed / max(recorded, 1))
        out.append(served_rows(
            lambda rows: head_logits(arch, rows, outer["final_norm"],
                                     outer["lm_head"], mm),
            x, len(prompt), len(tokens)))
        xs[r] = None
    if found[0]:
        recorded, differ, followed, own, short, most = found
        print(f"correct: routing: {int(recorded)} token-layers recorded, "
              f"picks not the reference's own in {differ / recorded:.4%}, "
              f"followed {followed / recorded:.4%}, refused "
              f"{(differ - followed) / recorded:.4%}, the widest shortfall "
              f"{short:.4f} of a score (followed up to {ROUTING_TOL}); "
              f"the most a request had followed {most:.4%}; {int(own)} "
              f"request(s) past {FOLLOW_MAX:.0%} followed and judged on the "
              f"reference's own routing", flush=True)
    return out


class _family:
    """What `weights` asks of a family (this file is loaded by its path and
    is in no `sys.modules`)."""

    layer_kinds = staticmethod(layer_kinds)
    layer_shapes = staticmethod(layer_shapes)
    leaf_init = staticmethod(leaf_init)


# -- the counts of this family's readers ---------------------------------------

def delta_flops_per_token(arch, chunk=64):
    """The chunked form's operations a prefill token in ONE delta layer
    (`kernels/gated_delta_rule.py`'s equations at a chunk of C tokens): K
    K^T and Q K^T a KEY head (2 C dk each); a VALUE head (its own decay and
    write strength) the triangular solve's two right-hand sides (C (dk +
    dv)), W_k S, Q S and the state's update K^T U (2 dk dv each), A U (2 C
    dv)."""
    Hk, Hv, dk, dv, _, _ = _delta_widths(arch)
    C = chunk
    return (Hk * 2 * 2 * C * dk
            + Hv * (C * (dk + dv) + 3 * 2 * dk * dv + 2 * C * dv))


def delta_state_bytes(arch):
    """One request's float32 matrix state read once and written once in ONE
    delta layer: what a decode step must move for a row."""
    _, Hv, dk, dv, _, _ = _delta_widths(arch)
    return Hv * dk * dv * 4 * 2


def traced_work(ctx):
    """The least seconds the chip could take for what the EQUATIONS need in
    the traced slice, {"delta": s, "latent": s, "experts": s}. experts and
    latent are `mla_moe.py`'s counts (a decode step reads the held experts a
    row picked once; a decoding row's latent bytes a cached token, or the
    absorbed form's flops; a prefill window's pairs' flops), the latent ones
    for this stack's latent layers alone. delta: a prefill token's
    chunk-form operations over the bf16 peak and its window's state once in
    and once out, a decoding row's state once in and once out over the HBM
    bandwidth, every delta layer. None where the prefill steps' windows
    cannot be rebuilt, on a run with no device trace, or on a program without
    the routing observations."""
    need = _mla.traced_work(ctx)
    windows = _mla.prefill_windows(ctx) if need else None
    if windows is None:
        return None
    arch = ctx.arch
    n_delta, n_latent = (mixers(arch).count(m) for m in (DELTA, LATENT))
    need["latent"] *= n_latent / arch["num_hidden_layers"]
    t0, t1 = ctx.trace_host_window
    flops, bw = ctx.peaks["bf16_flops"], ctx.peaks["hbm_bytes_per_s"]
    delta = sum((hi - lo) * delta_flops_per_token(arch) / flops
                + delta_state_bytes(arch) / bw
                for a, lo, hi in windows if t0 <= a < t1)
    delta += sum(delta_state_bytes(arch) / bw
                 for r in ctx.run.recs.values()
                 for j, t in enumerate(r.times) if j and t0 <= t < t1)
    need["delta"] = n_delta * delta
    return need
