"""DeepSeek-V2's stack: multi-head LATENT attention (MLA) over one cached
row a token, and a feed-forward of shared experts plus routed experts under
group-limited routing, of which this chip HOLDS one share. What the harness
knows of it (`harness/spec.py`):

  serve_args      the program's static description (`LatentMoEArgs`)
  layer_shapes    one EXPERT layer's leaves (the harness stacks these);
                  `dense_layer_shapes` the leading dense layers' (stacked
                  apart by `make_params` where `first_k_dense_replace` > 0:
                  the tests' presets; the benchmark's cell has none)
  decoder_layer   the plain layer, its kind by `first_k_dense_replace`
  served_logits   the plain float32 forward of each served request, in
                  blocks so that 16k positions fit
  the counts of its readers (`traced_work` and below)

THE EQUATIONS the reference is written from (the published config.json and
modeling file; h = RMSNorm(x), eps 1e-6). Every layer: x += Attn(h);
x += FFN(RMSNorm(x)). Input embedding[ids]; output lm_head(RMSNorm(x)),
untied.

  attention  c_q = RMSNorm(h W_qa); per head i [q_nope_i; q_pe_i] = c_q W_qb.
             [c_kv; k_pe] = h W_kva; c_kv = RMSNorm(c_kv); k_pe is ONE
             vector a token, shared by all heads. RoPE on q_pe_i and k_pe at
             the token's position. [k_nope_i; v_i] = c_kv W_kvb.
             score_i(t, s) = scale (q_nope_i(t) . k_nope_i(s) + q_pe_i(t) .
             k_pe(s)), causal softmax over s <= t, o_i = sum_s p_i(t, s)
             v_i(s), out = concat_i(o_i) W_o. Written here in exactly this
             NON-absorbed form: keys and values are decompressed for every
             position.
  YaRN       on the rotary slice (d = qk_rope_head_dim): inv_freq_j blends
             theta^(-2j/d) and the same over `factor` by a linear ramp
             between the dimensions that make `beta_fast` and `beta_slow`
             rotations over `original_max_position_embeddings`; cos and sin
             are multiplied by mscale(factor, mscale) / mscale(factor,
             mscale_all_dim); scale = (nope + rope)^-1/2 * mscale(factor,
             mscale_all_dim)^2, mscale(f, m) = 0.1 m ln f + 1.
  experts    s = softmax(h W_r) over ALL published experts, float32; a
             group scores the best of its experts; the `topk_group` best
             groups stay and the others' scores are masked; the
             `num_experts_per_tok` best experts among what stays, each
             weighing `routed_scaling_factor` * s_e (not renormalised).
             FFN(h) = SwiGLU_shared(h) (width n_shared_experts *
             moe_intermediate_size) + sum over the picked of w_e
             SwiGLU_e(h). Ties go to the lower index.
  dense      the `first_k_dense_replace` leading layers: FFN = SwiGLU of
             width `intermediate_size`.
  the share  the layer holds the experts [first, first + n_routed_experts)
             of the PUBLISHED count (`published.n_routed_experts`, the
             router's width; `deployment.expert_group_held` names the group
             of `n_group` held). A picked expert that is not held adds
             nothing; the shared experts, the router and the attention are
             whole. That partial result goes on to the next layer.

DEPARTURES and what is ASSUMED (the configuration file lists them too): the
rotary slice is rotated in halves (pairs (j, j + d/2)); the published code
first permutes the slice from interleaved pairs to halves, a fixed
permutation of W_qb's and W_kva's rotary columns, immaterial for weights
made from a seed. Ties in both top-k steps go to the lower index.

ROUTING IS DISCRETE, and the comparison says how it deals with that. A token
whose last pick and first miss score alike goes either way on rounding, and
the layer's output then differs by a whole expert (weight 16 x its score):
measured on the chip at the published widths, the program in bfloat16 and
this reference disagree on such a pick for ~1.4% of tokens a layer where an
expert held here is involved, each such token's logits then off by 1 to 4
(its hidden state by 30-60%), while the program in float32 agrees with this
reference to 1e-4 (PERF.md, Findings PR 33). So the program records which
experts every token picked (`Request.routing`, `REQUEST_RECORD`), and
`served_logits` FOLLOWS a token's recorded picks in a layer where they are,
by this reference's own float32 scores, a routing the rule could have made
to within `ROUTING_TOL`: every group a pick comes from scores (its best
expert) at least (1 - tol) of the weakest group that stays here; and, with
the groups kept being those filled up with this reference's best others,
every pick scores at least (1 - tol) of the k-th best expert of those
groups. The weights are always this reference's own scores. Where the
recorded picks fail that, the reference keeps its own picks and the token's
gap shows. `ROUTING_TOL` 0.4: over ALL the compared tokens of five runs on
the chip at the cell's size (4.1 million token-layers, five seeds; PERF.md,
Findings PR 33) the program's picks are not this reference's own for 9.4-9.5%
of token-layers, every one of those stands the check, and the widest
shortfall of a run reads 0.170-0.209 of a score (0.13 over the six thousand
token-layers of the first probe: the largest of N grows with log N, to
about 0.25 over a hundred runs). One refused pick can refuse a PR, so the
tolerance stands at 1.9 times the largest seen. A routing fault (a wrong
group count, a wrong k, a missing mask) misses by a whole score and is
judged as one; a fault that stays inside the tolerance pick by pick (a group
too few kept) moves the SHARE of picks that must be followed: a request more
than `FOLLOW_MAX` (a quarter; the runs above: 10.1-10.6% at most) of whose
token-layers had to be followed is judged on this reference's own routing,
and its gap shows (`benchmarks/tests/test_mla_moe.py` plants both faults
in the program and reads not correct).

Nothing here is the program's but `serve_args`: `jax.numpy`, float32, matmul
precision `highest`, no kernel, no cache, no batching; every expert held is
computed for every token and weighed (zero where it was not picked). Every
weight goes through `mm` (the control swaps it for fp8).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import weights
from benchmarks.harness.reference import (HIGHEST, f32_mm, pad_rows,
                                          rms_norm, served_rows)

T_BLOCK = 1024      # tokens a projection / feed-forward call takes
Q_BLOCK = 256       # queries attended at once: [heads, Q_BLOCK, keys] scores
K_BUCKET = 4096     # a query block sees its keys padded up to a multiple:
                    # at most four key counts, so four programs, whatever
                    # the requests' lengths (PR 36; 2,048 and the request's
                    # own length before: a dozen)
DENSE_FOLD = 1 << 10    # a dense leading layer's seed index lies past these
REQUEST_RECORD = "routing"      # the finished request's attribute (a
                                # `serving.latent.RoutingTrace`) that
                                # `served_logits` reads
ROUTING_TOL = 0.4   # see ROUTING IS DISCRETE above
FOLLOW_MAX = 0.25   # the share of a request's token-layers that may be
                    # followed: see ROUTING IS DISCRETE above


# -- the program's side: imported here and nowhere in the reference ----------

def serve_args(arch):
    from paddle_tpu.models import latent_moe_functional as lm

    y = arch["rope_scaling"]
    first, held = experts_held(arch)
    return lm.LatentMoEArgs(
        vocab_size=arch["vocab_size"], hidden_size=arch["hidden_size"],
        num_layers=arch["num_hidden_layers"],
        num_heads=arch["num_attention_heads"], q_rank=arch["q_lora_rank"],
        kv_rank=arch["kv_lora_rank"], nope_dim=arch["qk_nope_head_dim"],
        rope_dim=arch["qk_rope_head_dim"], v_dim=arch["v_head_dim"],
        dense_intermediate=arch["intermediate_size"],
        expert_intermediate=arch["moe_intermediate_size"],
        shared_experts=arch["n_shared_experts"],
        routed_experts=router_width(arch), first_expert=first,
        experts_held=held, n_group=arch["n_group"],
        topk_group=arch["topk_group"],
        experts_per_tok=arch["num_experts_per_tok"],
        routed_scaling=float(arch["routed_scaling_factor"]),
        first_k_dense=arch["first_k_dense_replace"],
        rope_theta=float(arch["rope_theta"]), rms_eps=arch["rms_norm_eps"],
        record_routing=True,        # `served_logits` reads `Request.routing`
        yarn=lm.YarnConfig(
            factor=float(y["factor"]),
            original_max_position=y["original_max_position_embeddings"],
            beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
            mscale=float(y["mscale"]),
            mscale_all_dim=float(y["mscale_all_dim"])))


# -- the share ---------------------------------------------------------------

def router_width(arch):
    """The published expert count: the router's outputs, whatever is held."""
    return arch.get("published", {}).get("n_routed_experts",
                                         arch["n_routed_experts"])


def experts_held(arch):
    """(first, count): the experts this chip holds, the group
    `deployment.expert_group_held` of the published `n_group` (all of them,
    from 0, where the file states no deployment)."""
    held = arch["n_routed_experts"]
    dep = arch.get("deployment")
    group = dep.get("expert_group_held", 0) if isinstance(dep, dict) else 0
    return group * held, held


# -- the weights -------------------------------------------------------------

def _attention_shapes(arch):
    h, H = arch["hidden_size"], arch["num_attention_heads"]
    qr, kr = arch["q_lora_rank"], arch["kv_lora_rank"]
    nope, rope, v = (arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
                     arch["v_head_dim"])
    return {"ln1": (h,), "ln2": (h,), "w_qa": (h, qr), "q_norm": (qr,),
            "w_qb": (qr, H * (nope + rope)), "w_kva": (h, kr + rope),
            "kv_norm": (kr,), "w_kvb": (kr, H * (nope + v)),
            "wo": (H * v, h)}


def layer_shapes(arch):
    """One expert layer's leaves; `we_*` hold the experts HELD."""
    h, m = arch["hidden_size"], arch["moe_intermediate_size"]
    sh, E = arch["n_shared_experts"] * m, arch["n_routed_experts"]
    return dict(_attention_shapes(arch), router=(h, router_width(arch)),
                ws_gate=(h, sh), ws_up=(h, sh), ws_down=(sh, h),
                we_gate=(E, h, m), we_up=(E, h, m), we_down=(E, m, h))


def dense_layer_shapes(arch):
    h, i = arch["hidden_size"], arch["intermediate_size"]
    return dict(_attention_shapes(arch), w_gate=(h, i), w_up=(h, i),
                w_down=(i, h))


def leaf_init(arch):
    """The matrices' init where the configuration states one (the tests'
    toy presets do, to make their attention and routing sharp); the real
    configuration takes the harness's rules."""
    std = arch.get("initializer_range")
    if std is None:
        return {}
    shapes = dict(dense_layer_shapes(arch), **layer_shapes(arch))
    return {name: (0.0, std) for name, shape in shapes.items()
            if len(shape) >= 2}


class _EXPERT_LEAVES:
    """What `weights` asks of a family (this file is loaded by its path and
    is in no `sys.modules`)."""

    layer_shapes = staticmethod(layer_shapes)
    leaf_init = staticmethod(leaf_init)


class _DENSE_LEAVES:
    layer_shapes = staticmethod(dense_layer_shapes)
    leaf_init = staticmethod(leaf_init)


def layer_weights(arch, seed, index, dtype=jnp.bfloat16):
    """Layer `index`'s leaves: a dense leading layer's, or those the harness
    stacks at `index - first_k_dense_replace`."""
    kd = arch["first_k_dense_replace"]
    if index < kd:
        return weights.layer_params(_DENSE_LEAVES, arch, seed,
                                    DENSE_FOLD + index, dtype)
    return weights.layer_params(_EXPERT_LEAVES, arch, seed, index - kd, dtype)


def make_params(arch, seed, dtype=jnp.bfloat16):
    """The program's whole tree where the stack has dense leading layers
    (which `weights.make_params` does not know): the harness's tree over the
    expert layers, and `dense_layers/*` stacked beside it."""
    kd = arch["first_k_dense_replace"]
    params = weights.make_params(
        _EXPERT_LEAVES, dict(arch, num_hidden_layers=arch[
            "num_hidden_layers"] - kd), seed, dtype)
    if kd:
        dense = [layer_weights(arch, seed, i, dtype) for i in range(kd)]
        params["dense_layers"] = jax.tree.map(lambda *a: jnp.stack(a), *dense)
    return params


# -- the plain layer -----------------------------------------------------------

def yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(arch):
    y, d, base = (arch["rope_scaling"], arch["qk_rope_head_dim"],
                  arch["rope_theta"])
    j = np.arange(0, d, 2) / d
    plain = base ** -j
    scaled = plain / y["factor"]

    def dim_of(rotations):
        return d * math.log(y["original_max_position_embeddings"]
                            / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(dim_of(y["beta_fast"])), 0)
    high = min(math.ceil(dim_of(y["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    return jnp.asarray(scaled * ramp + plain * (1 - ramp), jnp.float32)


def attention_scale(arch):
    y = arch["rope_scaling"]
    m = yarn_mscale(y["factor"], y["mscale_all_dim"])
    return (arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"]) ** -0.5 \
        * m * m


def rotary(x, pos, arch):
    """x [s, heads, d] at positions pos [s]; pairs (j, j + d/2) rotate by
    pos * inv_freq_j, times YaRN's cos / sin multiplier."""
    y = arch["rope_scaling"]
    m = yarn_mscale(y["factor"], y["mscale"]) / yarn_mscale(
        y["factor"], y["mscale_all_dim"])
    d = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None] * yarn_inv_freq(arch)[None, :]
    cos, sin = m * jnp.cos(ang)[:, None, :], m * jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def project(x, w, arch, mm, pos):
    """x [s, h] at positions pos -> q_nope [s, H, nope], q_pe [s, H, rope],
    k_nope [s, H, nope], k_pe [s, 1, rope], v [s, H, v]: the keys and values
    DECOMPRESSED for every position."""
    H, eps = arch["num_attention_heads"], arch["rms_norm_eps"]
    kr, nope = arch["kv_lora_rank"], arch["qk_nope_head_dim"]
    s = x.shape[0]
    hin = rms_norm(x, w["ln1"], eps)
    c_q = rms_norm(mm(hin, w["w_qa"]), w["q_norm"], eps)
    q = mm(c_q, w["w_qb"]).reshape(s, H, -1)
    kv = mm(hin, w["w_kva"])
    c_kv = rms_norm(kv[:, :kr], w["kv_norm"], eps)
    k_pe = rotary(kv[:, None, kr:], pos, arch)
    kvb = mm(c_kv, w["w_kvb"]).reshape(s, H, -1)
    return (q[..., :nope], rotary(q[..., nope:], pos, arch), kvb[..., :nope],
            k_pe, kvb[..., nope:])


def attend(q_nope, q_pe, qpos, k_nope, k_pe, v, arch):
    """Queries at positions qpos [n] over the keys and values of positions
    0 .. m - 1 (every qpos < m). Returns [n, H, v]."""
    sc = (jnp.einsum("nhd,mhd->hnm", q_nope, k_nope, precision=HIGHEST)
          + jnp.einsum("nhd,md->hnm", q_pe, k_pe[:, 0], precision=HIGHEST))
    see = jnp.arange(k_nope.shape[0])[None, :] <= qpos[:, None]
    p = jax.nn.softmax(jnp.where(see[None], sc * attention_scale(arch),
                                 -jnp.inf), axis=-1)
    return jnp.einsum("hnm,mhd->nhd", p, v, precision=HIGHEST)


def swiglu(h, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)


def routing_noting(h, w, arch, mm, given=None):
    """h [s, h] -> (the weight of every PUBLISHED expert for every token
    [s, published]: routed_scaling_factor * its score where it was picked,
    0 elsewhere; notes [s, 3]). `given` [s, k] (-1: none): the picks a
    served program recorded, followed where they stand this reference's
    check to within ROUTING_TOL (the module's head says which, and why).
    A token's notes: 1 where its recorded picks are not this reference's
    own, 1 where such picks were followed, and how far they fall short of
    the check's thresholds, as a share of a score (1: no routing the rule
    could make, whatever the scores)."""
    n, g = router_width(arch), arch["n_group"]
    scores = jax.nn.softmax(mm(h, w["router"]), axis=-1)
    best = jnp.max(scores.reshape(-1, g, n // g), axis=-1)         # [s, g]
    # the topk_group best groups, ties to the lower index: a group stays
    # when fewer than topk_group groups beat it (a higher score, or the
    # same score at a lower index)
    idx = jnp.arange(g)
    beats = (best[:, None, :] > best[:, :, None]) | (
        (best[:, None, :] == best[:, :, None])
        & (idx[None, None, :] < idx[None, :, None]))
    stays = jnp.sum(beats, axis=-1) < arch["topk_group"]           # [s, g]
    masked = jnp.where(jnp.repeat(stays, n // g, axis=1), scores, 0.0)
    e = jnp.arange(n)
    beats = (masked[:, None, :] > masked[:, :, None]) | (
        (masked[:, None, :] == masked[:, :, None])
        & (e[None, None, :] < e[None, :, None]))
    picked = jnp.sum(beats, axis=-1) < arch["num_experts_per_tok"]
    notes = jnp.zeros((h.shape[0], 3), jnp.float32)
    if given is not None:
        k = arch["num_experts_per_tok"]
        took = jnp.any(given[:, :, None] == e[None, None, :], axis=1)
        from_group = jnp.any(took.reshape(-1, g, n // g), axis=-1)  # [s, g]
        # its groups: each as good as the weakest that stays here, to
        # within the tolerance; the group set it must have kept is then
        # those, filled up with this reference's best others
        last_group = jnp.min(jnp.where(stays, best, jnp.inf), axis=-1)
        group_short = jnp.max(jnp.where(
            from_group, 1 - best / last_group[:, None], 0.0), axis=-1)
        first = best + 2.0 * from_group          # a score is at most 1
        beats = (first[:, None, :] > first[:, :, None]) | (
            (first[:, None, :] == first[:, :, None])
            & (idx[None, None, :] < idx[None, :, None]))
        kept = jnp.sum(beats, axis=-1) < arch["topk_group"]
        among = jnp.where(jnp.repeat(kept, n // g, axis=1), scores, 0.0)
        # its picks: each as good as the k-th best of those groups, to
        # within the tolerance
        last_pick = -jnp.sort(-among, axis=-1)[:, k - 1]
        pick_short = jnp.max(jnp.where(
            took, 1 - scores / last_pick[:, None], 0.0), axis=-1)
        possible = (jnp.sum(from_group, axis=-1) <= arch["topk_group"]) & (
            jnp.sum(took, axis=-1) == k)
        short = jnp.where(possible, jnp.maximum(group_short, pick_short), 1.0)
        recorded = jnp.any(given >= 0, axis=-1)
        differs = recorded & jnp.any(took != picked, axis=-1)
        follow = recorded & (short <= ROUTING_TOL)
        picked = jnp.where(follow[:, None], took, picked)
        notes = jnp.stack([differs, differs & follow,
                           jnp.where(differs, short, 0.0)],
                          axis=-1).astype(jnp.float32)
    return jnp.where(picked, arch["routed_scaling_factor"] * scores,
                     0.0), notes


def routing(h, w, arch, mm, given=None):
    return routing_noting(h, w, arch, mm, given)[0]


def routed_experts_noting(h, w, arch, mm, given=None):
    """The HELD experts' part of the routed sum: every held expert computed
    for every token and weighed (zero where it was not picked); and the
    routing's notes."""
    first, held = experts_held(arch)
    weigh, notes = routing_noting(h, w, arch, mm, given)
    weigh = weigh[:, first:first + held]                            # [s, E]

    def one(acc, xs):
        w_gate, w_up, w_down, we = xs
        return acc + we[:, None] * swiglu(h, w_gate, w_up, w_down, mm), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        w["we_gate"], w["we_up"], w["we_down"], weigh.T))
    return acc, notes


def routed_experts(h, w, arch, mm, given=None):
    return routed_experts_noting(h, w, arch, mm, given)[0]


def shared_experts(h, w, arch, mm):
    return swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"], mm)


def finish_noting(x, attn, w, arch, mm, dense, given=None):
    """The output projection, the residual, then the feed-forward of the
    layer's kind; and the routing's notes. x [s, h], attn [s, H, v];
    `given`: see `routing_noting`."""
    x = x + mm(attn.reshape(attn.shape[0], -1), w["wo"])
    h = rms_norm(x, w["ln2"], arch["rms_norm_eps"])
    if dense:
        return (x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"], mm),
                jnp.zeros((x.shape[0], 3), jnp.float32))
    routed, notes = routed_experts_noting(h, w, arch, mm, given)
    return x + shared_experts(h, w, arch, mm) + routed, notes


def finish(x, attn, w, arch, mm, dense, given=None):
    return finish_noting(x, attn, w, arch, mm, dense, given)[0]


def decoder_layer(x, w, arch, mm, index=0):
    """One whole layer over sequences x [b, s, h] from position 0, its kind
    by the layer's index against `first_k_dense_replace`."""
    dense = index < arch["first_k_dense_replace"]

    def one(x1):
        pos = jnp.arange(x1.shape[0])
        q_nope, q_pe, k_nope, k_pe, v = project(x1, w, arch, mm, pos)
        attn = attend(q_nope, q_pe, pos, k_nope, k_pe, v, arch)
        return finish(x1, attn, w, arch, mm, dense)

    return jax.lax.map(one, x)


# -- a served model: logits at the served positions ----------------------------

_KEYS = ("num_attention_heads", "q_lora_rank", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "rms_norm_eps", "rope_theta", "rope_scaling", "n_routed_experts",
         "n_group", "topk_group", "num_experts_per_tok",
         "routed_scaling_factor", "first_k_dense_replace")


def _frozen(arch):
    first, _ = experts_held(arch)
    rs = tuple(sorted(arch["rope_scaling"].items()))
    return tuple((k, rs if k == "rope_scaling" else arch[k])
                 for k in _KEYS) + (("router_width", router_width(arch)),
                                    ("first", first))


def _thaw(frozen):
    arch = dict(frozen)
    arch["rope_scaling"] = dict(arch["rope_scaling"])
    arch["published"] = {"n_routed_experts": arch.pop("router_width")}
    group = arch.pop("first") // arch["n_routed_experts"]
    arch["deployment"] = {"expert_group_held": group}
    return arch


def _f32(w):
    return jax.tree.map(lambda a: a.astype(jnp.float32), w)


@functools.lru_cache(maxsize=None)
def _project_fn(frozen, mm):
    arch = _thaw(frozen)
    return jax.jit(lambda x, w, pos: project(x, _f32(w), arch, mm, pos))


@functools.lru_cache(maxsize=None)
def _attend_fn(frozen):
    arch = _thaw(frozen)
    return jax.jit(lambda qn, qr, qpos, kn, kr, v: attend(qn, qr, qpos, kn,
                                                          kr, v, arch))


@functools.lru_cache(maxsize=None)
def _finish_fn(frozen, mm, dense):
    arch = _thaw(frozen)
    return jax.jit(lambda x, attn, w, given: finish_noting(
        x, attn, _f32(w), arch, mm, dense, given))


@functools.lru_cache(maxsize=None)
def _head_fn(eps, mm):
    def head(x, norm_w, head_w):
        return mm(rms_norm(x, norm_w.astype(jnp.float32), eps),
                  head_w.astype(jnp.float32))

    return jax.jit(head)


def _blocks(n, size):
    return [(a, min(a + size, n)) for a in range(0, n, size)]


def layer_forward(x, w, arch, mm, index, given=None, notes=None):
    """One layer over one sequence x [s, h] (s a multiple of the token
    block), block by block: every jitted call has one of a few fixed shapes
    whatever the sequence's length. `given` [s, k]: the picks a served
    program recorded for this layer (-1: none), see `routing_noting`, whose
    notes [s, 3] are appended to the list `notes` where one is given."""
    fz, s = _frozen(arch), x.shape[0]
    tb, qb = min(T_BLOCK, s), min(Q_BLOCK, s)
    dense = index < arch["first_k_dense_replace"]
    parts = [_project_fn(fz, mm)(x[a:b], w, jnp.arange(a, b))
             for a, b in _blocks(s, tb)]
    q_nope, q_pe, k_nope, k_pe, v = (jnp.concatenate(p) for p in zip(*parts))
    del parts
    bucket = K_BUCKET if s > T_BLOCK else s
    if s > T_BLOCK:
        # rows past a query's position are never read: the key counts are
        # the buckets' alone, whatever the request's own length
        k_nope, k_pe, v = pad_rows(K_BUCKET, k_nope, k_pe, v)
    outs = []
    for a, b in _blocks(s, qb):
        m = -(-b // bucket) * bucket
        outs.append(_attend_fn(fz)(q_nope[a:b], q_pe[a:b], jnp.arange(a, b),
                                   k_nope[:m], k_pe[:m], v[:m]))
    attn = jnp.concatenate(outs)
    del outs, q_nope, q_pe, k_nope, k_pe, v
    fin = _finish_fn(fz, mm, dense)
    if given is None:
        given = np.full((s, arch["num_experts_per_tok"]), -1, np.int32)
    outs = [fin(x[a:b], attn[a:b], w, jnp.asarray(given[a:b]))
            for a, b in _blocks(s, tb)]
    if notes is not None:
        notes.append(np.concatenate([np.asarray(n) for _, n in outs]))
    return jnp.concatenate([y for y, _ in outs])


def _embedded(arch, ids, embedding):
    """ids [n] -> their embeddings [size, h], float32, padded to whole token
    blocks."""
    n = len(ids)
    tb = T_BLOCK if n > T_BLOCK else -(-n // 8) * 8
    padded = np.zeros(-(-n // tb) * tb, np.int32)
    padded[:n] = ids
    return embedding[jnp.asarray(padded)].astype(jnp.float32)


def _layer_of(arch, x, n, w, mm, i, picks, notes):
    """Layer i over one padded sequence x [size, h] of n tokens; `picks` and
    `notes` as `forward_hidden` has them."""
    kd, given, noted = arch["first_k_dense_replace"], None, None
    if picks is not None and i >= kd:
        given = np.full((x.shape[0], picks.shape[-1]), -1, np.int32)
        given[:n] = picks[:, i - kd]
    if notes is not None and i >= kd:
        noted = []
    x = layer_forward(x, w, arch, mm, i, given, noted)
    if noted:
        notes.append(noted[0][:n])
    return x


def forward_hidden(arch, ids, weights_of, embedding, mm=f32_mm, picks=None,
                   notes=None):
    """ids [n] -> the last layer's output [n, h]: the whole model's plain
    forward over one sequence. `weights_of(i)` gives layer i's leaves;
    `picks` [n, expert layers, k]: a served program's recorded routing;
    `notes`: a list that gets each expert layer's routing notes [n, 3]."""
    x = _embedded(arch, ids, embedding)
    for i in range(arch["num_hidden_layers"]):
        x = _layer_of(arch, x, len(ids), weights_of(i), mm, i, picks, notes)
    return x[:len(ids)]


def head_logits(arch, x, final_norm, lm_head, mm=f32_mm):
    return _head_fn(arch["rms_norm_eps"], mm)(x, final_norm, lm_head)


def served_logits(arch, seed, requests, mm=f32_mm):
    """For each (prompt, tokens, ...) of `requests`, the reference logits
    [len(tokens), vocab] (the chip's slice of the vocabulary) at the
    positions where the server chose `tokens` after `prompt`, following the
    routing the server recorded (`record`, a `RoutingTrace`) where it stands
    this reference's check (`routing_noting`), unless more than FOLLOW_MAX
    of the request's token-layers had to be followed: then the request is
    judged on this reference's own routing. Layer by layer, every request
    one at a time through the layer, so a layer's weights are made from the
    seed once. Prints what the routing check found over all the requests."""
    outer = weights.outer_params(arch, seed)
    seqs = [np.concatenate([np.asarray(prompt), np.asarray(tokens)[:-1]])
            for prompt, tokens, *_ in requests]
    tables = [record[0].table(len(seq)) if record and record[0] is not None
              else None for (_, _, *record), seq in zip(requests, seqs)]
    xs = [_embedded(arch, seq, outer["embedding"]) for seq in seqs]
    notes = [None if t is None else [] for t in tables]
    for i in range(arch["num_hidden_layers"]):
        w = layer_weights(arch, seed, i)
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
                x = forward_hidden(arch, seqs[r],
                                   lambda i: layer_weights(arch, seed, i),
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


# -- the counts of this family's readers ---------------------------------------

def expert_bytes(arch, itemsize=2):
    """One routed expert's three matrices."""
    return 3 * arch["hidden_size"] * arch["moe_intermediate_size"] * itemsize


def expert_flops_per_pair(arch):
    """One (token, expert) pair through the expert's three matrices."""
    return 6 * arch["hidden_size"] * arch["moe_intermediate_size"]


def expert_layers(arch):
    return arch["num_hidden_layers"] - arch["first_k_dense_replace"]


def row_bytes(arch, itemsize=2):
    """What one token caches a layer: [c_kv; k_pe]."""
    return (arch["kv_lora_rank"] + arch["qk_rope_head_dim"]) * itemsize


def latent_decode_flops_per_pair(arch):
    """A (query, key) pair in the absorbed form, every head: the score over
    the row's width and the value over its latent part."""
    kr, rope = arch["kv_lora_rank"], arch["qk_rope_head_dim"]
    return arch["num_attention_heads"] * 2 * (kr + rope + kr)


def latent_prefill_flops_per_pair(arch):
    """A (query, key) pair in the form the reference uses, every head."""
    return arch["num_attention_heads"] * 2 * (
        arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"]
        + arch["v_head_dim"])


def prefill_windows(ctx):
    """[(step's start time, lo, hi)] for every prefill step of the run: the
    window [lo, hi) of its prompt that the step computed, rebuilt from the
    run's own records by the engine's rules (no prefix hit on this unshared
    mix). A step of type `prefill` is a prompt's LAST window and emits its
    first token, so it is the step whose span holds that token's time; a
    `prefill_chunk` is the next `prefill_chunk` tokens of the OLDEST prompt
    in a chunk stream, and streams finish in the order they began, so the
    chunk steps go, in time order, to the long prompts in the order of
    their first tokens (then to those still streaming, oldest first). None
    where the records do not add up."""
    chunk = ctx.engine_kw["prefill_chunk"]
    recs = sorted((r for r in ctx.run.recs.values()
                   if r.submitted is not None),
                  key=lambda r: (r.times[0] if r.times else math.inf, r.rid))
    done = [r for r in recs if r.times]
    finals = sorted((s for s in ctx.spans if s[0] == "prefill"),
                    key=lambda s: s[1])
    if len(finals) != len(done):
        return None
    out = []
    for (_, a, b, _), r in zip(finals, done):
        if not a <= r.times[0] <= b + 1e-3:
            return None
        n = len(r.prompt)
        out.append((a, (n - 1) // chunk * chunk, n))
    owed = [(r, k) for r in recs if len(r.prompt) > chunk
            for k in range((len(r.prompt) - 1) // chunk)]
    chunks = sorted((s for s in ctx.spans if s[0] == "prefill_chunk"),
                    key=lambda s: s[1])
    if len(chunks) > len(owed):
        return None
    out += [(a, k * chunk, (k + 1) * chunk)
            for (_, a, _, _), (r, k) in zip(chunks, owed)]
    return sorted(out)


def traced_work(ctx):
    """The least seconds the chip could take for what the EQUATIONS need in
    the traced slice, {"experts": s, "latent": s}.

    experts: a decode step reads the held experts that at least one row
    picked, once (the engine's own observation of how many a step hit, a
    mean over the run's decode steps); a prefill window the larger of the
    held experts' bytes over the bandwidth and its (token, held expert)
    pairs' flops over the peak (the pairs from the engine's observed share
    of picks that land here). latent: a decode step the larger of a row's
    bytes a visible key over the bandwidth and the absorbed form's flops a
    (query, key) pair over the peak; a prefill window its pairs' flops in
    the form the reference uses over the peak. All a layer, times the
    layers. None where the prefill steps' windows cannot be rebuilt
    (`prefill_windows`), on a run with no device trace, or on a program
    without the routing observations."""
    if not ctx.trace or ctx.peaks is None:
        return None
    obs = ctx.counters["observations"]
    hit, here = obs.get("serve.held_experts_hit"), obs.get(
        "serve.routed_here_share")
    windows = prefill_windows(ctx) if hit and here else None
    if windows is None:
        return None
    arch = ctx.arch
    t0, t1 = ctx.trace_host_window
    flops, bw = ctx.peaks["bf16_flops"], ctx.peaks["hbm_bytes_per_s"]
    L, Le, held = (arch["num_hidden_layers"], expert_layers(arch),
                   arch["n_routed_experts"])
    need = {"experts": 0.0, "latent": 0.0}
    for a, lo, hi in windows:
        if t0 <= a < t1:
            pairs = (hi - lo) * arch["num_experts_per_tok"] * here["mean"]
            need["experts"] += Le * max(
                min(held, pairs) * expert_bytes(arch) / bw,
                pairs * expert_flops_per_pair(arch) / flops)
            keys = (lo + 1 + hi) * (hi - lo) / 2      # sum of t + 1
            need["latent"] += L * keys * latent_prefill_flops_per_pair(
                arch) / flops
    for kind, a, _, _ in ctx.spans:
        if kind == "decode" and t0 <= a < t1:
            need["experts"] += Le * hit["mean"] * expert_bytes(arch) / bw
    for r in ctx.run.recs.values():
        for j, t in enumerate(r.times):
            if j and t0 <= t < t1:     # the j-th token came from a decode
                keys = len(r.prompt) + j
                need["latent"] += L * keys * max(
                    row_bytes(arch) / bw,
                    latent_decode_flops_per_pair(arch) / flops)
    return need
