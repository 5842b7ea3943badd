"""GLM-5's stack (`glm_moe_dsa`): latent attention (MLA) behind a LEARNED
TOKEN SELECTOR (DeepSeek Sparse Attention's lightning indexer), and a
feed-forward of one shared expert plus routed experts under sigmoid,
bias-corrected, renormalised routing, of which this chip HOLDS sixteen; the
leading layers' feed-forward is a plain SwiGLU. What the harness knows of it
(`harness/spec.py`):

  serve_args      the program's static description (`LatentMoEArgs` with an
                  `IndexerConfig`, no YaRN, `scoring` sigmoid)
  layer_kinds     `dense_layers` (the leading layers), then `layers` (the
                  expert layers): each kind stacked apart
  layer_shapes / leaf_init   by kind
  decoder_layer   the plain layer, told its kind
  served_logits   the plain float32 forward of each served request, and the
                  judgement of the selection the program recorded
  the counts of its readers (`traced_work`, `index_work`, `param_count`,
  `pool_bytes`)

The attention's projections, the rotary rotation, the SwiGLU and the head
are `mla_moe.py`'s (the file beside this one, loaded by its path and not
edited), read with a `rope_scaling` of factor 1: plain rotary positions.

THE EQUATIONS the reference is written from (h = RMSNorm(x), eps
`rms_norm_eps`). Every layer: x += Attn(h); x += FFN(RMSNorm(x)). Input
embedding[ids]; output lm_head(RMSNorm(x)), untied.

  attention  `mla_moe.py`'s: c_q = RMSNorm(h W_qa), [q_nope_i; q_pe_i] = c_q
             W_qb a head, [c_kv; k_pe] = h W_kva, c_kv = RMSNorm(c_kv), k_pe
             one vector a token, RoPE (theta `rope_theta`, no scaling) on
             q_pe_i and k_pe, [k_nope_i; v_i] = c_kv W_kvb, scale (nope +
             rope)^-1/2.
  indexer    (each layer its own) qI(t) = c_q(t) W_iq, `index_n_heads` heads
             of `index_head_dim`; kI(s) = LayerNorm(h(s) W_ik) (weight and
             bias, eps 1e-6), ONE vector a token; RoPE at the token's
             position on the first `qk_rope_head_dim` values of every qI
             head and of kI; w(t) = h(t) W_iw times heads^-1/2 dim^-1/2.
             I(t, s) = sum_j w_j(t) ReLU(qI_j(t) . kI(s)), s <= t, float32.
  selection  S(t) = the `index_topk` positions s <= t of largest I(t, s),
             ties to the lower position; every s <= t while t < index_topk.
             The softmax of every head runs over s in S(t) alone.
  experts    s = sigmoid(h W_r) over ALL published experts, float32; the
             pick is the `num_experts_per_tok` largest of s + b (b the
             router's correction bias; `n_group` = `topk_group` = 1: no group
             step), ties to the lower index; a pick weighs
             `routed_scaling_factor` s_e / (sum of the picked s + 1e-20):
             normalised over ALL the picks, held or not. FFN = SwiGLU_shared
             + sum over the picked of w_e SwiGLU_e.
  the share  experts [first, first + n_routed_experts) of the published
             count are held (`deployment.first_expert_held`, 0); a pick
             elsewhere adds nothing; shared expert, router, attention and
             indexer are whole.

HOW THE REFERENCE COMPUTES THE ATTENTION. A query attends `index_topk` keys
of a context of up to 71,680: the reference gathers the selected tokens'
cached pair [c_kv; k_pe] and attends them in the latent space, score_i(t, s)
= scale ((W_uk_i^T q_nope_i(t)) . c_kv(s) + q_pe_i(t) . k_pe(s)), o_i = (sum
p c_kv(s)) W_uv_i: the equations above with the two products by W_kvb's
halves moved across the sum (W_uk_i, W_uv_i the key and value halves of head
i's W_kvb). Decompressed keys and values of 64 heads for a whole 71,680-token
request are 8 GB in float32 and do not fit beside a layer; `mla_moe.py`'s
tests hold the two forms equal.

ASSUMED (the configuration file lists these too): the LayerNorm with bias on
kI, its eps 1e-6, and the rotary slice LEADING an index head are
DeepSeek-V3.2-Exp's public inference code, which `glm_moe_dsa` follows (no
key of the config says so); that code also rotates qI and kI by one
orthogonal (Hadamard) matrix before quantising them to fp8: their dot
products are unchanged, and nothing here is fp8; rotary pairs are (j, j +
d/2), a fixed permutation of columns away from the published interleaved
order, immaterial for seeded weights; b is seeded normal(0, 0.01) so that it
changes picks; kI's bias normal(0, 0.02); else the harness's rules.

TWO DISCRETE CHOICES stand between a bfloat16 program and this reference.
Routing: as in `mla_moe.py`, the program records every token's picks and the
reference FOLLOWS them where they are, by its own float32 scores, picks the
rule could have made to within `ROUTING_TOL` (every pick's s + b at least (1
- tol) of the k-th best); a request more than `FOLLOW_MAX` of whose
token-layers had to be followed is judged on the reference's own routing.
Selection: one key more or less of 2,048 moves a logit by less than the
arithmetic's noise (attention under seeded weights is near uniform), so a
program that selected WRONG keys would pass a comparison of logits. The
program therefore records the positions a sample of its queries selected, in
every layer (`serving.latent.RoutingTrace.selections`: eight queries of
each prefill window, one row of every eighth decode step), and the reference
checks each against its own float32 scores: a position the program selected
and the reference did not (or the other way round) is a DISPUTE, and its
shortfall is how far its score lies on the wrong side of the reference's
index_topk-th, in standard deviations of the query's visible scores. A
request is NOT CORRECT where a shortfall passes `SELECT_TOL`, where more than
`SELECT_DISPUTE_MAX` of its sampled picks are disputes, or where a sampled
query selected another count of positions than min(t + 1, index_topk): its
served tokens' reference logits are then lowered by `SELECT_PENALTY`, so
that the comparison's widest gap reads it. The reference's own forward
always attends its own selection.

Nothing here is the program's but `serve_args`: `jax.numpy`, float32, matmul
precision `highest`, no kernel, no cache, no batching; an expert held is
computed for the tokens that picked it and weighed. Every weight goes
through `mm` (the control swaps it for fp8).
"""

import functools
import importlib.util
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import weights
from benchmarks.harness.reference import (HIGHEST, f32_mm, pad_rows,
                                          rms_norm, served_rows)

_spec = importlib.util.spec_from_file_location(
    "bench_family_mla_dsa_moe_equations",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "mla_moe.py"))
_eq = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_eq)

DENSE, EXPERT = "dense_layers", "layers"
T_BLOCK = 1024      # tokens a projection / feed-forward call takes
Q_BLOCK = 128       # queries attended at once: [Q_BLOCK, index_topk] rows
K_BUCKETS = (32768, 73728)  # a query block scores its keys padded up to the
                            # next of these counts: two programs a function,
                            # whatever the requests' lengths (a cold run
                            # compiles them inside the 360 s)
INDEX_NORM_EPS = 1e-6
REQUEST_RECORD = "routing"      # `serving.latent.RoutingTrace`: the picks
                                # and the sampled selections
# The four limits of TWO DISCRETE CHOICES above, set from chip runs at the
# cell's size (PERF.md section 6, PR 41: 7 sound runs on 7 seeds, and the
# reference in fp8 on one): a pick's shortfall reads at most 0.093 of a score
# sound and 0.51 in fp8; the sigmoid rule's best scores lie close together,
# so 45-63% of a sound request's token-layers are followed (74% in fp8: the
# share tells little here, the shortfall tells); the widest selection
# shortfall reads 1.07-1.33 spreads sound (a hidden state that differs by a
# selection upstream: layer 0 disputes 0.4% of its picks, layers 1-4 11-13%)
# and 3.3-4.1 in fp8; a request's disputed picks 10.4-10.6% sound, 26-39% in
# fp8
ROUTING_TOL = 0.25
FOLLOW_MAX = 0.9
SELECT_TOL = 2.5
SELECT_DISPUTE_MAX = 0.17
SELECT_PENALTY = 100.0


# -- the program's side: imported here and nowhere in the reference ----------

def serve_args(arch):
    from paddle_tpu.models import latent_moe_functional as lm

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
        routed_experts=_eq.router_width(arch), first_expert=first,
        experts_held=held, n_group=arch["n_group"],
        topk_group=arch["topk_group"],
        experts_per_tok=arch["num_experts_per_tok"],
        routed_scaling=float(arch["routed_scaling_factor"]),
        first_k_dense=arch["first_k_dense_replace"],
        rope_theta=float(rope_theta(arch)), rms_eps=arch["rms_norm_eps"],
        yarn=None, record_routing=True,
        indexer=lm.IndexerConfig(
            heads=arch["index_n_heads"], dim=arch["index_head_dim"],
            topk=arch["index_topk"], norm_eps=INDEX_NORM_EPS),
        scoring=arch["scoring_func"], norm_topk=bool(arch["norm_topk_prob"]),
        record_selection=True)


def rope_theta(arch):
    return arch["rope_parameters"]["rope_theta"]


def experts_held(arch):
    """(first, count): the experts this chip holds."""
    dep = arch.get("deployment")
    first = dep.get("first_expert_held", 0) if isinstance(dep, dict) else 0
    return first, arch["n_routed_experts"]


# -- the weights ---------------------------------------------------------------

def layer_kinds(arch):
    kd = arch["first_k_dense_replace"]
    return [DENSE] * kd + [EXPERT] * (arch["num_hidden_layers"] - kd)


def _indexer_shapes(arch):
    h, J, d = arch["hidden_size"], arch["index_n_heads"], arch["index_head_dim"]
    return {"w_iq": (arch["q_lora_rank"], J * d), "w_ik": (h, d),
            "ik_norm": (d,), "ik_bias": (d,), "w_iw": (h, J)}


def layer_shapes(arch):
    both = dict(_eq._attention_shapes(arch), **_indexer_shapes(arch))
    expert = {k: v for k, v in _eq.layer_shapes(arch).items()
              if k not in both}
    dense = {k: v for k, v in _eq.dense_layer_shapes(arch).items()
             if k not in both}
    return {DENSE: dict(both, **dense),
            EXPERT: dict(both, router_bias=(_eq.router_width(arch),),
                         **expert)}


def leaf_init(arch):
    """The two biases start at zero mean (a 1-D leaf is a norm weight, 1 +
    0.05 normal, by the harness's rule); the matrices' init, and the router
    bias's spread, where the configuration states one (the tests' toy
    presets do)."""
    std = arch.get("initializer_range")
    out = {}
    for kind, shapes in layer_shapes(arch).items():
        out[kind] = {name: (0.0, std) for name, shape in shapes.items()
                     if std is not None and len(shape) >= 2}
        out[kind]["ik_bias"] = (0.0, 0.02)
        if kind == EXPERT:
            out[kind]["router_bias"] = (0.0, arch.get("router_bias_std",
                                                      0.01))
    return out


def param_count(arch):
    """Parameters of the configuration as it is run: the layers by kind,
    embedding, final norm and head."""
    per_kind = {kind: sum(math.prod(s) for s in shapes.values())
                for kind, shapes in layer_shapes(arch).items()}
    outer = 2 * arch["vocab_size"] * arch["hidden_size"] + arch["hidden_size"]
    return sum(per_kind[k] for k in layer_kinds(arch)) + outer


def pool_bytes(arch, tokens, itemsize=2):
    """(latent pool, index pool) bytes for `tokens` cached positions: a
    token keeps a layer one latent row, laid out in whole 128-lane tiles,
    and one index key."""
    lanes = -(-(arch["kv_lora_rank"] + arch["qk_rope_head_dim"]) // 128) * 128
    L = arch["num_hidden_layers"]
    return (tokens * L * lanes * itemsize,
            tokens * L * arch["index_head_dim"] * itemsize)


# -- the plain layer -------------------------------------------------------------

def _plain(arch):
    """`arch` as `mla_moe.py`'s functions read it: a rotary scaling of factor
    1 (plain positions, multiplier 1, scale (nope + rope)^-1/2)."""
    return dict(arch, rope_theta=rope_theta(arch), rope_scaling={
        "factor": 1.0, "mscale": 1.0, "mscale_all_dim": 1.0,
        "beta_fast": 32, "beta_slow": 1,
        "original_max_position_embeddings": arch["max_position_embeddings"]})


def cached(x, w, arch, mm, pos):
    """x [s, h] at positions pos -> what a token leaves for the queries
    after it, and its own query latent: c_q [s, q_rank], c_kv [s, kv_rank],
    k_pe [s, rope] (rotated), kI [s, d] (normed, rotated), wI [s, J]."""
    eps, kr, r = arch["rms_norm_eps"], arch["kv_lora_rank"], arch[
        "qk_rope_head_dim"]
    J, d = arch["index_n_heads"], arch["index_head_dim"]
    hin = rms_norm(x, w["ln1"], eps)
    c_q = rms_norm(mm(hin, w["w_qa"]), w["q_norm"], eps)
    kv = mm(hin, w["w_kva"])
    c_kv = rms_norm(kv[:, :kr], w["kv_norm"], eps)
    k_pe = _eq.rotary(kv[:, None, kr:], pos, _plain(arch))[:, 0]
    ki = mm(hin, w["w_ik"])
    ki = ki - jnp.mean(ki, -1, keepdims=True)
    ki = ki * jax.lax.rsqrt(jnp.mean(ki * ki, -1, keepdims=True)
                            + INDEX_NORM_EPS) * w["ik_norm"] + w["ik_bias"]
    ki = jnp.concatenate(
        [_eq.rotary(ki[:, None, :r], pos, _plain(arch))[:, 0], ki[:, r:]], -1)
    wi = mm(hin, w["w_iw"]) * (J ** -0.5 * d ** -0.5)
    return c_q, c_kv, k_pe, ki, wi


def index_scores(c_q, wi, qpos, ki, w, arch, mm):
    """I(t, s) for the queries of latents c_q [n, q_rank] and head weights wI
    [n, J] at positions qpos [n] against the index keys kI [m, d] of
    positions 0 .. m - 1: float32 [n, m], -inf where s > t."""
    J, d, r = arch["index_n_heads"], arch["index_head_dim"], arch[
        "qk_rope_head_dim"]
    qi = mm(c_q, w["w_iq"]).reshape(-1, J, d)
    qi = jnp.concatenate(
        [_eq.rotary(qi[..., :r], qpos, _plain(arch)), qi[..., r:]], -1)
    dots = jnp.einsum("njd,md->njm", qi, ki, precision=HIGHEST)
    sc = jnp.sum(wi[:, :, None] * jax.nn.relu(dots), axis=1)
    see = jnp.arange(ki.shape[0])[None, :] <= qpos[:, None]
    return jnp.where(see, sc, -jnp.inf)


def attend(x, c_q, wi, qpos, cached_rows, ki, w, arch, mm):
    """The queries at positions qpos [n] (inputs x [n, h], latents c_q, head
    weights wI) over the tokens 0 .. m - 1 (every qpos < m; `cached_rows`
    [m, kv_rank + rope] their [c_kv; k_pe]): the selection, the attention
    over it in the latent space (the module's head says why), the output
    projection and the residual. Returns x + Attn [n, h]."""
    H, nope, v = (arch["num_attention_heads"], arch["qk_nope_head_dim"],
                  arch["v_head_dim"])
    kr, k = arch["kv_lora_rank"], min(arch["index_topk"], ki.shape[0])
    vals, sel = jax.lax.top_k(index_scores(c_q, wi, qpos, ki, w, arch, mm), k)
    q = mm(c_q, w["w_qb"]).reshape(-1, H, nope + arch["qk_rope_head_dim"])
    q_nope = q[..., :nope]
    q_pe = _eq.rotary(q[..., nope:], qpos, _plain(arch))
    w_kvb = w["w_kvb"].reshape(kr, H, nope + v)
    q_lat = jnp.einsum("nhd,chd->nhc", q_nope, w_kvb[..., :nope],
                       precision=HIGHEST)
    picked = cached_rows[sel]                              # [n, k, ..]
    c_sel, r_sel = picked[..., :kr], picked[..., kr:]
    sc = (jnp.einsum("nhc,nkc->nhk", q_lat, c_sel, precision=HIGHEST)
          + jnp.einsum("nhr,nkr->nhk", q_pe, r_sel, precision=HIGHEST))
    p = jax.nn.softmax(jnp.where(
        (vals > -jnp.inf)[:, None, :], sc * _eq.attention_scale(_plain(arch)),
        -jnp.inf), axis=-1)
    o_lat = jnp.einsum("nhk,nkc->nhc", p, c_sel, precision=HIGHEST)
    o = jnp.einsum("nhc,chd->nhd", o_lat, w_kvb[..., nope:],
                   precision=HIGHEST)
    return x + mm(o.reshape(o.shape[0], -1), w["wo"])


def routing_noting(h, w, arch, mm, given=None):
    """h [s, h] -> (the weight of every PUBLISHED expert for every token [s,
    published]: 0 where it was not picked; notes [s, 3]). `given` [s, k]
    (-1: none): the picks a served program recorded, followed where each
    one's s + b is at least (1 - ROUTING_TOL) of this reference's k-th best.
    A token's notes: 1 where its recorded picks are not this reference's
    own, 1 where such picks were followed, and the widest shortfall as a
    share of that k-th best (1: picks no rule could make)."""
    n, k = _eq.router_width(arch), arch["num_experts_per_tok"]
    scores = jax.nn.sigmoid(mm(h, w["router"]))
    choice = scores + w["router_bias"]
    e = jnp.arange(n)
    beats = (choice[:, None, :] > choice[:, :, None]) | (
        (choice[:, None, :] == choice[:, :, None])
        & (e[None, None, :] < e[None, :, None]))
    picked = jnp.sum(beats, axis=-1) < k
    notes = jnp.zeros((h.shape[0], 3), jnp.float32)
    if given is not None:
        took = jnp.any(given[:, :, None] == e[None, None, :], axis=1)
        last = -jnp.sort(-choice, axis=-1)[:, k - 1]
        short = jnp.max(jnp.where(
            took, 1 - choice / jnp.maximum(last[:, None], 1e-20), 0.0), -1)
        short = jnp.where(jnp.sum(took, axis=-1) == k, short, 1.0)
        recorded = jnp.any(given >= 0, axis=-1)
        differs = recorded & jnp.any(took != picked, axis=-1)
        follow = recorded & (short <= ROUTING_TOL)
        picked = jnp.where(follow[:, None], took, picked)
        notes = jnp.stack([differs, differs & follow,
                           jnp.where(differs, short, 0.0)],
                          axis=-1).astype(jnp.float32)
    weigh = jnp.where(picked, scores, 0.0)
    if arch["norm_topk_prob"]:
        weigh = weigh / (jnp.sum(weigh, axis=-1, keepdims=True) + 1e-20)
    return weigh * arch["routed_scaling_factor"], notes


def finish_noting(x, w, arch, mm, dense, given=None):
    """The feed-forward of the layer's kind on top of x [s, h] (attention
    and its residual already in), and the routing's notes."""
    h = rms_norm(x, w["ln2"], arch["rms_norm_eps"])
    if dense:
        return (x + _eq.swiglu(h, w["w_gate"], w["w_up"], w["w_down"], mm),
                jnp.zeros((x.shape[0], 3), jnp.float32))
    first, held = experts_held(arch)
    weigh, notes = routing_noting(h, w, arch, mm, given)
    weigh = weigh[:, first:first + held]

    # an expert is computed for the tokens that picked it (a token picks 8
    # of 256 and 16 are held: 3% of the rows; computed for every row the
    # held experts were 70% of a check's matmuls), up to a sixteenth of the
    # rows; an expert more tokens than that picked is computed for all
    cap = max(1, h.shape[0] // 16)

    def one(acc, xs):
        w_gate, w_up, w_down, we = xs

        def every(acc):
            return acc + we[:, None] * _eq.swiglu(h, w_gate, w_up, w_down,
                                                  mm)

        def its_own(acc):
            rows = jnp.nonzero(we != 0, size=cap, fill_value=0)[0]
            real = jnp.arange(cap) < jnp.sum(we != 0)
            out = we[rows][:, None] * _eq.swiglu(h[rows], w_gate, w_up,
                                                 w_down, mm)
            return acc.at[rows].add(jnp.where(real[:, None], out, 0.0))

        return jax.lax.cond(jnp.sum(we != 0) > cap, every, its_own, acc), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        w["we_gate"], w["we_up"], w["we_down"], weigh.T))
    return x + _eq.shared_experts(h, w, arch, mm) + routed, notes


def decoder_layer(x, w, arch, mm, kind):
    """One whole layer over sequences x [b, s, h] from position 0."""
    def one(x1):
        pos = jnp.arange(x1.shape[0])
        c_q, c_kv, k_pe, ki, wi = cached(x1, w, arch, mm, pos)
        x1 = attend(x1, c_q, wi, pos, jnp.concatenate([c_kv, k_pe], -1), ki,
                    w, arch, mm)
        return finish_noting(x1, w, arch, mm, kind == DENSE)[0]

    return jax.lax.map(one, x)


# -- a served model: logits at the served positions ----------------------------

_KEYS = ("num_attention_heads", "q_lora_rank", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "rms_norm_eps", "max_position_embeddings",
         "n_routed_experts", "num_experts_per_tok", "routed_scaling_factor",
         "norm_topk_prob", "index_n_heads", "index_head_dim", "index_topk")


def _frozen(arch):
    first, _ = experts_held(arch)
    return tuple((k, arch[k]) for k in _KEYS) + (
        ("router_width", _eq.router_width(arch)), ("first", first),
        ("rope_theta", rope_theta(arch)))


def _thaw(frozen):
    arch = dict(frozen)
    arch["published"] = {"n_routed_experts": arch.pop("router_width")}
    arch["deployment"] = {"first_expert_held": arch.pop("first")}
    arch["rope_parameters"] = {"rope_theta": arch.pop("rope_theta")}
    return arch


def _f32(w):
    return jax.tree.map(lambda a: a.astype(jnp.float32), w)


@functools.lru_cache(maxsize=None)
def _cached_fn(frozen, mm):
    arch = _thaw(frozen)
    return jax.jit(lambda x, w, pos: cached(x, _f32(w), arch, mm, pos))


@functools.lru_cache(maxsize=None)
def _attend_fn(frozen, mm):
    arch = _thaw(frozen)
    return jax.jit(lambda x, c_q, wi, qpos, cached_rows, ki, w: attend(
        x, c_q, wi, qpos, cached_rows, ki, _f32(w), arch, mm))


@functools.lru_cache(maxsize=None)
def _scores_fn(frozen, mm):
    arch = _thaw(frozen)
    return jax.jit(lambda c_q, wi, qpos, ki, w: index_scores(
        c_q, wi, qpos, ki, _f32(w), arch, mm))


@functools.lru_cache(maxsize=None)
def _finish_fn(frozen, mm, dense):
    arch = _thaw(frozen)
    return jax.jit(lambda x, w, given: finish_noting(
        x, _f32(w), arch, mm, dense, given))


class _Clock:
    """Where the reference's seconds go, by part, over a whole check (the
    device is waited for at every split: the parts run one after the
    other anyway)."""

    spent = {}

    def __init__(self):
        self.at = time.perf_counter()

    def split(self, part, *arrays):
        jax.block_until_ready(arrays)
        now = time.perf_counter()
        _Clock.spent[part] = _Clock.spent.get(part, 0.0) + now - self.at
        self.at = now


def _blocks(n, size):
    return [(a, min(a + size, n)) for a in range(0, n, size)]


def layer_forward(x, w, arch, mm, kind, given=None, notes=None, sampled=None,
                  queries_from=0):
    """One layer over one sequence x [s, h] (s a multiple of the token
    block), block by block: every jitted call has one of a few fixed shapes
    whatever the sequence's length. `given` [s, k]: the picks a served
    program recorded for this layer (-1: none; `routing_noting`), whose
    notes [s, 3] are appended to the list `notes`. `sampled`: positions
    whose index scores [len(sampled), s] (this reference's, float32) are
    returned beside the layer's output, for `judge_selection`.
    `queries_from`: the first position whose output anyone reads (the last
    layer's rows before the served positions feed nothing: every position
    still leaves its keys, the blocks before this one are not attended and
    keep their input)."""
    fz, s = _frozen(arch), x.shape[0]
    tb, qb = min(T_BLOCK, s), min(Q_BLOCK, s)
    clock = _Clock()
    parts = [_cached_fn(fz, mm)(x[a:b], w, jnp.arange(a, b))
             for a, b in _blocks(s, tb)]
    c_q, c_kv, k_pe, ki, wi = (jnp.concatenate(p) for p in zip(*parts))
    cached_rows = jnp.concatenate([c_kv, k_pe], axis=-1)    # one gather
    del c_kv, k_pe
    # rows past a query's position are never read: the key counts are the
    # buckets' alone, whatever the request's own length (a sequence past the
    # last bucket, or within one token block, is attended at its own length)
    buckets = [n for n in K_BUCKETS if n >= s] if s > T_BLOCK else []
    if buckets:
        cached_rows = pad_rows(buckets[0], cached_rows, ki)
        cached_rows, ki = cached_rows
    scores = None
    if sampled is not None and len(sampled):
        at = jnp.asarray(sampled)
        clock.split("keys", ki)
        # every sampled query against the whole padded sequence: one
        # program a key count of its own would be another compilation
        scores = np.asarray(_scores_fn(fz, mm)(
            c_q[at], wi[at], at, pad_rows(K_BUCKETS[-1], ki)[0] if buckets
            else ki, w))[:, :s]
        clock.split("sampled scores")
    first = queries_from // tb * tb         # whole token blocks are skipped
    outs = [x[:first]]
    for a, b in _blocks(s, qb):
        if a >= first:
            m = next((n for n in K_BUCKETS if n >= b and buckets), len(ki))
            outs.append(_attend_fn(fz, mm)(
                x[a:b], c_q[a:b], wi[a:b], jnp.arange(a, b), cached_rows[:m],
                ki[:m], w))
    x = jnp.concatenate(outs)
    del outs, c_q, cached_rows, ki, wi
    clock.split("attention", x)
    fin = _finish_fn(fz, mm, kind == DENSE)
    if given is None:
        given = np.full((s, arch["num_experts_per_tok"]), -1, np.int32)
    outs = [fin(x[a:b], w, jnp.asarray(given[a:b]))
            for a, b in _blocks(s, tb) if a >= first]
    if notes is not None:
        notes.append(np.concatenate(
            [np.zeros((first, 3), np.float32)]
            + [np.asarray(n) for _, n in outs]))
    x = jnp.concatenate([x[:first]] + [y for y, _ in outs])
    clock.split("feed-forward", x)
    return x, scores


def judge_selection(scores, position, selected, topk):
    """One sampled query of one layer: this reference's scores of its
    visible keys `scores` [position + 1], the positions the program selected
    `selected` [n]. Returns (picks, disputes, the widest shortfall): a
    dispute is a position on one side's list alone, its shortfall how far
    its score lies on the wrong side of the reference's last pick, in
    standard deviations of the visible scores; a list of another length
    than min(position + 1, index_topk), or with a position twice or out of
    sight, is every pick a dispute at SELECT_PENALTY."""
    n = min(position + 1, topk)
    # the n largest, ties to the lower position: everything above the n-th
    # largest value, then the first at that value
    last = np.partition(scores, len(scores) - n)[len(scores) - n]
    above, at = np.nonzero(scores > last)[0], np.nonzero(scores == last)[0]
    mine = np.concatenate([above, at[:n - len(above)]])
    theirs = np.unique(selected)
    if len(selected) != n or len(theirs) != n or theirs[-1] > position \
            or theirs[0] < 0:
        return n, n, SELECT_PENALTY
    extra = np.setdiff1d(theirs, mine)
    if not len(extra):
        return n, 0, 0.0
    missing = np.setdiff1d(mine, theirs)
    spread = max(float(np.std(scores)), 1e-30)
    short = max(float(np.max(last - scores[extra])),
                float(np.max(scores[missing] - last))) / spread
    return n, len(extra), short


def _embedded(ids, embedding):
    """ids [n] -> their embeddings [size, h], float32, padded to whole token
    blocks."""
    n = len(ids)
    tb = T_BLOCK if n > T_BLOCK else -(-n // 8) * 8
    padded = np.zeros(-(-n // tb) * tb, np.int32)
    padded[:n] = ids
    return embedding[jnp.asarray(padded)].astype(jnp.float32)


def forward_hidden(arch, ids, weights_of, embedding, mm=f32_mm, picks=None,
                   notes=None, selections=None, found=None, read_from=0):
    """ids [n] -> the last layer's output [n, h]: the whole model's plain
    forward over one sequence. `weights_of(i)` gives layer i's leaves;
    `picks` [n, expert layers, k]: a served program's recorded routing;
    `notes`: a list that gets each expert layer's routing notes [n, 3];
    `selections` [(position, [layers, n_selected])]: the program's sampled
    selections, each judged against this forward's own scores into the list
    `found` as (layer, picks, disputes, shortfall). `read_from`: the first
    position of the result that the caller reads (the LAST layer computes
    from its token block on; the rows before it are not the model's)."""
    x, n = _embedded(ids, embedding), len(ids)
    kd = arch["first_k_dense_replace"]
    sampled = [t for t, _ in selections] if selections else None
    kinds = layer_kinds(arch)
    for i, kind in enumerate(kinds):
        given, noted = None, None
        if picks is not None and i >= kd:
            given = np.full((x.shape[0], picks.shape[-1]), -1, np.int32)
            given[:n] = picks[:, i - kd]
            noted = [] if notes is not None else None
        x, scores = layer_forward(
            x, weights_of(i), arch, mm, kind, given, noted, sampled,
            read_from if i == len(kinds) - 1 else 0)
        if noted:
            notes.append(noted[0][:n])
        if scores is not None:
            clock = _Clock()
            found.extend((i,) + judge_selection(scores[j, :t + 1], t, sel[i],
                                                arch["index_topk"])
                         for j, (t, sel) in enumerate(selections))
            clock.split("judging")
    return x[:n]


def head_logits(arch, x, final_norm, lm_head, mm=f32_mm):
    return _eq.head_logits(arch, x, final_norm, lm_head, mm)


def served_logits(arch, seed, requests, mm=f32_mm):
    """For each (prompt, tokens, record) of `requests`, the reference logits
    [len(tokens), vocab] (the chip's slice of the vocabulary) at the
    positions where the server chose `tokens` after `prompt`: the routing
    the server recorded followed where it stands the check (`routing_noting`;
    a request more than FOLLOW_MAX followed runs again on the reference's
    own routing), the selection always this reference's own, and the
    server's sampled selections judged against it (`judge_selection`): a
    request they fail has its served tokens' logits lowered by
    SELECT_PENALTY. One request after the other (a layer's weights are made
    from the seed again for each: 0.1 s). Prints what both checks found."""
    outer = weights.outer_params(arch, seed)
    family = _family()
    _Clock.spent = {}

    def weights_of(i):
        return weights.layer_params(family, arch, seed, i)

    out, routed, chosen, shorts = [], np.zeros(6), np.zeros(5), []
    by_layer = np.zeros((arch["num_hidden_layers"], 2))
    for prompt, tokens, *record in requests:
        seq = np.concatenate([np.asarray(prompt), np.asarray(tokens)[:-1]])
        trace = record[0] if record else None
        table = trace.table(len(seq)) if trace is not None else None
        picked = trace.selections(len(seq)) if trace is not None else None
        notes, found = ([] if table is not None else None), []
        x = forward_hidden(arch, seq, weights_of, outer["embedding"], mm,
                           table, notes, picked, found, len(prompt) - 1)
        if table is not None:
            recorded = int(np.sum(table[:, :, 0] >= 0))
            differ, followed, _ = np.sum(notes, axis=(0, 1))
            own = followed > FOLLOW_MAX * recorded
            if own:
                x = forward_hidden(arch, seq, weights_of, outer["embedding"],
                                   mm, read_from=len(prompt) - 1)
            routed[:4] += [recorded, differ, followed, own]
            routed[4] = max(routed[4], np.max(np.asarray(notes)[:, :, 2]))
            routed[5] = max(routed[5], followed / max(recorded, 1))
        logits = served_rows(
            lambda rows: head_logits(arch, rows, outer["final_norm"],
                                     outer["lm_head"], mm),
            x, len(prompt), len(tokens))
        if found:
            layer, picks, disputes, short = (np.asarray(c)
                                             for c in zip(*found))
            for i in range(len(by_layer)):
                by_layer[i] += [picks[layer == i].sum(),
                                disputes[layer == i].sum()]
            share = disputes.sum() / max(picks.sum(), 1)
            bad = short.max() > SELECT_TOL or share > SELECT_DISPUTE_MAX
            chosen[:2] += [len(found), bad]
            shorts.extend(short[short > 0])
            chosen[2] = max(chosen[2], short.max())
            chosen[3] = max(chosen[3], share)
            chosen[4] = len(found) if not chosen[4] else min(chosen[4],
                                                             len(found))
            if bad:
                logits = np.array(logits)
                gap = logits.max(-1) - logits[np.arange(len(tokens)),
                                              np.asarray(tokens)]
                print(f"correct: refused: a request of {len(prompt)} + "
                      f"{len(tokens)} tokens is NOT correct (shortfall "
                      f"{short.max():.4f}, disputed picks {share:.4%}); its "
                      f"gaps before the penalty: widest {gap.max():.5f} "
                      f"mean {gap.mean():.6f}", flush=True)
                logits[np.arange(len(tokens)), np.asarray(tokens)] -= \
                    SELECT_PENALTY
        out.append(logits)
    print("correct: the reference's seconds by part: " + ", ".join(
        f"{part} {seconds:.1f}" for part, seconds in _Clock.spent.items()),
        flush=True)
    if routed[0]:
        recorded, differ, followed, own, short, most = routed
        print(f"correct: routing: {int(recorded)} token-layers recorded, "
              f"picks not the reference's own in {differ / recorded:.4%}, "
              f"followed {followed / recorded:.4%}, refused "
              f"{(differ - followed) / recorded:.4%}, the widest shortfall "
              f"{short:.4f} of a score (followed up to {ROUTING_TOL}); "
              f"the most a request had followed {most:.4%}; {int(own)} "
              f"request(s) past {FOLLOW_MAX:.0%} followed and judged on the "
              f"reference's own routing", flush=True)
    if chosen[0]:
        print(f"correct: selection: {int(chosen[0])} sampled (query, layer) "
              f"pairs, at least {int(chosen[4])} a request, {len(shorts)} "
              f"with a dispute (their shortfalls' median "
              f"{np.median(shorts or [0]):.4f}, 99th percentile "
              f"{np.percentile(shorts or [0], 99):.4f}); the widest "
              f"shortfall {chosen[2]:.4f} of a spread (limit {SELECT_TOL}); "
              f"disputed picks by layer "
              f"{[round(100 * d / max(p, 1), 2) for p, d in by_layer]}%; "
              f"the most disputed picks of a request {chosen[3]:.4%} (limit "
              f"{SELECT_DISPUTE_MAX:.0%}); {int(chosen[1])} request(s) NOT "
              f"correct by their selection", flush=True)
    return out


class _family:
    """What `weights` asks of a family (this file is loaded by its path and
    is in no `sys.modules`)."""

    layer_kinds = staticmethod(layer_kinds)
    layer_shapes = staticmethod(layer_shapes)
    leaf_init = staticmethod(leaf_init)


# -- the counts of this family's readers ---------------------------------------

def index_key_bytes(arch, itemsize=2):
    """What the selector reads of one visible key a layer."""
    return arch["index_head_dim"] * itemsize


def index_flops_per_pair(arch):
    """One (query, key) pair's index score: every index head's dot."""
    return 2 * arch["index_n_heads"] * arch["index_head_dim"]


def _traced_queries(ctx):
    """([(lo, hi)] the prefill windows, [visible keys] of each decode token)
    of the traced slice. None where the windows cannot be rebuilt."""
    windows = _eq.prefill_windows(ctx)
    if windows is None:
        return None
    t0, t1 = ctx.trace_host_window
    return ([(lo, hi) for a, lo, hi in windows if t0 <= a < t1],
            [len(r.prompt) + j for r in ctx.run.recs.values()
             for j, t in enumerate(r.times) if j and t0 <= t < t1])


def index_work(ctx):
    """The least seconds the chip could take for the index scores the
    EQUATIONS need in the traced slice, a layer times the layers: a decode
    token the larger of its visible keys' bytes over the bandwidth and their
    pairs' flops over the bf16 peak; a prefill window the larger of its
    context's keys' bytes (read once for all its queries) and its (query,
    visible key) pairs' flops."""
    if not ctx.trace or ctx.peaks is None:
        return None
    seen = _traced_queries(ctx)
    if seen is None:
        return None
    arch = ctx.arch
    flops, bw = ctx.peaks["bf16_flops"], ctx.peaks["hbm_bytes_per_s"]
    per_key = max(index_key_bytes(arch) / bw,
                  index_flops_per_pair(arch) / flops)
    need = sum(keys * per_key for keys in seen[1])
    for lo, hi in seen[0]:
        pairs = (lo + 1 + hi) * (hi - lo) / 2              # sum of t + 1
        need += max(hi * index_key_bytes(arch) / bw,
                    pairs * index_flops_per_pair(arch) / flops)
    return arch["num_hidden_layers"] * need


def traced_work(ctx):
    """The least seconds the chip could take for what the EQUATIONS need in
    the traced slice, {"experts": s, "latent": s}: `mla_moe.py`'s counts
    with the attention's pairs and bytes those of the SELECTED keys (a
    query sees min(t + 1, index_topk) of them): a decode token in the
    absorbed form (a row's bytes a selected key), a prefill window in the
    form the equations state."""
    need = _eq.traced_work(ctx)
    seen = _traced_queries(ctx) if need else None
    if seen is None:
        return None
    arch, k = ctx.arch, ctx.arch["index_topk"]
    flops, bw = ctx.peaks["bf16_flops"], ctx.peaks["hbm_bytes_per_s"]
    per_key = max(_eq.row_bytes(arch) / bw,
                  _eq.latent_decode_flops_per_pair(arch) / flops)
    latent = sum(min(keys, k) * per_key for keys in seen[1])
    for lo, hi in seen[0]:
        pairs = sum(min(t + 1, k) for t in range(lo, hi))
        latent += pairs * _eq.latent_prefill_flops_per_pair(arch) / flops
    need["latent"] = arch["num_hidden_layers"] * latent
    return need
