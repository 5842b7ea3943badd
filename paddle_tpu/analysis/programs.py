"""Builders that trace the REAL program families at toy size.

The audit's whole value is that it inspects the programs production
actually runs — not idealized stand-ins. Each builder here constructs
the genuine code path (HybridParallelEngine.build_train_step, the
PagedEngine's compiled step dict, fused_linear_cross_entropy,
adamw_update) at a CPU-friendly toy size and returns `AuditProgram`
records carrying the jaxpr (for walker rules) and the lowered MLIR (for
the donation rule).

Serving programs are captured, not reconstructed: the engine's jitted
step callables are wrapped with a recorder, a couple of tiny requests
are served, and the recorded example arguments re-trace the exact
program objects the scheduler dispatched. A signature change in the
engine therefore can't silently diverge from what the audit inspects.

Everything is memoized per process — tests and tools/lint.py share one
build.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["AuditProgram", "TOY", "toy_args", "fused_ce_programs",
           "train_step_program", "opt_writeback_program",
           "serving_programs", "latent_serving_programs",
           "delta_serving_programs", "disagg_programs"]

# one toy geometry for every family: 2 layers, divisible by a degree-2
# TP mesh (heads, kv heads, intermediate), tiny enough that every build
# in this module traces in seconds on CPU. intermediate_size must NOT
# equal vocab_size or the forbidden-(b,s,vocab) probe would false-flag
# the MLP intermediates.
TOY = dict(vocab_size=64, hidden_size=32, intermediate_size=48,
           num_layers=2, num_heads=2, num_kv_heads=2)
TOY_BATCH, TOY_SEQ, TOY_CHUNK = 2, 16, 8


@dataclasses.dataclass
class AuditProgram:
    """One traced program, ready for rules: jaxpr for walker rules,
    lowered MLIR text + example args + donated argnums for the donation
    rule, meta for program-specific context (forbidden shapes, mesh)."""

    name: str
    jaxpr: object                       # ClosedJaxpr
    lowered_text: str | None = None
    example_args: tuple = ()
    donated: tuple = ()
    # kept_var_idx of the lowering (None = no pruning) and, for SPMD
    # programs, the compiled-HLO text where the resolved aliases live
    kept: frozenset | None = None
    compiled_text: str | None = None
    meta: dict = dataclasses.field(default_factory=dict)


def toy_args(**overrides):
    from paddle_tpu.models import llama_functional as lf

    kw = dict(TOY, **overrides)
    return lf.LlamaArgs(rope_theta=10000.0, rms_eps=1e-6, use_flash=False,
                        **kw)


def _sds(x):
    return jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x))


def _sds_tree(tree):
    return jax.tree_util.tree_map(_sds, tree)


def _from_traced(name, traced, example_args, donated, meta=None):
    """AuditProgram from a jit Traced: jaxpr + lowered MLIR, plus the
    lowering's kept_var_idx (unused-arg pruning shifts flat indices) and
    — when donation is requested but the StableHLO only carries the
    jax.buffer_donor mark (SPMD lowerings) — the compiled HLO text,
    where the resolved input_output_alias header lives."""
    lowered = traced.lower()
    text = lowered.as_text()
    kept = None
    try:
        kv = lowered._lowering.compile_args.get("kept_var_idx")
        if kv is not None:
            kept = frozenset(kv)
    except AttributeError:
        pass
    compiled_text = None
    if donated and "tf.aliasing_output" not in text:
        compiled_text = lowered.compile().as_text()
    return AuditProgram(
        name, traced.jaxpr, lowered_text=text, example_args=example_args,
        donated=donated, kept=kept, compiled_text=compiled_text,
        meta=dict(meta or {}))


class _Recorder:
    """Wrap a jitted callable; record the first call's args as
    ShapeDtypeStructs so the exact program can be re-traced for audit.
    Keyword args (the engines only pass static ones, e.g. the GPT
    programs' `sample=`) are kept verbatim and replayed at trace time."""

    def __init__(self, jitted):
        self.jitted = jitted
        self.args = None
        self.kwargs = {}

    def __call__(self, *a, **k):
        if self.args is None:
            self.args = tuple(_sds_tree(x) for x in a)
            self.kwargs = dict(k)
        return self.jitted(*a, **k)

    def trace(self):
        if self.args is None:
            return None
        return self.jitted.trace(*self.args, **self.kwargs)


@functools.lru_cache(maxsize=None)
def fused_ce_programs():
    """Fused-CE fwd+bwd (the no-[b,s,vocab] family) AND the unchunked
    reference — the reference is the teeth check: it MUST trip the
    forbidden-shape rule or the probe has silently gone blind."""
    from paddle_tpu.models import llama_functional as lf

    args = toy_args()
    b, s, chunk = TOY_BATCH, TOY_SEQ, TOY_CHUNK
    kh, kw, kl = jax.random.split(jax.random.PRNGKey(0), 3)
    h = jax.random.normal(kh, (b, s, args.hidden_size)) * 0.5
    head = jax.random.normal(kw, (args.hidden_size, args.vocab_size)) * 0.05
    labels = jax.random.randint(kl, (b, s), 0, args.vocab_size)

    fused = jax.make_jaxpr(jax.value_and_grad(
        lambda a, w: lf.fused_linear_cross_entropy(
            a, w, labels, args, None, 1, chunk), argnums=(0, 1)))(h, head)
    ref = jax.make_jaxpr(jax.value_and_grad(
        lambda a, w: lf.parallel_cross_entropy(a @ w, labels, args,
                                               None, 1),
        argnums=(0, 1)))(h, head)
    bsv = (b, s, args.vocab_size)
    return (AuditProgram("fused_ce_fwd_bwd", fused,
                         meta={"forbidden_shape": bsv,
                               "forbidden_carry": head.shape}),
            AuditProgram("unchunked_ce_reference", ref,
                         meta={"forbidden_shape": bsv}))


@functools.lru_cache(maxsize=None)
def train_step_program(dtype_name="bfloat16"):
    """The hybrid engine's REAL compiled train step (trivial 1x1x1 mesh —
    the degenerate-mesh fast path), bf16 params, chunked fused-CE loss,
    bf16 moments + f32 master weights: the program the MFU headline runs.
    Donates params and opt state (argnums 0, 1)."""
    from paddle_tpu.distributed.hybrid_engine import HybridParallelEngine
    from paddle_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig.tiny(
        vocab_size=TOY["vocab_size"], hidden_size=TOY["hidden_size"],
        intermediate_size=TOY["intermediate_size"],
        num_hidden_layers=TOY["num_layers"],
        num_attention_heads=TOY["num_heads"],
        num_key_value_heads=TOY["num_kv_heads"],
        max_position_embeddings=TOY_SEQ, use_flash_attention=False)
    eng = HybridParallelEngine(
        cfg, dp=1, pp=1, mp=1, micro_batches=1,
        dtype=jnp.dtype(dtype_name), remat=False,
        loss_chunk=TOY_CHUNK, moments="bf16", master_weights=True)
    params, opt = eng.init_state(0)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, TOY["vocab_size"],
                       (TOY_BATCH, TOY_SEQ)).astype(np.int32)
    labels = rng.integers(0, TOY["vocab_size"],
                          (TOY_BATCH, TOY_SEQ)).astype(np.int32)
    ids, labels = eng.shard_batch(ids, labels)
    step = eng.build_train_step()
    traced = step.trace(params, opt, ids, labels)
    example = (_sds_tree(params), _sds_tree(opt), _sds_tree(ids),
               _sds_tree(labels))
    return _from_traced(
        "hybrid_train_step", traced, example, donated=(0, 1),
        meta={"policy": ("bf16" if dtype_name == "bfloat16" else "f32"),
              "forbidden_shape": (TOY_BATCH, TOY_SEQ, TOY["vocab_size"])})


@functools.lru_cache(maxsize=None)
def opt_writeback_program(moments="bf16"):
    """The fused optimizer write-back on its own: one jitted tree-level
    adamw_update with donated params + opt state — the no-double-buffered
    -HBM contract for the optimizer family."""
    from paddle_tpu.distributed.hybrid_engine import adamw_init, adamw_update
    from paddle_tpu.models import llama_functional as lf

    # master_weights=False here: with masters on, adamw_update never
    # reads the raw params (only their static dtype), jit prunes them
    # from the lowering, and the flat-arg mapping breaks. The
    # master-weights donation path is covered by train_step_program,
    # where params feed the forward pass and survive pruning.
    args = toy_args()
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16),
        lf.init_params(args, jax.random.key(0)))
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    state = adamw_init(params, moments=moments, master_weights=False)
    step = jax.jit(functools.partial(adamw_update, moments=moments),
                   donate_argnums=(0, 2))
    traced = step.trace(params, grads, state)
    example = (_sds_tree(params), _sds_tree(grads), _sds_tree(state))
    return _from_traced("fused_opt_writeback", traced, example,
                        donated=(0, 2), meta={"policy": "bf16"})


def _tp_mesh(degree=2):
    from jax.sharding import Mesh

    if len(jax.devices()) < degree:
        return None
    return Mesh(np.array(jax.devices()[:degree]), ("mp",))


@functools.lru_cache(maxsize=None)
def serving_programs(tp=2, num_heads=None):
    """Capture the PagedEngine's real step programs by serving tiny
    requests through two engines (plain TP: prefill/decode/COW page-copy;
    TP + draft: the speculative verify), then re-tracing the captured
    callables. tp=0 builds without a mesh (single-chip program shapes).
    `num_heads` widens the toy head count when tp exceeds TOY's 2 heads
    (the deep -m slow audits run tp=4).

    Returns {name: AuditProgram}. The pool (pk/pv) argnums each program
    donates ride in `donated`; meta carries the mesh degree and layer
    count for the collective-census formula."""
    from paddle_tpu.models import generation as gen
    from paddle_tpu.models import llama_functional as lf
    from paddle_tpu.serving import PagedEngine, Request

    overrides = ({"num_heads": num_heads, "num_kv_heads": num_heads}
                 if num_heads else {})
    args = toy_args(**overrides)
    params = lf.init_params(args, jax.random.key(0))
    mesh = _tp_mesh(tp) if tp else None
    if tp and mesh is None:
        raise RuntimeError(
            f"serving_programs(tp={tp}) needs >= {tp} devices "
            f"(have {len(jax.devices())}); run under the virtual CPU mesh")
    kw = dict(max_slots=2, max_len=32, page_size=8, min_bucket=8,
              donate_steps=True, mesh=mesh)
    rng = np.random.default_rng(7)

    def prompt(n):
        return rng.integers(1, args.vocab_size, size=n).astype(np.int32)

    out = {}
    meta = {"tp": tp if mesh is not None else 0,
            "num_layers": args.num_layers}

    # plain engine, then the same step family over QuantizedKVPage pools
    # (int8 codes + per-(page, kv-head) scales): prefill + decode captured
    # by serving; the COW page-copy program never fires on the natural flow
    # (the allocator only COWs shared/registered tail pages), so it is
    # traced directly from the path's own jitted object with the live pool
    # shapes. Quantize-at-scatter and dequant-at-gather must not change the
    # collective structure (still the 2 row-parallel psums per scanned layer
    # body), and the int8 page copy must stay pure data movement over BOTH
    # leaves.
    recs, donated = {}, {}
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    for suffix, kv_dtype in (("", None), ("_int8", "int8")):
        eng = PagedEngine(params, args, kv_dtype=kv_dtype, **kw)
        path = eng.path
        for name, table, pools in (("paged_prefill", path._prefill, (6, 7)),
                                   ("paged_decode", path._decode, (2, 3))):
            recs[name + suffix] = table[False] = _Recorder(table[False])
            donated[name + suffix] = pools
        eng.serve([Request(prompt(16), max_new_tokens=4),
                   Request(prompt(10), max_new_tokens=3)])
        copy_args = (_sds_tree(path.pk), _sds_tree(path.pv), i32, i32)
        out["page_copy" + suffix] = _from_traced(
            "page_copy" + suffix, path._copy.trace(*copy_args), copy_args,
            donated=(0, 1), meta=meta)

    # draft engine: the speculative verify program (plain decode is
    # replaced by propose/verify rounds when a draft is loaded)
    draft_params, draft_args = gen.draft_from_params(params, args,
                                                     num_layers=1)
    spec = PagedEngine(params, args, draft_params=draft_params,
                       draft_args=draft_args, spec_tokens=2, **kw)
    recs["spec_verify"] = _Recorder(spec._spec._verify)
    spec._spec._verify = recs["spec_verify"]
    spec.serve([Request(prompt(9), max_new_tokens=4)])
    donated["spec_verify"] = (2, 3)

    for name, rec in recs.items():
        traced = rec.trace()
        if traced is None:
            continue  # program never dispatched (scheduler change?)
        out[name] = _from_traced(name, traced, rec.args,
                                 donated=donated[name], meta=meta)
    return out


@functools.lru_cache(maxsize=None)
def latent_serving_programs():
    """The latent-attention expert family's step programs through the one
    `serving/family.FamilyPath`, captured as `serving_programs` captures the
    dense ones: a tiny stack (one dense leading layer, two expert layers,
    one group of eight held) serves two requests, the second a prefix hit
    that ends mid-page (so the page copy runs), and the recorded callables
    are re-traced. Single chip: the family has no mesh. The pool is each
    program's one donated array (beside it the state tree, which is
    empty)."""
    from paddle_tpu.models import latent_moe_functional as lm
    from paddle_tpu.serving import PagedEngine, Request

    args = lm.LatentMoEArgs(
        vocab_size=96, hidden_size=32, num_layers=3, num_heads=2, q_rank=12,
        kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8, dense_intermediate=48,
        expert_intermediate=16, shared_experts=2, routed_experts=32,
        first_expert=4, experts_held=4, n_group=8, topk_group=3,
        experts_per_tok=6, routed_scaling=16.0, first_k_dense=1,
        rope_theta=10000.0, rms_eps=1e-6,
        yarn=lm.YarnConfig(40.0, 64, 32.0, 1.0, 0.707, 0.707))
    h, H, E = args.hidden_size, args.num_heads, args.experts_held
    rng = np.random.default_rng(3)

    def leaf(*shape):
        return jnp.asarray(0.1 * rng.standard_normal(shape), jnp.float32)

    def attention(n):
        return {"ln1": jnp.ones((n, h)), "ln2": jnp.ones((n, h)),
                "w_qa": leaf(n, h, 12), "q_norm": jnp.ones((n, 12)),
                "w_qb": leaf(n, 12, H * 12), "w_kva": leaf(n, h, 20),
                "kv_norm": jnp.ones((n, 16)), "w_kvb": leaf(n, 16, H * 16),
                "wo": leaf(n, H * 8, h)}

    params = {
        "embedding": leaf(96, h), "final_norm": jnp.ones(h),
        "lm_head": leaf(h, 96),
        "dense_layers": dict(attention(1), w_gate=leaf(1, h, 48),
                             w_up=leaf(1, h, 48), w_down=leaf(1, 48, h)),
        "layers": dict(attention(2), router=leaf(2, h, 32),
                       ws_gate=leaf(2, h, 32), ws_up=leaf(2, h, 32),
                       ws_down=leaf(2, 32, h), we_gate=leaf(2, E, h, 16),
                       we_up=leaf(2, E, h, 16), we_down=leaf(2, E, 16, h))}
    eng = PagedEngine(params, args, max_slots=2, max_len=32, page_size=8,
                      min_bucket=8, donate_steps=True)
    path, recs = eng.path, {}
    for name, table in (("latent_prefill", path._prefill),
                        ("latent_decode", path._decode)):
        recs[name] = table[False] = _Recorder(table[False])
    recs["latent_page_copy"] = path._copy = _Recorder(path._copy)
    donated = {"latent_prefill": (8, 9), "latent_decode": (6, 7),
               "latent_page_copy": (0,)}
    base = rng.integers(1, 96, size=12).astype(np.int32)
    eng.serve([Request(base, max_new_tokens=3)])
    eng.serve([Request(np.concatenate([base, base[:5]]), max_new_tokens=3)])
    meta = {"tp": 0, "num_layers": args.num_layers, "vocab": args.vocab_size,
            "slots": 2, "bucket": 16}
    out = {}
    for name, rec in recs.items():
        traced = rec.trace()
        if traced is not None:
            out[name] = _from_traced(name, traced, rec.args,
                                     donated=donated[name], meta=meta)
    return out


@functools.lru_cache(maxsize=None)
def delta_serving_programs():
    """The gated delta-rule hybrid family's step programs through the one
    `serving/family.FamilyPath`, captured as `latent_serving_programs`
    captures the latent ones: a tiny stack (one period: three linear layers
    and a full one) serves two requests, the second a prefix hit that ends
    mid-page (so the page copy and the snapshot's load run), and the
    recorded callables are re-traced. Single chip: the family has no mesh.
    The pools AND the tree of per-slot state are each step program's donated
    arguments; the state's mover donates its destination."""
    from paddle_tpu.models import gated_delta_functional as gdf
    from paddle_tpu.serving import PagedEngine, Request

    args = gdf.GatedDeltaArgs(
        vocab_size=96, hidden_size=32, intermediate_size=48, num_heads=2,
        head_dim=16, linear_heads=2, linear_key_dim=8, linear_value_dim=16,
        conv_kernel=4, layer_kinds=(gdf.LINEAR,) * 3 + (gdf.FULL,),
        rms_eps=1e-6)
    h, H, dk, dv = 32, 2, 8, 16
    C = args.conv_channels
    rng = np.random.default_rng(3)

    def leaf(*shape):
        return jnp.asarray(0.1 * rng.standard_normal(shape), jnp.float32)

    def ffn(n):
        return {"ln1": jnp.ones((n, h)), "ln2": jnp.ones((n, h)),
                "w_gate": leaf(n, h, 48), "w_up": leaf(n, h, 48),
                "w_down": leaf(n, 48, h)}

    params = {
        "embedding": leaf(96, h), "final_norm": jnp.ones(h),
        "lm_head": leaf(h, 96),
        gdf.LINEAR: dict(
            ffn(3), wq=leaf(3, h, H * dk), wk=leaf(3, h, H * dk),
            wv=leaf(3, h, H * dv), wg=leaf(3, h, H * dv),
            wo=leaf(3, H * dv, h), wa=leaf(3, h, H), wb=leaf(3, h, H),
            conv_w=leaf(3, C, 4), A_log=leaf(3, H), dt_bias=leaf(3, H),
            o_norm=jnp.ones((3, dv))),
        gdf.FULL: dict(
            ffn(1), wq=leaf(1, h, h), wk=leaf(1, h, h), wv=leaf(1, h, h),
            wo=leaf(1, h, h), q_norm=jnp.ones((1, h)),
            k_norm=jnp.ones((1, h)))}
    eng = PagedEngine(params, args, max_slots=2, max_len=32, page_size=8,
                      min_bucket=8, donate_steps=True)
    path, recs = eng.path, {}
    for name, table in (("delta_prefill", path._prefill),
                        ("delta_decode", path._decode)):
        recs[name] = table[False] = _Recorder(table[False])
    recs["delta_page_copy"] = path._copy = _Recorder(path._copy)
    recs["delta_state_move"] = path._move = _Recorder(path._move)
    donated = {"delta_prefill": (8, 9), "delta_decode": (6, 7),
               "delta_page_copy": (0,), "delta_state_move": (0,)}
    base = rng.integers(1, 96, size=12).astype(np.int32)
    eng.serve([Request(base, max_new_tokens=3)])
    eng.serve([Request(np.concatenate([base, base[:5]]), max_new_tokens=3)])
    meta = {"tp": 0, "num_layers": args.num_layers, "vocab": args.vocab_size,
            "slots": 2, "bucket": 16}
    out = {}
    for name, rec in recs.items():
        traced = rec.trace()
        if traced is not None:
            out[name] = _from_traced(name, traced, rec.args,
                                     donated=donated[name], meta=meta)
    return out


@functools.lru_cache(maxsize=None)
def disagg_programs():
    """Capture the disaggregated-serving + router device programs by
    migrating tiny requests end-to-end (prefill worker -> LocalTransport
    -> decode worker, model-dtype AND int8 pools) and serving a couple
    of GPT requests through the router's `GptEngine`:

      page_extract[/._int8]   the prefill side's pool gather (never
                              donates — the pool must survive the ship)
      page_scatter[/_int8]    the decode side's write of shipped page
                              contents into fresh pages (donates both
                              pool trees, like every other step program)
      gpt_prefill/gpt_decode  the second autoregressive model family on
                              the stripe scheduler (learned positions,
                              donated KV stripes)

    All six are single-chip programs; the migration pair is pinned
    collective-free (pure data movement) — on a TP mesh the pool leaves
    are sharded on the kv-head axis and extract/scatter still never
    cross shards. Returns {name: AuditProgram}."""
    from paddle_tpu.serving import PagedEngine, Request  # noqa: F401
    from paddle_tpu.serving.disagg import (DecodeWorker, LocalTransport,
                                           PrefillWorker)
    from paddle_tpu.serving.router import GptEngine
    from paddle_tpu.models import llama_functional as lf

    args = toy_args()
    params = lf.init_params(args, jax.random.key(0))
    kw = dict(max_slots=2, max_len=32, page_size=8, min_bucket=8,
              donate_steps=True)
    rng = np.random.default_rng(11)

    def prompt(n, vocab=args.vocab_size):
        return rng.integers(1, vocab, size=n).astype(np.int32)

    recs, donated = {}, {}
    meta = {"tp": 0, "num_layers": args.num_layers}

    def migrate(kv_dtype, suffix):
        lt = LocalTransport()
        pw = PrefillWorker(params, args, transport=lt,
                           kv_dtype=kv_dtype, **kw)
        done = []
        dw = DecodeWorker(params, args, transport=lt, kv_dtype=kv_dtype,
                          completion_cb=done.append, **kw)
        recs[f"page_extract{suffix}"] = pw.path._extract = _Recorder(
            pw.path._extract)
        recs[f"page_scatter{suffix}"] = dw.path._scatter = _Recorder(
            dw.path._scatter)
        donated[f"page_extract{suffix}"] = ()
        donated[f"page_scatter{suffix}"] = (0, 1)
        pw.submit(Request(prompt(12), max_new_tokens=3))
        for _ in range(64):
            if not (pw.queue or pw.slots.active_slots or pw._chunk_streams):
                break
            pw.step()
        for _ in range(64):
            if done:
                break
            dw.step()
        assert done, "migration never completed — capture harness broken"

    migrate(None, "")
    migrate("int8", "_int8")

    gpt = GptEngine(*_gpt_toy(), max_slots=2, max_len=32, min_bucket=8,
                    donate_steps=True)
    recs["gpt_prefill"] = gpt._prefill = _Recorder(gpt._prefill)
    recs["gpt_decode"] = gpt._decode = _Recorder(gpt._decode)
    donated["gpt_prefill"] = (3, 4)
    donated["gpt_decode"] = (2, 3)
    gpt.serve([Request(prompt(10, 64), max_new_tokens=3),
               Request(prompt(6, 64), max_new_tokens=2)])

    out = {}
    for name, rec in recs.items():
        traced = rec.trace()
        if traced is None:
            continue  # program never dispatched (scheduler change?)
        out[name] = _from_traced(name, traced, rec.args,
                                 donated=donated[name], meta=meta)
    return out


@functools.lru_cache(maxsize=None)
def _gpt_toy():
    """Toy GPT-2 params/args for the router's second autoregressive
    family — same scale discipline as TOY (2 layers, degree-2-divisible
    heads, position table bounding max_len=32)."""
    from paddle_tpu.models.generation import (GPTGenArgs,
                                              gpt_params_from_layer)
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    cfg = GPTConfig(vocab_size=64, hidden_size=32, intermediate_size=48,
                    num_hidden_layers=2, num_attention_heads=2,
                    max_position_embeddings=32)
    return gpt_params_from_layer(GPTForCausalLM(cfg)), \
        GPTGenArgs.from_config(cfg)
