"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py        (on a machine with a TPU; one process)

Drives the two main paths once through their public entry points at the full
width of the 0.94B Llama (vocab 32000, hidden 2048, FFN 5504, 16 layers,
16 heads x 128, bf16; random weights from a seed):

  kernels  every Pallas kernel the two paths dispatch, COMPILED (not
           interpreted), against its jnp oracle at the shapes this model
           produces — before the end-to-end phases, so a Mosaic refusal
           names the kernel
  train    `paddle.set_device('tpu')` -> `HybridParallelEngine` ->
           `init_state` -> `train_batch` x STEPS at b8 x s1024 on one fixed
           batch: loss finite every step and lower at the end
  serve    `PagedEngine` at 8 slots x 1024 positions, 64-token pages:
           mixed-length requests through `submit`/`step`, one prompt long
           enough to chunk, two sharing a prefix; once with the model-dtype
           page pool, once with `kv_dtype="int8"`, once with a draft model
           (speculative verify)
  4 chips  when jax sees >= 4 devices: pp2 x mp2 (1f1b, sp), dp2 x mp2
           ZeRO-3, and mp4 serving, with the per-device memory spread

Each step program is also lowered and its text searched for the Mosaic
custom calls it is supposed to carry — the backend's name proves nothing.

Exit code 0 and a last stdout line
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}`
only if every phase passed. Any failure raises: no phase is wrapped in
try/except, none can be skipped, and there is no size flag — a toy run cannot
look like the real one. Without a TPU it exits 2 before importing the model.
Per-phase compile seconds (trace + lower + XLA compile, from jax's own
monitoring events) and run seconds are printed as set-up facts, not metrics.
"""

import contextlib
import gc
import json
import re
import sys
import time

import numpy as np

from _platform_setup import configure_compile_cache
from bench import H2048

BATCH, SEQ, MICRO_BATCHES, STEPS = 8, 1024, 2, 4
SLOTS, MAX_LEN, PAGE, MIN_BUCKET, CHUNK = 8, 1024, 64, 64, 256
SPEC_TOKENS, DRAFT_LAYERS = 4, 2
TF_LEN = 640      # teacher-forcing pad: the longest request, 128-aligned
# teacher-forced bar: how far below the reference argmax's logit an emitted
# token may sit (logits of this random-weight model have sigma ~0.9 and a
# mean top-1/top-2 gap ~0.2; bf16 spacing at the top logit is 0.03)
GAP_BAR = {"model": 0.15, "int8": 0.3}


class _Clock:
    """Compile seconds and compile-cache hits of the current phase, from
    jax's own monitoring events. Compile seconds are the union of the
    trace / lower / XLA-compile time spans (they nest, so their sum would
    double count); run seconds are the rest of the phase's wall time."""

    _COMPILE = ("/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/jaxpr_to_mlir_module_duration",
                "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring as mon

        self.spans = []
        self.hits = self.misses = 0
        mon.register_event_time_span_listener(self._span)
        mon.register_event_listener(self._event)

    def _span(self, event, start, end, **_):
        if event in self._COMPILE:
            self.spans.append((start, end))

    def _event(self, event, **_):
        self.hits += event == "/jax/compilation_cache/cache_hits"
        self.misses += event == "/jax/compilation_cache/cache_misses"

    def _compile_s(self, first):
        total, edge = 0.0, float("-inf")
        for start, end in sorted(self.spans[first:]):
            total += max(0.0, end - max(start, edge))
            edge = max(edge, end)
        return total

    @contextlib.contextmanager
    def phase(self, name):
        """Print the phase's compile and run seconds when it ends cleanly (a
        failing phase propagates: nothing here catches)."""
        print(f"== {name}", flush=True)
        t0, first = time.perf_counter(), len(self.spans)
        hits, misses = self.hits, self.misses
        yield
        wall, comp = time.perf_counter() - t0, self._compile_s(first)
        print(f"-- {name}: compile_s={comp:.1f} run_s={wall - comp:.1f} "
              f"cache_hits={self.hits - hits} "
              f"cache_misses={self.misses - misses}", flush=True)


def _mosaic_kernels(wanted, jitted, *args):
    """Names of the Mosaic (Pallas TPU) custom calls in the lowered text of
    `jitted(*args)`; raises unless every name in `wanted` is among them."""
    text = jitted.lower(*args).as_text()
    names = sorted(set(re.findall(r'kernel_name\s*=\s*"([^"]+)"', text)))
    if not names and "tpu_custom_call" in text:
        raise AssertionError("tpu_custom_call present but no kernel_name "
                             "attribute: the text format changed")
    for w in wanted:
        if w not in names:
            raise AssertionError(f"lowered program lacks the Mosaic call "
                                 f"{w!r}; it has {names}")
    return names


def _rel_err(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape:
        raise AssertionError(f"shape {got.shape} != oracle {want.shape}")
    if not np.isfinite(got).all():
        raise AssertionError("non-finite kernel output")
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-9))


# ---------------------------------------------------------------------------
# kernels: compiled parity against the jnp oracles
# ---------------------------------------------------------------------------

def kernel_cases(args):
    """(name, Mosaic kernel names, dispatch fn, f32 oracle, operands) for
    every Pallas kernel the two paths dispatch, at this model's shapes."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.kernels import quantized_matmul as qm
    from paddle_tpu.nn.functional.flash_attention import _sdpa_reference

    nh, nkv = args.num_heads, args.num_kv_heads
    hd = args.hidden_size // nh
    P = MAX_LEN // PAGE
    rng = np.random.default_rng(0)
    bf16, f32 = jnp.bfloat16, jnp.float32
    scale = 1.0 / np.sqrt(hd)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), bf16)

    def up(*xs):
        return tuple(x.astype(f32) for x in xs)

    def flash(attn):
        def fn(q, k, v, w):
            out, vjp = jax.vjp(attn, q, k, v)
            return out, vjp(w.astype(out.dtype))
        return fn

    # flash fwd+bwd at the train step's micro-batch shape
    cases = [(
        "flash_fwd_bwd", ("_fwd_kernel", "_bwd_dq_kernel", "_bwd_dkv_kernel"),
        flash(lambda q, k, v: fa.flash_attention_fwd(q, k, v, causal=True)),
        flash(lambda q, k, v: _sdpa_reference(*up(q, k, v), causal=True)),
        tuple(normal(BATCH // MICRO_BATCHES, SEQ, n, hd)
              for n in (nh, nkv, nkv, nh)))]

    # stripe decode / window (verify + short-prefill shapes) over a slot cache
    pos = jnp.asarray([5, 63, 64, 200, 511, 777, 1000,
                       MAX_LEN - 1 - SPEC_TOKENS], jnp.int32)
    ck, cv = normal(SLOTS, nkv, MAX_LEN, hd), normal(SLOTS, nkv, MAX_LEN, hd)

    def window_oracle(q, k, v, p):
        return qm._window_attention_xla(*up(q, k, v), p, scale)

    cases += [
        ("stripe_decode", ("_decode_kernel",), qm.decode_attention,
         lambda q, k, v, p: qm._decode_attention_xla(*up(q, k, v), p, scale),
         (normal(SLOTS, 1, nh, hd), ck, cv, pos)),
        ("window_verify", ("_window_kernel",), qm.window_decode_attention,
         window_oracle, (normal(SLOTS, SPEC_TOKENS + 1, nh, hd), ck, cv, pos)),
        ("window_prefill", ("_window_kernel",), qm.window_decode_attention,
         window_oracle,
         (normal(1, MIN_BUCKET, nh, hd), normal(1, nkv, MAX_LEN + 128, hd),
          normal(1, nkv, MAX_LEN + 128, hd), jnp.int32(300)))]

    # paged decode through block tables, model-dtype and int8 pools
    num_pages = SLOTS * P + 1
    bt = jnp.asarray(rng.permutation(np.arange(1, num_pages)).reshape(
        SLOTS, P), jnp.int32)
    qd = normal(SLOTS, 1, nh, hd)

    def codes():
        return jnp.asarray(rng.integers(-127, 128, (num_pages, nkv, PAGE, hd)),
                           jnp.int8)

    def scales(*shape):
        return jnp.asarray(rng.uniform(0.5, 2.0, shape), f32)

    cases += [
        ("paged_decode", ("_paged_decode_kernel",), qm.paged_decode_attention,
         lambda q, k, v, b, p: qm._paged_decode_attention_xla(
             *up(q, k, v), b, p, scale),
         (qd, normal(num_pages, nkv, PAGE, hd),
          normal(num_pages, nkv, PAGE, hd), bt, pos)),
        ("paged_decode_int8", ("_paged_decode_kernel",),
         lambda q, k, v, b, p, ks, vs: qm.paged_decode_attention(
             q, k, v, b, p, k_scale=ks, v_scale=vs),
         lambda q, k, v, b, p, ks, vs: qm._paged_decode_attention_xla(
             q.astype(f32), k, v, b, p, scale, ks, vs),
         (qd, codes(), codes(), bt, pos, scales(num_pages, nkv),
          scales(num_pages, nkv)))]

    # a prefill window through one slot's table (a chunk that starts inside a
    # page and ends short of its bucket: a padded row sees what the last
    # real one does, in the kernel and the oracle), model-dtype and int8 pools
    from paddle_tpu.kernels import paged_prefill_attention as ppa

    qw, h, last = normal(CHUNK, nh, hd), jnp.int32(300), jnp.int32(CHUNK - 57)
    cases += [
        ("paged_prefill", ("paged_prefill_attention",),
         ppa.paged_prefill_attention,
         lambda q, k, v, b, h, l: ppa._xla(*up(q, k, v), b, h, l, None, None,
                                           None, scale),
         (qw, normal(num_pages, nkv, PAGE, hd),
          normal(num_pages, nkv, PAGE, hd), bt[0], h, last)),
        ("paged_prefill_int8", ("paged_prefill_attention",),
         lambda q, k, v, b, h, l, ks, vs: ppa.paged_prefill_attention(
             q, k, v, b, h, l, k_scale=ks, v_scale=vs),
         lambda q, k, v, b, h, l, ks, vs: ppa._xla(
             q.astype(f32), k, v, b, h, l, None, ks, vs, scale),
         (qw, codes(), codes(), bt[0], h, last, scales(num_pages, nkv),
          scales(num_pages, nkv)))]

    # weight-only int8 matmul at the decode batch, every weight shape (and
    # batch 1 against the K-tail weight: fewer rows than a sublane tile)
    H, I, V = args.hidden_size, args.intermediate_size, args.vocab_size
    for m, kk, nn in ((SLOTS, H, I), (SLOTS, I, H), (SLOTS, H, V), (1, I, H)):
        cases.append((
            f"dequant_matmul_{m}x{kk}x{nn}", ("_dqmm_kernel",),
            qm.weight_only_matmul,
            lambda x, w, s: qm._dequant_matmul_xla(x.astype(f32), w, s),
            (normal(m, kk),
             jnp.asarray(rng.integers(-127, 128, (kk, nn)), jnp.int8),
             scales(nn))))
    return cases


def check_kernel(name, kernels, fn, oracle, operands, tol=2e-2):
    """One kernel, compiled, against its oracle; raises on a refusal, a
    missing Mosaic call or an error beyond tol."""
    import jax

    jitted = jax.jit(fn)
    _mosaic_kernels(kernels, jitted, *operands)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(oracle)(*operands)
    errs = [_rel_err(g, w) for g, w in zip(
        jax.tree.leaves(jitted(*operands)), jax.tree.leaves(want))]
    if max(errs) > tol:
        raise AssertionError(f"kernel {name}: rel err {errs} > {tol}")
    print(f"kernel {name}: compiled parity ok, max rel err {max(errs):.2e}",
          flush=True)


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def _mem_mib(devices, key):
    gc.collect()     # dead engines' buffers must not count as in use
    return [int(d.memory_stats()[key]) >> 20 for d in devices]


def _spread(name, devices, before):
    """MiB each device gained since `before`: the state must be spread
    over the mesh, not piled on device 0."""
    gained = [a - b for a, b in zip(_mem_mib(devices, "bytes_in_use"),
                                    before)]
    print(f"{name}: per-device bytes_in_use gained (MiB) = {gained}",
          flush=True)
    if min(gained) <= 0 or max(gained) > 1.5 * sum(gained) / len(gained):
        raise AssertionError(f"{name}: state is not spread across the mesh: "
                             f"{gained}")


def train(label, devices, **mesh_kw):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.distributed.hybrid_engine import HybridParallelEngine
    from paddle_tpu.models.llama import LlamaConfig

    before = _mem_mib(devices, "bytes_in_use")
    eng = HybridParallelEngine(LlamaConfig(**H2048),
                               micro_batches=MICRO_BATCHES,
                               dtype=jnp.bfloat16, remat=False,
                               loss_chunk=128, devices=devices, **mesh_kw)
    params, opt = eng.init_state(0)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, H2048["vocab_size"], (BATCH, SEQ)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1)
    _mosaic_kernels(("_fwd_kernel", "_bwd_dq_kernel", "_bwd_dkv_kernel"),
                    eng.build_train_step(), params, opt,
                    *eng.shard_batch(ids, labels))
    losses = []
    for _ in range(STEPS):
        loss, params, opt = eng.train_batch(params, opt, ids, labels)
        losses.append(float(loss))
    print(f"train {label}: losses = {[round(x, 4) for x in losses]}",
          flush=True)
    if not np.isfinite(losses).all() or not losses[-1] < losses[0]:
        raise AssertionError(f"train {label}: loss not finite and "
                             f"decreasing: {losses}")
    _spread(f"train {label}", devices, before)
    print(f"train {label}: per-device peak_bytes_in_use, process lifetime "
          f"(MiB) = {_mem_mib(devices, 'peak_bytes_in_use')}", flush=True)
    del params, opt, eng
    gc.collect()
    return losses


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def make_requests(vocab):
    """(name, prompt, max_new_tokens): a and b share a 96-token prefix (a
    full-page radix hit for b), d is long enough to chunk at CHUNK, and f
    — submitted once the others have retired — shares the prefix past a's
    page boundary, so its hit ends mid-page and takes the copy-on-write
    page copy."""
    rng = np.random.default_rng(1)

    def toks(n):
        return rng.integers(1, vocab, n).astype(np.int32)

    prefix = toks(96)
    return [("a", np.concatenate([prefix, toks(8)]), 24),
            ("b", np.concatenate([prefix, toks(8)]), 24),
            ("c", toks(40), 24),
            ("d", toks(600), 8),
            ("e", toks(200), 16),
            ("f", np.concatenate([prefix, toks(8)]), 8)]


SECOND_WAVE = ("f",)


def reference_outputs(params, args, requests):
    """Sequential `generate`, one request at a time (a and b share one
    compiled shape)."""
    from paddle_tpu.models.generation import generate

    refs = {}
    for name, prompt, new in requests:
        out = np.asarray(generate(params, args, prompt[None],
                                  max_new_tokens=new))[0]
        refs[name] = out[len(prompt):].tolist()
    return refs


def teacher_forced_gap(forward, params, prompt, tokens):
    """max over emitted tokens of (reference max logit - the emitted
    token's logit), the context being the sequence the engine itself
    produced: 0 where the engine emitted the reference argmax, and robust
    to the cascade a single near-tie starts. `forward` is the jitted
    TRAINING forward (`llama_functional.forward`), a path that shares no
    cache code with the server; one padded length, one compile."""
    import jax.numpy as jnp

    seq = np.zeros((1, TF_LEN), np.int32)
    n, m = len(prompt), len(tokens)
    seq[0, :n] = prompt
    seq[0, n:n + m] = tokens
    logits = forward(params, jnp.asarray(seq))[0, n - 1:n + m - 1]
    logits = np.asarray(logits.astype(jnp.float32))
    return float(np.max(logits.max(-1) - logits[np.arange(m), tokens]))


def check_tokens(label, picked, reqs, forward, params, args, gap_bar):
    """Every picked request finished with its token count, in vocabulary,
    and never further than gap_bar below the reference argmax. Returns the
    per-request gaps."""
    for name, _, new in picked:
        r = reqs[name]
        if not (r.finished and len(r.token_ids) == new
                and all(0 <= t < args.vocab_size for t in r.token_ids)):
            raise AssertionError(f"serve {label}: request {name} did not "
                                 f"finish with {new} in-vocab tokens")
    gaps = {n: round(teacher_forced_gap(
        forward, params, prompt, np.asarray(reqs[n].token_ids)), 4)
        for n, prompt, _ in picked}
    if max(gaps.values()) > gap_bar:
        raise AssertionError(f"serve {label}: an emitted token sits "
                             f"{max(gaps.values())} below the reference "
                             f"argmax (bar {gap_bar}): {gaps}")
    return gaps


def serve(label, params, args, requests, refs, forward, *, gap_bar,
          expect_kernels, min_exact=0, only=None, **engine_kw):
    import jax.numpy as jnp

    from paddle_tpu.serving import PagedEngine, Request

    mesh = engine_kw.get("mesh")
    if mesh is not None:
        before = _mem_mib(mesh.devices.ravel(), "bytes_in_use")
    eng = PagedEngine(params, args, max_slots=SLOTS, max_len=MAX_LEN,
                      page_size=PAGE, min_bucket=MIN_BUCKET,
                      prefill_chunk=CHUNK, **engine_kw)
    if mesh is not None:
        _spread(f"serve {label}", mesh.devices.ravel(), before)
    # the step programs must carry their Mosaic calls (lowering only: the
    # donated pool buffers are not consumed)
    P, path = eng.pages_per_slot, eng.path
    zeros = jnp.zeros((P,), jnp.int32)
    prefill_names = _mosaic_kernels(
        ("paged_prefill_attention",), path._prefill[False], eng.params,
        jnp.zeros((1, MIN_BUCKET), jnp.int32), jnp.int32(0), jnp.int32(0),
        zeros, zeros, path.pk, path.pv, path.cos, path.sin, jnp.float32(0),
        jnp.float32(1), jnp.int32(0), jnp.zeros((1,), jnp.int32))
    decode_names = _mosaic_kernels(
        expect_kernels, path._decode[False], eng.params,
        jnp.asarray(eng._last_tok),
        path.pk, path.pv, jnp.zeros((SLOTS, P), jnp.int32),
        jnp.asarray(eng._npos), path.cos, path.sin, *eng._sampling_args())
    print(f"serve {label}: prefill[{MIN_BUCKET}] carries {prefill_names}, "
          f"decode carries {decode_names}", flush=True)
    if eng.spec_enabled:
        verify_names = _mosaic_kernels(
            ("_window_kernel",), eng._spec._verify, eng.params,
            jnp.zeros((SLOTS, SPEC_TOKENS + 1), jnp.int32), path.pk, path.pv,
            jnp.zeros((SLOTS, P), jnp.int32), jnp.asarray(eng._npos),
            jnp.asarray(eng._npos), path.cos, path.sin)
        print(f"serve {label}: verify carries {verify_names}", flush=True)

    picked = [r for r in requests if only is None or r[0] in only]
    reqs = {}
    for wave in ([r for r in picked if r[0] not in SECOND_WAVE],
                 [r for r in picked if r[0] in SECOND_WAVE]):
        for name, prompt, new in wave:
            reqs[name] = eng.submit(Request(prompt, new, request_id=name))
        while eng.queue or eng.slots.active_slots:
            eng.step()

    counters = eng.metrics.summary()["counters"]
    c = {k: counters.get(k, 0) for k in (
        "prefix_tokens_hit", "cow_copies", "chunked_prefills",
        "prefill_chunks", "spec_rounds", "prefill_compiles",
        "decode_compiles")}
    exact = {n: reqs[n].token_ids == refs[n] for n in reqs}
    gaps = check_tokens(label, picked, reqs, forward, params, args, gap_bar)
    print(f"serve {label}: served tokens = "
          f"{ {n: len(r.token_ids) for n, r in reqs.items()} }; exact parity "
          f"with generate = {exact}; teacher-forced logit gap = {gaps} "
          f"(bar {gap_bar}); " + " ".join(f"{k}={v}" for k, v in c.items()),
          flush=True)
    if sum(exact.values()) < min_exact:
        raise AssertionError(
            f"serve {label}: fewer than {min_exact} requests match "
            f"sequential generate token for token: {exact}")
    if only is None:
        if c["prefix_tokens_hit"] <= 0 or c["cow_copies"] < 1:
            raise AssertionError(f"serve {label}: no radix prefix hit, or "
                                 "no mid-page hit took the page copy")
        if c["chunked_prefills"] < 1 or c["prefill_chunks"] < 2:
            raise AssertionError(f"serve {label}: no chunked prefill ran")
    out = {n: list(r.token_ids) for n, r in reqs.items()}
    del eng
    gc.collect()
    return out


def serve_disagg(params, args, requests, forward, only=("a", "c")):
    """Prefill worker -> KV hand-off -> decode worker in one process: the
    page extract and the DONATED hand-off scatter on the real runtime."""
    from paddle_tpu.serving import Request
    from paddle_tpu.serving.disagg import DisaggServer

    srv = DisaggServer(params, args, max_slots=SLOTS, max_len=MAX_LEN,
                       page_size=PAGE, min_bucket=MIN_BUCKET,
                       prefill_chunk=CHUNK)
    picked = [r for r in requests if r[0] in only]
    reqs = {name: srv.submit(Request(prompt, new, request_id=name))
            for name, prompt, new in picked}
    srv.run_until_idle()
    handoffs = srv.decode.metrics.counter("handoffs_admitted")
    gaps = check_tokens("disagg", picked, reqs, forward, params, args,
                        GAP_BAR["model"])
    print(f"serve disagg: served tokens = "
          f"{ {n: len(r.token_ids) for n, r in reqs.items()} }; "
          f"handoffs_admitted={handoffs}; teacher-forced logit gap = {gaps} "
          f"(bar {GAP_BAR['model']})", flush=True)
    if handoffs != len(picked):
        raise AssertionError("serve disagg: a request did not hand off")
    del srv
    gc.collect()


# ---------------------------------------------------------------------------

def main():
    cache_dir = configure_compile_cache()
    import jax
    import jaxlib

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, jax.devices() = {devs}",
              file=sys.stderr)
        return 2
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    from importlib.metadata import version

    print(f"chip_smoke: platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']} "
          f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={version('libtpu')} compile_cache={cache_dir}", flush=True)
    four = len(devs) >= 4
    print(f"chip_smoke: {len(devs)} device(s) seen; legs: kernels, train, "
          f"serve(model, int8, spec, disagg)"
          + (", train pp2xmp2, train dp2xmp2 zero3, serve mp4" if four
             else " (four-chip legs need >= 4 devices: not run)"),
          flush=True)

    clock = _Clock()
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.core import native
    from paddle_tpu.distributed.mesh_utils import single_axis_mesh
    from paddle_tpu.models import llama_functional as lf
    from paddle_tpu.models.generation import draft_from_params
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.observability import global_registry

    paddle.set_device("tpu")
    print("chip_smoke: runtime core = "
          + ("native (csrc, built from source)" if native.available()
             else "pure-Python fallback (csrc did not build)"), flush=True)
    args = lf.LlamaArgs.from_config(LlamaConfig(**H2048))

    with clock.phase("kernels"):
        for case in kernel_cases(args):
            check_kernel(*case)

    with clock.phase("train 1x1x1"):
        losses = train("1x1x1", devs[:1], dp=1, pp=1, mp=1)

    params = lf.init_params(args, jax.random.key(0), jnp.bfloat16)
    requests = make_requests(args.vocab_size)
    forward = jax.jit(lambda p, ids: lf.forward(p, ids, args, remat=False))
    with clock.phase("reference generate"):
        refs = reference_outputs(params, args, requests)

    with clock.phase("serve model-dtype pool"):
        out = serve("model", params, args, requests, refs, forward,
                    gap_bar=GAP_BAR["model"], min_exact=2,
                    expect_kernels=("_paged_decode_kernel",))

    with clock.phase("serve int8 pool"):
        out8 = serve("int8", params, args, requests, refs, forward,
                     gap_bar=GAP_BAR["int8"], kv_dtype="int8",
                     expect_kernels=("_paged_decode_kernel",))
    agree = {n: round(float(np.mean(np.asarray(out8[n])
                                    == np.asarray(out[n]))), 3) for n in out}
    print(f"serve int8: top-1 agreement with the model-dtype pool = {agree}",
          flush=True)

    with clock.phase("serve speculative"):
        draft, draft_args = draft_from_params(params, args, DRAFT_LAYERS)
        serve("spec", params, args, requests, refs, forward, only=("a", "c"),
              gap_bar=GAP_BAR["model"], draft_params=draft,
              draft_args=draft_args, spec_tokens=SPEC_TOKENS,
              expect_kernels=("_paged_decode_kernel",))
        del draft

    with clock.phase("serve disaggregated"):
        serve_disagg(params, args, requests, forward)

    if four:
        for label, kw in (("pp2xmp2", dict(dp=1, pp=2, mp=2, sp=True,
                                           schedule="1f1b")),
                          ("dp2xmp2_zero3", dict(dp=2, pp=1, mp=2,
                                                 zero_stage=3))):
            with clock.phase(f"train {label}"):
                l4 = train(label, devs[:4], **kw)
            if abs(l4[0] - losses[0]) > 0.05:
                raise AssertionError(
                    f"train {label}: step-0 loss {l4[0]} vs one-chip "
                    f"{losses[0]} beyond bf16 tolerance")
        with clock.phase("serve mp4"):
            serve("mp4", params, args, requests, refs, forward,
                  gap_bar=GAP_BAR["model"], min_exact=2,
                  mesh=single_axis_mesh("mp", 4),
                  expect_kernels=("_paged_decode_kernel",))

    lookups = global_registry().snapshot()["counters"].get(
        "kernel_tuning_lookups", {})
    print(f"chip_smoke: kernel_tuning_lookups = {lookups}", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
