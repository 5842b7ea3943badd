"""Paged-KV serving engine: block tables, prefix reuse, tensor-parallel
decode, chunked prefill, and speculative decoding.

`Engine` (serving/engine.py) reserves a full `max_len` KV stripe per
slot, so HBM — not compute — caps concurrency, and identical system
prompts are re-prefilled for every request. `PagedEngine` replaces the
stripes with the vLLM PagedAttention memory model (Kwon et al.,
SOSP'23) plus SGLang-style prefix sharing, on the same iteration-level
scheduler:

  - ONE fixed page pool `[L, num_pages, nkv, page_size, hd]` (heads-major
    pages — the layout the Pallas paged decode kernel consumes) and a
    per-slot BLOCK TABLE mapping sequence positions to pages;
  - PREFIX CACHE: every prefilled prompt is registered in
    `BlockAllocator`'s radix tree and REF'd by later requests sharing
    the prefix at TOKEN granularity (refcounted, COW-protected; a
    mid-page divergence shares the straddled page through a
    copy-on-write split — the PR-8 exact-match hash chain survives as
    `prefix_policy="hash"`, the bench baseline);
  - PREFILL = gather the hit pages, run the suffix forward at traced
    position h (one program per suffix-length bucket), scatter the new
    pages; DECODE = one batched paged step through the block tables;
  - ADMISSION reserves the worst-case page count minus hits and defers
    the FIFO head under page pressure.

On top of that scheduler this engine adds the three serving-throughput
levers (ROADMAP item 1):

TENSOR PARALLELISM (`mesh=`): pass a Mesh with an `mp` axis and every
step program runs as one shard_map SPMD program over it — weights in
the Megatron split, the page pool sharded on its nkv axis, block tables
and the host-side allocator untouched (`serving/tp.py` has the
placement). Model size now scales with the mesh, not one chip's HBM.

CHUNKED PREFILL (`prefill_chunk=`): a long prompt no longer runs as one
monolithic program that stalls every decoding slot for its whole
duration. The suffix is split into page-aligned chunks and the
scheduler INTERLEAVES: chunk, then a decode step (or a short prefill),
then the next chunk — so TTFT for queued requests stays flat under
long-prompt bursts. Chunks reuse the suffix-bucket prefill program
(each chunk is "a suffix at a deeper h"), composing with prefix hits
unchanged.

SPECULATIVE DECODING (`draft_params=`): a cheap draft model (e.g.
`generation.draft_from_params` truncation) proposes `spec_tokens`
greedy tokens in ONE traced scan over its own stripe cache; the target
model scores the whole window in ONE batched paged verify forward; the
host commits the longest exactly-matching prefix plus the target's own
next token (Leviathan-style greedy acceptance — output is token-for-
token THE target's greedy sequence, just cheaper). Accepted tokens'
K/V land in the paged tail pages during verify; rejected positions are
garbage that the write-before-attend order overwrites, and positions
past a row's page reservation are redirected to the null page.

Greedy parity with sequential `generate` stays exact under every
combination of the three (and int8 `quantize_params` trees stream
through the same fused dequant-matmul dispatch).

A HYBRID STACK (`args` a `models/hybrid_functional.HybridArgs`: lightning
linear-attention layers beside block-sparse attention layers) runs through
the same `submit` / `step`, scheduler and allocator with a second kind of
per-request state: pages for the sparse layers only, and a fixed-size
recurrent state a slot for the lightning layers (`serving/hybrid.py` holds
both and the two step programs). A prefix hit is then usable only where a
SNAPSHOT of the recurrent state was taken: at a finished prompt's end, hung
on the radix tree when the request retires (`BlockAllocator(...,
state_snapshots=)`). `preempt` / `resume` carry the state; a mesh, an int8
pool and a draft model are refused at construction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.models import generation as gen
from paddle_tpu.models import llama_functional as lf
from paddle_tpu.serving.block_manager import NULL_PAGE, BlockAllocator
from paddle_tpu.serving.engine import Engine, Request
from paddle_tpu.serving.sampler import pick as _pick
from paddle_tpu.serving.scheduler import bucket_for, pages_for
from paddle_tpu.serving.spec_decode import SpecDecoder

__all__ = ["PagedEngine"]


def _paged_prefill_traced(params, ids, h, last_idx, bt_row, new_pages,
                          pk, pv, cos, sin, temp, top_p, top_k, seeds, *,
                          args, metrics, page_size, pages_per_slot,
                          sample=False, tp_axis=None, tp_degree=1):
    """Prefill a suffix window whose first `h` positions are already
    cached: gather the slot's pages into a contiguous scratch stripe,
    forward the window tokens at position h, scatter the freshly written
    pages back.

    ids: [1, sb] window right-padded to a length bucket; h: traced token
    count already cached (prefix hits AND previously prefilled chunks —
    TOKEN-granular under the radix cache, so h may sit mid-page: the
    straddled page is gathered from the frozen cached page and the
    scatter rewrites the slot's COW copy of it from the page-aligned
    base); last_idx: index of the window's last real token WITHIN the
    block; bt_row/new_pages: [P] page indices (unused entries -> null
    page 0). One XLA program per window bucket — h, last_idx and the
    page vectors are traced operands, so neither hit depth nor chunk
    position recompiles."""
    metrics.inc("prefill_compiles")
    quantized = isinstance(pk, gen.QuantizedKVPage)
    arr = pk.q if quantized else pk
    L, nkv, hd = arr.shape[0], arr.shape[2], arr.shape[4]
    ps, Pn = page_size, pages_per_slot
    sb = ids.shape[1]
    dtype = params["embedding"].dtype if quantized else pk.dtype

    # gather the block-table row into contiguous [L, 1, nkv, P*ps, hd]
    # (hit pages carry real prefix K/V; later entries are garbage that the
    # suffix writes + position mask keep unread), then pad by the suffix
    # bucket so the write at [h, h+sb) can never clamp. An int8 pool
    # dequantizes in the gather — the scratch stripe the forward runs
    # over is always the compute dtype
    with jax.named_scope("pt.kv_gather"):
        if quantized:
            def dq(pool):
                raw = pool.q[:, bt_row].astype(jnp.float32)  # [L,P,nkv,ps,hd]
                sc = (pool.scale[:, bt_row] / 127.0)[..., None, None]
                return (raw * sc).astype(dtype)

            g_k = jnp.swapaxes(dq(pk), 1, 2).reshape(L, 1, nkv, Pn * ps, hd)
            g_v = jnp.swapaxes(dq(pv), 1, 2).reshape(L, 1, nkv, Pn * ps, hd)
        else:
            g_k = jnp.swapaxes(pk[:, bt_row], 1, 2).reshape(
                L, 1, nkv, Pn * ps, hd)
            g_v = jnp.swapaxes(pv[:, bt_row], 1, 2).reshape(
                L, 1, nkv, Pn * ps, hd)
        # (the pad itself rounds up to the 128-position tile: the Pallas
        # window kernel only takes a 128-aligned stripe, and with a bare
        # `sb` pad the smallest bucket's stripe never was)
        pad = jnp.zeros((L, 1, nkv, -(-sb // 128) * 128, hd), dtype)
        temp_k = jnp.concatenate([g_k, pad], axis=3)
        temp_v = jnp.concatenate([g_v, pad], axis=3)

    logits, temp_k, temp_v = gen._forward_cached(
        params, ids, temp_k, temp_v, h, cos, sin, args, last_idx=last_idx,
        tp_axis=tp_axis, tp_degree=tp_degree)
    # the emitted token sits at sequence index h + last_idx + 1 — the
    # (seed, position) the offline generate(seeds=...) would use
    first = _pick(logits, sample, temp, top_p, top_k, seeds,
                  h + last_idx + 1)[0]

    # scatter the freshly written pages back from the page-aligned base
    # below h: when h is mid-page the first chunk carries the gathered
    # cached half [base, h) plus the new tokens — exactly the COW-copy
    # content. Unused entries land on the null page.
    base = h - h % ps
    pk, pv = _scatter_window(pk, pv, temp_k, temp_v, new_pages, base,
                             h + last_idx + 1, ps, Pn)
    return pk, pv, first


@jax.named_scope("pt.kv_write")
def _scatter_window(pk, pv, temp_k, temp_v, new_pages, base, end, ps, Pn):
    """Cut the scratch stripe into pages from `base` on and write them to
    `new_pages` of the pool (quantizing them for an int8 pool; `end` is the
    first position past the window's last real token)."""
    quantized = isinstance(pk, gen.QuantizedKVPage)

    def chunk(t, i):
        return jax.lax.dynamic_slice_in_dim(t, base + i * ps, ps, axis=3)

    new_k = jnp.concatenate([chunk(temp_k, i) for i in range(Pn)], axis=1)
    new_v = jnp.concatenate([chunk(temp_v, i) for i in range(Pn)], axis=1)
    if quantized:
        # scatter-time quantization: per-(page, kv-head) absmax over the
        # VALID positions only — the scratch stripe beyond the window's
        # last real token [end = h + last_idx + 1] is garbage (pad +
        # forward junk) that would otherwise inflate the scale and crush
        # the real values' precision. Masked positions store 0.
        pos_abs = (base + (jnp.arange(Pn, dtype=jnp.int32) * ps)[:, None]
                   + jnp.arange(ps, dtype=jnp.int32)[None, :])   # [Pn, ps]
        valid = (pos_abs < end)[None, :, None, :, None]

        def quant(newx):
            x = jnp.where(valid, newx.astype(jnp.float32), 0.0)
            s = jnp.max(jnp.abs(x), axis=(3, 4))                 # [L, Pn, nkv]
            qx = jnp.clip(jnp.round(
                x / jnp.maximum(s, 1e-9)[..., None, None] * 127.0),
                -127, 127).astype(jnp.int8)
            return qx, s

        qk, sk = quant(new_k)
        qv, sv = quant(new_v)
        pk = gen.QuantizedKVPage(pk.q.at[:, new_pages].set(qk),
                                 pk.scale.at[:, new_pages].set(sk))
        pv = gen.QuantizedKVPage(pv.q.at[:, new_pages].set(qv),
                                 pv.scale.at[:, new_pages].set(sv))
    else:
        pk = pk.at[:, new_pages].set(new_k)   # [L, P, nkv, ps, hd]
        pv = pv.at[:, new_pages].set(new_v)
    return pk, pv


def _paged_decode_traced(params, tokens, pk, pv, bt, pos, cos, sin, temp,
                         top_p, top_k, seeds, *, args, metrics, page_size,
                         sample=False, tp_axis=None, tp_degree=1):
    metrics.inc("decode_compiles")
    logits, pk, pv = gen._paged_forward_decode(
        params, tokens[:, None], pk, pv, bt, pos, cos, sin, args, page_size,
        tp_axis=tp_axis, tp_degree=tp_degree)
    return pk, pv, _pick(logits, sample, temp, top_p, top_k, seeds, pos + 1)


@jax.named_scope("pt.kv_write")
def _copy_page_traced(pk, pv, src, dst):
    """Device half of copy-on-write: clone one page's K/V across layers.
    The page axis is axis 1 of every pool leaf — the bf16 arrays AND both
    halves of an int8 `QuantizedKVPage` (codes [L, pages, ...] and scales
    [L, pages, nkv]) — so one tree_map covers both pool layouts."""
    def cp(a):
        return jax.lax.dynamic_update_slice_in_dim(
            a, jax.lax.dynamic_slice_in_dim(a, src, 1, axis=1), dst, axis=1)

    return (jax.tree_util.tree_map(cp, pk), jax.tree_util.tree_map(cp, pv))


class PagedEngine(Engine):
    """Continuous-batching engine over a paged KV cache with prefix
    reuse, optional tensor parallelism, chunked prefill, and speculative
    decoding.

    page_size: tokens per KV page. On TPU keep it a multiple of 16 (bf16
               sublane tile) with head_dim a multiple of 128 so the Pallas
               paged decode kernel stays eligible. Prefix sharing itself
               is TOKEN-granular (radix cache); page_size only sets the
               COW-copy unit a mid-page divergence pays for.
    num_pages: pool size INCLUDING the reserved null page 0. Defaults to
               max_slots * (max_len/page_size) + 1 — the stripe engine's
               capacity; set it lower to oversubscribe slots against the
               real (sub-max_len, prefix-shared) footprint, which is the
               entire point.
    max_len:   per-REQUEST cap (block tables hold max_len/page_size
               entries); no longer a per-slot HBM reservation.
    mesh:      optional jax Mesh carrying `tp_axis` (default 'mp'):
               weights and the page pool shard over it and every step
               program runs SPMD (serving/tp.py placement). num_kv_heads,
               num_heads and intermediate_size must divide the degree.
    prefill_chunk: optional chunk length (a multiple of page_size).
               Prompt suffixes longer than this prefill in chunks
               interleaved with decode steps — long prompts stop
               stalling in-flight requests.
    draft_params/draft_args: optional draft model (same vocab; e.g.
               `generation.draft_from_params`) enabling speculative
               decoding with `spec_tokens` drafts per round. Greedy
               requests only (exact-match acceptance); sampling requests
               are rejected at submit.
    kv_dtype:  None (pool in the model dtype) or 'int8' — quantize the
               KV page pool to int8 with per-(page, kv-head) absmax
               scales (`generation.QuantizedKVPage`). Prefill scatters
               quantize whole pages, decode/verify writes keep a RUNNING
               absmax (re-scaling a page's codes in-registers when a new
               token exceeds its scale), and attention dequantizes
               inside the paged kernel — KV bytes halve vs bf16, so an
               equal-HBM pool holds ~2x the pages. Outputs track the
               bf16 pool to a top-1 agreement bar, not bit-exactly
               (quantization perturbs KV); on TPU the int8 paged kernel
               needs page_size % 32 == 0 and head_dim % 128 == 0, other
               shapes ride the dequant-gather fallback.
    """

    def __init__(self, params, args, *, max_slots=4, max_len=256,
                 page_size=16, num_pages=None, min_bucket=16, pad_id=0,
                 metrics=None, mesh=None, tp_axis="mp", prefill_chunk=None,
                 draft_params=None, draft_args=None, spec_tokens=4,
                 donate_steps=None, prefix_policy="radix", kv_dtype=None):
        if prefix_policy not in ("radix", "hash"):
            raise ValueError(f"prefix_policy={prefix_policy!r} must be "
                             "'radix' or 'hash'")
        self.prefix_policy = prefix_policy
        if kv_dtype not in (None, "int8"):
            raise ValueError(f"kv_dtype={kv_dtype!r} must be None (the "
                             "model dtype) or 'int8'")
        self.kv_dtype = kv_dtype
        if max_len % page_size != 0:
            raise ValueError(
                f"max_len={max_len} must be a multiple of "
                f"page_size={page_size}")
        self.page_size = int(page_size)
        self.pages_per_slot = int(max_len) // self.page_size
        self.num_pages = (int(num_pages) if num_pages is not None
                          else int(max_slots) * self.pages_per_slot + 1)
        self.mesh = mesh
        self.tp_axis = tp_axis
        if prefill_chunk is not None:
            prefill_chunk = int(prefill_chunk)
            if prefill_chunk < 1 or prefill_chunk % self.page_size:
                raise ValueError(
                    f"prefill_chunk={prefill_chunk} must be a positive "
                    f"multiple of page_size={page_size}")
        self.prefill_chunk = prefill_chunk
        # a model description that lists its layers' kinds is a hybrid stack
        self._hybrid = hasattr(args, "layer_kinds")
        if self._hybrid:
            for given, what, why in (
                    (mesh, "mesh=", "the recurrent state and the selection "
                     "have no tensor-parallel placement yet"),
                    (kv_dtype, "kv_dtype='int8'", "the selector's "
                     "compressed keys are means of unquantized keys"),
                    (draft_params, "draft_params=", "a rejected draft "
                     "token cannot be taken back out of a recurrent state")):
                if given is not None:
                    raise ValueError(f"{what} is not supported for a "
                                     f"hybrid model: {why}")
        if draft_params is not None and draft_args is None:
            raise ValueError("draft_params requires draft_args "
                             "(see generation.draft_from_params)")
        self.draft_params = draft_params
        self.draft_args = draft_args
        self.spec_tokens = int(spec_tokens)
        if draft_params is not None:
            if self.spec_tokens < 1:
                raise ValueError("spec_tokens must be >= 1")
            if draft_args.vocab_size != args.vocab_size:
                raise ValueError("draft and target must share a vocab")
        super().__init__(params, args, max_slots=max_slots, max_len=max_len,
                         min_bucket=min_bucket, pad_id=pad_id,
                         metrics=metrics, donate_steps=donate_steps)

    @property
    def spec_enabled(self):
        return self.draft_params is not None

    # -- program construction ----------------------------------------------
    def _sharded(self, body, in_specs, out_specs, donate):
        """jit a traced step body, shard_map-wrapped when a mesh is set.
        check_vma stays off for these forward-only programs: the
        checker's value is guarding AD transposes, and serving has no
        gradients."""
        if self.mesh is None:
            return jax.jit(body, donate_argnums=donate)
        sm = jax.shard_map(body, mesh=self.mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        return jax.jit(sm, donate_argnums=donate)

    def _reset_host_state(self):
        """The allocator, block tables and reservations of an empty
        engine (construction and `reset`)."""
        self._alloc = BlockAllocator(
            self.num_pages, self.page_size, metrics=self.metrics,
            policy=self.prefix_policy,
            state_snapshots=self._hy.snapshots if self._hybrid else 0)
        self._bt = [[] for _ in range(self.max_slots)]   # host block tables
        self._resv = {}            # slot -> pages still reserved for decode
        self._reserved_total = 0
        self._chunk_streams = {}   # slot -> {req, n, done} mid-chunked-prefill
        self._chunk_turn = False
        self._admit_idx = None     # _can_prefill's cached admission scan

    def _setup_device_state(self):
        args = self.args
        axis = self.tp_axis
        if self._hybrid:
            # pools, recurrent state and step programs of the hybrid path
            from paddle_tpu.serving.hybrid import HybridPath

            self.tp_degree, self._spec = 1, None
            self._hy = HybridPath(self)
            self._reset_host_state()
            return
        if self.mesh is not None:
            from paddle_tpu.serving import tp as tp_lib

            self.tp_degree = int(self.mesh.shape[axis])
            tp_lib.tp_validate(args, self.tp_degree)
            # eager placement: weights land in their Megatron shards once,
            # at construction — never resharded on the hot path
            self.params = tp_lib.shard_params(self.params, self.mesh, axis)
            self._pspecs = tp_lib.llama_tp_specs(self.params, axis)
            self._poolspec = tp_lib.pool_spec(axis)
        else:
            self.tp_degree = 1
            self._pspecs = self._poolspec = None
        tp_kw = dict(tp_axis=axis if self.mesh is not None else None,
                     tp_degree=self.tp_degree)

        L = lf.stack_leading_dim(self.params["layers"])
        hd = args.hidden_size // args.num_heads
        dtype = jax.tree_util.tree_leaves(self.params["embedding"])[0].dtype
        nkv = args.num_kv_heads
        pool_shape = (L, self.num_pages, nkv, self.page_size, hd)
        if self.kv_dtype == "int8":
            # int8 pages + per-(page, kv-head) absmax scales: halves (vs
            # bf16) the KV bytes behind a page, so the same HBM budget
            # holds ~2x the pages -> ~2x the sustained slots. Scales
            # start at 0: the first write into a page sets them
            self._pk = gen.QuantizedKVPage(
                jnp.zeros(pool_shape, jnp.int8),
                jnp.zeros((L, self.num_pages, nkv), jnp.float32))
            self._pv = gen.QuantizedKVPage(
                jnp.zeros(pool_shape, jnp.int8),
                jnp.zeros((L, self.num_pages, nkv), jnp.float32))
        else:
            self._pk = jnp.zeros(pool_shape, dtype)
            self._pv = jnp.zeros_like(self._pk)
        self.metrics.set_gauge("kv_pool_bytes", 2 * sum(
            x.size * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves(self._pk)))
        if self.mesh is not None:
            # both halves of a QuantizedKVPage shard on nkv, so the bf16
            # pool spec applies to the pair as a pytree prefix
            sh = NamedSharding(self.mesh, self._poolspec)
            self._pk = jax.device_put(self._pk, sh)
            self._pv = jax.device_put(self._pv, sh)
        # 2*max_len: suffix prefills write at [h, h+bucket), which can
        # overshoot max_len before masking trims it
        self._cos, self._sin = lf.rope_tables(2 * self.max_len, hd,
                                              args.rope_theta)

        self._reset_host_state()

        donate = self._donate_enabled()
        rep = P()
        prefill_specs = dict(
            in_specs=(self._pspecs, rep, rep, rep, rep, rep,
                      self._poolspec, self._poolspec, rep, rep, rep, rep,
                      rep, rep),
            out_specs=(self._poolspec, self._poolspec, rep))
        decode_specs = dict(
            in_specs=(self._pspecs, rep, self._poolspec, self._poolspec,
                      rep, rep, rep, rep, rep, rep, rep, rep),
            out_specs=(self._poolspec, self._poolspec, rep))
        self._prefill_v, self._decode_v = {}, {}
        for sample in (False, True):
            self._prefill_v[sample] = self._sharded(
                functools.partial(
                    _paged_prefill_traced, args=args, metrics=self.metrics,
                    page_size=self.page_size,
                    pages_per_slot=self.pages_per_slot, sample=sample,
                    **tp_kw),
                donate=(6, 7) if donate else (), **prefill_specs)
            self._decode_v[sample] = self._sharded(
                functools.partial(
                    _paged_decode_traced, args=args, metrics=self.metrics,
                    page_size=self.page_size, sample=sample, **tp_kw),
                donate=(2, 3) if donate else (), **decode_specs)
        self._copy_page = self._sharded(
            _copy_page_traced,
            in_specs=(self._poolspec, self._poolspec, rep, rep),
            out_specs=(self._poolspec, self._poolspec),
            donate=(0, 1) if donate else ())

        # the speculative half (draft cache/programs + the sharded verify
        # program + the propose/verify/accept/roll-back round) lives in
        # serving/spec_decode.py
        self._spec = SpecDecoder(self, donate) if self.spec_enabled else None

    # -- admission ----------------------------------------------------------
    def submit(self, req):
        if not isinstance(req, Request):
            req = Request(req)
        need = pages_for(req.prompt_ids.size, req.max_new_tokens,
                         self.page_size)
        if need > self._alloc.capacity:
            raise ValueError(
                f"request needs {need} KV pages but the pool only has "
                f"{self._alloc.capacity} (num_pages={self.num_pages}, "
                f"page_size={self.page_size})")
        return super().submit(req)

    def _peek_hits(self, req):
        """Side-effect-free PrefixMatch for a queued request, memoized
        on the allocator's prefix_version: the anti-convoy scan below
        runs every step while a chunk stream is active, and re-walking
        every queued prompt each step is O(queue x prompt_len) host work
        for an answer that only changes when the prefix index does. Any
        registration, split, or eviction bumps prefix_version and
        invalidates the memo — a stale hit set here would skew the
        worst-case page reservation `_can_prefill` gates admission on."""
        ver = self._alloc.prefix_version
        cached = getattr(req, "_hits_memo", None)
        if cached is not None and cached[0] == ver:
            return cached[1]
        peek = self._alloc.match_prefix(req.prompt_ids, commit=False)
        req._hits_memo = (ver, peek)
        return peek

    def _admission_index(self):
        """Queue index to admit next. FIFO — except while a chunk stream
        is in flight, when the first SHORT prompt (suffix fits in one
        chunk) bypasses queued longs: a long prefill already streaming
        must not convoy every cheap prefill behind the NEXT long. Longs
        keep FIFO order among themselves, and the bypass only exists
        while a stream is active, so they cannot starve."""
        if not self.queue:
            return None
        if not (self.prefill_chunk and self._chunk_streams):
            return 0
        for i in range(len(self.queue)):
            req = self.queue.peek_at(i)
            if (req.prompt_ids.size - self._peek_hits(req).matched
                    <= self.prefill_chunk):
                return i
        return 0

    def _can_prefill(self):
        self._admit_idx = None
        if not (self.queue and self.slots.free_count):
            return False
        # cache the scan for the _prefill_step that immediately follows a
        # True answer — the anti-convoy walk match_prefix-hashes every
        # queued prompt, which is too much host work to repeat per step
        self._admit_idx = self._admission_index()
        req = self.queue.peek_at(self._admit_idx)
        peek = self._peek_hits(req)
        # reviving a cached (refcount-0) hit consumes availability just
        # like a fresh alloc; an actively shared hit is free. A mid-page
        # partial hit nets out: its COW copy costs one alloc but saves
        # one page of suffix — so `need` stays pages_for - full_hits.
        hit_pages = list(peek.pages)
        if peek.partial_page is not None:
            hit_pages.append(peek.partial_page)
        revive = sum(1 for p in hit_pages if self._alloc.refcount(p) == 0)
        need = (pages_for(req.prompt_ids.size, req.max_new_tokens,
                          self.page_size) - len(peek.pages) + revive)
        return need <= self._alloc.available - self._reserved_total

    # -- the interleaving scheduler -----------------------------------------
    def _step_action(self):
        """Chunked-prefill interleave: while a prompt is mid-stream, the
        engine alternates one chunk with one unit of other work (admit a
        waiting request or run a decode/speculation step), so queued and
        in-flight requests keep making progress underneath a long
        prefill. Decode becomes speculate-and-verify when a draft model
        is loaded."""
        if self._chunk_streams and self._chunk_turn:
            self._chunk_turn = False
            self._note_prefill_stall()
            return self._chunk_step()
        if self._can_prefill():
            self._chunk_turn = True
            self._note_prefill_stall()
            return self._prefill_step()
        if self._decodable_slots():
            self._chunk_turn = True
            if self.spec_enabled:
                return self._spec.step()
            return self._decode_step()
        if self._chunk_streams:
            return self._chunk_step()
        return {"type": "idle"}

    def _decodable_slots(self):
        active = self.slots.active_slots
        if not self._chunk_streams:
            return active
        return [s for s in active if s not in self._chunk_streams]

    # -- prefill ------------------------------------------------------------
    def _cow_device(self, src, dst):
        """Device half of copy-on-write: clone page `src` into `dst`."""
        if self._hybrid:
            self._hy.copy_page(src, dst)
        else:
            self._pk, self._pv = self._copy_page(
                self._pk, self._pv, jnp.int32(src), jnp.int32(dst))

    def _begin_paged_prefill(self, req, slot, n):
        """Match prefix hits, seat the block table, and reserve the
        request's remaining worst-case pages (prompt pages still to be
        written draw from this reservation chunk by chunk; the decode
        tail draws from it at page boundaries). Returns h — the cached
        token count the first window starts at."""
        ps = self.page_size
        hit = self._alloc.match_prefix(req.prompt_ids)   # refs hit pages
        h = hit.matched
        self._bt[slot] = list(hit.pages)
        held = len(hit.pages)
        if hit.partial_page is not None:
            # mid-page hit: the straddled page is frozen (tree-registered),
            # so take a copy-on-write split — ensure_writable swaps our ref
            # for a fresh page and the page-copy program clones the device
            # contents; the first window then overwrites [h, ...) in place
            src = hit.partial_page
            copy, _ = self._alloc.ensure_writable(src)
            with self._phase("stage", request_id=req.request_id, slot=slot):
                self._cow_device(src, copy)
            self._bt[slot].append(copy)
            held += 1
        if hit.state is not None:
            # a hybrid model resumes from the snapshot the match ends at
            with self._phase("stage", request_id=req.request_id, slot=slot):
                self._hy.load_snapshot(slot, hit.state)
        resv = pages_for(n, req.max_new_tokens, ps) - held
        self._resv[slot] = resv
        self._reserved_total += resv
        self.metrics.inc("prompt_tokens", n)
        self.metrics.inc("prefix_tokens_hit", h)
        self.metrics.inc("prefix_pages_hit", len(hit.pages))
        self.metrics.inc("prefix_pages_queried", (n - 1) // ps)
        return h

    def _window_prefill_device(self, req, slot, start, end, n):
        """Run one prefill window [start, end) of the prompt (the whole
        suffix, or one chunk of it) through the suffix program. Returns
        (bucket, token) — the token is meaningful only for the final
        window (end == n), which also registers the prompt's full pages
        in the prefix cache."""
        ps, Pn = self.page_size, self.pages_per_slot
        final = end == n
        # pages this window adds beyond those already seated (hits, the
        # partial-hit COW copy, earlier chunks); token-granular `start`
        # makes this ceil(end/ps) minus the seated count
        n_now = -(-end // ps) - len(self._bt[slot])
        new_pages = self._alloc.alloc_many(n_now)
        self._resv[slot] -= n_now
        self._reserved_total -= n_now
        self._bt[slot].extend(new_pages)
        pages = self._bt[slot]

        ids = dict(request_id=req.request_id, slot=slot)
        # prefill_s: stage + wait of a prefill-shaped step
        with self.metrics.timer("prefill_s"):
            with self._phase("stage", **ids):
                bt_row = np.zeros(Pn, np.int32)
                bt_row[:len(pages)] = pages
                # every page the window touches gets scattered: the
                # straddled page at start//ps (the mid-page-hit COW copy on
                # the first window, the slot's own tail page on later
                # chunks) is rewritten from the gathered stripe plus the
                # new tokens
                touched = pages[start // ps:]
                new_vec = np.full(Pn, NULL_PAGE, np.int32)
                new_vec[:len(touched)] = touched
                sb = bucket_for(end - start, self.min_bucket, self.max_len)
                padded = np.full((1, sb), self.pad_id, np.int32)
                padded[0, :end - start] = req.prompt_ids[start:end]
                sample = final and req.temperature > 0
                if self._hybrid:
                    first = self._hy.prefill(padded, start, end - 1 - start,
                                             bt_row, new_vec, slot, req,
                                             sample)
                    if final:
                        self._hy.save_snapshot(slot)
                else:
                    self._pk, self._pv, first = self._prefill_v[sample](
                        self.params, jnp.asarray(padded), jnp.int32(start),
                        jnp.int32(end - 1 - start), jnp.asarray(bt_row),
                        jnp.asarray(new_vec), self._pk, self._pv,
                        self._cos, self._sin, jnp.float32(req.temperature),
                        jnp.float32(req.top_p), jnp.int32(req.top_k),
                        jnp.asarray([req.seed], jnp.int32))
            with self._phase("wait", **ids):
                first = int(first)
        if final:
            # make this prompt's FULL pages hittable right away (a
            # concurrent identical prompt shares them while this one is
            # still decoding). The partial tail page stays unregistered
            # until _retire — decode keeps writing into it, and freezing
            # it now would force an unreserved COW on the first decode
            with self._phase("emit", **ids):
                self._alloc.register_prefix(req.prompt_ids, pages[:n // ps])
            # chunk-streamed prompts mirror into the draft window by
            # window instead (see _chunk_step) — one monolithic draft
            # prefill here would reintroduce the stall chunking removes
            if self.spec_enabled and slot not in self._chunk_streams:
                self._spec.prefill_slot(req, slot, n)
        return sb, first

    def _prefill_device(self, req, slot, n):
        """Monolithic prefill (no chunking, or suffix within one chunk)."""
        h = self._begin_paged_prefill(req, slot, n)
        return self._window_prefill_device(req, slot, h, n, n)

    def _prefill_step(self):
        """Admit the queue head; suffixes longer than `prefill_chunk`
        become a chunk STREAM advanced by later steps instead of one
        monolithic program."""
        if self.prefill_chunk is None:
            return super()._prefill_step()
        idx = self._admit_idx if self._admit_idx is not None \
            else self._admission_index()
        req = self.queue.pop_at(idx)
        slot = self._admit(req)
        n = int(req.prompt_ids.size)
        h = self._begin_paged_prefill(req, slot, n)
        if n - h <= self.prefill_chunk:
            bucket, first = self._window_prefill_device(req, slot, h, n, n)
            self.metrics.observe("chunks_per_prompt", 1)
            return self._complete_prefill(req, slot, bucket, first, n)
        self._chunk_streams[slot] = {"req": req, "n": n, "done": h,
                                     "ddone": 0, "chunks": 0,
                                     "bucket": None, "first": None}
        self.metrics.inc("chunked_prefills")
        return self._chunk_step()

    def _chunk_step(self):
        """Advance the oldest chunk stream (FIFO: the first admitted long
        prompt finishes first) by ONE bounded unit of prefill work: a
        target chunk, or — when speculation is on and the draft's mirror
        of the prompt lags the target's progress — one draft window of
        the same size, so the draft prefill never runs monolithically
        inside a single scheduler step."""
        slot = next(iter(self._chunk_streams))
        st = self._chunk_streams[slot]
        req, n = st["req"], st["n"]
        if self.spec_enabled and st["ddone"] < n and \
                (st["ddone"] < st["done"] or st["done"] == n):
            dstart = st["ddone"]
            dend = min(dstart + self.prefill_chunk, n)
            self._spec.prefill_window(req, slot, dstart, dend)
            st["ddone"] = dend
            self.metrics.inc("draft_prefill_chunks")
            if dend < n or st["done"] < n:
                return {"type": "draft_prefill_chunk",
                        "request_id": req.request_id, "slot": slot,
                        "from": dstart, "to": dend}
            return self._finish_stream(slot, st)
        start = st["done"]
        end = min(start + self.prefill_chunk, n)
        bucket, first = self._window_prefill_device(req, slot, start, end, n)
        st["done"] = end
        st["chunks"] += 1
        self.metrics.inc("prefill_chunks")
        self.metrics.inc("prefill_chunk_tokens", end - start)
        if end == n:
            st["bucket"], st["first"] = bucket, first
            # the TARGET's prompt KV is complete here; the first token is
            # only emitted at _finish_stream, which may wait whole steps
            # for the draft mirror — the prefill_done_s / ttft_s split
            self._record_prefill_done(req)
            if not (self.spec_enabled and st["ddone"] < n):
                return self._finish_stream(slot, st)
        return {"type": "prefill_chunk", "request_id": req.request_id,
                "slot": slot, "from": start, "to": end}

    def _finish_stream(self, slot, st):
        """Both the target chunks and (under speculation) the draft
        mirror are complete: retire the stream and emit the stashed
        first token."""
        del self._chunk_streams[slot]
        self.metrics.observe("chunks_per_prompt", st["chunks"])
        return self._complete_prefill(st["req"], slot, st["bucket"],
                                      st["first"], st["n"])

    # -- decode -------------------------------------------------------------
    def _ensure_tail_pages(self, slot, top):
        """Make the slot's KV positions [npos, top] writable: COW the
        current tail page if it is shared or hash-registered, then draw
        page-boundary allocations from the slot's admission-time
        reservation through `top`. The ONE home of the tail-page
        invariants — plain decode (top == npos) and the speculative
        verify window (top == min(npos + g, limit)) both call it."""
        ps = self.page_size
        pages = self._bt[slot]
        pi = int(self._npos[slot]) // ps
        if pi < len(pages):
            old = pages[pi]
            page, copied = self._alloc.ensure_writable(old)
            if copied:
                with self._phase("stage", slot=slot):
                    self._cow_device(old, page)
                pages[pi] = page
        while len(pages) * ps <= top:
            pages.append(self._alloc.alloc())
            self._resv[slot] -= 1
            self._reserved_total -= 1

    def _decode_device(self, active):
        Pn = self.pages_per_slot
        for slot in active:
            self._ensure_tail_pages(slot, int(self._npos[slot]))
        with self._phase("stage"):
            bt = np.full((self.max_slots, Pn), NULL_PAGE, np.int32)
            for slot in active:
                bt[slot, :len(self._bt[slot])] = self._bt[slot]
            # the share of the table's width the step's rows hold: the
            # pages the paged kernel fetches, over slots x pages a slot
            live = int(np.sum(self._npos[active] // self.page_size + 1))
            self.metrics.observe("decode_live_page_share",
                                 live / (self.max_slots * Pn))
            if self._hybrid:
                nxt = self._hy.decode(bt, active, self._sampling_active(),
                                      self._sampling_args())
            else:
                self._pk, self._pv, nxt = self._decode_v[
                    self._sampling_active()](
                    self.params, jnp.asarray(self._last_tok), self._pk,
                    self._pv, jnp.asarray(bt), jnp.asarray(self._npos),
                    self._cos, self._sin, *self._sampling_args())
        with self._phase("wait"):
            return np.asarray(nxt)

    # -- lifecycle ----------------------------------------------------------
    def _retire(self, slot):
        # the slot stops writing here, so its partial PROMPT tail page is
        # finally frozen: hang it on the radix tree (full pages were
        # registered at prefill; this extends the cached prefix to token
        # granularity — contents beyond the prompt are decode K/V that
        # partial_len keeps unreachable). Only prompt positions are
        # cached: their bytes came from prefill programs, so later hits
        # replay the exact values a fresh prefill would compute.
        req = self.slots.owner(slot)
        whole = req is not None and \
            int(self._npos[slot]) >= req.prompt_ids.size
        if whole:
            n = int(req.prompt_ids.size)
            n_pages = -(-n // self.page_size)
            self._alloc.register_prefix(req.prompt_ids,
                                        self._bt[slot][:n_pages])
        if self._hybrid and req is not None:
            # the snapshot of the state at the prompt's end joins the tree
            # with the prompt's last page
            self._hy.attach(slot, req.prompt_ids, whole)
        self._alloc.release_many(self._bt[slot])
        self._bt[slot] = []
        self._reserved_total -= self._resv.pop(slot, 0)
        if self.spec_enabled:
            self._spec.retire(slot)
        super()._retire(slot)

    # -- preemption ---------------------------------------------------------
    def preempt(self, slot):
        """Evict a DECODING request from its slot without losing work:
        the returned state is the block table (page ids, refcounts still
        held — the allocator cannot hand the pages out or evict them,
        and prefix hits against the prompt's registered pages stay
        COW-safe), the KV write position, and the last token. `resume`
        re-seats it and the continuation is bit-identical to never
        having been preempted: decode depends only on the held pages'
        contents, the block table, `npos`, the last token, and the
        (seed, pos) sampling stream — all preserved. The slot's
        remaining page reservation is refunded while preempted, which is
        the point: a waiting request can use it."""
        req = self.slots.owner(slot)
        if slot in self._chunk_streams:
            raise ValueError(f"slot {slot} is mid-prefill-stream; only "
                             "decoding slots are preemptible")
        if self.spec_enabled:
            raise ValueError("preemption with speculative decoding is "
                             "unsupported (the draft's stripe cache is "
                             "not checkpointed)")
        state = {"req": req, "pages": self._bt[slot],
                 "npos": int(self._npos[slot]),
                 "last_tok": int(self._last_tok[slot]),
                 "resv": self._resv.get(slot, 0)}
        if self._hybrid:
            # the recurrent state leaves the slot with the request, and
            # with it the snapshot waiting for the request's retirement
            state["recurrent"] = self._hy.take_state(slot)
            state["snapshot"] = self._hy.pending.pop(slot, None)
        self._bt[slot] = []
        self._reserved_total -= self._resv.pop(slot, 0)
        self.slots.retire(slot)
        self._npos[slot] = 0
        self._last_tok[slot] = self.pad_id
        self.sampler.clear(slot)
        self.metrics.inc("preemptions")
        return state

    def can_resume(self, state):
        return bool(self.slots.free_count) and \
            state["resv"] <= self._alloc.available - self._reserved_total

    def resume(self, state):
        """Re-seat a preempted request (see `preempt`); returns its new
        slot. Caller must have checked `can_resume`."""
        req = state["req"]
        slot = self._admit(req)
        self._bt[slot] = state["pages"]
        self._resv[slot] = state["resv"]
        self._reserved_total += state["resv"]
        self._npos[slot] = state["npos"]
        self._last_tok[slot] = state["last_tok"]
        if self._hybrid:
            self._hy.put_state(slot, state["recurrent"])
            if state["snapshot"] is not None:
                self._hy.pending[slot] = state["snapshot"]
        self.metrics.inc("resumes")
        return slot

    def reset(self):
        """Forget all requests, block tables, AND the prefix cache (cold
        cache — a warm timed run after reset would be all hits and lie);
        compiled programs and compile counters survive."""
        super().reset()
        # the page pool survives a reset, so its byte gauge must too
        if self._hybrid:
            self._hy.reset()
        else:
            self.metrics.set_gauge("kv_pool_bytes", 2 * sum(
                x.size * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(self._pk)))
        self._reset_host_state()
        if self.spec_enabled:
            self._spec.reset()
