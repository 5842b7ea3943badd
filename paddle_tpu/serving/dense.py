"""The device half of `PagedEngine` for the dense family
(`models/llama_functional.LlamaArgs`: GQA attention + SwiGLU): the page
pools, the RoPE tables, the tensor-parallel placement and the step programs
over them. `PagedEngine` holds the block tables and never a pool.

  - ONE page pool a side, `pk` / `pv` `[L, num_pages, nkv, page_size, hd]`
    (heads-major pages, the layout the Pallas paged decode kernel consumes),
    in the model dtype or, with `kv_dtype="int8"`, a
    `generation.QuantizedKVPage` of int8 codes and per-(page, kv-head)
    absmax scales: prefill scatters quantize whole pages, decode / verify
    writes keep a RUNNING absmax (re-scaling a page's codes in registers
    when a token exceeds its scale) and attention dequantizes inside the
    paged kernel (on TPU it needs page_size % 32 == 0 and head_dim % 128 ==
    0; other shapes ride the dequant-gather fall-back);
  - PREFILL gathers a slot's pages into a contiguous stripe, forwards the
    window at a traced position and scatters the written pages back (one
    program a window bucket); DECODE is one batched paged step through the
    block tables; a copy-on-write clones one page across layers;
  - with a `mesh`, weights take the Megatron split and the pools shard on
    their nkv axis (`serving/tp.py`); every program then runs as one
    shard_map SPMD program (`sharded`, which `SpecDecoder`'s verify programs
    use too);
  - the disaggregated workers' page mover (`extract_pages` /
    `scatter_pages`): pure page-axis data movement over the same layout.

The methods `PagedEngine` calls are `serving/paths.py`'s interface; a dense
request keeps nothing beside its pages, so the per-request-state half of it
is no-ops here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.models import generation as gen
from paddle_tpu.models import llama_functional as lf
from paddle_tpu.serving.sampler import pick as _pick

__all__ = ["DensePath"]


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


@jax.named_scope("pt.kv_gather")
def _extract_pages_traced(pk, pv, pages):
    """Gather the K/V contents of `pages` (int32 [P]) out of the pool:
    every pool leaf — the bf16/f32 arrays, or an int8 `QuantizedKVPage`'s
    codes [L, num_pages, nkv, ps, hd] AND scales [L, num_pages, nkv] —
    has the page axis at axis 1, so one tree_map covers both layouts.
    Pure data movement: the disagg transfer programs are pinned
    collective-free."""
    def take(a):
        return jnp.take(a, pages, axis=1)

    return (jax.tree_util.tree_map(take, pk),
            jax.tree_util.tree_map(take, pv))


@jax.named_scope("pt.kv_write")
def _scatter_pages_traced(pk, pv, pages, data_k, data_v):
    """Write extracted page contents back into a (different) pool at
    fresh page ids `pages` [P] — the inverse of `_extract_pages_traced`,
    leaf-wise over the same axis-1 layout (int8 codes and scales land
    verbatim: no quantization round-trip on migration)."""
    def put(a, d):
        return a.at[:, pages].set(d)

    return (jax.tree_util.tree_map(put, pk, data_k),
            jax.tree_util.tree_map(put, pv, data_v))



class DensePath:
    """Pools, RoPE tables, placement and step programs of one engine."""

    snapshots = 0      # a dense request keeps nothing beside its pages

    def __init__(self, eng):
        self.eng = eng
        args, axis, mesh = eng.args, eng.tp_axis, eng.mesh
        if mesh is not None:
            from paddle_tpu.serving import tp as tp_lib

            self.tp_degree = int(mesh.shape[axis])
            tp_lib.tp_validate(args, self.tp_degree)
            # eager placement: weights land in their Megatron shards once,
            # at construction — never resharded on the hot path
            eng.params = tp_lib.shard_params(eng.params, mesh, axis)
            self.pspecs = tp_lib.llama_tp_specs(eng.params, axis)
            self.poolspec = tp_lib.pool_spec(axis)
        else:
            self.tp_degree = 1
            self.pspecs = self.poolspec = None
        tp_kw = dict(tp_axis=axis if mesh is not None else None,
                     tp_degree=self.tp_degree)

        L = lf.stack_leading_dim(eng.params["layers"])
        hd = lf.head_dim(args)
        dtype = jax.tree_util.tree_leaves(eng.params["embedding"])[0].dtype
        nkv = args.num_kv_heads
        pool_shape = (L, eng.num_pages, nkv, eng.page_size, hd)
        if eng.kv_dtype == "int8":
            # int8 pages + per-(page, kv-head) absmax scales: halves (vs
            # bf16) the KV bytes behind a page, so the same HBM budget
            # holds ~2x the pages -> ~2x the sustained slots. Scales
            # start at 0: the first write into a page sets them
            self.pk = gen.QuantizedKVPage(
                jnp.zeros(pool_shape, jnp.int8),
                jnp.zeros((L, eng.num_pages, nkv), jnp.float32))
            self.pv = gen.QuantizedKVPage(
                jnp.zeros(pool_shape, jnp.int8),
                jnp.zeros((L, eng.num_pages, nkv), jnp.float32))
        else:
            self.pk = jnp.zeros(pool_shape, dtype)
            self.pv = jnp.zeros_like(self.pk)
        self.reset()
        if mesh is not None:
            # both halves of a QuantizedKVPage shard on nkv, so the bf16
            # pool spec applies to the pair as a pytree prefix
            sh = NamedSharding(mesh, self.poolspec)
            self.pk = jax.device_put(self.pk, sh)
            self.pv = jax.device_put(self.pv, sh)
        # 2*max_len: suffix prefills write at [h, h+bucket), which can
        # overshoot max_len before masking trims it
        self.cos, self.sin = lf.rope_tables(2 * eng.max_len, hd,
                                            args.rope_theta)

        donate = eng._donate_enabled()
        rep = P()
        pool = self.poolspec
        prefill_specs = dict(
            in_specs=(self.pspecs, rep, rep, rep, rep, rep, pool, pool, rep,
                      rep, rep, rep, rep, rep),
            out_specs=(pool, pool, rep))
        decode_specs = dict(
            in_specs=(self.pspecs, rep, pool, pool, rep, rep, rep, rep, rep,
                      rep, rep, rep),
            out_specs=(pool, pool, rep))
        self._prefill, self._decode = {}, {}
        for sample in (False, True):
            self._prefill[sample] = self.sharded(
                functools.partial(
                    _paged_prefill_traced, args=args, metrics=eng.metrics,
                    page_size=eng.page_size,
                    pages_per_slot=eng.pages_per_slot, sample=sample,
                    **tp_kw),
                donate=(6, 7) if donate else (), **prefill_specs)
            self._decode[sample] = self.sharded(
                functools.partial(
                    _paged_decode_traced, args=args, metrics=eng.metrics,
                    page_size=eng.page_size, sample=sample, **tp_kw),
                donate=(2, 3) if donate else (), **decode_specs)
        self._copy = self.sharded(
            _copy_page_traced, in_specs=(pool, pool, rep, rep),
            out_specs=(pool, pool), donate=(0, 1) if donate else ())
        # extraction never donates: the pool must survive the gather (the
        # slot retires on the HOST side after the ship)
        self._extract = self.sharded(
            _extract_pages_traced, in_specs=(pool, pool, None),
            out_specs=(pool, pool), donate=())
        self._scatter = self.sharded(
            _scatter_pages_traced, in_specs=(pool, pool, None, pool, pool),
            out_specs=(pool, pool), donate=(0, 1) if donate else ())

    def sharded(self, body, in_specs, out_specs, donate):
        """jit a traced step body, shard_map-wrapped when a mesh is set.
        check_vma stays off for these forward-only programs: the
        checker's value is guarding AD transposes, and serving has no
        gradients."""
        mesh = self.eng.mesh
        if mesh is None:
            return jax.jit(body, donate_argnums=donate)
        sm = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        return jax.jit(sm, donate_argnums=donate)

    def reset(self):
        """An empty engine: the pools survive a reset, so their byte gauge
        must too."""
        self.eng.metrics.set_gauge("kv_pool_bytes", 2 * sum(
            x.size * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves(self.pk)))

    # -- pages ----------------------------------------------------------------
    def copy_page(self, src, dst):
        self.pk, self.pv = self._copy(self.pk, self.pv, jnp.int32(src),
                                      jnp.int32(dst))

    def check_handoff(self):
        """A sequence's pages are all of it: nothing to refuse."""

    def extract_pages(self, pages):
        """The contents of `pages` as pool-shaped trees (a `KVHandoff`'s)."""
        return self._extract(self.pk, self.pv, jnp.asarray(pages))

    def scatter_pages(self, pages, data_k, data_v):
        self.pk, self.pv = self._scatter(
            self.pk, self.pv, jnp.asarray(pages, jnp.int32),
            jax.tree_util.tree_map(jnp.asarray, data_k),
            jax.tree_util.tree_map(jnp.asarray, data_v))

    # -- per-request state beside the pages: none -----------------------------
    def prompt_done(self, slot):
        pass

    def load_snapshot(self, slot, sid):
        pass

    def attach(self, slot, prompt_ids, registered):
        pass

    def take_state(self, slot):
        return None

    def put_state(self, slot, saved):
        pass

    # -- the two step programs ------------------------------------------------
    def prefill(self, ids, start, last_idx, bt_row, new_vec, slot, req,
                sample):
        self.pk, self.pv, first = self._prefill[sample](
            self.eng.params, jnp.asarray(ids), jnp.int32(start),
            jnp.int32(last_idx), jnp.asarray(bt_row), jnp.asarray(new_vec),
            self.pk, self.pv, self.cos, self.sin,
            jnp.float32(req.temperature), jnp.float32(req.top_p),
            jnp.int32(req.top_k), jnp.asarray([req.seed], jnp.int32))
        return first

    def decode(self, bt, active, sample, sampling_args):
        eng = self.eng
        self.pk, self.pv, nxt = self._decode[sample](
            eng.params, jnp.asarray(eng._last_tok), self.pk, self.pv,
            jnp.asarray(bt), jnp.asarray(eng._npos), self.cos, self.sin,
            *sampling_args)
        return nxt
