"""The device half of `PagedEngine` for the dense family
(`models/llama_functional.LlamaArgs`: GQA attention + SwiGLU): the page
pools, the RoPE tables, the tensor-parallel placement and the step programs
over them. `PagedEngine` holds the block tables and never a pool.

  - ONE page pool a side, `pk` / `pv` `[L, num_pages, nkv, page_size, hd]`
    (heads-major pages, the layout the Pallas paged decode kernel consumes),
    in the model dtype or, with `kv_dtype="int8"`, a
    `generation.QuantizedKVPage` of int8 codes and per-(page, kv-head)
    absmax scales: a prefill window quantizes its pages as it writes them
    (absmax over the valid positions, a straddled page's kept half
    dequantized first), decode / verify writes keep a RUNNING absmax
    (re-scaling a page's codes in registers when a token exceeds its scale)
    and attention dequantizes inside the paged kernels (on TPU they need
    page_size % 32 == 0 and head_dim % 128 == 0; other shapes ride the
    jnp fall-backs, which dequantize what they gather);
  - PREFILL writes a window's K / V into the window's own pages and
    attends over the pool through the slot's block table, as far as the
    window's last position (`kernels/paged_prefill_attention.py`; one
    program a window bucket): nothing in it has the table's width; DECODE
    is one batched paged step through the block tables; a copy-on-write
    clones one page across layers;
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
from paddle_tpu.serving.sampler import pick as _pick, seat_token, token_vector

__all__ = ["DensePath"]


def _paged_prefill_traced(params, ids, h, last_idx, bt_row, new_pages,
                          pk, pv, cos, sin, temp, top_p, top_k, seeds, *,
                          args, metrics, page_size, sample=False,
                          tp_axis=None, tp_degree=1):
    """Prefill a suffix window whose first `h` positions are already
    cached: write the window's K / V into the window's own pages and attend
    over the pool through the slot's block table
    (`generation._paged_forward_prefill`).

    ids: [1, sb] window right-padded to a length bucket; h: traced token
    count already cached (prefix hits AND previously prefilled chunks —
    TOKEN-granular under the radix cache, so h may sit mid-page: the
    straddled page is the slot's COW copy of the frozen cached page, whose
    positions below h keep what they hold); last_idx: index of the window's
    last real token WITHIN the block; bt_row: [P] the slot's pages;
    new_pages: [P] the pages the window writes, from the one that holds h
    on (unused entries -> null page 0). One XLA program per window bucket —
    h, last_idx and the page vectors are traced operands, so neither hit
    depth nor chunk position recompiles."""
    metrics.inc("prefill_compiles")
    logits, pk, pv = gen._paged_forward_prefill(
        params, ids, pk, pv, h, last_idx, bt_row, new_pages, cos, sin, args,
        page_size, tp_axis=tp_axis, tp_degree=tp_degree)
    # the emitted token sits at sequence index h + last_idx + 1 — the
    # (seed, position) the offline generate(seeds=...) would use
    first = _pick(logits, sample, temp, top_p, top_k, seeds,
                  h + last_idx + 1)[0]
    return pk, pv, first


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
        # the rows' last tokens: a decode step's output is the next one's
        # operand, a prompt's first token is seated (`seat`)
        self.tokens = token_vector(eng.max_slots, eng.pad_id)
        self.reset()
        if mesh is not None:
            # both halves of a QuantizedKVPage shard on nkv, so the bf16
            # pool spec applies to the pair as a pytree prefix
            sh = NamedSharding(mesh, self.poolspec)
            self.pk = jax.device_put(self.pk, sh)
            self.pv = jax.device_put(self.pv, sh)
            # replicated, as every program returns it
            self.tokens = jax.device_put(self.tokens,
                                         NamedSharding(mesh, P()))
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
                    page_size=eng.page_size, sample=sample, **tp_kw),
                donate=(6, 7) if donate else (), **prefill_specs)
            self._decode[sample] = self.sharded(
                functools.partial(
                    _paged_decode_traced, args=args, metrics=eng.metrics,
                    page_size=eng.page_size, sample=sample, **tp_kw),
                donate=(2, 3) if donate else (), **decode_specs)
        self._copy = self.sharded(
            _copy_page_traced, in_specs=(pool, pool, rep, rep),
            out_specs=(pool, pool), donate=(0, 1) if donate else ())
        # never donates: the vector it is given may be a step's output that
        # the host has not read yet
        self._seat = self.sharded(
            functools.partial(seat_token, metrics=eng.metrics),
            in_specs=(rep, rep, rep), out_specs=rep, donate=())
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

    def landed(self, out):
        """Nothing but the tokens rides a decode step's read-back."""

    # -- the token vector and the two step programs ---------------------------
    def seat(self, slot, token):
        self.tokens = self._seat(self.tokens, jnp.int32(slot),
                                 jnp.asarray(token, jnp.int32))

    def prefill(self, ids, start, last_idx, bt_row, new_vec, slot, req,
                sample):
        eng = self.eng
        # the share of the slot's table the window's attention walks: the
        # pages up to its last position, over pages a slot
        eng.metrics.observe(
            "prefill_live_page_share",
            ((start + last_idx) // eng.page_size + 1) / eng.pages_per_slot)
        self.pk, self.pv, first = self._prefill[sample](
            eng.params, jnp.asarray(ids), jnp.int32(start),
            jnp.int32(last_idx), jnp.asarray(bt_row), jnp.asarray(new_vec),
            self.pk, self.pv, self.cos, self.sin,
            jnp.float32(req.temperature), jnp.float32(req.top_p),
            jnp.int32(req.top_k), jnp.asarray([req.seed], jnp.int32))
        return first

    def decode(self, bt, active, sample, sampling_args):
        eng = self.eng
        # a COPY of the positions: the engine moves them on as soon as this
        # returns, and a host array handed to the device may be read later
        self.pk, self.pv, self.tokens = self._decode[sample](
            eng.params, self.tokens, self.pk, self.pv,
            jnp.asarray(bt), jnp.asarray(eng._npos.copy()), self.cos,
            self.sin, *sampling_args)
        return self.tokens
