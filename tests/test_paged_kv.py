"""Paged KV cache: block allocator, block-table gather kernel, and the
paged serving engine (paddle_tpu/serving/paged_engine.py).

Key properties under test:
  - BlockAllocator: alloc/free accounting, refcount lifecycle, COW on
    shared or tree-registered pages, pool-exhaustion error; the RADIX
    prefix cache (token-granular matches, COW page splits, leaf-LRU
    eviction that never touches referenced or interior pages) and the
    legacy hash-chain policy (insertion-order LRU + descendant
    orphaning so recycled page ids can never serve stale prefixes);
  - the Pallas paged decode-attention kernel (block-table gather with
    per-row page-index prefetch) matches the contiguous-gather XLA
    reference in interpret mode — the tier-1 parity gate for the kernel;
  - PARITY: paged greedy continuous batching is token-for-token equal to
    sequential `generate` AND to the stripe engine on mixed-length
    prompts, float and int8, with and without prefix-cache hits;
  - admission defers (never drops) requests when the page pool can't
    cover the queue head; everything still completes.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import quantized_matmul as qm
from paddle_tpu.models import llama_functional as lf
from paddle_tpu.models.generation import generate, quantize_params
from paddle_tpu.serving import (BlockAllocator, Engine, NULL_PAGE,
                                PagedEngine, PrefixMatch, Request, bucket_for,
                                pages_for)

from phase_ids import (check_identifiers, entries, record_annotations,
                       step_and_check_dispatch)
from step_phases import counting_clock, run_and_collect, synchronous

_INTERPRET = jax.default_backend() != "tpu"

ARGS = lf.LlamaArgs(vocab_size=128, hidden_size=64, intermediate_size=176,
                    num_layers=2, num_heads=4, num_kv_heads=2,
                    rope_theta=10000.0, rms_eps=1e-6, use_flash=False)


@pytest.fixture(scope="module")
def params():
    return lf.init_params(ARGS, jax.random.key(0))


@pytest.fixture(scope="module")
def engine(params):
    # ONE paged engine shared across tests (state drains between serves;
    # compiled programs are reused, keeping the tier-1 subset fast)
    return PagedEngine(params, ARGS, max_slots=2, max_len=64, page_size=8,
                       min_bucket=8)


def _prompts(lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, ARGS.vocab_size, size=n).astype(np.int32)
            for n in lengths]


def _sequential(params, prompts, max_new, eos=None):
    outs = []
    for p in prompts:
        row = np.asarray(generate(params, ARGS, p[None],
                                  max_new_tokens=max_new,
                                  eos_token_id=eos))[0]
        outs.append(row[len(p):])
    return outs


class TestPagesFor:
    def test_worst_case_page_math(self):
        # last written position is prompt + new - 2
        assert pages_for(1, 1, 8) == 1
        assert pages_for(8, 1, 8) == 1     # writes [0, 7]
        assert pages_for(8, 2, 8) == 2     # writes position 8
        assert pages_for(10, 6, 8) == 2    # last write at 14
        assert pages_for(10, 8, 8) == 3    # last write at 16


class TestBlockAllocator:
    def test_alloc_free_refcount_lifecycle(self):
        a = BlockAllocator(num_pages=5, page_size=4)
        assert a.capacity == 4 and a.available == 4
        p = a.alloc()
        assert p != NULL_PAGE and a.refcount(p) == 1
        assert a.pages_in_use == 1
        a.ref(p)
        assert a.refcount(p) == 2
        a.release(p)
        assert a.refcount(p) == 1 and a.pages_in_use == 1
        a.release(p)
        # unregistered page goes straight back to the free list
        assert a.refcount(p) == 0 and a.available == 4

    def test_exhaustion_raises(self):
        a = BlockAllocator(num_pages=3, page_size=4)
        a.alloc(), a.alloc()
        with pytest.raises(RuntimeError, match="exhausted"):
            a.alloc()

    def test_cow_exclusive_noop_shared_copies(self):
        a = BlockAllocator(num_pages=6, page_size=4)
        p = a.alloc()
        assert a.ensure_writable(p) == (p, False)   # exclusive: no-op
        a.ref(p)                                    # now shared
        new, copied = a.ensure_writable(p)
        assert copied and new != p
        assert a.refcount(p) == 1 and a.refcount(new) == 1

    def test_cow_on_registered_page(self):
        # a hash-registered page must be COW'd even at refcount 1: a
        # write would corrupt contents future prefix hits rely on
        a = BlockAllocator(num_pages=6, page_size=2)
        toks = [1, 2, 3]
        p = a.alloc()
        a.register_prefix(toks, [p])
        new, copied = a.ensure_writable(p)
        assert copied and new != p

    def test_prefix_match_register_and_strict_prefix_cap(self):
        a = BlockAllocator(num_pages=8, page_size=2)
        toks = [1, 2, 3, 4, 5, 6]
        assert a.match_prefix(toks) == PrefixMatch([], None, 0, 0)  # cold
        p0, p1, p2 = a.alloc(), a.alloc(), a.alloc()
        a.register_prefix(toks, [p0, p1, p2])
        # full hit is capped at a STRICT prefix: the final token is never
        # served from cache (its logits are the point of the prefill) —
        # under the radix policy the cap turns the last full page into a
        # token-granular PARTIAL hit of its first token
        m = a.match_prefix(toks, commit=False)
        assert m.pages == [p0, p1] and m.partial_page == p2
        assert m.partial_len == 1 and m.matched == 5
        # longer prompt sharing the prefix hits all three pages fully
        m = a.match_prefix(toks + [7, 8], commit=False)
        assert m.pages == [p0, p1, p2] and m.partial_page is None
        assert m.matched == 6
        # mid-page divergence: token-granular partial hit on page 1
        m = a.match_prefix([1, 2, 3, 9, 5, 6], commit=False)
        assert m.pages == [p0] and m.partial_page == p1
        assert m.partial_len == 1 and m.matched == 3
        # page-boundary divergence: full pages only
        m = a.match_prefix([1, 2, 9, 9, 5, 6], commit=False)
        assert m.pages == [p0] and m.partial_page is None
        # commit refs the full hits AND the partial page
        a.match_prefix(toks + [7])
        assert [a.refcount(p) for p in (p0, p1, p2)] == [2, 2, 2]

    def test_register_partial_tail_page_radix_vs_hash(self):
        # a prompt ending mid-page registers its partial tail under the
        # radix policy (token-granular future hits); hash trims to full
        # pages — the PR-8 baseline behavior
        toks = [1, 2, 3, 4, 5, 6]              # 1.5 pages at ps=4
        query = [1, 2, 3, 4, 5, 6, 7, 8]
        a = BlockAllocator(num_pages=8, page_size=4)
        p0, p1 = a.alloc(), a.alloc()
        a.register_prefix(toks, [p0, p1])
        m = a.match_prefix(query, commit=False)
        assert m.pages == [p0] and m.partial_page == p1
        assert m.partial_len == 2 and m.matched == 6
        b = BlockAllocator(num_pages=8, page_size=4, policy="hash")
        q0, q1 = b.alloc(), b.alloc()
        b.register_prefix(toks, [q0, q1])
        m = b.match_prefix(query, commit=False)
        assert m.pages == [q0] and m.partial_page is None and m.matched == 4

    def test_release_registered_goes_evictable_and_revives(self):
        a = BlockAllocator(num_pages=4, page_size=2)
        p = a.alloc()
        a.register_prefix([5, 6], [p])
        a.release(p)
        assert a.refcount(p) == 0
        assert a.available == 3            # still allocatable (evictable)
        hits = a.match_prefix([5, 6, 7])   # revive
        assert hits.pages == [p] and a.refcount(p) == 1

    def test_eviction_lru_order_hash_policy(self):
        a = BlockAllocator(num_pages=4, page_size=2, policy="hash")
        pages = {}
        for tag, toks in (("r1", [1, 1]), ("r2", [2, 2]), ("r3", [3, 3])):
            p = a.alloc()
            a.register_prefix(toks, [p])
            pages[tag] = p
        # release order r2, r1, r3 -> LRU eviction order r2, r1, r3
        for tag in ("r2", "r1", "r3"):
            a.release(pages[tag])
        assert a.free_count == 0 and a.available == 3
        got = [a.alloc() for _ in range(3)]
        assert got == [pages["r2"], pages["r1"], pages["r3"]]
        # evicted chains are gone: no stale hits for recycled page ids
        assert a.match_prefix([2, 2, 9], commit=False).pages == []

    def test_radix_leaf_lru_eviction_by_hit_recency(self):
        # radix eviction is LRU over the last committed HIT (or
        # registration), not over release order: a leaf re-hit after
        # younger registrations outlives them under pressure
        a = BlockAllocator(num_pages=8, page_size=2)
        pages = {}
        for tag, toks in (("r1", [1, 1]), ("r2", [2, 2]), ("r3", [3, 3])):
            p = a.alloc()
            a.register_prefix(toks, [p])
            pages[tag] = p
        for tag in ("r1", "r2", "r3"):
            a.release(pages[tag])
        a.match_prefix([1, 1, 9])          # revive r1: now most recent
        a.release(pages["r1"])
        drained = [a.alloc() for _ in range(a.free_count)]
        assert pages["r1"] not in drained
        got = [a.alloc() for _ in range(3)]
        assert got == [pages["r2"], pages["r3"], pages["r1"]]
        assert a.match_prefix([2, 2, 9], commit=False).pages == []

    def test_eviction_orphans_descendants_hash_policy(self):
        a = BlockAllocator(num_pages=5, page_size=2, policy="hash")
        toks = [1, 2, 3, 4]
        p0, p1 = a.alloc(), a.alloc()
        a.register_prefix(toks, [p0, p1])
        a.release(p0)
        a.release(p1)
        # exhaust free pages, forcing eviction of p0 (LRU root)
        a.alloc(), a.alloc()
        evicted_root = a.alloc()
        assert evicted_root == p0
        # p1's chain key embedded p0 — it must be unreachable AND free
        assert a.match_prefix(toks + [9], commit=False).pages == []
        assert a.alloc() == p1
        with pytest.raises(RuntimeError):
            a.alloc()


class TestRadixTree:
    """Adversarial invariants of the radix prefix cache: COW-split
    refcount exactness, leaf-LRU never touching referenced or interior
    pages, and token-granular matching across splits."""

    def test_cow_split_refcount_and_sharing_exactness(self):
        a = BlockAllocator(num_pages=16, page_size=4)
        t1 = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]          # 2.5 pages
        pg = [a.alloc() for _ in range(3)]
        a.register_prefix(t1, pg)
        t2 = t1[:6] + [99, 98, 97, 96]                 # diverges mid page 1
        m = a.match_prefix(t2)                          # commit
        assert m.pages == [pg[0]] and m.partial_page == pg[1]
        assert m.partial_len == 2 and m.matched == 6
        assert a.refcount(pg[0]) == 2 and a.refcount(pg[1]) == 2
        # engine-style COW: swap the partial ref for a writable copy
        copy, copied = a.ensure_writable(pg[1])
        assert copied and copy not in pg
        assert a.refcount(pg[1]) == 1 and a.refcount(copy) == 1
        # registering the divergent branch splits the t1 leaf mid-edge;
        # refcounts must be untouched by registration
        extra = a.alloc()
        a.register_prefix(t2, [pg[0], copy, extra])
        assert a.refcount(pg[0]) == 2 and a.refcount(copy) == 1
        # both branches now match token-granularly, sharing pg[0]
        m1 = a.match_prefix(t1, commit=False)
        assert m1.pages == [pg[0], pg[1]] and m1.partial_page == pg[2]
        m2 = a.match_prefix(t2, commit=False)
        assert m2.pages == [pg[0], copy] and m2.partial_page == extra
        # a third branch diverging inside the SPLIT edge re-splits
        t3 = t1[:3] + [55, 55]
        m3 = a.match_prefix(t3, commit=False)
        assert m3.pages == [] and m3.partial_page == pg[0]
        assert m3.partial_len == 3 and m3.matched == 3
        # release everything: every page reclaimable, none orphaned or
        # double-counted
        for p in (pg[0], pg[0], pg[1], pg[2], copy, extra):
            a.release(p)
        assert a.pages_in_use == 0
        assert a.available == a.capacity

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_kept_reclaimable_count_equals_a_fresh_walk(self, seed):
        # `available` keeps its walk of the radix tree until the tree or
        # the cached set changes; after every operation of a random
        # sequence (shared prefixes, splits, hits, releases, evictions) it
        # must read what a walk from nothing reads
        rng = np.random.default_rng(seed)
        a = BlockAllocator(num_pages=40, page_size=2)
        index = a._index

        def fresh():
            index._reclaimable = (None, 0)
            return a.free_count + index.reclaimable()

        held = []                            # (tokens, pages) of live requests
        for _ in range(400):
            op = rng.integers(4)
            if op == 0 and a.available >= 6:
                toks = [int(t) for t in rng.integers(1, 3, rng.integers(2, 9))]
                m = a.match_prefix(toks)
                pages = list(m.pages)
                need = -(-len(toks) // 2) - len(pages)
                pages += a.alloc_many(need)
                a.register_prefix(toks, pages)
                held.append((toks, pages))
            elif op == 1 and held:
                _, pages = held.pop(int(rng.integers(len(held))))
                a.release_many(pages)
            elif op == 2 and a.available > 0:
                held.append(([], [a.alloc()]))
            elif op == 3 and held:
                toks, _ = held[int(rng.integers(len(held)))]
                a.match_prefix(toks, commit=False)
            kept = a.available
            assert kept == fresh()

    def test_leaf_lru_never_evicts_referenced_or_interior_pages(self):
        a = BlockAllocator(num_pages=16, page_size=2)
        sys = [7, 8, 7, 8]                  # 2 shared system pages
        s1 = sys + [1, 1, 1]
        s2 = sys + [2, 2, 2]
        pg1 = [a.alloc() for _ in range(4)]
        a.register_prefix(s1, pg1)
        pg2 = pg1[:2] + [a.alloc(), a.alloc()]
        a.register_prefix(s2, pg2)
        held = pg1[2]                       # pin s1's divergent page
        for p in (pg1[0], pg1[1], pg1[3], pg2[2], pg2[3]):
            a.release(p)
        # drain the free list, then force evictions: only the UNPINNED
        # leaf tails may go (pg1[3]; then s2's leaf outside-in)
        evicted = [a.alloc() for _ in range(a.free_count + 3)]
        assert set(evicted[-3:]) == {pg1[3], pg2[3], pg2[2]}
        assert a.refcount(held) == 1        # untouched
        # the shared system pages are interior below a referenced page:
        # unreachable for eviction, so the pool is now exhausted even
        # though they sit at refcount 0
        assert a.refcount(pg1[0]) == 0 and a.is_registered(pg1[0])
        assert a.available == 0
        with pytest.raises(RuntimeError, match="exhausted"):
            a.alloc()
        # the hot prefix is still hittable
        m = a.match_prefix(sys + [9], commit=False)
        assert m.pages == [pg1[0], pg1[1]]

    def test_eviction_peels_leaf_outside_in_and_prunes_empty_nodes(self):
        a = BlockAllocator(num_pages=8, page_size=2)
        toks = [1, 2, 3, 4, 5, 6]
        pg = [a.alloc() for _ in range(3)]
        a.register_prefix(toks, pg)
        for p in pg:
            a.release(p)
        drained = [a.alloc() for _ in range(a.free_count)]
        # pages peel strictly from the tail toward the root; each evicted
        # page truncates the leaf to a page-aligned edge
        assert a.alloc() == pg[2]
        m = a.match_prefix(toks + [7], commit=False)
        assert m.pages == [pg[0], pg[1]] and m.matched == 4
        assert a.alloc() == pg[1]
        assert a.match_prefix(toks + [7], commit=False).pages == [pg[0]]
        assert a.alloc() == pg[0]
        # tree fully pruned: cold match, and the pool is exhausted
        assert a.match_prefix(toks + [7], commit=False).matched == 0
        with pytest.raises(RuntimeError):
            a.alloc()


# one row of the table: name -> (shape, pos per row, how the table is built,
# pages a compute block; None = what the wrapper works out from the shapes).
# ps = 16; with 2 pages a block a block boundary falls at position 32.
_KERNEL_CASES = {
    # rows at different depths, a page shared by two rows, null-page tails
    "shared_pages_null_tails": dict(
        b=3, pos=[49, 127, 33], ppb=None,
        bt=[[3, 7, 2, 11], [5, 6, 8, 9, 10, 12, 13, 14], [3, 15, 16]]),
    # an identity table: pages in table order ARE the contiguous cache
    "identity_table": dict(b=2, P=4, pos=[17, 63], bt="identity", ppb=None),
    "public_dispatch_hd128": dict(b=2, nh=2, nkv=1, hd=128, P=4,
                                  pos=[10, 60], bt="identity", ppb=None,
                                  via="dispatch"),
    "int8_pool": dict(b=3, hd=128, ps=32, P=4, NP=9, pos=[5, 37, 120],
                      bt="random", int8=True, via="dispatch", ppb=None,
                      atol=2e-5),
    "int8_pool_two_page_blocks": dict(b=3, hd=128, ps=32, P=4, NP=9,
                                      pos=[0, 64, 127], bt="random",
                                      int8=True, ppb=2, atol=2e-5),
    "pos_0": dict(pos=[0, 127], ppb=2),
    "pos_last_of_first_page": dict(pos=[15, 127], ppb=2),
    "pos_first_of_second_page": dict(pos=[16, 127], ppb=2),
    "pos_block_boundary_minus_1": dict(pos=[31, 127], ppb=2),
    "pos_block_boundary": dict(pos=[32, 127], ppb=2),
    "pos_block_boundary_plus_1": dict(pos=[33, 127], ppb=2),
    "full_table_every_row": dict(b=3, pos=[127, 127, 127], ppb=2),
    # 5 live pages in blocks of 3, which divide neither them nor P = 8
    "block_divides_neither_pages_nor_table": dict(b=3, pos=[70, 127, 40],
                                                  ppb=3),
    "one_page_blocks": dict(b=3, pos=[70, 5, 127], ppb=1),
    "whole_table_in_one_block": dict(b=3, pos=[70, 5, 127], ppb=8),
    # a free slot (position 0, every entry the null page) between full rows
    "free_row_beside_full_ones": dict(b=3, pos=[127, 0, 127],
                                      bt="free_middle", ppb=2),
    "free_row_first_and_last": dict(b=4, pos=[0, 90, 127, 0],
                                    bt="free_ends", ppb=2),
    # one kv head: what a tensor-parallel shard of the pool holds
    "one_local_kv_head": dict(b=3, nh=4, nkv=1, pos=[49, 127, 3], ppb=2),
    "bf16_pool": dict(b=3, pos=[49, 127, 33], ppb=2, dtype="bfloat16",
                      atol=2e-2),
    "bf16_pool_derived_block": dict(b=3, hd=128, pos=[49, 127, 0],
                                    ppb=None, dtype="bfloat16", atol=2e-2,
                                    via="dispatch"),
}


class TestPagedDecodeKernel:
    def _pool(self, rng, num_pages, nkv, ps, hd, dtype=jnp.float32):
        pk = jnp.asarray(rng.normal(size=(num_pages, nkv, ps, hd)), dtype)
        pv = jnp.asarray(rng.normal(size=(num_pages, nkv, ps, hd)), dtype)
        return pk, pv

    def _case(self, seed, b=2, nh=4, nkv=2, hd=32, ps=16, P=8, NP=None,
              pos=(), bt="permuted", dtype="float32", int8=False, **_):
        """Operands of one case: q, the pools (and their scales), the
        tables and the positions. Pages of a row are drawn without
        replacement from a shuffled pool unless the case says otherwise;
        entries past a row's last live page stay the null page (0)."""
        rng = np.random.default_rng(seed)
        NP = NP or b * P + 1
        dtype = jnp.dtype(dtype)
        q = jnp.asarray(rng.normal(size=(b, 1, nh, hd)),
                        jnp.float32 if int8 else dtype)
        if int8:
            pk, pv = (jnp.asarray(rng.integers(-127, 128,
                                               size=(NP, nkv, ps, hd)),
                                  jnp.int8) for _ in range(2))
            scales = tuple(jnp.asarray(rng.uniform(0.5, 2.0, size=(NP, nkv)),
                                       jnp.float32) for _ in range(2))
        else:
            pk, pv = self._pool(rng, NP, nkv, ps, hd, dtype)
            scales = (None, None)
        table = np.zeros((b, P), np.int32)
        if bt == "identity":
            table[:] = np.arange(1, 1 + b * P).reshape(b, P)
        elif bt == "random":
            table[:] = rng.integers(1, NP, size=(b, P))
        elif isinstance(bt, list):
            for r, pages in enumerate(bt):
                table[r, :len(pages)] = pages
        else:
            free = bt in ("free_middle", "free_ends")
            pages = rng.permutation(np.arange(1, NP))
            for r in range(b):
                live = 0 if free and pos[r] == 0 else pos[r] // ps + 1
                table[r, :live], pages = pages[:live], pages[live:]
        return (q, pk, pv, jnp.asarray(table),
                jnp.asarray(pos, jnp.int32)) + scales

    def _run(self, ops, ppb=None, via="kernel", interpret=_INTERPRET):
        q, pk, pv, bt, pos, ks, vs = ops
        sm = 1.0 / np.sqrt(q.shape[-1])
        if via == "dispatch":
            assert qm.paged_decode_supported(q.shape, pk.shape, bt.shape,
                                             pk.dtype.itemsize)
            with qm.fused_dispatch(enabled=True, interpret=_INTERPRET):
                return qm.paged_decode_attention(q, pk, pv, bt, pos,
                                                 k_scale=ks, v_scale=vs)
        return qm._paged_decode_attention_pallas(
            q, pk, pv, bt, pos, sm, interpret, ks, vs, pages_per_block=ppb)

    @pytest.mark.parametrize("name", list(_KERNEL_CASES))
    def test_kernel_matches_gather_reference(self, name):
        """The Pallas paged kernel (a grid step a row, a page loop bounded
        by the row's position, one copy a live page for all kv heads) must
        match the contiguous-gather XLA reference."""
        case = _KERNEL_CASES[name]
        ops = self._case(sorted(_KERNEL_CASES).index(name), **case)
        q, pk, pv, bt, pos, ks, vs = ops
        out = self._run(ops, case.get("ppb"), case.get("via", "kernel"))
        ref = qm._paged_decode_attention_xla(
            q.astype(jnp.float32),
            *((pk, pv) if ks is not None else
              (pk.astype(jnp.float32), pv.astype(jnp.float32))),
            bt, pos, 1.0 / np.sqrt(q.shape[-1]), ks, vs)
        assert out.dtype == q.dtype
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), atol=case.get("atol",
                                                                  1e-4))

    @pytest.mark.parametrize("kind", ["bfloat16", "int8"])
    def test_page_base_reads_one_layers_run_of_a_stack(self, kind):
        """`page_base` moves the copies and nothing else: over three
        layers' pools laid end to end, with the middle layer's base and
        its own scales, the kernel gives what it gives over that layer's
        pool alone, bit for bit (the other layers hold NaN, or codes under
        NaN scales it is not handed)."""
        int8 = kind == "int8"
        ops = self._case(11, b=3, hd=128, ps=32, P=4, NP=9,
                         pos=[5, 37, 120], bt="random", int8=int8,
                         dtype="float32" if int8 else kind)
        q, pk, pv, bt, pos, ks, vs = ops
        fill = 127 if int8 else np.nan
        stack = [jnp.concatenate([jnp.full_like(x, fill), x,
                                  jnp.full_like(x, fill)]) for x in (pk, pv)]
        sm = 1.0 / np.sqrt(q.shape[-1])
        want = self._run(ops, ppb=2)
        got = qm._paged_decode_attention_pallas(
            q, *stack, bt, pos, sm, _INTERPRET, ks, vs, pages_per_block=2,
            page_base=jnp.int32(pk.shape[0]))
        assert np.isfinite(np.asarray(got, np.float32)).all()
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
        ref = qm._paged_decode_attention_xla(q, *stack, bt, pos, sm, ks, vs,
                                             page_base=pk.shape[0])
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32), atol=2e-2)

    @pytest.mark.parametrize("kind", ["float32", "bfloat16", "int8"])
    def test_pages_past_the_last_live_one_are_never_read(self, kind):
        """Every table entry past a row's last live page points at a page
        of NaN (for an int8 pool: a page whose scales are NaN): the output
        is finite and equal to the reference over clean tables. Neither
        the copies nor the sums may touch a page the row does not hold."""
        int8 = kind == "int8"
        case = dict(b=3, hd=128 if int8 else 32, ps=32 if int8 else 16,
                    pos=[0, 70 if not int8 else 140, 33], int8=int8,
                    dtype="float32" if int8 else kind)
        q, pk, pv, bt, pos, ks, vs = self._case(77, **case)
        poison = max(set(range(1, pk.shape[0])) - set(np.asarray(bt).ravel()))
        if int8:
            ks, vs = ks.at[poison].set(jnp.nan), vs.at[poison].set(jnp.nan)
        else:
            pk, pv = pk.at[poison].set(jnp.nan), pv.at[poison].set(jnp.nan)
        ps = pk.shape[2]
        dead = (np.arange(bt.shape[1])[None, :]
                > (np.asarray(pos) // ps)[:, None])
        poisoned = jnp.asarray(np.where(dead, poison, np.asarray(bt)))
        # the TPU interpreter starts every buffer as NaN, as a page that
        # was not copied must be taken to be, and raises on a read past
        # an array's end; it also looks for a copy racing the sums
        from jax.experimental.pallas import tpu as pltpu

        tpu_like = (pltpu.InterpretParams(detect_races=True)
                    if _INTERPRET else False)
        for ppb in (1, 2, 3):
            out = self._run((q, pk, pv, poisoned, pos, ks, vs), ppb,
                            interpret=tpu_like)
            assert np.isfinite(np.asarray(out, np.float32)).all()
            ref = self._run((q, pk, pv, bt, pos, ks, vs), ppb)
            np.testing.assert_array_equal(np.asarray(out, np.float32),
                                          np.asarray(ref, np.float32))
        want = qm._paged_decode_attention_xla(
            q.astype(jnp.float32),
            *((pk, pv) if int8 else (pk.astype(jnp.float32),
                                     pv.astype(jnp.float32))),
            bt, pos, 1.0 / np.sqrt(q.shape[-1]),
            None if ks is None else ks.at[poison].set(1.0),
            None if vs is None else vs.at[poison].set(1.0))
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want),
                                   atol=2e-2 if kind == "bfloat16" else 1e-4)

    def test_pages_per_block_follows_the_shapes(self):
        # Mistral's widths: 512 positions a block = 8 pages of 64; a table
        # narrower than that is one block; a wide page is held to the VMEM
        # budget (K and V, two buffers, every kv head of a page)
        assert qm._paged_pages_per_block((896, 8, 64, 128), 128, 2) == 8
        assert qm._paged_pages_per_block((20, 2, 16, 32), 8, 4) == 8
        assert qm._paged_pages_per_block((64, 8, 16, 128), 128, 2) == 32
        assert qm._paged_pages_per_block((64, 32, 256, 128), 64, 2) == 1
        assert qm._paged_pages_per_block((64, 1, 64, 128), 128, 1) == 8

    def test_supports(self):
        pool, bt = (9, 1, 16, 128), (2, 4)
        assert qm.paged_decode_supported((2, 1, 2, 128), pool, bt, 4)
        # unsupported shapes: multi-query, lane-misaligned hd, odd page
        assert not qm.paged_decode_supported((2, 2, 2, 128), pool, bt)
        assert not qm.paged_decode_supported((2, 1, 2, 64),
                                             (9, 1, 16, 64), bt, 4)
        assert not qm.paged_decode_supported((2, 1, 2, 128),
                                             (9, 1, 12, 128), bt, 4)
        # int8 pools are eligible at ps % 32 == 0 (the int8 sublane
        # minimum); the engine's ps=8 fixtures take the gather fallback
        assert qm.paged_decode_supported((3, 1, 4, 128), (9, 2, 32, 128),
                                         (3, 4), 1)
        assert not qm.paged_decode_supported((3, 1, 4, 128),
                                             (9, 2, 16, 128), (3, 4), 1)
        # one page with every kv head, K and V, twice, must fit in VMEM
        assert not qm.paged_decode_supported((2, 1, 64, 128),
                                             (9, 64, 256, 128), bt, 2)

    def test_int8_gather_dequantizes_exactly(self):
        # the oracle's own dequantizing gather against a manual dequant
        rng = np.random.default_rng(11)
        b, nkv, hd, ps, NP, P = 3, 2, 128, 32, 9, 4
        kq = jnp.asarray(rng.integers(-127, 128, size=(NP, nkv, ps, hd)),
                         jnp.int8)
        ks = jnp.asarray(rng.uniform(0.5, 2.0, size=(NP, nkv)), jnp.float32)
        bt = jnp.asarray(rng.integers(1, NP, size=(b, P)), jnp.int32)
        man = (np.asarray(kq)[np.asarray(bt)].astype(np.float32)
               * (np.asarray(ks)[np.asarray(bt)] / 127.0)[..., None, None])
        man = np.swapaxes(man, 1, 2).reshape(b, nkv, P * ps, hd)
        np.testing.assert_allclose(
            np.asarray(qm.paged_gather(kq, bt, scale=ks)), man, atol=1e-6)

    def test_cow_device_copy(self):
        from paddle_tpu.serving.dense import _copy_page_traced

        rng = np.random.default_rng(3)
        pk = jnp.asarray(rng.normal(size=(2, 5, 2, 4, 8)), jnp.float32)
        pv = jnp.asarray(rng.normal(size=(2, 5, 2, 4, 8)), jnp.float32)
        nk, nv = _copy_page_traced(pk, pv, jnp.int32(3), jnp.int32(1))
        np.testing.assert_array_equal(np.asarray(nk[:, 1]),
                                      np.asarray(pk[:, 3]))
        np.testing.assert_array_equal(np.asarray(nv[:, 1]),
                                      np.asarray(pv[:, 3]))
        np.testing.assert_array_equal(np.asarray(nk[:, 2]),
                                      np.asarray(pk[:, 2]))

    def test_int8_pool_kernel_matches_dequant_gather_oracle(self):
        """The int8-pool kernel's in-registers dequant (scores scaled by
        this page's k absmax, the accumulator contribution by its v
        absmax) must match dequantizing in the gather — across rows at
        different depths, including a watermark mid-page."""
        from paddle_tpu.models.generation import QuantizedKVPage

        rng = np.random.default_rng(11)
        b, nh, nkv, hd, ps, NP, P = 3, 4, 2, 128, 32, 9, 4
        q = jnp.asarray(rng.normal(size=(b, 1, nh, hd)), jnp.float32)
        kq = jnp.asarray(rng.integers(-127, 128, size=(NP, nkv, ps, hd)),
                         jnp.int8)
        vq = jnp.asarray(rng.integers(-127, 128, size=(NP, nkv, ps, hd)),
                         jnp.int8)
        ks = jnp.asarray(rng.uniform(0.5, 2.0, size=(NP, nkv)), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.5, 2.0, size=(NP, nkv)), jnp.float32)
        bt = jnp.asarray(rng.integers(1, NP, size=(b, P)), jnp.int32)
        pos = jnp.asarray([5, 37, 120], jnp.int32)
        # int8 pools are eligible at ps % 32 == 0 (the int8 sublane
        # minimum); the engine's ps=8 fixtures take the gather fallback
        assert qm.paged_decode_supported(q.shape, kq.shape, bt.shape, 1)
        assert not qm.paged_decode_supported(q.shape, (NP, nkv, 16, hd),
                                             bt.shape, 1)
        ref = qm._paged_decode_attention_xla(q, kq, vq, bt, pos,
                                             1.0 / np.sqrt(hd), ks, vs)
        with qm.fused_dispatch(enabled=True, interpret=_INTERPRET):
            out = qm.paged_decode_attention(q, kq, vq, bt, pos,
                                            k_scale=ks, v_scale=vs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
        # dequantizing paged_gather is itself exact vs manual dequant
        man = (np.asarray(kq)[np.asarray(bt)].astype(np.float32)
               * (np.asarray(ks)[np.asarray(bt)] / 127.0)[..., None, None])
        man = np.swapaxes(man, 1, 2).reshape(b, nkv, P * ps, hd)
        np.testing.assert_allclose(
            np.asarray(qm.paged_gather(kq, bt, scale=ks)), man, atol=1e-6)

    def test_int8_cow_copy_clones_codes_and_scales(self):
        from paddle_tpu.models.generation import QuantizedKVPage
        from paddle_tpu.serving.dense import _copy_page_traced

        rng = np.random.default_rng(5)
        mk = lambda: QuantizedKVPage(
            jnp.asarray(rng.integers(-127, 128, size=(2, 5, 2, 4, 8)),
                        jnp.int8),
            jnp.asarray(rng.uniform(0.1, 3.0, size=(2, 5, 2)), jnp.float32))
        pk, pv = mk(), mk()
        nk, nv = _copy_page_traced(pk, pv, jnp.int32(3), jnp.int32(1))
        np.testing.assert_array_equal(np.asarray(nk.q[:, 1]),
                                      np.asarray(pk.q[:, 3]))
        np.testing.assert_array_equal(np.asarray(nk.scale[:, 1]),
                                      np.asarray(pk.scale[:, 3]))
        np.testing.assert_array_equal(np.asarray(nv.scale[:, 1]),
                                      np.asarray(pv.scale[:, 3]))
        np.testing.assert_array_equal(np.asarray(nk.q[:, 2]),
                                      np.asarray(pk.q[:, 2]))

    def test_page_reuse_resets_running_scale_at_offset_zero(self):
        """A page drawn from the free list carries its previous owner's
        codes and scale; the first live write (always offset 0 — pages
        fill sequentially) must RESTART the running absmax, not inherit
        the stale one, or a tiny token would be crushed to zero codes."""
        from paddle_tpu.models.generation import (QuantizedKVPage,
                                                  _kv_quant_write)

        nkv, ps, hd = 2, 4, 8
        stale = QuantizedKVPage(
            jnp.full((3, nkv, ps, hd), 100, jnp.int8),
            jnp.full((3, nkv), 1000.0, jnp.float32))
        tok = jnp.full((1, nkv, hd), 0.25, jnp.float32)
        page = jnp.asarray([2], jnp.int32)
        out = _kv_quant_write(stale, page, jnp.asarray([0], jnp.int32), tok)
        np.testing.assert_allclose(np.asarray(out.scale[2]), 0.25)
        np.testing.assert_array_equal(np.asarray(out.q[2, :, 0]),
                                      np.full((nkv, hd), 127, np.int8))
        # mid-page writes keep the running scale (and re-scale codes when
        # a louder token arrives)
        out2 = _kv_quant_write(out, page, jnp.asarray([1], jnp.int32),
                               jnp.full((1, nkv, hd), 0.5, jnp.float32))
        np.testing.assert_allclose(np.asarray(out2.scale[2]), 0.5)
        np.testing.assert_array_equal(np.asarray(out2.q[2, :, 0]),
                                      np.full((nkv, hd), 64, np.int8))


# name -> (page, offset) of each row's write into a pool of 7 pages of 8.
# Page 0 is the null page: rows that do not decode all name it.
_WRITE_CASES = {
    "first_offset_of_a_page": ([3, 5, 1], [0, 0, 0]),
    "last_offset_of_a_page": ([3, 5, 1], [7, 7, 7]),
    "a_row_on_the_last_page_of_the_pool": ([6, 2, 4], [3, 0, 7]),
    "rows_that_do_not_decode_share_the_null_page": ([0, 4, 0, 0],
                                                    [2, 5, 2, 6]),
}


class TestPageWrite:
    """A token's K/V goes into the pool as a read-modify-write of the
    row's own page (`generation._write_rows`, the dense and the hybrid
    path's one write): the pool afterwards is what the per-token scatter
    `pool.at[page, :, off].set(new)` left, bit for bit."""

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("name", list(_WRITE_CASES))
    def test_page_write_equals_the_per_token_scatter(self, name, dtype):
        from paddle_tpu.models.generation import _write_rows

        page, off = (jnp.asarray(x, jnp.int32) for x in _WRITE_CASES[name])
        rng = np.random.default_rng(len(name))
        pool = jnp.asarray(rng.normal(size=(7, 2, 8, 16)), dtype)
        new = jnp.asarray(rng.normal(size=(page.shape[0], 2, 16)),
                          jnp.float32)
        got = np.asarray(jax.jit(_write_rows)(pool, new, page, off),
                         np.float32)
        want = np.asarray(pool.at[page, :, off].set(new.astype(pool.dtype)),
                          np.float32)
        # the null page is the garbage sink: of the rows that share it one
        # page's worth survives, which one is nobody's business
        np.testing.assert_array_equal(got[1:], want[1:])
        if int(np.sum(np.asarray(page) == 0)) <= 1:
            np.testing.assert_array_equal(got[0], want[0])
        assert np.isfinite(got[0]).all()

    def test_int8_write_into_a_stack_keeps_each_pages_running_scale(self):
        """`_kv_quant_write` addresses the carried pool: a layer's write
        at `base + page` of the layers' pools laid end to end leaves that
        layer's run as a write into its own pool leaves it (codes
        re-scaled under a louder token, the scale restarted at offset 0)
        and every other layer's run as it was."""
        from paddle_tpu.models.generation import (QuantizedKVPage,
                                                  _kv_quant_write)

        rng = np.random.default_rng(5)
        L, NP, nkv, ps, hd = 3, 5, 2, 4, 8
        q = jnp.asarray(rng.integers(-127, 128, (L, NP, nkv, ps, hd)),
                        jnp.int8)
        sc = jnp.asarray(rng.uniform(0.1, 1.0, (L, NP, nkv)), jnp.float32)
        page = jnp.asarray([2, 4, 0, 0], jnp.int32)
        off = jnp.asarray([0, 3, 1, 2], jnp.int32)   # a restart, a running
        tok = jnp.asarray(rng.normal(size=(4, nkv, hd)) * 2.0, jnp.float32)
        flat = QuantizedKVPage(q.reshape(L * NP, nkv, ps, hd),
                               sc.reshape(L * NP, nkv))
        got = jax.jit(_kv_quant_write)(flat, 1 * NP + page, off, tok)
        want = _kv_quant_write(QuantizedKVPage(q[1], sc[1]), page, off, tok)
        got_q = np.asarray(got.q).reshape(L, NP, nkv, ps, hd)
        got_s = np.asarray(got.scale).reshape(L, NP, nkv)
        np.testing.assert_array_equal(got_q[1, 1:], np.asarray(want.q)[1:])
        np.testing.assert_array_equal(got_s[1, 1:],
                                      np.asarray(want.scale)[1:])
        for other in (0, 2):
            np.testing.assert_array_equal(got_q[other], np.asarray(q[other]))
            np.testing.assert_array_equal(got_s[other],
                                          np.asarray(sc[other]))


class TestDecodeScanCarriesThePools:
    """`_paged_forward_decode` carries the stacked pools through the
    layer scan and each layer writes and reads its own run of pages
    there: the logits AND the pools it returns are those of a plain loop
    that hands `_layer_step_paged` one layer's own pool at a time."""

    ARGS = ARGS._replace(num_layers=3)
    PS, NP, P, B = 8, 13, 4, 3

    @staticmethod
    def _loop(params, ids, pk, pv, bt, pos, cos, sin, args, ps, tp_axis=None,
              tp_degree=1):
        from paddle_tpu.models import generation as gen

        def layer(tree, l):
            return jax.tree_util.tree_map(lambda a: a[l], tree)

        h = jnp.take(params["embedding"], ids, axis=0)
        ks, vs = [], []
        for l in range(args.num_layers):
            h, k_l, v_l = gen._layer_step_paged(
                layer(params["layers"], l), h, layer(pk, l), layer(pv, l),
                bt, pos, cos, sin, args, ps, tp_axis, tp_degree)
            ks.append(k_l)
            vs.append(v_l)
        stack = lambda xs: jax.tree_util.tree_map(
            lambda *a: jnp.stack(a), *xs)
        h = lf.rms_norm(h, params["final_norm"], args.rms_eps)
        logits = gen._wmm(h[:, -1, :], params["lm_head"])
        return logits.astype(jnp.float32), stack(ks), stack(vs)

    def _programs(self, mesh):
        from paddle_tpu.models import generation as gen

        args, ps = self.ARGS, self.PS
        if mesh is None:
            return [jax.jit(lambda *a, f=f: f(*a, args, ps))
                    for f in (gen._paged_forward_decode, self._loop)]
        from jax.sharding import PartitionSpec
        from paddle_tpu.serving import tp

        params = jax.eval_shape(
            lambda: lf.init_params(args, jax.random.key(0)))
        rep, pool = PartitionSpec(), tp.pool_spec()
        specs = (tp.llama_tp_specs(params), rep, pool, pool, rep, rep, rep,
                 rep)
        return [jax.jit(jax.shard_map(
            lambda *a, f=f: f(*a, args, ps, "mp", 2), mesh=mesh,
            in_specs=specs, out_specs=(rep, pool, pool), check_vma=False))
            for f in (gen._paged_forward_decode, self._loop)]

    @pytest.mark.parametrize("sharded", [False, True],
                             ids=["one_device", "mp2_mesh"])
    @pytest.mark.parametrize("kind", ["bfloat16", "int8"])
    def test_scan_equals_a_loop_over_the_layers(self, kind, sharded):
        from paddle_tpu.models import generation as gen

        mesh = None
        if sharded:
            from paddle_tpu.distributed.mesh_utils import single_axis_mesh

            mesh = single_axis_mesh("mp", 2)
        args, ps, NP, P, B = self.ARGS, self.PS, self.NP, self.P, self.B
        L, nkv = args.num_layers, args.num_kv_heads
        hd = args.hidden_size // args.num_heads
        rng = np.random.default_rng(17)
        # float32 arithmetic over a bf16 (or int8) pool: what the two
        # programs round, they round at the same places
        params = lf.init_params(args, jax.random.key(3))
        shape = (L, NP, nkv, ps, hd)
        if kind == "int8":
            pk, pv = (gen.QuantizedKVPage(
                jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
                jnp.asarray(rng.uniform(0.5, 2.0, shape[:3]), jnp.float32))
                for _ in range(2))
        else:
            pk, pv = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
                      for _ in range(2))
        # row 0 crosses onto a fresh page at the second step, row 1 ends
        # on the last offset of the last page of its table, row 2 does not
        # decode (a table of null pages, as the engine stages it)
        bt = jnp.asarray([[3, 7, 0, 0], [5, 6, 8, 9], [0, 0, 0, 0]],
                         jnp.int32)
        pos0 = np.asarray([7, P * ps - 3, 0], np.int32)
        cos, sin = lf.rope_tables(P * ps, hd, args.rope_theta)
        scan, loop = self._programs(mesh)
        a = b = (pk, pv)
        for step in range(3):
            ids = jnp.asarray(rng.integers(1, args.vocab_size, (B, 1)),
                              jnp.int32)
            pos = jnp.asarray(pos0 + [step, step, 0], jnp.int32)
            la, *a = scan(params, ids, *a, bt, pos, cos, sin)
            lb, *b = loop(params, ids, *b, bt, pos, cos, sin)
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
            for x, y in zip(jax.tree_util.tree_leaves(a),
                            jax.tree_util.tree_leaves(b)):
                assert x.shape == y.shape and x.dtype == y.dtype
                # all but the null page of every layer
                np.testing.assert_array_equal(
                    np.asarray(x[:, 1:], np.float32),
                    np.asarray(y[:, 1:], np.float32))
        # and the three steps did write
        assert not np.array_equal(
            np.asarray(jax.tree_util.tree_leaves(a)[0], np.float32),
            np.asarray(jax.tree_util.tree_leaves(pk)[0], np.float32))


class TestPagedEngineParity:
    def test_greedy_matches_sequential_mixed_lengths(self, params, engine):
        prompts = _prompts([3, 5, 9, 12, 17])
        ref = _sequential(params, prompts, max_new=8)
        reqs = engine.serve([Request(p, 8) for p in prompts])
        for r, s in zip(reqs, ref):
            assert r.finished and r.finish_reason == "length"
            np.testing.assert_array_equal(np.asarray(r.token_ids), s)
        # fully drained: pages either free or cached-for-reuse, none leaked
        assert engine._alloc.pages_in_use == 0
        assert engine._alloc.available == engine._alloc.capacity

    def test_matches_stripe_engine_on_same_trace(self, params, engine):
        prompts = _prompts([4, 11, 6], seed=7)
        stripe = Engine(params, ARGS, max_slots=2, max_len=64, min_bucket=8)
        a = stripe.serve([Request(p, 6) for p in prompts])
        b = engine.serve([Request(p, 6) for p in prompts])
        for ra, rb in zip(a, b):
            assert ra.token_ids == rb.token_ids

    def test_prefix_cache_hit_parity_and_metrics(self, params):
        # 2 pages of shared system prompt + unique suffixes; second and
        # third requests must HIT the cache and still match sequential
        eng = PagedEngine(params, ARGS, max_slots=2, max_len=64,
                          page_size=8, min_bucket=8)
        rng = np.random.default_rng(41)
        prefix = rng.integers(1, ARGS.vocab_size, size=16).astype(np.int32)
        prompts = [np.concatenate([prefix, s])
                   for s in _prompts([5, 3, 9], seed=43)]
        ref = _sequential(params, prompts, max_new=6)
        reqs = eng.serve([Request(p, 6) for p in prompts])
        for r, s in zip(reqs, ref):
            np.testing.assert_array_equal(np.asarray(r.token_ids), s)
        m = eng.metrics.summary()["counters"]
        assert m["prefix_tokens_hit"] >= 2 * 16   # requests 2+3 hit 16 each
        assert m["prefix_pages_hit"] >= 4
        assert m.get("cow_copies", 0) == 0        # natural flow never COWs
        # serving the SAME prompts again is a pure cache walk for prefixes
        hits_before = m["prefix_tokens_hit"]
        reqs2 = eng.serve([Request(p, 6) for p in prompts])
        for r, s in zip(reqs2, ref):
            np.testing.assert_array_equal(np.asarray(r.token_ids), s)
        m2 = eng.metrics.summary()["counters"]
        assert m2["prefix_tokens_hit"] > hits_before

    def test_greedy_matches_sequential_int8(self, params):
        qp = quantize_params(params)
        prompts = _prompts([4, 7, 13], seed=5)
        ref = _sequential(qp, prompts, max_new=6)
        eng = PagedEngine(qp, ARGS, max_slots=2, max_len=64, page_size=8,
                          min_bucket=8)
        reqs = eng.serve([Request(p, 6) for p in prompts])
        for r, s in zip(reqs, ref):
            np.testing.assert_array_equal(np.asarray(r.token_ids), s)

    def test_int8_prefix_hits_match_sequential(self, params):
        qp = quantize_params(params)
        rng = np.random.default_rng(51)
        prefix = rng.integers(1, ARGS.vocab_size, size=16).astype(np.int32)
        prompts = [np.concatenate([prefix, s])
                   for s in _prompts([4, 6], seed=53)]
        ref = _sequential(qp, prompts, max_new=5)
        eng = PagedEngine(qp, ARGS, max_slots=2, max_len=64, page_size=8,
                          min_bucket=8)
        reqs = eng.serve([Request(p, 5) for p in prompts])
        assert eng.metrics.summary()["counters"]["prefix_tokens_hit"] >= 16
        for r, s in zip(reqs, ref):
            np.testing.assert_array_equal(np.asarray(r.token_ids), s)


class TestPagedDecodeStep:
    def test_public_api_matches_stripe_decode_step(self, params):
        """generation.paged_decode_step (the public per-step API) must
        agree with the contiguous decode_step when the block tables lay
        the same KV out page-by-page."""
        from paddle_tpu.models.generation import (decode_step,
                                                  paged_decode_step,
                                                  prefill)

        ids = np.array([[5, 11, 7, 2], [9, 3, 1, 8]], np.int32)
        logits, ck, cv = prefill(params, ARGS, ids, max_len=16)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        pos = jnp.asarray([4, 4], jnp.int32)
        l_ref, ck_ref, cv_ref = decode_step(params, ARGS, tok, ck, cv,
                                            pos, 16)
        # lay the stripe caches out as pages: row r's page i = slot cache
        # [r, :, i*ps:(i+1)*ps]; pool axis order [L, pages, nkv, ps, hd]
        ps, P, b = 8, 2, 2
        bt = np.array([[1, 2], [3, 4]], np.int32)
        pool_shape = (ARGS.num_layers, 1 + b * P, ARGS.num_kv_heads, ps,
                      ARGS.hidden_size // ARGS.num_heads)
        pk = np.zeros(pool_shape, np.float32)
        pv = np.zeros(pool_shape, np.float32)
        for r in range(b):
            for i in range(P):
                pk[:, bt[r, i]] = np.asarray(ck)[:, r, :, i * ps:(i + 1) * ps]
                pv[:, bt[r, i]] = np.asarray(cv)[:, r, :, i * ps:(i + 1) * ps]
        l_paged, npk, npv = paged_decode_step(
            params, ARGS, tok, jnp.asarray(pk), jnp.asarray(pv),
            jnp.asarray(bt), pos, page_size=ps)
        np.testing.assert_array_equal(np.asarray(l_ref), np.asarray(l_paged))
        # the new k/v landed in each row's tail page at offset pos % ps
        for r in range(b):
            np.testing.assert_array_equal(
                np.asarray(npk)[:, bt[r, 0], :, 4],
                np.asarray(ck_ref)[:, r, :, 4])
            np.testing.assert_array_equal(
                np.asarray(npv)[:, bt[r, 0], :, 4],
                np.asarray(cv_ref)[:, r, :, 4])


class TestPagedScheduling:
    def test_eos_retires_and_slot_readmits(self, params, engine):
        prompts = _prompts([3, 5, 7], seed=11)
        base = _sequential(params, prompts, max_new=6)
        eos0 = int(base[0][2])
        ref = _sequential(params, prompts, max_new=6, eos=eos0)

        def upto(row):
            idx = np.nonzero(row == eos0)[0]
            return row[: idx[0] + 1] if idx.size else row

        reqs = engine.serve(
            [Request(p, 6, eos_token_id=eos0) for p in prompts])
        for r, s in zip(reqs, ref):
            assert r.finished
            np.testing.assert_array_equal(np.asarray(r.token_ids), upto(s))
        assert engine.slots.free_count == engine.max_slots
        assert engine._alloc.pages_in_use == 0

    def test_admission_defers_on_page_pressure(self, params):
        # capacity 5 pages, 2 pages/request -> at most 2 concurrent even
        # though 3 slots exist; everything still completes, nothing drops
        eng = PagedEngine(params, ARGS, max_slots=3, max_len=32,
                          page_size=8, num_pages=6, min_bucket=8)
        prompts = _prompts([10, 10, 10, 10], seed=61)
        assert pages_for(10, 6, 8) == 2
        ref = _sequential(params, prompts, max_new=6)
        reqs = eng.serve([Request(p, 6) for p in prompts])
        for r, s in zip(reqs, ref):
            np.testing.assert_array_equal(np.asarray(r.token_ids), s)
        m = eng.metrics.summary()
        assert m["gauges"]["active_slots"]["max"] <= 2
        assert m["gauges"]["pages_free"]["value"] == 5

    def test_oversized_request_rejected(self, params, engine):
        with pytest.raises(ValueError, match="KV pages"):
            # pool is 2 slots * 8 pages; a request needing more must be
            # rejected at submit, not wedged in the queue forever
            PagedEngine(engine.params, ARGS, max_slots=2, max_len=64,
                        page_size=8, num_pages=4,
                        min_bucket=8).submit(
                Request(np.ones(40, np.int32), 8))

    def test_decode_compile_count_bounded(self, params):
        lengths = [2, 3, 5, 9, 11, 15]
        eng = PagedEngine(params, ARGS, max_slots=2, max_len=32,
                          page_size=8, min_bucket=8)
        eng.serve([Request(p, 2) for p in _prompts(lengths, seed=19)])
        m = eng.metrics.summary()["counters"]
        assert m["decode_compiles"] == 1
        assert m["prefill_compiles"] <= 3   # suffix buckets: 8, 16, 32


class TestSpecDecodePaged:
    """Speculative decoding at the PAGE level: accepted draft tokens'
    K/V must land in the slot's tail pages exactly where plain decode
    puts them (checked through the `paged_gather` oracle — the same
    gather that backs the kernel parity tests), and a worst-case
    all-rejected round must roll the verify window's allocations back
    to a state bit-identical to plain decode's."""

    def _spec_engine(self, p, **kw):
        from paddle_tpu.models.generation import draft_from_params

        dp, da = draft_from_params(p, ARGS, 1)
        return PagedEngine(p, ARGS, max_slots=2, max_len=64, page_size=8,
                           min_bucket=8, draft_params=dp, draft_args=da,
                           spec_tokens=3, **kw)

    def test_accepted_tokens_in_tail_pages_match_paged_gather_oracle(
            self, params):
        """Drive a speculative and a plain engine over the same request,
        stop mid-flight once the committed tokens have crossed a page
        boundary, and gather each pool through its block table: every
        committed position's K/V must agree — i.e. the batched verify
        forward scattered accepted tokens into the freshly allocated
        tail pages exactly as one-token-at-a-time decode would (page ids
        may differ; the gather normalizes the mapping away)."""
        (p,) = _prompts([12], seed=71)
        # read step by step beside the draft engine's, which never looks
        # ahead: the plain engine's positions are read at depth 0 too
        plain = synchronous(PagedEngine(params, ARGS, max_slots=2,
                                        max_len=64, page_size=8,
                                        min_bucket=8))
        spec = self._spec_engine(params)
        rs = spec.submit(Request(p, 40))
        rp = plain.submit(Request(p, 40))
        spec.step(), plain.step()                      # prefill
        while int(spec._npos[0]) < 25 and not rs.finished:
            spec.step()
        while int(plain._npos[0]) < int(spec._npos[0]):
            plain.step()
        npos = int(spec._npos[0])
        assert not rs.finished and npos == int(plain._npos[0])
        assert rp.token_ids[:len(rs.token_ids)] == rs.token_ids
        ps = spec.page_size
        prompt_pages = -(-p.size // ps)
        assert len(spec._bt[0]) > prompt_pages         # tail pages in use
        assert spec.metrics.summary()["counters"]["spec_rounds"] > 0

        def gathered(eng, pool):
            bt = np.full((1, eng.pages_per_slot), NULL_PAGE, np.int32)
            bt[0, :len(eng._bt[0])] = eng._bt[0]
            rows = [qm.paged_gather(pool[l], jnp.asarray(bt))
                    for l in range(pool.shape[0])]
            return np.asarray(jnp.stack(rows))[:, 0, :, :npos]

        for pool_s, pool_p in ((spec.path.pk, plain.path.pk),
                               (spec.path.pv, plain.path.pv)):
            got, want = gathered(spec, pool_s), gathered(plain, pool_p)
            # tail positions really carry K/V (not zeros/null garbage)
            assert np.abs(got[:, :, prompt_pages * ps:]).max() > 0
            # verify writes vs single-token decode writes: same values up
            # to reduction-order ulps (shapes differ between the two
            # programs, so bitwise equality is not the contract)
            np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)

    def test_all_rejected_round_state_matches_plain_decode(self, params):
        """Worst-case rollback: an adversarial draft whose every token
        the target rejects. Each round commits exactly 1 token (the
        target's own), and after EVERY round the block tables, page
        refcounts, free/available counts and reservations are
        bit-identical to a plain engine decoding the same request —
        the speculative window leaves no trace in the allocator."""
        (p,) = _prompts([20], seed=51)
        ref = _sequential(params, [p], max_new=10)[0]
        used = set(ref.tolist()) | set(p.tolist())
        bad = next(t for t in range(1, ARGS.vocab_size) if t not in used)

        # compared after every step: the plain engine's block tables and
        # reservations are read at depth 0, as the draft engine's always are
        plain = synchronous(PagedEngine(params, ARGS, max_slots=2,
                                        max_len=64, page_size=8,
                                        min_bucket=8))
        spec = self._spec_engine(params)
        spec._spec._propose_device = \
            lambda forced, n_forced, start, sample=False: (np.full(
                (spec.max_slots, spec.spec_tokens), bad, np.int32), None)

        def state(eng):
            return (tuple(tuple(row) for row in eng._bt),
                    tuple(tuple(eng._alloc.refcount(pg) for pg in row)
                          for row in eng._bt),
                    eng._alloc.free_count, eng._alloc.available,
                    dict(eng._resv), eng._reserved_total)

        rp = plain.submit(Request(p, 10))
        rs = spec.submit(Request(p, 10))
        plain.step(), spec.step()            # prefill
        assert state(plain) == state(spec)
        while not rs.finished:
            ev = spec.step()
            assert ev["type"] == "spec_decode"
            (committed,) = ev["tokens"].values()
            assert len(committed) == 1       # every draft token rejected
            plain.step()
            assert state(plain) == state(spec)
        assert rp.token_ids == rs.token_ids == list(ref)
        c = spec.metrics.summary()["counters"]
        assert c["spec_pages_rewound"] > 0   # the window did alloc pages
        assert c["draft_tokens_accepted"] == 0


class TestAdmissionPeekStaleness:
    """_peek_hits memoizes the admission-scan prefix match per request;
    the memo MUST be invalidated by any prefix-index mutation between
    the peek and the admit, or the worst-case page reservation is
    computed against a hit set that no longer exists."""

    def test_memo_hit_and_eviction_invalidates(self, params):
        eng = PagedEngine(params, ARGS, max_slots=2, max_len=64,
                          page_size=8, min_bucket=8, prefill_chunk=8)
        rng = np.random.default_rng(23)
        prompt = rng.integers(1, ARGS.vocab_size, 24).astype(np.int32)
        eng.serve([Request(prompt, 4)])      # warm: registers the pages
        queued = Request(np.concatenate(
            [prompt, rng.integers(1, ARGS.vocab_size, 5).astype(np.int32)]),
            4)
        peek1 = eng._peek_hits(queued)
        assert peek1.matched >= 24 - eng.page_size
        assert peek1.pages, "warm cache must produce full-page hits"
        # same version -> the memoized object comes back, no re-walk
        assert eng._peek_hits(queued) is peek1
        # EVICT between peek and admit: drain the pool so every cached
        # page is recycled, then the stale hit set must not survive
        ver = eng._alloc.prefix_version
        while True:
            try:
                eng._alloc.alloc()
            except RuntimeError:
                break
        assert eng._alloc.prefix_version != ver
        peek2 = eng._peek_hits(queued)
        assert peek2 is not peek1
        assert peek2.matched == 0 and peek2.pages == []

    def test_registration_invalidates(self, params):
        eng = PagedEngine(params, ARGS, max_slots=2, max_len=64,
                          page_size=8, min_bucket=8, prefill_chunk=8)
        rng = np.random.default_rng(29)
        prompt = rng.integers(1, ARGS.vocab_size, 20).astype(np.int32)
        queued = Request(prompt, 4)
        cold = eng._peek_hits(queued)
        assert cold.matched == 0
        eng.serve([Request(prompt.copy(), 4)])   # registers the prefix
        warm = eng._peek_hits(queued)
        assert warm is not cold and warm.matched > 0


class TestRadixEngineParity:
    """Mid-page-divergence parity: radix greedy serving must equal
    sequential generate() token-for-token while hitting MORE cached
    prefix tokens than the hash baseline on the same trace."""

    def _divergent_prompts(self, seed=97):
        rng = np.random.default_rng(seed)
        base = rng.integers(1, ARGS.vocab_size, 21).astype(np.int32)
        extra = [rng.integers(1, ARGS.vocab_size, k).astype(np.int32)
                 for k in (5, 9, 13)]
        return [np.concatenate([base, e]) for e in extra] + [base.copy()]

    def _run(self, p, prompts, ref, policy, max_new=6):
        eng = PagedEngine(p, ARGS, max_slots=2, max_len=64, page_size=8,
                          min_bucket=8, prefix_policy=policy)
        reqs = eng.serve([Request(pr, max_new) for pr in prompts])
        for r, s in zip(reqs, ref):
            np.testing.assert_array_equal(np.asarray(r.token_ids), s)
        assert eng._alloc.pages_in_use == 0
        assert eng._alloc.available == eng._alloc.capacity
        return eng.metrics.summary()["counters"]

    def test_bf16_parity_and_radix_hit_gain(self, params):
        prompts = self._divergent_prompts()
        ref = _sequential(params, prompts, max_new=6)
        radix = self._run(params, prompts, ref, "radix")
        hash_ = self._run(params, prompts, ref, "hash")
        assert radix["prefix_tokens_hit"] > hash_["prefix_tokens_hit"]
        assert radix.get("prefix_partial_hits", 0) >= 1
        assert radix.get("radix_splits", 0) >= 1
        assert radix.get("cow_copies", 0) >= 1     # the split's page copy
        assert hash_.get("cow_copies", 0) == 0

    def test_int8_weights_parity(self, params):
        qp = quantize_params(params)
        prompts = self._divergent_prompts(seed=101)
        ref = _sequential(qp, prompts, max_new=5)
        radix = self._run(qp, prompts, ref, "radix", max_new=5)
        assert radix.get("prefix_partial_hits", 0) >= 1


    @pytest.mark.parametrize("kv_dtype", [None, "int8"])
    def test_window_bucket_below_a_page_crosses_the_page(self, params,
                                                         kv_dtype):
        """`min_bucket` < `page_size`: after a mid-page hit (28 of 16-token
        pages) a 6-token suffix in a bucket of 8 covers positions 28..33,
        the tail of one page and the head of the next. Both are written:
        no position is read before its own token wrote it."""
        rng = np.random.default_rng(131)
        base = rng.integers(1, ARGS.vocab_size, 28).astype(np.int32)
        prompts = [np.concatenate([base, rng.integers(
            1, ARGS.vocab_size, k).astype(np.int32)]) for k in (2, 6)]
        # the first request ends inside the second page: the third is fresh
        new = (2, 6)
        ref = [_sequential(params, [p], max_new=n)[0]
               for p, n in zip(prompts, new)]
        eng = PagedEngine(params, ARGS, max_slots=1, max_len=64,
                          page_size=16, min_bucket=4, kv_dtype=kv_dtype)
        # a position nobody wrote holds anything: here, what would win
        # every softmax it entered
        eng.path.pk, eng.path.pv = jax.tree_util.tree_map(
            lambda a: jnp.full_like(a, 100), (eng.path.pk, eng.path.pv))
        reqs = eng.serve([Request(p, n) for p, n in zip(prompts, new)])
        assert eng.metrics.summary()["counters"]["prefix_tokens_hit"] == 28
        for r, s in zip(reqs, ref):
            if kv_dtype is None:
                np.testing.assert_array_equal(np.asarray(r.token_ids), s)
            else:
                assert np.mean(np.asarray(r.token_ids) == s) >= 0.8
        assert eng._alloc.pages_in_use == 0


class TestInt8KVPool:
    """kv_dtype='int8' swaps the page pools for QuantizedKVPage pairs
    (int8 codes + per-(page, kv-head) absmax scales). The parity bar is
    TOP-1 AGREEMENT with sequential generate, not bit-exactness: a COW
    split of a partially-filled page dequantizes then requantizes under
    a new page absmax, which can perturb codes by ±1. On this test model
    agreement is empirically 1.00; the asserted floor is 0.8 per row."""

    AGREEMENT_BAR = 0.8

    def _agreement(self, reqs, ref):
        return [float(np.mean(np.asarray(r.token_ids) == s))
                for r, s in zip(reqs, ref)]

    def _run(self, p, prompts, policy):
        eng = PagedEngine(p, ARGS, max_slots=2, max_len=64, page_size=8,
                          min_bucket=8, prefix_policy=policy,
                          kv_dtype="int8")
        reqs = eng.serve([Request(pr, 6) for pr in prompts])
        assert eng._alloc.pages_in_use == 0
        return eng, reqs

    def test_agreement_hit_gain_and_pool_bytes(self, params):
        from paddle_tpu.models.generation import QuantizedKVPage

        prompts = TestRadixEngineParity()._divergent_prompts(seed=113)
        ref = _sequential(params, prompts, max_new=6)
        radix, r_reqs = self._run(params, prompts, "radix")
        hash_, h_reqs = self._run(params, prompts, "hash")
        for agr in (self._agreement(r_reqs, ref),
                    self._agreement(h_reqs, ref)):
            assert min(agr) >= self.AGREEMENT_BAR, agr
        rc = radix.metrics.summary()["counters"]
        hc = hash_.metrics.summary()["counters"]
        assert rc["prefix_tokens_hit"] > hc["prefix_tokens_hit"]
        assert rc.get("prefix_partial_hits", 0) >= 1
        assert rc.get("cow_copies", 0) >= 1
        assert isinstance(radix.path.pk, QuantizedKVPage)
        # gauge = exact pytree bytes (int8 codes + f32 scales); the test
        # params are f32, so the quantized pool is ~1/4 the default here
        # (~1/2 under bf16 params)
        base = PagedEngine(params, ARGS, max_slots=2, max_len=64,
                           page_size=8, min_bucket=8)
        b8 = radix.metrics.summary()["gauges"]["kv_pool_bytes"]["value"]
        bb = base.metrics.summary()["gauges"]["kv_pool_bytes"]["value"]
        assert b8 == 2 * sum(x.size * x.dtype.itemsize for x in
                             jax.tree_util.tree_leaves(radix.path.pk))
        assert b8 <= bb // 2

    def test_spec_decode_int8_agreement(self, params):
        eng = PagedEngine(params, ARGS, max_slots=2, max_len=64,
                          page_size=8, min_bucket=8, kv_dtype="int8",
                          draft_params=params, draft_args=ARGS,
                          spec_tokens=3)
        prompts = _prompts([12, 20], seed=61)
        ref = _sequential(params, prompts, max_new=6)
        reqs = eng.serve([Request(p, 6) for p in prompts])
        agr = self._agreement(reqs, ref)
        assert min(agr) >= self.AGREEMENT_BAR, agr
        assert eng._alloc.pages_in_use == 0

    def test_kv_dtype_validation(self, params):
        with pytest.raises(ValueError, match="kv_dtype"):
            PagedEngine(params, ARGS, max_slots=2, max_len=64,
                        page_size=8, min_bucket=8, kv_dtype="fp8")


@pytest.mark.slow
class TestPagedSoak:
    def test_shared_prefix_trace_replay(self, params):
        from tools.serving_trace import make_trace, trace_stats

        trace = make_trace(seed=7, n_requests=24,
                           mean_interarrival_steps=1.0,
                           prompt_len_choices=(3, 5, 7, 9, 12),
                           new_tokens_choices=(4, 8),
                           vocab_size=ARGS.vocab_size,
                           shared_prefix_len=16, shared_prefix_ratio=0.75)
        stats = trace_stats(trace)
        assert stats["shared_prefix_requests"] >= 12
        eng = PagedEngine(params, ARGS, max_slots=4, max_len=64,
                          page_size=8, min_bucket=8)
        reqs = eng.replay(trace)
        assert all(r.finished for r in reqs)
        for t, r in list(zip(trace, reqs))[::5]:
            ref = _sequential(params, [np.asarray(t["prompt"])],
                              max_new=t["max_new_tokens"])[0]
            np.testing.assert_array_equal(np.asarray(r.token_ids), ref)
        m = eng.metrics.summary()["counters"]
        assert m["prefix_tokens_hit"] > 0
        assert m["decode_compiles"] == 1
        assert eng._alloc.pages_in_use == 0


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-x", "-q"]))


class TestStepPhases:
    """Each step type of the paged engine (prefill, prefill_chunk, decode,
    one speculative round) is split over the four phases: every phase gains
    exactly one sample a step and the samples sum to the step span less the
    clock reads outside any phase (arithmetic under a counting clock)."""

    @pytest.fixture(scope="class")
    def chunked(self, params):
        return PagedEngine(params, ARGS, max_slots=2, max_len=64,
                           page_size=8, min_bucket=8, prefill_chunk=16)

    @pytest.mark.parametrize("step_type,seed", [
        ("prefill", 31), ("prefill_chunk", 35), ("decode", 36)])
    def test_four_phases_tile_the_step(self, chunked, monkeypatch,
                                       step_type, seed):
        counting_clock(monkeypatch)
        # a 40-token prompt streams in three chunks; the short ones prefill
        # in one step and decode beside it (a seed of its own each time:
        # the shared engine's prefix cache would swallow a repeated prompt)
        for p in _prompts([40, 6, 9], seed=seed):
            chunked.submit(Request(p, 4))
        seen = run_and_collect(chunked,
                               ["prefill", "prefill_chunk", "decode"])
        # a call's `stage` is the NEXT program's. Two decode steps find none
        # to dispatch ahead and only read and emit: the run's last, and the
        # one whose two rows end by length while the queue's head waits for
        # one of their slots (freed when their tokens are read)
        unstaged = [p for p in seen["decode"] if p["stage"] == 0]
        assert seen["decode"][-1]["stage"] == 0 and len(unstaged) <= 2
        for phases in seen[step_type]:
            assert phases["stage"] >= 1 or phases in unstaged
            assert phases["wait"] >= 1
            # page allocation, the radix walk and the admission scan are
            # scheduling; a chunk that is not the prompt's last emits
            # nothing beyond the step's own gauges
            assert phases["schedule"] >= 1 and phases["emit"] >= 1

    def test_speculative_round_reuses_the_four_phases(self, params,
                                                      monkeypatch):
        from paddle_tpu.models.generation import draft_from_params

        dp, da = draft_from_params(params, ARGS, 1)
        eng = PagedEngine(params, ARGS, max_slots=2, max_len=64, page_size=8,
                          min_bucket=8, draft_params=dp, draft_args=da,
                          spec_tokens=3)
        counting_clock(monkeypatch)
        for p in _prompts([7, 12], seed=32):
            eng.submit(Request(p, 6))
        seen = run_and_collect(eng, ["prefill", "spec_decode"])
        for phases in seen["spec_decode"]:
            # propose and verify each stage and wait once
            assert phases["stage"] >= 2 and phases["wait"] >= 2
            assert phases["emit"] >= 1
        obs = eng.metrics.summary()["observations"]
        assert "tokens_per_decode_step" not in obs

    def test_cow_page_copy_is_staged_inside_scheduling(self, params,
                                                       monkeypatch):
        """`_ensure_tail_pages` schedules, dispatches a page copy, then
        schedules again: the copy is its own stage span and the step's
        totals still add up."""
        eng = PagedEngine(params, ARGS, max_slots=2, max_len=64, page_size=8,
                          min_bucket=8)
        (base,) = _prompts([12], seed=33)
        eng.serve([Request(base, 2)])
        copies = eng.metrics.counter("cow_copies")
        counting_clock(monkeypatch)
        # a prefix hit that ends mid-page takes a copy-on-write split
        eng.submit(Request(np.concatenate([base, base[:3]]), 3))
        run_and_collect(eng, ["prefill", "decode"])
        assert eng.metrics.counter("cow_copies") > copies

    def test_dispatch_is_one_sample_a_step_within_stage(self, chunked,
                                                        monkeypatch):
        """`serve.stage_dispatch_s`, the fifth observation: the `stage`
        entries that are the path's call. A decode step and a prefill
        window each build their arrays first, so both halves are there."""
        counting_clock(monkeypatch)
        for p in _prompts([40, 6], seed=37):
            chunked.submit(Request(p, 4))
        seen = set()
        while chunked.queue or chunked.slots.active_slots:
            ev, phases, dispatch = step_and_check_dispatch(chunked)
            if chunked.slots.active_slots:
                assert 1 <= dispatch < phases["stage"], (ev, phases,
                                                         dispatch)
            else:
                # the last call: nothing was left to dispatch ahead
                assert dispatch == phases["stage"] == 0
            seen.add(ev["type"])
        assert seen == {"prefill", "prefill_chunk", "decode"}

    def test_phase_entries_say_kind_part_and_size(self, chunked,
                                                  monkeypatch):
        """Every `stage` entry of a decode step, a prefill window and a page
        copy carries `kind` and `part`, every `wait` entry `kind`; a
        decode's `rows` is the rows it decoded and a window's `tokens`,
        `bucket`, `start` are its arguments."""
        windows, decoded = [], []
        window, decode = chunked._window_prefill_device, \
            chunked._decode_device

        def spy_window(req, slot, start, end, n):
            windows.append(dict(request_id=req.request_id, slot=slot,
                                start=start, tokens=end - start,
                                bucket=bucket_for(end - start,
                                                  chunked.min_bucket,
                                                  chunked.max_len)))
            return window(req, slot, start, end, n)

        def spy_decode(active):
            decoded.append(len(active))
            return decode(active)

        monkeypatch.setattr(chunked, "_window_prefill_device", spy_window)
        monkeypatch.setattr(chunked, "_decode_device", spy_decode)
        (base, other) = _prompts([44, 7], seed=38)
        chunked.serve([Request(base, 2)])
        seen = record_annotations(monkeypatch)
        del windows[:], decoded[:]
        copies = chunked.metrics.counter("cow_copies")
        # a 40-token prompt in three chunks beside a short one, and a prefix
        # hit that ends mid-page (a copy-on-write page copy)
        chunked.serve([Request(np.concatenate([base[:12], base[:3]]), 3),
                       Request(other, 5)])
        assert chunked.metrics.counter("cow_copies") > copies
        check_identifiers(seen)
        assert len(windows) >= 2
        for w in windows:
            assert [ids["part"] for ids in entries(
                seen, "stage", kind="prefill", **w)] == ["build", "dispatch"]
            assert len(entries(seen, "wait", kind="prefill", **w)) == 1
        assert len(entries(seen, "stage", kind="prefill")) == 2 * len(windows)
        rows = [ids["rows"] for ids in entries(seen, "stage", kind="decode",
                                               part="dispatch")]
        assert rows == decoded and set(rows) == {1, 2}
        assert [ids["rows"] for ids in entries(seen, "wait", kind="decode")] \
            == decoded
        page_copies = entries(seen, "stage", kind="copy")
        assert page_copies and all(ids["part"] == "dispatch"
                                   for ids in page_copies)

    def test_a_verify_steps_entries_say_kind_and_part(self, params,
                                                      monkeypatch):
        from paddle_tpu.models.generation import draft_from_params

        dp, da = draft_from_params(params, ARGS, 1)
        eng = PagedEngine(params, ARGS, max_slots=2, max_len=64, page_size=8,
                          min_bucket=8, draft_params=dp, draft_args=da,
                          spec_tokens=3)
        seen = record_annotations(monkeypatch)
        eng.serve([Request(p, 6) for p in _prompts([7, 12], seed=39)])
        check_identifiers(seen)
        rounds = eng.metrics.counter("spec_rounds")
        assert rounds >= 1
        verify = entries(seen, "stage", kind="verify")
        assert [ids["part"] for ids in verify] == ["build", "dispatch"] * rounds
        assert all(ids["rows"] in (1, 2) for ids in verify)
        assert len(entries(seen, "wait", kind="verify")) == rounds
        # the draft proposes (build, dispatch, wait) once a round and
        # mirrors each finished prompt (a dispatch with the window's size)
        draft = entries(seen, "stage", kind="draft")
        assert len([ids for ids in draft if "tokens" not in ids]) \
            == 2 * rounds
        assert sorted(ids["tokens"] for ids in draft if "tokens" in ids) \
            == [7, 12]
        assert len(entries(seen, "wait", kind="draft")) == rounds
        obs = eng.metrics.summary()["observations"]
        for gone in ("verify_s", "draft_propose_s", "draft_prefill_s",
                     "prefill_s"):
            assert gone not in obs

    def test_admit_time_and_queue_wait(self, engine):
        before = engine.metrics.observation("queue_wait_s")
        before = before["count"] if before else 0
        reqs = engine.serve([Request(p, 2)
                             for p in _prompts([4, 6, 8], seed=34)])
        assert all(r.admit_time >= r.submit_time for r in reqs)
        got = engine.metrics.observation("queue_wait_s")
        assert got["count"] - before == len(reqs)


def _lowered_text(engine, program):
    """The lowered text, with locations, of one of the engine's own program
    objects at the shapes the engine calls it with."""
    Pn, S, path = engine.pages_per_slot, engine.max_slots, engine.path
    if program == "decode":
        low = path._decode[False].lower(
            engine.params, jnp.zeros(S, jnp.int32), path.pk, path.pv,
            jnp.zeros((S, Pn), jnp.int32), jnp.zeros(S, jnp.int32),
            path.cos, path.sin, *engine._sampling_args())
    elif program == "prefill":
        low = path._prefill[False].lower(
            engine.params, jnp.zeros((1, 16), jnp.int32), jnp.int32(0),
            jnp.int32(3), jnp.zeros(Pn, jnp.int32), jnp.zeros(Pn, jnp.int32),
            path.pk, path.pv, path.cos, path.sin,
            jnp.float32(0), jnp.float32(1), jnp.int32(0),
            jnp.zeros(1, jnp.int32))
    else:
        low = path._copy.lower(path.pk, path.pv, jnp.int32(1),
                               jnp.int32(2))
    return low.as_text(debug_info=True)


@pytest.mark.parametrize("program,scope", [
    ("decode", "pt.paged_attention"), ("decode", "pt.kv_write"),
    ("decode", "pt.attention"), ("decode", "pt.mlp"), ("decode", "pt.norm"),
    ("decode", "pt.sample"),
    ("prefill", "pt.attention/pt.paged_attention"),
    ("prefill", "pt.attention/pt.kv_write"),
    ("prefill", "pt.paged_attention"), ("prefill", "pt.sample"),
    ("copy_page", "pt.kv_write")])
def test_serve_programs_carry_stable_scope_names(engine, program, scope):
    import re

    # as an entry of an operation's name stack: followed by the next entry
    # or, where the scope holds a nested jit (argmax), by the closing quote
    assert re.search(re.escape(scope) + r'[/"]',
                     _lowered_text(engine, program))


def test_pallas_paged_kernel_keeps_the_scan_body_as_innermost_scope(params):
    """The benchmark's paged-decode reader finds the kernel by the
    instruction name the TPU compiler derives from the innermost scope
    (`closed_call`, the layer scan's body); the `pt.` scope sits above it."""
    import re

    args128 = ARGS._replace(hidden_size=512, num_heads=4, num_kv_heads=2)
    p128 = lf.init_params(args128, jax.random.key(1))
    eng = PagedEngine(p128, args128, max_slots=2, max_len=128, page_size=16,
                      min_bucket=16)
    with qm.fused_dispatch(True, interpret=True):
        text = _lowered_text(eng, "decode")
    assert re.search(r"pt\.attention/pt\.paged_attention/closed_call/"
                     r"pallas_call", text)


def test_prefill_program_names_its_kernel_and_never_the_decode_kernels(
        params):
    """The prefill window's kernel carries a name of its own: the trace
    calls an unnamed one by its innermost scope, the layer scan's body, a
    second `closed_call.N` beside the paged decode kernel, and the
    benchmark's reader would count it into that kernel's time."""
    args128 = ARGS._replace(hidden_size=512, num_heads=4, num_kv_heads=2)
    p128 = lf.init_params(args128, jax.random.key(1))
    eng = PagedEngine(p128, args128, max_slots=2, max_len=128, page_size=16,
                      min_bucket=16)
    with qm.fused_dispatch(True, interpret=True):
        text = _lowered_text(eng, "prefill")
    assert ("pt.attention/pt.paged_attention/paged_prefill_attention/"
            "pallas_call") in text
    assert "closed_call/pallas_call" not in text


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_prefill_program_holds_no_stripe(params, kv_dtype):
    """A window's K / V goes into the window's own pages and attention
    walks the table: no tensor of the program has as many positions as a
    slot's table (2,048 here, or that plus a window's pad: the gathered
    stripe the program used to forward over and cut pages back out of).
    The CPU backend's `temp_size_in_bytes` counts its own copies of
    undonated pools, so the figure itself is read where the program is
    compiled for the chip (`tests/test_tpu_compile.py`)."""
    import re

    eng = PagedEngine(params, ARGS, max_slots=2, max_len=2048, page_size=8,
                      min_bucket=8, kv_dtype=kv_dtype)
    positions = eng.pages_per_slot * eng.page_size
    dims = {int(d) for shape in re.findall(
        r"tensor<((?:\d+x)+)[a-z]", _lowered_text(eng, "prefill"))
        for d in shape.rstrip("x").split("x")}
    assert eng.num_pages in dims and 16 in dims     # the pools, the window
    # the rotary tables have 2 * max_len rows; nothing lies between a
    # window's blocks of keys and those but the pools' pages
    assert not {d for d in dims if positions <= d < 2 * positions
                and d not in (eng.num_pages, 2 * eng.num_pages)}, dims


def test_prefill_live_page_share_counts_the_pages_the_window_walks(
        params, monkeypatch):
    """One observation a prefill window: the pages up to the window's last
    position over pages a slot: how much of the table its attention walks,
    from the host's own numbers."""
    eng = PagedEngine(params, ARGS, max_slots=2, max_len=64, page_size=8,
                      min_bucket=8, prefill_chunk=8)
    seen, observe = [], eng.metrics.observe

    def record(name, value, **kw):
        if name == "prefill_live_page_share":
            seen.append(value)
        return observe(name, value, **kw)

    monkeypatch.setattr(eng.metrics, "observe", record)
    a, b = _prompts([5, 19], seed=41)
    # a: one window that ends at position 4; b: chunks of 8 that end at
    # positions 7, 15 and 18, in the order the scheduler runs them
    eng.serve([Request(a, 2), Request(b, 2)])
    assert sorted(seen) == [1 / 8, 1 / 8, 2 / 8, 3 / 8]
    got = eng.metrics.observation("prefill_live_page_share")
    assert got["count"] == 4 and abs(got["mean"] - 7 / 32) < 1e-9


def test_decode_live_page_share_counts_the_pages_the_rows_hold(params,
                                                               monkeypatch):
    """One observation a decode step: the pages its rows hold (position //
    page size + 1 each) over slots x pages a slot: what the paged kernel
    fetches, as a share of what the table could name."""
    eng = PagedEngine(params, ARGS, max_slots=2, max_len=64, page_size=8,
                      min_bucket=8)
    seen, observe = [], eng.metrics.observe

    def record(name, value, **kw):
        if name == "decode_live_page_share":
            seen.append(value)
        return observe(name, value, **kw)

    monkeypatch.setattr(eng.metrics, "observe", record)
    a, b = _prompts([5, 19], seed=41)
    # one token comes from the prefill: a decodes once (at position 5), b
    # three times (at positions 19, 20, 21), the first beside a
    eng.serve([Request(a, 2), Request(b, 4)])
    assert seen == [(1 + 3) / 16, 3 / 16, 3 / 16]
    got = eng.metrics.observation("decode_live_page_share")
    assert got["count"] == 3 and abs(got["mean"] - 10 / 48) < 1e-9
    assert eng.metrics.counter("decode_steps") == 3
