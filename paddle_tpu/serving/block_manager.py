"""Page-pool allocator + radix-tree prefix cache for the paged KV cache.

The paged serving engine (`serving/paged_engine.py`) replaces per-slot
`max_len` KV stripes with a fixed pool of PAGES
(`[L, num_pages, nkv, page_size, hd]`) and a per-slot block table — the
vLLM PagedAttention (Kwon et al., SOSP'23) memory model. This module is
the host-side brain of that cache; nothing here touches device arrays:

  - `BlockAllocator` hands out page ids from a free list with REFCOUNTS,
    so one physical page can back many slots (a shared system prompt is
    resident once);
  - the PREFIX CACHE is a RADIX TREE over token sequences
    (RadixAttention, Zheng et al. 2023): `match_prefix` returns the
    longest cached prefix at TOKEN granularity — whole shared pages plus
    one PARTIAL page when two prompts diverge mid-page. Each tree node
    stores the token edge from its parent and owns the pages it
    introduced; a mid-edge divergence SPLITS the node, and the
    straddling page is shared copy-on-write (the engine gathers the
    cached half out of the frozen page and scatters into a fresh copy,
    so both children keep reading the ancestor's bytes). The exact-match
    hash chain this replaces survives as `policy="hash"` — the bench
    baseline the radix hit-rate is measured against;
  - pages whose refcount drops to zero but that remain tree-registered
    become EVICTABLE instead of free: they keep their contents and can
    be revived by a later prefix hit, or reclaimed under pressure by
    LEAF-LRU eviction — only the trailing page of a least-recently-hit
    LEAF is ever taken, so hot interior prefixes (the shared system
    prompt) survive while cold divergent tails are peeled off from the
    outside in;
  - `ensure_writable` is the COPY-ON-WRITE gate: writing into a page
    that is shared (refcount > 1) or tree-registered would corrupt the
    other readers, so the writer gets a fresh page and the caller copies
    the device contents across.

Page id 0 is the NULL page: never allocated, a garbage sink for inactive
block-table rows and a safe gather target for unused entries (the
position mask keeps it unread on every real path).
"""

from __future__ import annotations

from collections import OrderedDict
from typing import List, NamedTuple, Optional

import numpy as np

__all__ = ["BlockAllocator", "PrefixMatch", "NULL_PAGE"]

NULL_PAGE = 0


class PrefixMatch(NamedTuple):
    """Result of a longest-prefix scan over the cache.

    pages:        full cached pages; pages[i] holds tokens
                  [i*page_size, (i+1)*page_size) of the query.
    partial_page: page whose leading `partial_len` positions hold tokens
                  [len(pages)*page_size, matched) — the mid-page share a
                  radix split exposes. None under the hash policy and on
                  page-aligned matches. The page is FROZEN: the engine
                  must gather from it and write into its own copy.
    partial_len:  valid leading tokens on partial_page (0 when None).
    matched:      total cached tokens = len(pages)*page_size+partial_len.
    state:        id of the recurrent-state snapshot taken at `matched`
                  tokens, where the allocator keeps snapshots (a model
                  with recurrent layers can resume only from one); else
                  None.
    """

    pages: List[int]
    partial_page: Optional[int]
    partial_len: int
    matched: int
    state: Optional[int] = None


_EMPTY_MATCH = PrefixMatch([], None, 0, 0)


def _ints(tokens):
    """Python ints of a token sequence: `tolist` of an array is ten times
    faster than a loop, which matters at prompts of tens of thousands of
    tokens walked for every queued request."""
    return np.asarray(tokens).tolist()


class _RadixNode:
    """One radix-tree node. `edge` is the token run from the parent;
    `start` its absolute offset in any sequence through this node. The
    node OWNS the pages it introduced: page indices
    [start//ps, end//ps - 1] when it has children (the straddling end
    page, if any, belongs to the children's COW copies), and
    [start//ps, (end-1)//ps] when it is a leaf (the trailing partial
    page is frozen here). A node whose edge starts and ends inside the
    same page owns nothing — its boundary copy lives with whichever
    child extends it. Every owned page id appears exactly once in the
    whole tree."""

    __slots__ = ("edge", "start", "pages", "children", "parent", "stamp",
                 "snaps")

    def __init__(self, edge, start, pages, parent):
        self.edge = edge          # tuple of ints
        self.start = start        # absolute token offset of edge[0]
        self.pages = pages        # owned page ids, path order
        self.children = {}        # first edge token -> _RadixNode
        self.parent = parent
        self.stamp = 0            # LRU clock of the last committed hit
        # absolute token count (start < count <= end) -> id of the recurrent
        # state snapshot taken after that many tokens of this path
        self.snaps = {}

    @property
    def end(self):
        return self.start + len(self.edge)


class _RadixIndex:
    """Token-granular radix prefix index (policy="radix")."""

    def __init__(self, alloc):
        self._a = alloc
        self.root = _RadixNode((), 0, [], None)
        self._owner = {}          # page id -> owning node
        self._clock = 0
        self._reclaimable = (None, 0)   # (versions, pages) of the last walk

    def _tick(self):
        self._clock += 1
        return self._clock

    def owns(self, page):
        return page in self._owner

    # -- longest-prefix match ----------------------------------------------
    def match(self, tokens, touch=False, need_state=False):
        """Longest cached prefix of `tokens`, capped at len-1 so the
        final token is always recomputed (its next-token logits are the
        point of the prefill). Pure tree walk — refcounts are the
        allocator's business. With `need_state` the match is cut back to
        the deepest recurrent-state snapshot on the matched path (a
        model with recurrent layers resumes only from one): no snapshot,
        no hit, whatever pages matched."""
        ps = self._a.page_size
        limit = len(tokens) - 1
        if limit <= 0 or int(tokens[0]) not in self.root.children:
            return _EMPTY_MATCH   # (before a long prompt is converted)
        toks = _ints(tokens[:limit])
        acc = []                  # pages in path order: acc[i] covers page i
        node = self.root
        path = [node]
        m, snap = 0, None         # snap: deepest (token count, snapshot id)
        while m < limit:
            child = node.children.get(toks[m])
            if child is None:
                break
            edge, k = child.edge, 0
            while k < len(edge) and m + k < limit and edge[k] == toks[m + k]:
                k += 1
            acc.extend(child.pages)
            path.append(child)
            m += k
            for count, sid in child.snaps.items():
                if count <= m and (snap is None or count > snap[0]):
                    snap = (count, sid)
            if k < len(edge):
                break
            node = child
        if touch:
            t = self._tick()
            for nd in path:
                nd.stamp = t
        state = None
        if need_state:
            if snap is None:
                if m and touch and self._a._metrics is not None:
                    self._a._metrics.inc("prefix_hits_without_state")
                return _EMPTY_MATCH
            m, state = snap
            if touch:
                self._a._touch_snapshot(state)
        full, plen = m // ps, m % ps
        partial = None
        if plen:
            partial = self._page_covering(path[-1], acc, full)
            if partial is None:     # defensive: degrade to page-aligned
                if need_state:      # a snapshot is of one token count
                    return _EMPTY_MATCH
                plen, m = 0, full * ps
        return PrefixMatch(acc[:full], partial, plen, m, state)

    def _page_covering(self, last, acc, idx):
        """Physical page holding page-index `idx` of the matched path.
        Usually already in `acc`; when the walk ended at a node whose
        edge straddles into a page owned by its children, descend — any
        branch works, every descendant shares the path's tokens through
        at least the walk's end."""
        if idx < len(acc):
            return acc[idx]
        node = last
        while node.children:
            node = next(iter(node.children.values()))
            first = node.start // self._a.page_size
            if first <= idx < first + len(node.pages):
                return node.pages[idx - first]
        return None

    # -- registration -------------------------------------------------------
    def register(self, tokens, pages):
        """Insert `tokens` (backed by `pages`, page i holding tokens
        [i*ps, (i+1)*ps), the last page possibly partial) into the tree.
        Walks existing edges, splits at a mid-edge divergence, and hangs
        one new leaf owning the pages past the divergence. Pages already
        owned elsewhere are never re-claimed (the walk passes through
        them); registration never touches refcounts."""
        ps = self._a.page_size
        toks = _ints(tokens)
        n = len(toks)
        if not n or not pages:
            return
        if (len(pages) - 1) * ps >= n:
            raise ValueError("register_prefix: more pages than the token "
                             "prefix covers")
        pages = list(pages)
        node, i = self.root, 0
        while i < n:
            child = node.children.get(toks[i])
            if child is None:
                self._insert_leaf(node, toks, i, pages)
                return
            edge, k = child.edge, 0
            while k < len(edge) and i + k < n and edge[k] == toks[i + k]:
                k += 1
            if k == len(edge):
                node = child
                i += k
                continue
            if i + k == n:
                return          # strict prefix of an existing edge
            mid = self._split(child, k)
            self._insert_leaf(mid, toks, i + k, pages)
            return
        # walked the whole sequence along existing edges: already cached

    def attach_state(self, tokens, sid):
        """Hang snapshot `sid`, taken after ALL of `tokens`, on the node
        whose edge holds that token count. False where the tree does not
        hold the whole sequence (or already has a snapshot there)."""
        toks = _ints(tokens)
        n, node, i = len(toks), self.root, 0
        while i < n:
            child = node.children.get(toks[i])
            if child is None:
                return False
            edge, k = child.edge, 0
            while k < len(edge) and i + k < n and edge[k] == toks[i + k]:
                k += 1
            i += k
            if i == n:
                # the pages up to n must be there too: a leaf's edge can
                # outrun them only by what an eviction cut, never
                if n in child.snaps:
                    return False
                child.snaps[n] = sid
                self._a.prefix_version += 1
                return True
            if k < len(edge):
                return False
            node = child
        return False

    def _drop_snaps(self, node, beyond):
        """Give back the snapshots of `node` taken past `beyond` tokens."""
        for count in [c for c in node.snaps if c > beyond]:
            self._a._snapshot_gone(node.snaps.pop(count))

    def detach_state(self, sid):
        """Forget snapshot `sid` wherever it hangs (its buffer is being
        taken for a newer one)."""
        stack = [self.root]
        while stack:
            nd = stack.pop()
            stack.extend(nd.children.values())
            for count, s in list(nd.snaps.items()):
                if s == sid:
                    del nd.snaps[count]
                    self._a.prefix_version += 1
                    return

    def _insert_leaf(self, parent, toks, i, pages):
        """Hang a new leaf for tokens [i, n) under `parent`. The leaf
        owns pages from index i//ps on — including the caller's COW copy
        of a straddled boundary page. Any candidate page already owned
        elsewhere (the tree moved between match and register) truncates
        the claim at the preceding page boundary."""
        ps = self._a.page_size
        n = len(toks)
        first = i // ps
        sel = []
        for idx in range(first, len(pages)):
            p = pages[idx]
            if p == NULL_PAGE or p in self._owner:
                break
            sel.append(p)
        if not sel:
            return
        end = min(n, (first + len(sel)) * ps)
        if end <= i:
            return
        leaf = _RadixNode(tuple(toks[i:end]), i, sel, parent)
        parent.children[toks[i]] = leaf
        for p in sel:
            self._owner[p] = leaf
        leaf.stamp = self._tick()
        self._a.prefix_version += 1

    def _split(self, child, k):
        """Split `child` at edge offset k: a new interior node keeps
        edge[:k] and the whole pages before the split point; `child` is
        demoted under it keeping the rest — including its copy of the
        straddled boundary page, which the new sibling will mirror with
        a COW copy of its own."""
        parent = child.parent
        d = child.start + k
        ps = self._a.page_size
        keep = d // ps - child.start // ps      # whole pages before d
        mid = _RadixNode(child.edge[:k], child.start, child.pages[:keep],
                         parent)
        parent.children[mid.edge[0]] = mid
        child.edge = child.edge[k:]
        child.start = d
        child.pages = child.pages[keep:]
        child.parent = mid
        mid.children = {child.edge[0]: child}
        mid.stamp = child.stamp
        mid.snaps = {c: s for c, s in child.snaps.items() if c <= d}
        child.snaps = {c: s for c, s in child.snaps.items() if c > d}
        for p in mid.pages:
            self._owner[p] = mid
        if self._a._metrics is not None:
            self._a._metrics.inc("radix_splits")
        self._a.prefix_version += 1
        return mid

    # -- eviction -----------------------------------------------------------
    def evict_one(self):
        """Reclaim ONE page by leaf-LRU: among leaves whose trailing
        page is refcount-0 (cached), take the least recently hit and
        peel its last page. Interior pages — the shared hot prefix — are
        structurally untouchable until their subtree has been consumed
        leaf by leaf. Returns the page id, or None when nothing is
        evictable."""
        cached = self._a._cached
        best = None
        stack = [self.root]
        while stack:
            nd = stack.pop()
            stack.extend(nd.children.values())
            if nd.children or nd is self.root or not nd.pages:
                continue
            if nd.pages[-1] not in cached:
                continue
            if best is None or nd.stamp < best.stamp:
                best = nd
        if best is None:
            return None
        p = best.pages.pop()
        del cached[p]
        del self._owner[p]
        self._a.prefix_version += 1
        ps = self._a.page_size
        if best.pages:
            new_end = (best.start // ps + len(best.pages)) * ps
            best.edge = best.edge[:new_end - best.start]
            self._drop_snaps(best, new_end)
        else:
            self._remove(best)
        return p

    def _remove(self, node):
        """Unlink a page-less leaf, cascading through interior nodes
        that held no pages of their own and just lost their last
        child."""
        while node is not self.root:
            parent = node.parent
            self._drop_snaps(node, -1)
            del parent.children[node.edge[0]]
            if parent.children or parent.pages or parent is self.root:
                return
            node = parent

    # -- accounting ---------------------------------------------------------
    def reclaimable(self):
        """Pages alloc() could obtain by repeated leaf-LRU eviction: the
        trailing run of cached pages of every node whose whole subtree
        is evictable (an interior page only frees up once everything
        hanging off it is gone). Iterative post-order — tree depth grows
        with registrations, not page counts. The walk is kept until the
        tree or the set of cached pages changes (`prefix_version`,
        `cached_version`): the gauge behind every `alloc()` asks again
        once a page a decoding row, and a page taken from the free list
        changes neither."""
        key = (self._a.prefix_version, self._a.cached_version)
        if self._reclaimable[0] == key:
            return self._reclaimable[1]
        cached = self._a._cached
        order, stack = [], [self.root]
        while stack:
            nd = stack.pop()
            order.append(nd)
            stack.extend(nd.children.values())
        res = {}
        for nd in reversed(order):
            total, fully = 0, True
            for c in nd.children.values():
                t, f = res[id(c)]
                total += t
                fully = fully and f
            if fully:
                tail = 0
                for p in reversed(nd.pages):
                    if p in cached:
                        tail += 1
                    else:
                        break
                total += tail
                fully = tail == len(nd.pages)
            res[id(nd)] = (total, fully)
        self._reclaimable = (key, res[id(self.root)][0])
        return self._reclaimable[1]


class _HashChainIndex:
    """The PR-8 exact-match chain, kept verbatim as `policy="hash"`: a
    table keyed on `(parent_page_id, page_of_token_ids)` shares only
    FULL pages on a strict chain, and eviction is insertion-order LRU
    with descendant orphaning. It is the baseline the radix policy's
    hit-rate gain is benchmarked against."""

    def __init__(self, alloc):
        self._a = alloc
        self._table = {}          # (parent | -1, tokens tuple) -> page
        self._key_of = {}         # registered page -> its table key
        self._parent = {}         # registered page -> parent page (or -1)
        self._children = {}       # page -> set of registered child pages

    def _chunk(self, tokens, i):
        ps = self._a.page_size
        return tuple(int(t) for t in tokens[i * ps:(i + 1) * ps])

    def owns(self, page):
        return page in self._key_of

    def match(self, tokens, touch=False, need_state=False):
        ps = self._a.page_size
        max_pages = (len(tokens) - 1) // ps
        pages, parent = [], -1
        for i in range(max_pages):
            p = self._table.get((parent, self._chunk(tokens, i)))
            if p is None:
                break
            pages.append(p)
            parent = p
        return PrefixMatch(pages, None, 0, len(pages) * ps)

    def register(self, tokens, pages):
        ps = self._a.page_size
        if (len(pages) - 1) * ps >= len(tokens):
            raise ValueError("register_prefix: more pages than the token "
                             "prefix covers")
        pages = pages[:len(tokens) // ps]   # full pages only
        parent = -1
        for i, p in enumerate(pages):
            key = (parent, self._chunk(tokens, i))
            existing = self._table.get(key)
            if existing is not None:
                parent = existing
                continue
            if p in self._key_of:   # already registered under another chain
                parent = p
                continue
            self._table[key] = p
            self._key_of[p] = key
            self._parent[p] = parent
            if parent != -1:
                self._children.setdefault(parent, set()).add(p)
            parent = p
            self._a.prefix_version += 1

    def reclaimable(self):
        return len(self._a._cached)

    def evict_one(self):
        cached = self._a._cached
        if not cached:
            return None
        p = next(iter(cached))              # least recently used
        del cached[p]
        self._unregister(p)
        return p

    def _unregister(self, page):
        """Remove a page's hash registration and ORPHAN its descendants:
        their chain keys embed this page's id, which a recycled page
        could spoof into serving stale contents. Orphaned cached
        descendants become plain free pages; orphaned in-use descendants
        just lose future hits."""
        key = self._key_of.pop(page, None)
        if key is None:
            return
        self._a.prefix_version += 1
        self._table.pop(key, None)
        parent = self._parent.pop(page, None)
        if parent is not None and parent != -1:
            self._children.get(parent, set()).discard(page)
        for child in list(self._children.pop(page, ())):
            self._unregister(child)
            if child in self._a._cached:
                del self._a._cached[child]
                self._a._free.append(child)


class BlockAllocator:
    """Host-side page allocator with refcounts, prefix reuse, eviction
    of cached pages, and copy-on-write. Single-threaded — called only
    from the engine's scheduler loop between device steps.

    policy="radix" (default) indexes prefixes in a token-granular radix
    tree with COW page splits and leaf-LRU eviction; policy="hash"
    keeps the PR-8 exact-match full-page chain as a baseline.

    state_snapshots=N (radix only) is for a model with RECURRENT layers,
    whose per-request state is no function of pages: a prefix is reusable
    only from a token count at which the state was saved. The allocator
    then owns N snapshot ids (the engine owns the N device buffers):
    `take_snapshot` hands one out, taking the least recently hit one back
    from the tree when none is free; `attach_state` hangs it on the radix
    node that holds its token count; `match_prefix` returns the longest
    match that ENDS AT a snapshot (`PrefixMatch.state`), else no hit, and
    counts `prefix_hits_without_state`; evicting the pages under a snapshot
    frees it."""

    def __init__(self, num_pages, page_size, metrics=None, policy="radix",
                 state_snapshots=0):
        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is the null page)")
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.policy = str(policy)
        self._metrics = metrics
        self._free = list(range(self.num_pages - 1, 0, -1))  # pop -> lowest
        self._ref = {}              # page -> refcount (>= 1)
        self._cached = OrderedDict()  # refcount-0 registered pages, LRU order
        # bumped on every prefix-index mutation (registration, split,
        # eviction) — lets callers memoize side-effect-free match_prefix
        # scans (the chunked-prefill anti-convoy admission walk) until a
        # change could alter the answer
        self.prefix_version = 0
        # bumped whenever a page joins or leaves `_cached` without a change
        # to the index (a release to refcount 0, a hit's revival)
        self.cached_version = 0
        if self.policy == "radix":
            self._index = _RadixIndex(self)
        elif self.policy == "hash":
            self._index = _HashChainIndex(self)
        else:
            raise ValueError(f"unknown prefix policy {policy!r} "
                             "(expected 'radix' or 'hash')")
        if state_snapshots and self.policy != "radix":
            raise ValueError("state snapshots hang on the radix tree: "
                             "state_snapshots needs policy='radix'")
        self.state_snapshots = int(state_snapshots)
        self._snap_free = list(range(self.state_snapshots - 1, -1, -1))
        self._snap_lru = OrderedDict()    # attached snapshot ids, LRU first
        self._bulk = False
        self._gauges()

    # -- introspection ------------------------------------------------------
    @property
    def capacity(self):
        """Allocatable pages (the null page excluded)."""
        return self.num_pages - 1

    @property
    def free_count(self):
        return len(self._free)

    @property
    def available(self):
        """Pages an alloc() can obtain: free + evictable cached. Under
        the radix policy an interior cached page only counts once its
        whole subtree is evictable (leaf-LRU can't reach it before)."""
        return len(self._free) + self._index.reclaimable()

    @property
    def pages_in_use(self):
        return len(self._ref)

    def refcount(self, page):
        return self._ref.get(page, 0)

    def is_registered(self, page):
        return self._index.owns(page)

    def _gauges(self):
        # `available` walks the radix tree: once an operation, so the bulk
        # forms below (a window's pages, a retiring slot's) pay it once
        if self._metrics is not None and not self._bulk:
            self._metrics.set_gauge("pages_in_use", len(self._ref))
            self._metrics.set_gauge("pages_free", self.available)

    def alloc_many(self, n):
        """`alloc()` n times, the gauges set once."""
        self._bulk = True
        try:
            return [self.alloc() for _ in range(n)]
        finally:
            self._bulk = False
            self._gauges()

    def release_many(self, pages):
        """`release()` of every page, the gauges set once."""
        self._bulk = True
        try:
            for p in pages:
                self.release(p)
        finally:
            self._bulk = False
            self._gauges()

    # -- alloc / ref / release ---------------------------------------------
    def alloc(self):
        """Take an exclusive page (refcount 1): from the free list, else
        by evicting per the policy (leaf-LRU for radix, insertion-order
        LRU for hash). Raises when the pool is exhausted."""
        if self._free:
            p = self._free.pop()
        else:
            p = self._index.evict_one()
            if p is None:
                raise RuntimeError(
                    f"KV page pool exhausted ({self.capacity} pages, "
                    f"{len(self._ref)} in use) — admission should have "
                    f"gated this request")
            if self._metrics is not None:
                self._metrics.inc("page_evictions")
        self._ref[p] = 1
        self._gauges()
        return p

    def ref(self, page):
        """Add a reader. Reviving a cached (refcount-0) page pulls it
        off the eviction list but keeps its tree registration — the
        prefix-hit path."""
        if page == NULL_PAGE:
            raise ValueError("cannot ref the null page")
        if page in self._ref:
            self._ref[page] += 1
        elif page in self._cached:
            del self._cached[page]
            self.cached_version += 1
            self._ref[page] = 1
        else:
            raise KeyError(f"ref of unallocated page {page}")
        self._gauges()

    def release(self, page):
        """Drop a reader. At refcount 0 a tree-registered page becomes
        evictable (contents kept for future prefix hits, most recent at
        the back of the LRU); an unregistered page returns to the free
        list."""
        if page == NULL_PAGE:
            return
        r = self._ref[page] - 1
        if r > 0:
            self._ref[page] = r
            return
        del self._ref[page]
        if self._index.owns(page):
            self._cached[page] = True       # most-recently-used position
            self.cached_version += 1
        else:
            self._free.append(page)
        self._gauges()

    # -- copy-on-write ------------------------------------------------------
    def ensure_writable(self, page):
        """COW gate before writing into `page`. An exclusive,
        unregistered page comes back unchanged (the overwhelmingly
        common case — a slot's partially-filled tail page). A shared or
        tree-registered page is swapped for a freshly allocated one:
        returns (new_page, True) and the caller must copy the device
        contents old -> new before writing."""
        if page != NULL_PAGE and self._ref.get(page, 0) == 1 \
                and not self._index.owns(page):
            return page, False
        new = self.alloc()
        self.release(page)
        if self._metrics is not None:
            self._metrics.inc("cow_copies")
        self._gauges()
        return new, True

    # -- recurrent-state snapshots -------------------------------------------
    def take_snapshot(self):
        """An id to save a slot's recurrent state under, or None when every
        id is held by a request that has not retired yet. The id is the
        caller's until `attach_state` or `release_snapshot`."""
        if self._snap_free:
            return self._snap_free.pop()
        if not self._snap_lru:
            return None
        sid, _ = self._snap_lru.popitem(last=False)
        self._index.detach_state(sid)
        return sid

    def attach_state(self, tokens, sid):
        """Make snapshot `sid`, the state after all of `tokens`, hittable:
        it hangs on the radix node that holds that token count. Where the
        tree does not hold the sequence the id goes back. Returns whether
        it hangs."""
        if self._index.attach_state(tokens, sid):
            self._snap_lru[sid] = True
            return True
        self._snap_free.append(sid)
        return False

    def release_snapshot(self, sid):
        """Give back an id that was taken and never attached."""
        self._snap_free.append(sid)

    def _touch_snapshot(self, sid):
        self._snap_lru.move_to_end(sid)

    def _snapshot_gone(self, sid):
        """The index dropped an attached snapshot (its pages were evicted)."""
        del self._snap_lru[sid]
        self._snap_free.append(sid)

    # -- prefix cache -------------------------------------------------------
    def match_prefix(self, tokens, commit=True):
        """Longest cached prefix of `tokens` as a PrefixMatch — full
        pages plus (radix only) one frozen partial page — capped at
        len-1 tokens so at least the final token is always recomputed.
        With commit=True every hit page INCLUDING the partial is ref'd
        for the caller (reviving cached pages) and the path's LRU stamp
        is bumped; commit=False is a side-effect-free peek for admission
        checks."""
        m = self._index.match(tokens, touch=commit,
                              need_state=bool(self.state_snapshots))
        if commit:
            for p in m.pages:
                self.ref(p)
            if m.partial_page is not None:
                self.ref(m.partial_page)
                if self._metrics is not None:
                    self._metrics.inc("prefix_partial_hits")
        return m

    def register_prefix(self, tokens, pages):
        """Make `pages` (the block-table prefix; page i holds tokens
        [i*ps, (i+1)*ps), the last possibly partial) hittable for future
        prompts. The radix policy keeps the partial tail page (frozen —
        the owner must COW before writing past it); the hash policy
        trims to full pages. Pages already indexed (this prompt's own
        hits) are walked through, not re-claimed."""
        self._index.register(tokens, pages)
