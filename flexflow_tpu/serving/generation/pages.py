"""KV page pool + shared-prefix trie — the host-side memory manager of
the paged generation engine (docs/serving.md "Paged KV & prefix
caching").

Everything here is dispatcher-thread-only pure Python: the pool hands
out page ids, refcounts them, tracks the in-use high-water mark, and
evicts cached prefix pages under pressure; the trie maps token-id
chains (one node per FULL page of tokens) to pooled pages so a submit
whose prompt extends a cached prefix skips recomputing the shared
pages.  The device side only ever sees page ids as gather/scatter
indices (``MultiHeadAttention.serve_step``, ops/attention.py) into
pools stored LANE-DENSE: ``(num_pages,
page_size, heads * head_dim)``, a token's heads side by side in one
minor dim, so that no compiled serving program holds a copy the size of
the pool (``analysis/kv_memory.py`` says why; ``GraphDecoder.
pool_copies`` counts).  Everything below that touches a pool leaf asks
only that it be page-major (``shape[0] == num_pages``).

Sharing is all-or-nothing per page, and a shared page is immutable by
construction: a lookup only ever matches COMPLETE pages strictly
covered by the prompt's first ``len - 1`` positions, so the prefill
recomputes at least the last prompt position and every write (suffix
prefill rows, decode tokens) lands in the slot's PRIVATE pages — the
copy-on-write case where a stream would mutate shared history cannot
arise, divergence simply stops the trie walk and allocates private
pages from there.

This module is ALSO the one place pool device arrays are allocated
(:func:`alloc_pool_arrays`) — repo_lint RL013 bans KV-shaped
``jnp.zeros``/``np.zeros`` anywhere else under ``serving/generation/``
so no second allocation path can drift from the
``analysis.kv_memory`` accounting.
"""

from __future__ import annotations

import heapq
from typing import Dict, List, Optional, Tuple

from ...analysis.kv_memory import DEFAULT_PAGE_SIZE


class KVPagePool:
    """Fixed-size pool of interchangeable KV pages (one id spans every
    attention op's K/V pools — allocation is in lockstep across ops).
    Single-threaded by design: only the engine's dispatcher thread
    allocates/frees (the same single-writer discipline as the slot
    table)."""

    def __init__(self, num_pages: int, page_size: int = DEFAULT_PAGE_SIZE):
        if num_pages < 1:
            raise ValueError(f"num_pages must be >= 1, got {num_pages}")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size) or DEFAULT_PAGE_SIZE
        # the OOB sentinel: gather clamps it (masked anyway), scatter
        # mode='drop' discards writes to it — "no page" on device
        self.no_page = self.num_pages
        self._free: List[int] = list(range(self.num_pages - 1, -1, -1))
        self._refs: Dict[int, int] = {}
        self.high_water = 0
        self.allocs = 0

    @property
    def pages_in_use(self) -> int:
        return self.num_pages - len(self._free)

    @property
    def pages_free(self) -> int:
        return len(self._free)

    def alloc(self) -> Optional[int]:
        """One fresh page at refcount 1, or None when exhausted (the
        caller evicts from the prefix cache and retries, then fails the
        stream — never blocks: this runs on the dispatcher thread)."""
        if not self._free:
            return None
        page = self._free.pop()
        self._refs[page] = 1
        self.allocs += 1
        self.high_water = max(self.high_water, self.pages_in_use)
        return page

    def ref(self, page: int) -> None:
        self._refs[page] += 1

    def release(self, page: int) -> bool:
        """Drop one reference; True when the page returned to the free
        list (refcount hit zero)."""
        n = self._refs[page] - 1
        if n > 0:
            self._refs[page] = n
            return False
        del self._refs[page]
        self._free.append(page)
        return True

    def refcount(self, page: int) -> int:
        return self._refs.get(page, 0)


class _TrieNode:
    __slots__ = ("page", "children", "parent", "key", "last_used")

    def __init__(self, page: int, parent: Optional["_TrieNode"],
                 key: Tuple[int, ...]):
        self.page = page
        self.children: Dict[Tuple[int, ...], "_TrieNode"] = {}
        self.parent = parent
        self.key = key
        self.last_used = 0


class PrefixCache:
    """Ref-counted prefix trie over FULL pages of prompt token ids.

    One node per page: the path root -> node spells the token prefix
    the node's page holds the K/V for.  Children are keyed on the exact
    page token tuple (a hash chain with exact-match confirmation — two
    different prefixes can never alias, so a hit is always
    bit-identical history).  The trie holds ONE pool reference per
    node; lookups take an extra reference per matched page for the
    joining slot.  Eviction is LRU over leaf nodes nobody else
    references — interior nodes and pages still held by live slots are
    never evicted.

    The cache knows its next victim without walking the trie: the
    LEAVES are kept in a heap of ``(last_used, seq, node)``, pushed
    where the trie changes — an insert's or a lookup's deepest node if
    it is a leaf, a parent its last child's eviction exposes (at its
    OWN ``last_used``, which is not the newest).  An entry is never
    updated in place; it is STALE once its node was touched again, got
    a child or left the trie, which all show as ``node.children`` or a
    ``last_used`` other than the entry's (a node that gets a child is
    touched by that insert), and a stale entry is dropped when popped.
    Two leaves never share a ``last_used`` (a tick touches one
    root-to-node path, and of one path only the deepest node can be a
    leaf), so the order is total.  Whether a live slot holds a leaf's
    page changes in the pool, out of the trie's sight, and is asked at
    pop time (:meth:`evict`)."""

    def __init__(self, pool: KVPagePool):
        self.pool = pool
        self.page_size = pool.page_size
        self._root: Dict[Tuple[int, ...], _TrieNode] = {}
        self._nodes = 0
        self._clock = 0  # LRU tick (monotonic counter, no wall time)
        self._leaves: List[Tuple[int, int, _TrieNode]] = []
        self._seq = 0  # heap tie-break: a stale entry may share a tick
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        # heap entries evict() popped — stale and held ones included:
        # evict_scanned / evictions says how often the order answered
        # at once (docs/observability.md)
        self.evict_scanned = 0

    def __len__(self) -> int:
        return self._nodes

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def _push_leaf(self, node: Optional[_TrieNode]) -> None:
        """Enter ``node`` in the leaf order at its ``last_used`` if it
        is a leaf.  Stale entries only leave when popped, so a cache
        that is hit often and evicts seldom would grow the heap by one
        a lookup: past twice the trie's size it is rebuilt from its
        live entries (amortised O(1) a push)."""
        if node is None or node.children:
            return
        self._seq += 1
        heapq.heappush(self._leaves, (node.last_used, self._seq, node))
        if len(self._leaves) > 2 * self._nodes + 64:
            self._leaves = [e for e in self._leaves if self._live(e)]
            heapq.heapify(self._leaves)

    @staticmethod
    def _live(entry: Tuple[int, int, _TrieNode]) -> bool:
        stamp, _, node = entry
        return node.last_used == stamp and not node.children

    @staticmethod
    def _pages_of(tokens, page_size: int) -> List[Tuple[int, ...]]:
        """Complete-page token tuples strictly covering positions
        [0, len-1): the last prompt position is always recomputed (it
        yields the stream's first token), so the page holding it is
        only shareable once COMPLETE — see the immutability note in
        the module docstring."""
        n = len(tokens)
        full = max(0, (n - 1)) // page_size
        return [tuple(int(t) for t in tokens[i * page_size:
                                             (i + 1) * page_size])
                for i in range(full)]

    def lookup(self, tokens) -> List[int]:
        """Walk the trie along the prompt's full pages; returns the
        matched page ids IN ORDER with one pool reference taken per
        page for the caller (the joining slot).  The caller's prefill
        starts at ``len(result) * page_size``."""
        out: List[int] = []
        level = self._root
        last: Optional[_TrieNode] = None
        now = self._tick()
        for key in self._pages_of(tokens, self.page_size):
            node = level.get(key)
            if node is None:
                break
            node.last_used = now
            self.pool.ref(node.page)
            out.append(node.page)
            last = node
            level = node.children
        self._push_leaf(last)
        if out:
            self.hits += 1
        else:
            self.misses += 1
        return out

    def insert(self, tokens, pages: List[int]) -> int:
        """Promote a slot's freshly-computed full-page prefix into the
        trie: ``pages[i]`` holds the K/V of the prompt's i-th full
        page.  Pages already cached (the slot's own lookup hits) are
        skipped; new nodes take one extra pool reference (the trie's).
        Returns the number of nodes added."""
        added = 0
        level = self._root
        parent: Optional[_TrieNode] = None
        now = self._tick()
        keys = self._pages_of(tokens, self.page_size)
        for key, page in zip(keys, pages):
            node = level.get(key)
            if node is None:
                node = _TrieNode(page, parent, key)
                node.last_used = now
                self.pool.ref(page)
                level[key] = node
                self._nodes += 1
                added += 1
            else:
                node.last_used = now
            parent = node
            level = node.children
        self._push_leaf(parent)
        return added

    def _evict_node(self, node: _TrieNode) -> None:
        level = (node.parent.children if node.parent is not None
                 else self._root)
        del level[node.key]
        node.last_used = -1  # no entry of a node that left is live
        self._nodes -= 1
        self.pool.release(node.page)
        self.evictions += 1

    def evict(self, count: int) -> int:
        """Free up to ``count`` least-recently-used unreferenced LEAF
        pages back to the pool (page-pool pressure): pop the leaf
        order until ``count`` victims are found.  A popped leaf a live
        slot still holds (``pool.refcount > 1``: a live request's last
        prompt page, so young, and seldom ahead of a victim) is set
        aside and put back after the batch, so one eviction costs
        O((1 + held leaves older than the victim) log n) whatever the
        trie's size.  A batch's victims are the leaves of the moment
        it began, oldest first; a parent its child's eviction exposes
        joins the order when those run dry, or after the batch.
        Returns the number of pages freed — fewer than ``count`` means
        every cached page left backs a live slot or an interior node."""
        freed = 0
        held: List[Tuple[int, int, _TrieNode]] = []
        exposed: List[_TrieNode] = []
        while freed < count:
            if not self._leaves:
                if not exposed:
                    break
                for node in exposed:
                    self._push_leaf(node)
                exposed = []
                continue
            entry = heapq.heappop(self._leaves)
            self.evict_scanned += 1
            if not self._live(entry):
                continue
            node = entry[2]
            if self.pool.refcount(node.page) > 1:
                held.append(entry)
                continue
            self._evict_node(node)
            freed += 1
            if node.parent is not None and not node.parent.children:
                exposed.append(node.parent)
        for entry in held:
            heapq.heappush(self._leaves, entry)
        for node in exposed:
            self._push_leaf(node)
        return freed

    def evict_one(self) -> bool:
        """Single-page :meth:`evict` (the unit-test surface)."""
        return self.evict(1) == 1

    def clear(self) -> None:
        """Release every cached page (engine shutdown)."""
        stack = list(self._root.values())
        self._root = {}
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            self.pool.release(node.page)
        self._nodes = 0
        self._leaves = []


def entry_dtype(ent: Dict, compute_dtype):
    """The dtype a declared entry's leaves are held in (``Op.serve_state``'s
    ``"dtype"``: the compute dtype, float32 or int32)."""
    import jax.numpy as jnp

    return {"compute": jnp.dtype(compute_dtype),
            "i32": jnp.dtype(jnp.int32)}.get(ent["dtype"],
                                             jnp.dtype(jnp.float32))


def alloc_pool_arrays(layout: Dict[str, Dict], mesh, compute_dtype):
    """Materialize the ``analysis.kv_memory.kv_cache_layout`` on
    device: attention K/V page pools, the rows of windowed entries, LSTM
    state pairs and the ops' counters, placed under
    the layout's PartitionSpec entries (K/V leaves in the layout's
    lane-dense ``(num_pages, page_size, heads * head_dim)`` form).
    THE one KV allocation site (repo_lint RL013) — byte-for-byte what
    :func:`kv_page_plan` accounts, pinned in tests/test_generation.py."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec

    caches: Dict[str, Dict[str, jax.Array]] = {}
    for name, ent in layout.items():
        dt = entry_dtype(ent, compute_dtype)
        sub: Dict[str, jax.Array] = {}
        for leaf, shape in ent["shapes"].items():
            arr = jnp.zeros(shape, dt)
            if mesh is not None and mesh.is_distributed:
                arr = jax.device_put(
                    arr, mesh.sharding(PartitionSpec(
                        *ent["entries"][leaf])))
            sub[leaf] = arr
        caches[name] = sub
    return caches


def export_pages(caches, pages: List[int], num_pages: int,
                 pad_to: int = 0):
    """Gather a slot's page rows out of every pool leaf and bring them
    to host in ONE ``device_get`` — the export half of disaggregated
    prefill/decode migration (docs/serving.md "Disaggregated
    prefill/decode").  ``pages`` is the slot's page-id chain IN ORDER;
    every leaf must be page-major (``shape[0]`` the pool's ``num_pages``,
    or a whole number of REGIONS of them: an op a looped stack calls
    several times a token keeps a region a pass, and a page id names one
    page in each, :func:`region_rows`), which is
    true exactly for the attention K/V pools — LSTM ``state`` leaves
    are slot-major and cannot migrate (the engine gates migration on
    chunkable attention graphs for the same reason).  Returns a host
    pytree ``{op: {leaf: np.ndarray[rows, ...]}}``.

    ``pad_to`` pads the gather index to a FIXED row count by repeating
    the last page id (the caller passes its pages-per-slot maximum):
    the gather then traces one XLA program per pool geometry instead
    of one per chain length, so a migration never pays a fresh compile
    mid-serve.  :func:`import_pages` mirrors the padding; the real
    chain length travels beside the payload."""
    import jax
    import numpy as np

    idx = np.asarray(list(pages), np.int32)
    if pad_to > idx.size:
        idx = np.concatenate(
            [idx, np.full(pad_to - idx.size, idx[-1], np.int32)])
    gathered: Dict[str, Dict] = {}
    for name, sub in caches.items():
        rows = {}
        for leaf, arr in sub.items():
            if arr.shape[0] % num_pages:
                raise ValueError(
                    f"cache leaf {name}.{leaf} is not page-major "
                    f"(shape {tuple(arr.shape)}, pool has {num_pages} "
                    f"pages): this graph's state cannot migrate")
            rows[leaf] = arr[region_rows(idx, arr.shape[0], num_pages)]
        gathered[name] = rows
    # one transfer for the whole pytree (RL010-class budget: migration
    # costs one sync on the source, one put on the destination)
    return jax.device_get(gathered)


def region_rows(idx, rows: int, num_pages: int):
    """The rows of a page-major leaf of ``rows`` rows that the page ids
    ``idx`` name: the ids themselves, and for a leaf that holds several
    REGIONS of the pool's ``num_pages`` pages (one a pass of a looped
    stack) the same pages of every region, region by region."""
    import numpy as np

    return np.concatenate([idx + r * num_pages
                           for r in range(rows // num_pages)])


def import_pages(caches, payload, pages: List[int], num_pages: int = 0):
    """Scatter an :func:`export_pages` payload into ``pages`` of the
    DESTINATION pool (of ``num_pages`` pages; 0: as many as a leaf has
    rows, no leaf holds regions) with ONE ``device_put`` of
    the payload pytree —
    the import half of KV page migration.  ``pages`` are freshly
    allocated destination page ids (one per exported page, same order).
    Returns the updated caches pytree (functional ``.at[].set`` — the
    caller reassigns its ``_caches``).

    A payload with MORE rows than ``pages`` was export-padded: the
    destination index is padded the same way (repeat the last real
    page id), so the duplicate scatter positions rewrite the last real
    page with its own row — idempotent — and the scatter keeps one
    fixed shape per pool geometry.

    The pool leaf is DONATED into the scatter: the caller must treat
    the input caches as consumed (the engine reassigns ``_caches`` to
    the return value, and nothing else aliases the pool arrays), so
    the update is in-place where the backend allows instead of a
    full-pool copy per migration."""
    import jax
    import numpy as np

    idx = np.asarray(list(pages), np.int32)
    dev = jax.device_put(payload)

    def regions(arr):
        return arr.shape[0] // num_pages if num_pages else 1

    rows0 = idx.size
    if isinstance(dev, dict) and dev:
        name0, sub0 = next(iter(dev.items()))
        leaf0, val0 = next(iter(sub0.items()))
        # a leaf of several regions ships its pages once a region
        rows0 = val0.shape[0] // regions(
            caches.get(name0, {}).get(leaf0, val0))
    if rows0 > idx.size:
        idx = np.concatenate(
            [idx, np.full(rows0 - idx.size, idx[-1], np.int32)])
    # validate EVERYTHING before the first donating scatter: a graph/
    # geometry mismatch must leave the resident pool untouched (the
    # engine's per-stream containment); once validation passed, the
    # only scatter failures left are catastrophic backend errors
    for name, sub in caches.items():
        rows = dev.get(name) if isinstance(dev, dict) else None
        if rows is None or set(rows) != set(sub):
            raise ValueError(
                f"migration payload does not cover cache op {name!r}: "
                f"source and destination graphs differ")
        for leaf, arr in sub.items():
            val = rows[leaf]
            if tuple(val.shape[1:]) != tuple(arr.shape[1:]) \
                    or val.shape[0] != idx.size * regions(arr):
                raise ValueError(
                    f"migration payload {name}.{leaf} shape "
                    f"{tuple(val.shape)} does not fit destination pool "
                    f"leaf {tuple(arr.shape)} over {idx.size} page(s): "
                    f"page geometry must match across engines")
    out: Dict[str, Dict] = {}
    for name, sub in caches.items():
        rows = dev[name]
        out[name] = {
            leaf: _scatter_rows(
                arr, region_rows(idx, arr.shape[0],
                                 num_pages or arr.shape[0]),
                rows[leaf].astype(arr.dtype))
            for leaf, arr in sub.items()}
    return out


_SCATTER_ROWS = None


def _scatter_rows(arr, idx, val):
    """One jitted, BUFFER-DONATING row scatter shared by every import
    (fixed shape per pool geometry — see the padding contract above):
    in-place on backends that honor donation, one compile ever."""
    global _SCATTER_ROWS
    if _SCATTER_ROWS is None:
        import jax
        _SCATTER_ROWS = jax.jit(
            lambda a, i, v: a.at[i].set(v), donate_argnums=(0,))
    return _SCATTER_ROWS(arr, idx, val)


__all__ = ["KVPagePool", "PrefixCache", "alloc_pool_arrays", "entry_dtype",
           "export_pages", "import_pages"]
