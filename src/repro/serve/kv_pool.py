"""The paged KV pool for continuous batching: pages, block tables, sharing.

``PagedKVPool`` holds a global pool of ``block_size``-token KV pages plus
per-slot block tables: HBM is claimed page-by-page as requests actually
deepen, so capacity is bounded by *tokens in flight*, not ``num_slots *
max_len``. Page 0 is a reserved scratch page — dead padding tokens of the
serve step scatter their KV there, and unmapped block-table entries point
at it (they are only ever read past a slot's depth, i.e. fully masked).
The serve step writes every token's KV straight into its slot's pages;
the pool only publishes depths (:meth:`PagedKVPool.commit_prefill`,
:meth:`PagedKVPool.advance`).

Pages are refcounted so slots can share them: copy-on-write forks (n > 1
samples), the cross-request :class:`PrefixCache`, and compaction of
duplicate prompt pages.

Bookkeeping (alloc/free, lengths, task ids, block tables) is deliberately
host-side numpy: it is O(num_slots + num_blocks) integers, mutated between
device steps, and the serve step only consumes it as small int vectors.
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Set

import jax
import jax.numpy as jnp
import numpy as np


def chain_keys(task_id: int, toks, block_size: int,
               nblocks: int) -> List[bytes]:
    """Chained blake2b page keys for a task-scoped token prefix:
    ``key_0 = H(task_id ‖ tokens[0:bs])``,
    ``key_i = H(key_{i-1} ‖ tokens[i·bs:(i+1)·bs])``.

    The content-identity primitive shared by the cross-request
    :class:`PrefixCache` and :meth:`PagedKVPool.compact`: equal keys mean
    equal (task, token-prefix), which — with the per-task bias being
    position-independent — means bitwise-equal KV page contents."""
    toks = np.asarray(toks, np.int32)
    prev = b"task:%d" % task_id
    keys: List[bytes] = []
    for i in range(nblocks):
        block = toks[i * block_size:(i + 1) * block_size].tobytes()
        prev = hashlib.blake2b(prev + block, digest_size=16).digest()
        keys.append(prev)
    return keys


def _copy_page_impl(pool_cache, src, dst):
    """Duplicate physical page ``src`` into ``dst`` on every layer's pool
    (copy-on-write). Traced page ids: one compilation covers all copies."""
    def cp(p):      # p: (repeats, num_blocks, bs, kvh, hd)
        row = jax.lax.dynamic_slice_in_dim(p, src, 1, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(p, row, dst, axis=1)
    return jax.tree.map(cp, pool_cache)


_COPY_PAGE = None


def _copy_page(pool_cache, src, dst):
    global _COPY_PAGE
    if _COPY_PAGE is None:
        donate = (0,) if jax.default_backend() == "tpu" else ()
        _COPY_PAGE = jax.jit(_copy_page_impl, donate_argnums=donate)
    return _COPY_PAGE(pool_cache, jnp.asarray(src, jnp.int32),
                      jnp.asarray(dst, jnp.int32))


@dataclass
class _PrefixEntry:
    """One cached full page of prompt KV, addressed by its chained hash.

    ``parent`` links the entry to the page one block earlier in the same
    prompt prefix (None for block 0); ``children`` is the reverse edge.
    Eviction only ever takes entries with no children, so the cache always
    holds *contiguous-from-block-0* chains — a match can stop at the first
    missing key without ever stranding unreachable descendants."""
    key: bytes
    page: int
    depth: int                          # block index within the prefix
    parent: Optional[bytes] = None
    pins: int = 0                       # live slots matched through this entry
    children: Set[bytes] = field(default_factory=set)


class PrefixCache:
    """Cross-request shared-prefix page cache layered on a PagedKVPool.

    The AoT-serving workload is many requests per task hammering the same
    per-task system prompt, and the per-task bias is position-independent:
    two requests for the SAME task with the same token prefix produce
    bitwise-identical KV pages. This cache extends PR 3's intra-request
    refcount/COW sharing to cross-request reuse: when a request finishes,
    its *full* prompt pages are retained here (the cache holds one
    refcount on each, exactly like a phantom slot) instead of returning to
    the free list; admission then maps a new request's longest matching
    run of full pages straight into its block table and starts chunked
    prefill at the first uncached token.

    Keys are chained blake2b hashes: ``key_0 = H(task_id ‖ tokens[0:bs])``,
    ``key_i = H(key_{i-1} ‖ tokens[i·bs:(i+1)·bs])`` — the task id is in
    the root on purpose (Adaptive Prefix Tuning's point: the same tokens
    under a different task carry a different bias and different KV), and
    chaining makes a key cover the whole prefix, not just its own block,
    so a match is a plain dict walk.

    Capacity is bounded (``capacity`` entries == pages) with LRU eviction
    over *childless, unpinned* entries — pinned entries (matched by a live
    slot) and interior chain entries are never evicted, so under page
    pressure the cache yields its coldest leaves first and the pool only
    falls back to preemption when the cache has nothing left to give.
    Eviction drops the cache's refcount; the page returns to the free list
    only when no slot still maps it.
    """

    def __init__(self, pool: "PagedKVPool", capacity: int):
        assert capacity >= 1, capacity
        self.pool = pool
        self.capacity = capacity
        self.block_size = pool.block_size
        self._entries: "OrderedDict[bytes, _PrefixEntry]" = OrderedDict()
        self._slot_pins: Dict[int, List[bytes]] = {}    # slot -> pinned keys
        self.hits = 0                   # admissions that matched >= 1 page
        self.misses = 0                 # admissions that matched nothing
        self.hit_tokens = 0             # prefill tokens skipped via matches
        self.retained_pages = 0         # entries ever inserted
        self.evicted_pages = 0          # entries ever evicted
        self._m = None                  # optional obs instruments

    # ------------------------------------------------------------------
    # hashing
    # ------------------------------------------------------------------
    def _chain_keys(self, task_id: int, toks, nblocks: int) -> List[bytes]:
        return chain_keys(task_id, toks, self.block_size, nblocks)

    # ------------------------------------------------------------------
    # lookup / insert
    # ------------------------------------------------------------------
    def match(self, task_id: int, toks) -> List[bytes]:
        """Longest cached run of full pages prefixing ``toks``, as entry
        keys (block 0 first). Capped at ``(len(toks) - 1) // block_size``
        pages: the last prefill token must always be recomputed because
        its *logits* (not just its KV) seed the first decode step."""
        limit = (len(toks) - 1) // self.block_size
        keys: List[bytes] = []
        for key in self._chain_keys(task_id, toks, limit):
            if key not in self._entries:
                break
            keys.append(key)
        for key in keys:                # one LRU touch per matched chain
            self._entries.move_to_end(key)
        return keys

    def pages(self, keys: Sequence[bytes]) -> List[int]:
        return [self._entries[k].page for k in keys]

    def record_lookup(self, matched_tokens: int) -> None:
        """Admission-time hit/miss accounting (one call per admission)."""
        if matched_tokens > 0:
            self.hits += 1
            self.hit_tokens += matched_tokens
            if self._m is not None:
                self._m["hits"].inc()
                self._m["hit_tokens"].inc(matched_tokens)
        else:
            self.misses += 1
            if self._m is not None:
                self._m["misses"].inc()

    def retain(self, task_id: int, prompt, slot: int) -> int:
        """Retain a finishing slot's full prompt pages: one cache refcount
        per page (bumped here, dropped at eviction), chain entries keyed
        by the prompt's block hashes. Already-cached keys are LRU-touched,
        not replaced — the first physical page to carry a prefix wins, and
        content equality makes the choice unobservable. Returns the number
        of pages newly retained. Over capacity, the coldest unpinned
        leaves are evicted first; if nothing is evictable the chain stops
        (a chain must stay contiguous from block 0)."""
        nfull = len(prompt) // self.block_size
        if nfull == 0:
            return 0
        pages = self.pool._pages[slot]
        keys = self._chain_keys(task_id, prompt, nfull)
        protect = set(keys)
        parent: Optional[bytes] = None
        added = 0
        for i, key in enumerate(keys):
            ent = self._entries.get(key)
            if ent is not None:
                self._entries.move_to_end(key)
                parent = key
                continue
            if len(self._entries) >= self.capacity and \
                    not self._evict_lru(protect=protect):
                break
            page = pages[i]
            self._entries[key] = _PrefixEntry(
                key=key, page=page, depth=i, parent=parent)
            self.pool._refs[page] += 1
            if parent is not None:
                self._entries[parent].children.add(key)
            parent = key
            added += 1
        if added:
            self.retained_pages += added
            if self._m is not None:
                self._m["retained"].inc(added)
            self._gauge_sync()
        return added

    # ------------------------------------------------------------------
    # pinning (live slots matched through the cache)
    # ------------------------------------------------------------------
    def pin(self, keys: Sequence[bytes]) -> None:
        for k in keys:
            self._entries[k].pins += 1

    def unpin(self, keys: Sequence[bytes]) -> None:
        for k in keys:
            self._entries[k].pins -= 1

    def bind_slot(self, slot: int, keys: Sequence[bytes]) -> None:
        """Record already-pinned ``keys`` against ``slot`` so the pool's
        ``free(slot)`` releases the pins no matter which path (finish,
        preempt, abort, shutdown) tears the slot down."""
        self._slot_pins[slot] = list(keys)

    def release_slot(self, slot: int) -> None:
        keys = self._slot_pins.pop(slot, None)
        if keys:
            self.unpin(keys)
            self._gauge_sync()

    # ------------------------------------------------------------------
    # eviction
    # ------------------------------------------------------------------
    def _evict_lru(self, protect: Optional[Set[bytes]] = None) -> bool:
        """Evict the least-recently-used childless unpinned entry (skipping
        ``protect``). Returns False when nothing is evictable."""
        for key, ent in self._entries.items():
            if ent.pins or ent.children or (protect and key in protect):
                continue
            self._evict_entry(key)
            return True
        return False

    def _evict_entry(self, key: bytes) -> None:
        ent = self._entries.pop(key)
        assert not ent.pins and not ent.children, "evicted a live entry"
        if ent.parent is not None:
            parent = self._entries.get(ent.parent)
            if parent is not None:
                parent.children.discard(key)
        pool = self.pool
        pool._refs[ent.page] -= 1
        if pool._refs[ent.page] == 0:
            pool._free_blocks.append(ent.page)
            if pool._m is not None:
                pool._m["freed"].inc()
        self.evicted_pages += 1
        if self._m is not None:
            self._m["evicted"].inc()
        self._gauge_sync()

    def reclaim(self, npages: int) -> bool:
        """Evict until the pool's free list holds ``npages`` pages (or
        nothing more is evictable). Evicting an entry whose page a slot
        still maps frees no page but unlocks its ancestors, so the loop
        keeps going while eviction makes *any* progress."""
        while len(self.pool._free_blocks) < npages:
            if not self._evict_lru():
                return False
        return True

    def evictable_free(self, exclude: Sequence[bytes] = ()) -> int:
        """How many pages eviction could return to the free list right
        now, treating ``exclude`` keys as pinned (admission passes the
        keys it is about to match so a hit's own pages are never counted
        as reclaimable headroom). An entry is removable only when it and
        every descendant are unpinned and unexcluded; a removable entry
        frees a page only when the cache holds its last reference."""
        excl = set(exclude)
        removable: Dict[bytes, bool] = {}
        ents = sorted(self._entries.values(), key=lambda e: -e.depth)
        for ent in ents:                # children strictly deeper: done first
            removable[ent.key] = (
                ent.pins == 0 and ent.key not in excl
                and all(removable[c] for c in ent.children))
        return sum(1 for ent in ents
                   if removable[ent.key] and self.pool._refs[ent.page] == 1)

    def flush(self) -> int:
        """Evict every evictable entry (drain/shutdown). Returns the
        number of pages returned to the free list; pinned entries — live
        requests — survive."""
        before = len(self.pool._free_blocks)
        while self._evict_lru():
            pass
        return len(self.pool._free_blocks) - before

    # ------------------------------------------------------------------
    # introspection / observability
    # ------------------------------------------------------------------
    def __len__(self) -> int:
        return len(self._entries)

    def cached_pages(self) -> Set[int]:
        return {e.page for e in self._entries.values()}

    def pinned_entries(self) -> int:
        return sum(1 for e in self._entries.values() if e.pins)

    def attach_metrics(self, registry) -> None:
        self._m = {
            "hits": registry.counter(
                "prefix_cache_hits_total",
                "admissions that mapped >= 1 cached prefix page"),
            "misses": registry.counter(
                "prefix_cache_misses_total",
                "admissions that matched no cached prefix"),
            "hit_tokens": registry.counter(
                "prefix_cache_hit_tokens_total",
                "prefill tokens skipped via cached prefix pages"),
            "retained": registry.counter(
                "prefix_cache_retained_pages_total",
                "prompt pages retained at request finish"),
            "evicted": registry.counter(
                "prefix_cache_evicted_pages_total",
                "cache entries evicted (LRU or reclaim)"),
            "entries": registry.gauge(
                "prefix_cache_pages", "cached prefix pages right now"),
            "pinned": registry.gauge(
                "prefix_cache_pinned", "cache entries pinned by live slots"),
        }
        self._gauge_sync()

    def _gauge_sync(self) -> None:
        self.pool._gauge_sync()
        if self._m is not None:
            self._m["entries"].set(len(self._entries))
            self._m["pinned"].set(self.pinned_entries())


class PagedKVPool:
    """Block-granular decode cache: a global page pool + per-slot block tables.

    ``num_blocks`` counts physical pages *including* the reserved scratch
    page 0, so usable capacity is ``(num_blocks - 1) * block_size`` tokens.
    ``num_slots`` bounds the decode batch width (rows in the mixed step);
    HBM is bounded by pages actually mapped, so num_slots can far exceed
    what a contiguous pool could afford at the same budget.

    Pages are *refcounted* so slots can share them: :meth:`fork` claims a
    new slot whose block table aliases every page of the source slot (the
    n-samples-per-prompt path — one prefill, near-zero extra HBM), and
    :meth:`ensure_append_page` copies a shared tail page on the first
    divergent append (copy-on-write). Reads never need COW: pages below a
    slot's depth are append-only history, identical for every sharer.
    ``free`` decrements refcounts and only returns refcount-zero pages to
    the free list.
    """

    def __init__(self, model, num_slots: int, max_len: int,
                 block_size: int = 16, num_blocks: Optional[int] = None):
        self.model = model
        self.num_slots = num_slots
        self.max_len = max_len
        self.block_size = block_size
        self.max_pages = -(-max_len // block_size)
        if num_blocks is None:      # one max_len of pages per slot
            num_blocks = num_slots * self.max_pages + 1
        assert num_blocks >= self.max_pages + 1, (
            f"num_blocks {num_blocks} cannot hold even one max_len request "
            f"({self.max_pages} pages + scratch)")
        self.num_blocks = num_blocks
        self.cache = model.init_paged_cache(num_blocks, block_size)
        self.block_tables = np.zeros((num_slots, self.max_pages), np.int32)
        self.cur_len = np.zeros(num_slots, np.int32)
        self.task_id = np.zeros(num_slots, np.int32)
        self._free_slots: List[int] = list(range(num_slots - 1, -1, -1))
        self._used_slots: Set[int] = set()
        # page 0 is scratch: free rows in the mixed step scatter there and
        # unmapped table entries read it fully masked
        self._free_blocks: List[int] = list(range(num_blocks - 1, 0, -1))
        self._pages: Dict[int, List[int]] = {}
        self._refs = np.zeros(num_blocks, np.int32)  # sharers per page
        self.forks = 0
        self.cow_copies = 0
        self.peak_pages = 0                 # high-water blocks_in_use
        self._seized: Set[int] = set()      # pages held by fault injection
        self._quarantined: Set[int] = set()  # poisoned pages held for forensics
        self.quarantined_pages_total = 0    # cumulative quarantine holds
        self.compactions = 0                # compact() calls that freed pages
        self.pages_deduped = 0              # pages freed by compact()
        self.prefix_cache: Optional[PrefixCache] = None
        self._m = None                      # optional obs instruments

    def enable_prefix_cache(self, capacity: int) -> "PrefixCache":
        """Layer a cross-request :class:`PrefixCache` (``capacity`` pages)
        over this pool's free list. Enable before ``attach_metrics`` so
        the cache's instruments register alongside the pool's."""
        assert self.prefix_cache is None, "prefix cache already enabled"
        self.prefix_cache = PrefixCache(self, capacity)
        return self.prefix_cache

    # ------------------------------------------------------------------
    # observability (repro.obs): page-lifecycle counters + pressure gauges
    # ------------------------------------------------------------------
    def attach_metrics(self, registry) -> None:
        """Register this pool's instruments on an obs metrics registry.
        All bookkeeping here is host-side numpy between device steps, so
        the instruments only ever observe scalars the pool already holds
        — attaching cannot perturb the served tokens."""
        self._m = {
            "claimed": registry.counter(
                "kv_pages_claimed_total", "pages taken off the free list"),
            "freed": registry.counter(
                "kv_pages_freed_total", "pages returned to the free list"),
            "forks": registry.counter(
                "kv_forks_total", "COW slot forks (n>1 sampling)"),
            "cow": registry.counter(
                "kv_cow_copies_total", "shared tail pages copied on first "
                "divergent append"),
            "free": registry.gauge("kv_pages_free", "free pages right now"),
            "used": registry.gauge("kv_pages_used", "mapped pages right now"),
            "peak": registry.gauge("kv_pages_peak", "high-water mapped pages"),
            "refs": registry.gauge(
                "kv_page_refs_max", "max sharers of any one page"),
            "slots_used": registry.gauge(
                "kv_slots_used", "occupied decode slots"),
            "quarantined_total": registry.counter(
                "kv_pages_quarantined_total",
                "poisoned pages moved to the quarantine hold"),
            "quarantined": registry.gauge(
                "kv_pages_quarantined", "pages in the quarantine hold now"),
            "compactions": registry.counter(
                "kv_compactions_total",
                "defrag passes that freed at least one page"),
            "deduped": registry.counter(
                "kv_pages_deduped_total",
                "duplicate prompt pages freed by compaction"),
        }
        if self.prefix_cache is not None:
            self.prefix_cache.attach_metrics(registry)
        self._gauge_sync()

    def _gauge_sync(self) -> None:
        used = self.blocks_in_use()
        self.peak_pages = max(self.peak_pages, used)
        if self._m is None:
            return
        m = self._m
        m["free"].set(len(self._free_blocks))
        m["used"].set(used)
        m["peak"].set_max(used)
        m["refs"].set(int(self._refs.max()))
        m["slots_used"].set(len(self._used_slots))
        m["quarantined"].set(len(self._quarantined))

    # ------------------------------------------------------------------
    # capacity queries
    # ------------------------------------------------------------------
    def has_free(self) -> bool:
        return bool(self._free_slots)

    def num_free(self) -> int:
        return len(self._free_slots)

    def free_blocks(self) -> int:
        return len(self._free_blocks)

    def blocks_in_use(self) -> int:
        return self.num_blocks - 1 - len(self._free_blocks)

    def pages_needed(self, tokens: int) -> int:
        return -(-tokens // self.block_size)

    def num_seized(self) -> int:
        """Pages currently held by fault injection (see seize_pages)."""
        return len(self._seized)

    def num_quarantined(self) -> int:
        """Pages in the quarantine hold (see quarantine_slot)."""
        return len(self._quarantined)

    def can_claim(self, npages: int, reserve: int = 0,
                  exclude_keys: Sequence[bytes] = ()) -> bool:
        """True when ``npages`` pages can be claimed while leaving at least
        ``reserve`` pages free. Admission paths that hold pages for many
        ticks before producing anything (chunked prefill) pass a reserve
        of one append page per running decode row, so claiming a prompt's
        pages can never starve the decode batch into preempting or
        aborting on its very next page-crossing.

        Pages the prefix cache could free by evicting unpinned entries
        count as claimable — claims evict on demand. ``exclude_keys``
        names cache entries the caller is about to map (a prefix hit):
        those pages must not double as reclaimable headroom, since
        pinning them is exactly what the claim will do."""
        avail = len(self._free_blocks)
        if self.prefix_cache is not None:
            avail += self.prefix_cache.evictable_free(exclude=exclude_keys)
        return avail >= npages + reserve

    def occupied(self) -> List[int]:
        return sorted(self._used_slots)

    def kv_bytes_per_token(self) -> int:
        tot = 0
        for leaf in jax.tree.leaves(self.cache):
            tot += (leaf.size // (self.num_blocks * self.block_size)) * leaf.dtype.itemsize
        return tot

    # ------------------------------------------------------------------
    # slot + page lifecycle
    # ------------------------------------------------------------------
    def _reclaim(self, npages: int) -> bool:
        """Ensure ``npages`` pages sit on the free list, evicting cold
        prefix-cache entries if needed. False when even eviction cannot
        get there."""
        if len(self._free_blocks) >= npages:
            return True
        if self.prefix_cache is None:
            return False
        return self.prefix_cache.reclaim(npages)

    def alloc(self, task_id: int = 0, npages: int = 0) -> Optional[int]:
        """Claim a slot plus ``npages`` pages (None if either is short)."""
        assert npages <= self.max_pages, (
            f"{npages} pages exceeds max_len ({self.max_pages} pages)")
        if not self._free_slots or not self._reclaim(npages):
            return None
        slot = self._free_slots.pop()
        self._used_slots.add(slot)
        self.task_id[slot] = task_id
        self.cur_len[slot] = 0
        pages = [self._free_blocks.pop() for _ in range(npages)]
        self._pages[slot] = pages
        self._refs[pages] = 1
        self.block_tables[slot, :npages] = pages
        if self._m is not None:
            self._m["claimed"].inc(npages)
        self._gauge_sync()
        return slot

    def alloc_cached(self, task_id: int, keys: Sequence[bytes],
                     npages_total: int) -> Optional[int]:
        """Claim a slot whose leading pages ALIAS the prefix-cache entries
        ``keys`` (a refcount bump per page — the cross-request analog of
        :meth:`fork`), plus fresh pages up to ``npages_total``. The
        matched entries are pinned until the slot frees, so page pressure
        can never evict a prefix out from under a live request. Returns
        None when no slot is free or fresh pages cannot be claimed even
        after cache eviction."""
        cache = self.prefix_cache
        assert cache is not None and keys, "alloc_cached needs a cache hit"
        npages_new = npages_total - len(keys)
        assert 0 <= npages_new and npages_total <= self.max_pages
        if not self._free_slots:
            return None
        cache.pin(keys)         # freeze the hit before eviction-for-claim
        if not self._reclaim(npages_new):
            cache.unpin(keys)
            return None
        slot = self._free_slots.pop()
        self._used_slots.add(slot)
        self.task_id[slot] = task_id
        self.cur_len[slot] = 0
        shared = cache.pages(keys)
        for p in shared:
            self._refs[p] += 1
        fresh = [self._free_blocks.pop() for _ in range(npages_new)]
        self._refs[fresh] = 1
        pages = shared + fresh
        self._pages[slot] = pages
        self.block_tables[slot, :len(pages)] = pages
        cache.bind_slot(slot, keys)
        if self._m is not None:
            self._m["claimed"].inc(npages_new)
        cache._gauge_sync()
        return slot

    def fork(self, slot: int) -> Optional[int]:
        """Claim a new slot sharing every page of ``slot`` (refcount bump,
        zero page copies). The forked slot inherits depth and task id; the
        first divergent append on either sharer triggers COW in
        :meth:`ensure_append_page`. Returns None when no slot is free."""
        if slot not in self._used_slots:
            raise ValueError(f"slot {slot} is not allocated")
        if not self._free_slots:
            return None
        new = self._free_slots.pop()
        self._used_slots.add(new)
        pages = list(self._pages[slot])
        self._pages[new] = pages
        for p in pages:
            self._refs[p] += 1
        self.block_tables[new] = self.block_tables[slot]
        self.cur_len[new] = self.cur_len[slot]
        self.task_id[new] = self.task_id[slot]
        self.forks += 1
        if self._m is not None:
            self._m["forks"].inc()
        self._gauge_sync()
        return new

    def ensure_append_page(self, slot: int) -> bool:
        """Map (and exclusively own) the page holding depth ``cur_len[slot]``
        — the next decode append. A shared tail page (refcount > 1 after a
        fork) is copied to a fresh page first, so sharers never see each
        other's divergent rows; the last sharer left writes in place.
        Returns False when the pool is out of pages — the caller must
        preempt someone or stall."""
        need = int(self.cur_len[slot]) // self.block_size
        pages = self._pages[slot]
        if need < len(pages):
            page = pages[need]
            if self._refs[page] == 1:
                return True
            if not self._reclaim(1):    # COW needs a destination page
                return False
            new = self._free_blocks.pop()
            self.cache = _copy_page(self.cache, page, new)
            self._refs[page] -= 1
            self._refs[new] = 1
            pages[need] = new
            self.block_tables[slot, need] = new
            self.cow_copies += 1
            if self._m is not None:
                self._m["cow"].inc()
                self._m["claimed"].inc()
            self._gauge_sync()
            return True
        assert need == len(pages), "append skipped a page"
        if not self._reclaim(1):
            return False
        page = self._free_blocks.pop()
        self._refs[page] = 1
        pages.append(page)
        self.block_tables[slot, need] = page
        if self._m is not None:
            self._m["claimed"].inc()
        self._gauge_sync()
        return True

    def seize_pages(self, n: int) -> List[int]:
        """Fault injection: pull up to ``n`` pages off the free list so the
        pool looks exhausted to the scheduler (admission backpressure,
        preemption, prefill aborts — the real overload machinery, not a
        mock). Seized pages hold no KV and are never mapped; give them
        back with :meth:`restore_pages`. A drain-time
        :meth:`leak_report` counts still-seized pages as a finding, so a
        fault plan that forgets to restore fails loudly."""
        take = min(max(n, 0), len(self._free_blocks))
        pages = [self._free_blocks.pop() for _ in range(take)]
        self._seized.update(pages)
        self._gauge_sync()
        return pages

    def flush_prefix_cache(self) -> int:
        """Evict every evictable prefix-cache entry (graceful drain);
        returns the number of pages released to the free list. No-op (0)
        without a cache."""
        if self.prefix_cache is None:
            return 0
        return self.prefix_cache.flush()

    def restore_pages(self, pages: List[int]) -> None:
        """Return pages taken by :meth:`seize_pages` to the free list."""
        for p in pages:
            if p not in self._seized:
                raise ValueError(f"page {p} was not seized")
            self._seized.remove(p)
            self._free_blocks.append(p)
        self._gauge_sync()

    def free(self, slot: int) -> None:
        if slot not in self._used_slots:
            raise ValueError(f"slot {slot} is not allocated")
        self._used_slots.remove(slot)
        if self.prefix_cache is not None:   # release the slot's prefix pins
            self.prefix_cache.release_slot(slot)
        returned = 0
        for page in reversed(self._pages.pop(slot)):
            self._refs[page] -= 1
            if self._refs[page] == 0:
                self._free_blocks.append(page)
                returned += 1
        self.block_tables[slot] = 0
        self.cur_len[slot] = 0
        self.task_id[slot] = 0
        self._free_slots.append(slot)
        if self._m is not None:
            self._m["freed"].inc(returned)
        self._gauge_sync()

    def quarantine_slot(self, slot: int) -> int:
        """:meth:`free` variant for poisoned requests (NaN/inf logits): the
        slot returns to the free list, but every page the slot exclusively
        owned goes to a quarantine hold instead — never reallocated, so
        the KV that produced the bad logits stays dumpable for post-mortem
        until :meth:`release_quarantined` (the scheduler's shutdown calls
        it). Pages still shared with other slots or the prefix cache just
        drop this slot's refcount as usual: their content is vouched for
        by the surviving sharers. Returns the number of pages held."""
        if slot not in self._used_slots:
            raise ValueError(f"slot {slot} is not allocated")
        self._used_slots.remove(slot)
        if self.prefix_cache is not None:
            self.prefix_cache.release_slot(slot)
        held = 0
        for page in reversed(self._pages.pop(slot)):
            self._refs[page] -= 1
            if self._refs[page] == 0:
                self._quarantined.add(page)
                held += 1
        self.block_tables[slot] = 0
        self.cur_len[slot] = 0
        self.task_id[slot] = 0
        self._free_slots.append(slot)
        self.quarantined_pages_total += held
        if self._m is not None:
            self._m["quarantined_total"].inc(held)
        self._gauge_sync()
        return held

    def release_quarantined(self) -> int:
        """Return every quarantine-held page to the free list. Returns the
        count released."""
        n = len(self._quarantined)
        self._free_blocks.extend(sorted(self._quarantined, reverse=True))
        self._quarantined.clear()
        if self._m is not None and n:
            self._m["freed"].inc(n)
        self._gauge_sync()
        return n

    def compact(self, slot_prompts: Dict[int, Any]) -> int:
        """On-device paged-KV defrag: deduplicate identical full prompt
        pages across committed slots by remapping block tables, so pages
        fragmented across duplicate prompts come back without the cost of
        preempt-and-recompute.

        ``slot_prompts`` maps each candidate slot to its request's PROMPT
        tokens (not the recompute suffix). Callers must pass only
        *committed* slots (running decode rows) — never slots mid-prefill,
        whose pages the ragged kernel is still scattering into. Safety of
        the remap rests on three existing invariants: full prompt pages
        below a slot's append page are append-only history and never
        written again; content identity comes from the same chained hashes
        the prefix cache trusts (equal key ⇒ bitwise-equal page); and
        every future append goes through :meth:`ensure_append_page`, which
        COWs any shared page before writing. Prefix-cache pages seed the
        canonical-owner map, so duplicates fold into cached pages first
        (refcounts keep them alive across eviction — no pinning needed).

        Returns the number of pages returned to the free list."""
        owner: Dict[bytes, int] = {}
        if self.prefix_cache is not None:
            for ent in self.prefix_cache._entries.values():
                owner[ent.key] = ent.page
        freed = 0
        for slot in sorted(slot_prompts):
            if slot not in self._used_slots:
                continue
            prompt = np.asarray(slot_prompts[slot])
            nfull = len(prompt) // self.block_size
            # belt and braces: never touch the page decode appends into
            nfull = min(nfull, int(self.cur_len[slot]) // self.block_size,
                        len(self._pages[slot]))
            if nfull <= 0:
                continue
            keys = chain_keys(int(self.task_id[slot]), prompt,
                              self.block_size, nfull)
            pages = self._pages[slot]
            for i, key in enumerate(keys):
                page = pages[i]
                canon = owner.setdefault(key, page)
                if canon == page:
                    continue
                self._refs[canon] += 1
                self._refs[page] -= 1
                if self._refs[page] == 0:
                    self._free_blocks.append(page)
                    freed += 1
                pages[i] = canon
                self.block_tables[slot, i] = canon
        if freed:
            self.compactions += 1
            self.pages_deduped += freed
            if self._m is not None:
                self._m["compactions"].inc()
                self._m["deduped"].inc(freed)
                self._m["freed"].inc(freed)
            self._gauge_sync()
        return freed

    # ------------------------------------------------------------------
    # cache writes
    # ------------------------------------------------------------------
    def commit_prefill(self, slot: int, length: int) -> None:
        """Publish a prefill whose KV the unified serve step already
        scattered straight into this slot's mapped pages — bookkeeping
        only, no cache copy (the whole point of the ragged mixed step)."""
        if length > self.max_len:
            raise ValueError(f"prompt length {length} exceeds pool max_len "
                             f"{self.max_len}")
        assert len(self._pages[slot]) >= self.pages_needed(length), (
            f"slot {slot}: {len(self._pages[slot])} pages mapped, prefill "
            f"wrote {length} tokens")
        self.cur_len[slot] = length

    def advance(self, slots) -> None:
        """Record one decode append for each slot in ``slots``."""
        for s in slots:
            self.cur_len[s] += 1

    # ------------------------------------------------------------------
    def leak_report(self) -> List[str]:
        """Invariant sweep: slots partition into free/used; every page's
        refcount equals the number of holders referencing it — slots
        mapping it plus one for the prefix cache if it retains it; pages
        partition into free / mapped / seized / cache-retained (scratch
        page 0 excluded). Cache-retained pages are a *distinct category*,
        neither leaked nor free: a warm cache at drain time is by design,
        so ``--check-leaks`` stays clean without flushing it.

        Returns human-readable findings (empty = clean) instead of
        asserting — the scheduler's drain-time debug check
        (``SchedulerConfig.check_leaks``) reports them through the obs
        metrics snapshot so live ``launch/serve.py`` runs catch page
        leaks in the wild; tests assert via :meth:`check_no_leaks`."""
        bad: List[str] = []
        free = set(self._free_slots)
        if len(self._free_slots) != len(free):
            bad.append("duplicate slots on free list")
        both = free & self._used_slots
        if both:
            bad.append(f"slots both free and used: {sorted(both)}")
        lost = set(range(self.num_slots)) - (free | self._used_slots)
        if lost:
            bad.append(f"lost slots (neither free nor used): {sorted(lost)}")
        deep = [s for s in free if self.cur_len[s] != 0]
        if deep:
            bad.append(f"freed slots with nonzero length: {deep}")
        if set(self._pages) != self._used_slots:
            bad.append("page map out of sync with used slots: "
                       f"{sorted(set(self._pages) ^ self._used_slots)}")
        fb = set(self._free_blocks)
        if len(self._free_blocks) != len(fb):
            bad.append("duplicate pages on free list")
        if 0 in fb:
            bad.append("scratch page 0 leaked onto the free list")
        refs = np.zeros(self.num_blocks, np.int32)
        for slot, pages in self._pages.items():
            ps = set(pages)
            if len(pages) != len(ps):
                bad.append(f"slot {slot} double-mapped a page")
            if 0 in ps:
                bad.append(f"slot {slot} mapped the scratch page")
            if len(pages) < self.pages_needed(int(self.cur_len[slot])):
                bad.append(f"slot {slot} is deeper than its mapped pages")
            refs[pages] += 1
        cached: Set[int] = set()
        cache = self.prefix_cache
        if cache is not None:
            ents = list(cache._entries.values())
            cpages = [e.page for e in ents]
            cached = set(cpages)
            if len(cpages) != len(cached):
                bad.append("prefix cache retained the same page twice")
            if 0 in cached:
                bad.append("prefix cache retained the scratch page")
            refs[cpages] += 1   # the cache's own hold on each retained page
            for e in ents:
                if e.parent is not None and e.parent not in cache._entries:
                    bad.append(f"prefix cache chain broken at depth {e.depth} "
                               "(parent entry evicted under a child)")
            pins = {}
            for keys in cache._slot_pins.values():
                for k in keys:
                    pins[k] = pins.get(k, 0) + 1
            for e in ents:
                if e.pins != pins.get(e.key, 0):
                    bad.append("prefix cache pin counts out of sync with "
                               "slot bindings")
                    break
            stray = set(cache._slot_pins) - self._used_slots
            if stray:
                bad.append(f"prefix cache pins held by freed slots: "
                           f"{sorted(stray)}")
        if not np.array_equal(refs, self._refs):
            off = np.nonzero(refs != self._refs)[0]
            bad.append(f"page refcounts out of sync at pages {off.tolist()}")
        mapped = {p for pages in self._pages.values() for p in pages}
        if fb & mapped:
            bad.append(f"pages both free and mapped: {sorted(fb & mapped)}")
        if fb & cached:
            bad.append(f"pages both free and cache-retained: "
                       f"{sorted(fb & cached)}")
        if self._seized & (fb | mapped | cached):
            bad.append(f"seized pages also free, mapped, or cached: "
                       f"{sorted(self._seized & (fb | mapped | cached))}")
        if self._quarantined & (fb | mapped | cached | self._seized):
            bad.append(
                f"quarantined pages also free, mapped, cached, or seized: "
                f"{sorted(self._quarantined & (fb | mapped | cached | self._seized))}")
        if self._seized:
            bad.append(f"pages still seized by fault injection: "
                       f"{sorted(self._seized)}")
        # cache-retained and quarantine-held pages are accounted, NOT
        # leaked: a warm cache is exactly the state a drained server
        # should keep, and a quarantine hold is a deliberate forensic
        # choice released explicitly (shutdown does). Seized pages by
        # contrast are always a finding — a fault plan must restore them.
        leaked = set(range(1, self.num_blocks)) - (
            fb | mapped | self._seized | cached | self._quarantined)
        if leaked:
            bad.append(f"leaked pages (neither free, mapped, "
                       f"cache-retained, nor quarantined): {sorted(leaked)}")
        return bad

    def check_no_leaks(self) -> None:
        report = self.leak_report()
        assert not report, "paged pool invariants violated: " + "; ".join(report)
