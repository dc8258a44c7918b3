"""Content-addressed shared-prefix index over the paged KV pool.

RAG chat traffic is dominated by shared prefixes: every request repeats
the system prompt and every follow-up turn repeats the whole prior
conversation, so recomputing prefill for those tokens is pure waste.
This module is the host-side index that lets the engine skip it — the
block-level KV reuse behind vLLM's PagedAttention prefix caching
(Kwon et al., SOSP 2023) and SGLang's RadixAttention (Zheng et al.,
2024), adapted to this repo's paged pool:

- **Block hashing.** The token stream is hashed in page-sized blocks
  with each block's hash chained through its parent's, so a block hash
  identifies the entire prefix up to and including that block — two
  different conversations can never collide on a mid-stream block.
  Chaining makes the plain dict below an implicit trie: walking
  ``hashes[0..k]`` in order IS the root-to-leaf descent.
- **Refcounted pages.** Each cached block maps to one physical pool
  page plus a refcount of the live requests mapping it. Pages at
  refcount 0 stay resident (warm for the next turn) and are reclaimed
  leaf-first in LRU order only under pool pressure — the pool itself
  stays the single capacity authority (the engine's ``kv_pool_tokens``
  sizing; there is no second cache budget to mistune).
- **Copy-on-write demotion.** A request must prefill at least one
  token to sample its first output, and the paged chunk prefill writes
  whole page-aligned blocks — so when a prompt is *fully* covered by
  cached blocks, the final block is demoted: its shared page is NOT
  mapped; the engine allocates a private page for that logical slot
  and recomputes the block into it (``usable_prefix_tokens``). The
  write that would have hit a shared page lands on a private copy —
  copy-on-write where the "copy" is a full-block recompute, which the
  chunk geometry makes total (no partial-page device copy needed).

The cache is mutated only from the engine's serve loop thread; the
engine republishes counters under its own stats lock.
"""

from __future__ import annotations

import hashlib
import heapq
from dataclasses import dataclass, field
from typing import Callable, Optional, Sequence

BlockHash = bytes


def hash_blocks(token_ids: Sequence[int], page_size: int) -> list[BlockHash]:
    """Chained content hashes of the stream's FULL page-sized blocks.

    Block i's hash covers tokens [0, (i+1)*page) via the parent chain, so
    equal hashes mean equal whole prefixes. The trailing partial block
    (if any) is not hashed — only whole pages are shareable. blake2b
    (16-byte digests) rather than Python ``hash()``: a collision here
    would silently serve another conversation's KV, so the hash must be
    cryptographic, not merely well-distributed.
    """
    out: list[BlockHash] = []
    parent = b""
    n_full = len(token_ids) // page_size
    if not n_full:
        return out
    # One numpy render of the hashable span: this runs on the serve
    # loop's admission path, where a per-token Python to_bytes loop on a
    # 16k-token prompt would cost real milliseconds per attempt.
    import numpy as np
    raw = np.asarray(token_ids[:n_full * page_size], dtype="<i4").tobytes()
    stride = 4 * page_size
    for i in range(n_full):
        h = hashlib.blake2b(parent, digest_size=16)
        h.update(raw[i * stride:(i + 1) * stride])
        parent = h.digest()
        out.append(parent)
    return out


def usable_prefix_tokens(matched_blocks: int, n_tokens: int,
                         page_size: int) -> int:
    """How many prompt tokens a match of ``matched_blocks`` blocks lets
    admission actually skip. Always page-aligned (the paged chunk
    prefill starts on page boundaries) and always < ``n_tokens``: at
    least one token must run through prefill to produce first-token
    logits, so a full-cover match is capped one block short — the COW
    demotion (module docstring)."""
    start = min(matched_blocks * page_size, n_tokens)
    if start >= n_tokens:
        start = ((n_tokens - 1) // page_size) * page_size
    return start


@dataclass
class _Entry:
    page: int
    parent: Optional[BlockHash]
    refcount: int = 0
    children: int = 0     # live child entries (chain integrity for eviction)
    tick: int = 0         # LRU recency, bumped on release


@dataclass
class CacheStats:
    hit_tokens: int = 0
    lookup_tokens: int = 0
    hits: int = 0          # lookups that matched >= 1 block
    lookups: int = 0
    evicted_pages: int = 0
    inserted_pages: int = 0

    def snapshot(self) -> dict:
        return {
            "prefix_cache_hit_tokens": self.hit_tokens,
            "prefix_cache_lookup_tokens": self.lookup_tokens,
            "prefix_cache_hits": self.hits,
            "prefix_cache_lookups": self.lookups,
            "prefix_cache_evicted_pages": self.evicted_pages,
            "prefix_cache_hit_rate": (
                self.hit_tokens / self.lookup_tokens
                if self.lookup_tokens else 0.0),
        }


@dataclass
class PrefixCache:
    """Block-chain hash -> pool page map with refcounts + LRU reclaim."""

    page_size: int
    _entries: dict[BlockHash, _Entry] = field(default_factory=dict)
    _pages: dict[int, BlockHash] = field(default_factory=dict)  # reverse map
    _tick: int = 0
    # Evictable-leaf min-heap of (tick, hash), maintained INCREMENTALLY:
    # an entry is pushed when it becomes evictable (released to
    # refcount 0 with no children; or its last child goes) and lazily
    # invalidated — acquire/insert never touch the heap, a popped entry
    # is re-checked against the live _Entry (refcount, children, tick)
    # and skipped when stale. evict() therefore does O(log n) work per
    # freed page plus O(stale) skips, never an O(entries) rescan per
    # admission (warm-chat steady state evicts nearly every admission).
    _heap: list = field(default_factory=list)
    # Entries some live request holds a ref on (refcount > 0), kept as a
    # count so the pool gauge (Engine: pool_used_pages) costs O(1).
    pinned_pages: int = 0
    stats: CacheStats = field(default_factory=CacheStats)

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def cached_pages(self) -> int:
        return len(self._pages)

    def page_of(self, h: BlockHash) -> Optional[int]:
        """Physical pool page holding a cached block, or None — the
        KV-tier export path's read-only probe (cached pages hold pure
        prompt KV and are immutable while resident, so reading them out
        is always safe)."""
        e = self._entries.get(h)
        return e.page if e is not None else None

    def owns(self, page: int) -> bool:
        """Whether this page is cache property (must NOT return to the
        free list on request retire — it keeps its content warm)."""
        return page in self._pages

    def match(self, hashes: Sequence[BlockHash]) -> int:
        """Longest cached prefix, in blocks. Chained hashes make this the
        trie descent: the first miss ends every longer chain too."""
        n = 0
        for h in hashes:
            if h not in self._entries:
                break
            n += 1
        return n

    def acquire(self, hashes: Sequence[BlockHash]) -> list[int]:
        """Ref every block of an (already-matched) chain prefix and
        return their pages in logical order. Caller must later
        ``release`` the same hashes exactly once."""
        pages = []
        for h in hashes:
            e = self._entries[h]
            self.pinned_pages += e.refcount == 0
            e.refcount += 1
            pages.append(e.page)
        return pages

    def _push_if_evictable(self, h: BlockHash, e: _Entry) -> None:
        """Heap maintenance: an entry enters the evictable-leaf heap the
        moment it becomes reclaimable. Duplicate pushes for the same
        hash (e.g. released, re-acquired, released again) are fine —
        stale copies carry an old tick and are skipped at pop."""
        if e.refcount == 0 and e.children == 0:
            heapq.heappush(self._heap, (e.tick, h))

    def release(self, hashes: Sequence[BlockHash]) -> None:
        """Drop one ref per hash (request retire). Refcount-0 entries
        stay resident — reclaimable leaf-first by ``evict`` — with
        their LRU recency bumped to now."""
        self._tick += 1
        for h in hashes:
            e = self._entries[h]
            e.refcount -= 1
            self.pinned_pages -= e.refcount == 0
            e.tick = self._tick
            if e.refcount < 0:  # pragma: no cover - invariant guard
                raise AssertionError("prefix cache refcount underflow")
            self._push_if_evictable(h, e)

    def insert(self, h: BlockHash, parent: Optional[BlockHash],
               page: int) -> bool:
        """Register a freshly prefilled block. Returns True when the
        cache took ownership of ``page`` (entry created, one ref held by
        the registering request); False when the chain hash is already
        cached — e.g. the COW-demoted tail block of a full-cover match,
        recomputed into a private page — in which case the caller keeps
        the page private and holds no ref."""
        if h in self._entries:
            return False
        if parent is not None:
            self._entries[parent].children += 1
        self._entries[h] = _Entry(page=page, parent=parent, refcount=1)
        self.pinned_pages += 1
        self._pages[page] = h
        self.stats.inserted_pages += 1
        return True

    def _unlink(self, h: BlockHash, victim: _Entry) -> None:
        """Remove one evictable entry, keeping chain integrity: the
        parent's child count drops, and a parent that just became an
        evictable leaf joins the heap."""
        if victim.parent is not None:
            parent = self._entries[victim.parent]
            parent.children -= 1
            self._push_if_evictable(victim.parent, parent)
        del self._entries[h]
        del self._pages[victim.page]

    def evict(self, n_pages: int,
              sink: Optional[Callable[[BlockHash, _Entry], None]] = None
              ) -> list[int]:
        """Reclaim up to ``n_pages`` refcount-0 pages, LRU first and
        leaf-first (a parent only becomes evictable once its children
        are gone, so every resident chain stays walkable root-to-leaf).
        Returns the freed page ids.

        Runs on the serve loop's admission path, and in warm-chat steady
        state (pool full of resident prefixes) nearly EVERY admission
        evicts — so the evictable-leaf heap is maintained INCREMENTALLY
        across calls (pushed on release-to-zero and last-child-gone,
        lazily invalidated on acquire/remove): O(log entries) per freed
        page plus stale-entry skips, never an O(entries) rescan per
        call (pinned by the no-rescan counting test).

        ``sink`` is called with ``(hash, entry)`` for each victim just
        BEFORE removal — the engine's KV-tier offload hook (the entry's
        page content is about to leave HBM)."""
        freed: list[int] = []
        while self._heap and len(freed) < n_pages:
            tick, h = heapq.heappop(self._heap)
            victim = self._entries.get(h)
            if victim is None or victim.refcount or victim.children \
                    or victim.tick != tick:
                continue  # stale: re-acquired, re-released, or removed
            if sink is not None:
                sink(h, victim)
            self._unlink(h, victim)
            freed.append(victim.page)
        self.stats.evicted_pages += len(freed)
        return freed

    def remove(self, h: BlockHash) -> Optional[int]:
        """Explicitly demote one block (session suspend): drop the entry
        and return its page — but only when it is reclaimable right now
        (refcount 0, no resident children). Returns None otherwise; the
        caller walks chains leaf-first so shared interior blocks simply
        stay resident. Not counted as a pressure eviction. Heap copies
        of the removed hash go stale and are skipped at pop."""
        e = self._entries.get(h)
        if e is None or e.refcount or e.children:
            return None
        self._unlink(h, e)
        return e.page
