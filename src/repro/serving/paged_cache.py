"""Paged KV cache: refcounted page store, block tables, prefix index.

DESIGN.md §paged-cache, §prefix-sharing.  The dense serving cache
allocates every slot at ``max_seq_len`` so HBM scales with the
worst-case request.  Here each attention layer's cache is a *pool* of
fixed-size pages

    kc: (P, Hkv, page_size, R_k)    vc: (P, Hkv, page_size, R_v)

and a single block table (shared by all layers, vLLM-style) maps
``(slot, logical_page) -> physical_page``.  A sequence of length L owns
``ceil(L / page_size)`` pages, so a mixed-length batch occupies
``sum_b ceil(len_b / ps)`` pages of HBM instead of ``B * max_seq_len``
— the same low-rank compressed ``R_k/R_v`` layout the paper pays for,
just allocated on demand (LoRC keeps compression *inside* the pages).

Pool invariants (enforced by ``PagePool``):

* physical page 0 is the **garbage page**: never allocated, never
  freed.  Freed slots' block-table rows are reset to 0, so masked
  writes from finished slots in the fused decode scan land in garbage
  instead of corrupting pages that were recycled to live sequences;
* pages are **refcounted** (DESIGN.md §prefix-sharing): ``alloc``
  hands out pages at refcount 1, ``share`` pins an extra reference
  (cross-request prefix sharing, the prefix index), and ``free``
  drops one reference — a page returns to the free list only at
  refcount zero, so releasing one sharer can never corrupt another;
* allocation is host-side and happens only at chunk boundaries
  (admission + ``ensure_capacity`` headroom for the next
  ``decode_chunk`` tokens), so the fused decode scan never allocates.

The device-side primitives (``append_token``, ``append_chunk``,
``copy_page``, ``gather_pages``) are pure jnp and jit-safe; the
allocator and the prefix index are plain numpy/Python host state.
They take a layer's pool either as an array or as a ``LayerPool``, one
layer of a pool stacked over the scanned layers: a write then stores
its entries into the stack in place, so a program that carries the
stack through the layer scan and donates it never rebuilds a pool.
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

GARBAGE_PAGE = 0


class PagePoolExhausted(RuntimeError):
    """No free pages left for a required allocation."""


class PagePool:
    """Host-side refcounted allocator over ``n_pages`` physical pages.

    Physical ids run ``1 .. n_pages`` (0 is the reserved garbage page);
    the backing arrays are sized ``n_pages + 1``.

    Refcounts (DESIGN.md §prefix-sharing): ``alloc`` returns pages at
    refcount 1, ``share`` increments (another slot or the prefix index
    pinning a page), ``free`` decrements and recycles the page only at
    zero.  ``used_count`` counts *distinct* live pages, so a prefix
    shared by ten requests occupies the pool once.

    Watermarks (DESIGN.md §preemption), as fractions of the pool:
    ``high_watermark`` caps how full optimistic admission may pack the
    pool (``can_admit``) so some headroom stays for decode growth;
    ``low_watermark`` becomes ``low_extra`` — slack pages a preemption
    pass frees *beyond* the strict deficit, so the very next chunk
    boundary does not immediately preempt again (thrash guard).
    """

    def __init__(self, n_pages: int, high_watermark: float = 1.0,
                 low_watermark: float = 0.0):
        assert n_pages >= 1, "pool needs at least one allocatable page"
        assert 0.0 < high_watermark <= 1.0
        assert 0.0 <= low_watermark < 1.0
        self.n_pages = n_pages
        self._free: List[int] = list(range(n_pages, 0, -1))  # pop() -> 1..
        self._refs = np.zeros(n_pages + 1, np.int32)
        self.high_pages = max(1, int(round(high_watermark * n_pages)))
        self.low_extra = int(round(low_watermark * n_pages))
        # optional FaultInjector (DESIGN.md §robustness): the engine
        # attaches its injector here so ``page_alloc`` exhaustion races
        # can be forced deterministically
        self.faults = None

    @property
    def free_count(self) -> int:
        """Pages currently on the free list."""
        return len(self._free)

    @property
    def used_count(self) -> int:
        """Pages currently allocated (including shared/pinned ones)."""
        return self.n_pages - len(self._free)

    def ref(self, page: int) -> int:
        """Current reference count of ``page``."""
        return int(self._refs[page])

    def can_admit(self, n: int) -> bool:
        """Optimistic-admission check: ``n`` pages are free *and* the
        pool stays at or below the high watermark afterwards."""
        return n <= len(self._free) and self.used_count + n <= self.high_pages

    def alloc(self, n: int) -> List[int]:
        """Pop ``n`` pages at refcount 1; raises PagePoolExhausted
        (allocating none) if fewer than ``n`` are free — or when the
        attached injector fires ``page_alloc`` (a forced exhaustion
        race; callers recover exactly as they would from the real
        thing)."""
        if n and self.faults is not None and self.faults.fires(
                "page_alloc"):
            raise PagePoolExhausted(
                f"injected page_alloc fault (need {n}, "
                f"{len(self._free)} free)")
        if n > len(self._free):
            raise PagePoolExhausted(
                f"need {n} pages, {len(self._free)} free"
                f" (pool of {self.n_pages})")
        pages = [self._free.pop() for _ in range(n)]
        self._refs[pages] = 1
        return pages

    def share(self, pages: Sequence[int]) -> None:
        """Pin one extra reference on each (live) page."""
        for p in pages:
            if p == GARBAGE_PAGE:
                raise ValueError("cannot share the garbage page")
            if not self._refs[p]:
                raise ValueError(f"share of unowned page {p}")
            self._refs[p] += 1

    def free(self, pages: Sequence[int]) -> None:
        """Drop one reference per page; recycle at refcount zero."""
        for p in pages:
            if p == GARBAGE_PAGE:
                raise ValueError("cannot free the garbage page")
            if not self._refs[p]:
                raise ValueError(f"double free of page {p}")
            self._refs[p] -= 1
            if not self._refs[p]:
                self._free.append(p)


class PrefixIndex:
    """Host-side prefix index: token-chunk chains -> physical pages
    (DESIGN.md §prefix-sharing).

    Each entry maps ``child_key(parent, chunk_tokens)`` — a digest
    chained over the page_size-aligned token chunks of a prompt — to
    the physical page whose cache entries were computed for exactly
    that token prefix.  Entries pin their page with one pool reference,
    so a finished request's prefix pages survive ``release`` for reuse
    until ``reclaim`` drops them under pool pressure (LRU; entries
    still shared by a live slot are skipped — dropping them frees
    nothing).

    A *terminal* entry (the final, possibly partial, chunk of a served
    prompt) may also carry the prompt's next-token ``logits``, letting
    an exact-duplicate prompt skip prefill entirely.
    """

    ROOT = b""

    def __init__(self, capacity: int):
        assert capacity >= 1
        self.capacity = capacity
        # key -> [page, n_tokens, logits]
        self._entries: "OrderedDict[bytes, List]" = OrderedDict()

    @staticmethod
    def child_key(parent: bytes, tokens) -> bytes:
        """Chained digest of one page-aligned token chunk.  The chain
        makes the key a function of the *whole* token prefix — cache
        entries at position t depend on every earlier token, so two
        chunks are interchangeable only if their full prefixes match."""
        raw = np.ascontiguousarray(np.asarray(tokens, np.int32))
        return hashlib.sha1(parent + raw.tobytes()).digest()

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def n_pinned(self) -> int:
        """Pages currently pinned by index references (one per entry)."""
        return len(self._entries)

    def insert(self, key: bytes, page: int, n_tokens: int, pool: PagePool,
               logits: Optional[np.ndarray] = None) -> bool:
        """Pin ``page`` under ``key``; no-op (plus optional logits
        attach and LRU bump) when the key is already cached — the
        caller's duplicate page stays private to its slot.  Returns
        whether a new entry was created."""
        assert page != GARBAGE_PAGE
        hit = self._entries.get(key)
        if hit is not None:
            self._entries.move_to_end(key)
            if logits is not None:
                hit[2] = logits
            return False
        pool.share([page])
        self._entries[key] = [page, n_tokens, logits]
        while len(self._entries) > self.capacity:
            _, (old_page, _, _) = self._entries.popitem(last=False)
            pool.free([old_page])
        return True

    def attach_logits(self, key: bytes, logits: np.ndarray) -> None:
        """Attach terminal next-token logits to an existing entry."""
        hit = self._entries.get(key)
        if hit is not None:
            hit[2] = logits

    def get(self, key: bytes
            ) -> Optional[Tuple[int, int, Optional[np.ndarray]]]:
        """Single-entry lookup with LRU bump (no reference taken):
        ``(page, n_tokens, logits)`` or None."""
        hit = self._entries.get(key)
        if hit is None:
            return None
        self._entries.move_to_end(key)
        return hit[0], hit[1], hit[2]

    def touch(self, keys) -> None:
        """LRU-bump entries a caller is about to share."""
        for k in keys:
            if k in self._entries:
                self._entries.move_to_end(k)

    def walk(self, prompt: np.ndarray, page_size: int
             ) -> Tuple[List[Tuple[bytes, int, int]], bytes, int,
                        Optional[np.ndarray]]:
        """Longest cached prefix of ``prompt`` (read-only; no refs).

        Returns ``(hits, chain_key, full_tokens, logits)``: ``hits``
        is a list of ``(key, page, n_tokens)`` per matched chunk
        (full page_size chunks, then at most one shorter terminal
        chunk), ``chain_key`` / ``full_tokens`` describe the fully
        page-aligned part of the match (the parent for indexing this
        prompt's *next* full page), and ``logits`` is the stored
        next-token logits when the match covers the whole prompt and
        a terminal entry carries them."""
        L = len(prompt)
        key = self.ROOT
        hits: List[Tuple[bytes, int, int]] = []
        logits = None
        i = 0
        while i + page_size <= L:
            k2 = self.child_key(key, prompt[i: i + page_size])
            e = self._entries.get(k2)
            if e is None:
                break
            hits.append((k2, e[0], page_size))
            key = k2
            i += page_size
            if i == L:
                logits = e[2]
        full_tokens = i
        if i < L:
            # terminal partial chunk: longest stored prefix wins.  The
            # chain cannot continue past a partial entry (children hash
            # page-aligned chunks), so this ends the walk.
            for n in range(min(L - i, page_size - 1), 0, -1):
                k2 = self.child_key(key, prompt[i: i + n])
                e = self._entries.get(k2)
                if e is not None:
                    hits.append((k2, e[0], n))
                    i += n
                    if i == L:
                        logits = e[2]
                    break
        return hits, key, full_tokens, logits

    def match(self, prompt: np.ndarray, page_size: int, pool: PagePool
              ) -> Tuple[List[int], int, int, bytes, Optional[np.ndarray]]:
        """``walk`` plus reference pinning and LRU bumps.

        Returns ``(pages, n_tokens, full_tokens, chain_key, logits)``
        with one pool reference taken per returned page (the caller
        owns them: ``free`` to unshare)."""
        hits, chain_key, full_tokens, logits = self.walk(prompt, page_size)
        pages = [p for _, p, _ in hits]
        n_tokens = sum(n for _, _, n in hits)
        for k, _, _ in hits:
            self._entries.move_to_end(k)
        if pages:
            pool.share(pages)
        return pages, n_tokens, full_tokens, chain_key, logits

    def reclaimable(self, pool: PagePool) -> int:
        """Pages a ``reclaim`` pass could free right now: entries whose
        page is pinned *only* by the index (refcount 1)."""
        return sum(1 for page, _, _ in self._entries.values()
                   if pool.ref(page) == 1)

    def reclaim(self, pool: PagePool, need_free: int) -> int:
        """Drop LRU entries whose page only the index still pins until
        ``pool.free_count >= need_free`` (or nothing reclaimable is
        left).  Entries still shared by a live slot are kept: dropping
        them would free no page and lose a useful match.  Returns the
        number of entries dropped."""
        dropped = 0
        for key in list(self._entries):
            if pool.free_count >= need_free:
                break
            page = self._entries[key][0]
            if pool.ref(page) == 1:
                del self._entries[key]
                pool.free([page])
                dropped += 1
        return dropped


def pages_needed(n_tokens: int, page_size: int) -> int:
    """Pages required to hold ``n_tokens`` cache entries."""
    return -(-max(n_tokens, 0) // page_size)


class BlockTables:
    """Per-slot block tables: host numpy state + device export.

    ``rows[b, j]`` is the physical page holding logical page ``j`` of
    slot ``b``; unallocated entries point at the garbage page.  With
    ``ring`` > 0 each row ends in ``ring`` more columns: the slot's
    window ring (DESIGN.md §window-rings), pages ``1 + b * ring + i`` of
    the windowed layers' own pools, fixed per slot and set while the
    slot holds a request.
    """

    def __init__(self, n_slots: int, pages_per_seq: int, ring: int = 0):
        self.ring = ring
        self.rows = np.zeros((n_slots, pages_per_seq + ring), np.int32)
        self.slot_pages: List[List[int]] = [[] for _ in range(n_slots)]
        # cached device export: (live-mask key, array).  Every step()
        # re-exports the rows; between assign/release/COW-fork they are
        # unchanged, so the upload is skipped unless the rows or the
        # live mask actually moved.
        self._dev_cache: Optional[Tuple[Optional[bytes], jnp.ndarray]] = None

    def assign(self, slot: int, pages: Sequence[int], start: int = 0
               ) -> None:
        """Append ``pages`` to ``slot`` starting at logical page
        ``start`` (== pages already owned)."""
        assert start == len(self.slot_pages[slot])
        self.rows[slot, start: start + len(pages)] = pages
        self.slot_pages[slot].extend(pages)
        if self.ring:
            self.rows[slot, -self.ring:] = (1 + slot * self.ring
                                            + np.arange(self.ring))
        self._dev_cache = None

    def set_page(self, slot: int, logical: int, page: int) -> None:
        """Point logical page ``logical`` of ``slot`` at a different
        physical page (copy-on-write fork rewrites its row entry)."""
        assert logical < len(self.slot_pages[slot])
        self.rows[slot, logical] = page
        self.slot_pages[slot][logical] = page
        self._dev_cache = None

    def release(self, slot: int, pool: PagePool) -> None:
        """Drop the slot's page references; row resets to garbage.
        Pages another slot or the prefix index still references stay
        alive (refcounted ``free``)."""
        pool.free(self.slot_pages[slot])
        self.slot_pages[slot] = []
        self.rows[slot, :] = GARBAGE_PAGE
        self._dev_cache = None

    def device(self, live=None) -> jnp.ndarray:
        """Device export of the rows (cached until rows/mask change).

        ``live``: optional (n_slots,) bool — rows of non-live slots
        (e.g. mid-prefill slots excluded from the fused decode scan)
        export as the garbage page, so the scan's masked writes cannot
        touch pages a concurrent chunked prefill is filling."""
        key = None if live is None else np.asarray(live, bool).tobytes()
        if self._dev_cache is not None and self._dev_cache[0] == key:
            return self._dev_cache[1]
        rows = self.rows
        if live is not None:
            rows = np.where(np.asarray(live, bool)[:, None], rows,
                            GARBAGE_PAGE)
        out = jnp.asarray(rows)
        self._dev_cache = (key, out)
        return out

    def host(self, live=None) -> np.ndarray:
        """Host-side copy of the rows with ``device``'s garbage
        masking, but no upload: the sharded engine concatenates every
        shard's masked rows (local physical ids) into one global
        export before its single sharded decode dispatch."""
        rows = self.rows
        if live is not None:
            rows = np.where(np.asarray(live, bool)[:, None], rows,
                            GARBAGE_PAGE)
        return np.array(rows)


# ---------------------------------------------------------------------------
# Device-side paged primitives (pure jnp, jit-safe)
# ---------------------------------------------------------------------------


class LayerPool(NamedTuple):
    """Layer ``layer`` of a cache leaf stacked over the scanned layers:
    ``stack`` is (L, ...) and ``layer`` a (traced) index.  ``shape`` and
    ``dtype`` are the layer's; ``layer_set`` writes into ``stack`` at
    the layer and ``layer_array`` reads the layer out (DESIGN.md
    §paged-cache)."""
    stack: jnp.ndarray
    layer: jnp.ndarray

    @property
    def shape(self) -> Tuple[int, ...]:
        """The layer's shape (the stack's, less its layer axis)."""
        return self.stack.shape[1:]

    @property
    def dtype(self):
        """The stack's dtype."""
        return self.stack.dtype


def is_layer_pool(x) -> bool:
    """``is_leaf`` for tree maps over caches that hold ``LayerPool``s."""
    return isinstance(x, LayerPool)


def layer_array(pool) -> jnp.ndarray:
    """A layer's cache leaf as an array (its slice of a stack)."""
    if isinstance(pool, LayerPool):
        return jax.lax.dynamic_index_in_dim(pool.stack, pool.layer, 0,
                                            keepdims=False)
    return pool


def _layer_get(pool, idx: tuple) -> jnp.ndarray:
    """``pool[idx]`` of a layer's leaf, read straight from its stack."""
    if isinstance(pool, LayerPool):
        return pool.stack[(pool.layer,) + idx]
    return pool[idx]


def layer_set(pool, idx: tuple, val):
    """``pool.at[idx].set(val)`` for a layer's leaf; a ``LayerPool``
    stores into its stack at its layer, in place."""
    if isinstance(pool, LayerPool):
        return pool._replace(stack=pool.stack.at[(pool.layer,) + idx].set(
            val.astype(pool.dtype)))
    return pool.at[idx].set(val.astype(pool.dtype))


def _store_entries(pool, pages, slot, off, vals):
    """Store cache entries by rewriting whole pages.

    pages: (B, n) physical pages row b may write (its own real pages, or
    the garbage page); slot, off: (B, S) which of the row's pages, and
    where in it, entry s goes; vals: (B, Hkv, S, R).  Each page that
    takes an entry is read, its entries set and the page stored back:
    a page is the window of the pool's write, so the pool keeps the
    layout it has at rest whatever the order of its minor axes (a
    window of one entry, (Hkv, R), makes XLA relayout the pool around
    the write).  A column no entry goes to is not stored (its index is
    pointed past the pool, where the write drops it)."""
    n = pages.shape[1]
    hit = jnp.any(slot[:, :, None] == jnp.arange(n)[None, None, :], 1)
    pages = jnp.where(hit, pages, pool.shape[0])
    buf = _layer_get(pool, (pages,))                        # (B,n,Hkv,ps,R)
    b = jnp.arange(pages.shape[0])[:, None]
    buf = buf.at[b, slot, :, off].set(
        vals.transpose(0, 2, 1, 3).astype(buf.dtype))
    return layer_set(pool, (pages,), buf)


def _garbage_column(pages: jnp.ndarray) -> jnp.ndarray:
    """(B, n) pages and a last column of the garbage page, where the
    entries that write no real page go."""
    return jnp.pad(pages, ((0, 0), (0, 1)), constant_values=GARBAGE_PAGE)


def append_token(pool: jnp.ndarray, block_table: jnp.ndarray,
                 pos: jnp.ndarray, val: jnp.ndarray) -> jnp.ndarray:
    """Write one new cache entry per sequence through the block table.

    pool: (P, Hkv, ps, R); block_table: (B, n_pages) int32; pos: (B,)
    destination position of each sequence; val: (B, Hkv, R).  Dead
    slots point at the garbage page, so their (masked) writes are
    harmless by construction.
    """
    ps = pool.shape[2]
    b = jnp.arange(pos.shape[0])
    phys = block_table[b, pos // ps]                        # (B,)
    zero = jnp.zeros((pos.shape[0], 1), jnp.int32)
    return _store_entries(pool, phys[:, None], zero, (pos % ps)[:, None],
                          val[:, :, None])


def append_chunk(pool: jnp.ndarray, block_table: jnp.ndarray,
                 pos0: jnp.ndarray, vals: jnp.ndarray,
                 valid: jnp.ndarray) -> jnp.ndarray:
    """Write a prefill chunk of cache entries through the block table.

    pool: (P, Hkv, ps, R); block_table: (B, n_pages) int32; pos0: (B,)
    position of each sequence's first chunk token; vals: (B, Hkv, S, R)
    chunk entries; valid: (B, S) bool — bucket-padding entries (False)
    are routed to the garbage page, so padded chunk tails can never
    touch a real page (DESIGN.md §prefill).  ``valid`` may instead be a
    (B,) int count of real tokens per row — the budget-truncated form
    (DESIGN.md §scheduler): a chunk cut at the residual token budget
    passes how many leading entries are real and the mask is derived
    here, since truncation always keeps a contiguous prefix.  Positions
    past the block table's logical capacity are clamped before the
    dereference; only padding can reach them, so the clamped rows are
    garbage-routed anyway.
    The pages are written whole (``_store_entries``): those the chunk's
    real entries touch, and the garbage page for its padding.
    """
    ps, S = pool.shape[2], vals.shape[2]
    if valid.ndim == 1:                 # per-row count -> prefix mask
        valid = jnp.arange(S)[None, :] < valid[:, None]
    n_pages = block_table.shape[1]
    n = (S + ps - 2) // ps + 1          # most pages S positions span
    pos = pos0[:, None] + jnp.arange(S)[None, :]            # (B, S)
    first = pos0 // ps
    logical = jnp.minimum(first[:, None] + jnp.arange(n)[None, :],
                          n_pages - 1)
    pages = jnp.take_along_axis(block_table, logical, 1)    # (B, n)
    slot = jnp.where(valid, pos // ps - first[:, None], n)
    return _store_entries(pool, _garbage_column(pages), slot, pos % ps,
                          vals)


def copy_page(pool: jnp.ndarray, src, dst) -> jnp.ndarray:
    """Device-side page copy: the copy-on-write fork primitive
    (DESIGN.md §prefix-sharing).  pool: (P, Hkv, ps, R); src/dst are
    physical page ids.  The writer's block-table row is then repointed
    at ``dst`` host-side, so subsequent appends land in the private
    copy while other sharers keep reading ``src``."""
    return pool.at[dst].set(pool[src])


def swap_out(pool: jnp.ndarray, row, n_tokens: int,
             fetch=np.asarray) -> np.ndarray:
    """Swap one slot's cache entries out to a host-RAM buffer.

    pool: (P, Hkv, ps, R); row: (n_pages,) block-table row of the
    victim.  Gathers only the slot's *occupied* pages (``gather_pages``
    over the row's live prefix — the tail is garbage-page entries) and
    copies its first ``n_tokens`` entries to host memory ->
    (Hkv, n_tokens, R) numpy, so the transfer is ~``n_tokens`` wide,
    not ``max_seq_len``.  The victim's pages can then be freed;
    ``swap_in`` restores the bytes through a fresh row.  ``fetch`` does
    the device-to-host read (the engine passes its spanned reader).
    """
    ps = pool.shape[2]
    occupied = pages_needed(n_tokens, ps)
    seq = gather_pages(pool, jnp.asarray(row[:occupied], jnp.int32)[None])
    return fetch(seq[0])[:, :n_tokens]


def swap_in(pool: jnp.ndarray, row, vals: np.ndarray) -> jnp.ndarray:
    """Swap a host buffer back into the pool through a (fresh) row.

    vals: (Hkv, n_tokens, R) numpy from ``swap_out``.  The entries are
    written through ``append_chunk`` at positions ``[0, n_tokens)`` of
    the block-table ``row`` the slot now owns — a byte-exact restore,
    so a swap round-trip preserves token-for-token outputs.
    """
    n_tokens = vals.shape[1]
    row = jnp.asarray(row, jnp.int32)[None]
    pos0 = jnp.zeros((1,), jnp.int32)
    valid = jnp.ones((1, n_tokens), bool)
    return append_chunk(pool, row, pos0, jnp.asarray(vals)[None], valid)


def gather_pages(pool: jnp.ndarray, block_table: jnp.ndarray
                 ) -> jnp.ndarray:
    """Materialize each slot's logical cache from its pages.

    pool: (P, Hkv, ps, R) -> (B, n_pages * ps, ...) gathered per slot,
    returned as (B, Hkv, n_pages * ps, R).  This is the lax reference
    path (and test oracle); the Pallas paged kernel reads the same
    pages in place via the block table instead of materializing.
    """
    g = _layer_get(pool, (block_table,))                    # (B,n,Hkv,ps,R)
    B, n, Hkv, ps, R = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, n * ps, R)


# ---------------------------------------------------------------------------
# Window rings (DESIGN.md §window-rings)
# ---------------------------------------------------------------------------
# A windowed layer reads only the last ``window`` positions, so a slot's
# state there is a ring of ``ring_pages`` whole pages, reached through
# ring columns at the end of the slot's block-table row: logical page j
# of the sequence lives in ring column ``j % ring_pages``.  The ring
# holds at least the last ``(ring_pages - 1) * page_size >= window``
# positions whatever the context, and its position-to-entry map is
# fixed, so no per-entry positions are stored.


def ring_pages(window: int, page_size: int) -> int:
    """Pages in a slot's ring: a window starting mid-page spans
    ``ceil(window / page_size) + 1`` pages."""
    return -(-window // page_size) + 1


def ring_positions(last: jnp.ndarray, n: int) -> jnp.ndarray:
    """(B, n) position held by each of a ring's ``n`` entries when
    ``last`` (B,) is the newest position written: entry r holds the
    newest position p <= last with p % n == r (negative: never
    written)."""
    r = jnp.arange(n, dtype=jnp.int32)[None, :]
    return last[:, None] - jnp.mod(last[:, None] - r, n)


def gather_ring(pool: jnp.ndarray, ring_table: jnp.ndarray) -> jnp.ndarray:
    """(B, Hkv, ring * ps, R) ring entries of each slot, in entry order
    (``ring_positions`` gives their positions)."""
    return gather_pages(pool, ring_table)


def append_ring(pool: jnp.ndarray, ring_table: jnp.ndarray,
                pos0: jnp.ndarray, vals: jnp.ndarray,
                valid: jnp.ndarray) -> jnp.ndarray:
    """Write ``vals`` (B, Hkv, S, R) at positions ``pos0 + s`` into the
    rings of ``ring_table`` (B, ring).  ``valid``: (B, S) bool or a (B,)
    count of leading real entries.  Of a chunk longer than the ring only
    the newest ``ring * ps`` entries are written (the rest would be
    overwritten in the same scatter); the others, and padding, go to the
    garbage page."""
    ps, S = pool.shape[2], vals.shape[2]
    ring = ring_table.shape[1]
    if valid.ndim == 1:
        valid = jnp.arange(S)[None, :] < valid[:, None]
    n_valid = valid.sum(axis=1)
    s = jnp.arange(S)[None, :]
    keep = valid & (s >= n_valid[:, None] - ring * ps)
    pos = pos0[:, None] + s                                 # (B, S)
    return _store_entries(pool, _garbage_column(ring_table),
                          jnp.where(keep, (pos // ps) % ring, ring),
                          pos % ps, vals)

