"""Serving-state invariant auditing (DESIGN.md §robustness).

``audit(engine)`` cross-checks the host-side bookkeeping the paged
engine's correctness rests on — the structures every scaling PR
(split-KV, sharding, quantized pages) mutates and must prove it did
not corrupt:

* **refcount agreement**: every physical page's ``PagePool`` refcount
  equals the number of block-table references to it (over all slots)
  plus its prefix-index pins — no leaked references (pages that can
  never be recycled) and no premature frees (a page recycled while a
  slot or the index still reads it);
* **free-list soundness**: no duplicates, never the garbage page,
  disjoint from every referenced page, and *complete* — every page
  with refcount zero is on it (free + distinct-live partitions the
  pool, so ``used_count`` is truthful);
* **block-table agreement**: each slot's ``rows`` prefix equals its
  ``slot_pages`` ownership list, the tail is all garbage-page, and the
  garbage page is never owned;
* **live-slot agreement**: empty slots hold no pages and no per-slot
  accounting; occupied slots own at most their reserved worst case,
  their private-page count is sane, and their decode position /
  prefill progress fits inside the pages they own;
* **page-layout agreement** (DESIGN.md §page-layouts): every paged
  layer's cache leaves match the configured ``PageLayout`` schema —
  names, pool-sized page axis, widths, dtypes — so quantized data
  pages and their scale-pool pages cannot drift out of lockstep;
* **swap/pending agreement**: every saved swap state belongs to a
  request currently waiting in the pending queue.

Violations raise ``InvariantViolation`` carrying *all* failed checks
plus a scheduler-state dump, so a chaos run reports the full corruption
picture, not just the first symptom.  Enable per-step auditing with
``ServeConfig.audit=True`` (``--audit`` on the serve CLI); every chaos
test runs with it on, and ``decode_audit_on`` in ``BENCH_decode.json``
gates its overhead against the un-audited drain.

The audit reads host state only (numpy mirrors + one device sync for
positions); it never mutates the engine.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from repro.serving.paged_cache import GARBAGE_PAGE, pages_needed
from repro.serving.page_layouts import get_layout


class InvariantViolation(AssertionError):
    """Engine bookkeeping failed an ``audit`` cross-check."""

    def __init__(self, violations: List[str], dump: str = ""):
        self.violations = list(violations)
        msg = "engine invariant audit failed:\n  - " + "\n  - ".join(
            violations)
        if dump:
            msg += f"\n{dump}"
        super().__init__(msg)


def scheduler_dump(eng) -> str:
    """One-screen scheduler-state summary (attached to every
    ``InvariantViolation`` and ``EngineStalledError``)."""
    lines = [f"step={getattr(eng, '_step_count', '?')} "
             f"pending={[r.rid for r in eng._pending]} "
             f"swapped={len(eng._swapped)}"]
    lines.append(
        f"pool: {eng.pool.used_count}/{eng.pool.n_pages} used, "
        f"{eng.pool.free_count} free"
        + (f", index pins={eng._pindex.n_pinned}"
           if eng._pindex is not None else ""))
    pos = np.asarray(eng._pos)
    done = np.asarray(eng._done)
    for b in range(eng.sc.max_batch):
        r = eng._slot_req[b]
        if r is None:
            continue
        owned = len(eng._btabs.slot_pages[b])
        lines.append(
            f"slot {b}: rid={r.rid} pos={int(pos[b])} "
            f"prefilled={eng._prefilled[b]} done={bool(done[b])} "
            f"pages={owned} reserved={eng._reserved[b]} "
            f"charged={eng._charged[b]} private={eng._private[b]}")
    return "\n".join("    " + ln for ln in lines)


def _audit_pool(eng, bad: List[str]) -> None:
    pool, btabs = eng.pool, eng._btabs
    # expected refcounts: block-table ownership + prefix-index pins
    expect = np.zeros(pool.n_pages + 1, np.int64)
    for b in range(eng.sc.max_batch):
        for p in btabs.slot_pages[b]:
            if p == GARBAGE_PAGE:
                bad.append(f"slot {b} owns the garbage page")
                continue
            if not 1 <= p <= pool.n_pages:
                bad.append(f"slot {b} owns out-of-range page {p}")
                continue
            expect[p] += 1
    if eng._pindex is not None:
        for key, (page, _, _) in eng._pindex._entries.items():
            if not 1 <= page <= pool.n_pages:
                bad.append(f"index entry {key.hex()[:8]} pins "
                           f"out-of-range page {page}")
                continue
            expect[page] += 1
    refs = np.asarray(pool._refs)
    mism = np.nonzero(refs[1:] != expect[1:])[0] + 1
    for p in mism[:8]:
        bad.append(f"page {int(p)}: refcount {int(refs[p])} != "
                   f"{int(expect[p])} references "
                   f"(block tables + index pins)")
    if len(mism) > 8:
        bad.append(f"... and {len(mism) - 8} more refcount mismatches")
    # free-list soundness
    free = pool._free
    if len(set(free)) != len(free):
        bad.append("free list contains duplicates")
    if GARBAGE_PAGE in free:
        bad.append("garbage page on the free list")
    freeset = set(free)
    live = {int(p) for p in np.nonzero(refs)[0]}
    overlap = freeset & live
    if overlap:
        bad.append(f"pages both free and referenced: "
                   f"{sorted(overlap)[:8]}")
    leaked = set(range(1, pool.n_pages + 1)) - freeset - live
    if leaked:
        bad.append(f"pages neither free nor referenced (leaked): "
                   f"{sorted(leaked)[:8]}")
    if pool.used_count != len(live):
        bad.append(f"used_count {pool.used_count} != "
                   f"{len(live)} distinct referenced pages")


def _audit_block_tables(eng, bad: List[str]) -> None:
    btabs = eng._btabs
    ring = btabs.ring
    for b in range(eng.sc.max_batch):
        owned = btabs.slot_pages[b]
        row = btabs.rows[b][:btabs.rows.shape[1] - ring]
        k = len(owned)
        if ring:
            # the window ring's columns (DESIGN.md §window-rings): the
            # slot's fixed ring pages while it holds pages, else garbage
            want = (1 + b * ring + np.arange(ring) if k
                    else np.full(ring, GARBAGE_PAGE))
            if np.any(btabs.rows[b][-ring:] != want):
                bad.append(f"slot {b}: ring columns "
                           f"{list(btabs.rows[b][-ring:])} != {list(want)}")
        if list(row[:k]) != list(owned):
            bad.append(f"slot {b}: rows[:{k}] {list(row[:k])} != "
                       f"slot_pages {owned}")
        if np.any(row[k:] != GARBAGE_PAGE):
            bad.append(f"slot {b}: stale row entries past its "
                       f"{k} owned pages")


def _audit_slots(eng, bad: List[str]) -> None:
    sc = eng.sc
    pos = np.asarray(eng._pos)
    done = np.asarray(eng._done)
    for b in range(sc.max_batch):
        r = eng._slot_req[b]
        owned = len(eng._btabs.slot_pages[b])
        if r is None:
            if owned:
                bad.append(f"slot {b}: empty but owns {owned} pages")
            if eng._prefilled[b] is not None:
                bad.append(f"slot {b}: empty but mid-prefill")
            if eng._reserved[b] or eng._charged[b] or eng._private[b]:
                bad.append(f"slot {b}: empty but reserved/charged/"
                           f"private = {eng._reserved[b]}/"
                           f"{eng._charged[b]}/{eng._private[b]}")
            continue
        if r.done:
            bad.append(f"slot {b}: rid {r.rid} already done but "
                       f"still occupies the slot")
        if owned > eng._reserved[b]:
            bad.append(f"slot {b}: owns {owned} pages past its "
                       f"reserved cap {eng._reserved[b]}")
        if not 0 <= eng._private[b] <= owned:
            bad.append(f"slot {b}: private count {eng._private[b]} "
                       f"outside [0, {owned}]")
        pf = eng._prefilled[b]
        if pf is not None:
            if not 0 <= pf <= len(eng._slot_prompt[b]):
                bad.append(f"slot {b}: prefill progress {pf} outside "
                           f"prompt [0, {len(eng._slot_prompt[b])}]")
            if pages_needed(pf, sc.page_size) > owned:
                bad.append(f"slot {b}: prefilled {pf} tokens but owns "
                           f"only {owned} pages")
        elif not done[b]:
            if pages_needed(int(pos[b]), sc.page_size) > owned:
                bad.append(f"slot {b}: pos {int(pos[b])} but owns "
                           f"only {owned} pages")


def _audit_layout(eng, bad: List[str]) -> None:
    """Page-layout agreement (DESIGN.md §page-layouts): every paged
    attention layer's cache must match the configured layout's schema
    — same leaf names, a pool-sized leading page axis, and the
    declared per-leaf widths/dtypes — so a quantized data page can
    never drift out of lockstep with its scale-pool page (allocation,
    COW forks and swaps move whole leaf sets through ``tree.map``,
    which this check keeps honest)."""
    rk, rv = eng.ranks
    if not rk:
        return                       # full-cache pages: single kc/vc pair
    layout = get_layout(eng.cfg)
    expect = {}
    for side, rank in (("k", rk), ("v", rv)):
        for name, width, dtype in layout.leaves(side, rank):
            expect[name] = (width, dtype)
    n_rows = eng.pool.n_pages + 1    # + the garbage page
    # window layers hold a ring of pages per slot (DESIGN.md
    # §window-rings), whatever the pool
    n_ring = 1 + eng.sc.max_batch * eng._btabs.ring

    def _check(tag: str, leaves, lead: int, layer: int) -> None:
        rows = n_rows
        if eng.cfg.attn_kinds is not None and eng.cfg.attn_spec(
                layer).window:
            rows = n_ring
        if set(leaves) != set(expect):
            bad.append(f"{tag}: cache leaves {sorted(leaves)} != "
                       f"layout {layout.name!r} schema {sorted(expect)}")
            return
        for name, arr in leaves.items():
            width, dtype = expect[name]
            if arr.shape[lead] != rows:
                bad.append(f"{tag}/{name}: page axis {arr.shape[lead]} "
                           f"!= pool size {rows}")
            if arr.shape[-1] != width:
                bad.append(f"{tag}/{name}: width {arr.shape[-1]} != "
                           f"layout width {width}")
            if dtype is not None and arr.dtype != dtype:
                bad.append(f"{tag}/{name}: dtype {arr.dtype} != "
                           f"layout dtype {dtype}")

    model = eng.model
    for i, layer in enumerate(eng._cache["prefix"]):
        _check(f"prefix layer {i}", layer, 0, model.prefix[i])
    steps = eng._cache["steps"]
    if steps is not None:
        # stacked scan steps: leaves carry a leading (n_steps,) axis
        for j, layer in enumerate(steps["layers"]):
            _check(f"steps sublayer {j}", layer, 1,
                   model.step_template[j])


def _audit_swapped(eng, bad: List[str]) -> None:
    pending_ids = {id(r) for r in eng._pending}
    for key in eng._swapped:
        if key not in pending_ids:
            bad.append(f"swap state {key} has no pending request "
                       f"(leaked host buffer)")


def audit(eng) -> None:
    """Cross-check the engine's serving state; raise
    ``InvariantViolation`` (with every failed check and a scheduler
    dump) on the first inconsistency.  Safe to call after any
    ``step()``; with ``ServeConfig.audit=True`` the engine calls it
    itself at the end of every step."""
    bad: List[str] = []
    _audit_pool(eng, bad)
    _audit_block_tables(eng, bad)
    _audit_layout(eng, bad)
    _audit_swapped(eng, bad)
    _audit_slots(eng, bad)
    if bad:
        raise InvariantViolation(bad, scheduler_dump(eng))


def audit_sharded(eng) -> None:
    """Cross-shard accounting for the data-sharded engine (DESIGN.md
    §sharded-engine).  Per-shard state is checked by running the
    ordinary ``audit`` over each worker; this pass checks what no
    single worker can see:

    * **exclusive ownership**: every live request is owned by exactly
      one shard — its slots, local pending queue and swap store are
      pairwise disjoint with every other shard's;
    * **router/worker disjointness**: the parent's global queue holds
      no request a shard also owns (a routed request never reappears
      upstream);
    * **uniform partitioning**: every shard agrees on its slot-slice
      width and physical pool size (the global cache page axis is
      ``shards * (local_pages + 1)``);
    * **slot-axis cover**: the shard slices tile the global
      ``max_batch`` exactly."""
    bad: List[str] = []
    owner: Dict[int, int] = {}
    for w in eng.workers:
        owned = [r for r in w._slot_req if r is not None]
        owned += list(w._pending)
        for r in owned:
            prev = owner.get(id(r))
            if prev is not None and prev != w._shard:
                bad.append(f"request rid={r.rid} owned by both shard "
                           f"{prev} and shard {w._shard}")
            owner[id(r)] = w._shard
    for r in eng._pending:
        if id(r) in owner:
            bad.append(f"request rid={r.rid} both in the global queue "
                       f"and owned by shard {owner[id(r)]}")
    widths = {w.sc.max_batch for w in eng.workers}
    if len(widths) != 1:
        bad.append(f"unequal shard slot widths: {sorted(widths)}")
    pools = {w.pool.n_pages for w in eng.workers}
    if len(pools) != 1:
        bad.append(f"unequal shard pool sizes: {sorted(pools)}")
    if sum(w.sc.max_batch for w in eng.workers) != eng.sc.max_batch:
        bad.append(
            f"shard slot slices cover "
            f"{sum(w.sc.max_batch for w in eng.workers)} slots != "
            f"max_batch {eng.sc.max_batch}")
    bases = [w._base for w in eng.workers]
    if bases != sorted(set(bases)) or (bases and bases[0] != 0):
        bad.append(f"shard slot bases not a contiguous tiling: {bases}")
    if bad:
        dump = "\n".join(f"[shard {s}]\n" + scheduler_dump(w)
                         for s, w in enumerate(eng.workers))
        raise InvariantViolation(bad, dump)


def refcount_histogram(eng) -> Dict[int, int]:
    """refcount -> page count (observability helper for tests and the
    serve CLI's failure printout)."""
    refs = np.asarray(eng.pool._refs)[1:]
    vals, counts = np.unique(refs, return_counts=True)
    return {int(v): int(c) for v, c in zip(vals, counts)}
