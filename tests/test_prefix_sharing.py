"""Refcounted page store with cross-request prefix sharing and COW
(DESIGN.md §prefix-sharing).

Parity contract: with ``share_prefix=True`` a batch of requests sharing
a common prompt prefix produces token-for-token identical outputs vs
``share_prefix=False``, with strictly lower peak pool occupancy and
strictly fewer prefill chunk invocations; diverging two shared requests
mid-decode exercises a copy-on-write fork instead of corrupting the
sibling.  Satellites: sharing x preemption isolation (recompute and
swap), PagePool refcount invariants (hypothesis), prefix-index
LRU/reclaim, and the cached BlockTables device export.
"""
import pytest

import jax
import numpy as np

from repro.config import ServeConfig
from repro.configs import get_config
from repro.models import build_model
from repro.serving import (BlockTables, PagePool, PagePoolExhausted,
                           PrefixIndex, Request, ServingEngine)


def _setup():
    cfg = get_config("tinyllama-1.1b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _sc(**kw) -> ServeConfig:
    base = dict(max_seq_len=32, max_batch=4, temperature=0.0,
                decode_chunk=4, page_size=4, prefill_chunk=4)
    base.update(kw)
    return ServeConfig(**base)


def _run(cfg, params, sc, prompts, max_new=5):
    eng = ServingEngine(cfg, params, sc)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    eng.generate(reqs)
    return eng, [r.out_tokens for r in reqs]


def _family(cfg, n_common, tails, seed=0):
    """Prompts sharing one ``n_common``-token prefix + distinct tails."""
    rng = np.random.default_rng(seed)
    common = rng.integers(0, cfg.vocab_size, n_common).astype(np.int32)
    return [np.concatenate([common,
                            rng.integers(0, cfg.vocab_size,
                                         k).astype(np.int32)])
            for k in tails]


def _drained_invariant(eng):
    """After a full drain every remaining reference belongs to the
    prefix index (one pinned page per entry)."""
    assert (eng.pool.free_count + eng._pindex.n_pinned
            == eng.pool.n_pages)


# ---------------------------------------------------------------------------
# Acceptance: parity + savings + COW divergence
# ---------------------------------------------------------------------------


def test_shared_prefix_parity_and_savings():
    """The acceptance contract: identical outputs, strictly lower peak
    pool occupancy, strictly fewer prefill chunk invocations for a
    concurrently-admitted batch sharing a long common prefix."""
    cfg, model, params = _setup()
    prompts = _family(cfg, 16, (3, 5, 2, 3), seed=1)
    off, out_off = _run(cfg, params, _sc(), prompts)
    on, out_on = _run(cfg, params, _sc(share_prefix=True), prompts)
    assert out_off == out_on
    assert on.peak_used_pages < off.peak_used_pages
    assert on.n_prefill_chunks < off.n_prefill_chunks
    assert on.n_shared_pages > 0
    _drained_invariant(on)


def test_shared_prefix_sequential_reuse_skips_prefill():
    """A finished request's pages stay in the index past release: an
    exact-duplicate prompt later skips prefill entirely (terminal
    logits hit), and a prompt extending it prefills only the tail."""
    cfg, model, params = _setup()
    p = _family(cfg, 10, (0,), seed=2)[0]           # 10 tokens: 2.5 pages
    ext = np.concatenate(
        [p, np.asarray([5, 9, 2, 7], np.int32)])
    sc = _sc(share_prefix=True, max_batch=1)        # strictly sequential
    eng, outs = _run(cfg, params, sc, [p.copy(), p.copy(), ext])
    assert eng.n_full_hits >= 1                     # duplicate: no prefill
    # the duplicate's generations match the original's prefix
    assert outs[1][:len(outs[0])][:5] == outs[0][:5]
    # oracle: each prompt served alone without sharing
    for i, prompt in enumerate((p, p, ext)):
        _, solo = _run(cfg, params, _sc(max_batch=1), [prompt.copy()])
        assert outs[i] == solo[0], i
    _drained_invariant(eng)


def test_cow_fork_on_mid_decode_divergence():
    """Two requests fully sharing a prompt whose last page is partial
    diverge mid-decode: the writer forks the shared page (append-token
    path) instead of corrupting the entries its sibling still reads."""
    cfg, model, params = _setup()
    p = _family(cfg, 10, (0,), seed=3)[0]           # L % page_size == 2
    sc = _sc(share_prefix=True, max_batch=1)
    eng = ServingEngine(cfg, params, sc)
    reqs = [Request(rid=0, prompt=p.copy(), max_new_tokens=3),
            Request(rid=1, prompt=p.copy(), max_new_tokens=6)]
    eng.generate(reqs)
    assert eng.n_full_hits >= 1
    assert eng.n_cow_forks >= 1
    # greedy: the longer request's stream extends the shorter's
    assert reqs[1].out_tokens[:3] == reqs[0].out_tokens
    _, solo = _run(cfg, params, _sc(max_batch=1), [p.copy()], max_new=6)
    assert reqs[1].out_tokens == solo[0]


def test_cow_fork_on_partial_page_prefill():
    """A prompt extending a cached prefix mid-page forks the shared
    partial page on its first prefill write (append-chunk path); the
    original entries stay valid for other matches."""
    cfg, model, params = _setup()
    p = _family(cfg, 10, (0,), seed=5)[0]
    ext = np.concatenate([p, np.asarray([3, 11, 4, 6], np.int32)])
    sc = _sc(share_prefix=True, max_batch=1)
    eng, outs = _run(cfg, params, sc, [p.copy(), ext.copy()])
    assert eng.n_cow_forks >= 1
    assert eng.n_shared_tokens >= 10        # full page + partial tail
    _, solo = _run(cfg, params, _sc(max_batch=1), [ext.copy()])
    assert outs[1] == solo[0]
    _drained_invariant(eng)


def test_shared_pages_survive_sibling_release():
    """Releasing one sharer only drops its references: the sibling
    still decoding from the shared pages is unaffected (refcounted
    free, never a page recycle under a live reader)."""
    cfg, model, params = _setup()
    prompts = _family(cfg, 12, (2, 2), seed=7)
    sc = _sc(share_prefix=True, max_batch=2)
    eng = ServingEngine(cfg, params, sc)
    reqs = [Request(rid=0, prompt=prompts[0], max_new_tokens=2),
            Request(rid=1, prompt=prompts[1], max_new_tokens=8)]
    eng.generate(reqs)                      # rid 0 finishes far earlier
    for i, p in enumerate(prompts):
        _, solo = _run(cfg, params, _sc(max_batch=1), [p.copy()],
                       max_new=reqs[i].max_new_tokens)
        assert reqs[i].out_tokens == solo[0], i
    _drained_invariant(eng)


# ---------------------------------------------------------------------------
# Sharing x preemption (DESIGN.md §preemption interaction)
# ---------------------------------------------------------------------------


OVERSUB = dict(max_seq_len=32, max_batch=4, temperature=0.0,
               decode_chunk=4, page_size=8, prefill_chunk=8,
               share_prefix=True)


@pytest.mark.parametrize("mode", ["recompute", "swap"])
def test_sharing_under_preemption_matches_ample(mode):
    """Preempting slots whose tables contain shared pages (both modes)
    must not corrupt the siblings still referencing them: outputs
    match the ample-pool run token-for-token with preemptions
    observed, and after the drain only index pins remain."""
    cfg, model, params = _setup()
    prompts = _family(cfg, 8, (6, 5, 6, 5, 6), seed=3)
    _, ref = _run(cfg, params, ServeConfig(**OVERSUB), prompts,
                  max_new=6)
    sc = ServeConfig(**OVERSUB, n_pages=6, admission="optimistic",
                     preempt_mode=mode, watermark_low=0.1)
    eng, out = _run(cfg, params, sc, prompts, max_new=6)
    assert out == ref
    assert eng.n_preempted >= 1
    if mode == "swap":
        assert eng.n_swapped_out >= 1
    _drained_invariant(eng)


def test_index_reclaim_under_pool_pressure():
    """Pages pinned only by the index are reclaimed (LRU) before any
    live slot is preempted, and the gate counters surface it."""
    cfg, model, params = _setup()
    prompts = _family(cfg, 8, (6, 5, 6, 5, 6), seed=3)
    sc = ServeConfig(**OVERSUB, n_pages=5, admission="optimistic",
                     preempt_mode="recompute", watermark_low=0.1)
    eng, out = _run(cfg, params, sc, prompts, max_new=6)
    assert eng.n_reclaimed >= 1
    _, ref = _run(cfg, params, ServeConfig(**OVERSUB), prompts,
                  max_new=6)
    assert out == ref
    _drained_invariant(eng)


# ---------------------------------------------------------------------------
# PagePool refcounts
# ---------------------------------------------------------------------------


def test_pool_share_free_refcounts():
    pool = PagePool(4)
    (a, b) = pool.alloc(2)
    assert pool.ref(a) == 1
    pool.share([a])
    assert pool.ref(a) == 2
    pool.free([a])                          # one sharer drops out
    assert pool.ref(a) == 1
    assert pool.used_count == 2             # still live: not recycled
    pool.free([a])
    assert pool.ref(a) == 0 and pool.free_count == 3
    with pytest.raises(ValueError):
        pool.free([a])                      # double free past zero
    with pytest.raises(ValueError):
        pool.share([a])                     # cannot share a dead page
    with pytest.raises(ValueError):
        pool.share([0])                     # never the garbage page
    pool.free([b])


def test_pool_refcount_invariants_hypothesis():
    """Property test: across random alloc/share/free sequences the
    pool never recycles a referenced page, never leaks, and
    free_count always complements the distinct live pages."""
    hypothesis = pytest.importorskip("hypothesis")  # noqa: F841
    from hypothesis import given, settings, strategies as st

    ops = st.lists(st.tuples(st.sampled_from(["alloc", "share", "free"]),
                             st.integers(0, 7)), max_size=60)

    @settings(deadline=None, max_examples=60)
    @given(ops)
    def run(seq):
        pool = PagePool(8)
        refs = {}                           # page -> expected refcount
        for op, k in seq:
            if op == "alloc":
                n = (k % 3) + 1
                if n > pool.free_count:
                    with pytest.raises(PagePoolExhausted):
                        pool.alloc(n)
                    continue
                for p in pool.alloc(n):
                    assert p not in refs    # never recycled while live
                    refs[p] = 1
            elif op == "share" and refs:
                p = sorted(refs)[k % len(refs)]
                pool.share([p])
                refs[p] += 1
            elif op == "free" and refs:
                p = sorted(refs)[k % len(refs)]
                pool.free([p])
                refs[p] -= 1
                if not refs[p]:
                    del refs[p]
            assert pool.used_count == len(refs)
            assert all(pool.ref(p) == c for p, c in refs.items())
        for p in sorted(refs):              # full teardown: no leaks
            for _ in range(refs[p]):
                pool.free([p])
        assert pool.free_count == pool.n_pages

    run()


# ---------------------------------------------------------------------------
# PrefixIndex
# ---------------------------------------------------------------------------


def test_prefix_index_match_and_chain():
    pool = PagePool(8)
    idx = PrefixIndex(capacity=8)
    ps = 4
    prompt = np.arange(10, dtype=np.int32)
    k0 = PrefixIndex.child_key(PrefixIndex.ROOT, prompt[:4])
    k1 = PrefixIndex.child_key(k0, prompt[4:8])
    kt = PrefixIndex.child_key(k1, prompt[8:10])
    p0, p1, pt = pool.alloc(3)
    assert idx.insert(k0, p0, 4, pool)
    assert idx.insert(k1, p1, 4, pool)
    assert idx.insert(kt, pt, 2, pool, logits=np.ones(3))
    assert not idx.insert(k0, 99, 4, pool)  # dedupe keeps the original
    assert pool.ref(p0) == 2                # slot + index pins
    pages, n, full, chain, logits = idx.match(prompt, ps, pool)
    assert pages == [p0, p1, pt] and n == 10 and full == 8
    assert chain == k1 and logits is not None
    assert pool.ref(pt) == 3                # match took references
    pool.free(pages)
    # a diverging prompt matches only the common chain
    other = prompt.copy()
    other[6] = 99
    pages2, n2, _, _, lg = idx.match(other, ps, pool)
    assert pages2 == [p0] and n2 == 4 and lg is None
    pool.free(pages2)


def test_prefix_index_capacity_and_reclaim():
    pool = PagePool(8)
    idx = PrefixIndex(capacity=2)
    keys = [PrefixIndex.child_key(PrefixIndex.ROOT,
                                  np.asarray([i], np.int32))
            for i in range(3)]
    pages = pool.alloc(3)
    for k, p in zip(keys, pages):
        idx.insert(k, p, 1, pool)
    assert len(idx) == 2                    # LRU-evicted beyond capacity
    assert pool.ref(pages[0]) == 1          # eviction dropped its pin
    pool.free(pages)                        # slots release their refs
    assert pool.free_count == 6             # two pages still index-pinned
    assert idx.reclaimable(pool) == 2
    dropped = idx.reclaim(pool, need_free=8)
    assert dropped == 2 and pool.free_count == 8 and len(idx) == 0


def test_prefix_index_reclaim_skips_shared_entries():
    """Reclaiming an entry whose page a live slot still references
    would free nothing: those entries are kept."""
    pool = PagePool(4)
    idx = PrefixIndex(capacity=4)
    (p0, p1) = pool.alloc(2)
    idx.insert(PrefixIndex.child_key(b"", np.asarray([0], np.int32)),
               p0, 1, pool)
    idx.insert(PrefixIndex.child_key(b"", np.asarray([1], np.int32)),
               p1, 1, pool)
    pool.free([p1])                         # p1 now index-only
    assert idx.reclaimable(pool) == 1       # p0 still slot-held
    idx.reclaim(pool, need_free=4)
    assert len(idx) == 1                    # p0's entry survives
    assert pool.ref(p0) == 2


# ---------------------------------------------------------------------------
# Satellites: cached device export, config validation
# ---------------------------------------------------------------------------


def test_block_table_device_export_is_cached():
    pool = PagePool(8)
    bt = BlockTables(2, 4)
    bt.assign(0, pool.alloc(2))
    dev1 = bt.device()
    assert bt.device() is dev1              # unchanged rows: no re-upload
    live = np.asarray([True, False])
    dev_live = bt.device(live=live)
    assert dev_live is not dev1             # live mask keyed separately
    assert bt.device(live=live.copy()) is dev_live
    assert bt.device(live=np.asarray([True, True])) is not dev_live
    bt.set_page(0, 1, pool.alloc(1)[0])     # COW fork invalidates
    dev2 = bt.device()
    assert dev2 is not dev1
    assert bt.device() is dev2
    bt.release(0, pool)                     # release invalidates
    assert bt.device() is not dev2


def test_prefix_index_capacity_validation():
    with pytest.raises(ValueError, match="capacity"):
        ServeConfig(page_size=4, prefill_chunk=4, share_prefix=True,
                    prefix_index_capacity=0)


def test_reserve_admission_not_pessimized_by_decode_growth():
    """Regression (review finding): pages a slot allocates while
    *growing* during decode must count as its private pages, or the
    reserve-mode outstanding-growth sum double-counts them and a
    request that PR 4 would admit is wrongly refused.  Pool of 12:
    slot A (worst case 8) decodes long; request B (worst case 4) must
    be admitted while A is still mid-generation."""
    cfg, model, params = _setup()
    rng = np.random.default_rng(29)
    a = rng.integers(0, cfg.vocab_size, 4).astype(np.int32)
    bp = rng.integers(0, cfg.vocab_size, 4).astype(np.int32)
    sc = _sc(share_prefix=True, max_batch=2, n_pages=12, max_seq_len=32)
    eng = ServingEngine(cfg, params, sc)
    reqs = [Request(rid=0, prompt=a, max_new_tokens=28),   # grows to 8
            Request(rid=1, prompt=bp, max_new_tokens=12)]  # worst 4
    eng.start([reqs[0]])
    grown = False
    for _ in range(3):                      # let A grow past its prompt
        eng.step()
        grown = grown or len(eng._btabs.slot_pages[0]) > 2
    assert grown and not reqs[0].done
    eng._pending.append(reqs[1])
    eng.step()
    assert eng._slot_req[1] is reqs[1]      # admitted mid-growth
    while eng.step():
        pass
    assert all(r.done and not r.failed for r in reqs)


def test_tight_pool_sharing_never_raises():
    """Regression (review finding): admission headroom must not count
    index pins the request itself would take over as reclaimable —
    over-admitting crashed the private-tail allocation.  Sweep tight
    pools under both admission policies: every batch must drain, never
    raise."""
    cfg, model, params = _setup()
    prompts = _family(cfg, 8, (6, 5, 12, 5, 6), seed=31)
    _, ref = _run(cfg, params,
                  _sc(share_prefix=True, page_size=4, prefill_chunk=8),
                  prompts, max_new=6)
    for n_pages in (5, 6, 7, 8):
        for admission in ("reserve", "optimistic"):
            sc = _sc(share_prefix=True, page_size=4, prefill_chunk=8,
                     n_pages=n_pages, admission=admission,
                     watermark_low=0.1 if admission == "optimistic"
                     else 0.0)
            eng, out = _run(cfg, params, sc, prompts, max_new=6)
            # whoever completed matches the ample run; a request may
            # only be dropped for genuine infeasibility (its whole
            # worst case exceeds this pool), never by a crash
            for i, toks in enumerate(out):
                if toks:
                    assert toks == ref[i], (n_pages, admission, i)
                else:
                    worst = eng._worst_case_pages(
                        Request(rid=i, prompt=prompts[i],
                                max_new_tokens=6))
                    assert worst > n_pages, (n_pages, admission, i)
            _drained_invariant(eng)


def test_worst_case_charges_private_tail_only():
    """The PR 5 accounting bugfix: under reserve admission two
    same-prefix requests fit a pool that could never hold two
    *independently* worst-cased requests — the shared prefix is
    charged once, so shared-heavy workloads do not re-inherit the
    pessimistic cap."""
    cfg, model, params = _setup()
    prompts = _family(cfg, 8, (2, 2, 2), seed=11)   # 10 tokens each
    # worst case per request: ceil((10 + 4) / 4) = 4 pages; two
    # independent requests need 8 — but sharing the 2-page prefix the
    # pair's distinct worst case is 2 + 2*2 (+1 fork headroom) = 7
    sc = _sc(share_prefix=True, max_batch=2, n_pages=7)
    eng = ServingEngine(cfg, params, sc)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=4)
            for i, p in enumerate(prompts)]
    eng.start(reqs)
    eng.step()                              # rid 0 admitted, prefilling
    concurrent = False
    for _ in range(64):
        resident = [r for r in eng._slot_req if r is not None]
        concurrent = concurrent or len(resident) == 2
        if not eng.step():
            break
    assert concurrent                       # both held slots at once
    assert all(r.done and not r.failed for r in reqs)
    for i, p in enumerate(prompts):
        _, solo = _run(cfg, params, _sc(max_batch=1), [p.copy()],
                       max_new=4)
        assert reqs[i].out_tokens == solo[0], i
