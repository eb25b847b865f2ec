"""State-invariant auditing and request cancellation
(DESIGN.md §robustness).

``invariants.audit`` must stay silent through every healthy lifecycle
(sharing, COW, swap preemption, oversubscription) and must catch
seeded corruption of any audited structure — refcounts, the free
list, block-table rows, per-slot accounting, leaked swap state.
``ServingEngine.cancel`` may fire at any lifecycle stage (pending,
mid-prefill, decoding, swapped out) and must leave a state the audit
accepts, the batch unharmed, and the pool fully drainable.
"""
import pytest

import jax
import numpy as np

from repro.config import ServeConfig
from repro.configs import get_config
from repro.models import build_model
from repro.serving import (InvariantViolation, Request, ServingEngine,
                           audit, scheduler_dump)
from repro.serving.invariants import refcount_histogram


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("tinyllama-1.1b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


# oversubscribed sharing + swap workload: every audited structure is
# exercised (shared refcounts > 1, COW, index pins, swap state)
SC = dict(max_seq_len=32, max_batch=4, temperature=0.0,
          decode_chunk=4, page_size=8, prefill_chunk=8, share_prefix=True,
          admission="optimistic", preempt_mode="swap", n_pages=8,
          watermark_low=0.1)


def _reqs(cfg, max_new=6, n=6):
    rng = np.random.default_rng(3)
    common = rng.integers(0, cfg.vocab_size, 10).astype(np.int32)
    prompts = [np.concatenate([common, rng.integers(
                   0, cfg.vocab_size, k).astype(np.int32)])
               for k in (4, 3, 4, 3, 4, 6)[:n]]
    return [Request(rid=i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]


def _start(cfg, params, reqs, **kw):
    eng = ServingEngine(cfg, params, ServeConfig(**{**SC, **kw}))
    eng.start(reqs)
    return eng


def test_audit_clean_through_full_lifecycle(setup):
    """The public API contract: audit after every step of a healthy
    oversubscribed sharing+swap drain never raises, from first
    admission through final release."""
    cfg, model, params = setup
    reqs = _reqs(cfg, max_new=8)
    eng = _start(cfg, params, reqs, n_pages=6)
    audit(eng)                                  # pre-first-step state
    steps = 0
    while eng.step():
        audit(eng)
        steps += 1
        assert steps < 200
    audit(eng)
    assert all(r.done and not r.failed for r in reqs)
    assert eng.n_preempted >= 1                 # pressure was real


def test_audit_every_samples_steps(setup):
    """audit_every=k runs the audit pass on every k-th step only: the
    n_audits counter lands at step_count // k, and the sampled drain
    still finishes with identical outcomes."""
    cfg, model, params = setup
    outs = {}
    for k in (1, 3):
        reqs = _reqs(cfg, max_new=6)
        eng = _start(cfg, params, reqs, n_pages=6, audit=True,
                     audit_every=k)
        steps = 0
        while eng.step():
            steps += 1
            assert steps < 200
        assert eng.n_audits == eng._step_count // k
        assert all(r.done and not r.failed for r in reqs)
        outs[k] = [list(r.out_tokens) for r in reqs]
    assert outs[1] == outs[3]                   # sampling never perturbs


def test_audit_count_independent_of_pool_size(setup):
    """The *number* of audit passes is a pure function of step count
    and audit_every — growing the pool must not add audits (the
    per-pass cost is what scales with pool size; sampling is the lever
    that bounds the total)."""
    cfg, model, params = setup
    counts = {}
    for n_pages in (12, 48):        # both ample: same admission schedule
        reqs = _reqs(cfg, max_new=4, n=3)
        eng = _start(cfg, params, reqs, n_pages=n_pages, audit=True,
                     audit_every=2, admission="reserve",
                     preempt_mode="recompute", share_prefix=False)
        steps = 0
        while eng.step():
            steps += 1
            assert steps < 200
        counts[n_pages] = (eng.n_audits, eng._step_count)
    assert counts[12] == counts[48]


def test_audit_every_validation():
    with pytest.raises(ValueError):
        ServeConfig(audit_every=0)
    with pytest.raises(ValueError):
        ServeConfig(audit_every=-2)


def _run_until_live(eng):
    """Step until at least one slot is occupied and owns pages."""
    for _ in range(16):
        eng.step()
        if any(r is not None for r in eng._slot_req):
            return
    raise AssertionError("no slot ever became live")


def test_audit_detects_refcount_drift(setup):
    """A refcount bumped behind the engine's back (the classic leak) is
    reported with the page number and both counts."""
    cfg, model, params = setup
    eng = _start(cfg, params, _reqs(cfg))
    _run_until_live(eng)
    owned = next(p for b in range(eng.sc.max_batch)
                 for p in eng._btabs.slot_pages[b])
    eng.pool._refs[owned] += 1
    with pytest.raises(InvariantViolation, match="refcount") as ei:
        audit(eng)
    assert any(f"page {owned}" in v for v in ei.value.violations)
    eng.pool._refs[owned] -= 1
    audit(eng)                                  # restored -> clean


def test_audit_detects_free_list_corruption(setup):
    """A referenced page pushed onto the free list (premature free) and
    a free page silently dropped (leak) are both caught."""
    cfg, model, params = setup
    eng = _start(cfg, params, _reqs(cfg))
    _run_until_live(eng)
    owned = next(p for b in range(eng.sc.max_batch)
                 for p in eng._btabs.slot_pages[b])
    eng.pool._free.append(owned)
    with pytest.raises(InvariantViolation, match="both free and"):
        audit(eng)
    eng.pool._free.pop()
    if eng.pool.free_count:                     # drop one -> leaked
        dropped = eng.pool._free.pop()
        with pytest.raises(InvariantViolation, match="leaked"):
            audit(eng)
        eng.pool._free.append(dropped)
    audit(eng)


def test_audit_detects_block_table_and_slot_corruption(setup):
    """A stale block-table row entry and impossible per-slot
    accounting are reported per slot."""
    cfg, model, params = setup
    eng = _start(cfg, params, _reqs(cfg))
    _run_until_live(eng)
    b = next(b for b in range(eng.sc.max_batch)
             if eng._slot_req[b] is not None
             and eng._btabs.slot_pages[b])
    row = eng._btabs.rows[b]
    k = len(eng._btabs.slot_pages[b])
    saved = row[k:].copy()
    row[k:] = 1                                 # stale entry past owned
    with pytest.raises(InvariantViolation, match="stale row"):
        audit(eng)
    row[k:] = saved
    old = eng._private[b]
    eng._private[b] = 99
    with pytest.raises(InvariantViolation, match="private"):
        audit(eng)
    eng._private[b] = old
    audit(eng)


def test_violation_carries_all_checks_and_dump(setup):
    """One bad state with several inconsistencies reports *all* of
    them plus the scheduler dump — the full corruption picture."""
    cfg, model, params = setup
    eng = _start(cfg, params, _reqs(cfg))
    _run_until_live(eng)
    owned = next(p for b in range(eng.sc.max_batch)
                 for p in eng._btabs.slot_pages[b])
    eng.pool._refs[owned] += 1
    eng.pool._free.append(owned)
    with pytest.raises(InvariantViolation) as ei:
        audit(eng)
    assert len(ei.value.violations) >= 2
    assert "pool:" in str(ei.value)             # scheduler dump inline
    eng.pool._free.pop()
    eng.pool._refs[owned] -= 1


def test_scheduler_dump_and_histogram(setup):
    cfg, model, params = setup
    eng = _start(cfg, params, _reqs(cfg))
    _run_until_live(eng)
    dump = scheduler_dump(eng)
    assert "pool:" in dump and "slot" in dump and "rid=" in dump
    hist = refcount_histogram(eng)
    assert sum(hist.values()) == eng.pool.n_pages
    assert any(rc >= 1 for rc in hist)          # something is live


# ---------------------------------------------------------------------------
# cancel() at every lifecycle stage
# ---------------------------------------------------------------------------


def _drain_and_check(eng, reqs, cancelled_rids):
    while eng.step():
        audit(eng)
    audit(eng)
    for r in reqs:
        if r.rid in cancelled_rids:
            assert r.failed and r.error.kind == "cancelled"
        else:
            assert r.done and not r.failed, r.rid
    assert (eng.pool.free_count + eng._pindex.n_pinned
            == eng.pool.n_pages)


def test_cancel_pending_request(setup):
    """Cancelling a request still waiting in the queue: never admitted,
    never decoded, batch unaffected."""
    cfg, model, params = setup
    reqs = _reqs(cfg)                           # 6 reqs, 4 slots
    eng = _start(cfg, params, reqs)
    eng.step()
    waiting = [r.rid for r in eng._pending]
    assert waiting                              # someone is queued
    assert eng.cancel(waiting[0])
    audit(eng)
    assert reqs[waiting[0]].out_tokens == []
    _drain_and_check(eng, reqs, {waiting[0]})


def test_cancel_resident_request(setup):
    """Cancelling a request mid-flight in a slot (prefilling or
    decoding) frees its pages immediately; siblings sharing pages with
    it are untouched."""
    cfg, model, params = setup
    reqs = _reqs(cfg)
    eng = _start(cfg, params, reqs)
    eng.step()
    b = next(b for b in range(eng.sc.max_batch)
             if eng._slot_req[b] is not None)
    rid = eng._slot_req[b].rid
    assert eng.cancel(rid)
    assert eng._slot_req[b] is None             # slot unwound now
    audit(eng)
    _drain_and_check(eng, reqs, {rid})


def test_cancel_swapped_out_request(setup):
    """Cancelling a victim whose pages live in host RAM drops the swap
    state (no leaked buffer) without disturbing residents."""
    cfg, model, params = setup
    reqs = _reqs(cfg, max_new=12)
    eng = _start(cfg, params, reqs)
    for _ in range(64):
        eng.step()
        if eng._swapped:
            break
    assert eng._swapped, "workload produced no swap victim"
    key = next(iter(eng._swapped))
    victim = next(r for r in eng._pending if id(r) == key)
    assert eng.cancel(victim.rid)
    assert not eng._swapped or key not in eng._swapped
    audit(eng)
    _drain_and_check(eng, reqs, {victim.rid})


def test_cancel_unknown_or_done_returns_false(setup):
    cfg, model, params = setup
    reqs = _reqs(cfg, n=2)
    eng = _start(cfg, params, reqs)
    assert not eng.cancel(999)                  # unknown rid
    assert eng.cancel(reqs[0].rid)
    assert not eng.cancel(reqs[0].rid)          # already terminal
    while eng.step():
        pass
    assert not eng.cancel(reqs[1].rid)          # completed normally


def test_cancel_at_arbitrary_stage_property(setup):
    """Property test: cancelling any request after any number of steps
    leaves a state the audit accepts and the rest of the batch able to
    drain (hypothesis explores the (step, rid) grid)."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    cfg, model, params = setup

    @hyp.settings(max_examples=10, deadline=None)
    @hyp.given(steps=st.integers(min_value=0, max_value=8),
               rid=st.integers(min_value=0, max_value=5))
    def prop(steps, rid):
        reqs = _reqs(cfg)
        eng = _start(cfg, params, reqs)
        for _ in range(steps):
            if not eng.step():
                break
        eng.cancel(rid)
        audit(eng)
        while eng.step():
            audit(eng)
        audit(eng)
        for r in reqs:
            assert r.done
            assert (not r.failed) or r.error.kind == "cancelled"
        assert (eng.pool.free_count + eng._pindex.n_pinned
                == eng.pool.n_pages)

    prop()
