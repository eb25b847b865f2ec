"""Deterministic fault injection and graceful request failure
(DESIGN.md §robustness).

Acceptance contract: under a seeded chaos schedule that fires every
fault point at least once, a mixed continuous batch (prefix sharing +
COW + swap preemption, oversubscribed pool) completes with structured
``RequestError``s for the faulted requests, token-for-token greedy
parity with the fault-free run for every unfaulted request, and the
state audit passing after every step.  Satellites: injector
determinism, swap corruption detection -> recompute fallback,
admission retry exhaustion, the no-progress watchdog, NaN quarantine,
and per-request deadlines.
"""
import dataclasses

import pytest

import jax
import numpy as np

from repro.config import ServeConfig
from repro.configs import get_config
from repro.models import build_model
from repro.serving import (FAULT_POINTS, RECOVERABLE_POINTS,
                           EngineStalledError, FaultInjector, FaultSpec,
                           Request, ServingEngine)
from repro.serving import invariants
from repro.serving.faults import checksum


# ---------------------------------------------------------------------------
# Injector unit tests (no model)
# ---------------------------------------------------------------------------


def test_spec_validation():
    with pytest.raises(ValueError, match="unknown fault point"):
        FaultSpec("bad_point", nth=1)
    with pytest.raises(ValueError, match="exactly one"):
        FaultSpec("page_alloc", nth=1, prob=0.5)
    with pytest.raises(ValueError, match="exactly one"):
        FaultSpec("page_alloc")
    with pytest.raises(ValueError, match="1-based"):
        FaultSpec("page_alloc", nth=0)
    with pytest.raises(ValueError, match="prob"):
        FaultSpec("page_alloc", prob=1.5)
    with pytest.raises(ValueError, match="unknown fault point"):
        FaultInjector(0).fires("bad_point")


def test_nth_trigger_fires_exactly_once():
    inj = FaultInjector(seed=0).add("page_alloc", nth=3)
    seq = [inj.fires("page_alloc") for _ in range(8)]
    assert seq == [False, False, True] + [False] * 5
    assert inj.hits("page_alloc") == 8
    assert inj.fired_log == [("page_alloc", 3)]
    assert inj.points_fired() == ("page_alloc",)


def test_prob_stream_deterministic_and_point_independent():
    def run(seed, interleave):
        inj = FaultInjector(seed=seed)
        inj.add("swap_out", prob=0.5, times=None)
        seq = []
        for _ in range(64):
            if interleave:            # traffic at other points must
                inj.fires("page_alloc")   # not reshuffle this stream
                inj.fires("nan_logits")
            seq.append(inj.fires("swap_out"))
        return seq

    base = run(7, interleave=False)
    assert any(base) and not all(base)          # a real Bernoulli mix
    assert run(7, interleave=False) == base     # same seed -> same
    assert run(7, interleave=True) == base      # per-point streams
    assert run(8, interleave=False) != base     # seed matters


def test_times_budget_caps_firings():
    inj = FaultInjector(seed=0).add("swap_out", prob=1.0, times=3)
    assert sum(inj.fires("swap_out") for _ in range(10)) == 3
    # the default times=1 makes nth semantics one-shot too
    inj = FaultInjector(seed=0).add("prefill_delay", prob=1.0)
    assert sum(inj.fires("prefill_delay") for _ in range(10)) == 1


def test_chaos_schedule_arms_recoverable_points_only():
    inj = FaultInjector.chaos(seed=0, rate=1.0)
    for p in RECOVERABLE_POINTS:
        assert inj.fires(p), p
    assert not inj.fires("nan_logits")          # parity-breaking: out
    assert set(inj.points_fired()) == set(RECOVERABLE_POINTS)


def test_corrupt_flips_one_byte_deterministically():
    buf = np.arange(32, dtype=np.float32)
    out = FaultInjector(seed=3).corrupt("swap_corrupt", buf)
    assert out.shape == buf.shape and out.dtype == buf.dtype
    diff = np.nonzero(buf.view(np.uint8).reshape(-1)
                      != out.view(np.uint8).reshape(-1))[0]
    assert len(diff) == 1                       # exactly one bit-flip
    again = FaultInjector(seed=3).corrupt("swap_corrupt", buf)
    assert np.array_equal(out, again)           # reproducible
    assert checksum([out]) != checksum([buf])   # swap-in catches it


def test_checksum_over_pytree():
    tree = {"k": np.arange(6, dtype=np.float32).reshape(2, 3),
            "v": np.ones(4, np.int32)}
    same = {"k": tree["k"].copy(), "v": tree["v"].copy()}
    assert checksum(tree) == checksum(same)
    same["v"][0] = 2
    assert checksum(tree) != checksum(same)


# ---------------------------------------------------------------------------
# Engine scenarios
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("tinyllama-1.1b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


# the chaos workload (tuned so every fault point is genuinely hit):
# four short sharers publish prefix-index entries and finish fast, a
# duplicate of the first finisher full-hits its terminal entry and
# forks the shared partial page on divergence (copy_page), and three
# long fresh requests grow the decode footprint past the 8-page pool
# (swap preemption + reclaim under pressure)
CHAOS_SC = dict(max_seq_len=32, max_batch=4, temperature=0.0,
                decode_chunk=4, page_size=8, prefill_chunk=8,
                share_prefix=True, admission="optimistic",
                preempt_mode="swap", n_pages=8, watermark_low=0.1)


def _chaos_reqs(cfg):
    rng = np.random.default_rng(3)
    common = rng.integers(0, cfg.vocab_size, 10).astype(np.int32)

    def fam(k):
        tail = rng.integers(0, cfg.vocab_size, k).astype(np.int32)
        return np.concatenate([common, tail])

    def fresh(n, seed):
        r = np.random.default_rng(seed)
        return r.integers(0, cfg.vocab_size, n).astype(np.int32)

    p0, p1, p2, p3 = fam(4), fam(3), fam(4), fam(3)
    specs = [(p0, 2), (p1, 2), (p2, 2), (p3, 2),
             (p1.copy(), 12),                   # dup -> full hit + COW
             (fresh(14, 21), 12), (fresh(13, 22), 12),
             (fresh(14, 23), 12)]
    return [Request(rid=i, prompt=p, max_new_tokens=m)
            for i, (p, m) in enumerate(specs)]


def _chaos_baseline(cfg, params, **sc_kw):
    sc = ServeConfig(**CHAOS_SC, **sc_kw)
    reqs = _chaos_reqs(cfg)
    ServingEngine(cfg, params, sc).generate(reqs)
    return [r.out_tokens for r in reqs]


def test_chaos_every_fault_point_acceptance(setup):
    """The acceptance run: one seeded schedule fires all eight fault
    points in a single mixed batch.  Exactly one request dies (the
    terminal ``nan_logits``) with a structured error; every other
    request matches the fault-free run token for token; the state
    audit ran after every step (``audit=True``); the pool drains."""
    cfg, model, params = setup
    ref = _chaos_baseline(cfg, params)
    inj = (FaultInjector(seed=0)
           .add("page_alloc", nth=2)
           .add("copy_page", nth=1)
           .add("swap_out", nth=1)
           .add("swap_corrupt", nth=1)
           .add("swap_in", nth=1)
           .add("prefix_reclaim", nth=1)
           .add("prefill_delay", nth=6)
           .add("nan_logits", nth=8))
    sc = ServeConfig(**CHAOS_SC, audit=True)
    eng = ServingEngine(cfg, params, sc, faults=inj)
    reqs = _chaos_reqs(cfg)
    eng.generate(reqs)

    assert set(inj.points_fired()) == set(FAULT_POINTS)
    failed = [r for r in reqs if r.failed]
    assert len(failed) == 1
    assert failed[0].error.kind == "numerics"
    assert failed[0].error.step > 0
    assert eng.error_counts["numerics"] == 1
    for i, r in enumerate(reqs):
        if not r.failed:
            assert r.out_tokens == ref[i], r.rid
            assert r.done and not r.truncated
    # recovery machinery demonstrably ran
    assert eng.n_retried >= 1
    assert eng.n_swap_fallbacks >= 1
    assert eng.n_preempted >= 1
    # full drain: only index pins hold pages, audit still clean
    assert (eng.pool.free_count + eng._pindex.n_pinned
            == eng.pool.n_pages)
    invariants.audit(eng)


def test_chaos_acceptance_reproduces_bit_for_bit(setup):
    """Same seed, same schedule, same workload -> identical firing
    receipt and identical outputs (the property that makes a chaos
    failure debuggable at all)."""
    cfg, model, params = setup

    def run():
        inj = (FaultInjector(seed=0)
               .add("swap_corrupt", nth=1)
               .add("prefill_delay", nth=6)
               .add("page_alloc", prob=0.2, times=None))
        eng = ServingEngine(cfg, params, ServeConfig(**CHAOS_SC),
                            faults=inj)
        reqs = _chaos_reqs(cfg)
        eng.generate(reqs)
        return inj.fired_log, [r.out_tokens for r in reqs]

    log_a, outs_a = run()
    log_b, outs_b = run()
    assert log_a == log_b
    assert outs_a == outs_b
    assert log_a                                # something fired


def test_config_chaos_seed_preserves_parity(setup):
    """``ServeConfig.chaos_seed`` (the paged-chaos CI leg's switch)
    arms the recoverable-points schedule engine-side: faults fire, yet
    every request completes with full greedy parity."""
    cfg, model, params = setup
    ref = _chaos_baseline(cfg, params)
    sc = ServeConfig(**CHAOS_SC, audit=True, chaos_seed=0,
                     chaos_rate=0.25)
    eng = ServingEngine(cfg, params, sc)
    reqs = _chaos_reqs(cfg)
    eng.generate(reqs)
    assert eng.faults is not None and eng.faults.fired_log
    assert [r.out_tokens for r in reqs] == ref
    assert all(r.done and not r.failed for r in reqs)
    assert (eng.pool.free_count + eng._pindex.n_pinned
            == eng.pool.n_pages)


def test_swap_corruption_detected_and_recomputed(setup):
    """A bit-flipped host swap buffer fails its crc32 check at swap-in
    and the victim is recomputed instead of resuming from garbage:
    outputs keep parity, the fallback counter surfaces it."""
    cfg, model, params = setup
    ref = _chaos_baseline(cfg, params)
    inj = FaultInjector(seed=0).add("swap_corrupt", nth=1)
    sc = ServeConfig(**CHAOS_SC, audit=True)
    eng = ServingEngine(cfg, params, sc, faults=inj)
    reqs = _chaos_reqs(cfg)
    eng.generate(reqs)
    assert inj.points_fired() == ("swap_corrupt",)
    assert eng.n_swap_fallbacks >= 1
    assert [r.out_tokens for r in reqs] == ref
    assert all(not r.failed for r in reqs)


def test_swap_failure_terminal_without_fallback(setup):
    """With ``swap_fallback=False`` a failed swap-in is a structured
    terminal error (kind ``swap_failed``) for that request only; the
    rest of the batch keeps parity."""
    cfg, model, params = setup
    ref = _chaos_baseline(cfg, params, swap_fallback=False)
    inj = FaultInjector(seed=0).add("swap_in", nth=1)
    sc = ServeConfig(**CHAOS_SC, audit=True, swap_fallback=False)
    eng = ServingEngine(cfg, params, sc, faults=inj)
    reqs = _chaos_reqs(cfg)
    eng.generate(reqs)
    failed = [r for r in reqs if r.failed]
    assert len(failed) == 1
    assert failed[0].error.kind == "swap_failed"
    assert "swap_in" in failed[0].error.detail
    for i, r in enumerate(reqs):
        if not r.failed:
            assert r.out_tokens == ref[i], r.rid
    assert eng.pool.free_count + eng._pindex.n_pinned \
        == eng.pool.n_pages


def test_admission_retry_exhaustion_fails_pool_exhausted(setup):
    """Persistent allocation failure at admission is retried with
    backoff ``admission_retries`` times, then surfaced as a structured
    ``pool_exhausted`` failure instead of hanging the queue."""
    cfg, model, params = setup
    inj = FaultInjector(seed=0).add("page_alloc", prob=1.0, times=None)
    sc = ServeConfig(max_seq_len=32, max_batch=2, temperature=0.0,
                     decode_chunk=4, page_size=8, prefill_chunk=8,
                     n_pages=8, admission="optimistic",
                     admission_retries=2, audit=True)
    eng = ServingEngine(cfg, params, sc, faults=inj)
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i, prompt=rng.integers(
                0, cfg.vocab_size, 8).astype(np.int32),
                max_new_tokens=4) for i in range(2)]
    eng.generate(reqs)
    assert all(r.failed for r in reqs)
    assert all(r.error.kind == "pool_exhausted" for r in reqs)
    assert eng.n_retried >= 2 * sc.admission_retries
    assert eng.error_counts["pool_exhausted"] == 2
    assert eng.pool.free_count == eng.pool.n_pages


def test_watchdog_raises_on_stall(setup):
    """A prefill that never completes (every chunk delayed, forever)
    makes zero progress; after ``stall_steps`` such steps the engine
    raises ``EngineStalledError`` carrying a scheduler dump instead of
    spinning silently."""
    cfg, model, params = setup
    inj = FaultInjector(seed=0).add("prefill_delay", prob=1.0,
                                    times=None)
    sc = ServeConfig(max_seq_len=32, max_batch=2, temperature=0.0,
                     decode_chunk=4, page_size=8, prefill_chunk=8,
                     stall_steps=5, audit=True)
    eng = ServingEngine(cfg, params, sc, faults=inj)
    rng = np.random.default_rng(5)
    reqs = [Request(rid=0, prompt=rng.integers(
                0, cfg.vocab_size, 12).astype(np.int32),
                max_new_tokens=4)]
    with pytest.raises(EngineStalledError) as ei:
        eng.generate(reqs)
    assert ei.value.n_steps == 5
    assert "slot 0" in ei.value.dump           # the stuck slot
    assert "rid=0" in ei.value.dump


def test_stall_steps_zero_disables_watchdog(setup):
    """``stall_steps=0`` must mean 'off', not 'trip immediately'."""
    cfg, model, params = setup
    sc = ServeConfig(max_seq_len=32, max_batch=2, temperature=0.0,
                     decode_chunk=4, page_size=8, prefill_chunk=8,
                     stall_steps=0)
    eng = ServingEngine(cfg, params, sc)
    rng = np.random.default_rng(5)
    reqs = [Request(rid=0, prompt=rng.integers(
                0, cfg.vocab_size, 8).astype(np.int32),
                max_new_tokens=4)]
    eng.generate(reqs)                          # must not raise
    assert reqs[0].done and not reqs[0].failed


def test_numerics_quarantine_fails_only_poisoned_slot(setup):
    """NaN logits out of the decode kernel quarantine exactly the
    offending slot (kind ``numerics``); its sibling's stream is
    untouched and matches a fault-free run."""
    cfg, model, params = setup
    sc = ServeConfig(max_seq_len=32, max_batch=2, temperature=0.0,
                     decode_chunk=4, page_size=8, prefill_chunk=8)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
               for _ in range(2)]
    base = [Request(rid=i, prompt=p.copy(), max_new_tokens=6)
            for i, p in enumerate(prompts)]
    ServingEngine(cfg, params, sc).generate(base)

    inj = FaultInjector(seed=0).add("nan_logits", nth=1)
    eng = ServingEngine(cfg, params, sc, faults=inj)
    reqs = [Request(rid=i, prompt=p.copy(), max_new_tokens=6)
            for i, p in enumerate(prompts)]
    eng.generate(reqs)
    assert reqs[0].failed and reqs[0].error.kind == "numerics"
    assert reqs[0].out_tokens == []             # poisoned chunk dropped
    assert not reqs[1].failed
    assert reqs[1].out_tokens == base[1].out_tokens
    assert eng.error_counts["numerics"] == 1


def test_guard_numerics_off_keeps_legacy_behavior(setup):
    """With the guard disabled a poisoned slot is not failed — the
    request runs to completion (emitting whatever argmax-of-NaN
    yields), matching the pre-taxonomy engine."""
    cfg, model, params = setup
    sc = ServeConfig(max_seq_len=32, max_batch=1, temperature=0.0,
                     decode_chunk=4, page_size=8, prefill_chunk=8,
                     guard_numerics=False)
    inj = FaultInjector(seed=0).add("nan_logits", nth=1)
    eng = ServingEngine(cfg, params, sc, faults=inj)
    rng = np.random.default_rng(9)
    reqs = [Request(rid=0, prompt=rng.integers(
                0, cfg.vocab_size, 8).astype(np.int32),
                max_new_tokens=4)]
    eng.generate(reqs)
    assert not reqs[0].failed and reqs[0].done


def test_total_deadline_fails_with_partial_output(setup):
    """``deadline_steps`` bounds a request's total step budget: an
    over-budget request fails with kind ``deadline`` keeping the
    tokens it already produced; an unbounded sibling is unaffected."""
    cfg, model, params = setup
    sc = ServeConfig(max_seq_len=64, max_batch=2, temperature=0.0,
                     decode_chunk=2, page_size=8, prefill_chunk=8)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
               for _ in range(2)]
    reqs = [Request(rid=0, prompt=prompts[0], max_new_tokens=24,
                    deadline_steps=3),
            Request(rid=1, prompt=prompts[1], max_new_tokens=24)]
    eng = ServingEngine(cfg, params, sc)
    eng.generate(reqs)
    assert reqs[0].failed and reqs[0].error.kind == "deadline"
    assert 0 < len(reqs[0].out_tokens) < 24     # partial output kept
    assert "3 steps" in reqs[0].error.detail
    assert reqs[1].done and not reqs[1].failed
    assert len(reqs[1].out_tokens) == 24


def test_ttft_deadline(setup):
    """``ttft_deadline_steps`` fails a request that produced no first
    token in time (here: a multi-chunk prefill that cannot finish
    within one step); a sibling with budget completes."""
    cfg, model, params = setup
    sc = ServeConfig(max_seq_len=32, max_batch=2, temperature=0.0,
                     decode_chunk=4, page_size=8, prefill_chunk=8)
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, cfg.vocab_size, 14).astype(np.int32)
               for _ in range(2)]
    reqs = [Request(rid=0, prompt=prompts[0], max_new_tokens=4,
                    ttft_deadline_steps=1),
            Request(rid=1, prompt=prompts[1], max_new_tokens=4,
                    ttft_deadline_steps=20)]
    eng = ServingEngine(cfg, params, sc)
    eng.generate(reqs)
    assert reqs[0].failed and reqs[0].error.kind == "deadline"
    assert "TTFT" in reqs[0].error.detail
    assert reqs[0].out_tokens == []
    assert reqs[1].done and not reqs[1].failed
    assert eng.pool.free_count == eng.pool.n_pages


def test_fault_points_recoverable_one_at_a_time(setup):
    """Each recoverable point, armed alone on its first hit, preserves
    full-batch greedy parity — the per-point decomposition of the
    chaos acceptance run (shrinking a failing schedule to one point
    stays meaningful)."""
    cfg, model, params = setup
    ref = _chaos_baseline(cfg, params)
    for point in RECOVERABLE_POINTS:
        inj = FaultInjector(seed=0).add(point, nth=1)
        sc = ServeConfig(**CHAOS_SC, audit=True)
        eng = ServingEngine(cfg, params, sc, faults=inj)
        reqs = _chaos_reqs(cfg)
        eng.generate(reqs)
        assert all(r.done and not r.failed for r in reqs), point
        assert [r.out_tokens for r in reqs] == ref, point
