"""Token-budget scheduler (DESIGN.md §scheduler).

``ServeConfig.max_num_batched_tokens`` puts every per-step source of
device work — decode charges, admission, prefill chunks — under one
global token budget, with the first staged chunk fused into the decode
dispatch.  These tests pin the budget accounting rules (decode charges
first, prefill truncates to the residual, admission capped at
budget occupancy), the degenerate budget=1 serialization, greedy
parity against the legacy per-request scheduler, and the structured
``oversize`` failure path that replaces engine aborts in budget mode.
The file pins its own paged+chunked layout (budget mode requires
chunked prefill), so it runs identically on every REPRO_ENGINE leg.
"""
import jax
import numpy as np
import pytest

from conftest import dropless
from repro.config import ServeConfig
from repro.configs import get_config
from repro.models import build_model
from repro.serving import Request, ServingEngine

BASE = dict(max_seq_len=32, max_batch=4, temperature=0.0,
            decode_chunk=4, page_size=4, prefill_chunk=8)
LENS = (18, 3, 12, 9, 26, 5, 14)        # mixed multi/sub-chunk prompts


@pytest.fixture(scope="module")
def setup():
    cfg = dropless(get_config("tinyllama-1.1b").reduced())
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def mk_reqs(cfg, lens=LENS, max_new=6, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        int(n)).astype(np.int32),
                    max_new_tokens=max_new)
            for i, n in enumerate(lens)]


def check_accounting(eng):
    """Budget invariants every step: decode charged first, prefill
    fills only the residual, admission never lifts occupancy past the
    budget (so n_decode itself can never exceed it)."""
    budget = eng.sc.max_num_batched_tokens
    for e in eng.budget_log:
        assert e["n_decode"] <= budget, e
        assert e["prefill_tokens"] <= budget - e["n_decode"], e


def test_config_validation():
    with pytest.raises(ValueError, match="max_num_batched_tokens"):
        ServeConfig(**BASE, max_num_batched_tokens=-1)


@pytest.mark.parametrize("budget", [1, 6, 16])
def test_parity_with_legacy_scheduler(setup, budget):
    """Greedy (temp 0) outputs are scheduling-invariant: the budgeted
    engine must reproduce the legacy engine token-for-token on a mixed
    workload, with identical error counts, for any budget."""
    cfg, model, params = setup
    legacy = mk_reqs(cfg)
    eng_l = ServingEngine(cfg, params, ServeConfig(**BASE))
    eng_l.generate(legacy)
    budgeted = mk_reqs(cfg)
    eng_b = ServingEngine(cfg, params, ServeConfig(
        **BASE, max_num_batched_tokens=budget))
    eng_b.generate(budgeted)
    for a, b in zip(legacy, budgeted):
        assert a.out_tokens == b.out_tokens, (budget, a.rid)
        assert len(b.out_tokens) == 6
    assert eng_b.n_failed == eng_l.n_failed == 0
    assert eng_b.error_counts == eng_l.error_counts
    check_accounting(eng_b)


def test_decode_charged_before_prefill(setup):
    """On every step with live decode slots, prefill gets only the
    residual: the accounting invariant plus at least one step where a
    chunk was actually squeezed below the configured chunk size."""
    cfg, params = setup[0], setup[2]
    eng = ServingEngine(cfg, params, ServeConfig(
        **BASE, max_num_batched_tokens=6))
    eng.generate(mk_reqs(cfg))
    check_accounting(eng)
    mixed = [e for e in eng.budget_log
             if e["n_decode"] and e["prefill_tokens"]]
    assert mixed, "no step mixed decode with prefill"
    for e in mixed:
        assert e["prefill_tokens"] <= 6 - e["n_decode"]


def test_chunk_truncation_at_residual(setup):
    """budget < prefill_chunk forces every leading chunk to truncate:
    the prompt still lands completely and the truncation counter
    records the squeezed chunks."""
    cfg, params = setup[0], setup[2]
    eng = ServingEngine(cfg, params, ServeConfig(
        **BASE, max_num_batched_tokens=6))
    reqs = mk_reqs(cfg, lens=(26,), max_new=4)
    eng.generate(reqs)
    assert reqs[0].done and not reqs[0].failed
    assert len(reqs[0].out_tokens) == 4
    assert eng.n_truncated_chunks > 0
    assert all(e["prefill_tokens"] <= 6 for e in eng.budget_log)


def test_budget_one_serializes(setup):
    """budget=1 is the degenerate case: one token of work per step
    (a single decode charge or a single-token prefill chunk), no
    fusion possible, and the outputs still match legacy exactly."""
    cfg, params = setup[0], setup[2]
    lens = (9, 4, 12)
    legacy = mk_reqs(cfg, lens=lens)
    ServingEngine(cfg, params, ServeConfig(**BASE)).generate(legacy)
    reqs = mk_reqs(cfg, lens=lens)
    eng = ServingEngine(cfg, params, ServeConfig(
        **BASE, max_num_batched_tokens=1))
    eng.generate(reqs)
    for a, b in zip(legacy, reqs):
        assert a.out_tokens == b.out_tokens
    for e in eng.budget_log:
        assert e["n_decode"] + e["prefill_tokens"] <= 1, e
    assert eng.n_fused_steps == 0


def test_fused_steps_fire(setup):
    """With decode and prefill overlapping, the first staged chunk
    rides the decode dispatch — the fused-iteration counter must move
    and no request may lose tokens to the fusion (the deferred-
    activation rule: slots finishing prefill mid-step join the decode
    batch next step, not the one whose live mask already snapshotted
    them out)."""
    cfg, params = setup[0], setup[2]
    eng = ServingEngine(cfg, params, ServeConfig(
        **BASE, max_num_batched_tokens=8))
    reqs = mk_reqs(cfg, lens=(4, 26, 20, 9, 18), max_new=6)
    eng.generate(reqs)
    assert eng.n_fused_steps > 0
    for r in reqs:
        assert r.done and not r.failed
        assert len(r.out_tokens) == 6
    check_accounting(eng)


def test_oversize_prompt_structured_failure(setup):
    """In budget mode an over-``max_seq_len`` prompt fails with
    kind=oversize through the taxonomy instead of aborting the
    engine; the rest of the batch is untouched."""
    cfg, params = setup[0], setup[2]
    eng = ServingEngine(cfg, params, ServeConfig(
        **BASE, max_num_batched_tokens=6))
    rng = np.random.default_rng(2)
    reqs = [Request(rid=0,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        40).astype(np.int32),
                    max_new_tokens=4),
            Request(rid=1,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        8).astype(np.int32),
                    max_new_tokens=4)]
    eng.generate(reqs)
    assert reqs[0].failed and reqs[0].error.kind == "oversize"
    assert reqs[0].out_tokens == []
    assert reqs[1].done and not reqs[1].failed
    assert len(reqs[1].out_tokens) == 4
    assert eng.error_counts["oversize"] == 1


def test_bucket_error_is_structured_failure(setup, monkeypatch):
    """The satellite bugfix: ``bucket_for`` raising ValueError for a
    chunk mid-prefill surfaces as RequestError(kind=oversize) through
    the budget path — the request unwinds, its pages free, and the
    batch keeps going."""
    cfg, params = setup[0], setup[2]
    sc = ServeConfig(**BASE, max_num_batched_tokens=8)
    orig = type(sc).bucket_for

    def boom(self, n):
        if n == 2:                       # the 10-token prompt's tail
            raise ValueError(f"no bucket for chunk of {n}")
        return orig(self, n)

    monkeypatch.setattr(type(sc), "bucket_for", boom)
    eng = ServingEngine(cfg, params, sc)
    reqs = mk_reqs(cfg, lens=(10, 8), max_new=4, seed=3)
    eng.generate(reqs)
    assert reqs[0].failed and reqs[0].error.kind == "oversize"
    assert "no bucket" in reqs[0].error.detail
    assert reqs[1].done and not reqs[1].failed
    assert len(reqs[1].out_tokens) == 4
    assert eng.error_counts["oversize"] == 1
    assert eng.pool.free_count == eng.pool.n_pages  # pages unwound
