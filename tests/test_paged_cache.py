"""Paged KV cache: pool invariants, paged kernel parity, paged serving
(DESIGN.md §paged-cache).  The dense path is the oracle throughout."""
import dataclasses

import pytest

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import CompressionConfig, ServeConfig
from repro.configs import get_config
from repro.core.calibration import GramAccumulator
from repro.kernels.kq_decode import (kq_decode_attention_op,
                                     kq_decode_attention_ref,
                                     kq_decode_paged_attention_int8_ref,
                                     kq_decode_paged_attention_op,
                                     kq_decode_paged_attention_ref)
from repro.models import build_model
from repro.serving import (PagePool, PagePoolExhausted, Request,
                           ServingEngine, pages_needed)


# ---------------------------------------------------------------------------
# PagePool / block-table invariants
# ---------------------------------------------------------------------------


def test_pool_alloc_free_roundtrip():
    pool = PagePool(4)
    assert pool.free_count == 4
    a = pool.alloc(3)
    assert len(set(a)) == 3 and 0 not in a       # unique, never garbage
    assert pool.free_count == 1
    pool.free(a[:2])
    assert pool.free_count == 3
    b = pool.alloc(3)
    assert 0 not in b and pool.free_count == 0
    assert set(b) & set(a[:2])                    # freed pages recycle


def test_pool_exhaustion_allocates_nothing():
    pool = PagePool(2)
    pool.alloc(1)
    with pytest.raises(PagePoolExhausted):
        pool.alloc(2)
    assert pool.free_count == 1                   # failed alloc took none


def test_pool_double_free_and_garbage_guard():
    pool = PagePool(2)
    pages = pool.alloc(1)
    pool.free(pages)
    with pytest.raises(ValueError):
        pool.free(pages)
    with pytest.raises(ValueError):
        pool.free([0])


def test_pages_needed():
    assert pages_needed(0, 8) == 0
    assert pages_needed(1, 8) == 1
    assert pages_needed(8, 8) == 1
    assert pages_needed(9, 8) == 2


def test_pool_watermarks():
    """High watermark caps optimistic admission; low watermark becomes
    the slack a preemption pass frees beyond the strict deficit."""
    pool = PagePool(10, high_watermark=0.8, low_watermark=0.2)
    assert pool.high_pages == 8 and pool.low_extra == 2
    pool.alloc(7)
    assert pool.can_admit(1)              # 7 + 1 <= 8
    assert not pool.can_admit(2)          # would cross the high watermark
    # default watermarks are neutral: admit while anything is free
    full = PagePool(4)
    assert full.high_pages == 4 and full.low_extra == 0
    full.alloc(3)
    assert full.can_admit(1) and not full.can_admit(2)


def test_swap_roundtrip_is_byte_exact():
    """swap_out -> free -> alloc elsewhere -> swap_in restores the
    slot's live entries exactly through a *different* block-table row,
    and never touches the other slot's pages."""
    from repro.serving import gather_pages, swap_in, swap_out

    rng_ = np.random.default_rng(0)
    Hkv, ps, R, L = 2, 4, 8, 11                     # 11 tokens -> 3 pages
    P = 8
    pool = jnp.asarray(rng_.normal(size=(P, Hkv, ps, R)), jnp.float32)
    row = np.array([3, 1, 6, 0], np.int32)          # victim's pages
    other = np.array([2, 5, 0, 0], np.int32)        # bystander slot
    buf = swap_out(pool, row, L)
    assert buf.shape == (Hkv, L, R)
    ref = np.asarray(gather_pages(pool, jnp.asarray(other[None])))
    new_row = np.array([7, 4, 3, 0], np.int32)      # re-alloc'd elsewhere
    pool2 = swap_in(pool, new_row, buf)
    restored = np.asarray(gather_pages(pool2, jnp.asarray(new_row[None])))
    np.testing.assert_array_equal(restored[0, :, :L], buf)
    # bystander pages untouched
    np.testing.assert_array_equal(
        np.asarray(gather_pages(pool2, jnp.asarray(other[None]))), ref)


# ---------------------------------------------------------------------------
# Paged kernel vs oracles
# ---------------------------------------------------------------------------


def _paged_setup(B, Hkv, n_pages, ps, Rk, Rv, seed=0):
    """Pool + *scrambled* block table: physical ids deliberately do not
    follow logical order, so parity only holds if the kernel really
    dereferences the table."""
    P = 1 + B * n_pages
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    kc = jax.random.normal(ks[1], (P, Hkv, ps, Rk))
    vc = jax.random.normal(ks[2], (P, Hkv, ps, Rv))
    perm = np.random.default_rng(seed).permutation(np.arange(1, P))
    btab = jnp.asarray(perm[: B * n_pages].reshape(B, n_pages), jnp.int32)
    return ks[0], kc, vc, btab


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Hkv,n_pages,ps,Rk,Rv,lengths", [
    (2, 4, 2, 4, 16, 16, 16, (64, 7)),            # full + short
    (3, 4, 2, 5, 8, 16, 8, (40, 8, 9)),           # page-boundary edges
    (1, 8, 4, 3, 16, 8, 16, (17,)),               # crosses into page 2
    (2, 2, 2, 2, 32, 16, 16, (1, 33)),
    # the benchmark cells' head shapes at narrow ranks: MHA, group 1
    # (phi-3), and GQA with 8 KV heads of group 8 (deepseek-67b); one
    # token, one page, one past a page edge, the full bound
    (4, 8, 8, 3, 8, 16, 16, (1, 8, 9, 24)),
    (4, 64, 8, 3, 8, 16, 8, (1, 8, 9, 24)),
])
def test_paged_kernel_matches_ref(B, H, Hkv, n_pages, ps, Rk, Rv, lengths,
                                  dtype):
    kq, kc, vc, btab = _paged_setup(B, Hkv, n_pages, ps, Rk, Rv)
    # one more slot exported as the garbage page (page 0), as the engine
    # exports an idle or mid-prefill slot, at the longest length
    btab = jnp.concatenate([btab, jnp.zeros((1, n_pages), jnp.int32)])
    lengths = (*lengths, max(lengths))
    qc = jax.random.normal(kq, (B + 1, H, Rk)).astype(dtype)
    kc, vc = kc.astype(dtype), vc.astype(dtype)
    lens = jnp.asarray(lengths, jnp.int32)
    out = kq_decode_paged_attention_op(qc, kc, vc, lens, btab, scale=0.25)
    ref = kq_decode_paged_attention_ref(qc, kc, vc, lens, btab, scale=0.25)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("num_splits", [1, 3])
@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
def test_paged_kernel_grouped_heads(monkeypatch, quant, num_splits):
    """A VMEM budget that holds fewer than all KV heads' pages folds them
    in groups: 6 KV heads at a budget of 4 heads' blocks take 3 a
    program, so the grid walks two head groups."""
    from repro.kernels.kq_decode import paged
    B, H, Hkv, n_pages, ps, R = 3, 12, 6, 3, 8, 16
    kq, kc, vc, btab = _paged_setup(B, Hkv, n_pages, ps, R, R, seed=4)
    qc = jax.random.normal(kq, (B, H, R))
    lens = jnp.asarray([24, 9, 1], jnp.int32)
    kw = {}
    if quant:
        kc, vc = (jnp.round(x * 40).astype(jnp.int8) for x in (kc, vc))
        kw = {"kscale": jnp.full(kc.shape[:3] + (1,), 0.025, jnp.bfloat16),
              "vscale": jnp.full(vc.shape[:3] + (1,), 0.03, jnp.bfloat16)}
    sc_bytes = 2 if quant else 0
    # double-buffered K and V blocks of one head, a scale block filling
    # a 128-lane row per token
    per_head = 2 * ps * (2 * R * kc.dtype.itemsize + 2 * 128 * sc_bytes)
    monkeypatch.setattr(paged, "DECODE_VMEM_BUDGET", 4 * per_head)
    assert paged.decode_heads_per_block(Hkv, ps, R, R, kc.dtype.itemsize,
                                        sc_bytes) == 3
    out = paged.kq_decode_paged_attention(qc, kc, vc, lens, btab,
                                          scale=0.3, num_splits=num_splits,
                                          **kw)
    if quant:
        ref = kq_decode_paged_attention_int8_ref(
            qc, kc, vc, kw["kscale"], kw["vscale"], lens, btab, scale=0.3)
    else:
        ref = kq_decode_paged_attention_ref(qc, kc, vc, lens, btab,
                                            scale=0.3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_kernel_matches_dense_kernel():
    """Gathering the pages into a dense cache and running the dense
    varlen kernel must agree with the paged kernel on the same data."""
    B, H, Hkv, n_pages, ps, Rk, Rv = 2, 4, 2, 4, 16, 16, 16
    kq, kc, vc, btab = _paged_setup(B, Hkv, n_pages, ps, Rk, Rv, seed=3)
    qc = jax.random.normal(kq, (B, H, Rk))
    lens = jnp.asarray([50, 16], jnp.int32)
    from repro.serving import gather_pages
    kd = gather_pages(kc, btab)
    vd = gather_pages(vc, btab)
    out_p = kq_decode_paged_attention_op(qc, kc, vc, lens, btab, scale=0.2)
    out_d = kq_decode_attention_op(qc, kd, vd, lens, block_t=ps, scale=0.2)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_d),
                               rtol=2e-5, atol=2e-5)


def test_lane_padding_non_multiple_ranks():
    """Arbitrary calibrated ranks: the op wrapper pads R_k/R_v to lane
    multiples and slices back bit-identically (forced on here; on real
    TPU it triggers automatically)."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    B, H, Hkv, T, Rk, Rv = 2, 4, 2, 48, 20, 12        # 20, 12 % 128 != 0
    qc = jax.random.normal(ks[0], (B, H, Rk))
    kc = jax.random.normal(ks[1], (B, Hkv, T, Rk))
    vc = jax.random.normal(ks[2], (B, Hkv, T, Rv))
    lens = jnp.asarray([48, 5], jnp.int32)
    out = kq_decode_attention_op(qc, kc, vc, lens, block_t=16, scale=0.3,
                                 pad_lanes=True)
    ref = kq_decode_attention_ref(qc, kc, vc, lens, scale=0.3)
    assert out.shape == ref.shape == (B, H, Rv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_lane_padding_paged():
    B, H, Hkv, n_pages, ps, Rk, Rv = 2, 4, 2, 3, 16, 20, 12
    kq, kc, vc, btab = _paged_setup(B, Hkv, n_pages, ps, Rk, Rv, seed=9)
    qc = jax.random.normal(kq, (B, H, Rk))
    lens = jnp.asarray([30, 17], jnp.int32)
    out = kq_decode_paged_attention_op(qc, kc, vc, lens, btab, scale=0.3,
                                       pad_lanes=True)
    ref = kq_decode_paged_attention_ref(qc, kc, vc, lens, btab, scale=0.3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Paged serving == dense serving
# ---------------------------------------------------------------------------


def _tiny(compressed=False, rank=None):
    cfg = get_config("tinyllama-1.1b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    proj = None
    if compressed:
        acc = GramAccumulator(len(model.attn_layers))
        for i in range(2):
            toks = jax.random.randint(jax.random.PRNGKey(5 + i), (2, 32),
                                      0, cfg.vocab_size)
            caps = model.calibrate(params, toks)
            acc.update_from_captures([jax.tree.map(np.asarray, c)
                                      for c in caps])
        ccfg = CompressionConfig(method="kqsvd",
                                 rank_k=rank or cfg.d_head,
                                 rank_v=rank or cfg.d_head)
        proj = acc.solve(ccfg, model.group_output_weights(params))
    return cfg, model, params, proj


def _run(cfg, params, proj, sc, prompts, max_new=6):
    eng = ServingEngine(cfg, params, sc, projections=proj)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    eng.generate(reqs)
    return eng, reqs


def _mixed_prompts(cfg, lens, seed=3):
    rng_ = np.random.default_rng(seed)
    return [rng_.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in lens]


def test_paged_engine_matches_dense_mixed_lengths():
    """Mixed prompt lengths crossing page boundaries, more requests than
    slots (forces refill into freed pages): token-identical to the
    dense engine."""
    cfg, model, params, _ = _tiny()
    prompts = _mixed_prompts(cfg, [3, 9, 6, 12, 5, 8])   # 8, 9 straddle ps=8
    sc = ServeConfig(max_seq_len=32, max_batch=4, temperature=0.0,
                     decode_chunk=4)
    _, dense = _run(cfg, params, None, sc, prompts)
    sc_p = dataclasses.replace(sc, paged=True, page_size=8)
    eng, paged = _run(cfg, params, None, sc_p, prompts)
    for d, p in zip(dense, paged):
        assert d.out_tokens == p.out_tokens, d.rid
        assert p.done and not p.truncated
    # every page returned to the pool once the batch drained
    assert eng.pool.free_count == eng.pool.n_pages


def test_paged_engine_compressed_pallas_kernel(tpu_kernels):
    """Compressed cache with the kernels on the path, as on a TPU
    backend: the paged Pallas kernel runs inside the fused decode scan
    and matches the dense engine."""
    cfg, model, params, proj = _tiny(compressed=True)
    prompts = _mixed_prompts(cfg, [4, 11, 7], seed=5)
    sc = ServeConfig(max_seq_len=32, max_batch=2, temperature=0.0,
                     decode_chunk=4)
    _, dense = _run(cfg, params, proj, sc, prompts, max_new=5)
    sc_p = dataclasses.replace(sc, paged=True, page_size=8)
    _, paged = _run(cfg, params, proj, sc_p, prompts, max_new=5)
    for d, p in zip(dense, paged):
        assert d.out_tokens == p.out_tokens, d.rid


def test_engine_full_cache_kernels_match_lax(monkeypatch):
    """A full (uncompressed) cache takes the kernels too when they are
    on the path: dense decode, paged decode and paged chunked prefill
    all match the lax engine token for token."""
    import repro.kernels
    cfg, model, params, _ = _tiny()
    prompts = _mixed_prompts(cfg, [4, 11, 7], seed=9)
    sc = ServeConfig(max_seq_len=32, max_batch=2, temperature=0.0,
                     decode_chunk=4)
    sc_p = dataclasses.replace(sc, paged=True, page_size=8,
                               chunked_prefill=True, prefill_chunk=8)
    _, ref = _run(cfg, params, None, sc, prompts, max_new=5)
    monkeypatch.setattr(repro.kernels, "use_kernels", lambda: True)
    for conf in (sc, sc_p):
        _, got = _run(cfg, params, None, conf, prompts, max_new=5)
        for r, g in zip(ref, got):
            assert r.out_tokens == g.out_tokens, (conf.paged, r.rid)


def test_paged_engine_oversubscribed_pool_reuses_freed_pages():
    """A pool sized for ~one request at a time: admission backpressure
    holds later requests pending until freed pages return, and outputs
    stay identical to the dense engine."""
    cfg, model, params, _ = _tiny()
    prompts = _mixed_prompts(cfg, [9, 7, 10], seed=11)
    sc = ServeConfig(max_seq_len=32, max_batch=2, temperature=0.0,
                     decode_chunk=4)
    _, dense = _run(cfg, params, None, sc, prompts)
    # 3 pages: fits one request (prompt<=10 tokens + 6 new < 3*8) but
    # never two concurrently -> the second/third must reuse freed pages
    sc_p = dataclasses.replace(sc, paged=True, page_size=8, n_pages=3)
    eng, paged = _run(cfg, params, None, sc_p, prompts)
    for d, p in zip(dense, paged):
        assert d.out_tokens == p.out_tokens, d.rid
    assert eng.pool.free_count == 3


def test_paged_engine_too_big_prompt_fails():
    """A prompt that cannot ever fit the pool is failed at admission
    (not raised, not hung) — DESIGN.md §preemption."""
    cfg, model, params, _ = _tiny()
    sc = ServeConfig(max_seq_len=32, max_batch=2, paged=True, page_size=8,
                     n_pages=1)
    eng = ServingEngine(cfg, params, sc)
    prompt = _mixed_prompts(cfg, [12])[0]            # needs 2 pages > 1
    reqs = [Request(rid=0, prompt=prompt, max_new_tokens=4)]
    eng.generate(reqs)
    assert reqs[0].failed and reqs[0].done and not reqs[0].out_tokens
    assert reqs[0].error.kind == "oversize"
    assert eng.n_failed == 1
    assert eng.error_counts["oversize"] == 1


def test_paged_engine_too_big_growth_fails():
    """A request whose worst-case growth exceeds the whole pool is
    failed at admission (it could never complete even alone), not
    aborted mid-decode."""
    cfg, model, params, _ = _tiny()
    sc = ServeConfig(max_seq_len=32, max_batch=1, paged=True, page_size=8,
                     n_pages=1, decode_chunk=4)
    eng = ServingEngine(cfg, params, sc)
    prompt = _mixed_prompts(cfg, [5])[0]             # 1 page, then grows
    reqs = [Request(rid=0, prompt=prompt, max_new_tokens=12)]
    eng.generate(reqs)
    assert reqs[0].failed and reqs[0].done and not reqs[0].out_tokens
    assert reqs[0].error.kind == "oversize"


def test_paged_engine_truncation_matches_dense():
    cfg, model, params, _ = _tiny()
    prompts = _mixed_prompts(cfg, [10], seed=13)
    sc = ServeConfig(max_seq_len=16, max_batch=2, decode_chunk=4)
    _, dense = _run(cfg, params, None, sc, prompts, max_new=10)
    sc_p = dataclasses.replace(sc, paged=True, page_size=8)
    _, paged = _run(cfg, params, None, sc_p, prompts, max_new=10)
    assert dense[0].out_tokens == paged[0].out_tokens
    assert paged[0].done and paged[0].truncated


def test_paged_rejects_unsupported_configs():
    cfg, model, params, _ = _tiny()
    cfg_w = dataclasses.replace(cfg, sliding_window=16)
    params_w = build_model(cfg_w).init(jax.random.PRNGKey(0))
    sc = ServeConfig(max_seq_len=32, max_batch=2, paged=True, page_size=8)
    with pytest.raises(NotImplementedError):
        ServingEngine(cfg_w, params_w, sc)
    with pytest.raises(ValueError):                  # T % page_size != 0
        ServeConfig(max_seq_len=20, max_batch=2, paged=True, page_size=8)
