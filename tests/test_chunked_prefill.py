"""Chunked, bucketed prefill into pages (DESIGN.md §prefill).

Parity contract: the chunked+paged prefill path produces token-for-token
identical generations to the model-level reference (exact-length
``prefill`` plus ``decode_step`` over a dense cache, ``manual_greedy``),
with at most ``len(buckets)`` prefill compiles per engine lifetime, and
decode of other slots is unaffected while a slot is mid-prefill.
"""
import pytest

import jax
import jax.numpy as jnp
import numpy as np

from conftest import dropless, manual_greedy
from repro.config import ServeConfig
from repro.configs import get_config
from repro.kernels.kq_decode import (kq_prefill_paged_attention_op,
                                     kq_prefill_paged_attention_ref)
from repro.models import build_model
from repro.serving import Request, ServingEngine
from repro.serving.paged_cache import (GARBAGE_PAGE, append_chunk,
                                       gather_pages)

CHUNK = 4
T = 64


def _setup():
    cfg = dropless(get_config("tinyllama-1.1b").reduced())
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _chunked_sc(**kw) -> ServeConfig:
    base = dict(max_seq_len=T, max_batch=4, temperature=0.0,
                decode_chunk=4, page_size=4, prefill_chunk=CHUNK,
                prefill_buckets=(2, CHUNK))
    base.update(kw)
    return ServeConfig(**base)


def _reference(model, params, prompts, n=6, proj=None):
    return [manual_greedy(model, params, p, n, T, proj=proj)
            for p in prompts]


def _generate(cfg, params, sc, prompts, n=6, proj=None):
    eng = ServingEngine(cfg, params, sc, projections=proj)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=n)
            for i, p in enumerate(prompts)]
    eng.generate(reqs)
    return [r.out_tokens for r in reqs], eng


# ---------------------------------------------------------------------------
# Engine parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rem", [0, 1, CHUNK - 1],
                         ids=["chunk-aligned", "one-over", "one-under"])
def test_chunked_matches_exact_at_chunk_boundaries(rem):
    """Token-for-token parity across L % chunk in {0, 1, chunk-1}."""
    cfg, model, params = _setup()
    L = 2 * CHUNK + rem
    rng = np.random.default_rng(7 + rem)
    prompts = [rng.integers(0, cfg.vocab_size, L).astype(np.int32)]
    chunked, _ = _generate(cfg, params, _chunked_sc(), prompts)
    assert chunked == _reference(model, params, prompts)


def test_chunked_mixed_lengths_match_exact():
    """A refilling continuous batch of mixed lengths stays identical."""
    cfg, model, params = _setup()
    rng = np.random.default_rng(11)
    lens = [3, 9, 6, 12, 5, 8]                 # > max_batch: forces refill
    prompts = [rng.integers(0, cfg.vocab_size, L).astype(np.int32)
               for L in lens]
    chunked, eng = _generate(cfg, params, _chunked_sc(), prompts)
    assert chunked == _reference(model, params, prompts)
    assert eng.pool.free_count == eng.pool.n_pages   # full drain


def test_chunked_compressed_matches_exact():
    """Chunked prefill through the compressed R_k/R_v layout."""
    from repro.config import CompressionConfig
    from repro.core.calibration import GramAccumulator

    cfg, model, params = _setup()
    acc = GramAccumulator(len(model.attn_layers))
    for i in range(2):
        toks = jax.random.randint(jax.random.PRNGKey(5 + i), (2, 32),
                                  0, cfg.vocab_size)
        caps = model.calibrate(params, toks)
        acc.update_from_captures([jax.tree.map(np.asarray, c)
                                  for c in caps])
    ccfg = CompressionConfig(method="kqsvd", rank_k=cfg.d_head,
                             rank_v=cfg.d_head)
    proj = acc.solve(ccfg, model.group_output_weights(params))
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, cfg.vocab_size, L).astype(np.int32)
               for L in (9, 5)]
    chunked, _ = _generate(cfg, params, _chunked_sc(), prompts, n=5,
                           proj=proj)
    assert chunked == _reference(model, params, prompts, n=5, proj=proj)


def test_decode_unchanged_while_other_slot_prefills():
    """A decoding slot's output is identical while another slot's long
    prompt prefills chunk-by-chunk next to it (the overlap schedule),
    i.e. the in-flight prefill's pages are isolated from the decode
    scan's masked writes."""
    cfg, model, params = _setup()
    rng = np.random.default_rng(17)
    short = rng.integers(0, cfg.vocab_size, 3).astype(np.int32)
    long = rng.integers(0, cfg.vocab_size, 20).astype(np.int32)
    sc = _chunked_sc(max_batch=2, prefill_chunks_per_step=1)
    eng = ServingEngine(cfg, params, sc)
    reqs = [Request(rid=0, prompt=short, max_new_tokens=8),
            Request(rid=1, prompt=long, max_new_tokens=8)]
    # the long prompt needs 5 chunks at one chunk per step, so the
    # short request decodes its first chunks while slot 1 is mid-prefill
    eng.generate(reqs)
    for i, p in enumerate((short, long)):
        solo, _ = _generate(cfg, params, _chunked_sc(max_batch=1), [p],
                            n=8)
        assert reqs[i].out_tokens == solo[0], i


def test_prefill_compile_count_bounded_by_buckets():
    """Many distinct prompt lengths, at most len(buckets) chunk shapes."""
    cfg, model, params = _setup()
    sc = _chunked_sc()
    rng = np.random.default_rng(23)
    prompts = [rng.integers(0, cfg.vocab_size, L).astype(np.int32)
               for L in (1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 13)]
    _, eng = _generate(cfg, params, sc, prompts, n=2)
    assert eng.prefill_chunk_shapes <= set(sc.buckets)
    assert len(eng.prefill_chunk_shapes) <= len(sc.buckets)


# ---------------------------------------------------------------------------
# Bucketing
# ---------------------------------------------------------------------------


def test_bucket_derivation_and_lookup():
    sc = ServeConfig(page_size=4, prefill_chunk=64)
    assert sc.buckets == (8, 16, 32, 64)       # derived by doubling
    assert sc.bucket_for(1) == 8
    assert sc.bucket_for(8) == 8
    assert sc.bucket_for(9) == 16
    assert sc.bucket_for(64) == 64
    explicit = ServeConfig(page_size=4, prefill_chunk=6,
                           prefill_buckets=(2, 6))
    assert explicit.buckets == (2, 6)
    assert explicit.bucket_for(3) == 6


def test_bucket_validation():
    with pytest.raises(ValueError):            # largest bucket != chunk
        ServeConfig(page_size=4, prefill_chunk=8, prefill_buckets=(2, 4))


def test_bucket_for_out_of_range_raises():
    """A chunk longer than the largest bucket must raise a clear error,
    not silently trace a fresh XLA shape past the len(buckets) compile
    bound (and never clamp, which would drop tokens)."""
    sc = ServeConfig(page_size=4, prefill_chunk=64)
    with pytest.raises(ValueError, match="compile bound"):
        sc.bucket_for(65)
    with pytest.raises(ValueError, match="chunk length"):
        sc.bucket_for(0)
    assert sc.bucket_for(64) == 64             # boundary still fine


def test_bucket_padding_does_not_change_logits():
    """The same chunk padded to two different buckets yields the same
    last-valid logits and cache contents."""
    from repro.serving.paged_cache import BlockTables, PagePool

    cfg, model, params = _setup()
    ps, n_pages = 4, 8
    prompt = (np.arange(5) * 3 % cfg.vocab_size).astype(np.int32)

    def chunked_last(bucket):
        pool = PagePool(n_pages)
        btabs = BlockTables(1, n_pages)
        btabs.assign(0, pool.alloc(2))         # 5 tokens, 4-token pages
        cache = model.init_paged_cache(n_pages + 1, ps, (0, 0))
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :5] = prompt
        valid = jnp.arange(bucket)[None, :] < 5
        logits, cache = model.prefill_chunk(
            params, cache, jnp.asarray(toks),
            jnp.asarray([0], jnp.int32), valid,
            block_table=btabs.device())
        return np.asarray(logits[0, 4])

    np.testing.assert_allclose(chunked_last(5), chunked_last(8),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# Page-write primitive + kernel
# ---------------------------------------------------------------------------


def test_append_chunk_routes_padding_to_garbage():
    rng = np.random.default_rng(0)
    B, Hkv, ps, n_pages, R, S = 2, 2, 4, 3, 8, 6
    P = 1 + B * n_pages
    pool = jnp.full((P, Hkv, ps, R), -1.0)
    btab = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    pos0 = jnp.asarray([2, 0], jnp.int32)
    n_valid = np.array([3, 6])
    vals = jnp.asarray(rng.normal(size=(B, Hkv, S, R)), jnp.float32)
    valid = jnp.arange(S)[None, :] < jnp.asarray(n_valid)[:, None]
    out = append_chunk(pool, btab, pos0, vals, valid)
    seq = gather_pages(out, btab)              # (B, Hkv, n_pages*ps, R)
    for b in range(B):
        for i in range(int(n_valid[b])):
            np.testing.assert_allclose(
                np.asarray(seq[b, :, int(pos0[b]) + i]),
                np.asarray(vals[b, :, i]), rtol=1e-6)
    # positions past each sequence's valid chunk keep the sentinel:
    # padded entries went to the garbage page, not the real pages
    for b in range(B):
        tail = np.asarray(seq[b, :, int(pos0[b]) + int(n_valid[b]):])
        assert (tail == -1.0).all()
    # real pages of the other slot untouched
    assert (np.asarray(out[GARBAGE_PAGE]) != -1.0).any()


@pytest.mark.parametrize("pos0", [(0, 0), (3, 8), (5, 13)],
                         ids=["start", "page-aligned", "mid-page"])
def test_prefill_kernel_matches_ref(pos0):
    rng = np.random.default_rng(1)
    B, Hkv, m, ps, n_pages, Rk, Rv, S = 2, 2, 2, 8, 4, 16, 12, 8
    H = Hkv * m
    P = 1 + B * n_pages
    kp = jnp.asarray(rng.normal(size=(P, Hkv, ps, Rk)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, Hkv, ps, Rv)), jnp.float32)
    perm = rng.permutation(np.arange(1, P, dtype=np.int32))
    btab = jnp.asarray(perm.reshape(B, n_pages))
    qc = jnp.asarray(rng.normal(size=(B, H, S, Rk)), jnp.float32)
    pos0 = jnp.asarray(pos0, jnp.int32)
    n_valid = jnp.asarray([S, S - 3], jnp.int32)
    lengths = pos0 + n_valid
    ref = kq_prefill_paged_attention_ref(qc, kp, vp, lengths, pos0, btab,
                                         scale=0.3)
    out = kq_prefill_paged_attention_op(qc, kp, vp, lengths, pos0, btab,
                                        scale=0.3, max_len=n_pages * ps)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5)
    # lane-padding path (non-128-multiple ranks) is exact
    padded = kq_prefill_paged_attention_op(qc, kp, vp, lengths, pos0,
                                           btab, scale=0.3,
                                           max_len=n_pages * ps,
                                           pad_lanes=True)
    np.testing.assert_allclose(np.asarray(padded), np.asarray(ref),
                               atol=1e-5)


def test_prefill_kernel_under_jit_traced_lengths():
    """max_len bounds the grid when lengths/pos0 are traced."""
    rng = np.random.default_rng(2)
    B, Hkv, m, ps, n_pages, R, S = 1, 2, 2, 4, 4, 8, 4
    P = 1 + n_pages
    kp = jnp.asarray(rng.normal(size=(P, Hkv, ps, R)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, Hkv, ps, R)), jnp.float32)
    btab = jnp.asarray(np.arange(1, P, dtype=np.int32).reshape(1, -1))
    qc = jnp.asarray(rng.normal(size=(B, Hkv * m, S, R)), jnp.float32)

    @jax.jit
    def f(lengths, pos0):
        return kq_prefill_paged_attention_op(
            qc, kp, vp, lengths, pos0, btab, scale=0.5,
            max_len=n_pages * ps)

    lengths = jnp.asarray([10], jnp.int32)
    pos0 = jnp.asarray([6], jnp.int32)
    ref = kq_prefill_paged_attention_ref(qc, kp, vp, lengths, pos0, btab,
                                         scale=0.5)
    np.testing.assert_allclose(np.asarray(f(lengths, pos0)),
                               np.asarray(ref), atol=1e-5)


def test_serve_config_chunked_requires_whole_page_seq():
    """max_seq_len must be a whole number of pages."""
    with pytest.raises(ValueError):
        ServeConfig(max_seq_len=62, page_size=4)
