"""Split-KV flash-decoding (DESIGN.md §split-kv): split kernel parity
against the unsplit kernel / dense ref / independent split oracle, the
combine pass in isolation, the lax split twin, the dispatch heuristic,
and engine-level greedy parity decode_splits>1 vs =1."""
import dataclasses

import pytest

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ServeConfig
from repro.configs import get_config
from repro.kernels.kq_decode import (combine_split_partials,
                                     default_decode_splits,
                                     kq_decode_paged_attention_op,
                                     kq_decode_paged_attention_ref,
                                     kq_decode_paged_attention_split_ref)
from repro.models import build_model
from repro.models.attention import decode_attention, split_decode_attention
from repro.serving import Request, ServingEngine


def _paged_setup(B, Hkv, n_pages, ps, Rk, Rv, seed=0):
    """Pool + *scrambled* block table (physical ids out of logical
    order), same shape conventions as test_paged_cache."""
    P = 1 + B * n_pages
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    kc = jax.random.normal(ks[1], (P, Hkv, ps, Rk))
    vc = jax.random.normal(ks[2], (P, Hkv, ps, Rv))
    perm = np.random.default_rng(seed).permutation(np.arange(1, P))
    btab = jnp.asarray(perm[: B * n_pages].reshape(B, n_pages), jnp.int32)
    return ks[0], kc, vc, btab


# ---------------------------------------------------------------------------
# Split kernel parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("num_splits,H,Hkv", [
    pytest.param(2, 4, 2, id="2"),
    pytest.param(3, 4, 2, id="3"),
    pytest.param(4, 4, 2, id="4"),
    # the benchmark cells' head shapes: MHA, group 1 (phi-3), and GQA
    # with 8 KV heads of group 8 (deepseek-67b)
    pytest.param(2, 8, 8, id="mha-2"),
    pytest.param(4, 8, 8, id="mha-4"),
    pytest.param(2, 64, 8, id="gqa8-2"),
    pytest.param(4, 64, 8, id="gqa8-4"),
])
def test_split_kernel_matches_ref_boundary_lengths(num_splits, H, Hkv):
    """Lengths straddling every split boundary: for each span edge,
    len % (span*ps) in {0, 1, span*ps - 1} plus the global edges.  A
    second slot, exported as the garbage page, runs at the same
    length."""
    n_pages, ps, Rk, Rv = 6, 4, 16, 16
    kq, kc, vc, btab = _paged_setup(1, Hkv, n_pages, ps, Rk, Rv)
    btab = jnp.concatenate([btab, jnp.zeros((1, n_pages), jnp.int32)])
    qc = jax.random.normal(kq, (2, H, Rk))
    span = -(-n_pages // num_splits)
    step = span * ps
    lengths = {1, ps, ps + 1, n_pages * ps}
    for edge in range(step, n_pages * ps + 1, step):
        lengths |= {edge - 1, edge, min(edge + 1, n_pages * ps)}
    for L in sorted(lengths):
        lens = jnp.asarray([L, L], jnp.int32)
        out = kq_decode_paged_attention_op(qc, kc, vc, lens, btab,
                                           scale=0.3,
                                           num_splits=num_splits)
        ref = kq_decode_paged_attention_ref(qc, kc, vc, lens, btab,
                                            scale=0.3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5, err_msg=f"L={L}")


def test_split_one_is_bitwise_unsplit():
    """num_splits=1 must dispatch the identical unsplit kernel — the
    parity oracle reduction, bit for bit."""
    B, H, Hkv, n_pages, ps, Rk, Rv = 2, 4, 2, 4, 8, 16, 16
    kq, kc, vc, btab = _paged_setup(B, Hkv, n_pages, ps, Rk, Rv)
    qc = jax.random.normal(kq, (B, H, Rk))
    lens = jnp.asarray([29, 8], jnp.int32)
    base = kq_decode_paged_attention_op(qc, kc, vc, lens, btab, scale=0.5)
    out = kq_decode_paged_attention_op(qc, kc, vc, lens, btab, scale=0.5,
                                       num_splits=1)
    assert jnp.array_equal(out, base)


def test_split_scrambled_block_table_and_mixed_lengths():
    """Multi-slot batch over a scrambled table: every slot's chain is
    discontiguous in physical pages and a different set of splits is
    live per slot."""
    B, H, Hkv, n_pages, ps, Rk, Rv = 3, 8, 4, 8, 4, 16, 8
    kq, kc, vc, btab = _paged_setup(B, Hkv, n_pages, ps, Rk, Rv, seed=5)
    qc = jax.random.normal(kq, (B, H, Rk))
    lens = jnp.asarray([32, 3, 17], jnp.int32)
    ref = kq_decode_paged_attention_ref(qc, kc, vc, lens, btab, scale=0.4)
    for S in (2, 3, 5, 8):
        out = kq_decode_paged_attention_op(qc, kc, vc, lens, btab,
                                           scale=0.4, num_splits=S)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5, err_msg=f"S={S}")


def test_split_lane_padded_ranks():
    """Non-lane-multiple R_k/R_v through the pad/unpad recursion with
    splits on (pad_lanes=True forces the path interpret mode skips)."""
    B, H, Hkv, n_pages, ps, Rk, Rv = 2, 4, 2, 4, 8, 20, 12
    kq, kc, vc, btab = _paged_setup(B, Hkv, n_pages, ps, Rk, Rv, seed=2)
    qc = jax.random.normal(kq, (B, H, Rk))
    lens = jnp.asarray([27, 14], jnp.int32)
    ref = kq_decode_paged_attention_ref(qc, kc, vc, lens, btab, scale=0.3)
    out = kq_decode_paged_attention_op(qc, kc, vc, lens, btab, scale=0.3,
                                       num_splits=3, pad_lanes=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_split_ref_matches_dense_ref():
    """The independent split oracle agrees with the dense paged ref —
    the two references cross-check each other before either checks
    the kernel."""
    B, H, Hkv, n_pages, ps, Rk, Rv = 2, 4, 2, 5, 4, 16, 16
    kq, kc, vc, btab = _paged_setup(B, Hkv, n_pages, ps, Rk, Rv, seed=7)
    qc = jax.random.normal(kq, (B, H, Rk))
    lens = jnp.asarray([20, 9], jnp.int32)
    ref = kq_decode_paged_attention_ref(qc, kc, vc, lens, btab, scale=0.6)
    for S in (1, 2, 3, 5):
        split = kq_decode_paged_attention_split_ref(
            qc, kc, vc, lens, btab, num_splits=S, scale=0.6)
        np.testing.assert_allclose(np.asarray(split), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5, err_msg=f"S={S}")


# ---------------------------------------------------------------------------
# Combine pass in isolation
# ---------------------------------------------------------------------------


def test_combine_matches_concatenated_softmax():
    """Merging per-segment partials must equal one softmax over the
    concatenated scores."""
    rng = np.random.default_rng(0)
    m, Rv, S, seg = 4, 8, 3, 5
    s = jnp.asarray(rng.standard_normal((m, S * seg)), jnp.float32) * 3
    v = jnp.asarray(rng.standard_normal((S * seg, Rv)), jnp.float32)
    want = jax.nn.softmax(s, axis=-1) @ v
    o_parts, lses = [], []
    for i in range(S):
        blk = s[:, i * seg:(i + 1) * seg]
        mx = blk.max(axis=-1)
        p = jnp.exp(blk - mx[:, None])
        l = p.sum(axis=-1)
        o_parts.append(p @ v[i * seg:(i + 1) * seg] / l[:, None])
        lses.append(mx + jnp.log(l))
    got = combine_split_partials(jnp.stack(o_parts, axis=0)[None],
                                 jnp.stack(lses, axis=0)[None])[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_combine_empty_split_is_neutral():
    """An empty split's (0, ~-inf) partial must not perturb the merge,
    and an all-empty merge must produce exactly 0 (the unsplit
    kernel's length-0 output)."""
    m, Rv = 2, 4
    live = jnp.ones((m, Rv)) * 2.0
    lse_live = jnp.zeros((m,))
    empty = jnp.zeros((m, Rv))
    lse_empty = jnp.full((m,), -1e30 + np.log(1e-30))
    out = combine_split_partials(
        jnp.stack([live, empty], axis=0),
        jnp.stack([lse_live, lse_empty], axis=0))
    np.testing.assert_allclose(np.asarray(out), np.asarray(live),
                               rtol=0, atol=0)
    out0 = combine_split_partials(
        jnp.stack([empty, empty], axis=0),
        jnp.stack([lse_empty, lse_empty], axis=0))
    assert float(jnp.max(jnp.abs(out0))) == 0.0


def test_combine_extreme_scale_stability():
    """Partials whose LSEs differ by hundreds must merge without
    overflow: the max-subtraction keeps every exponent <= 0."""
    m, Rv = 2, 4
    o = jnp.stack([jnp.ones((1, m, Rv)), jnp.full((1, m, Rv), 5.0)],
                  axis=1)
    lse = jnp.stack([jnp.full((1, m), 400.0), jnp.full((1, m), -400.0)],
                    axis=1)
    out = combine_split_partials(o, lse)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(o[:, 0]),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Lax split twin
# ---------------------------------------------------------------------------


def test_lax_split_twin_matches_decode_attention():
    """split_decode_attention must agree with decode_attention for any
    segmentation, including ragged valid masks and S > T."""
    rng = np.random.default_rng(4)
    B, H, Hkv, T, dk, rv = 3, 8, 4, 21, 16, 8
    q = jnp.asarray(rng.standard_normal((B, H, 1, dk)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, Hkv, T, dk)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, Hkv, T, rv)), jnp.float32)
    valid = jnp.arange(T)[None, :] < jnp.asarray([21, 1, 13])[:, None]
    want = decode_attention(q, k, v, valid, 0.25)
    for S in (1, 2, 3, 7, 21, 64):
        got = split_decode_attention(q, k, v, valid, 0.25, S)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5, err_msg=f"S={S}")


# ---------------------------------------------------------------------------
# Dispatch heuristic
# ---------------------------------------------------------------------------


def test_default_decode_splits_heuristic():
    # short chains stay unsplit: the combine pass must pay for itself
    assert default_decode_splits(64, 64) == 1
    assert default_decode_splits(7 * 64, 64) == 1
    # one split per min_pages_per_split pages...
    assert default_decode_splits(8 * 64, 64) == 2
    assert default_decode_splits(16 * 64, 64) == 4
    # ...capped at max_splits
    assert default_decode_splits(1 << 20, 64) == 8
    assert default_decode_splits(1 << 20, 64, max_splits=16) == 16
    # monotone in max_len
    prev = 0
    for L in range(64, 64 * 64, 64):
        s = default_decode_splits(L, 64)
        assert s >= prev
        prev = s


# ---------------------------------------------------------------------------
# Hypothesis property over (length, num_splits)
# ---------------------------------------------------------------------------

_B, _H, _Hkv, _NP, _PS, _R = 1, 4, 2, 8, 4, 16
_T = _NP * _PS
_KQ, _KC, _VC, _BTAB = _paged_setup(_B, _Hkv, _NP, _PS, _R, _R, seed=11)
_QC = jax.random.normal(_KQ, (_B, _H, _R))


def _split_parity_case(length, num_splits):
    lens = jnp.asarray([length], jnp.int32)
    out = kq_decode_paged_attention_op(_QC, _KC, _VC, lens, _BTAB,
                                       scale=0.4, max_len=_T,
                                       num_splits=num_splits)
    ref = kq_decode_paged_attention_ref(_QC, _KC, _VC, lens, _BTAB,
                                        scale=0.4)
    sref = kq_decode_paged_attention_split_ref(
        _QC, _KC, _VC, lens, _BTAB, num_splits=num_splits, scale=0.4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(sref), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # container has no hypothesis; CI does
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(length=st.integers(min_value=1, max_value=_T),
           num_splits=st.integers(min_value=1, max_value=2 * _NP))
    def test_split_parity_property(length, num_splits):
        """For every (length, num_splits) the split kernel, the split
        oracle, and the dense ref agree (static max_len=_T keeps one
        compile per num_splits)."""
        _split_parity_case(length, num_splits)
else:
    @pytest.mark.parametrize("length,num_splits",
                             [(1, 3), (15, 2), (16, 5), (17, 4),
                              (31, 16), (32, 7)])
    def test_split_parity_property(length, num_splits):
        """Fixed-grid fallback of the hypothesis property when
        hypothesis is not installed (CI runs the full property)."""
        _split_parity_case(length, num_splits)


# ---------------------------------------------------------------------------
# Engine-level greedy parity
# ---------------------------------------------------------------------------


def test_engine_split_decode_greedy_parity():
    """The full paged engine with decode_splits=3 must emit the same
    greedy tokens as decode_splits=1 on a mixed-length batch (the
    acceptance bar for the paged-longctx CI leg, in miniature)."""
    cfg = get_config("tinyllama-1.1b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, cfg.vocab_size, L).astype(np.int32)
               for L in (11, 3, 17, 7)]

    def reqs():
        return [Request(rid=i, prompt=prompts[i], max_new_tokens=6)
                for i in range(4)]
    base = dict(max_seq_len=32, max_batch=4, temperature=0.0,
                decode_chunk=4, paged=True, page_size=4,
                chunked_prefill=True, prefill_chunk=8)
    outs = {}
    for splits in (1, 3):
        eng = ServingEngine(cfg, params,
                            ServeConfig(**base, decode_splits=splits))
        served = eng.generate(reqs())
        assert all(r.done and not r.failed for r in served)
        outs[splits] = [list(r.out_tokens) for r in served]
    assert outs[1] == outs[3]


def test_decode_splits_config_validation():
    with pytest.raises(ValueError):
        ServeConfig(decode_splits=2)          # requires paged
    with pytest.raises(ValueError):
        ServeConfig(paged=True, decode_splits=-1)
    # 0 derives the heuristic at engine construction
    sc = ServeConfig(paged=True, page_size=64, max_seq_len=4096,
                     decode_splits=0)
    assert sc.decode_splits == 0
    sc1 = dataclasses.replace(sc, decode_splits=1)
    assert sc1.decode_splits == 1
