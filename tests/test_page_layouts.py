"""Quantized page layouts (DESIGN.md §page-layouts).

Property tests for the layout contracts: per-layout roundtrip error
bounds (``s * w_b`` per rank at bit width ``b``), paged-int8
kernel parity against the dense int8 path, scale pools riding COW
forks byte-exactly, corrupted swapped scale bytes degrading to
recompute, the SVDq fidelity bound tying attention error to the
calibrated spectrum's tail allocation, and the per-step dynamic
split-count derivation (``decode_splits=0``) staying inside a bounded
compile set.  The random-input properties run under hypothesis when
installed (CI) and over a fixed grid otherwise (the container has no
hypothesis).
"""
import dataclasses

import pytest

import jax
import jax.numpy as jnp
import numpy as np

from conftest import dropless
from repro.config import CompressionConfig, ServeConfig
from repro.configs import get_config
from repro.core.calibration import GramAccumulator
from repro.kernels.kq_decode import (kq_decode_paged_attention_int8_ref,
                                     kq_decode_paged_attention_op)
from repro.models import build_model
from repro.models.attention import int8_decode_attention
from repro.serving import Request, ServingEngine
from repro.serving.faults import FaultInjector
from repro.serving.page_layouts import (FpLayout, Int8Layout, SvdqLayout,
                                        default_svdq_bits, packed_width,
                                        svdq_bits_from_spectrum)

try:
    from hypothesis import example, given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # container has no hypothesis; CI does
    HAVE_HYPOTHESIS = False


def _step_widths(bits):
    """Per-rank step widening ``w_b = 127 / (2^(b-1) - 1)``."""
    return np.array([127.0 / (2 ** (b - 1) - 1) for b in bits])


def _roundtrip_case(layout, seed, amp):
    """Encode/decode both sides; every element must sit within
    ``s * w_b`` of the original — 0.5 step of rounding plus up to 0.5
    step from storing the scale in bf16 (half-ulp ``2^-8`` times
    ``|q| <= 127``); the layout contract the SVDq fidelity bound
    builds on."""
    rng = np.random.default_rng(seed)
    R = 8
    x = jnp.asarray(rng.normal(size=(3, 2, 5, R)) * amp, jnp.float32)
    for side in ("k", "v"):
        enc = layout.encode(side, x)
        dec = np.asarray(layout.decode(side, enc, R), np.float32)
        s = np.asarray(enc[side + "scale"], np.float32)      # (..., 1)
        if side == "k" and isinstance(layout, SvdqLayout):
            bits = layout.resolve_bits(R)
        else:
            bits = (8,) * R
        bound = 1.0 * s * _step_widths(bits)                 # (..., R)
        assert np.all(np.abs(dec - np.asarray(x)) <= bound + 1e-7), (
            layout.name, side)


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2 ** 16),
           amp=st.floats(min_value=1e-3, max_value=1e3),
           svdq=st.booleans())
    def test_roundtrip_error_bound_property(seed, amp, svdq):
        """For every input scale and seed, int8 and svdq encode/decode
        stay within the per-rank step bound."""
        _roundtrip_case(SvdqLayout() if svdq else Int8Layout(), seed, amp)
else:
    @pytest.mark.parametrize("seed,amp", [(0, 1.0), (1, 1e-3), (2, 37.5),
                                          (3, 1e3)])
    @pytest.mark.parametrize("layout", [Int8Layout(), SvdqLayout()],
                             ids=["int8", "svdq"])
    def test_roundtrip_error_bound_property(layout, seed, amp):
        """Fixed-grid fallback of the hypothesis property when
        hypothesis is not installed (CI runs the full property)."""
        _roundtrip_case(layout, seed, amp)


def test_fp_layout_identity():
    """The parity-oracle layout is bitwise identity both ways."""
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 3, 8)),
                    jnp.bfloat16)
    lay = FpLayout()
    for side in ("k", "v"):
        enc = lay.encode(side, x)
        assert list(enc) == [side + "c"]
        dec = lay.decode(side, enc, 8)
        assert np.array_equal(np.asarray(dec), np.asarray(x))


def test_svdq_bit_allocation_shapes():
    """Default ladder, spectrum-driven allocation, and packed stride."""
    assert default_svdq_bits(8) == (8, 8, 4, 4, 4, 4, 2, 2)
    bits = svdq_bits_from_spectrum([5, 3, 2, 1, .5, .2, .1, .05])
    assert bits == tuple(sorted(bits, reverse=True))         # monotone
    assert bits[0] == 8
    assert packed_width(bits) < 8                            # packs
    lay = SvdqLayout()
    assert lay.token_bytes("k", 8) < Int8Layout().token_bytes("k", 8)


# ---------------------------------------------------------------------------
# Paged int8 kernel vs the dense int8 path
# ---------------------------------------------------------------------------


# (KV heads, group size): the narrow default, then the benchmark cells'
# head shapes — MHA, group 1 (phi-3), and GQA with 8 KV heads of group
# 8 (deepseek-67b)
_INT8_SHAPES = [(2, 2), (4, 1), (8, 8)]


def _paged_int8_case(seed, num_splits, shape=(2, 2)):
    G, m = shape
    T, ps, R = 16, 4, 8
    # one token, one page, one past a page edge, the full bound, a tail
    lens = jnp.asarray([13, T, 1, ps, ps + 1], jnp.int32)
    B = lens.shape[0]
    rng = np.random.default_rng(seed)
    k = jnp.asarray(rng.normal(size=(B, G, T, R)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, G, T, R)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B + 1, G * m, R)), jnp.float32)
    lay = Int8Layout()
    enc_k, enc_v = lay.encode("k", k), lay.encode("v", v)

    # repage the dense-quantized leaves into shuffled physical pools; a
    # last slot is exported as the garbage page, whose contents are the
    # first slot's first page
    n_phys = 1 + B * (T // ps)
    perm = rng.permutation(np.arange(1, n_phys, dtype=np.int32))
    btab = np.concatenate([perm.reshape(B, T // ps),
                           np.zeros((1, T // ps), np.int32)])

    def pool_of(dense, width):
        pool = np.zeros((n_phys, G, ps, width), np.asarray(dense).dtype)
        d = np.asarray(dense)
        for b in range(B):
            for j in range(T // ps):
                pool[btab[b, j]] = d[b, :, j * ps:(j + 1) * ps, :]
        pool[0] = d[0, :, :ps, :]
        return jnp.asarray(pool)

    pools = (pool_of(enc_k["kc"], R), pool_of(enc_v["vc"], R))
    scales = (pool_of(enc_k["kscale"], 1), pool_of(enc_v["vscale"], 1))
    all_lens = jnp.concatenate([lens, lens[1:2]])
    out = kq_decode_paged_attention_op(
        q, *pools, all_lens, jnp.asarray(btab), scale=0.3, max_len=T,
        num_splits=num_splits, kscale=scales[0], vscale=scales[1])

    valid = jnp.arange(T)[None, :] < lens[:, None]
    dense = int8_decode_attention(
        q[:B].reshape(B, G, m, R), enc_k["kc"], enc_v["vc"],
        jnp.asarray(enc_k["kscale"])[..., 0],
        jnp.asarray(enc_v["vscale"])[..., 0], valid, 0.3)
    # the dense twin casts its output to bf16 — compare at bf16 grain
    np.testing.assert_allclose(np.asarray(out[:B], np.float32),
                               np.asarray(dense,
                                          np.float32).reshape(B, G * m, R),
                               rtol=1e-2, atol=1e-2)
    ref = kq_decode_paged_attention_int8_ref(
        q, *pools, *scales, all_lens, jnp.asarray(btab), scale=0.3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-5, atol=2e-5)


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2 ** 16),
           num_splits=st.integers(min_value=1, max_value=4),
           shape=st.sampled_from(_INT8_SHAPES))
    @example(seed=0, num_splits=1, shape=(4, 1))
    @example(seed=1, num_splits=3, shape=(4, 1))
    @example(seed=2, num_splits=1, shape=(8, 8))
    @example(seed=3, num_splits=3, shape=(8, 8))
    def test_paged_int8_matches_dense_int8(seed, num_splits, shape):
        """The dequantize-on-the-fly paged kernel (unsplit and split)
        equals the dense int8 decode on the same quantized entries."""
        _paged_int8_case(seed, num_splits, shape)
else:
    @pytest.mark.parametrize("seed,num_splits,shape",
                             [(0, 1, (2, 2)), (1, 2, (2, 2)), (2, 3, (2, 2)),
                              (3, 4, (2, 2)), (0, 1, (4, 1)), (1, 3, (4, 1)),
                              (2, 1, (8, 8)), (3, 3, (8, 8))])
    def test_paged_int8_matches_dense_int8(seed, num_splits, shape):
        """Fixed-grid fallback of the hypothesis property when
        hypothesis is not installed (CI runs the full property)."""
        _paged_int8_case(seed, num_splits, shape)


# ---------------------------------------------------------------------------
# SVDq fidelity bound
# ---------------------------------------------------------------------------


def test_svdq_fidelity_bound_from_spectrum():
    """Attention error under SVDq key quantization stays below the
    analytic bound driven by the spectrum's tail allocation.

    Per token the score perturbation is ``|q . dk| <= sum_i |q_i| *
    s * w_{b_i}`` (the roundtrip contract), and softmax is
    2-Lipschitz in the max-norm of its logits, so the output error is
    bounded by ``2 * scale * max_t |q . dk_t| * max |v|``.  Bits follow
    the calibrated spectrum, so the wide steps (small ``b``) land on
    ranks where ``sigma`` — and with sigma-shaped keys the actual
    coordinates — are small; allocating against the spectrum
    (reversed bits) must measurably hurt."""
    R, T = 8, 32
    sigma = np.array([5, 3, 2, 1, .5, .2, .1, .05])
    bits = svdq_bits_from_spectrum(sigma)
    rng = np.random.default_rng(7)
    k = jnp.asarray(rng.normal(size=(T, R)) * sigma, jnp.float32)
    v = jnp.asarray(rng.normal(size=(T, R)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(R,)) * sigma, jnp.float32)
    scale = 0.3

    def attend(keys):
        w = jax.nn.softmax(scale * (np.asarray(keys) @ np.asarray(q)))
        return w @ np.asarray(v)

    lay = SvdqLayout(bits)
    enc = lay.encode("k", k)
    k_hat = np.asarray(lay.decode("k", enc, R), np.float32)
    err = np.max(np.abs(attend(k_hat) - attend(k)))

    s = np.asarray(enc["kscale"], np.float32)                # (T, 1)
    dk_bound = (np.abs(np.asarray(q)) * s
                * _step_widths(bits)).sum(axis=-1)           # (T,)
    bound = 2.0 * scale * dk_bound.max() * np.abs(np.asarray(v)).max()
    assert err <= bound, (err, bound)

    # element-wise key error is itself spectrum-bounded: each rank's
    # deviation is within its step of a sigma-sized coordinate
    assert np.all(np.abs(k_hat - np.asarray(k)).max(axis=0)
                  <= s.max() * _step_widths(bits) + 1e-7)

    # misallocate by reversing the rank axis under the same ladder:
    # wide steps land on the high-energy head of the spectrum
    k_flip = k[..., ::-1]
    k_rev = np.asarray(lay.decode("k", lay.encode("k", k_flip), R),
                       np.float32)[..., ::-1]
    err_rev = np.max(np.abs(attend(k_rev) - attend(k)))
    assert err < err_rev, (err, err_rev)


# ---------------------------------------------------------------------------
# Engine: scale pools through COW forks, swap, sharing
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def setup():
    cfg = dropless(get_config("tinyllama-1.1b").reduced())
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
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
    return cfg, model, params, proj


QUANT_SC = dict(max_seq_len=32, max_batch=2, temperature=0.0,
                decode_chunk=4, paged=True, page_size=4,
                chunked_prefill=True, prefill_chunk=8,
                cache_quant="int8", audit=True)


def _reqs(cfg, lens, seed=5, max_new=4, common=0):
    rng = np.random.default_rng(seed)
    pre = rng.integers(0, cfg.vocab_size, common).astype(np.int32)
    return [Request(rid=i,
                    prompt=np.concatenate(
                        [pre, rng.integers(0, cfg.vocab_size,
                                           n).astype(np.int32)]),
                    max_new_tokens=max_new)
            for i, n in enumerate(lens)]


def test_cow_fork_copies_scale_pools_byte_exact(setup):
    """``_fork_page`` moves *every* layout leaf — int8 data pages and
    their bf16 scale pools — so the forked row is byte-identical to
    the source across the whole cache tree."""
    cfg, model, params, proj = setup
    eng = ServingEngine(cfg, params, ServeConfig(**QUANT_SC),
                        projections=proj)
    eng.generate(_reqs(cfg, [9, 7]))
    src, dst = np.int32(1), np.int32(eng.pool.n_pages)
    forked = eng._fork_page(eng._cache, src, dst)
    leaves = [("prefix", lf, name, arr)
              for lf, layer in enumerate(eng._cache["prefix"])
              for name, arr in layer.items()]
    saw_scale = False
    for where, lf, name, arr in leaves:
        new = forked["prefix"][lf][name]
        saw_scale |= name.endswith("scale")
        assert np.array_equal(np.asarray(new[dst]), np.asarray(arr[src])), \
            (where, lf, name)
    if eng._cache["steps"] is not None:
        for j, layer in enumerate(eng._cache["steps"]["layers"]):
            for name, arr in layer.items():
                new = forked["steps"]["layers"][j][name]
                saw_scale |= name.endswith("scale")
                assert np.array_equal(np.asarray(new[:, dst]),
                                      np.asarray(arr[:, src])), (j, name)
    assert saw_scale         # the int8 layout actually took effect


def test_shared_prefix_int8_matches_unshared(setup):
    """Prefix sharing + COW over int8 pages: same greedy outputs as
    the unshared int8 engine, with pages actually shared (audits on
    every step via ``audit=True``)."""
    cfg, model, params, proj = setup
    lens, common = [3, 4, 2, 3], 12
    base = ServingEngine(cfg, params, ServeConfig(**QUANT_SC),
                         projections=proj)
    r0 = _reqs(cfg, lens, common=common)
    base.generate(r0)
    eng = ServingEngine(cfg, params,
                        ServeConfig(**QUANT_SC, share_prefix=True),
                        projections=proj)
    r1 = _reqs(cfg, lens, common=common)
    eng.generate(r1)
    assert [r.out_tokens for r in r1] == [r.out_tokens for r in r0]
    assert eng.n_shared_pages > 0


def test_corrupted_swap_scale_bytes_degrade_to_recompute(setup):
    """A swapped slot whose host buffers (data *and* scale leaves ride
    the same checksum) are corrupted must fail verification on
    swap-in and fall back to recompute — greedy outputs unchanged."""
    cfg, model, params, proj = setup
    # n_pages is an fp-unit HBM budget: the int8 layout's capacity
    # multiplier (64/36 at rk=rv=16) turns 6 fp pages into 10 physical
    # pages — exactly tight enough that three 14-token requests still
    # oversubscribe and swap
    sc_kw = dict(QUANT_SC, n_pages=6, admission="optimistic",
                 preempt_mode="swap", watermark_low=0.1)
    lens, max_new = [14, 13, 14], 8
    base = ServingEngine(cfg, params, ServeConfig(**sc_kw),
                         projections=proj)
    r0 = _reqs(cfg, lens, max_new=max_new)
    base.generate(r0)
    assert base.n_swapped_out > 0          # the pool does oversubscribe

    inj = FaultInjector(seed=0).add("swap_corrupt", nth=1)
    eng = ServingEngine(cfg, params, ServeConfig(**sc_kw),
                        projections=proj, faults=inj)
    r1 = _reqs(cfg, lens, max_new=max_new)
    eng.generate(r1)
    assert eng.n_swap_fallbacks > 0        # checksum caught the flip
    assert [r.out_tokens for r in r1] == [r.out_tokens for r in r0]


# ---------------------------------------------------------------------------
# Per-step dynamic split derivation (decode_splits=0)
# ---------------------------------------------------------------------------


def test_dynamic_splits_snap_to_pow2():
    """``decode_splits=0`` derives the split count per step from the
    live max length, snapped down to {1, 2, 4, 8} — monotone in the
    length, so a drain walks at most 4 compiled decode variants."""
    cfg = dropless(get_config("tinyllama-1.1b").reduced())
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sc = ServeConfig(max_seq_len=512, max_batch=2, temperature=0.0,
                     paged=True, page_size=4, chunked_prefill=True,
                     prefill_chunk=8, decode_splits=0)
    eng = ServingEngine(cfg, params, sc)
    assert eng._dynamic_splits
    seen = [eng._splits_for_step(n) for n in range(1, 513, 7)]
    assert set(seen) <= {1, 2, 4, 8}
    assert seen == sorted(seen)            # monotone in live length
    assert eng._splits_for_step(512) == 8


def test_dynamic_splits_bounded_compile_count():
    """Draining requests across length regimes under decode_splits=0
    compiles at most one decode variant per snapped split count."""
    cfg = dropless(get_config("tinyllama-1.1b").reduced())
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sc = ServeConfig(max_seq_len=64, max_batch=2, temperature=0.0,
                     decode_chunk=4, paged=True, page_size=4,
                     chunked_prefill=True, prefill_chunk=8,
                     decode_splits=0)
    eng = ServingEngine(cfg, params, sc)
    rng_ = np.random.default_rng(9)
    for L, n in ((3, 4), (20, 8), (40, 16)):
        reqs = [Request(rid=i,
                        prompt=rng_.integers(0, cfg.vocab_size,
                                             L).astype(np.int32),
                        max_new_tokens=n) for i in range(2)]
        eng.generate(reqs)
        assert all(r.done and not r.failed for r in reqs)
    assert 1 <= eng._decode_chunk._cache_size() <= 4

    # splits=1 parity: the dynamic engine's outputs match a fixed
    # unsplit engine on the same requests
    fixed = ServingEngine(cfg, params,
                          dataclasses.replace(sc, decode_splits=1))
    rng_ = np.random.default_rng(9)
    for L, n in ((3, 4), (20, 8), (40, 16)):
        prompts = [rng_.integers(0, cfg.vocab_size, L).astype(np.int32)
                   for _ in range(2)]
        ra = [Request(rid=i, prompt=p, max_new_tokens=n)
              for i, p in enumerate(prompts)]
        rb = [Request(rid=i, prompt=p, max_new_tokens=n)
              for i, p in enumerate(prompts)]
        eng.generate(ra)
        fixed.generate(rb)
        assert [r.out_tokens for r in ra] == [r.out_tokens for r in rb]
