"""End-to-end: calibrate -> solve -> compressed decode (the paper's
serving path), including full-rank exactness and method ordering on real
model caches."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import dropless
from repro.config import CompressionConfig
from repro.configs import get_config
from repro.core.calibration import GramAccumulator, calibrate_model
from repro.core.compressed import cache_footprint
from repro.core.projections import Factors, solve_key
from repro.core.theory import score_error
from repro.models import build_model


def calibrated(arch, n_batches=3, rank=None):
    cfg = dropless(get_config(arch).reduced())
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    acc = GramAccumulator(len(model.attn_layers))
    for i in range(n_batches):
        toks = jax.random.randint(jax.random.PRNGKey(10 + i), (2, 32), 0,
                                  cfg.vocab_size)
        caps = model.calibrate(params, toks)
        acc.update_from_captures([jax.tree.map(np.asarray, c)
                                  for c in caps])
    return cfg, model, params, acc


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "deepseek-v2-lite-16b",
                                  "jamba-1.5-large-398b"])
def test_full_rank_compression_is_exact(arch):
    cfg, model, params, acc = calibrated(arch)
    full_rank = (32 if cfg.mla is not None else cfg.d_head)
    ccfg = CompressionConfig(method="kqsvd", rank_k=full_rank,
                             rank_v=full_rank)
    mp = acc.solve(ccfg, model.group_output_weights(params))
    proj = model.projections_pytree(mp, jnp.float32)
    B, S, extra = 2, 16, 4
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + extra), 0,
                              cfg.vocab_size)
    lr, cr = model.prefill(params, {"tokens": toks[:, :S]}, S + extra)
    lc, cc = model.prefill(params, {"tokens": toks[:, :S]}, S + extra,
                           proj=proj)
    np.testing.assert_allclose(np.asarray(lc), np.asarray(lr), rtol=2e-4,
                               atol=2e-4)
    for t in range(extra):
        tok = toks[:, S + t: S + t + 1]
        lr, cr = model.decode_step(params, cr, tok, jnp.int32(S + t))
        lc, cc = model.decode_step(params, cc, tok, jnp.int32(S + t),
                                   proj=proj)
        np.testing.assert_allclose(np.asarray(lc), np.asarray(lr),
                                   rtol=2e-4, atol=2e-4)


def test_method_ordering_on_model_caches():
    """On real captured caches: opt(kqsvd) <= eigen, ksvd (Thm 2/3)."""
    cfg, model, params, acc = calibrated("tinyllama-1.1b", n_batches=2)
    # build raw caches from a fresh capture for direct error evaluation
    toks = jax.random.randint(jax.random.PRNGKey(99), (2, 32), 0,
                              cfg.vocab_size)
    caps = model.calibrate(params, toks)
    cap = jax.tree.map(np.asarray, caps[0])
    g = 0
    m = cfg.n_heads // cfg.n_kv_heads
    K = cap["k"][:, g].reshape(-1, cfg.d_head)
    Q = cap["q"][:, g * m:(g + 1) * m].reshape(-1, cfg.d_head)
    R = cfg.d_head // 2
    errs = {}
    fk, fq = Factors.from_matrix(K), Factors.from_matrix(Q)
    for method in ("kqsvd", "ksvd", "eigen"):
        p = solve_key(method, fk, fq, R)
        errs[method] = score_error(K, Q, p)
    assert errs["kqsvd"] <= errs["ksvd"] + 1e-8
    assert errs["kqsvd"] <= errs["eigen"] + 1e-8


def test_compression_reduces_cache_footprint():
    fp = cache_footprint(n_kv_heads=8, d_head=128, rank_k=64, rank_v=64)
    assert fp.ratio == 0.5
    fp2 = cache_footprint(8, 128, 32, 32)
    assert fp2.ratio == 0.25


def test_calibrate_model_driver():
    cfg = dropless(get_config("smollm-360m").reduced())
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batches = [jax.random.randint(jax.random.PRNGKey(i), (2, 32), 0,
                                  cfg.vocab_size) for i in range(2)]
    mp = calibrate_model(model, params, batches,
                         CompressionConfig(method="kqsvd", epsilon=0.2))
    assert mp.a_k.shape[0] == len(model.attn_layers)
    assert all(r >= 1 for r in mp.ranks_k)


def test_int8_compressed_cache_close_to_bf16():
    """kqsvd+int8 decode stays near the unquantized compressed decode."""
    cfg, model, params, acc = calibrated("tinyllama-1.1b")
    ccfg = CompressionConfig(method="kqsvd", rank_k=cfg.d_head,
                             rank_v=cfg.d_head)
    mp = acc.solve(ccfg, model.group_output_weights(params))
    proj = model.projections_pytree(mp, jnp.float32)
    cfg8 = dataclasses.replace(cfg, cache_quant="int8")
    model8 = build_model(cfg8)
    B, S, extra = 2, 16, 4
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + extra), 0,
                              cfg.vocab_size)
    lr, cr = model.prefill(params, {"tokens": toks[:, :S]}, S + extra,
                           proj=proj)
    l8, c8 = model8.prefill(params, {"tokens": toks[:, :S]}, S + extra,
                            proj=proj)
    assert c8["steps"]["layers"][0]["kc"].dtype == jnp.int8
    np.testing.assert_allclose(np.asarray(l8), np.asarray(lr), rtol=0.1,
                               atol=0.15)
    for t in range(extra):
        tok = toks[:, S + t: S + t + 1]
        lr, cr = model.decode_step(params, cr, tok, jnp.int32(S + t),
                                   proj=proj)
        l8, c8 = model8.decode_step(params, c8, tok, jnp.int32(S + t),
                                    proj=proj)
        np.testing.assert_allclose(np.asarray(l8), np.asarray(lr),
                                   rtol=0.1, atol=0.2)


# ---------------------------------------------------------------------------
# Variable-length batched decode over compressed caches
# ---------------------------------------------------------------------------


def _compressed_varlen(cfg_xform=None, rtol=1e-4):
    """Per-sequence-position compressed decode == per-request decode."""
    from test_attention import merge_slot_caches
    cfg, model, params, acc = calibrated("tinyllama-1.1b", n_batches=2)
    ccfg = CompressionConfig(method="kqsvd", rank_k=cfg.d_head,
                             rank_v=cfg.d_head)
    mp = acc.solve(ccfg, model.group_output_weights(params))
    if cfg_xform is not None:
        cfg = cfg_xform(cfg)
    model = build_model(cfg)
    proj = model.projections_pytree(mp, jnp.float32)
    lens, extra = (6, 13, 9), 3
    B, T = len(lens), max(lens) + extra + 2
    toks = jax.random.randint(jax.random.PRNGKey(1),
                              (B, max(lens) + extra), 0, cfg.vocab_size)
    caches, singles = [], []
    for b, L in enumerate(lens):
        _, c1 = model.prefill(params, {"tokens": toks[b: b + 1, :L]}, T,
                              proj=proj)
        caches.append(c1)
        singles.append(c1)
    cache = merge_slot_caches(caches)
    pos = jnp.asarray(lens, jnp.int32)
    for t in range(extra):
        feed = jnp.stack([toks[b, lens[b] + t] for b in range(B)])[:, None]
        lg, cache = model.decode_step(params, cache, feed, pos + t,
                                      proj=proj)
        for b, L in enumerate(lens):
            lg1, singles[b] = model.decode_step(
                params, singles[b], feed[b: b + 1], jnp.int32(L + t),
                proj=proj)
            np.testing.assert_allclose(np.asarray(lg[b]),
                                       np.asarray(lg1[0]),
                                       rtol=rtol, atol=rtol)


def test_varlen_compressed_decode():
    _compressed_varlen()


def test_varlen_compressed_decode_int8():
    # looser: int8 rounding at quantization boundaries is sensitive to
    # batch-shape-dependent einsum tiling (1-ulp int8 flips)
    _compressed_varlen(
        cfg_xform=lambda c: dataclasses.replace(c, cache_quant="int8"),
        rtol=0.05)


def test_varlen_compressed_decode_pallas_kernel(tpu_kernels):
    """As on a TPU backend, compressed decode runs the lengths-aware
    Pallas kernel (interpret mode on CPU); outputs must match the lax
    path bit-for-tolerance."""
    _compressed_varlen()
