"""MiMo-V2-Flash-shaped serving on CPU: window and full attention kinds
with different KV head counts, sinks, partial RoPE, window page rings,
and the dropless held-experts layer, checked against the benchmark's
plain float32 reference (``bench/configs/mimo_reference.py``) at test
widths (``bench/tests/tiny_mimo.py``)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.configs import mimo_program as prog
from bench.configs import mimo_reference as ref
from bench.tests import tiny_mimo
from repro.config import ServeConfig
from repro.configs import get_config
from repro.kernels.kq_decode.paged import kq_window_paged_attention
from repro.models import build_model
from repro.models.attention import window_attention
from repro.models.blocks import step_layout
from repro.models.moe import held_moe_ffn
from repro.serving import Request, ServingEngine
from repro.serving.paged_cache import ring_pages, ring_positions

#: engine logits against the float32 reference with float32 weights:
#: the two differ only in summation order (measured 3e-6 on logits of
#: magnitude ~4); bfloat16 anywhere on the path moves them by 3e-2
LOGIT_TOL = 1e-4


def _setup(n_layers=6, n_held=4, seed=0):
    conf = tiny_mimo.conf(n_layers=n_layers, n_held=n_held)
    d = ref.Dims.of(conf)
    w = jax.jit(lambda k: ref.init_weights(d, k))(jax.random.PRNGKey(seed))
    # the benchmark's selection bias is zero (balanced load): a drawn one
    # here, so that the program's bias path meets the reference's
    for i in d.moe_layers:
        w[f"bias.{i}"] = (0.3 * jax.random.normal(
            jax.random.PRNGKey(seed + i), w[f"bias.{i}"].shape)
                          ).astype(w[f"bias.{i}"].dtype)
    rng = np.random.default_rng(seed)
    grams = None
    for _ in range(2):
        g = ref.calibration_grams(
            d, w, jnp.asarray(rng.integers(0, d.vocab, 64), jnp.int32))
        grams = g if grams is None else tuple(a + b
                                              for a, b in zip(grams, g))
    proj = ref.solve_kqsvd(d, grams, np.asarray(w["wo"]), 12, 8)
    return conf, d, w, proj


def _engine(conf, w, proj, **serve):
    # audited every step: ring columns and ring pools included
    sc = ServeConfig(**{**dict(max_seq_len=96, max_batch=2, page_size=8,
                                prefill_chunk=32, decode_chunk=1,
                                audit=True), **serve})
    params = jax.tree.map(lambda a: a.astype(jnp.float32), prog.params(w))
    return ServingEngine(prog.model_config(conf), params, sc,
                         projections=prog.projections(proj))


def _ref_logits(d, w, proj, seq):
    pad = -(-len(seq) // ref.Q_BLOCK) * ref.Q_BLOCK
    toks = jnp.asarray(np.pad(seq, (0, pad - len(seq))), jnp.int32)
    hid, _ = ref.hidden(d, w, {k: jnp.asarray(v) for k, v in proj.items()},
                        toks, "f32")
    return np.asarray(hid @ w["lm_head"].astype(jnp.float32))


@pytest.mark.parametrize("n_layers,kernels", [
    pytest.param(6, False, id="6l-lax"),
    pytest.param(6, True, id="6l-kernels"),
    pytest.param(18, False, id="18l-scanned")])
def test_engine_matches_reference(n_layers, kernels, monkeypatch):
    """Chunked prefill then cached decode through the engine, logits at
    every decoded position against the reference's full forward: prompts
    longer than the window and than a chunk (chunks of 32 over a window
    of 16), decode past the ring's wrap (3 pages of 8), a slot reused by
    a later request; 6 layers unrolled, 18 with two scanned steps of six
    sublayers (both kinds in one step)."""
    if kernels:
        import repro.kernels
        monkeypatch.setattr(repro.kernels, "use_kernels", lambda: True)
    conf, d, w, proj = _setup(n_layers)
    eng = _engine(conf, w, proj)
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, max_new_tokens=n,
                    prompt=rng.integers(0, d.vocab, p).astype(np.int32))
            for i, (p, n) in enumerate([(40, 30), (23, 12), (57, 9)])]
    seen = {r.rid: [] for r in reqs}
    eng.start(reqs)
    while eng.step():
        for b, r in enumerate(eng._slot_req):
            if r is not None and not r.done and r.out_tokens:
                seen[r.rid].append((len(r.out_tokens),
                                    np.asarray(eng._logits[b])))
    for r in reqs:
        assert r.done and len(r.out_tokens) == r.max_new_tokens
        full = np.concatenate([r.prompt, np.asarray(r.out_tokens,
                                                    np.int32)])
        lg = _ref_logits(d, w, proj, full)
        P = len(r.prompt)
        assert int(lg[P - 1].argmax()) == r.out_tokens[0]
        assert len(seen[r.rid]) == r.max_new_tokens - 1
        for k, got in seen[r.rid]:
            np.testing.assert_allclose(got, lg[P + k - 1], rtol=0,
                                       atol=LOGIT_TOL)


def _moe_inputs(n_held, n_experts=16, N=24, seed=0):
    conf = tiny_mimo.conf(n_held=n_held, n_experts=n_experts)
    cfg = prog.model_config(conf)
    from repro.models.moe import init_moe
    p = init_moe(jax.random.PRNGKey(seed), cfg.d_model, cfg.moe,
                 jnp.float32, cfg.routing)
    p["bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                        p["bias"].shape)
    x = jax.random.normal(jax.random.PRNGKey(seed + 2), (2, N // 2,
                                                         cfg.d_model))
    return cfg, p, x


def test_expert_shares_sum_to_uncut_layer():
    """Four devices holding experts 0-3, 4-7, 8-11 and 12-15 add up to
    the layer that holds all 16; and the share of experts 0-3 is the
    reference's."""
    cfg, p, x = _moe_inputs(n_held=16)
    whole, aux = held_moe_ffn(p, x, cfg.moe, cfg.routing)
    assert float(aux["held_pairs"]) == x.shape[0] * x.shape[1] * 4
    parts = []
    for lo in range(0, 16, 4):
        rt = dataclasses.replace(cfg.routing, held=(lo, lo + 4))
        pe = dict(p, **{k: p[k][lo:lo + 4] for k in ("wi_e", "wg_e",
                                                     "wo_e")})
        parts.append(held_moe_ffn(pe, x, cfg.moe, rt)[0])
    # float32 sums of the same products in another order
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=0, atol=1e-5)
    d = ref.Dims.of(tiny_mimo.conf(n_held=4))
    lw = {"router": p["router"], "bias": p["bias"],
          **{k: p[k][:4] for k in ("wi_e", "wg_e", "wo_e")}}
    with jax.default_matmul_precision("highest"):
        want = ref._experts(d, x.reshape(-1, x.shape[-1]), lw, "f32")[0]
    # the reference computes every held expert densely at full precision
    np.testing.assert_allclose(np.asarray(parts[0]).reshape(want.shape),
                               np.asarray(want), rtol=0, atol=1e-5)


@pytest.mark.parametrize("skew", ["one", "all"])
def test_skewed_router_loses_no_token(skew):
    """A selection bias that sends every token to held expert 1 (or all
    of its 4 choices to the 4 held experts): every pair is computed, as
    a per-token loop over the chosen experts computes it."""
    cfg, p, x = _moe_inputs(n_held=4, N=64)
    hot = [1] if skew == "one" else [0, 1, 2, 3]
    p["bias"] = p["bias"].at[jnp.asarray(hot)].add(100.0)
    y, aux = held_moe_ffn(p, x, cfg.moe, cfg.routing)
    N = x.shape[0] * x.shape[1]
    from repro.models.moe import route
    xf = x.reshape(N, -1)
    idx, wts = route(p, xf, cfg.moe)
    assert (np.asarray(idx)[:, :len(hot)] < 4).all()
    assert float(aux["held_pairs"]) == int((np.asarray(idx) < 4).sum())
    want = np.zeros((N, cfg.d_model), np.float32)
    for n in range(N):
        for e, wt in zip(np.asarray(idx[n]), np.asarray(wts[n])):
            if e < 4:
                a = xf[n] @ p["wi_e"][e]
                g = xf[n] @ p["wg_e"][e]
                want[n] += float(wt) * np.asarray(
                    (jax.nn.silu(g) * a) @ p["wo_e"][e])
    # float32, the same products summed in another order (outputs ~1)
    np.testing.assert_allclose(np.asarray(y).reshape(N, -1), want,
                               rtol=0, atol=1e-4)


def test_held_pairs_counter_rides_the_token_stream():
    """With every expert held, each decode step routes all 4 choices of
    every live token in each of the 5 expert layers, to 4 experts."""
    conf, d, w, proj = _setup(n_held=16)
    conf["published"]["n_routed_experts"] = 16
    eng = _engine(conf, w, proj, decode_chunk=4)
    r = Request(rid=0, prompt=np.arange(20, dtype=np.int32),
                max_new_tokens=11)
    eng.generate([r])
    assert eng.n_decode_steps == 10          # the last token feeds none
    assert eng.n_held_pairs == eng.n_decode_steps * 4 * 5
    assert eng.n_held_experts == eng.n_decode_steps * 4 * 5


def test_window_state_is_bounded_by_the_window():
    """The window layers' pools hold a 3-page ring per slot whatever the
    context; the full layers' pools grow with it."""
    conf, d, w, proj = _setup()
    shapes = {}
    for T in (64, 512):
        eng = _engine(conf, w, proj, max_seq_len=T)
        eng.start([])
        c = eng._cache["prefix"]
        shapes[T] = (c[1]["kc"].shape, c[0]["kc"].shape)
        assert eng._btabs.rows.shape == (2, T // 8 + 3)
    (win64, full64), (win512, full512) = shapes[64], shapes[512]
    assert win64 == win512 == (1 + 2 * 3, 2, 8, 12)
    assert full512[0] > full64[0]


@pytest.mark.parametrize("sink", [False, True])
def test_window_kernel_matches_lax(sink):
    """``kq_window_paged_attention`` (interpret mode) against the lax
    window attention over the same rings, at lengths before, at and past
    the ring's wrap."""
    rng = np.random.default_rng(0)
    B, Hkv, m, ps, R, W = 6, 2, 3, 8, 16, 16
    ring = ring_pages(W, ps)
    P = 1 + B * ring
    kp = jnp.asarray(rng.normal(size=(P, Hkv, ps, R)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, Hkv, ps, R)), jnp.float32)
    table = jnp.asarray(1 + np.arange(B)[:, None] * ring
                        + np.arange(ring)[None], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, Hkv * m, R)), jnp.float32)
    lengths = jnp.asarray([1, 5, 16, 17, 23, 40], jnp.int32)
    sk = jnp.asarray(rng.normal(size=(Hkv * m,)), jnp.float32) if sink \
        else None
    got = kq_window_paged_attention(q, kp, vp, lengths, table, window=W,
                                    scale=0.25, sink=sk, interpret=True)
    n = ring * ps
    kpos = ring_positions(lengths - 1, n)
    from repro.serving.paged_cache import gather_ring
    want = window_attention(q.reshape(B, Hkv, m, 1, R),
                            gather_ring(kp, table), gather_ring(vp, table),
                            kpos, kpos >= 0, (lengths - 1)[:, None], W,
                            0.25, sk)
    # float32 online softmax over pages against one softmax over the
    # ring: the same terms, rescaled in another order
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want).reshape(B, Hkv * m, R),
                               rtol=1e-5, atol=1e-5)


def test_window_kernel_lane_padded_path():
    """The path a TPU takes (ranks padded to 128 lanes on the call, the
    output sliced back) reads what the unpadded kernel reads."""
    rng = np.random.default_rng(1)
    B, Hkv, m, ps, W = 3, 2, 2, 8, 16
    ring = ring_pages(W, ps)
    P = 1 + B * ring
    kp = jnp.asarray(rng.normal(size=(P, Hkv, ps, 12)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, Hkv, ps, 8)), jnp.float32)
    table = jnp.asarray(1 + np.arange(B)[:, None] * ring
                        + np.arange(ring)[None], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, Hkv * m, 12)), jnp.float32)
    lengths = jnp.asarray([3, 16, 29], jnp.int32)
    sk = jnp.asarray(rng.normal(size=(Hkv * m,)), jnp.float32)
    args = (q, kp, vp, lengths, table)
    kw = dict(window=W, scale=0.25, sink=sk, interpret=True)
    padded = kq_window_paged_attention(*args, pad_lanes=True, **kw)
    plain = kq_window_paged_attention(*args, pad_lanes=False, **kw)
    assert padded.shape == plain.shape == (B, Hkv * m, 8)
    # zero lanes add nothing to a score or a weighted sum
    np.testing.assert_allclose(np.asarray(padded), np.asarray(plain),
                               rtol=1e-6, atol=1e-6)


def test_step_layout_of_the_published_pattern():
    """The irregular first period runs unrolled, the rest scans; a stack
    of one period or less is unrolled whole."""
    cfg = get_config("mimo-v2-flash")
    prefix, steps = step_layout(cfg)
    assert prefix == list(range(6)) and len(steps) == 7
    assert [cfg.attn_spec(i).window for i in steps[0]] == [128] * 5 + [0]
    prefix, steps = step_layout(dataclasses.replace(cfg, n_layers=12))
    assert prefix == list(range(12)) and steps == []
    assert build_model(get_config("mimo-v2-flash").reduced()).steps == []


@pytest.mark.parametrize("bad", [dict(share_prefix=True),
                                 dict(preempt_mode="swap"),
                                 dict(cache_quant="int8")])
def test_unsupported_serving_modes_are_refused(bad):
    conf, d, w, proj = _setup()
    with pytest.raises(NotImplementedError, match="window rings"):
        _engine(conf, w, proj, **bad)


def test_prefill_kernel_splits_a_wide_group(monkeypatch):
    """A group with more query rows than ``PREFILL_ROWS`` runs as calls
    over slices of its query heads, with the outputs of one call."""
    import repro.kernels.kq_decode.paged as paged
    rng = np.random.default_rng(0)
    B, H, Hkv, S, ps, R = 1, 8, 2, 8, 8, 16
    P, npp = 6, 5
    q = jnp.asarray(rng.normal(size=(B, H, S, R)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, Hkv, ps, R)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, Hkv, ps, R)), jnp.float32)
    table = jnp.asarray([[1, 2, 3, 4, 5]], jnp.int32)
    args = (q, kp, vp, jnp.asarray([13]), jnp.asarray([5]), table)
    whole = paged.kq_prefill_paged_attention(*args, scale=0.3,
                                             interpret=True)
    monkeypatch.setattr(paged, "PREFILL_ROWS", 8)       # 4 x 8 rows: 4
    split = paged.kq_prefill_paged_attention(*args, scale=0.3,
                                             interpret=True)
    # each query row's arithmetic is the same in both: only the grouping
    # of rows into programs differs
    np.testing.assert_allclose(np.asarray(split), np.asarray(whole),
                               rtol=0, atol=1e-6)
