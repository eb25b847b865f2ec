"""The engine's programs update the cache in place (DESIGN.md
§paged-cache): every program that takes the cache donates it, and a
decode dispatch writes the entries of the tokens it decoded and nothing
else.  The compiled side (aliasing, no whole-pool copies) is checked for
a described v5e in ``test_tpu_compile.py``."""
import jax
import numpy as np
import pytest

from repro.config import ServeConfig
from repro.configs import get_config
from repro.core.calibration import ModelProjections
from repro.models import build_model
from repro.serving import Request, ServingEngine
from repro.serving.faults import FaultInjector


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("tinyllama-1.1b").reduced()
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def _projections(cfg, rank=8, seed=0):
    """Random KQ-SVD factors of the right shapes (the serving path does
    not care what they were solved from)."""
    rng = np.random.default_rng(seed)
    L, hkv, dh = cfg.n_layers, cfg.n_kv_heads, cfg.d_head
    m = cfg.n_heads // hkv

    def f(*shape):
        return (rng.normal(size=shape) / np.sqrt(shape[-2])).astype(
            np.float32)

    return ModelProjections(
        a_k=f(L, hkv, dh, rank), b_q=f(L, hkv, dh, rank),
        a_v=f(L, hkv, dh, rank), c_v=f(L, hkv, rank, m * cfg.d_model),
        ranks_k=[rank] * L, ranks_v=[rank] * L)


def _decoding(eng, cfg, n, seed=1):
    """Start ``n`` long requests and step until every one decodes."""
    rng = np.random.default_rng(seed)
    eng.start([Request(rid=i, prompt=rng.integers(
        0, cfg.vocab_size, 9 + i).astype(np.int32), max_new_tokens=20)
               for i in range(n)])
    while not all(r is not None and p is None
                  for r, p in zip(eng._slot_req, eng._prefilled)):
        eng.step()


def _written(eng, leaf_shape, pos0, pos1, rows):
    """Mask of the entries one decode dispatch may write in a stacked
    cache leaf: each slot's positions ``pos0 .. pos1 - 1`` in every layer
    and head, through its block-table row; the garbage page takes
    whatever the dead rows write."""
    mask = np.zeros(leaf_shape, bool)
    ps = eng.sc.page_size
    for b in range(len(pos0)):
        for p in range(pos0[b], pos1[b]):
            mask[:, rows[b, p // ps], :, p % ps] = True
    mask[:, 0] = True
    return mask


@pytest.mark.parametrize("quant", ["none", "int8"],
                         ids=["paged", "paged-int8"])
def test_decode_dispatch_writes_only_its_tokens(setup, quant):
    """One decode dispatch changes exactly the (layer, page, head,
    offset) entries of the tokens it decoded -- data pages and, for
    int8 pages, their scale pools alike -- and nothing else in any pool;
    the cache it was handed is donated (deleted)."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, ServeConfig(
        max_seq_len=48, max_batch=3, temperature=0.0, decode_chunk=4,
        page_size=4, prefill_chunk=8, cache_quant=quant),
        projections=_projections(cfg))
    _decoding(eng, cfg, 3)
    old = eng._cache
    # a host view of a buffer would keep it from being donated: copy
    # on the device first
    before = jax.tree.map(lambda x: np.asarray(x.copy()), old)
    pos0 = np.asarray(eng._pos).copy()
    eng.step()
    pos1 = np.asarray(eng._pos)
    assert (pos1 - pos0 == eng.sc.decode_chunk).all()
    assert all(x.is_deleted() for x in jax.tree.leaves(old))
    after = jax.tree.map(np.asarray, eng._cache)
    rows = eng._btabs.rows
    names = set()
    for layer_b, layer_a in zip(before["steps"]["layers"],
                                after["steps"]["layers"]):
        for name, b in layer_b.items():
            a = layer_a[name]
            mask = _written(eng, b.shape, pos0, pos1, rows)
            np.testing.assert_array_equal(a[~mask], b[~mask], err_msg=name)
            assert (a[mask] != b[mask]).any(), name
            names.add(name)
    expect = {"kc", "vc"} | ({"kscale", "vscale"} if quant == "int8"
                             else set())
    assert names == expect


# the chaos workload of test_faults.py: sharers that fork a partial page
# (copy_page), swap preemption over an 8-page pool, prefix reclaim
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
    specs = [(p0, 2), (p1, 2), (p2, 2), (p3, 2), (p1.copy(), 12),
             (fresh(14, 21), 12), (fresh(13, 22), 12), (fresh(14, 23), 12)]
    return [Request(rid=i, prompt=p, max_new_tokens=m)
            for i, (p, m) in enumerate(specs)]


def test_donated_cache_drains_through_swaps_forks_and_faults(setup):
    """Swap preemption (out and back in), a copy-on-write fork and an
    injected page-allocation fault read and replace the engine's cache
    between donated dispatches: the batch drains with the fault-free
    outputs, every step leaves a live cache, and every cache a step
    replaced was deleted by donation."""
    cfg, params = setup
    ref = _chaos_reqs(cfg)
    ServingEngine(cfg, params, ServeConfig(**CHAOS_SC)).generate(ref)
    inj = FaultInjector(seed=0).add("page_alloc", nth=2)
    eng = ServingEngine(cfg, params, ServeConfig(**CHAOS_SC, audit=True),
                        faults=inj)
    reqs = _chaos_reqs(cfg)
    eng.start(reqs)
    replaced = 0
    busy = True
    while busy:
        old = jax.tree.leaves(eng._cache)
        busy = eng.step()
        new = jax.tree.leaves(eng._cache)
        assert not any(x.is_deleted() for x in new)
        if new[0] is not old[0]:
            replaced += 1
            assert all(x.is_deleted() for x in old)
    assert replaced and eng.n_swapped_out and eng.n_swapped_in
    assert eng.n_cow_forks and inj.points_fired() == ("page_alloc",)
    for r, d in zip(reqs, ref):
        assert r.done and not r.failed and r.out_tokens == d.out_tokens
    assert eng.pool.free_count + eng._pindex.n_pinned == eng.pool.n_pages
