"""The traffic generator repeats exactly for a seed, and every seed asks
the same work in the same order."""
import numpy as np
import pytest

from bench import traffic

MIXES = ["azure-conv", "sharegpt"]
BIG = 2 ** 33 + 12345          # seeds reach past 32 signed bits


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_backlog(mix):
    m = traffic.load(mix)
    a = traffic.generate(m, BIG, 32000)
    b = traffic.generate(m, BIG, 32000)
    assert len(a) == m["backlog"]
    for (pa, oa), (pb, ob) in zip(a, b):
        assert oa == ob and np.array_equal(pa, pb)


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_asks_the_same_work(mix):
    m = traffic.load(mix)
    a = traffic.generate(m, 1, 32000)
    b = traffic.generate(m, 2, 32000)
    assert [(len(p), o) for p, o in a] == [(len(p), o) for p, o in b]
    assert not np.array_equal(a[0][0], b[0][0])     # other token ids
    n = m["strata"]
    assert [(len(p), o) for p, o in a[:n]] == traffic.block(m)
    assert [(len(p), o) for p, o in a[n: 2 * n]] == traffic.block(m)


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_inside_the_law(mix):
    m = traffic.load(mix)
    for p, o in traffic.generate(m, 7, 100):
        assert m["prompt"]["min"] <= len(p) <= m["prompt"]["max"]
        assert m["output"]["min"] <= o <= m["output"]["max"]
        assert p.min() >= 0 and p.max() < 100


@pytest.mark.parametrize("mix", MIXES)
def test_block_prefixes_spread_over_both_laws(mix):
    """Any four consecutive requests of a block hold a prompt and an
    answer from each half of their laws."""
    m = traffic.load(mix)
    blk = traffic.block(m) * 2
    n = m["strata"]
    p_med = np.median([p for p, _ in blk[:n]])
    o_med = np.median([o for _, o in blk[:n]])
    for i in range(n):
        run = blk[i: i + 4]
        assert min(p for p, _ in run) < p_med < max(p for p, _ in run)
        assert min(o for _, o in run) < o_med < max(o for _, o in run)


@pytest.mark.parametrize("mix", MIXES)
def test_mix_names_its_source(mix):
    m = traffic.load(mix)
    assert m["source"].strip()
    for what in ("prompt.median", "output.median", "sigma", "max"):
        assert any(what in k for k in m["derivation"]), what


def test_order_is_a_permutation_with_spread_prefixes():
    for n in (4, 16, 64):
        for step in traffic.R2:
            o = traffic.order(n, step)
            assert sorted(o) == list(range(n))
            half = o[: n // 2]
            assert abs((half < n // 2).sum() - n // 4) <= 2


def test_strata_are_quantiles():
    law = {"median": 100, "sigma": 0.5, "min": 1, "max": 10 ** 6}
    x = traffic.strata_lengths(law, 5)
    assert x[2] == 100                               # the median stratum
    assert list(x) == sorted(x) and x[0] < 100 < x[-1]
    assert x[0] * x[-1] == pytest.approx(100 ** 2, rel=0.02)  # symmetric
