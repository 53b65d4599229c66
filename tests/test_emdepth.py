"""Batched EM kernel vs the reference golden vectors and the sequential
oracle, plus streaming CNV merge tests."""

import numpy as np
import pytest

from goleft_tpu.models import emdepth as em
import oracle_emdepth as oracle


GOLDEN = [
    # (depths, expected CN) from emdepth_test.go:11-38
    ([1, 8, 33, 34, 35, 37, 31, 22, 66], [0, 1, 2, 2, 2, 2, 2, 2, 4]),
    ([30, 28, 33, 34, 35, 37, 31, 22, 38], [2] * 9),
    ([296.6, 16.7, 17.0, 3019.2, 14.4, 16.5, 14.2, 26, 7],
     [8, 2, 2, 8, 2, 2, 2, 3, 1]),
]


@pytest.mark.parametrize("depths,expected", GOLDEN)
def test_golden_cn(depths, expected):
    d = np.asarray(depths, dtype=np.float64)[None]
    lam = np.asarray(em.em_depth_batch(d))
    cns = np.asarray(em.cn_batch(lam, d))[0]
    assert list(cns) == expected


def test_lambda_matches_oracle():
    rng = np.random.default_rng(0)
    for _ in range(20):
        n = int(rng.integers(4, 40))
        d = rng.gamma(5, 6, size=n).astype(np.float64)
        # sprinkle outliers and zeros
        if rng.random() < 0.5:
            d[0] *= 10
        if rng.random() < 0.3:
            d[-1] = 0
        lam_o = oracle.em_depth(d)
        lam_k = np.asarray(em.em_depth_batch(d[None]))[0]
        np.testing.assert_allclose(lam_k, lam_o, rtol=1e-9, atol=1e-9)


def test_cn_matches_oracle_batch():
    rng = np.random.default_rng(1)
    B, S = 50, 24
    depths = rng.gamma(5, 6, size=(B, S))
    depths[rng.random((B, S)) < 0.05] *= 8  # dups
    depths[rng.random((B, S)) < 0.05] /= 4  # dels
    lam = np.asarray(em.em_depth_batch(depths))
    cns = np.asarray(em.cn_batch(lam, depths))
    for b in range(B):
        want = [min(c, em.MAX_CN) for c in oracle.cns(depths[b])]
        assert list(cns[b]) == want, b


def test_same_golden():
    # emdepth_test.go:40-53
    v1 = np.array([296.6, 16.7, 17.0, 3019.2, 14.4, 16.5, 14.2, 26, 7])
    v2 = np.array([96.6, 16.7, 17.0, 319.2, 14.4, 16.5, 14.2, 7, 16])
    e1 = em.em_depth(v1)
    e2 = em.em_depth(v2)
    non2, changed, pct = e2.same(e1)
    assert pct == pytest.approx(7.0 / 9.0)
    assert non2 == [0, 3]
    assert changed == [7, 8]


def same_by_loop(ee, oo, n):
    """``EMD.same`` as the reference writes it, a sample at a time
    (emdepth.go:227-247): the oracle for the array comparisons."""
    non2, changed = [], []
    n_same = 0
    for i in range(len(ee)):
        if em.LOWER < ee[i] < em.UPPER and em.LOWER < oo[i] < em.UPPER:
            n_same += 1
        elif (oo[i] >= em.UPPER and ee[i] >= em.UPPER) or (
            oo[i] <= em.LOWER and ee[i] <= em.LOWER
        ):
            non2.append(i)
            n_same += 1
        else:
            changed.append(i)
    return non2, changed, n_same / n


def every_pair(values):
    """Two log2FC vectors that hold each ordered pair of ``values``."""
    ee, oo = np.meshgrid(np.asarray(values, np.float64),
                         np.asarray(values, np.float64))
    return [(ee.ravel(), oo.ravel())]


def edges():
    out = []
    for t in (em.LOWER, em.UPPER):
        out += [t, np.nextafter(t, -np.inf), np.nextafter(t, np.inf)]
    return out + [0.0]


def random_windows():
    rng = np.random.default_rng(39)
    pairs = []
    for _ in range(200):
        ee, oo = rng.normal(0, 0.7, size=(2, 2504))
        for v in (ee, oo):
            v[rng.random(2504) < 0.01] = np.nan
            v[rng.random(2504) < 0.01] = np.inf
            v[rng.random(2504) < 0.01] = -np.inf
        pairs.append((ee, oo))
    return pairs


SAME_CASES = {
    "thresholds-and-ulps": lambda: every_pair(edges()),
    "non-finite": lambda: every_pair([np.nan, np.inf, -np.inf, 0.0, -2.0,
                                      1.0]),
    "all-cn2": lambda: [(np.full(2504, 0.1),
                         np.linspace(-0.79, 0.39, 2504))],
    "all-aberrant": lambda: [
        (np.r_[np.full(1252, -1.0), np.full(1252, 0.4)],
         np.r_[np.full(1252, -np.inf), np.full(1252, 2.0)])],
    "one-sample": lambda: [(np.array([v]), np.array([w]))
                           for v in (-1.0, 0.0, 0.5, np.nan)
                           for w in (-0.8, 0.0, 0.4, -np.inf)],
    "random-2504": random_windows,
}


@pytest.mark.parametrize("case", list(SAME_CASES))
def test_same_agrees_with_the_scalar_loop(case):
    for ee, oo in SAME_CASES[case]():
        n = len(ee)
        mine = em.EMD(np.ones(9), np.ones(n), 0, 0, _l2=ee)
        other = em.EMD(np.ones(9), np.ones(n), 0, 0, _l2=oo)
        got = mine.same(other)
        assert got == same_by_loop(ee, oo, n)
        non2, changed, share = got
        assert type(share) is float
        assert all(type(i) is int for i in non2 + changed)


def test_cache_merges_cnvs():
    rng = np.random.default_rng(2)
    S = 10
    cache = em.Cache()
    out_all = []
    # windows of 1kb; sample 3 has a deletion in windows 5..9
    for w in range(30):
        d = rng.gamma(40, 0.8, size=S)
        if 5 <= w <= 9:
            d[3] *= 0.25
        e = em.em_depth(d, start=w * 1000, end=(w + 1) * 1000)
        out_all += cache.add(e)
    out_all += cache.clear(None)
    assert any(c.sample_i == 3 for c in out_all)
    c3 = next(c for c in out_all if c.sample_i == 3)
    # Cache.add registers a sample only when BOTH adjacent windows are
    # aberrant (emdepth.go:339), so the merged CNV starts one window in
    assert c3.positions[0][0] == 6000
    assert c3.positions[-1][1] == 10000
    assert all(cn < 2 for cn in c3.cn)
    assert all(fc <= -0.5 for fc in c3.log2fc)


def test_cache_gap_rule():
    rng = np.random.default_rng(3)
    S = 8
    cache = em.Cache()
    emitted = []
    # deletion at window 0 for sample 0, then long gap: the 30kb gap rule
    # must flush it once subsequent windows are far enough
    for w in range(6):
        d = rng.gamma(40, 0.8, size=S)
        if w == 0:
            d[0] *= 0.2
        start = w * 40_000
        e = em.em_depth(d, start=start, end=start + 1000)
        emitted += cache.add(e)
    assert any(c.sample_i == 0 for c in emitted)
