"""The EM and CN kernels against the per-window form they replaced.

``ref_em_depth_batch`` and ``ref_cn_batch`` below are the kernels as they
were written before the select-and-masked-sum form: ``vmap`` over one
window, λ read by per-element gathers, bins summed through a one-hot.
The batched kernels must give the same CN exactly and the same λ up to
the order of each row's sums; and their compiled programs must hold no
gather as large as the matrix, which is what made the old form slow on
the chip.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from goleft_tpu.models import emdepth as em

N = em.N_LAMBDA


# ------------------------------------------------ the reference kernels


def _ref_assign_bins(d, lam):
    idx = jnp.sum(lam[None, :] < d[:, None], axis=1)
    idx_hi = jnp.minimum(idx, N - 1)
    near_hi = jnp.abs(d - lam[idx_hi]) < jnp.abs(
        d - lam[jnp.maximum(idx - 1, 0)])
    pick = jnp.where(idx == 0, 0, jnp.where(
        idx >= N, N - 1,
        jnp.where(near_hi, idx_hi, jnp.maximum(idx - 1, 0))))
    pref2 = ((d > lam[1]) & (d < lam[3])
             & (jnp.abs(d - lam[2]) < jnp.abs(d - lam[1]))
             & (jnp.abs(d - lam[2]) < jnp.abs(d - lam[3])))
    return jnp.where(pref2, 2, pick)


def _ref_lam0(d):
    m = em._median32_even_quirk(d)
    i_arr = jnp.arange(N, dtype=d.dtype)
    return jnp.where(i_arr == 0, em.EPS * m,
                     jnp.where(i_arr == 2, m, m * (i_arr / 2) ** 1.1))


def _ref_em_one(d):
    dtype = d.dtype
    i_arr = jnp.arange(N, dtype=dtype)
    n = d.shape[0]

    def body(_, carry):
        lam, active = carry
        onehot = jax.nn.one_hot(_ref_assign_bins(d, lam), N, dtype=dtype)
        counts = onehot.sum(axis=0)
        sums = (onehot * d[:, None]).sum(axis=0)
        means = jnp.where(counts > 0, sums / jnp.maximum(counts, 1), 0.0)
        mid = jnp.arange(1, N - 1)
        fallback = jnp.sum(
            means[mid] * (2.0 / mid.astype(dtype)) * (counts[mid] / n))
        lam2 = jnp.where(means[2] != 0, means[2], fallback)
        new = jnp.where(i_arr == 0, lam[0], lam2 * i_arr / 2)
        span = new[2] - new[1]
        new = new.at[1].add(-span / 1.5).at[3].add(span / 1.5)
        diff = jnp.abs(new - lam)
        still = (diff.sum() > em.EPS) & (diff.max() > 0.5)
        return jnp.where(active, new, lam), active & still

    lam, _ = jax.lax.fori_loop(0, em.MAX_ITER, body,
                               (_ref_lam0(d), jnp.asarray(True)))
    return lam


ref_em_depth_batch = jax.jit(jax.vmap(_ref_em_one))


@jax.jit
def ref_cn_batch(lambdas, depths):
    def one(lam, d):
        idx = jnp.sum(lam[None, :] < d[:, None], axis=1)
        idx_hi = jnp.minimum(idx, N - 1)
        near_hi = jnp.abs(d - lam[idx_hi]) < jnp.abs(
            d - lam[jnp.maximum(idx - 1, 0)])
        cn = jnp.where(idx == 0, 0, jnp.where(
            idx >= N, em.MAX_CN,
            jnp.where(near_hi, idx_hi, jnp.maximum(idx - 1, 0))))
        dk = jnp.floor(0.5 + d)
        o = em._poisson_pmf(dk, lam[jnp.clip(cn, 0, N - 1)])
        o2 = em._poisson_pmf(dk, lam[2])
        return jnp.where((cn != 2) & (o * 0.9 < o2), 2, cn).astype(
            jnp.int32)

    return jax.vmap(one)(lambdas, depths)


# ------------------------------------------------------------- the cases


def _random(rng, b=96, s=40):
    d = rng.gamma(30, 1, size=(b, s))
    d[rng.random((b, s)) < 0.05] *= 2.5  # dups
    d[rng.random((b, s)) < 0.05] /= 4  # dels
    return d


def _bin2_empty_at_first():
    # even S: the quirk's median (100 + 300) / 2 lies between the data,
    # so no depth is nearest λ2 and the fallback mixes bins 1 and 3
    return np.array([[1.0, 1.0, 100.0, 300.0],
                     [2.0, 3.0, 110.0, 290.0]])


CASES = {
    "random": lambda rng: _random(rng),
    "random-wide": lambda rng: _random(rng, b=16, s=300),
    "some-zeros": lambda rng: np.where(rng.random((48, 40)) < 0.3, 0.0,
                                       _random(rng, 48, 40)),
    "bin2-empty": lambda rng: _bin2_empty_at_first(),
    "above-lambda8": lambda rng: np.concatenate(
        [_random(rng, 8, 30), np.full((8, 2), 1e4)], axis=1),
    "all-zero-window": lambda rng: np.vstack(
        [np.zeros((2, 24)), _random(rng, 2, 24)]),
    "one-sample": lambda rng: rng.gamma(30, 1, size=(12, 1)),
}


def _case(name, dtype):
    rng = np.random.default_rng(sorted(CASES).index(name))
    return CASES[name](rng).astype(dtype)


def test_the_crafted_cases_reach_their_branches():
    """The crafted rows do what their names say under the reference."""
    d = jnp.asarray(_case("bin2-empty", np.float64))
    for row in d:
        assert not np.any(np.asarray(
            _ref_assign_bins(row, _ref_lam0(row))) == 2)
    d = _case("above-lambda8", np.float64)
    lam = np.asarray(ref_em_depth_batch(d))
    assert np.all(d[:, -2:] > lam[:, -1:])
    assert np.all(np.asarray(ref_cn_batch(lam, d))[:, -2:] == em.MAX_CN)
    lam = np.asarray(ref_em_depth_batch(_case("all-zero-window",
                                              np.float64)))
    assert np.all(lam[:2] == 0)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernels_match_the_per_window_reference(case, dtype):
    d = _case(case, dtype)
    want_lam = np.asarray(ref_em_depth_batch(d))
    got_lam = np.asarray(em.em_depth_batch(d))
    assert got_lam.dtype == dtype
    # the one difference: the order of each row's float sums
    rtol = 1e-12 if dtype == np.float64 else 1e-5
    np.testing.assert_allclose(got_lam, want_lam, rtol=rtol, atol=0)
    # the select reads exactly what the gather read
    np.testing.assert_array_equal(np.asarray(em.cn_batch(want_lam, d)),
                                  np.asarray(ref_cn_batch(want_lam, d)))
    np.testing.assert_array_equal(np.asarray(em.cn_batch(got_lam, d)),
                                  np.asarray(ref_cn_batch(want_lam, d)))


# --------------------------------------------------- the compiled program

B, S = 64, 48


def _gather_sizes(hlo: str) -> list[int]:
    """Element counts of every gather's output in optimised HLO text."""
    sizes = []
    for line in hlo.splitlines():
        m = re.search(r"= \w+\[([\d,]*)\][^ ]* gather\(", line)
        if m:
            sizes.append(int(np.prod([int(x) for x in
                                      m.group(1).split(",") if x])))
    return sizes


def test_the_gather_parser_sees_a_per_element_gather():
    hlo = ref_cn_batch.lower(
        jax.ShapeDtypeStruct((B, N), jnp.float32),
        jax.ShapeDtypeStruct((B, S), jnp.float32)).compile().as_text()
    assert B * S in _gather_sizes(hlo)


@pytest.mark.parametrize("kernel", ["em_depth_batch", "cn_batch"])
def test_no_gather_as_large_as_the_matrix(kernel):
    d = jax.ShapeDtypeStruct((B, S), jnp.float32)
    args = (d,) if kernel == "em_depth_batch" else (
        jax.ShapeDtypeStruct((B, N), jnp.float32), d)
    hlo = getattr(em, kernel).lower(*args).compile().as_text()
    assert all(n < B * S for n in _gather_sizes(hlo)), kernel
