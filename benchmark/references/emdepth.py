"""The plain reference of ``emdepth``: what the program must write from a
depthwed-style matrix of integer window means (``#chrom start end
sample...``), the calls table on stdout and the ``--matrix-out`` CN matrix.

NumPy only, never through JAX or ``goleft_tpu``; a block of windows at a
time, every window of a block side by side. It follows upstream
``emdepth/emdepth.go`` (brentp/goleft v0.2.6) as
``goleft_tpu/models/emdepth.py``'s docstring cites it:

- the median of a window's depths with the even-length quirk: the mean of
  the two elements *above* the midpoint, ``s[n/2]`` and ``s[n/2+1]``
  (":25-28");
- lambda init: ``l0 = 0.01 m``, ``l2 = m``, ``li = m (i/2)^1.1`` (":129-138");
- binning: the nearest lambda (``idx`` = how many lambdas lie below the
  depth, the upper of the two neighbours where strictly nearer), except
  that a depth inside ``(l1, l3)`` strictly nearer ``l2`` than both goes to
  bin 2, the CN2 preference (":152-176");
- M-step: ``l2`` = the mean of bin 2, where that is 0 (an empty bin, or
  one of zeros) the occupancy-weighted mix of bins 1-7 scaled to CN2;
  ``li = l2 i/2``; ``l1``, ``l3`` widened by ``(l2 - l1)/1.5`` (":180-201");
- at most ``MAX_ITER`` 10 iterations, a window stopping once
  ``sum|dl| <= 0.01`` or ``max|dl| <= 0.5`` (":67,71-81");
- CN: the nearest lambda, then CN2 where ``pmf(k, l_cn) * 0.9 <
  pmf(k, l2)`` with ``k = floor(0.5 + depth)`` (":263-304").

The host stages follow ``commands/emdepth_cmd.py`` ``call_cnvs``: each
sample scaled by ``median(med) / med`` with a median of 0 taken as 1; the
streaming merge (":310-398") with ``same()``'s -0.80 / 0.40, a window kept
in a call where its log2FC lies outside (-0.5, 0.3), the 30 kb gap; a
call's CN ``int(round(median))`` and its log2FC the mean, printed ``%.3f``.

Departures from the Go, all the program's documented ones: a depth above
``l8`` gets CN 8 (``MAX_CN``) and goes through the Poisson tiebreak like
any CN but 2, where the Go returns ``len(Lambda)`` = 9 and skips it (the
intent its own test states, ``models/emdepth.py`` docstring); a log2FC is
``log2(depth / l2)`` in float64 from the float32 depth and lambda, so a
depth of 0 reads ``-inf`` and ``l2 = 0`` reads ``inf`` or ``nan``; the
bins' sums are accumulated in float64 and rounded to the working dtype
once (``np.bincount``), where the device sums in float32 in its own order.

``dtype`` is the precision of the EM, the CN and the Poisson pmfs:
float32 as the configuration states, float64 for the maker's
conditioning (``makers/em_matrix.py``). In every precision the depths are
the float32 ones the program's host normalises, and ``k`` the float32
``floor(0.5 + depth)``: program and reference share those bits, so what
the float64 run checks is the device's arithmetic. The maker watches the
decisions through ``watch``, the one argument here that is not the
answer's: an observer that ``em_lambdas`` and ``copy_numbers`` hand what
they decided on, and that changes nothing of what they return.

``break_guarantee`` names the controls, the reference with one statement
broken (``benchmark/control.py``).
"""

from __future__ import annotations

import concurrent.futures as cf
import math

import numpy as np

CONTROLS = ("bf16_depths", "textbook_even_median", "no_cn2_preference",
            "no_poisson_tiebreak", "one_em_iteration")
MAX_CN = 8
N_LAMBDA = MAX_CN + 1
MAX_ITER = 10
EPS = 0.01
LOWER, UPPER = -0.80, 0.40  # same(), emdepth.go:224-225
KEEP_LO, KEEP_HI = -0.5, 0.3  # makecnvs, emdepth.go:380
GAP = 30_000
TINY = 1e-30  # the program's floor under a Poisson mean's log
BLOCK = 256  # windows a block


def bf16(x: np.ndarray) -> np.ndarray:
    """float32 rounded to the nearest bfloat16, ties to even, as float32."""
    b = np.ascontiguousarray(x, np.float32).view(np.uint32)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.view(np.float32)


def medians(raw: np.ndarray) -> tuple[np.ndarray, float]:
    """Each sample's median over the windows (0 taken as 1) and the median
    of those: integers and halves, exact in any float."""
    med = np.median(raw.astype(np.float64), axis=0)
    med[med == 0] = 1.0
    return med, float(np.median(med))


def normalise(raw: np.ndarray, med: np.ndarray, medmed: float,
              dtype) -> np.ndarray:
    """``raw / med * medmed`` elementwise in ``dtype``, as the program's
    ``_norm_chunk`` does in float32."""
    d = raw.astype(dtype)
    np.divide(d, med.astype(dtype)[None, :], out=d)
    np.multiply(d, dtype(medmed), out=d)
    return d


def nearest(d, lam):
    """The nearest lambda's index: ``idx`` = how many lambdas lie below the
    depth, then the upper of the two neighbours where strictly nearer; 8
    above them all."""
    idx = (lam[:, None, :] < d[:, :, None]).sum(axis=2)
    hi = np.minimum(idx, N_LAMBDA - 1)
    lo = np.maximum(idx - 1, 0)
    near_hi = (np.abs(d - np.take_along_axis(lam, hi, axis=1))
               < np.abs(d - np.take_along_axis(lam, lo, axis=1)))
    return np.where(idx == 0, 0, np.where(
        idx >= N_LAMBDA, N_LAMBDA - 1, np.where(near_hi, hi, lo)))


def bins(d, lam, pref2=True):
    """The nearest lambda, but bin 2 for a depth inside (l1, l3) strictly
    nearer l2 than both (the CN2 preference)."""
    pick = nearest(d, lam)
    if not pref2:
        return pick
    l1, l2, l3 = lam[:, 1:2], lam[:, 2:3], lam[:, 3:4]
    a2 = np.abs(d - l2)
    pref = ((d > l1) & (d < l3) & (a2 < np.abs(d - l1))
            & (a2 < np.abs(d - l3)))
    return np.where(pref, 2, pick)


def em_lambdas(d: np.ndarray, quirk: bool = True, pref2: bool = True,
               max_iter: int = MAX_ITER, watch=None) -> np.ndarray:
    """(W,S) normalised depths -> (W,9) lambdas, in ``d``'s dtype;
    ``watch.em_step`` is shown each iteration's decisions."""
    dt = d.dtype.type
    W, n = d.shape
    s = np.sort(d, axis=1)
    if n % 2:
        m = s[:, n // 2]
    elif quirk:
        m = (s[:, n // 2] + s[:, n // 2 + 1]) / dt(2)
    else:
        m = (s[:, n // 2 - 1] + s[:, n // 2]) / dt(2)
    i = np.arange(N_LAMBDA, dtype=d.dtype)
    lam = m[:, None] * (i / dt(2)) ** dt(1.1)
    lam[:, 0] = dt(EPS) * m
    lam[:, 2] = m
    active = np.ones(W, bool)
    mid = np.arange(1, N_LAMBDA - 1)
    for it in range(max_iter):
        rows = np.flatnonzero(active)
        if not len(rows):
            break
        dd, ll = d[rows], lam[rows]
        got = bins(dd, ll, pref2)
        flat = (np.arange(len(rows))[:, None] * N_LAMBDA + got).ravel()
        counts = np.bincount(flat, minlength=len(rows) * N_LAMBDA
                             ).reshape(-1, N_LAMBDA).astype(d.dtype)
        sums = np.bincount(flat, weights=dd.ravel(),
                           minlength=len(rows) * N_LAMBDA
                           ).reshape(-1, N_LAMBDA).astype(d.dtype)
        means = np.where(counts > 0, sums / np.maximum(counts, dt(1)),
                         dt(0))
        l2_bin = means[:, 2]
        fallback = (means[:, mid] * (dt(2) / mid.astype(d.dtype))
                    * (counts[:, mid] / dt(n))).sum(axis=1)
        l2 = np.where(l2_bin != 0, l2_bin, fallback)
        new = l2[:, None] * i / dt(2)
        new[:, 0] = ll[:, 0]
        span = new[:, 2] - new[:, 1]
        new[:, 1] -= span / dt(1.5)
        new[:, 3] += span / dt(1.5)
        diff = np.abs(new - ll)
        total, top = diff.sum(axis=1), diff.max(axis=1)
        big, moved = total > dt(EPS), top > dt(0.5)
        if watch is not None:
            watch.em_step(rows, dd, ll, got, l2_bin, l2, new, total, top,
                          pref2)
        lam[rows] = new
        active[rows] = big & moved
    return lam


def lgamma_table(k_max: int) -> np.ndarray:
    return np.array([math.lgamma(k + 1.0) for k in range(k_max + 1)])


def poisson_pmf(k: np.ndarray, mu: np.ndarray, dt) -> np.ndarray:
    lg = lgamma_table(int(k.max()) if k.size else 0)[k].astype(dt)
    with np.errstate(under="ignore"):
        return np.exp(k.astype(dt) * np.log(np.maximum(mu, dt(TINY)))
                      - lg - mu)


def poisson_k(d: np.ndarray) -> np.ndarray:
    """``floor(0.5 + depth)`` of the float32 depth, in float32 as the
    device takes it: the same bits and one IEEE addition everywhere."""
    d32 = d.astype(np.float32)
    return np.floor(np.float32(0.5) + d32).astype(np.int64)


def copy_numbers(lam: np.ndarray, d: np.ndarray, tiebreak: bool = True,
                 watch=None) -> np.ndarray:
    """(W,S) int8 CN: the nearest lambda (8 above l8), then CN2 where the
    Poisson pmf at l2 beats 0.9 x the pmf at the CN's lambda;
    ``watch.copy_number`` is shown both decisions."""
    dt = d.dtype.type
    cn = nearest(d, lam)
    if not tiebreak:
        return cn.astype(np.int8)
    k = poisson_k(d)
    o = poisson_pmf(k, np.take_along_axis(lam, cn, axis=1), dt)
    o2 = poisson_pmf(k, lam[:, 2:3], dt)
    if watch is not None:
        watch.copy_number(d, lam, cn, k, o, o2)
    return np.where((cn != 2) & (o * dt(0.9) < o2), 2, cn).astype(np.int8)


def log2fc(d: np.ndarray, lam: np.ndarray) -> np.ndarray:
    """float64 ``log2(depth / l2)`` of the float depth and lambda, as the
    program's ``EMD.log2fc``."""
    with np.errstate(divide="ignore", invalid="ignore"):
        return np.log2(d.astype(np.float64)
                       / lam[:, 2:3].astype(np.float64))


def merge(fc: np.ndarray, cn: np.ndarray, starts, ends) -> list[tuple]:
    """The streaming merge of one contig (``Cache.add``/``clear`` and
    ``_make_cnv``): [(start, end, sample, CN, mean log2FC, last window)],
    in the order the program emits them."""
    calls: list[tuple] = []
    cnvs: dict[int, list[int]] = {}

    def clear(pos0):
        done = []
        for si, wins in cnvs.items():
            if pos0 - ends[wins[-1]] < GAP:
                continue
            kept = [w for w in wins
                    if not KEEP_LO < fc[w, si] < KEEP_HI]
            if kept:
                with np.errstate(invalid="ignore"):  # inf - inf: nan
                    mean = float(np.mean([float(fc[w, si]) for w in kept]))
                calls.append((starts[kept[0]], ends[kept[-1]], si,
                              int(round(np.median([int(cn[w, si])
                                                   for w in kept]))),
                              mean, kept[-1]))
            done.append(si)
        for si in done:
            del cnvs[si]

    low, high = fc <= LOWER, fc >= UPPER
    for w in range(len(fc)):
        last = w - 1 if w else 0
        clear(starts[w])
        non2 = np.flatnonzero((high[last] & high[w]) | (low[last] & low[w]))
        for si in non2.tolist():
            cnvs.setdefault(si, []).append(w)
    if len(fc):
        clear(starts[-1] + 100_000)
    return calls


def calls_text(chrom: str, calls: list[tuple], samples: list[str]) -> str:
    out = ["#chrom\tstart\tend\tsample\tCN\tlog2FC\n"]
    out += [f"{chrom}\t{s}\t{e}\t{samples[si]}\t{c}\t{f:.3f}\n"
            for s, e, si, c, f, _ in calls]
    return "".join(out)


def cn_matrix_text(chrom: str, starts, ends, cn: np.ndarray,
                   samples: list[str]) -> str:
    """``--matrix-out``: one digit a cell (CN 0-8)."""
    cells = np.full((len(cn), 2 * cn.shape[1]), ord("\t"), np.uint8)
    cells[:, 0::2] = cn.astype(np.uint8) + ord("0")
    cells[:, -1] = ord("\n")
    head = "#chrom\tstart\tend\t" + "\t".join(samples) + "\n"
    return head + "".join(
        f"{chrom}\t{s}\t{e}\t" + row.tobytes().decode()
        for s, e, row in zip(starts, ends, cells))


def window_stages(d: np.ndarray, break_guarantee: str | None = None,
                  watch=None, workers: int = 8) -> dict:
    """The per-window stages of normalised depths (W,S): {"lam", "cn",
    "fc"}. ``watch(lo, hi)``, where given, makes the observer of windows
    ``lo:hi`` (``em_lambdas``, ``copy_numbers``)."""
    W, S = d.shape
    lam = np.empty((W, N_LAMBDA), d.dtype)
    cn = np.empty(d.shape, np.int8)

    def block(lo):
        hi = min(lo + BLOCK, W)
        seen = watch(lo, hi) if watch is not None else None
        lam[lo:hi] = em_lambdas(
            d[lo:hi], quirk=break_guarantee != "textbook_even_median",
            pref2=break_guarantee != "no_cn2_preference",
            max_iter=1 if break_guarantee == "one_em_iteration"
            else MAX_ITER, watch=seen)
        cn[lo:hi] = copy_numbers(
            lam[lo:hi], d[lo:hi],
            tiebreak=break_guarantee != "no_poisson_tiebreak", watch=seen)

    with cf.ThreadPoolExecutor(workers) as pool:
        list(pool.map(block, range(0, W, BLOCK)))
    return {"lam": lam, "cn": cn, "fc": log2fc(d, lam)}


def depths(raw: np.ndarray, dtype=np.float32,
           break_guarantee: str | None = None) -> np.ndarray:
    """The normalised depths in float32, as the program's host makes them,
    then in ``dtype``."""
    d = normalise(raw, *medians(raw), np.float32)
    if break_guarantee == "bf16_depths":
        d = bf16(d)
    return d.astype(dtype)


def emdepth(raw: np.ndarray, chrom: str, starts, ends, samples: list[str],
            dtype=np.float32, break_guarantee: str | None = None,
            workers: int = 8) -> dict:
    """{"calls": text, "cn_matrix": text} for one contig's matrix of raw
    integer window means (W,S)."""
    got = window_stages(depths(raw, dtype, break_guarantee),
                        break_guarantee, workers=workers)
    calls = merge(got["fc"], got["cn"], starts, ends)
    return {"calls": calls_text(chrom, calls, samples),
            "cn_matrix": cn_matrix_text(chrom, starts, ends, got["cn"],
                                        samples)}
