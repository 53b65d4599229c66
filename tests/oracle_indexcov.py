"""Sequential NumPy oracle of indexcov's per-sample normalization and
copy-number semantics, independent of goleft_tpu.ops.indexcov_ops and
free of jax — tests/test_indexcov_oracle.py compares run_indexcov with
it, and chip_smoke.py (whose parent process must stay off jax) the
chip's output."""

import numpy as np


def oracle_median(all_sizes):
    flat = np.sort(np.concatenate(all_sizes).astype(np.int64))
    n98 = flat[int(0.98 * len(flat))]
    cum = np.cumsum(np.minimum(flat, n98))
    idx = int(np.searchsorted(cum, int(cum[-1]) // 2, side="right"))
    return float(flat[min(idx, len(flat) - 1)])


def oracle_normalized(sizes):
    """Per-chromosome tile sizes of one sample → normalized depths."""
    med = oracle_median([s for s in sizes if len(s)])
    return [
        np.minimum((s.astype(np.float64) / med).astype(np.float32), 50000)
        for s in sizes
    ]


def oracle_cn(depths, ploidy=2):
    tmp = sorted(float(x) for x in depths if x != 0)
    lows = sum(1 for x in depths if x != 0 and x < 0.02)
    if not tmp:
        return -0.1
    if lows / len(depths) > 0.3:
        tmp = tmp[lows:]
    if not tmp:
        return 0.0
    return float(np.float32(ploidy) * np.float32(tmp[int(len(tmp) * 0.4)]))


def oracle_counters(d, longest):
    """{in, out, hi, lo} bin counters of one sample's chromosome against
    the cohort's longest row (missing tail bins count as out and lo)."""
    tail = longest - len(d)
    return {
        "in": int(np.sum((d >= 0.85) & (d <= 1.15))),
        "out": int(np.sum((d < 0.85) | (d > 1.15))) + tail,
        "hi": int(np.sum(d > 1.15)),
        "lo": int(np.sum(d < 0.15)) + tail,
    }
