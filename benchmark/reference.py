"""The plain reference: what ``depth`` and ``cohortdepth`` must print.

NumPy only, from the fixture's own read list (starts, MAPQ, flags), never
through ``goleft_tpu``: per-base depth as difference array -> cumsum,
window means from exact integer sums, classes by threshold. After
``chip_smoke.py``'s oracles (PR 22), which PR 25 lists for deletion there.

``break_guarantee`` names the controls: the same reference with one stated
guarantee broken, which the comparison has to refuse
(``benchmark/control.py``, ``benchmark/tests``).
"""

from __future__ import annotations

import numpy as np

READ_LEN = 150
CLASS_NAMES = ("NO_COVERAGE", "LOW_COVERAGE", "CALLABLE")
CONTROLS = ("bf16_window_sum", "no_mapq_filter", "wrong_window_sum")


def kept_mask(mapq, flag, min_mapq: int, flag_mask: int,
              break_guarantee: str | None = None):
    keep = (flag & flag_mask) == 0
    if break_guarantee != "no_mapq_filter":
        keep &= mapq >= min_mapq
    return keep


def per_base_depth(kept_starts, contig_len: int,
                   chunk: int = 1 << 20) -> np.ndarray:
    """Difference array -> running sum, a chunk of the contig at a time
    so that no temporary is contig-sized. ``kept_starts`` is sorted."""
    depth = np.empty(contig_len, np.int32)
    kept_ends = kept_starts + READ_LEN
    carry = 0
    for lo in range(0, contig_len, chunk):
        hi = min(lo + chunk, contig_len)
        s0, s1 = np.searchsorted(kept_starts, (lo, hi))
        e0, e1 = np.searchsorted(kept_ends, (lo, hi))
        delta = (np.bincount(kept_starts[s0:s1] - lo, minlength=hi - lo)
                 - np.bincount(kept_ends[e0:e1] - lo, minlength=hi - lo))
        delta[0] += carry
        depth[lo:hi] = np.cumsum(delta)
        carry = int(depth[hi - 1])
    return depth


def to_bf16(x) -> np.ndarray:
    """float32 rounded to bfloat16's 8 significant bits (nearest even),
    kept as float32."""
    b = np.ascontiguousarray(x, np.float32).view(np.uint32)
    b = (b + np.uint32(0x7FFF) + ((b >> 16) & 1)) & np.uint32(0xFFFF0000)
    return b.view(np.float32)


def window_sums(depth, lo: int, hi: int, window: int,
                break_guarantee: str | None = None):
    """(starts, ends, sums) of absolute-aligned windows clipped to
    [lo, hi). Sums are exact integers; ``bf16_window_sum`` is the kindest
    bfloat16 reduction (bf16 operands, f32 accumulator, bf16 result).
    ``wrong_window_sum`` moves one mean by 1: ``depth`` prints %.4g, so at
    30x a sum off by 1 in 10,000 is invisible to the user and to this
    comparison alike."""
    starts = np.arange(lo // window * window, hi, window)
    ends = np.minimum(starts + window, hi)
    starts = np.maximum(starts, lo)
    sums = np.add.reduceat(depth[lo:hi], starts - lo, dtype=np.int64)
    if break_guarantee == "bf16_window_sum":
        sums = to_bf16(sums.astype(np.float32)).astype(np.float64)
    elif break_guarantee == "wrong_window_sum":
        sums = sums.copy()
        sums[len(sums) // 2] += window  # one window's mean off by 1
    return starts, ends, sums


def depth_bed(chrom: str, depth, lo: int, hi: int, window: int,
              break_guarantee: str | None = None) -> str:
    starts, ends, sums = window_sums(depth, lo, hi, window, break_guarantee)
    means = sums / (ends - starts)
    return "".join(f"{chrom}\t{s}\t{e}\t{m:.4g}\n"
                   for s, e, m in zip(starts, ends, means))


def callable_bed(chrom: str, depth, lo: int, hi: int, mincov: int,
                 shard: int) -> str:
    """Run-length-encoded classes; runs break where the command's shards
    do (every ``shard`` bases)."""
    d = depth[lo:hi]
    cls = (d > 0).view(np.int8) + (d >= mincov)
    cuts = np.union1d(np.flatnonzero(cls[1:] != cls[:-1]) + 1,
                      np.arange(lo // shard * shard + shard, hi, shard) - lo)
    starts = np.concatenate(([0], cuts))
    ends = np.concatenate((cuts, [hi - lo]))
    return "".join(f"{chrom}\t{s + lo}\t{e + lo}\t{CLASS_NAMES[v]}\n"
                   for s, e, v in zip(starts, ends, cls[starts]))


def matrix_tsv(chrom: str, depths: list, columns: list[int],
               names: list[str], lo: int, hi: int, window: int,
               break_guarantee: str | None = None) -> str:
    """``cohortdepth``'s matrix: a header of sample names, then one row a
    window with each sample's mean rounded half up. ``depths`` holds the
    distinct samples' per-base depth, ``columns`` which of them each
    column shows."""
    per_sample = []
    for d in depths:
        starts, ends, sums = window_sums(d, lo, hi, window, break_guarantee)
        per_sample.append((0.5 + sums / (ends - starts)).astype(np.int64))
    vals = np.stack(per_sample)[columns].T  # (windows, columns)
    rows = ["\t".join(map(str, r)) for r in vals.tolist()]
    return ("#chrom\tstart\tend\t" + "\t".join(names) + "\n"
            + "".join(f"{chrom}\t{s}\t{e}\t{r}\n"
                      for s, e, r in zip(starts, ends, rows)))
