"""The maker of ``emdepth2504``: a depthwed-style matrix of integer window
means, ``#chrom start end sample...``, as ``cohortdepth`` writes it
(``emit_block``: a window's mean depth rounded half up), for one region of
a contig over a 1000 Genomes-sized cohort.

The cohort (``fixture`` in the configuration's file), all from the seed:
each sample's coverage log-normal around ``reads_per_window`` reads of
``read_len`` bases a window (sigma ``coverage_sigma``); a window profile
the cohort shares (sigma ``profile_sigma``) with ``gap_fraction`` of the
windows in runs of near-empty ones (mappability gaps, ``gap_level`` of the
profile: these drive the empty-bin-2 fallback); ``private_cnvs`` a sample
of ``cnv_windows`` long, of CN 0, 1, 3 or 4 with ``cnv_states``' odds, a
CN-0 window keeping ``cn0_level`` of the reads (mismapped ones); and
``cnp_regions`` common copy-number polymorphisms, each carried by a
``cnp_carriers`` share of the samples in one of ``cnp_states`` (the
configuration's are homozygous deletions at 10-25%, so CN2 stays the
majority of every window: its ``assumed`` says why). A window-sample's
reads are Poisson at its rate; its mean depth is ``reads * read_len /
window``, rounded half up. Names are 1000 Genomes' kind, ``HG`` or
``NA`` and five digits.

**Conditioning**, what ``correct`` at a limit of 0 rests on: the plain
reference (``references/emdepth.py``) is run in float64 and every decision
it takes, in every window and every EM iteration, is checked against its
threshold: the bin-2 membership the M-step reads (every bin's where the
fallback mixes them), the convergence test, ``l2 != 0``, each CN's
nearest lambda, ``0.9 o`` against ``o2`` (in log space, relative to the
exponent's terms; a pmf under FLT_MIN counts as undecidable: the chip
flushes it), the ``same()`` and ``makecnvs``
thresholds of every log2FC, and the ``%.3f`` of every call's mean. A
window-sample that holds one within ``DELTA`` (relative; ``TIE`` of a
rounding tie for the text, where the call's last window-sample is the
one) has its reads drawn again from the seed's stream, its copy number
kept; a window's own decisions, and a window-sample fragile two passes
running, draw the whole window again. A new value that would sit on the
other side of its sample's median is not taken, so no median moves and
only the windows drawn again need a new check, which runs until no
decision is left near a threshold. ``meta.json`` records the passes and
the windows drawn again. Then the float32 reference, what the texts are,
has to give what the float64 one gives, byte for byte.
"""

from __future__ import annotations

import functools
import json
import math
import os

import numpy as np

from references import emdepth as reference

CONTROLS = reference.CONTROLS
MATRIX = "matrix.tsv"
EM_CHUNK = 16384  # the program's windows a device chunk (emdepth_cmd.py)
MAX_PASSES = 100
# a decision closer than this, relative, to its threshold is fragile: some
# 4,000 float32 ulps, so an ulp-off division or pow cannot flip one, and a
# sixteenth of bfloat16's 2^-8, so a bfloat16 computation flips many
DELTA = 2.0 ** -12
TIE = 1e-5  # a %.3f mean this close to a rounding tie is fragile
FLT_MIN = 2.0 ** -126  # the chip flushes what lies under it to 0


class Fragile:
    """The window-samples of a block of windows that hold a fragile
    decision (a window's whole row where the decision is the window's:
    convergence, ``l2``), and how many decisions of each kind: the
    reference's ``watch``, shown each decision it takes."""

    def __init__(self, windows: int, samples: int):
        self.cells = np.zeros((windows, samples), bool)
        self.kinds: dict[str, int] = {}

    def mark(self, kind: str, rows: np.ndarray, bad: np.ndarray) -> None:
        """``bad``: (len(rows),) for the window's own decisions, or
        (len(rows), samples) for a sample's."""
        if bad.any():
            self.cells[rows] |= bad if bad.ndim == 2 else bad[:, None]
            self.kinds[kind] = self.kinds.get(kind, 0) + int(bad.sum())

    def em_step(self, rows, d, lam, got, l2_bin, l2, new, total, top,
                pref2):
        """One EM iteration of the windows ``rows``: bins ``got`` of the
        depths ``d`` by ``lam``, bin 2's mean ``l2_bin`` (0 where the
        fallback mixes bins 1-7 into ``l2``), the lambdas ``new`` and
        their moves ``total`` and ``top``."""
        # what the M-step reads of the bins: bin 2's members, and every
        # bin's where bin 2's mean is 0 and the fallback mixes bins 1-7
        def members(x, lam, mixed=(l2_bin == 0)[:, None]):
            b = reference.bins(x, lam, pref2)
            return np.where(mixed, b, b == 2)

        self.mark("bin", rows, unsure(members, d, lam))
        # each sum is of differences of lambdas, so its noise is the
        # lambdas': the margin is relative to them. A window whose bins
        # did not change computes the same bits again (a difference of
        # exactly 0 everywhere), and one whose next bins would not change
        # ends on the same lambdas whichever way it decides
        dt = total.dtype.type
        big, moved = total > dt(reference.EPS), top > dt(0.5)
        size = np.abs(new) + np.abs(lam)
        sure = [~close(total, reference.EPS, size.sum(axis=1)) | (total == 0),
                ~close(top, 0.5, size.max(axis=1)) | (top == 0)]
        held = big & moved & sure[0] & sure[1]
        failed = (~big & sure[0]) | (~moved & sure[1])
        open_ = ~held & ~failed
        if open_.any():
            again = reference.bins(d[open_], new[open_], pref2)
            open_[open_] = (again != got[open_]).any(axis=1)
        self.mark("convergence", rows, open_)
        self.mark("l2_underflow", rows,
                  (l2 != 0) & (np.abs(l2) < reference.TINY))

    def copy_number(self, d, lam, cn, k, o, o2):
        """The nearest lambda ``cn`` of each depth, and the Poisson
        tiebreak of ``0.9 o`` against ``o2`` at ``k``."""
        rows = np.arange(len(d))
        self.mark("cn", rows, unsure(reference.nearest, d, lam))
        # 0.9 o against o2 in log space: each exponent is a difference of
        # terms of some hundreds (k log mu, lgamma, mu), whose rounding and
        # the device's own log, exp and lgamma set the noise, so the margin
        # is relative to them
        lg = reference.lgamma_table(int(k.max()))[k]
        mu, mu2 = (np.maximum(x.astype(np.float64), reference.TINY)
                   for x in (np.take_along_axis(lam, cn, axis=1),
                             lam[:, 2:3]))
        gap = (np.log(0.9) + k * (np.log(mu) - np.log(mu2)) - (mu - mu2))
        size = (k * (np.abs(np.log(mu)) + np.abs(np.log(mu2))) + 2 * lg
                + mu + mu2)
        self.mark("poisson_tiebreak", rows, (cn != 2) & (
            (np.abs(gap) <= DELTA * size) | (o < FLT_MIN)
            | (o2 < FLT_MIN)))


def close(x, t, scale=0.0):
    """Where ``x`` lies within DELTA of ``t``, relative to the larger of
    the two and ``scale`` (the operands a difference was taken of)."""
    x, t = np.asarray(x, np.float64), np.asarray(t, np.float64)
    width = DELTA * np.maximum(np.maximum(np.abs(x), np.abs(t)), scale)
    return (np.abs(x - t) <= width) & ~((x == 0) & (t == 0))


def unsure(f, d: np.ndarray, *args) -> np.ndarray:
    """Where ``f(d, *args)`` changes when each depth moves by DELTA,
    relative, either way: the depth lies near a boundary of the decision,
    wherever the lambdas put it (a boundary moves with them, relative)."""
    base = f(d, *args)
    return ((f(d * (1 - DELTA), *args) != base)
            | (f(d * (1 + DELTA), *args) != base))


def watched_stages(d: np.ndarray) -> tuple[dict, Fragile]:
    """The reference's stages of the float64 depths ``d`` and a
    ``Fragile`` of every decision they took: each block's, in the order of
    the blocks, then every log2FC against the merge's thresholds."""
    W, S = d.shape
    blocks: dict[int, Fragile] = {}

    def watch(lo, hi):
        blocks[lo] = Fragile(hi - lo, S)
        return blocks[lo]

    stages = reference.window_stages(d, watch=watch, workers=workers())
    fragile = Fragile(W, S)
    for lo in sorted(blocks):
        fragile.cells[lo:lo + len(blocks[lo].cells)] = blocks[lo].cells
        for k, v in blocks[lo].kinds.items():
            fragile.kinds[k] = fragile.kinds.get(k, 0) + v
    fc = stages["fc"]
    fin = np.isfinite(fc)
    for t in (reference.LOWER, reference.UPPER, reference.KEEP_LO,
              reference.KEEP_HI):
        fragile.mark("log2fc_threshold", np.arange(W),
                     fin & close(np.where(fin, fc, 0.0), t))
    return stages, fragile


def text_ties(calls: list[tuple]) -> tuple[np.ndarray, np.ndarray]:
    """(window, sample) of the last window of each call whose mean log2FC
    lies within ``TIE`` of a ``%.3f`` rounding tie."""
    means = np.array([c[4] for c in calls], np.float64)
    x = np.where(np.isfinite(means), means, 0.0) * 1000.0
    near = np.isfinite(means) & (np.abs(x - np.floor(x) - 0.5)
                                 <= TIE * 1000.0)
    at = np.array([(c[5], c[2]) for c in calls], np.int64).reshape(-1, 2)
    return at[near, 0], at[near, 1]


def workers() -> int:
    return min(8, os.cpu_count() or 1)


def sample_names(rng, n: int) -> list[str]:
    numbers = rng.choice(100_000, size=n, replace=False)
    prefixes = np.where(rng.random(n) < 0.6, "HG", "NA")
    return sorted(f"{p}{k:05d}" for p, k in zip(prefixes, numbers))


def runs(rng, total: int, share: float, longest: int) -> np.ndarray:
    """A mask of about ``share`` of ``total`` windows in runs of 1 to
    ``longest``."""
    mask = np.zeros(total, bool)
    while mask.mean() < share:
        n = int(rng.integers(1, longest + 1))
        lo = int(rng.integers(0, total - n + 1))
        mask[lo:lo + n] = True
    return mask


def plant(fx: dict, seed: int) -> dict:
    """Everything but the noise: names, the rate of reads of every
    window-sample and its copy number."""
    rng = np.random.default_rng([seed, 0])
    W, S = fx["windows"], fx["samples"]
    samples = sample_names(rng, S)
    coverage = np.exp(fx["coverage_sigma"] * rng.normal(size=S))
    profile = np.exp(fx["profile_sigma"] * rng.normal(size=W))
    gaps = runs(rng, W, fx["gap_fraction"], fx["gap_run"])
    lo, hi = fx["gap_level"]
    profile[gaps] = np.exp(rng.uniform(math.log(lo), math.log(hi),
                                       size=int(gaps.sum())))
    cn = np.full((W, S), 2, np.int8)
    states = np.array(fx["cnv_states"]["cn"])
    odds = np.array(fx["cnv_states"]["odds"], float)
    short, long_ = fx["cnv_windows"]
    for s in range(S):
        for _ in range(rng.poisson(fx["private_cnvs"])):
            n = min(W, int(round(math.exp(rng.uniform(
                math.log(short), math.log(long_))))))
            at = int(rng.integers(0, W - n + 1))
            cn[at:at + n, s] = rng.choice(states, p=odds / odds.sum())
    for _ in range(fx["cnp_regions"]):
        n = min(W, int(rng.integers(*fx["cnp_windows"])))
        at = int(rng.integers(0, W - n + 1))
        share = rng.uniform(*fx["cnp_carriers"])
        carriers = rng.random(S) < share
        cn[at:at + n, carriers] = rng.choice(fx["cnp_states"])
    factor = np.where(cn == 0, fx["cn0_level"], cn / 2.0)
    rate = (fx["reads_per_window"] * coverage[None, :] * profile[:, None]
            * factor)
    return {"samples": samples, "rate": rate, "cn": cn,
            "gaps": int(gaps.sum())}


def mean_depth(reads: np.ndarray, fx: dict) -> np.ndarray:
    """A window's mean depth rounded half up, in integers."""
    return ((reads * fx["read_len"] + fx["window"] // 2)
            // fx["window"]).astype(np.int16)


def middle(raw: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Each column's two middle order statistics, whose mean is its
    median."""
    n = len(raw)
    part = np.partition(raw, [(n - 1) // 2, n // 2], axis=0)
    return part[(n - 1) // 2], part[n // 2]


def side(x: np.ndarray, lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """Which of five places a value holds about its column's middle: under
    it, at its lower value, between, at its upper value, over it."""
    return ((x >= lo).astype(np.int8) + (x > lo) + (x >= hi)
            + (x > hi))


def condition(fx: dict, seed: int, planted: dict) -> dict:
    """The raw matrix drawn from the seed and drawn again where a decision
    lies near its threshold, until none does. What is drawn again is the
    window-samples whose decisions are fragile (a window's whole row for
    its own decisions, and for a window-sample that was fragile in the
    pass before too); a cell keeps its old value where the new one would
    sit on another side of its sample's middle, so that no median moves
    and only the windows drawn again need a new check."""
    rng = np.random.default_rng([seed, 1])
    rate = planted["rate"]
    raw = mean_depth(rng.poisson(rate), fx)
    W, S = raw.shape
    starts = np.arange(W, dtype=np.int64) * fx["window"] + fx["start"]
    ends = starts + fx["window"]
    lo, hi = middle(raw)
    med = reference.medians(raw)
    d = reference.normalise(raw, *med, np.float32).astype(np.float64)
    stages, seen = watched_stages(d)
    fragile, kinds = seen.cells, dict(seen.kinds)
    passes, redrawn, again = [], set(), np.arange(W)
    stuck = np.zeros((W, S), bool)
    while True:
        calls = reference.merge(stages["fc"], stages["cn"], starts, ends)
        tie_w, tie_s = text_ties(calls)
        cells = fragile.copy()
        cells[tie_w, tie_s] = True
        rows = np.flatnonzero(cells.any(axis=1))
        passes.append({"windows": int(len(again)), "fragile": int(len(rows)),
                       "cells": int(cells.sum()), "text_ties": len(tie_w),
                       "kinds": kinds})
        if not len(rows):
            break
        if len(passes) > MAX_PASSES:
            raise RuntimeError(f"emdepth fixture, seed {seed}: "
                               f"{len(rows)} windows still fragile after "
                               f"{MAX_PASSES} passes")
        sub = cells[rows]
        sub[(sub & stuck[rows]).any(axis=1)] = True
        stuck[:] = False
        stuck[rows] = sub
        new = mean_depth(rng.poisson(rate[rows]), fx)
        old = raw[rows]
        raw[rows] = np.where(sub & (side(new, lo, hi) == side(old, lo, hi)),
                             new, old)
        redrawn.update(rows.tolist())
        again = rows
        d[again] = reference.normalise(raw[again], *med, np.float32)
        part, seen = watched_stages(d[again])
        for k in ("lam", "cn", "fc"):
            stages[k][again] = part[k]
        fragile[again] = seen.cells
        kinds = dict(seen.kinds)
    assert all(np.array_equal(a, b) for a, b in zip(
        (lo, hi), middle(raw))), "a redraw moved a median"
    return {"raw": raw, "starts": starts, "ends": ends, "calls": len(calls),
            "conditioning": {
                "delta": DELTA, "text_tie": TIE,
                "passes": passes, "windows_redrawn": sorted(redrawn)}}


@functools.lru_cache(maxsize=1)
def _made(fixture_json: str, seed: int) -> dict:
    fx = json.loads(fixture_json)
    planted = plant(fx, seed)
    made = condition(fx, seed, planted)
    made.update(samples=planted["samples"], gap_windows=planted["gaps"],
                cnv_cells=int((planted["cn"] != 2).sum()))
    return made


def made(fx: dict, seed: int) -> dict:
    """The last (fixture, seed) is kept, so that ``build``, ``expected``
    and each control condition it once; nobody writes to it."""
    return _made(json.dumps(fx, sort_keys=True), seed)


def matrix_text(chrom: str, made: dict) -> bytes:
    """The input: integers of up to three digits, tab-separated."""
    raw = made["raw"].astype(np.int64)
    if raw.min() < 0 or raw.max() > 999:
        raise ValueError("a window mean outside 0..999")
    W, S = raw.shape
    cells = np.zeros((W, S, 4), np.uint8)
    cells[..., 0] = raw // 100 + 48
    cells[..., 1] = raw // 10 % 10 + 48
    cells[..., 2] = raw % 10 + 48
    cells[..., 3] = ord("\t")
    cells[:, -1, 3] = ord("\n")
    keep = np.ones((W, S, 4), bool)
    keep[..., 0] = raw >= 100
    keep[..., 1] = raw >= 10
    head = ("#chrom\tstart\tend\t" + "\t".join(made["samples"])
            + "\n").encode()
    return head + b"".join(
        f"{chrom}\t{s}\t{e}\t".encode() + row[k].tobytes()
        for s, e, row, k in zip(made["starts"], made["ends"], cells, keep))


def expected(config: dict, seed: int,
             break_guarantee: str | None = None) -> tuple[dict, dict]:
    """({expected file name: text}, meta) by the plain reference in
    float32; without a control, also the float64 reference's texts, which
    have to be the same."""
    fx = config["fixture"]
    m = made(fx, seed)
    args = (m["raw"], fx["chrom"], m["starts"], m["ends"], m["samples"])
    texts = reference.emdepth(*args, dtype=np.float32,
                              break_guarantee=break_guarantee,
                              workers=workers())
    if break_guarantee is None:
        wide = reference.emdepth(*args, dtype=np.float64, workers=workers())
        for kind in ("calls", "cn_matrix"):
            if wide[kind] != texts[kind]:
                raise AssertionError(
                    f"emdepth fixture, seed {seed}: the float32 and float64 "
                    f"references write different {kind} after conditioning")
    W, S = m["raw"].shape
    meta = {
        "job_bases": fx["window"] * W * S,
        "windows": W, "samples": S, "calls": m["calls"],
        "gap_windows": m["gap_windows"], "cnv_cells": m["cnv_cells"],
        "conditioning": m["conditioning"],
        "work": {"kind": "em_windows", "windows": W, "samples": S,
                 "em_chunk": EM_CHUNK},
    }
    return ({"expected.calls": texts["calls"],
             "expected.cn.tsv": texts["cn_matrix"]}, meta)


def build(config: dict, seed: int, out: str) -> dict:
    """Write the job's input into ``out``; the part of ``meta.json`` that
    says what it is."""
    fx = config["fixture"]
    with open(f"{out}/{MATRIX}", "wb") as fh:
        fh.write(matrix_text(fx["chrom"], made(fx, seed)))
    return {"inputs": [MATRIX], "native_probe": None}
