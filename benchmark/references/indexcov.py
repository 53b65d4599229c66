"""The plain reference of ``indexcov``: what the program must write from
a cohort's per-tile sizes (the deltas of a ``.bai``'s linear index).

NumPy only, one sample and one contig at a time, never through
``goleft_tpu``. It follows upstream ``indexcov/indexcov.go`` as
``goleft_tpu/ops/indexcov_ops.py``'s docstring cites it:

- median size a tile: all of a sample's sizes sorted, capped at their
  98th percentile, the value where the capped running sum first passes
  half the capped total, in int64 (":104-124");
- normalised depth: float32(size / median), capped at 50,000 (":129-151");
- ROC: slot = trunc(depth * float32(70 * float32(2/3)) + 0.5) in float32,
  clipped to 0..69; counts summed from the top slot down, over the count
  of tiles, in float32 (":153-193");
- counters of the non-sex contigs: in [0.85, 1.15], out, hi > 1.15,
  low < 0.15; tiles that a sample lacks up to the cohort's longest count
  as out and low (":1050-1078");
- copy number of a sex contig: zeros dropped, and where over 30% of all
  tiles are non-zero but under 0.02 those too; twice the value at 0.4 of
  what is left, sorted (":957-991");
- slope: ROC[40] - ROC[54] times float32(length / 1e6), summed in float32
  over the non-sex contigs of more than 100 tiles in their order, over
  their number (":739-750");
- PCA: depths capped at 8, quantised, every non-sex contig side by side;
  columns centred, the raw matrix projected on the top five right
  singular vectors (":773-807"), all in float64 and by way of the
  samples' Gram matrix, which is summed exactly.

Departures from upstream, all the program's documented ones: the
quantisation is to uint16 (upstream's ``uint8(65535/8*d+0.5)`` wraps mod
256; ``quantise_u8_wrap`` is that); a sample name from a bare index path
is its file name less the last suffix with dots turned to dashes; a sex
contig whose tiles are all dropped reads 0 (upstream would index past
the end); the ``.ped`` always has its mapped and unmapped columns (the
program writes them where any index has the counts).

``break_guarantee`` names the controls: the same reference with one of
its statements broken (``benchmark/control.py``).
"""

from __future__ import annotations

import concurrent.futures as cf
import re

import numpy as np

CONTROLS = ("median_uncapped", "roc_slot_truncates", "no_tail_bins",
            "pca_not_centred", "quantise_u8_wrap")
TILE = 16384
SLOTS = 70
SLOT_SCALE = np.float32(SLOTS * np.float32(2.0 / 3.0))  # 46.666668
DEPTH_CAP = np.float32(50000.0)
MAX_CN = np.float32(8.0)
EXCLUDE = r"^chrEBV$|^NC|_random$|Un_|^HLA\-|_alt$|hap\d$"  # the default -p


def median_size(sizes: list[np.ndarray], capped: bool = True) -> int:
    """One sample's scaling median from every contig's sizes."""
    flat = np.sort(np.concatenate(sizes).astype(np.int64))
    if capped:
        flat_c = np.minimum(flat, flat[int(0.98 * len(flat))])
    else:
        flat_c = flat
    running = np.cumsum(flat_c)
    first = int(np.argmax(running > running[-1] // 2))
    return int(flat[first])


def normalised_depth(sizes: np.ndarray, median: int) -> np.ndarray:
    depth = (sizes.astype(np.float64) / float(median)).astype(np.float32)
    return np.minimum(depth, DEPTH_CAP)


def roc(depth: np.ndarray, round_half: bool = True) -> np.ndarray:
    """The share of a sample's tiles at or above each of the 70 slots."""
    x = depth * SLOT_SCALE
    if round_half:
        x = x + np.float32(0.5)
    slot = np.clip(x.astype(np.int32), 0, SLOTS - 1)
    counts = np.bincount(slot, minlength=SLOTS)
    from_top = np.cumsum(counts[::-1])[::-1]
    return from_top.astype(np.float32) / np.float32(from_top[0])


def bin_counts(depth: np.ndarray, longest: int, tail: bool = True) -> dict:
    lo, hi = np.float32(0.85), np.float32(1.15)
    missing = longest - len(depth) if tail else 0
    return {
        "in": int(np.count_nonzero((depth >= lo) & (depth <= hi))),
        "out": int(np.count_nonzero((depth < lo) | (depth > hi))) + missing,
        "hi": int(np.count_nonzero(depth > hi)),
        "low": int(np.count_nonzero(depth < np.float32(0.15))) + missing,
    }


def copy_number(depth: np.ndarray) -> np.float32:
    kept = np.sort(depth[depth != 0])
    if len(kept) == 0:
        return np.float32(-0.1)
    lows = int(np.count_nonzero(kept < np.float32(0.02)))
    if lows / len(depth) > 0.3:
        kept = kept[lows:]
    if len(kept) == 0:
        return np.float32(0.0)
    return np.float32(2) * kept[int(len(kept) * 0.4)]


def quantise(depth: np.ndarray, u8_wrap: bool = False) -> np.ndarray:
    q = (np.float32(65535.0 / 8.0) * np.minimum(depth, MAX_CN)
         + np.float32(0.5)).astype(np.uint16)
    return q.astype(np.uint8).astype(np.uint16) if u8_wrap else q


def gram(block: np.ndarray) -> np.ndarray:
    """A contig's share of the samples' Gram matrix X X', exact: sums of
    products of uint16 stay whole numbers under 2^53 in float64."""
    x = block.astype(np.float64)
    return x @ x.T


def principal_components(g: np.ndarray, k: int = 5,
                         centre: bool = True) -> np.ndarray:
    """(samples, k) float64 from ``g`` = X X', X the raw matrix (samples
    x tiles): X on the top ``k`` right singular vectors of its
    column-centred self. Centring the columns is Xc = H X with H = I -
    11'/n, so Xc Xc' = H g H; with Xc = U S V', U and S^2 are that
    matrix's eigenvectors and eigenvalues, and X V = X Xc' U / S =
    g H U / S."""
    n = len(g)
    h = np.eye(n) - 1.0 / n if centre else np.eye(n)
    eigenvalues, u = np.linalg.eigh(h @ g @ h)
    top = np.argsort(eigenvalues)[::-1][:k]
    return g @ h @ u[:, top] / np.sqrt(eigenvalues[top])


def sample_name(path: str) -> str:
    parts = path.rsplit("/", 1)[-1].split(".")
    return parts[0] if len(parts) <= 2 else "-".join(parts[:-1])


def is_sex(contig: str, sex: tuple[str, ...]) -> bool:
    bare = contig[3:] if contig.startswith("chr") else contig
    return any(bare == (s[3:] if s.startswith("chr") else s) for s in sex)


class G3:
    """``"%.3g" % v`` for many non-negative float32 at once. The text is a
    step function of the value, so each step is formatted once, by
    Python's own ``%``, and a value finds its step by bisection. The
    steps' edges are found by formatting the float32 neighbours of every
    decimal tie (k + 0.5) * 10^(e-2): nothing is assumed of how a tie
    rounds. Values outside [1e-12, 1e5) other than 0 go through ``%``
    one by one."""

    DECADES = range(-12, 5)

    def __init__(self):
        ties = np.array([(k + 0.5) * 10.0 ** (e - 2) for e in self.DECADES
                         for k in range(100, 1000)]).astype(np.float32)
        # the eight float32 around each tie (positive floats are ordered
        # as their bits), and the first whose text is the upper step's
        near = (ties.view(np.int32)[:, None]
                + np.arange(-3, 5, dtype=np.int32)).view(np.float32)
        edges = []
        for row in near.tolist():
            texts = ["%.3g" % v for v in row]
            changes = [j for j in range(1, 8) if texts[j] != texts[j - 1]]
            if len(changes) != 1:
                raise AssertionError(f"no single step near {row[3]!r}")
            edges.append(row[changes[0]])
        self.lo = np.float32(10.0 ** self.DECADES[0])
        self.edges = np.array([self.lo] + edges, np.float32)
        if np.any(np.diff(self.edges) <= 0):
            raise AssertionError("step edges out of order")
        texts = ["%.3g" % float(v) for v in self.edges]
        self.width = max(map(len, texts)) + 1  # and the separator
        self.lengths = np.array([0] + [len(t) for t in texts], np.int64)
        self.chars = np.zeros((len(texts) + 1, self.width), np.uint8)
        for i, t in enumerate(texts):
            self.chars[i + 1, :len(t)] = np.frombuffer(t.encode(), np.uint8)
        self.chars[0, 0], self.lengths[0] = ord("0"), 1  # step 0: the value 0

    def rows(self, prefix: list[str], values: np.ndarray) -> bytes:
        """One line a row of ``values`` (rows x columns, NaN-free):
        ``prefix[i]``, then the cells tab-separated, then a newline."""
        n_rows, n_cols = values.shape
        if np.any((values != 0) & ((values < self.lo)
                                   | (values >= np.float32(1e5)))):
            return self._slow_rows(prefix, values)
        step = np.searchsorted(self.edges, values, side="right")
        step[values == 0] = 0
        width = max(map(len, prefix))
        grid = np.zeros((n_rows, width + n_cols * self.width), np.uint8)
        keep = np.zeros(grid.shape, bool)
        for i, p in enumerate(prefix):
            grid[i, :len(p)] = np.frombuffer(p.encode(), np.uint8)
            keep[i, :len(p)] = True
        cells = self.chars[step]  # rows x columns x width
        n = self.lengths[step]
        at = np.arange(self.width)
        cells[at == n[..., None]] = ord("\t")
        cells[:, -1][at == n[:, -1, None]] = ord("\n")
        grid[:, width:] = cells.reshape(n_rows, -1)
        keep[:, width:] = (at <= n[..., None]).reshape(n_rows, -1)
        return grid[keep].tobytes()

    @staticmethod
    def _slow_rows(prefix: list[str], values: np.ndarray) -> bytes:
        return "".join(
            p + "\t".join("%.3g" % v for v in row.tolist()) + "\n"
            for p, row in zip(prefix, values)).encode()


def contig_qc(contig: str, sizes: list[np.ndarray], medians: list[int],
              sex_contig: bool, g3: G3,
              break_guarantee: str | None = None) -> dict | None:
    """One contig of the cohort, a sample at a time: its lines of the
    bed and of the ROC file, and what the ``.ped`` gathers from it."""
    n = len(sizes)
    depths = [normalised_depth(sizes[s], medians[s]) for s in range(n)]
    longest = max(len(d) for d in depths)
    if longest == 0:
        return None
    table = np.zeros((longest, n), np.float32)  # a missing tile prints 0
    rocs = np.zeros((n, SLOTS), np.float32)
    cn, counts = [], []
    for s, d in enumerate(depths):
        table[:len(d), s] = d
        rocs[s] = roc(d, round_half=break_guarantee != "roc_slot_truncates")
        if sex_contig:
            cn.append(copy_number(d))
        else:
            counts.append(bin_counts(
                d, longest, tail=break_guarantee != "no_tail_bins"))
    bed = [g3.rows([f"{contig}\t{t * TILE}\t{(t + 1) * TILE}\t"
                    for t in range(lo, min(lo + 2048, longest))],
                   table[lo:lo + 2048]) for lo in range(0, longest, 2048)]
    coverage = ["%.2f" % (i / (SLOTS * (2.0 / 3.0))) for i in range(SLOTS)]
    roc_lines = [f"{contig}\t{coverage[i]}\t"
                 + "\t".join("%.2f" % v for v in rocs[:, i].tolist()) + "\n"
                 for i in range(SLOTS)]
    out = {"bed": b"".join(bed), "roc": "".join(roc_lines), "cn": cn,
           "counts": counts, "longest": longest,
           "roc_drop": rocs[:, 40] - rocs[:, 54]}
    if not sex_contig:
        out["gram"] = gram(quantise(
            table, u8_wrap=break_guarantee == "quantise_u8_wrap").T)
    return out


def cohort_qc(cohort: dict, break_guarantee: str | None = None,
              workers: int = 1) -> dict:
    """{"bed", "roc", "ped"} texts and "pcs" (samples x 5, float64) for
    ``cohort``: ``paths`` (one a sample), ``contigs`` ([(name, length)],
    the ``.fai``'s), ``sizes`` ([sample][contig] int64 tile sizes, ragged),
    ``mapped`` and ``unmapped`` (one a sample), ``sex`` (names as -X).
    ``workers`` threads take a contig each; what is summed over contigs
    is summed afterwards, in their order."""
    if break_guarantee not in (None, *CONTROLS):
        raise ValueError(f"unknown control {break_guarantee!r}")
    names = [sample_name(p) for p in cohort["paths"]]
    sizes, sex = cohort["sizes"], tuple(cohort["sex"])
    n = len(names)
    g3 = G3()
    kept = [(c, name, length) for c, (name, length)
            in enumerate(cohort["contigs"]) if not re.search(EXCLUDE, name)]
    with cf.ThreadPoolExecutor(workers) as pool:
        medians = list(pool.map(
            lambda s: median_size(
                s, capped=break_guarantee != "median_uncapped"), sizes))
        done = list(pool.map(
            lambda k: contig_qc(
                k[1], [sizes[s][k[0]] for s in range(n)], medians,
                is_sex(k[1], sex), g3, break_guarantee), kept))

    bed = [("#chrom\tstart\tend\t" + "\t".join(names) + "\n").encode()]
    roc_text = ["#chrom\tcov\t" + "\t".join(names) + "\n"]
    counters = {k: [0] * n for k in ("in", "out", "hi", "low")}
    cn: dict[str, list] = {}
    slopes, n_slopes = np.zeros(n, np.float32), 0
    g = np.zeros((n, n))
    for (_, contig, length), got in zip(kept, done):
        if got is None:
            continue
        bed.append(got["bed"])
        roc_text.append(got["roc"])
        if is_sex(contig, sex):
            cn[contig] = got["cn"]
            continue
        g += got["gram"]
        for s, counts in enumerate(got["counts"]):
            for k in counters:
                counters[k][s] += counts[k]
        if got["longest"] > 100 and not contig.startswith("GL"):
            slopes += got["roc_drop"] * np.float32(length / 1e6)
            n_slopes += 1
    if n_slopes:
        slopes = slopes / np.float32(n_slopes)
    pcs = principal_components(
        g, k=min(5, n), centre=break_guarantee != "pca_not_centred")

    keys = sorted(cn)
    head = (["#family_id", "sample_id", "paternal_id", "maternal_id", "sex",
             "phenotype"] + ["CN" + k for k in keys]
            + ["bins.out", "bins.lo", "bins.hi", "bins.in", "slope", "p.out"]
            + [f"PC{j + 1}" for j in range(pcs.shape[1])]
            + ["mapped", "unmapped"])
    ped = ["\t".join(head) + "\n"]
    for s, name in enumerate(names):
        out, inn = counters["out"][s], counters["in"][s]
        row = ["unknown", name, "-9", "-9",
               str(int(0.5 + cn[keys[0]][s])) if keys else "-9", "-9"]
        row += ["%.2f" % cn[k][s] for k in keys]
        row += [str(out), str(counters["low"][s]), str(counters["hi"][s]),
                str(inn), "%.3f" % slopes[s],
                "%.2f" % (out / inn if inn else float("inf"))]
        row += ["%.2f" % v for v in pcs[s]]
        row += [str(cohort["mapped"][s]), str(cohort["unmapped"][s])]
        ped.append("\t".join(row) + "\n")
    # latin-1 maps bytes to code points one to one: no pass to validate
    return {"bed": b"".join(bed).decode("latin-1"), "roc": "".join(roc_text),
            "ped": "".join(ped), "pcs": pcs}
