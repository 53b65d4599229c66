"""indexcov: whole-cohort coverage QC from .bai/.crai indexes only.

TPU-native rebuild of the reference flagship (indexcov/indexcov.go, 1078
LoC). Host work is index parsing (io.bai/io.crai) and report writing; the
per-bin numerics — histogram/ROC, bin counters, copy number, cross-sample
normalization, PCA — run as batched JAX kernels over a padded
(samples × bins) matrix per chromosome (ops/indexcov_ops.py), instead of
the reference's per-sample Go loops (indexcov.go:599-734).

Output surface matches the reference: <dir>/<name>-indexcov.bed.gz (per-
16KB-bin scaled depths), .roc, .ped (sex/CN/bin-QC/slope/PCA columns,
indexcov.go:815-953), per-chromosome -depth-<chrom>.html/png and
-roc-<chrom>.html/png, and index.html.
"""

from __future__ import annotations

import argparse
import glob as _glob
import os
import re
import sys
import threading

import numpy as np

from .. import obs
from ..io import native
from ..io.bai import TileSizes, read_bai, read_tile_sizes
from ..io.bedgz import BedGzStream
from ..io.crai import read_crai
from ..io.fai import read_fai
from ..ops import indexcov_ops as ops
from ..utils import report

from ..obs.logging import get_logger

log = get_logger("indexcov")

DEFAULT_EXCLUDE = r"^chrEBV$|^NC|_random$|Un_|^HLA\-|_alt$|hap\d$"
MAX_SAMPLES = 100  # above this, interactive depth plots are skipped
TILE = 16384


class SampleIndex:
    """Parsed index: per-chromosome tile sizes + scaling median.

    Mirrors the reference's Index wrapper (indexcov.go:57-67,83-125).
    """

    def __init__(self, path: str):
        self.path = path
        if path.endswith(".cram"):
            # reference behavior: .cram rides its companion .crai
            # (indexcov.go:471-525 readIndex on rdr path + ".crai")
            path = path + ".crai"
        from ..io import remote

        if path.endswith(".crai"):
            data = remote.fetch_bytes(path)
            t = TileSizes(read_crai(data).sizes(), 0, 0, len(data), None)
        else:
            bai_path = path
            if not path.endswith(".bai"):
                bai_path = path + ".bai"
                if not remote.exists(bai_path):
                    bai_path = path[:-4] + ".bai"
            # a local .bai in one native pass on buffers the thread keeps;
            # a URL, or a build without the library, through read_bai
            t = (None if remote.is_remote(bai_path)
                 else read_tile_sizes(bai_path))
            if t is None:
                data = remote.fetch_bytes(bai_path)
                idx = read_bai(data)
                t = TileSizes(idx.sizes(), idx.mapped_total,
                              idx.unmapped_total, len(data), None)
        self.sizes = t.sizes
        self.mapped = t.mapped
        self.unmapped = t.unmapped
        self.nbytes = t.nbytes  # of the index file as read
        # the native pass brings the median unless the index has no tile,
        # which median_size_per_tile refuses in its own words
        self.median = (ops.median_size_per_tile(t.sizes)
                       if t.median is None else t.median)

    def normalized_depth(self, ref_id: int) -> np.ndarray:
        if ref_id >= len(self.sizes):
            return np.zeros(0, dtype=np.float32)
        return ops.normalized_depth(self.sizes[ref_id], self.median)


def get_short_name(path: str) -> str:
    """Sample name: unique SM tag from the BAM header when available,
    else derived from the filename (indexcov.go:213-246)."""
    if not path.endswith((".crai", ".bai")):
        try:
            from ..io.bam import read_alignment_header

            names = read_alignment_header(path).sample_names()
            if len(names) > 1:
                raise ValueError(f"more than one RG SM for {path}")
            if names:
                return names[0]
        except (OSError, ValueError):
            pass
    base = path.rsplit("/", 1)[-1]
    parts = base.split(".")
    if len(parts) <= 2:
        return parts[0]
    return "-".join(parts[:-1])


def references(
    bams: list[str], fai: str | None, chrom: str = ""
) -> list[tuple[int, str, int]]:
    """(ref_id, name, length) list from an .fai (required for crai inputs)
    or the first BAM's header (indexcov.go:276-342). ref_id is the position
    in the full reference dictionary — the key into per-sample size arrays
    — even when ``chrom`` restricts the output."""
    if fai:
        recs = read_fai(fai)
        refs = [(i, r.name, r.length) for i, r in enumerate(recs)]
    else:
        path = next((b for b in bams if not b.endswith((".crai", ".bai"))),
                    None)
        if path is None:
            raise SystemExit(
                "indexcov: --fai is required when only index files are given"
            )
        from ..io.bam import read_alignment_header

        h = read_alignment_header(path)
        refs = [(i, n, l)
                for i, (n, l) in enumerate(zip(h.ref_names, h.ref_lens))]
    if chrom:
        want = chrom[3:] if chrom.startswith("chr") else chrom
        refs = [
            (i, n, l) for i, n, l in refs
            if n == chrom or (n[3:] if n.startswith("chr") else n) == want
        ]
        if not refs:
            raise SystemExit(f"indexcov: chromosome {chrom} not found")
    return refs


def expand_globs(paths: list[str]) -> list[str]:
    out = []
    for p in paths:
        if any(ch in p for ch in "*?["):
            out.extend(sorted(_glob.glob(p)))
        else:
            out.append(p)
    return out


def _width_bucket(w: int) -> int:
    """Quarter-power-of-two bucket ≥ w (max 25% padding).

    Every chromosome has a distinct tile count, and ``chrom_qc``
    compiles once per (samples, width) signature — 4-10s each on a
    remote accelerator. Bucketing the padded width collapses a
    25-chromosome genome from 25 compiles to ~4; the padding columns
    carry valid=False so every result is identical (the device QC masks
    on valid/longest, not on the array width)."""
    if w <= 256:
        return 256
    b = 1 << (w - 1).bit_length()  # next pow2
    for cand in (b // 2 + b // 8, b // 2 + b // 4, b // 2 + 3 * (b // 8),
                 b):
        if cand >= w:
            return cand
    return b


def _pad_rows(rows: list[np.ndarray]) -> tuple[np.ndarray, np.ndarray,
                                               np.ndarray]:
    """ragged float32 rows → (matrix, valid mask, lengths); the matrix
    width is bucketed (see _width_bucket) to bound compile count."""
    n = len(rows)
    longest = max((len(r) for r in rows), default=0)
    mat = np.zeros((n, _width_bucket(max(longest, 1))), dtype=np.float32)
    valid = np.zeros_like(mat, dtype=bool)
    lengths = np.zeros(n, dtype=np.int32)
    for i, r in enumerate(rows):
        mat[i, : len(r)] = r
        valid[i, : len(r)] = True
        lengths[i] = len(r)
    return mat, valid, lengths


def _index_file(path: str) -> str:
    """The file actually read for ``path`` — what checkpoint keys must
    bind (a .bam input's evidence is its .bai; rewriting the index
    must invalidate the sample's shards even when the BAM is
    untouched)."""
    from ..io import remote

    if path.endswith(".cram"):
        return path + ".crai"
    if path.endswith((".crai", ".bai")):
        return path
    if remote.exists(path + ".bai"):
        return path + ".bai"
    return path[:-4] + ".bai"


# write_roc_rows' text scratch, a thread's own: kept from block to block
# and from job to job, so a chromosome touches no fresh pages
_roc_scratch = threading.local()


def write_roc_rows(roc_fh, ref_name: str, rocs: np.ndarray) -> None:
    """One chromosome's ROC block (SLOTS rows), one format pass — shared
    by indexcov and cohortscan for byte-parity. Where the native library
    is built and the values are float32 the block is one GIL-free call
    (``native.format_fixed2_rows``: np.char.mod's bytes, a NaN's sign and
    every tie included) and +1 on ``indexcov.roc_native_blocks_total``;
    where not, NumPy formats it a cell at a time."""
    cov_col = np.char.mod(
        "%.2f", np.arange(ops.SLOTS) / (ops.SLOTS * ops.SLOTS_MID),
    )
    if rocs.dtype == np.float32 and native.get_lib() is not None:
        need = native.fixed2_rows_scratch_bytes(ref_name, cov_col,
                                                rocs.shape[0])
        out = getattr(_roc_scratch, "out", None)
        if out is None or len(out) < need:
            # rounded up, so that a longer chromosome name finds it
            # large enough
            out = _roc_scratch.out = np.empty(-(-need // 65536) * 65536,
                                              dtype=np.uint8)
        n = native.format_fixed2_rows(out, ref_name, cov_col, rocs)
        roc_fh.write(str(memoryview(out[:n]), "utf-8"))
        obs.get_registry().counter("indexcov.roc_native_blocks_total").inc()
        return
    cells = np.char.mod("%.2f", rocs.T)  # (SLOTS, S)
    roc_fh.write("".join(
        ref_name + "\t" + cov_col[i] + "\t" + "\t".join(cells[i]) + "\n"
        for i in range(ops.SLOTS)
    ))


def run_indexcov(
    bams: list[str],
    directory: str,
    sex: str = "X,Y",
    exclude_patt: str = DEFAULT_EXCLUDE,
    chrom: str = "",
    fai: str | None = None,
    extra_normalize: bool = False,
    include_gl: bool = False,
    write_html: bool = True,
    write_png: bool = True,
    checkpoint_dir: str | None = None,
    resume: bool = False,
) -> dict:
    os.makedirs(directory, exist_ok=True)
    sex_chroms = [s for s in sex.split(",") if s] if sex else []
    exclude = re.compile(exclude_patt) if exclude_patt else None

    bams = expand_globs(bams)
    refs = references(bams, fai, chrom)
    log.info("running on %d indexes", len(bams))
    from ..utils.profiling import StageTimer

    # wall-clock per pipeline stage, returned under "stages": the span
    # vocabulary of docs/observability.md, "pca" being this command's own
    timer = StageTimer()
    reg = obs.get_registry()
    # 8-way parallel index load, mirroring indexcov.go:417-434
    import concurrent.futures as cf

    ctx = obs.capture()  # a bare pool does not carry this thread's trace

    def _load(p):
        # one span an index file, on the thread that reads and scans it;
        # 500 spans of ~15 ms a cohort, too many to read the CPU clock
        # on each (obs/tracing.py). Corrupt/truncated index -> clean CLI
        # error naming the file, not a traceback (the codecs' contract
        # is typed ValueError)
        with obs.attach(ctx), timer.stage("host-decode", read_cpu=False):
            try:
                return SampleIndex(p)
            except ValueError as e:
                raise SystemExit(f"indexcov: {p}: {e}")

    with cf.ThreadPoolExecutor(max_workers=8) as ex:
        idxs = list(ex.map(_load, bams))
        names = list(ex.map(get_short_name, bams))
    n_samples = len(idxs)
    reg.counter("indexcov.indexes_total").inc(n_samples)
    reg.counter("indexcov.index_bytes_total").inc(
        sum(i.nbytes for i in idxs))

    # per-chromosome checkpointing: the shard unit is one chromosome's
    # launched QC state. Every sample contributes to every chromosome
    # (cross-sample normalization), so keys bind ALL resolved index
    # files' content identities — one stale index invalidates the run's
    # shards, a stale chromosome list only its own.
    checkpoint = None
    ck_sig = None
    if checkpoint_dir:
        from ..parallel.scheduler import file_key
        from ..resilience.checkpoint import CheckpointStore

        def _safe_key(p):
            try:
                return file_key(_index_file(p))
            except OSError:
                return (p, -1, -1)

        ck_sig = (tuple(_safe_key(b) for b in bams), sex,
                  exclude_patt, chrom, extra_normalize)
        checkpoint = CheckpointStore(checkpoint_dir, resume=resume)

    name = os.path.basename(os.path.abspath(directory))
    base = os.path.join(directory, name + "-indexcov")

    bed_fh = open(base + ".bed.gz", "wb")
    # counts its text before BGZF into indexcov.bed_text_bytes_total
    bed = BedGzStream(
        bed_fh, ("#chrom\tstart\tend\t" + "\t".join(names) + "\n").encode(),
        timer)
    roc_fh = open(base + ".roc", "w")
    roc_fh.write("#chrom\tcov\t" + "\t".join(names) + "\n")

    sexes: dict[str, np.ndarray] = {}
    pca_blocks: list[np.ndarray] = []
    totals = {"in": 0, "out": 0, "hi": 0, "low": 0}
    counters = {
        k: np.zeros(n_samples, dtype=np.int64) for k in totals
    }
    slopes = np.zeros(n_samples, dtype=np.float32)
    n_slopes = 0
    chrom_names: list[str] = []

    def _launch(ref_id, ref_name, ref_len):
        """Host prep + async device QC dispatch for one chromosome.

        One fused device call + ONE fetch per chromosome (ROC, counters,
        CN together — per-transfer latency dominates on slow links);
        ``copy_to_host_async`` starts that fetch immediately so it rides
        the link while the PREVIOUS chromosome's host formatting runs
        (the 1-deep software pipeline below hides the per-fetch
        latency of each chromosome). Empty chromosomes contribute
        nothing.
        """
        with timer.stage("device-compute"):
            with obs.span("pack", category="transfer"):
                rows = [idx.normalized_depth(ref_id) for idx in idxs]
                mat, valid, lengths = _pad_rows(rows)
            longest = int(lengths.max())
            is_sex = _same_chrom(sex_chroms, ref_name)
            if extra_normalize and not is_sex and n_samples >= 5:
                mat = np.asarray(
                    ops.normalize_across_samples(mat, lengths))
                mat = np.where(valid, mat, 0.0)
            packed_dev = None
            if longest > 0:
                packed_dev = ops.chrom_qc(*obs.h2d((mat, valid)),
                                          np.int32(longest))
                packed_dev.copy_to_host_async()
                reg.counter("indexcov.qc_dispatches_total").inc()
                reg.counter("indexcov.tile_samples_total").inc(
                    int(lengths.sum()))
        return (ref_name, ref_len, mat, valid, lengths, longest, is_sex,
                packed_dev)

    def _bed_blocks(state) -> int:
        """Hand one chromosome's bed rows to the stream, 2,048 tiles a
        block so that a big cohort's text in flight stays bounded. They
        need the host's matrix only, not the device's answer, so they
        go as soon as the chromosome is launched (or resumed) and are
        formatted under its QC, the fetch of the one before, the ROC
        rows and the quantisation. Returns the last block's ticket."""
        ref_name, _, mat, valid, _, longest, _, _ = state
        for lo in range(0, longest, 2048):
            hi = min(lo + 2048, longest)
            bed.submit(ref_name, lo, hi, mat[:, lo:hi], valid[:, lo:hi])
        return bed.last_ticket

    def _emit(state):
        nonlocal slopes, n_slopes
        (ref_name, ref_len, mat, valid, lengths, longest, is_sex,
         packed_dev) = state
        rocs = chrom_counters = chrom_cn = None
        if packed_dev is not None:
            with timer.stage("device-compute"):
                # a resumed chromosome's state is on the host already
                packed = (packed_dev if isinstance(packed_dev, np.ndarray)
                          else obs.fetch(packed_dev)[0])
                with obs.span("unpack", category="transfer"):
                    rocs, chrom_counters, chrom_cn = ops.unpack_chrom_qc(
                        packed, n_samples)

        if is_sex:
            if longest > 0:
                sexes[ref_name] = chrom_cn
        else:
            # cap at MaxCN before quantization (indexcov.go:694-698);
            # missing tail bins quantize to 0
            with timer.stage("pca"), obs.span("pack", category="transfer"):
                capped = np.where(valid, np.minimum(mat, ops.MAX_CN), 0.0)
                q = ops.quantize_depths(capped)
                q[~valid] = 0
                pca_blocks.append(q[:, :max(longest, 0)])
            if chrom_counters is not None:
                for k in counters:
                    counters[k] += chrom_counters[k]

        if longest > 0:
            with timer.stage("write-output"):
                write_roc_rows(roc_fh, ref_name, rocs)
            if (include_gl or not ref_name.startswith("GL")) and longest > 2:
                if not is_sex and longest > 100:
                    slopes += ops.update_slopes(rocs, ref_len / 1e6)
                    n_slopes += 1
                chrom_names.append(ref_name)
                if write_html:
                    # render + write pages in worker threads: the page
                    # bytes ride a (possibly slow) filesystem while the
                    # next chromosome's QC/bed/roc work proceeds; the
                    # futures are joined (and errors surfaced) before
                    # index.html is written
                    with timer.stage("plots"):
                        plot_futs.append(plot_ex.submit(
                            _plot_depth_chrom,
                            base, ref_name, mat, lengths, names,
                            interactive=n_samples <= MAX_SAMPLES,
                            write_png=write_png,
                        ))
                        plot_futs.append(plot_ex.submit(
                            _plot_roc_chrom, base, ref_name, rocs,
                            names, write_png))
                        # bound the queue: each queued depth future
                        # pins its chromosome's full (samples x bins)
                        # matrix, so joining the oldest beyond a small
                        # window caps resident memory at ~4 chroms
                        # (the serial code held 1) while keeping the
                        # render/compute overlap
                        while len(plot_futs) > 8:
                            plot_futs.pop(0).result()

    from ..plan import Executor as PlanExecutor, Step

    pex = PlanExecutor(checkpoint=checkpoint)

    def _launch_or_resume(ref_id, ref_name, ref_len):
        """One chromosome's QC as a plan Step: unless the state is
        already committed — then the stored state (device result
        fetched to host numpy) re-enters the emit pipeline with zero
        QC/device work and byte-identical downstream artifacts. The
        'shard' fault site fires per computed chromosome, uniform with
        the cohortdepth region boundary."""

        def fn():
            state = _launch(ref_id, ref_name, ref_len)
            if checkpoint is not None and state[-1] is not None:
                # host-side for pickling (unchanged bytes downstream)
                state = (*state[:-1], np.asarray(state[-1]))
            return state

        return pex.run(Step(
            key=("indexcov", ref_name), fn=fn, site="shard",
            retry=False,
            checkpoint_key=(("indexcov", ck_sig, ref_id, ref_name,
                             ref_len) if checkpoint is not None
                            else None)))

    plot_ex = cf.ThreadPoolExecutor(max_workers=4)
    plot_futs: list = []
    try:
        with bed:  # a clean exit drains the stream and writes its EOF
            pending = None  # the chromosome launched and not yet emitted
            settled = -1  # last bed ticket of the chromosome before it
            for ref_id, ref_name, ref_len in refs:
                if exclude is not None and exclude.search(ref_name):
                    continue
                # two chromosomes' matrices are alive, never a third:
                # the blocks that still read the one before `pending`
                # reach the file before the next is made
                bed.wait_through(settled)
                cur = _launch_or_resume(ref_id, ref_name, ref_len)
                cur_last = _bed_blocks(cur)
                if pending is not None:
                    _emit(pending[0])
                    settled = pending[1]
                pending = (cur, cur_last)
            if pending is not None:
                _emit(pending[0])
            with timer.stage("plots"):
                for f in plot_futs:
                    f.result()  # surface the first page-render failure
    finally:
        plot_ex.shutdown(wait=True, cancel_futures=True)
        if checkpoint is not None:
            checkpoint.close()
        bed_fh.close()
        roc_fh.close()
    if n_slopes > 0:
        slopes = slopes / np.float32(n_slopes)
    _check_sexes(sexes, sex_chroms)

    # PCA over autosome bins (indexcov.go:773-807)
    pcs = None
    var_frac = None
    n_bins = sum(b.shape[1] for b in pca_blocks)
    if n_bins >= 3 and n_samples >= 3:
        with timer.stage("pca"):
            with obs.span("pack", category="transfer"):
                # uint16 as quantised: pca_project casts on the device,
                # so the host makes no float32 copy (351 MB at 500
                # indexes) and half the bytes cross
                pca_mat = np.concatenate(pca_blocks, axis=1)
            # k clamps to the sample count: same projection values
            # (the SVD only has min(n, bins) right vectors anyway),
            # but inside pca_project's guarded domain
            pcs, var_frac = obs.fetch(*ops.pca_project(
                *obs.h2d((pca_mat,)), k=min(5, n_samples)))

    with timer.stage("write-output"):
        ped_path = _write_ped(
            base, directory, sexes, counters, names, slopes, pcs,
            [i.mapped for i in idxs], [i.unmapped for i in idxs],
        )
    if write_html:
        with timer.stage("plots"):
            _write_index_html(
                directory, base, name, sexes, counters, names, pcs,
                var_frac,
                [i.mapped for i in idxs], [i.unmapped for i in idxs],
                chrom_names, write_png=write_png,
            )
        log.info("indexcov finished: see %s/index.html", directory)
    return {
        "sexes": sexes,
        "counters": counters,
        "slopes": slopes,
        "pcs": pcs,
        "ped": ped_path,
        "bed": base + ".bed.gz",
        "roc": base + ".roc",
        "chrom_names": chrom_names,
        "stages": {k: round(v, 3) for k, v in timer.totals.items()},
    }


def _same_chrom(sex_chroms: list[str], chrom: str) -> bool:
    # tolerate chr-prefix mismatches (indexcov.go:526-547)
    for a in sex_chroms:
        if a == chrom:
            return True
        na = "chr" + a if not a.startswith("chr") else a[3:]
        if na == chrom:
            return True
    return False


def _check_sexes(obs: dict, exp: list[str]) -> None:
    if len(obs) != len(exp):
        msg = (
            f"indexcov: expected {len(exp)} sex chromosomes, found: "
            f"{len(obs)}. you can set the expected with --sex "
            f"'{','.join(obs)}'"
        )
        if len(obs) == 0 and exp != ["X", "Y"]:
            raise SystemExit("(FATAL) " + msg)
        print("(WARNING) " + msg, file=sys.stderr)


def _write_ped(base, directory, sexes, counters, samples, slopes, pcs,
               mapped, unmapped) -> str:
    """.ped columns per indexcov.go:815-894."""
    keys = sorted(sexes)
    hdr = ["CN" + k for k in keys]
    hdr += ["bins.out", "bins.lo", "bins.hi", "bins.in", "slope", "p.out"]
    n_pc = 0
    if pcs is not None:
        n_pc = min(5, pcs.shape[1])
        hdr += [f"PC{i + 1}" for i in range(n_pc)]
    has_map = any(m > 0 for m in mapped) or any(u > 0 for u in unmapped)
    if has_map:
        hdr += ["mapped", "unmapped"]
    path = base + ".ped"
    with open(path, "w") as f:
        f.write(
            "#family_id\tsample_id\tpaternal_id\tmaternal_id\tsex\t"
            "phenotype\t" + "\t".join(hdr) + "\n"
        )
        for i, s in enumerate(samples):
            inferred = (
                int(0.5 + sexes[keys[0]][i]) if keys else -9
            )
            row = ["unknown", s, "-9", "-9", str(inferred), "-9"]
            row += ["%.2f" % sexes[k][i] for k in keys]
            out, lo = counters["out"][i], counters["low"][i]
            hi, inn = counters["hi"][i], counters["in"][i]
            row += [str(out), str(lo), str(hi), str(inn),
                    "%.3f" % slopes[i],
                    "%.2f" % (out / inn if inn else float("inf"))]
            if pcs is not None:
                row += ["%.2f" % pcs[i, j] for j in range(n_pc)]
            if has_map:
                row += [str(mapped[i]), str(unmapped[i])]
            f.write("\t".join(row) + "\n")
    return path


def _plot_depth_chrom(base, chrom, mat, lengths, names, interactive,
                      write_png):
    # numpy end-to-end: these series carry whole-genome tile vectors and
    # Python-list round trips were ~20% of e2e wall
    x = np.arange(mat.shape[1], dtype=np.float64) * TILE
    width = 0.4 if len(names) <= 30 else (0.3 if len(names) <= 50 else 0.2)
    series = [
        {"label": names[k], "x": x[: lengths[k]],
         "y": mat[k, : lengths[k]], "width": width}
        for k in range(len(names))
    ]
    if interactive:
        div, js = report.line_chart(
            "depth", series, f"position on {chrom}", "scaled coverage",
            y_max=2.5,
        )
        report.write_page(
            f"{base}-depth-{chrom}.html", f"depth {chrom}", [(div, js)],
            nav_html='<nav><a href="index.html">back to index</a></nav>',
        )
    if write_png:
        sub = 1 + len(x) // 2000
        report.save_png(f"{base}-depth-{chrom}.png", series,
                        f"position on {chrom}", "scaled coverage",
                        y_max=2.5, subsample=sub)


def _plot_roc_chrom(base, chrom, rocs, names, write_png):
    x = np.arange(ops.SLOTS, dtype=np.float64) / (ops.SLOTS * ops.SLOTS_MID)
    n_bg = report._n_backgrounds()  # plot.go:338-341 relabels them
    series = [
        {"label": "background" if k < n_bg else names[k],
         "x": x, "y": rocs[k]}
        for k in range(len(names))
    ]
    div, js = report.line_chart(
        "roc", series, "scaled coverage", "proportion of regions covered",
        legend=False, stepped=False,
    )
    report.write_page(
        f"{base}-roc-{chrom}.html", f"ROC {chrom}", [(div, js)],
        nav_html='<nav><a href="index.html">back to index</a></nav>',
    )
    if write_png:
        report.save_png(f"{base}-roc-{chrom}.png", series,
                        "scaled coverage", "proportion of regions covered")


def _write_index_html(directory, base, name, sexes, counters, samples, pcs,
                      var_frac, mapped, unmapped, chrom_names, write_png):
    charts = []
    keys = sorted(sexes)
    if len(keys) >= 2:
        # background samples are excluded from the sex scatter entirely
        # (plot.go:443-445)
        bg = report._n_backgrounds()
        pts = [{
            "label": "samples",
            "x": sexes[keys[0]][bg:].tolist(),
            "y": sexes[keys[1]][bg:].tolist(),
            "names": samples[bg:],
        }]
        charts.append(report.scatter_chart(
            "sex", pts, f"inferred copy number for {keys[0]}",
            f"inferred copy number for {keys[1]}"))
        if write_png:
            report.save_png(f"{base}-sex.png", pts,
                            f"CN {keys[0]}", f"CN {keys[1]}", kind="scatter")
    inn = np.maximum(counters["in"], 1)
    pts_bins = [{
        "label": "samples",
        "x": counters["in"].tolist(),
        "y": counters["out"].tolist(),
        "names": samples,
    }]
    charts.append(report.scatter_chart(
        "bins", pts_bins, "bins with depth in (0.85, 1.15)",
        "bins with depth outside (0.85, 1.15)"))
    if pcs is not None and var_frac is not None:
        charts.append(report.scatter_chart(
            "pca12",
            [{"label": "samples", "x": pcs[:, 0].tolist(),
              "y": pcs[:, 1].tolist(), "names": samples}],
            f"PC1 ({100 * var_frac[0]:.1f}% variance)",
            f"PC2 ({100 * var_frac[1]:.1f}% variance)"))
        if pcs.shape[1] > 2:
            charts.append(report.scatter_chart(
                "pca13",
                [{"label": "samples", "x": pcs[:, 0].tolist(),
                  "y": pcs[:, 2].tolist(), "names": samples}],
                "PC1", f"PC3 ({100 * var_frac[2]:.1f}% variance)"))
    if any(mapped) or any(unmapped):
        charts.append(report.scatter_chart(
            "mapped",
            [{"label": "samples", "x": [float(m) for m in mapped],
              "y": [float(u) for u in unmapped], "names": samples}],
            "mapped reads", "unmapped reads"))
    links = "".join(
        f'<li><a href="{os.path.basename(base)}-depth-{c}.html">depth {c}'
        f'</a> / <a href="{os.path.basename(base)}-roc-{c}.html">ROC {c}'
        f"</a></li>"
        for c in chrom_names
    )
    extra = f"<h2>chromosomes</h2><ul>{links}</ul>"
    report.write_page(
        os.path.join(directory, "index.html"),
        f"indexcov: {name}", charts, extra_html=extra,
    )


def main(argv=None):
    p = argparse.ArgumentParser(
        "goleft-tpu indexcov",
        description="cohort coverage QC from BAM/CRAM indexes only",
    )
    p.add_argument("-d", "--directory", required=True,
                   help="directory for output files")
    p.add_argument("-e", "--includegl", action="store_true",
                   help="plot GL chromosomes")
    p.add_argument("-p", "--excludepatt", default=DEFAULT_EXCLUDE,
                   help="regex of chromosomes to exclude")
    p.add_argument("-X", "--sex", default="X,Y",
                   help="comma-delimited sex chromosomes ('' for none)")
    p.add_argument("-c", "--chrom", default="",
                   help="optional chromosome to restrict")
    p.add_argument("-f", "--fai", default=None,
                   help="fasta index; required for crais")
    p.add_argument("-n", "--extranormalize", action="store_true",
                   help="normalize across samples (recommended for CRAI)")
    p.add_argument("--no-html", action="store_true",
                   help="skip html/png reports")
    p.add_argument("--checkpoint-dir", default=None,
                   help="per-chromosome QC checkpoint store "
                        "(docs/resilience.md); with --resume, "
                        "committed chromosomes skip index/QC work "
                        "with byte-identical artifacts")
    p.add_argument("--resume", action="store_true",
                   help="replay the checkpoint journal and skip "
                        "committed chromosomes (requires "
                        "--checkpoint-dir)")
    p.add_argument("bam", nargs="+", help="bam(s)/bai(s)/crai(s)")
    a = p.parse_args(argv)
    if a.resume and not a.checkpoint_dir:
        p.error("--resume requires --checkpoint-dir")
    run_indexcov(
        a.bam, a.directory, sex=a.sex, exclude_patt=a.excludepatt,
        chrom=a.chrom, fai=a.fai, extra_normalize=a.extranormalize,
        include_gl=a.includegl, write_html=not a.no_html,
        write_png=not a.no_html, checkpoint_dir=a.checkpoint_dir,
        resume=a.resume,
    )


if __name__ == "__main__":
    main()
