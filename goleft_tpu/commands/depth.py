"""depth: windowed depth + callable-region classification on the TPU.

The reference shells out to ``samtools depth`` per 10Mb shard and parses
per-base text (depth/depth.go:45,236-364). Here the BAM is decoded once on
the host into columnar ref-aligned segments (BAI linear-index seek per
shard) and depth is a scatter-add + cumsum device kernel
(ops/depth_pipeline.py); window means and callable classes come back as
arrays and are written as the same two BED files:

  <prefix>.depth.bed     chrom  s  e  %.4g-mean [gc cpg masked with -s]
  <prefix>.callable.bed  chrom  s  e  NO_/LOW_/CALLABLE/EXCESSIVE_COVERAGE

Semantics preserved from the reference:
  - windows aligned to absolute coordinates, clipped to the region, mean
    denominator = clipped span (depth/depth.go:293-305, 329-341)
  - per-base classes with NO_COVERAGE gap fill (":307-323, 343-359");
    class thresholds at getCovClass (":223-234")
  - shard step = 10Mb rounded to a window multiple (":48,130-132")
  - samtools flags inherited: -Q mapq cutoff (keep mapq ≥ Q), skip
    UNMAP/SECONDARY/QCFAIL/DUP, per-base cap -d = MaxMeanDepth+2500
    (":45,116"); deletions/ref-skips don't count (M/=/X blocks only)
  - -b BED restricts to listed regions; ``-s`` appends GC/CpG/masked
    ("%.3g") per window (":191-200")
"""

from __future__ import annotations

import argparse

import functools
import os
import sys

import numpy as np

from ..io.bai import read_bai, query_voffset
from ..io.bam import ReadColumns, open_bam_file
from ..io.fai import Faidx, read_fai
from .. import obs
from ..ops.coverage import (
    bucket_size, pack_segments_u16, run_length_encode, window_bounds,
    CLASS_NAMES,
)
from ..ops.depth_pipeline import (
    shard_depth_pipeline_cls_packed,
    shard_depth_pipeline_packed_cls_packed, unpack_cls_2bit,
)
from ..utils.xopen import xopen

STEP = 10_000_000  # shard size, depth/depth.go:48
DEPTH_CAP_EXTRA = 2500  # -d = MaxMeanDepth + 2500, depth/depth.go:116


def gen_regions(
    fai_records, chrom: str, window: int, bed: str | None
) -> list[tuple[str, int, int]]:
    """(chrom, start, end) 0-based half-open shards (depth.go:103-159)."""
    if bed:
        out = []
        with xopen(bed) as fh:
            for lineno, line in enumerate(fh, 1):
                line = line.strip()
                if not line or line.startswith(("#", "track")):
                    continue
                t = line.split("\t")
                if len(t) < 3:
                    raise ValueError(
                        f"{bed}:{lineno}: bed line needs chrom/start/"
                        f"end, got {len(t)} fields"
                    )
                try:
                    out.append((t[0], max(int(t[1]), 0), int(t[2])))
                except ValueError:
                    raise ValueError(
                        f"{bed}:{lineno}: non-integer bed coordinate"
                    )
        return out
    step = max(1, STEP // window) * window
    out = []
    for rec in fai_records:
        if chrom and rec.name != chrom:
            continue
        for i in range(0, rec.length, step):
            out.append((rec.name, i, min(i + step, rec.length)))
    return out


_EMPTY_SEGS = (np.empty(0, np.int32), np.empty(0, np.int32))


@functools.lru_cache(maxsize=None)
def _batched_cls_packed():
    """Jitted vmap of the per-sample shard pipeline over a batch axis —
    the serve daemon's micro-batched depth pass (one device dispatch
    for a whole batch of requests' samples on the same region). Built
    lazily so importing this module keeps its no-jax-at-import
    discipline; cached so every batch geometry reuses one wrapper."""
    import jax

    @functools.partial(jax.jit, static_argnames=("length", "window"))
    def fn(seg_s, seg_e, keep, w0, rs, re, cap, mincov, maxmean,
           length, window):
        pipe = functools.partial(shard_depth_pipeline_cls_packed,
                                 length=length, window=window)
        return jax.vmap(
            lambda a, b, c: pipe(a, b, c, w0, rs, re, cap, mincov,
                                 maxmean)
        )(seg_s, seg_e, keep)

    return fn


def _decode_shard_segments(bam, bai, tid: int, start: int, end: int,
                           min_mapq: int, flag_mask: int = 0x704):
    """Host decode of the shard's FILTERED clipped segment endpoints —
    what the device pipeline actually consumes. BamFile handles stream
    them through the C walk shared with the cohort reduce engines
    (io/bam.py::read_segments: no column arrays, no uncompressed-body
    materialization); CRAM handles fall back to columns + the shared
    filter/clip helper. Returns (seg_start, seg_end); pair with an
    all-true keep mask."""
    from ..io.bam import filter_clip_segments

    if tid < 0:
        return _EMPTY_SEGS
    rs = getattr(bam, "read_segments", None)
    if rs is not None and bai is not None:
        voff = query_voffset(bai, tid, start)
        if voff is None:
            return _EMPTY_SEGS
        return rs(tid, start, end, min_mapq, flag_mask, voffset=voff)
    cols = _decode_shard(bam, bai, tid, start, end)
    return filter_clip_segments(cols, start, end, min_mapq, flag_mask)


def _decode_shard(bam, bai, tid: int, start: int, end: int) -> ReadColumns:
    """Host decode of records overlapping [start, end) on tid.

    ``bam`` is an open_bam() handle: the native C++ decoder when
    available (lazy handles inflate only the shard's block range,
    GIL-free), else the pure-Python streaming reader. The BAI linear
    index bounds the block window on both sides; CRAM handles (bai is
    None) do their own .crai-driven container selection.
    """
    if tid < 0:
        return ReadColumns.empty()
    if bai is None:
        return bam.read_columns(tid=tid, start=start, end=end)
    voff = query_voffset(bai, tid, start)
    if voff is None:
        return ReadColumns.empty()
    end_voff = query_voffset(bai, tid, end)
    return bam.read_columns(tid=tid, start=start, end=end, voffset=voff,
                            end_voffset=end_voff)


class DepthEngine:
    """Reusable shard→(window sums, classes) runner over
    stream-extracted segment endpoints (_decode_shard_segments feeds
    it here; multidepth shares the same decode helper)."""

    def __init__(self, window: int, min_cov: int, max_mean_depth: int,
                 mapq: int, max_span: int = STEP,
                 packed: bool | None = None):
        """``max_span`` = max over regions of (end - aligned_origin) —
        the longest per-base buffer any shard needs. ``packed`` ships
        segments as u16 delta+length (4 bytes/segment vs 9) and
        reconstructs on device, with automatic fallback to the unpacked
        path for ultra-long segments (≥ 65536 bases). Default (None):
        enabled when the host has cores to spare — packing trades host
        cycles for link bytes, a win exactly when decode threads aren't
        already saturating the CPU."""
        self.window = window
        self.min_cov = min_cov
        self.max_mean = max_mean_depth
        self.mapq = mapq
        if packed is None:
            packed = (os.cpu_count() or 1) >= 4
        self.packed = packed
        self.cap = max_mean_depth + DEPTH_CAP_EXTRA
        # one static length (a multiple of the reshape window covering the
        # longest region from its aligned origin) → one XLA compile per
        # segment bucket for the whole genome. Windows larger than the
        # span mean every region fits one absolute window, so the reshape
        # uses the whole buffer as a single window.
        if window >= max_span:
            self.w_eff = ((max_span + 1023) // 1024) * 1024
            self.length = self.w_eff
        else:
            self.w_eff = window
            self.length = (max_span + window - 1) // window * window

    def run_segments(self, seg_start, seg_end, kp, start: int,
                     end: int):
        """Core shard runner over stream-extracted (or pre-filtered
        column-decoded) segment endpoint arrays. ``kp=None`` means all
        segments are already keepers (the _decode_shard_segments
        contract) and skips the mask copies on the hot path."""
        w0 = start // self.window * self.window
        assert end - w0 <= self.length
        n = len(seg_start)
        scalars = (np.int32(w0), np.int32(start), np.int32(end),
                   np.int32(self.cap), np.int32(self.min_cov),
                   np.int32(self.max_mean))
        sel = slice(None) if kp is None else kp
        with obs.span("pack", category="transfer"):
            packed = pack_segments_u16(seg_start, seg_end, sel) \
                if self.packed else None
            if packed is not None:
                d, l, base, n_ent = packed
                b = bucket_size(max(n_ent, 1))
                dd = np.zeros(b, np.uint16)
                ll = np.zeros(b, np.uint16)
                dd[:n_ent] = d
                ll[:n_ent] = l
                pipeline = shard_depth_pipeline_packed_cls_packed
                wire, rest = (dd, ll), (base,)
            else:
                b = bucket_size(n)
                seg_s = np.full(b, 0, dtype=np.int32)
                seg_e = np.full(b, 0, dtype=np.int32)
                keep = np.zeros(b, dtype=bool)
                if n:
                    seg_s[:n] = seg_start
                    seg_e[:n] = seg_end
                    keep[:n] = True if kp is None else kp
                pipeline = shard_depth_pipeline_cls_packed
                wire, rest = (seg_s, seg_e, keep), ()
        wire = obs.h2d(wire)
        sums, cls_p = obs.fetch(*pipeline(
            *wire, *rest, *scalars,
            length=self.length, window=self.w_eff,
        ))
        with obs.span("unpack", category="transfer"):
            starts, ends, _, _ = window_bounds(start, end, self.window)
            sums = sums[:len(starts)]
            # classes come back 2-bit packed (1/4 the D2H bytes) and
            # unpack on host with vectorized shifts
            cls = unpack_cls_2bit(cls_p, self.length)
            cls = cls[start - w0 : end - w0]
        return starts, ends, sums, cls

    def run_segments_batch(self, segs, start: int, end: int):
        """Batched variant of :meth:`run_segments`: B samples' already-
        filtered ``(seg_start, seg_end)`` endpoint arrays for the SAME
        region run as ONE vmapped device pass (the serve micro-batcher's
        coalesced path). Value-identical to B single-sample calls on
        either wire: per-base depths are exact small ints, window sums
        are exact ints in f32 below 2**24, and vmap adds no cross-lane
        ops. Returns (starts, ends, sums (B, n_win), cls (B, span))."""
        w0 = start // self.window * self.window
        assert end - w0 <= self.length
        B = len(segs)
        b = bucket_size(max(max((len(ss) for ss, _ in segs), default=0),
                            1))
        with obs.span("pack", category="transfer"):
            seg_s = np.zeros((B, b), np.int32)
            seg_e = np.zeros((B, b), np.int32)
            keep = np.zeros((B, b), bool)
            for i, (ss, ee) in enumerate(segs):
                n = len(ss)
                if n:
                    seg_s[i, :n] = ss
                    seg_e[i, :n] = ee
                    keep[i, :n] = True
        scalars = (np.int32(w0), np.int32(start), np.int32(end),
                   np.int32(self.cap), np.int32(self.min_cov),
                   np.int32(self.max_mean))
        wire = obs.h2d((seg_s, seg_e, keep))
        sums, cls_p = obs.fetch(*_batched_cls_packed()(
            *wire, *scalars,
            length=self.length, window=self.w_eff,
        ))
        with obs.span("unpack", category="transfer"):
            starts, ends, _, _ = window_bounds(start, end, self.window)
            sums = sums[:, :len(starts)]
            cls = np.stack([
                unpack_cls_2bit(row, self.length)[start - w0:end - w0]
                for row in cls_p
            ])
        return starts, ends, sums, cls


def write_shard_output(
    chrom: str, starts, ends, sums, cls, region_start: int,
    depth_out, call_out, fa: Faidx | None,
) -> None:
    from ..io import native

    spans = ends - starts
    means = sums / spans
    use_native = native.get_lib() is not None
    if fa is None:
        if use_native:
            depth_out.write(
                native.format_depth_rows(chrom, starts, ends, means)
                .decode("ascii")
            )
        else:
            for s, e, m in zip(starts, ends, means):
                depth_out.write(f"{chrom}\t{s}\t{e}\t{m:.4g}\n")
    else:
        for s, e, m in zip(starts, ends, means):
            st = fa.window_stats(chrom, int(s), int(e))
            depth_out.write(
                f"{chrom}\t{s}\t{e}\t{m:.4g}"
                f"\t{st['gc']:.3g}\t{st['cpg']:.3g}\t{st['masked']:.3g}\n"
            )
    rs, re_, rv = run_length_encode(cls)
    if use_native:
        call_out.write(
            native.format_class_rows(
                chrom, rs.astype(np.int64) + region_start,
                re_.astype(np.int64) + region_start, rv,
            ).decode("ascii")
        )
    else:
        for s, e, v in zip(rs, re_, rv):
            call_out.write(
                f"{chrom}\t{s + region_start}\t{e + region_start}\t"
                f"{CLASS_NAMES[v]}\n"
            )


def run_depth(
    bam: str,
    prefix: str,
    reference: str | None = None,
    fai: str | None = None,
    window: int = 250,
    min_cov: int = 4,
    max_mean_depth: int = 0,
    mapq: int = 1,
    chrom: str = "",
    bed: str | None = None,
    stats: bool = False,
    processes: int = 4,
    cache_dir: str | None = None,
    profile_dir: str | None = None,
) -> tuple[str, str]:
    handle = open_bam_file(bam, lazy=True)
    hdr = handle.header
    from ..io import remote

    if getattr(handle, "is_cram", False):
        bai = None  # CRAM random access rides the .crai inside the handle
    else:
        bai = read_bai(bam + ".bai" if remote.exists(bam + ".bai")
                       else bam[:-4] + ".bai")
    fai_path = fai or (reference + ".fai" if reference else None)
    if bed is None:
        if fai_path is None:
            raise SystemExit(
                "depth: need -r reference (with .fai) or -b bed regions"
            )
        if not remote.exists(fai_path):
            if reference and not remote.is_remote(reference) \
                    and os.path.exists(reference):
                from ..io.fai import write_fai

                write_fai(reference)
            else:
                raise SystemExit(f"depth: fasta index not found: {fai_path}")
        fai_records = read_fai(fai_path)
    else:
        fai_records = []
    regions = gen_regions(fai_records, chrom, window, bed)

    fa = Faidx(reference) if stats and reference else None
    max_span = max(
        (e - (s // window) * window for _, s, e in regions), default=1
    )
    engine = DepthEngine(window, min_cov, max_mean_depth, mapq,
                         max_span=max_span)

    suffix = f".{chrom}" if chrom else ""
    depth_path = f"{prefix}{suffix}.depth.bed"
    call_path = f"{prefix}{suffix}.callable.bed"
    tid_of = {n: i for i, n in enumerate(hdr.ref_names)}

    from ..parallel.scheduler import ResultCache, file_key, run_sharded
    from ..utils.profiling import StageTimer, trace

    rc = ResultCache(cache_dir) if cache_dir else None
    fkey = file_key(bam) if cache_dir else bam
    timer = StageTimer()
    reg = obs.get_registry()

    def shard_fn(c, s, e, _fk):
        with timer.stage("host-decode"):
            seg_s, seg_e = _decode_shard_segments(
                handle, bai, tid_of.get(c, -1), s, e, mapq)
        with timer.stage("device-compute"):
            starts, ends, sums, cls = engine.run_segments(
                seg_s, seg_e, None, s, e)
        return starts, ends, sums, cls

    params = (window, min_cov, max_mean_depth, mapq)
    tasks = [(c, s, e, (fkey, params)) for (c, s, e) in regions]
    n_failed = 0
    with trace(profile_dir), open(depth_path, "w") as dout, \
            open(call_path, "w") as cout:
        for (c, s, e), res in zip(
            regions,
            run_sharded(tasks, shard_fn, processes=processes,
                        retries=1, cache=rc, ordered=True),
        ):
            reg.counter("depth.shards_total").inc()
            if res.error is not None:
                # reference behavior: failed shard reports in red, others
                # keep going, nonzero exit at the end
                # (depth/depth.go:395-399, fatih/color banner)
                msg = f"ERROR with shard {c}:{s}-{e}: {res.error}"
                if sys.stderr.isatty():
                    msg = f"\033[31m{msg}\033[0m"
                print(msg, file=sys.stderr)
                n_failed += 1
                reg.counter("depth.shards_failed_total").inc()
                continue
            starts, ends, sums, cls = res.value
            with timer.stage("write-output"):
                write_shard_output(c, starts, ends, sums, cls, s,
                                   dout, cout, fa)
    if profile_dir:
        timer.log_report()
    if n_failed:
        raise SystemExit(1)
    return depth_path, call_path


def main(argv=None):
    p = argparse.ArgumentParser(
        "goleft-tpu depth",
        description="windowed depth + callable regions via the TPU engine",
    )
    p.add_argument("-w", "--windowsize", type=int, default=250)
    p.add_argument("-m", "--maxmeandepth", type=int, default=0,
                   help="per-base depths >= this are EXCESSIVE_COVERAGE")
    p.add_argument("-Q", "--mapq", type=int, default=1,
                   help="mapping quality cutoff (keep >= Q)")
    p.add_argument("-c", "--chrom", default="")
    p.add_argument("--mincov", type=int, default=4,
                   help="minimum depth considered callable")
    p.add_argument("-o", "--ordered", action="store_true",
                   help="accepted for reference-CLI parity; output here "
                        "is ALWAYS in input order (the shard scheduler "
                        "consumes results ordered even with -p)")
    p.add_argument("-s", "--stats", action="store_true",
                   help="report GC CpG masked stats per window")
    p.add_argument("-r", "--reference", default=None,
                   help="reference fasta (with .fai)")
    p.add_argument("-p", "--processes", type=int, default=4)
    p.add_argument("-b", "--bed", default=None,
                   help="restrict to regions in this bed")
    p.add_argument("--cache", default=None,
                   help="shard result-cache directory (resume support)")
    p.add_argument("--profile", default=None,
                   help="write a JAX profiler trace to this directory")
    p.add_argument("--prefix", required=True)
    from . import add_no_crc_flag, apply_no_crc

    add_no_crc_flag(p)
    p.add_argument("bam")
    a = p.parse_args(argv)
    apply_no_crc(a.no_crc)
    run_depth(
        a.bam, a.prefix, reference=a.reference, window=a.windowsize,
        min_cov=a.mincov, max_mean_depth=a.maxmeandepth, mapq=a.mapq,
        chrom=a.chrom, bed=a.bed, stats=a.stats, processes=a.processes,
        cache_dir=a.cache, profile_dir=a.profile,
    )


if __name__ == "__main__":
    main()
