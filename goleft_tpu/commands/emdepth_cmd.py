"""emdepth: EM copy-number calls from a depth matrix.

The reference ships emdepth as a library only (SURVEY.md §2.3); this
command exposes the batched TPU kernel on a depthwed-style matrix
(#chrom start end sample...), writing per-sample CNV calls as
  chrom  start  end  sample  CN  log2FC
after the streaming 30kb-gap merge (models/emdepth.py Cache).
"""

from __future__ import annotations

import argparse
import sys

import numpy as np

from .. import obs
from ..models import emdepth as em
from ..utils.xopen import xopen


def read_matrix(path: str):
    """depthwed matrix → (chroms, starts, ends, depths (B,S), samples)."""
    from ..utils.dtypes import preferred_float

    chroms, starts, ends, rows = [], [], [], []
    with xopen(path) as fh:
        header = fh.readline().rstrip("\n").split("\t")
        samples = header[3:]
        for line in fh:
            t = line.rstrip("\n").split("\t")
            chroms.append(t[0])
            starts.append(int(t[1]))
            ends.append(int(t[2]))
            rows.append([float(x) for x in t[3:]])
    return (np.array(chroms), np.array(starts), np.array(ends),
            np.array(rows, dtype=preferred_float()), samples)


EM_CHUNK = 16384  # windows per device batch


def _norm_chunk(chunk: np.ndarray, med, medmed, dtype) -> np.ndarray:
    """Per-chunk normalization in the compute dtype.

    Applies exactly the elementwise ``v / med * median(med)`` the full-
    matrix path used, so results are bitwise identical — but only one
    chunk ever materializes in float. This is what lets ``cnv`` hold
    the whole-genome cohort matrix as int16 window means (the hybrid
    engine caps depth at 2500, so means always fit) instead of f64:
    500-sample WGS at 250bp drops from ~48GB to ~12GB peak RSS."""
    c = np.asarray(chunk, dtype=dtype)
    if med is None:
        return c
    if c is chunk:  # same-dtype input came through as a view
        c = c.copy()  # never mutate the caller's matrix
    m = med.astype(dtype)
    if c.ndim == 2:
        m = m[None, :]
    # in-place: the chunk is the transient peak at cohort scale, so
    # apply both ops without temporaries (same elementwise values)
    np.divide(c, m, out=c)
    np.multiply(c, np.dtype(dtype).type(medmed), out=c)
    return c


def _batched_em(depths: np.ndarray, med=None, medmed=None,
                dtype=None, want_cn: bool = True):
    """Run the EM chunk by chunk: one chunk of all the windows where they
    fit in EM_CHUNK, unpadded; else EM_CHUNK windows a chunk with the last
    padded with ones and sliced off, so whole-genome matrices (300k
    windows × 2504 samples ≈ 3GB f32) stream through the device with ONE
    compile. ``med``/``medmed`` apply the median normalization lazily per
    chunk (see _norm_chunk); outputs fill preallocated arrays so nothing
    is double-held, and the (B,S) CN matrix is only produced when the
    caller writes it (want_cn).

    Each chunk is a ``device-compute`` span: its ``pack`` and ``h2d``
    (the next chunk's ride inside it, staged while this one computes),
    then ``device-wait`` and ``d2h`` of its results."""
    from ..utils.dtypes import preferred_float

    import jax

    dtype = dtype or (depths.dtype if depths.dtype.kind == "f"
                      else preferred_float())
    B, S = depths.shape
    if B == 0:  # no chunk: empty results of the shapes a chunk gives
        return (np.empty((0, em.N_LAMBDA), dtype),
                np.empty((0, S), np.int32) if want_cn else None)
    rows = min(B, EM_CHUNK)

    # multi-chip: the window axis is embarrassingly parallel, so padded
    # chunks shard across this host's devices and XLA partitions the
    # row-wise EM as pure SPMD (no collectives); EM_CHUNK rows divide
    # evenly. LOCAL devices only, and only in a single-process world: in
    # a multi-host cnv run process 0 alone reaches the EM (the others
    # returned after the gather), so a global mesh would address remote
    # devices whose processes are gone and hang the SPMD program.
    sharding = None
    devs = jax.local_devices()
    if (rows == EM_CHUNK and jax.process_count() == 1 and len(devs) > 1
            and EM_CHUNK % len(devs) == 0):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        sharding = NamedSharding(Mesh(np.array(devs), ("w",)),
                                 PartitionSpec("w", None))

    def staged(lo):
        with obs.span("pack", category="transfer"):
            chunk = _norm_chunk(depths[lo : lo + rows], med, medmed,
                                dtype)
            n = len(chunk)
            if n < rows:
                pad = np.ones((rows - n, S), chunk.dtype)
                chunk = np.concatenate([chunk, pad])
        return obs.h2d((chunk,), sharding)[0], n

    lams = cns = None
    offsets = range(0, B, rows)
    pending = None
    for lo in offsets:
        with obs.span("device-compute", category="stage"):
            dev, n = pending if pending else staged(lo)
            # dispatch chunk k's device work FIRST (async), then do chunk
            # k+1's host normalization + H2D while the device computes
            lam_dev = em.em_depth_batch(dev)
            out = (lam_dev, em.cn_batch(lam_dev, dev)) if want_cn else (
                lam_dev,)
            pending = staged(lo + rows) if lo + rows < B else None
            got = obs.fetch(*out)
        if lams is None:
            lams = np.empty((B,) + got[0].shape[1:], got[0].dtype)
            if want_cn:
                cns = np.empty((B,) + got[1].shape[1:], got[1].dtype)
        lams[lo : lo + n] = got[0][:n]
        if want_cn:
            cns[lo : lo + n] = got[1][:n]
    reg = obs.get_registry()
    reg.counter("emdepth.windows_total").inc(B)
    reg.counter("emdepth.chunks_total").inc(len(offsets))
    return lams, cns


def run_emdepth(matrix_path: str, out=None, normalize: bool = True,
                matrix_out: str | None = None,
                vcf_out: str | None = None,
                mops_out: str | None = None,
                gain_out: str | None = None,
                candidates_out: str | None = None):
    with obs.span("host-decode", category="stage"):
        matrix = read_matrix(matrix_path)
    return call_cnvs(*matrix, out=out,
                     normalize=normalize, matrix_out=matrix_out,
                     vcf_out=vcf_out, mops_out=mops_out,
                     gain_out=gain_out, candidates_out=candidates_out)


def _mops_outputs(chroms, starts, ends, depths, samples, med, medmed,
                  dtype, mops_out: str | None, gain_out: str | None):
    """cn.mops posterior outputs over the same normalized matrix the EM
    consumes: per-window posterior CN matrix (argmax over the α_ik
    posterior, models/mops.py) and/or per-window information gain
    (windows where the cohort deviates from all-CN2 — the cn.mops
    segmentation statistic, mops.go:126-137). Streams in EM_CHUNK
    batches with the ragged tail padded to the chunk shape (ones, like
    _batched_em) so mops_batch compiles exactly once; this optional
    pass runs the matrix through the device a second time, separate
    from the EM's double-buffered loop."""
    from ..models import mops

    fhs = {}
    if mops_out:
        fhs["cn"] = xopen(mops_out, "w")
        fhs["cn"].write("#chrom\tstart\tend\t" + "\t".join(samples)
                        + "\n")
    if gain_out:
        fhs["gain"] = xopen(gain_out, "w")
        fhs["gain"].write("#chrom\tstart\tend\tgain\n")
    try:
        B = len(depths)
        for lo in range(0, B, EM_CHUNK):
            chunk = _norm_chunk(depths[lo : lo + EM_CHUNK], med, medmed,
                                dtype)
            n = len(chunk)
            if B > EM_CHUNK and n < EM_CHUNK:
                pad = np.ones((EM_CHUNK - n, depths.shape[1]),
                              chunk.dtype)
                chunk = np.concatenate([chunk, pad])
            r = mops.mops_batch(chunk)
            if "cn" in fhs:
                cn = np.asarray(mops.posterior_cn(r["aik"]))[:n]
                for i in range(len(cn)):
                    b = lo + i
                    fhs["cn"].write(
                        f"{chroms[b]}\t{starts[b]}\t{ends[b]}\t"
                        + "\t".join(str(int(c)) for c in cn[i]) + "\n"
                    )
            if "gain" in fhs:
                g = np.asarray(mops.information_gain(r["aik"]))[:n]
                for i in range(len(g)):
                    b = lo + i
                    fhs["gain"].write(
                        f"{chroms[b]}\t{starts[b]}\t{ends[b]}\t"
                        f"{float(g[i]):.4f}\n"
                    )
    finally:
        for fh in fhs.values():
            fh.close()


def call_cnvs(chroms, starts, ends, depths, samples, out=None,
              normalize: bool = True, matrix_out: str | None = None,
              vcf_out: str | None = None, mops_out: str | None = None,
              gain_out: str | None = None,
              contig_lengths: dict | None = None,
              ref_fasta: str | None = None,
              ref_fai: str | None = None,
              candidates_out: str | None = None):
    """EM copy-number calls from in-memory matrix arrays (the device
    pipeline's native feed — ``cnv`` passes cohortdepth's blocks here
    directly, no text round-trip)."""
    out = out or sys.stdout
    if len(depths) == 0:
        return
    from ..utils.dtypes import preferred_float

    dt = depths.dtype if depths.dtype.kind == "f" else preferred_float()
    med = medmed = None
    if normalize:
        # scale each sample to its median so depths are comparable; the
        # reference expects pre-normalized input (emdepth.go:7).
        # Column-at-a-time so integer matrices never convert wholesale
        # to f64 (np.median would copy the full matrix); normalization
        # itself is applied lazily per EM chunk (_norm_chunk).
        with obs.span("normalize", category="stage"):
            med = np.empty(depths.shape[1], dtype=np.float64)
            for j in range(depths.shape[1]):
                med[j] = np.median(depths[:, j])
            med[med == 0] = 1.0
            medmed = float(np.median(med))

    if mops_out or gain_out:
        _mops_outputs(chroms, starts, ends, depths, samples, med,
                      medmed, dt, mops_out, gain_out)
    lambdas, cns = _batched_em(depths, med, medmed, dt,
                               want_cn=matrix_out is not None)
    if matrix_out:
        with obs.span("write-output", category="stage"), \
                open(matrix_out, "w") as mf:
            mf.write("#chrom\tstart\tend\t" + "\t".join(samples) + "\n")
            for b in range(len(cns)):
                mf.write(
                    f"{chroms[b]}\t{starts[b]}\t{ends[b]}\t"
                    + "\t".join(str(int(c)) for c in cns[b]) + "\n"
                )
    results = []

    def emit(cnvs, chrom):
        for c in cnvs:
            results.append(
                (chrom, c.positions[0][0], c.positions[-1][1],
                 samples[c.sample_i],
                 int(round(np.median(c.cn))),
                 float(np.mean(c.log2fc)))
            )

    # hoisted normalization constants: the per-window loop runs B times
    # and must not re-cast the med vector each iteration
    med_dt = med.astype(dt) if med is not None else None
    mm = np.dtype(dt).type(medmed) if med is not None else None
    # a CN row the merge reads comes from the chunk's CN matrix where
    # one was made (--matrix-out), else from one dispatch a window; both
    # counters exist, at 0 or not, once a merge has run
    reg = obs.get_registry()
    reg.counter("emdepth.cn_rows_from_chunk_total")
    reg.counter("emdepth.cn_dispatches_total")
    with obs.span("merge", category="stage"):
        cache = em.Cache()
        cur = None
        for b in range(len(depths)):
            if chroms[b] != cur:
                emit(cache.clear(None), cur)
                cache = em.Cache()
                cur = chroms[b]
            row = depths[b].astype(dt)  # always a fresh copy
            if med_dt is not None:
                np.divide(row, med_dt, out=row)
                np.multiply(row, mm, out=row)
            e = em.EMD(lambdas[b], row, int(starts[b]), int(ends[b]),
                       cns[b] if cns is not None else None)
            emit(cache.add(e), cur)
        emit(cache.clear(None), cur)
    reg.counter("emdepth.calls_total").inc(len(results))
    with obs.span("write-output", category="stage"):
        out.write("#chrom\tstart\tend\tsample\tCN\tlog2FC\n")
        for chrom, s, e, sample, cn, fc in results:
            out.write(f"{chrom}\t{s}\t{e}\t{sample}\t{cn}\t{fc:.3f}\n")
    if vcf_out:
        from ..utils.vcf import write_cnv_vcf

        write_cnv_vcf(vcf_out, results, samples,
                      contig_lengths=contig_lengths,
                      ref_fasta=ref_fasta, ref_fai=ref_fai)
    if candidates_out:
        # the machine-readable handoff to `pairhmm --candidates`: the
        # same merged calls as the stdout table, stable schema
        from ..models.candidates import (
            candidates_from_calls, write_candidates,
        )

        write_candidates(candidates_out,
                         candidates_from_calls(results), "emdepth")
    return results


def main(argv=None):
    p = argparse.ArgumentParser(
        "goleft-tpu emdepth",
        description="EM copy-number calls from a depthwed matrix",
    )
    p.add_argument("--no-normalize", action="store_true",
                   help="input is already normalized")
    p.add_argument("--matrix-out", default=None,
                   help="also write the per-window CN matrix here")
    p.add_argument("--vcf", default=None,
                   help="also write merged CNV calls as VCF 4.2 "
                        "(<DEL>/<DUP> symbolic alleles, GT:CN:L2FC)")
    p.add_argument("--mops-out", default=None,
                   help="write the cn.mops posterior-CN matrix here")
    p.add_argument("--gain-out", default=None,
                   help="write per-window cn.mops information gain here")
    p.add_argument("--candidates-out", default=None, metavar="FILE",
                   help="export the merged CNV calls as candidate "
                        "intervals (BED-style TSV, or JSON for "
                        "*.json) — the `pairhmm --candidates` input")
    p.add_argument("matrix", help="depthwed-style matrix (tsv/gz)")
    a = p.parse_args(argv)
    run_emdepth(a.matrix, normalize=not a.no_normalize,
                matrix_out=a.matrix_out, vcf_out=a.vcf,
                mops_out=a.mops_out, gain_out=a.gain_out,
                candidates_out=a.candidates_out)


if __name__ == "__main__":
    main()
