"""Biobank-scale cohort QC end-to-end: the ``make biobank-smoke``
body.

A hermetic 15-sample 3-chromosome BAM cohort staged into a loopback
:mod:`~goleft_tpu.io.remote_stub` object store, driven through the
real ``goleft-tpu cohortscan`` CLI in subprocesses:

  1. **URL byte-identity**: a 12-sample cohort of ``http://`` URLs
     scans byte-identical (bed.gz content / .roc / .ped) to one-shot
     local ``indexcov`` — the streaming chunked path over the ranged-
     read data plane reproduces the monolithic artifacts exactly.
  2. **append-k incrementality**: 3 more samples appended to the same
     output directory with ``--resume`` perform EXACTLY 3×n_chroms
     per-sample QC computations (pinned via the committed manifest's
     counters; the original 12 samples' blocks all resume by content
     key) and the artifacts are byte-identical to a fresh 15-sample
     one-shot ``indexcov``.
  3. **crash-resume**: a SIGKILL injected mid-scan
     (``--inject-faults shard:...:kill``) followed by ``--resume``
     lands on the same bytes, with the pre-kill commits replayed from
     the checkpoint journal instead of recomputed.

Host-pinned (JAX_PLATFORMS=cpu) like every other smoke. Run
directly::

    python -m goleft_tpu.cohort.biobank_smoke
"""

from __future__ import annotations

import gzip
import hashlib
import json
import os
import subprocess
import sys
import tempfile
import time

REFS = ("chr1", "X", "Y")
LENS = (900_000, 400_000, 200_000)


def _make_biobank_cohort(d: str, n: int = 15):
    """n BAMs (+.bai) over chr1/X/Y with alternating 'male'/'female'
    coverage so sex inference has real work, plus the .fai."""
    import numpy as np

    from ..io.bai import build_bai, write_bai
    from ..io.bam import BamWriter

    rng = np.random.default_rng(29)
    header = "@HD\tVN:1.6\tSO:coordinate\n" + "".join(
        f"@SQ\tSN:{r}\tLN:{ln}\n" for r, ln in zip(REFS, LENS))
    bams = []
    for i in range(n):
        male = i % 2 == 0
        counts = [2500,
                  (2500 * LENS[1] // LENS[0]) // (2 if male else 1),
                  (2500 * LENS[2] // LENS[0]) // 2 if male else 0]
        p = os.path.join(d, f"s{i:03d}.bam")
        with open(p, "wb") as fh:
            with BamWriter(fh, header
                           + f"@RG\tID:r\tSM:s{i:03d}\n",
                           list(REFS), list(LENS), level=1) as w:
                for tid, cnt in enumerate(counts):
                    if not cnt:
                        continue
                    starts = np.sort(rng.integers(
                        0, LENS[tid] - 150, size=cnt))
                    for j, s in enumerate(starts):
                        w.write_record(tid, int(s), [(100, 0)],
                                       mapq=60, name=f"r{tid}_{j}")
        write_bai(build_bai(p), p + ".bai")
        bams.append(p)
    fai = os.path.join(d, "ref.fa.fai")
    with open(fai, "w") as fh:
        for r, ln in zip(REFS, LENS):
            fh.write(f"{r}\t{ln}\t6\t60\t61\n")
    return bams, fai


def _stage(srv, paths):
    urls = []
    for p in paths:
        with open(p, "rb") as fh:
            urls.append(srv.put(os.path.basename(p), fh.read()))
    return urls


def _run(args, env, timeout_s=300.0, expect_rc=0):
    rc = subprocess.run(
        [sys.executable, "-m", "goleft_tpu", *args], env=env,
        timeout=timeout_s, capture_output=True, text=True)
    if expect_rc is not None and rc.returncode != expect_rc:
        raise RuntimeError(
            f"goleft-tpu {args[0]} exited {rc.returncode}, want "
            f"{expect_rc}:\n{rc.stderr}")
    return rc


def _digests(outdir: str) -> dict:
    """sha256 of the indexcov artifact surface: bed.gz compared by
    CONTENT (gunzipped), .roc/.ped by raw bytes."""
    name = os.path.basename(os.path.abspath(outdir))
    out = {}
    for suffix in (".bed.gz", ".roc", ".ped"):
        p = os.path.join(outdir, f"{name}-indexcov{suffix}")
        with open(p, "rb") as fh:
            data = fh.read()
        if suffix == ".bed.gz":
            data = gzip.decompress(data)
        out[suffix] = hashlib.sha256(data).hexdigest()
    return out


def _manifest_counters(outdir: str) -> dict:
    name = os.path.basename(os.path.abspath(outdir))
    p = os.path.join(outdir, f"{name}-indexcov.manifest.json")
    with open(p) as fh:
        doc = json.load(fh)
    if doc.get("format") != "goleft-tpu.cohort-manifest/1":
        raise RuntimeError(f"unexpected manifest format in {p}")
    return doc["counters"]


def run_smoke(timeout_s: float = 600.0, verbose: bool = True) -> int:
    """Returns 0 on success; raises on any failed leg."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("GOLEFT_TPU_FAULTS", None)  # hermetic (leg 3 adds it)
    from ..io.remote_stub import StubServer

    t0 = time.monotonic()
    with tempfile.TemporaryDirectory(prefix="goleft_bb_") as d, \
            StubServer() as srv:
        bams, fai = _make_biobank_cohort(os.path.join(d, ""))
        urls = _stage(srv, [b for p in bams for b in (p, p + ".bai")])
        bam_urls = urls[::2]
        fai_url = _stage(srv, [fai])[0]

        # ---- leg 1: 12 URL samples == local one-shot indexcov ----
        ref12 = os.path.join(d, "ref12", "qc")
        os.makedirs(ref12)
        _run(["indexcov", "-d", ref12, "--fai", fai, "--no-html",
              *bams[:12]], env)
        out = os.path.join(d, "scan", "qc")
        ck = os.path.join(d, "scan", "ck")
        base = ["cohortscan", "-d", out, "-f", fai_url,
                "--chunk-samples", "5", "--checkpoint-dir", ck]
        _run(base + bam_urls[:12], env)
        if _digests(out) != _digests(ref12):
            raise RuntimeError(
                "12-sample URL cohortscan != local indexcov bytes")
        n_chroms = len(REFS)
        c = _manifest_counters(out)
        if c["chrom_qc_samples_computed_total"] != 12 * n_chroms \
                or c["chrom_qc_samples_resumed_total"] != 0:
            raise RuntimeError(f"cold-scan counters off: {c}")
        if verbose:
            print("biobank-smoke: 12-sample URL cohort byte-"
                  "identical to local indexcov "
                  f"({12 * n_chroms} QC blocks computed)")

        # ---- leg 2: append 3 — exactly 3×n_chroms QC computes ----
        _run(base + ["--resume"] + bam_urls, env)
        c = _manifest_counters(out)
        if c["chrom_qc_samples_computed_total"] != 3 * n_chroms:
            raise RuntimeError(
                f"append-3 computed {c} blocks, want {3 * n_chroms}")
        if c["chrom_qc_samples_resumed_total"] != 12 * n_chroms:
            raise RuntimeError(f"append-3 resumed counters off: {c}")
        if c["samples_new"] != 3 or c["samples_unchanged"] != 12:
            raise RuntimeError(f"append-3 manifest diff off: {c}")
        ref15 = os.path.join(d, "ref15", "qc")
        os.makedirs(ref15)
        _run(["indexcov", "-d", ref15, "--fai", fai, "--no-html",
              *bams], env)
        if _digests(out) != _digests(ref15):
            raise RuntimeError(
                "incremental 15-sample artifacts != fresh one-shot")
        if verbose:
            print("biobank-smoke: +3 incremental append performed "
                  f"exactly {3 * n_chroms} QC computations, bytes == "
                  "fresh 15-sample one-shot")

        # ---- leg 3: SIGKILL mid-scan, then --resume ----
        out_k = os.path.join(d, "kill", "qc")
        ck_k = os.path.join(d, "kill", "ck")
        base_k = ["cohortscan", "-d", out_k, "-f", fai_url,
                  "--chunk-samples", "5", "--checkpoint-dir", ck_k]
        rc = subprocess.run(
            [sys.executable, "-m", "goleft_tpu", *base_k,
             "--inject-faults", "shard:after=4:kill", *bam_urls],
            env=env, timeout=300, capture_output=True)
        if rc.returncode not in (-9, 137):
            raise RuntimeError(
                f"injected kill did not fire: rc={rc.returncode} "
                f"{rc.stderr.decode()}")
        journal = os.path.join(ck_k, "journal.jsonl")
        with open(journal) as fh:
            committed = sum(1 for _ in fh)
        if not 0 < committed < 15 * n_chroms:
            raise RuntimeError(
                f"kill landed outside the scan: {committed} commits")
        _run(base_k + ["--resume"] + bam_urls, env)
        if _digests(out_k) != _digests(ref15):
            raise RuntimeError(
                "post-SIGKILL --resume artifacts != reference bytes")
        c = _manifest_counters(out_k)
        if c["chrom_qc_samples_resumed_total"] != committed:
            raise RuntimeError(
                f"resume replayed {c} blocks, journal holds "
                f"{committed}")
        if c["chrom_qc_samples_computed_total"] \
                != 15 * n_chroms - committed:
            raise RuntimeError(f"resume recompute count off: {c}")
        if verbose:
            print("biobank-smoke: SIGKILL mid-scan resumed byte-"
                  f"identically ({committed} blocks replayed, "
                  f"{15 * n_chroms - committed} recomputed)")
        if time.monotonic() - t0 > timeout_s:
            raise RuntimeError(
                f"biobank-smoke exceeded its {timeout_s:g}s budget")
    if verbose:
        print(f"biobank-smoke: PASS ({time.monotonic() - t0:.1f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(run_smoke())
