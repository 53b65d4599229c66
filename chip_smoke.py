#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that goleft-tpu still starts on the chip.

Drives the main path once through the entry points a user calls —
``python -m goleft_tpu depth | cohortdepth | indexcov | emdepth | serve`` —
at the sizes of BASELINE.json's configs, on data fabricated from ``--seed``,
and checks every output against a NumPy oracle kept apart from the code
under test (or, for indexcov's float outputs, against an explicit CPU twin
of the same command).

This process never imports jax: a chip belongs to one process at a time, so
each phase is ONE child that has the chip alone, one after another, all
sharing the persistent compile cache that ``take_backend`` places.

Each phase prints one JSON line. The last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

read from the children's run manifests; any failed phase, any child that
did not run on a tpu, or no accelerator at all, ends the run non-zero with
``"ok": false``.

``--multichip`` runs only what exists only across chips: the sample-sharded
``cohortdepth --engine device`` on all devices against ``--engine hybrid``,
and ``__graft_entry__.dryrun_multichip`` (the one path with collectives).
"""

from __future__ import annotations

import argparse
import gzip
import json
import multiprocessing
import os
import shutil
import signal
import struct
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [ROOT, os.path.join(ROOT, "tests")]

import numpy as np  # noqa: E402

CHROM = "chr20"
WINDOW = 500
MAPQ = 20
MINCOV = 4
READ_LEN = 150
FLAG_MASK = 0x704  # unmapped | secondary | qcfail | dup, as depth filters
STEP = 10_000_000  # the commands' shard size (commands/depth.py STEP)
SEX = (22, 23)  # chr23, chr24 of the fabricated index cohort
CHILD_TIMEOUT_S = 900

# full: GRCh38 chr20 end to end; BASELINE.json configs 1-5. tiny: the
# CPU rehearsal of the same control flow (prints ok: false — no tpu).
SIZES = {
    "full": dict(contig_len=64_444_167, coverage=10,
                 reduced=["coverage 30x -> 10x: fixture writer"],
                 cohort_end=20_000_000, cohort_samples=8,
                 n_bai=30, genome_scale=1.0,
                 em_samples=2504, em_windows=2048, em_run=100,
                 serve_region=1_000_000),
    "tiny": dict(contig_len=2_400_321, coverage=6,
                 reduced=["tiny: CPU rehearsal size"],
                 cohort_end=1_000_000, cohort_samples=8,
                 n_bai=8, genome_scale=0.02,
                 em_samples=96, em_windows=512, em_run=60,
                 serve_region=100_000),
}


class SmokeFailure(Exception):
    pass


def emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


# ------------------------------------------------------------ fixtures

def _reg2bin(beg, end):
    end = end - 1
    out = np.zeros(len(beg), np.int64)
    done = np.zeros(len(beg), bool)
    for shift, first in ((14, 4681), (17, 585), (20, 73), (23, 9), (26, 1)):
        hit = ~done & ((beg >> shift) == (end >> shift))
        out[hit] = first + (beg[hit] >> shift)
        done |= hit
    return out


def fabricate_bam(path: str, sample: str, contig_len: int, coverage: int,
                  rng) -> tuple:
    """One coordinate-sorted single-contig BAM of 150M reads with mixed
    MAPQ and 2% duplicates, written in bulk through the repo's BamWriter,
    plus its .bai (the repo's builder). Returns the kept reads' starts —
    what the oracle counts."""
    from goleft_tpu.io.bai import build_bai, write_bai
    from goleft_tpu.io.bam import BamWriter

    n = contig_len * coverage // READ_LEN
    pos = np.sort(rng.integers(0, contig_len - READ_LEN, size=n))
    mapq = rng.integers(0, 61, size=n)
    flag = np.where(rng.random(n) < 0.02, 0x400, 0)
    rec = np.dtype([
        ("block_size", "<i4"), ("tid", "<i4"), ("pos", "<i4"),
        ("l_name", "u1"), ("mapq", "u1"), ("bin", "<u2"),
        ("n_cigar", "<u2"), ("flag", "<u2"), ("l_seq", "<i4"),
        ("mtid", "<i4"), ("mpos", "<i4"), ("tlen", "<i4"),
        ("name", "S2"), ("cigar", "<u4"),
        ("seq", "u1", ((READ_LEN + 1) // 2,)), ("qual", "u1", (READ_LEN,)),
    ])
    header = (f"@HD\tVN:1.6\tSO:coordinate\n@SQ\tSN:{CHROM}\t"
              f"LN:{contig_len}\n@RG\tID:r\tSM:{sample}\n")
    with open(path, "wb") as fh, \
            BamWriter(fh, header, [CHROM], [contig_len], level=1) as w:
        for lo in range(0, n, 500_000):
            p = pos[lo:lo + 500_000]
            a = np.zeros(len(p), rec)
            a["block_size"] = rec.itemsize - 4
            a["pos"] = p
            a["l_name"] = 2
            a["mapq"] = mapq[lo:lo + 500_000]
            a["bin"] = _reg2bin(p, p + READ_LEN)
            a["n_cigar"] = 1
            a["flag"] = flag[lo:lo + 500_000]
            a["l_seq"] = READ_LEN
            a["mtid"] = a["mpos"] = -1
            a["name"] = b"r"
            a["cigar"] = READ_LEN << 4  # 150M
            a["seq"] = 0x11  # "AA"
            a["qual"] = 0xFF
            w.write_encoded(a.tobytes())
    write_bai(build_bai(path), path + ".bai")
    kept = (mapq >= MAPQ) & ((flag & FLAG_MASK) == 0)
    return pos[kept], n


def fabricate_bai_cohort(d: str, n: int, scale: float, rng) -> tuple:
    """n whole-genome .bai files + ref.fa.fai (25 chromosomes of
    2.5e8 bp falling by 3% each, one 16 kb tile per linear-index
    entry), with what a real
    cohort has and i.i.d. tiles lack: chr23/chr24 at sex-dependent copy
    number, and five batch effects of well-separated strength, so the
    top principal components are defined and not a rotation of noise."""
    chrom_lens = [int(2.5e8 * scale * (1 - i * 0.03)) for i in range(25)]
    with open(f"{d}/ref.fa.fai", "w") as fh:
        for i, ln in enumerate(chrom_lens):
            fh.write(f"chr{i + 1}\t{ln}\t6\t60\t61\n")
    scores = np.linalg.qr(rng.standard_normal((n, 5)))[0] * np.sqrt(n)
    strength = (0.30, 0.22, 0.15, 0.10, 0.06)
    paths = []
    for s in range(n):
        male = s % 2 == 0
        blob = bytearray(b"BAI\x01") + struct.pack("<i", len(chrom_lens))
        for c, ln in enumerate(chrom_lens):
            n_t = ln // 16384
            copy = 1.0
            if c == 22:
                copy = 0.5 if male else 1.0
            elif c == 23:
                copy = 0.5 if male else 0.02
            elif c < 20:  # effect k lives on chromosomes 4k+1 .. 4k+4
                copy = 1.0 + strength[c // 4] * 0.3 * scores[s, c // 4]
            deltas = (40_000 * copy
                      * rng.uniform(0.95, 1.05, size=n_t)).astype(np.int64)
            ivs = ((int(rng.integers(0, 1 << 30)) + np.cumsum(deltas))
                   .astype(np.uint64) * np.uint64(1 << 16))
            blob += struct.pack("<i", 1)
            blob += struct.pack("<Ii", 0x924A, 2)
            blob += struct.pack("<QQ", 0, 0)
            blob += struct.pack("<QQ", 40_000_000, 80_000)
            blob += struct.pack("<i", n_t) + ivs.astype("<u8").tobytes()
        blob += struct.pack("<Q", 0)
        paths.append(f"{d}/s{s:03d}.bai")
        with open(paths[-1], "wb") as fh:
            fh.write(bytes(blob))
    return paths, f"{d}/ref.fa.fai"


def fabricate_em_matrix(path: str, n_samples: int, n_windows: int,
                        run: int, rng) -> tuple:
    """Integer depth matrix around 30x (10% noise, per-sample coverage
    factor) with planted 1-copy and 3-copy runs of ``run`` windows.
    Returns (depths (B, S) int, planted [(sample, lo, hi, copies)])."""
    factor = rng.uniform(0.8, 1.2, size=n_samples)
    copies = np.ones((n_windows, n_samples))
    planted = []
    n_plant = max(2, min(24, n_samples // 8))
    for k, s in enumerate(rng.choice(n_samples, n_plant, replace=False)):
        lo = int(rng.integers(0, n_windows - run))
        c = 1 if k % 2 == 0 else 3
        copies[lo:lo + run, s] = c / 2
        planted.append((int(s), lo, lo + run, c))
    depths = np.rint(30.0 * factor[None, :] * copies * (
        1 + 0.1 * rng.standard_normal((n_windows, n_samples)))).astype(int)
    depths = np.maximum(depths, 0)
    with open(path, "w") as fh:
        fh.write("#chrom\tstart\tend\t" + "\t".join(
            f"s{j:04d}" for j in range(n_samples)) + "\n")
        for b in range(n_windows):
            fh.write(f"{CHROM}\t{b * WINDOW}\t{(b + 1) * WINDOW}\t"
                     + "\t".join(map(str, depths[b])) + "\n")
    return depths, planted


# -------------------------------------------------------------- oracles

def depth_oracle(kept_starts, contig_len: int) -> np.ndarray:
    """Per-base depth of the kept reads: diff -> cumsum."""
    delta = (np.bincount(kept_starts, minlength=contig_len + 1)
             - np.bincount(kept_starts + READ_LEN,
                           minlength=contig_len + 1))
    return np.cumsum(delta[:contig_len]).astype(np.int32)


def _window_sums(depth, lo: int, hi: int):
    starts = np.arange(lo, hi, WINDOW)
    ends = np.minimum(starts + WINDOW, hi)
    sums = np.add.reduceat(depth[lo:hi].astype(np.int64), starts - lo)
    return starts, ends, sums


def expected_depth_bed(depth, lo: int, hi: int) -> str:
    starts, ends, sums = _window_sums(depth, lo, hi)
    means = sums / (ends - starts)
    return "".join(f"{CHROM}\t{s}\t{e}\t{m:.4g}\n"
                   for s, e, m in zip(starts, ends, means))


def expected_callable_bed(depth, lo: int, hi: int, step: int) -> str:
    """Run-length-encoded callable classes; runs break where the
    command's shards do (every ``step`` bases)."""
    names = ("NO_COVERAGE", "LOW_COVERAGE", "CALLABLE")
    d = depth[lo:hi]
    cls = np.where(d == 0, 0, np.where(d < MINCOV, 1, 2))
    cuts = np.union1d(np.flatnonzero(cls[1:] != cls[:-1]) + 1,
                      np.arange(lo // step * step + step, hi, step) - lo)
    starts = np.concatenate(([0], cuts))
    ends = np.concatenate((cuts, [hi - lo]))
    return "".join(f"{CHROM}\t{s + lo}\t{e + lo}\t{names[v]}\n"
                   for s, e, v in zip(starts, ends, cls[starts]))


def expected_matrix_rows(depth, hi: int, n_samples: int) -> str:
    starts, ends, sums = _window_sums(depth, 0, hi)
    vals = (0.5 + sums / (ends - starts)).astype(np.int64)
    return "".join(f"{CHROM}\t{s}\t{e}\t" + "\t".join([str(v)] * n_samples)
                   + "\n" for s, e, v in zip(starts, ends, vals))


def indexcov_oracle(bais: list[str]) -> dict:
    """What the NumPy oracle says of three samples of the index cohort:
    {"picks", "norm": {sample: per-chromosome normalized depths},
    "longest": the cohort's longest row per chromosome}."""
    from goleft_tpu.io.bai import read_bai
    from oracle_indexcov import oracle_normalized

    sizes = [read_bai(p).sizes() for p in bais]
    picks = sorted({0, len(bais) // 2, len(bais) - 1})
    return {"picks": picks,
            "norm": {k: oracle_normalized(sizes[k]) for k in picks},
            "longest": [max(len(s[c]) for s in sizes)
                        for c in range(len(sizes[0]))]}


def em_normalized(depths) -> np.ndarray:
    """Median normalization by its definition, in f64 and apart from
    the command's own f32 code: each sample's depths over that sample's
    median (0 counts as 1), times the median of those medians."""
    d = depths.astype(np.float64)
    med = np.median(d, axis=0)
    med[med == 0] = 1.0
    return d / med * np.median(med)


def em_oracle_rows(rows):
    """Worker: integer CN of each row by the sequential oracle."""
    import oracle_emdepth

    return [oracle_emdepth.cns(r) for r in rows]


def same_text(what: str, got: str, want: str) -> None:
    if got == want:
        return
    g, w = got.splitlines(), want.splitlines()
    for i, (a, b) in enumerate(zip(g, w)):
        if a != b:
            raise SmokeFailure(
                f"{what}: line {i + 1} is {a!r}, oracle has {b!r}")
    raise SmokeFailure(f"{what}: {len(g)} lines, oracle has {len(w)}")


def read(path: str) -> str:
    with open(path) as fh:
        return fh.read()


# ------------------------------------------------------------- children

class Smoke:
    def __init__(self, out: str, cfg: dict):
        self.out = out
        self.cfg = cfg
        self.phases: list[dict] = []
        self.devices: list[tuple] = []  # of the children that ran on a tpu
        self.off_chip: list[str] = []  # children that did not
        self.cache_dirs: set = set()  # compile caches the children used
        self.env = dict(os.environ, PYTHONPATH=os.pathsep.join(
            [ROOT] + [p for p in os.environ.get(
                "PYTHONPATH", "").split(os.pathsep) if p]))

    def path(self, *parts: str) -> str:
        p = os.path.join(self.out, *parts)
        os.makedirs(os.path.dirname(p), exist_ok=True)
        return p

    def child(self, label: str, argv: list[str], stdout_path=None,
              env=None, want_platform: str = "tpu") -> dict:
        """One ``python -m goleft_tpu`` child to its end. Returns
        {child_seconds, compiles, compile_seconds, cache_hits,
        cache_misses, platform, device_kind, device_count,
        compile_cache_dir, gauges};
        raises on a non-zero exit. A manifest that names another
        platform than wanted fails the phase after its comparisons
        (so that a CPU rehearsal still makes them)."""
        manifest = self.path(f"{label}.json")
        cmd = [sys.executable, "-m", "goleft_tpu", *argv,
               "--metrics-out", manifest]
        t0 = time.monotonic()
        with open(self.path(f"{label}.stderr"), "w") as err, \
                open(stdout_path or os.devnull, "w") as out:
            rc = subprocess.run(cmd, cwd=ROOT, env=env or self.env,
                                stdout=out, stderr=err,
                                timeout=CHILD_TIMEOUT_S).returncode
        seconds = time.monotonic() - t0
        if rc != 0:
            raise SmokeFailure(
                f"{label}: exit code {rc}: "
                + read(self.path(f"{label}.stderr"))[-1500:])
        return dict(self.read_manifest(label, want_platform),
                    child_seconds=round(seconds, 2))

    def read_manifest(self, label: str, want_platform: str = "tpu") -> dict:
        with open(self.path(f"{label}.json")) as fh:
            doc = json.load(fh)
        b = doc["backend"]
        if "error" in b:
            raise SmokeFailure(f"{label}: no backend: {b['error']}")
        if b["platform"] != want_platform:
            self.off_chip.append(
                f"{label}: manifest says platform {b['platform']!r}, "
                f"not {want_platform!r}")
        elif want_platform == "tpu":
            self.devices.append(
                (b["platform"], b["device_kind"], b["device_count"]))
        self.cache_dirs.add(b["compile_cache_dir"])
        c = doc["metrics"]["counters"]
        return {
            "platform": b["platform"], "device_kind": b["device_kind"],
            "device_count": b["device_count"],
            "compile_cache_dir": b["compile_cache_dir"],
            "compiles": c.get("xla.compiles_total", 0),
            "compile_seconds": round(
                c.get("xla.compile_seconds_total", 0.0), 2),
            "cache_hits": c.get("xla.cache_hits_total", 0),
            "cache_misses": c.get("xla.cache_misses_total", 0),
            "gauges": doc["metrics"]["gauges"],
        }

    def phase(self, name: str, fn, *args) -> bool:
        """Run one phase, ``fn(self, *args)``, and print its line; a
        failure is recorded and printed, and fails the run at the end."""
        t0 = time.monotonic()
        rec = {"phase": name}
        n_off = len(self.off_chip)
        try:
            rec.update(fn(self, *args))
            if self.off_chip[n_off:]:
                raise SmokeFailure("; ".join(self.off_chip[n_off:]))
            rec["ok"] = True
        except (SmokeFailure, subprocess.TimeoutExpired, OSError,
                KeyError, ValueError) as e:
            rec.update(ok=False, error=f"{type(e).__name__}: {e}")
        rec["seconds"] = round(time.monotonic() - t0, 2)
        self.phases.append(rec)
        emit(rec)
        return rec["ok"]


def line_of(run: dict, *keys: str) -> dict:
    keys = keys or ("child_seconds", "compiles", "compile_seconds",
                    "cache_hits", "cache_misses")
    return {k: run[k] for k in keys}


def device_of(run: dict) -> dict:
    return line_of(run, "platform", "device_kind", "device_count")


# --------------------------------------------------------------- phases

def phase_depth(sm: Smoke, fx: dict) -> dict:
    cfg = sm.cfg
    args = ["depth", "-w", str(WINDOW), "-Q", str(MAPQ), "-r", fx["ref"]]
    cold = sm.child("depth_cold", args + [
        "--prefix", sm.path("depth", "cold"), fx["bam"]])
    warm = sm.child("depth_warm", args + [
        "--prefix", sm.path("depth", "warm"), fx["bam"]])
    want_depth = expected_depth_bed(fx["depth"], 0, cfg["contig_len"])
    want_call = expected_callable_bed(fx["depth"], 0, cfg["contig_len"],
                                      STEP)
    for run in ("cold", "warm"):
        same_text(f"depth {run} depth.bed",
                  read(sm.path("depth", f"{run}.depth.bed")), want_depth)
        same_text(f"depth {run} callable.bed",
                  read(sm.path("depth", f"{run}.callable.bed")), want_call)
    if warm["cache_hits"] < 1:
        raise SmokeFailure(
            "depth: the second process found nothing in the compile "
            f"cache ({line_of(warm)})")
    # a first process that wrote to the cache really compiled: then the
    # second must be the cheaper one. (A cache already warm from an
    # earlier call makes both cheap, and says nothing.)
    if cold["cache_misses"] and \
            warm["compile_seconds"] >= cold["compile_seconds"]:
        raise SmokeFailure(
            f"depth: compile seconds warm {warm['compile_seconds']} >= "
            f"cold {cold['compile_seconds']} through the shared cache")
    return dict(device_of(cold), cold=line_of(cold), warm=line_of(warm),
                compiles=cold["compiles"],
                shards=-(-cfg["contig_len"] // STEP),
                reads=fx["n_reads"], reduced=cfg["reduced"],
                compared="window means (%.4g) and callable classes of "
                         "both runs, every line, with the NumPy "
                         "per-base oracle")


def phase_cohortdepth(sm: Smoke, fx: dict, want_devices: int = 1) -> dict:
    cfg = sm.cfg
    bed = sm.path("cohort", "region.bed")
    with open(bed, "w") as fh:
        fh.write(f"{CHROM}\t0\t{cfg['cohort_end']}\n")
    bams = []
    for i in range(cfg["cohort_samples"]):
        p = sm.path("cohort", f"s{i:03d}.bam")
        os.link(fx["bam"], p)
        os.link(fx["bam"] + ".bai", p + ".bai")
        bams.append(p)
    args = ["cohortdepth", "-w", str(WINDOW), "-Q", str(MAPQ),
            "--fai", fx["ref"] + ".fai", "-b", bed]
    dev = sm.child("cohortdepth_device", args + ["--engine", "device"]
                   + bams, stdout_path=sm.path("cohort", "device.tsv"))
    hyb = sm.child("cohortdepth_hybrid", args + ["--engine", "hybrid"]
                   + bams, stdout_path=sm.path("cohort", "hybrid.tsv"))
    got = read(sm.path("cohort", "device.tsv"))
    same_text("cohortdepth device vs hybrid", got,
              read(sm.path("cohort", "hybrid.tsv")))
    header, _, rows = got.partition("\n")
    if not header.startswith("#chrom\tstart\tend\t") \
            or header.count("\t") != 2 + len(bams):
        raise SmokeFailure(f"cohortdepth: header {header!r}")
    same_text("cohortdepth device vs oracle", rows, expected_matrix_rows(
        fx["depth"], cfg["cohort_end"], len(bams)))
    rec = dict(device_of(dev), device=line_of(dev), hybrid=line_of(hyb),
               compiles=dev["compiles"], samples=len(bams),
               compared="device and hybrid matrices byte for byte; the "
                        "device one with the NumPy oracle, every row")
    if dev["device_count"] != want_devices:
        raise SmokeFailure(
            f"cohortdepth: device_count {dev['device_count']}, "
            f"want {want_devices}")
    if want_devices > 1:
        g = dev["gauges"]
        rec["batch_devices"] = g.get("cohortdepth.batch_devices")
        rec["batch_shard_rows"] = g.get("cohortdepth.batch_shard_rows")
        if rec["batch_devices"] != want_devices or \
                rec["batch_shard_rows"] != len(bams) // want_devices:
            raise SmokeFailure(
                f"cohortdepth: the dispatched batch sits on "
                f"{rec['batch_devices']} devices with "
                f"{rec['batch_shard_rows']} rows each, not split over "
                f"{want_devices}")
    return rec


def phase_indexcov(sm: Smoke, fx: dict) -> dict:
    from oracle_indexcov import oracle_cn, oracle_counters

    args = ["indexcov", "-p", "", "-X", "chr23,chr24",
            "-f", fx["bai_fai"]]
    tpu = sm.child("indexcov", args + [
        "-d", sm.path("indexcov", "tpu", "ix", "")] + fx["bais"])
    twin = sm.child(
        "indexcov_cpu_twin",
        args + ["-d", sm.path("indexcov", "cpu", "ix", "")] + fx["bais"],
        env=dict(sm.env, GOLEFT_TPU_CPU="1"), want_platform="cpu")
    for ext in ("bed.gz", "roc"):
        a, b = (sm.path("indexcov", k, "ix", f"ix-indexcov.{ext}")
                for k in ("tpu", "cpu"))
        with open(a, "rb") as fa, open(b, "rb") as fb:
            if fa.read() != fb.read():
                raise SmokeFailure(f"indexcov: .{ext} differs from the "
                                   "CPU twin's")
    peds = []
    for k in ("tpu", "cpu"):
        lines = read(sm.path("indexcov", k, "ix",
                             "ix-indexcov.ped")).splitlines()
        peds.append([ln.split("\t") for ln in lines])
    hdr = peds[0][0]
    pcs = [i for i, h in enumerate(hdr) if h.startswith("PC")]
    worst = 0.0
    if peds[0][0] != peds[1][0] or len(peds[0]) != len(peds[1]):
        raise SmokeFailure("indexcov: .ped shape differs from the twin's")
    for i, h in enumerate(hdr):
        ca = [r[i] for r in peds[0][1:]]
        cb = [r[i] for r in peds[1][1:]]
        if i not in pcs:
            if ca != cb:
                raise SmokeFailure(
                    f"indexcov: .ped column {h} differs from the twin's")
            continue
        va, vb = np.array(ca, float), np.array(cb, float)
        err = min(np.abs(va - vb).max(), np.abs(va + vb).max()) \
            / np.abs(vb).max()
        worst = max(worst, float(err))
        if err > 1e-3:
            raise SmokeFailure(
                f"indexcov: {h} is {err:.2e} of its largest value away "
                "from the CPU twin's, up to sign (limit 1e-3)")
    # the NumPy oracle on three samples: bed.gz values, sex-chromosome
    # copy number and the autosomal bin counters
    n = len(fx["bais"])
    picks, norm, longest = (fx["indexcov_oracle"][k]
                            for k in ("picks", "norm", "longest"))
    with gzip.open(sm.path("indexcov", "tpu", "ix",
                           "ix-indexcov.bed.gz"), "rt") as fh:
        fh.readline()
        row_i = {}
        for line in fh:
            t = line.rstrip("\n").split("\t")
            c = int(t[0][3:]) - 1
            b = row_i[c] = row_i.get(c, -1) + 1
            for k in picks:
                d = norm[k][c]
                want = "%.3g" % d[b] if b < len(d) else "0"
                if t[3 + k] != want or int(t[1]) != b * 16384:
                    raise SmokeFailure(
                        f"indexcov: bed.gz {t[0]} bin {b} sample {k}: "
                        f"{t[3 + k]!r}, oracle {want!r}")
    for k in picks:
        row = peds[0][1 + k]
        for c in SEX:
            want = float("%.2f" % oracle_cn(norm[k][c]))
            if float(row[hdr.index(f"CNchr{c + 1}")]) != want:
                raise SmokeFailure(
                    f"indexcov: sample {k} CNchr{c + 1} "
                    f"{row[hdr.index(f'CNchr{c + 1}')]}, oracle {want}")
        for name in ("in", "out", "hi", "lo"):
            want = sum(oracle_counters(norm[k][c], longest[c])[name]
                       for c in range(len(longest)) if c not in SEX)
            if int(row[hdr.index("bins." + name)]) != want:
                raise SmokeFailure(
                    f"indexcov: sample {k} bins.{name} "
                    f"{row[hdr.index('bins.' + name)]}, oracle {want}")
    return dict(device_of(tpu), **line_of(tpu), samples=n,
                cpu_twin=dict(line_of(twin, "child_seconds"),
                              platform=twin["platform"],
                              asked_by="GOLEFT_TPU_CPU=1"),
                pc_worst_rel_err=worst,
                compared=".bed.gz and .roc byte-identical to the explicit "
                         "CPU twin; .ped equal but PC1-5, those within "
                         "1e-3 up to sign; bed values, sex-chromosome CN "
                         f"and bin counters of samples {picks} with the "
                         "NumPy oracle")


def phase_emdepth(sm: Smoke, fx: dict) -> dict:
    run = sm.child("emdepth", [
        "emdepth", "--matrix-out", sm.path("emdepth", "cn.tsv"),
        fx["em_matrix"]], stdout_path=sm.path("emdepth", "calls.tsv"))
    want = np.array([cn for part in fx["em_oracle"].get(CHILD_TIMEOUT_S)
                     for cn in part])
    got = np.loadtxt(sm.path("emdepth", "cn.tsv"), dtype=int,
                     skiprows=1, usecols=range(3, 3 + want.shape[1]),
                     ndmin=2)
    if got.shape != want.shape:
        raise SmokeFailure(f"emdepth: CN matrix {got.shape}, oracle "
                           f"{want.shape}")
    bad = np.argwhere(got != want)
    if len(bad):
        b, s = bad[0]
        raise SmokeFailure(
            f"emdepth: {len(bad)} of {got.size} integer CNs differ from "
            f"the sequential oracle, first at window {b} sample {s}: "
            f"{got[b, s]} vs {want[b, s]}")
    calls = [ln.split("\t") for ln in read(
        sm.path("emdepth", "calls.tsv")).splitlines()[1:]]
    for s, lo, hi, copies in fx["em_planted"]:
        # a call's CN column is the median over its windows, which the
        # reference's Poisson check pulls to 2 for a 1-copy run; its
        # log2FC (beyond -0.5 / 0.3 in every kept window) says which way
        hit = [c for c in calls
               if c[3] == f"s{s:04d}" and int(c[1]) < hi * WINDOW
               and int(c[2]) > lo * WINDOW
               and (float(c[5]) < -0.5 if copies == 1
                    else float(c[5]) > 0.3)]
        if not hit:
            raise SmokeFailure(
                f"emdepth: planted {copies}-copy run of sample {s}, "
                f"windows {lo}-{hi}, was not called")
    return dict(device_of(run), **line_of(run), samples=want.shape[1],
                windows=want.shape[0], planted_runs=len(fx["em_planted"]),
                calls=len(calls),
                compared="integer CN of every window and sample with the "
                         "sequential oracle (tests/oracle_emdepth.py, run "
                         "here on an f64 normalization of its own); every "
                         "planted run called")


def phase_serve(sm: Smoke, fx: dict) -> dict:
    from goleft_tpu.serve.client import ServeClient
    from oracle_indexcov import oracle_cn, oracle_counters

    cfg = sm.cfg
    manifest = sm.path("serve.json")
    t0 = time.monotonic()
    err = open(sm.path("serve.stderr"), "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "goleft_tpu", "serve", "--port", "0",
         "--metrics-out", manifest],
        cwd=ROOT, env=sm.env, stdout=subprocess.PIPE, stderr=err,
        text=True)
    try:
        line = proc.stdout.readline()
        if "listening on " not in line:
            proc.wait(timeout=60)
            raise SmokeFailure(
                f"serve: no port announced (exit {proc.returncode}): "
                + read(sm.path("serve.stderr"))[-1500:])
        client = ServeClient(line.rsplit(" ", 1)[-1].strip(),
                             timeout_s=CHILD_TIMEOUT_S)
        cold_depth = read(sm.path("depth", "cold.depth.bed")).splitlines(
            keepends=True)
        span = cfg["serve_region"]
        for k, lo in enumerate((span, cfg["contig_len"] // 2 // WINDOW
                                * WINDOW)):
            bed = sm.path("serve", f"region{k}.bed")
            with open(bed, "w") as fh:
                fh.write(f"{CHROM}\t{lo}\t{lo + span}\n")
            r = client.depth(fx["bam"], bed=bed, window=WINDOW, mapq=MAPQ)
            want = "".join(ln for ln in cold_depth
                           if lo <= int(ln.split("\t")[1]) < lo + span)
            same_text(f"serve /v1/depth {lo} depth_bed vs the depth "
                      "command's lines", r["depth_bed"], want)
            same_text(f"serve /v1/depth {lo} callable_bed",
                      r["callable_bed"], expected_callable_bed(
                          fx["depth"], lo, lo + span, STEP))
        r = client.indexcov(fx["bais"], fx["bai_fai"], excludepatt="")
        ped = [ln.split("\t") for ln in read(sm.path(
            "indexcov", "tpu", "ix", "ix-indexcov.ped")).splitlines()]
        for c in SEX:  # where the command prints CN too
            col = ped[0].index(f"CNchr{c + 1}")
            got = ["%.2f" % v for v in r["cn"][f"chr{c + 1}"]]
            if got != [row[col] for row in ped[1:]]:
                raise SmokeFailure(
                    f"serve /v1/indexcov: CN of chr{c + 1} differs from "
                    "the indexcov command's .ped")
        longest = fx["indexcov_oracle"]["longest"]
        for k, norm in fx["indexcov_oracle"]["norm"].items():
            for c in range(len(longest)):
                want = round(oracle_cn(norm[c]), 4)
                if r["cn"][f"chr{c + 1}"][k] != want:
                    raise SmokeFailure(
                        f"serve /v1/indexcov: sample {k} chr{c + 1} CN "
                        f"{r['cn'][f'chr{c + 1}'][k]}, oracle {want}")
            for name, key in (("in", "in"), ("out", "out"), ("hi", "hi"),
                              ("lo", "low")):
                want = sum(oracle_counters(norm[c], longest[c])[name]
                           for c in range(len(longest)))
                if r["bin_counters"][key][k] != want:
                    raise SmokeFailure(
                        f"serve /v1/indexcov: sample {k} {key} "
                        f"{r['bin_counters'][key][k]}, oracle {want}")
        health = client.healthz()
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
        if rc != 0:
            raise SmokeFailure(f"serve: drain exited {rc}")
        if health.get("platform") != "tpu" or health.get("status") != "ok":
            raise SmokeFailure(f"serve: /healthz says {health}")
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        proc.stdout.close()
        err.close()
    run = sm.read_manifest("serve")
    return dict(device_of(run),
                **line_of(run, "compiles", "compile_seconds",
                          "cache_hits", "cache_misses"),
                child_seconds=round(time.monotonic() - t0, 2),
                requests=3, healthz=health["platform"], drain_exit=rc,
                compared="2 x /v1/depth: depth_bed byte-identical to the "
                         "depth command's lines, callable_bed to the "
                         "oracle's; /v1/indexcov: CN at the .ped's %.2f "
                         "equal to the indexcov command's, CN and bin "
                         "counters of three samples to the NumPy oracle")


def phase_dryrun(sm: Smoke, n_devices: int) -> dict:
    t0 = time.monotonic()
    with open(sm.path("dryrun.stderr"), "w") as err:
        p = subprocess.run(
            [sys.executable, "-c", "import __graft_entry__ as g; "
             f"g.dryrun_multichip({n_devices})"],
            cwd=ROOT, env=sm.env, stdout=subprocess.PIPE, stderr=err,
            text=True, timeout=CHILD_TIMEOUT_S)
    if p.returncode != 0:
        raise SmokeFailure(f"dryrun_multichip: exit {p.returncode}: "
                           + read(sm.path("dryrun.stderr"))[-1500:])
    last = p.stdout.strip().splitlines()[-1]
    if not last.startswith("dryrun_multichip OK") \
            or f"platform=tpu devices={n_devices}" not in last:
        raise SmokeFailure(f"dryrun_multichip: {last!r}")
    return dict(platform="tpu", device_count=n_devices,
                child_seconds=round(time.monotonic() - t0, 2), said=last,
                compared="shard_map coverage with all_gather and "
                         "ppermute-scan carries against brute force, the "
                         "chunked prefetch path and the sharded EM "
                         "against their single-program results "
                         "(__graft_entry__.dryrun_multichip's own "
                         "assertions)")


# ----------------------------------------------------------------- main

def build_native() -> str:
    """Remove build/libgoleftio.so and build it again from
    csrc/fastio.cpp on this machine; the variant it linked."""
    lib_path = os.path.join(ROOT, "build", "libgoleftio.so")
    if os.path.exists(lib_path):
        os.remove(lib_path)
    from goleft_tpu.io import native

    lib = native.get_lib()
    if lib is None or not os.path.exists(lib_path):
        raise SystemExit("chip_smoke: the native library did not build "
                         "from csrc/fastio.cpp on this machine")
    return "libdeflate" if hasattr(
        lib, "libdeflate_alloc_decompressor") else "zlib"


def emit_fixtures(a, cfg: dict, fx: dict, t0: float) -> None:
    emit({"setup": "fixtures", "seed": a.seed, "size": a.size,
          "reads": fx["n_reads"], "reduced": cfg["reduced"],
          "seconds": round(time.monotonic() - t0, 2)})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="drive goleft-tpu's main path once on the chip and "
                    "check what comes out")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every fabricated input (default 0)")
    ap.add_argument("--out", default=os.path.join(ROOT, "chip_smoke_out"),
                    help="directory for fixtures and outputs, emptied "
                         "first (default chip_smoke_out/)")
    ap.add_argument("--size", choices=sorted(SIZES), default="full",
                    help="tiny: CPU rehearsal of the control flow; it "
                         "cannot pass (no tpu)")
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: only cohortdepth --engine device "
                         "across all devices against --engine hybrid, and "
                         "__graft_entry__.dryrun_multichip(4)")
    a = ap.parse_args(argv)
    from goleft_tpu.utils.device_guard import cpu_requested

    if cpu_requested() and a.size == "full":
        print("chip_smoke: GOLEFT_TPU_CPU / JAX_PLATFORMS=cpu asks for "
              "the CPU; the smoke needs the chip (--size tiny rehearses "
              "the control flow on the CPU)", file=sys.stderr)
        return 2
    cfg = SIZES[a.size]
    want_devices = 4 if a.multichip else 1
    shutil.rmtree(a.out, ignore_errors=True)
    sm = Smoke(a.out, cfg)
    t_start = time.monotonic()

    t0 = time.monotonic()
    variant = build_native()
    emit({"setup": "native", "variant": variant,
          "seconds": round(time.monotonic() - t0, 2)})

    # one stream per fixture, so that none depends on which others a
    # mode makes
    rng_bam, rng_em, rng_bai = (np.random.default_rng([a.seed, k])
                                for k in range(3))
    t0 = time.monotonic()
    fx = {"bam": sm.path("bam", "sample.bam"),
          "ref": sm.path("bam", "ref.fa")}
    with open(fx["ref"] + ".fai", "w") as fh:
        fh.write(f"{CHROM}\t{cfg['contig_len']}\t6\t60\t61\n")
    kept, fx["n_reads"] = fabricate_bam(
        fx["bam"], "smoke", cfg["contig_len"], cfg["coverage"], rng_bam)
    fx["depth"] = depth_oracle(kept, cfg["contig_len"])
    if a.multichip:
        emit_fixtures(a, cfg, fx, t0)
        sm.phase("cohortdepth", phase_cohortdepth, fx, want_devices)
        sm.phase("dryrun_multichip", phase_dryrun, want_devices)
    else:
        # the emdepth oracle is sequential Python: it runs in processes
        # of its own from here, and is collected at its phase
        with multiprocessing.get_context("spawn").Pool(
                max(1, min(8, (os.cpu_count() or 2) - 2))) as pool:
            fx["em_matrix"] = sm.path("emdepth", "m.tsv")
            depths, fx["em_planted"] = fabricate_em_matrix(
                fx["em_matrix"], cfg["em_samples"], cfg["em_windows"],
                cfg["em_run"], rng_em)
            rows = em_normalized(depths).tolist()
            fx["em_oracle"] = pool.map_async(
                em_oracle_rows,
                [rows[i:i + 16] for i in range(0, len(rows), 16)])
            fx["bais"], fx["bai_fai"] = fabricate_bai_cohort(
                os.path.dirname(sm.path("bai", "x")), cfg["n_bai"],
                cfg["genome_scale"], rng_bai)
            fx["indexcov_oracle"] = indexcov_oracle(fx["bais"])
            emit_fixtures(a, cfg, fx, t0)
            # a first child that left no manifest never had a backend:
            # nothing after it can pass
            if sm.phase("depth", phase_depth, fx) \
                    or os.path.exists(sm.path("depth_cold.json")):
                sm.phase("cohortdepth", phase_cohortdepth, fx)
                sm.phase("indexcov", phase_indexcov, fx)
                sm.phase("emdepth", phase_emdepth, fx)
                sm.phase("serve", phase_serve, fx)

    assert "jax" not in sys.modules, "the parent must stay off jax"
    emit({"summary": {p["phase"]: p["seconds"] for p in sm.phases},
          "native": variant,
          "compile_cache_dirs": sorted(map(str, sm.cache_dirs)),
          "JAX_COMPILATION_CACHE_DIR": os.environ.get(
              "JAX_COMPILATION_CACHE_DIR"),
          "total_seconds": round(time.monotonic() - t_start, 2)})
    seen = set(sm.devices)
    # one compile cache for every child, or the warm runs prove nothing
    ok = (all(p["ok"] for p in sm.phases) and len(seen) == 1
          and next(iter(seen))[2] == want_devices
          and len(sm.cache_dirs) == 1)
    if not ok:
        emit({"ok": False,
              "failed": [p["phase"] for p in sm.phases if not p["ok"]],
              "devices_seen": sorted(seen)})
        return 1
    platform, kind, count = next(iter(seen))
    emit({"ok": True, "device": {"platform": platform, "kind": kind,
                                 "count": count}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
