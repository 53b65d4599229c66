"""Fixtures and expected outputs of one configuration and seed.

Runs as a child of ``run.py`` (``python benchmark/fixtures.py --config F
--seed N --out DIR``) and imports neither JAX nor ``goleft_tpu``: what it
allocates never shows in the measured process's ``VmHWM``, and the inputs
do not move when the program's readers and writers do.

It leaves in DIR the BAMs with their ``.bai``, ``ref.fa.fai``,
``region.bed``, the plain reference's output texts (``expected.*``) and
``meta.json`` (the job's bases, the shards and the kept segments of each
sample-shard that ``work.py`` counts bytes from). DIR is written under a
temporary name and renamed when whole; a DIR that exists is reused.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bamwrite  # noqa: E402
import reference  # noqa: E402

BASE_CODES = np.array([1, 2, 4, 8], np.uint8)  # A C G T in BAM's 4 bits
SEQ_LUT = (BASE_CODES[np.arange(16) >> 2] << 4
           | BASE_CODES[np.arange(16) & 3]).astype(np.uint8)
# small enough that malloc recycles a batch's arrays: a fresh page costs far
# more than the arithmetic on it
BATCH = bamwrite.RECORDS_PER_BLOCK * 100


def read_list(fx: dict, seed: int, k: int):
    """(pos, mapq, flag) of distinct sample ``k``, sorted by position."""
    if fx["read_len"] != bamwrite.READ_LEN:
        raise ValueError("the record layout is fixed at 150 bp reads")
    rng = np.random.default_rng([seed, k, 0])
    n = fx["contig_len"] * fx["coverage"] // fx["read_len"]
    pos = np.sort(rng.integers(0, fx["contig_len"] - fx["read_len"], size=n))
    mapq = rng.integers(0, fx["mapq_max"] + 1, size=n)
    flag = np.where(rng.random(n) < fx["duplicate_fraction"], 0x400, 0)
    return pos, mapq, flag


def write_bam(path: str, fx: dict, sample: str, seed: int, k: int,
              reads) -> None:
    """Bases uniform over ACGT, qualities drawn from the binned values:
    BGZF has real entropy to inflate, unlike a constant payload."""
    pos, mapq, flag = reads
    rng = np.random.default_rng([seed, k, 1])
    edges = np.cumsum(fx["quality_probabilities"])
    qual_lut = np.asarray(fx["quality_values"], np.uint8)[np.minimum(
        np.searchsorted(edges, (np.arange(256) + 0.5) / 256), len(edges) - 1)]
    n_seq = (fx["read_len"] + 1) // 2
    header = (f"@HD\tVN:1.6\tSO:coordinate\n@SQ\tSN:{fx['chrom']}\t"
              f"LN:{fx['contig_len']}\n@RG\tID:r\tSM:{sample}\n")
    with bamwrite.BamBaiWriter(path, header, fx["chrom"], fx["contig_len"],
                               threads=min(12, os.cpu_count() or 1)) as w:
        for lo in range(0, len(pos), BATCH):
            sl = slice(lo, lo + BATCH)
            r = rng.integers(0, 256, size=(len(pos[sl]),
                                           n_seq + fx["read_len"]),
                             dtype=np.uint8)
            w.write(bamwrite.encode_records(
                pos[sl], mapq[sl], flag[sl],
                SEQ_LUT[r[:, :n_seq] & 15], qual_lut[r[:, n_seq:]]))


def shards_of(lo: int, hi: int, shard: int) -> list[tuple[int, int]]:
    return [(s, min(s + shard, hi)) for s in range(lo, hi, shard)]


def expected_texts(config: dict, seed: int,
                   break_guarantee: str | None = None,
                   reads: list | None = None) -> tuple[dict, dict]:
    """({expected file name: text}, meta) by the plain reference.
    ``break_guarantee`` is one of ``reference.CONTROLS``."""
    fx, ref = config["fixture"], config["reference"]
    lo, hi = ref["region"]
    reads = reads or [read_list(fx, seed, k)
                      for k in range(fx["distinct_samples"])]
    columns = [j % fx["distinct_samples"] for j in range(fx["samples"])]
    shards = shards_of(lo, hi, ref["shard"])
    depths, kept_segments = [], []
    for pos, mapq, flag in reads:
        keep = reference.kept_mask(mapq, flag, ref["min_mapq"],
                                   ref["flag_mask"], break_guarantee)
        kp = pos[keep]
        depths.append(reference.per_base_depth(kp, fx["contig_len"]))
        kept_segments.append([int(np.count_nonzero(
            (kp < e) & (kp + fx["read_len"] > s))) for s, e in shards])
    chrom, w = fx["chrom"], ref["window"]
    if ref["kind"] == "depth":
        texts = {
            "expected.depth.bed": reference.depth_bed(
                chrom, depths[0], lo, hi, w, break_guarantee),
            "expected.callable.bed": reference.callable_bed(
                chrom, depths[0], lo, hi, ref["mincov"], ref["shard"]),
        }
    elif ref["kind"] == "cohort_matrix":
        names = [f"s{k}" for k in columns]
        texts = {"expected.matrix.tsv": reference.matrix_tsv(
            chrom, depths, columns, names, lo, hi, w, break_guarantee)}
    else:
        raise ValueError(f"unknown reference kind {ref['kind']!r}")
    in_region = [int(np.count_nonzero((pos < hi) & (pos + fx["read_len"] > lo)))
                 for pos, _, _ in reads]
    job_reads = sum(in_region[k] for k in columns)
    meta = {
        "job_reads": job_reads,
        "job_bases": job_reads * fx["read_len"],
        "window": w,
        "classes_out": ref["kind"] == "depth",
        "shards": [{"start": s, "end": e,
                    "kept_segments": [kept_segments[k][i] for k in columns]}
                   for i, (s, e) in enumerate(shards)],
    }
    return texts, meta


def build(config: dict, seed: int, out: str) -> dict:
    t0 = time.monotonic()
    fx = config["fixture"]
    tmp = f"{out}.tmp{os.getpid()}"
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    reads = []
    for k in range(fx["distinct_samples"]):
        reads.append(read_list(fx, seed, k))
        write_bam(f"{tmp}/d{k}.bam", fx, f"s{k}", seed, k, reads[-1])
    bams = []
    for j in range(fx["samples"]):
        k = j % fx["distinct_samples"]
        if fx["samples"] == fx["distinct_samples"]:
            bams.append(f"d{k}.bam")
            continue
        bams.append(f"c{j:03d}.bam")
        os.link(f"{tmp}/d{k}.bam", f"{tmp}/{bams[-1]}")
        os.link(f"{tmp}/d{k}.bam.bai", f"{tmp}/{bams[-1]}.bai")
    with open(f"{tmp}/ref.fa.fai", "w") as fh:
        fh.write(f"{fx['chrom']}\t{fx['contig_len']}\t6\t60\t61\n")
    lo, hi = config["reference"]["region"]
    with open(f"{tmp}/region.bed", "w") as fh:
        fh.write(f"{fx['chrom']}\t{lo}\t{hi}\n")
    texts, meta = expected_texts(config, seed, reads=reads)
    for name, text in texts.items():
        with open(f"{tmp}/{name}", "w") as fh:
            fh.write(text)
    meta.update(
        bams=bams, seed=seed, config=config["name"],
        bam_bytes=sum(os.path.getsize(f"{tmp}/d{k}.bam")
                      for k in range(fx["distinct_samples"])),
        fixture_seconds=round(time.monotonic() - t0, 3))
    with open(f"{tmp}/meta.json", "w") as fh:
        json.dump(meta, fh)
    shutil.rmtree(out, ignore_errors=True)
    os.rename(tmp, out)
    os.sync()  # the write-back of ~0.5 GB must not fall into the window
    return meta


def read_through(d: str) -> None:
    """Read every file once, so that a run that reuses a fixture finds it
    in the page cache as the run that built it does."""
    for name in sorted(os.listdir(d)):
        with open(os.path.join(d, name), "rb") as fh:
            while fh.read(1 << 24):
                pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)
    with open(a.config) as fh:
        config = json.load(fh)
    said = {"fixture": "reused", "dir": a.out}
    if not os.path.exists(f"{a.out}/meta.json"):
        meta = build(config, a.seed, a.out)
        said.update(fixture="built", seconds=meta["fixture_seconds"],
                    bam_bytes=meta["bam_bytes"], job_reads=meta["job_reads"])
    read_through(a.out)
    print(json.dumps(said))
    return 0


if __name__ == "__main__":
    sys.exit(main())
