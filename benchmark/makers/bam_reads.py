"""The maker of the BAM configurations (``depth30x``, ``cohort4x``): reads
drawn from the seed, written as BAM + BAI by ``bamwrite.py``, and what the
program must print from them by the plain reference, ``reference.py``.

It leaves in the fixture's directory the BAMs with their ``.bai``,
``ref.fa.fai`` and a one-line ``region.bed``. Fixtures: 150 bp reads,
150M, MAPQ uniform, 2% duplicates, uniform bases, binned qualities.
A job's work is the aligned read bases of its input: every read the
fixture wrote x 150, filtered reads too.
"""

from __future__ import annotations

import functools
import json
import os

import numpy as np

import bamwrite
import reference

CONTROLS = reference.CONTROLS
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


@functools.lru_cache(maxsize=1)
def _read_lists(fixture_json: str, seed: int) -> list:
    fx = json.loads(fixture_json)
    return [read_list(fx, seed, k) for k in range(fx["distinct_samples"])]


def read_lists(fx: dict, seed: int) -> list:
    """Every distinct sample's reads. The last (fixture, seed) is kept, so
    that ``build``, ``expected`` and each control draw them once; nobody
    writes to them."""
    return _read_lists(json.dumps(fx, sort_keys=True), seed)


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


def expected(config: dict, seed: int,
             break_guarantee: str | None = None) -> tuple[dict, dict]:
    """({expected file name: text}, meta) by the plain reference.
    ``break_guarantee`` is one of ``CONTROLS``."""
    fx, ref = config["fixture"], config["reference"]
    lo, hi = ref["region"]
    reads = read_lists(fx, seed)
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
        "work": {
            "kind": "depth_shards",
            "window": w,
            "classes_out": ref["kind"] == "depth",
            "shards": [{"start": s, "end": e,
                        "kept_segments": [kept_segments[k][i]
                                          for k in columns]}
                       for i, (s, e) in enumerate(shards)]},
    }
    return texts, meta


def build(config: dict, seed: int, out: str) -> dict:
    """Write the job's input files into ``out``; the part of ``meta.json``
    that says what they are."""
    fx = config["fixture"]
    for k, reads in enumerate(read_lists(fx, seed)):
        write_bam(f"{out}/d{k}.bam", fx, f"s{k}", seed, k, reads)
    bams = []
    for j in range(fx["samples"]):
        k = j % fx["distinct_samples"]
        if fx["samples"] == fx["distinct_samples"]:
            bams.append(f"d{k}.bam")
            continue
        bams.append(f"c{j:03d}.bam")
        os.link(f"{out}/d{k}.bam", f"{out}/{bams[-1]}")
        os.link(f"{out}/d{k}.bam.bai", f"{out}/{bams[-1]}.bai")
    with open(f"{out}/ref.fa.fai", "w") as fh:
        fh.write(f"{fx['chrom']}\t{fx['contig_len']}\t6\t60\t61\n")
    lo, hi = config["reference"]["region"]
    with open(f"{out}/region.bed", "w") as fh:
        fh.write(f"{fx['chrom']}\t{lo}\t{hi}\n")
    return {
        "inputs": bams,
        "native_probe": bams[0],
        "bam_bytes": sum(os.path.getsize(f"{out}/d{k}.bam")
                         for k in range(fx["distinct_samples"]))}
