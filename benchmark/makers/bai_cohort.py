"""The maker of ``indexcov500``: a cohort of BAM indexes and no BAM.

Every ``.bai`` is written here from the SAM specification, section 5.2
(magic, then for each reference its bins, each with its chunks of virtual
offsets, and its linear index of one virtual offset a 16,384-base tile),
not through ``goleft_tpu/io``. What ``indexcov`` reads of it is the
linear index: a tile's size is the difference of two neighbouring
offsets. The maker draws those sizes from the seed and writes their
running sums; the bin table holds what a real index holds, one chunk a
populated 16 kb bin and the pseudo-bin 37450 with the mapped and unmapped
counts.

The cohort (``fixture`` in the configuration's file): each sample has a
mean size a tile, log-uniform over ``coverage``; all share one profile
along the genome (``profile_sigma``, with runs of empty and near-empty
tiles where a chromosome has its gaps and centromere); ``factors`` planted
cohort factors of falling strength give the PCA five components to find;
every tile-sample has noise of its own (``noise_sigma``). Half the samples
are XX (chrY a sparse trickle) and half XY, chrM is many copies deep,
``arm_gains`` samples carry half a chromosome at 1.5x, and
``short_tail_fraction`` of the samples lack the last one to three tiles of
a contig, so that rows are ragged.

Beside the indexes it leaves ``ref.fa.fai`` and one small BAM,
``probe.bam``, which is no input of the job: the harness's probe of the
native library decodes it (PERF.md section 7, row 0).
"""

from __future__ import annotations

import concurrent.futures as cf
import functools
import json
import os
import re
import struct

import numpy as np

from makers import bam_reads
from references import indexcov as reference

CONTROLS = reference.CONTROLS
TILE = reference.TILE
BAI_MAGIC = b"BAI\x01"
FIRST_TILE_BIN = 4681  # the first bin of 16 kb, ((1 << 15) - 1) / 7
STATS_BIN = 37450
BIN_RECORD = np.dtype([("bin", "<u4"), ("n_chunk", "<i4"),
                       ("beg", "<u8"), ("end", "<u8")])
# a tile's size at 1x: 16,384 / 150 reads of some 85 compressed bytes, in
# units of a virtual offset (the compressed offset is shifted 16 bits)
SIZE_AT_1X = 16384 / 150 * 85 * 65536
PROBE = "probe.bam"
SEX = ("X", "Y")  # -X's default


def workers() -> int:
    return min(8, os.cpu_count() or 1)


def draw_contig(fx: dict, seed: int, c: int, people: dict) -> np.ndarray:
    """(samples x whole tiles) int64 sizes of contig ``c``, from a
    generator of its own."""
    rng = np.random.default_rng([seed, 1, c])
    name, length = fx["contigs"][c]
    n, tiles = fx["samples"], length // TILE
    xy = people["xy"]
    profile = np.exp(fx["profile_sigma"] * rng.normal(size=tiles))
    if tiles >= 200:
        for _ in range(1 + tiles // 4000):
            run = int(rng.integers(tiles // 200, tiles // 50 + 2))
            at = int(rng.integers(0, tiles - run))
            profile[at:at + run] = np.where(rng.random(run) < 0.5, 0.0, 0.004)
    factors = rng.normal(size=(len(fx["factors"]), tiles))
    level = np.maximum(profile * (1.0 + people["loadings"] @ factors), 0.0)
    if reference.is_sex(name, SEX[:1]):
        level[xy] *= 0.5
    elif reference.is_sex(name, SEX[1:]):
        level[xy] *= 0.5
        # XX: most tiles empty, a third a few stray reads, the rest the
        # mismapped ends
        u = rng.random((int((~xy).sum()), tiles))
        level[~xy] *= np.where(u < 0.6, 0.0, np.where(u < 0.95, 0.006, 0.08))
    elif name in ("chrM", "MT"):
        level *= rng.uniform(20, 200, size=(n, 1))
    for s, gained, half in people["gains"]:
        if gained == c:
            arm = (slice(0, tiles // 2) if half == 0
                   else slice(tiles // 2, tiles))
            level[s, arm] *= 1.5
    level *= np.exp(fx["noise_sigma"] * rng.normal(size=(n, tiles)))
    return np.rint(level * people["mean"][:, None]).astype(np.int64)


def draw(fx: dict, seed: int) -> dict:
    """The cohort of the seed: what ``reference.cohort_qc`` takes, and
    the mapped and unmapped counts of each contig for the indexes."""
    rng = np.random.default_rng([seed, 0])
    contigs = [(name, int(length)) for name, length in fx["contigs"]]
    n = fx["samples"]
    lo, hi = fx["coverage"]
    long_enough = [c for c, (_, length) in enumerate(contigs)
                   if length // TILE >= 2000] or [0]
    people = {
        "mean": SIZE_AT_1X * np.exp(rng.uniform(np.log(lo), np.log(hi), n)),
        "xy": rng.permutation(n) % 2 == 1,
        "loadings": rng.normal(size=(n, len(fx["factors"]))) * fx["factors"],
        "gains": [(int(s), long_enough[int(c)], int(h)) for s, c, h in zip(
            rng.choice(n, fx["arm_gains"], replace=False),
            rng.integers(0, len(long_enough), fx["arm_gains"]),
            rng.integers(0, 2, fx["arm_gains"]))]}
    with cf.ThreadPoolExecutor(workers()) as pool:
        whole = list(pool.map(lambda c: draw_contig(fx, seed, c, people),
                              range(len(contigs))))
    sizes = [[] for _ in range(n)]
    mapped = np.zeros((n, len(contigs)), np.int64)
    for c, size in enumerate(whole):
        tiles = size.shape[1]
        short = np.zeros(n, np.int64)
        if tiles >= 100:
            short = np.where(rng.random(n) < fx["short_tail_fraction"],
                             rng.integers(1, 4, n), 0)
            short[0] = 0  # one sample always has the contig whole
        for s in range(n):
            sizes[s].append(size[s, :tiles - short[s]])
            mapped[s, c] = int(sizes[s][c].sum() / (85 * 65536))
    return {"paths": [f"s{s:03d}.bam.bai" for s in range(n)],
            "contigs": contigs, "sizes": sizes, "sex": SEX,
            "per_contig_mapped": mapped,
            "per_contig_unmapped": mapped // 150,
            "mapped": mapped.sum(axis=1).tolist(),
            "unmapped": (mapped // 150).sum(axis=1).tolist()}


@functools.lru_cache(maxsize=1)
def _cohort(fixture_json: str, seed: int) -> dict:
    return draw(json.loads(fixture_json), seed)


def cohort(fx: dict, seed: int) -> dict:
    """The last (fixture, seed) is kept, so that ``build``, ``expected``
    and each control draw it once; nobody writes to it."""
    return _cohort(json.dumps(fx, sort_keys=True), seed)


def bai_bytes(sizes: list[np.ndarray], mapped, unmapped,
              bin_table: bool) -> bytes:
    """One index: SAM specification 5.2. The offsets run on from contig
    to contig, as a coordinate-sorted file's do."""
    out = [BAI_MAGIC, struct.pack("<i", len(sizes))]
    start = np.uint64(1 << 16)  # the first record lies behind the header
    for c, tile_sizes in enumerate(sizes):
        offsets = start + np.concatenate(
            [[0], np.cumsum(tile_sizes)]).astype(np.uint64)
        populated = (np.flatnonzero(tile_sizes > 0) if bin_table
                     else np.zeros(0, np.int64))
        bins = np.zeros(len(populated), BIN_RECORD)
        bins["bin"] = FIRST_TILE_BIN + populated
        bins["n_chunk"] = 1
        bins["beg"], bins["end"] = offsets[populated], offsets[populated + 1]
        out += [struct.pack("<i", len(bins) + 1), bins.tobytes(),
                struct.pack("<IiQQQQ", STATS_BIN, 2, int(offsets[0]),
                            int(offsets[-1]), int(mapped[c]),
                            int(unmapped[c])),
                struct.pack("<i", len(offsets)), offsets.tobytes()]
        start = offsets[-1]
    out.append(struct.pack("<Q", 0))  # reads without coordinates
    return b"".join(out)


def expected(config: dict, seed: int,
             break_guarantee: str | None = None) -> tuple[dict, dict]:
    """({expected file name: text}, meta) by the plain reference.
    ``break_guarantee`` is one of ``CONTROLS``."""
    fx = config["fixture"]
    made = cohort(fx, seed)
    texts = reference.cohort_qc(made, break_guarantee, workers())
    n = fx["samples"]
    # the contigs a job dispatches: those the default -p leaves
    dispatched = [c for c, (name, _) in enumerate(made["contigs"])
                  if not re.search(reference.EXCLUDE, name)]
    whole = sum(made["contigs"][c][1] // TILE for c in dispatched)
    meta = {
        "job_bases": TILE * whole * n,
        "whole_tiles": whole,
        "work": {
            "kind": "index_tiles",
            "samples": n,
            "contigs": len(dispatched),
            "slots": reference.SLOTS,
            "components": min(5, n),
            # tiles that a sample has, over the dispatched contigs
            "tile_samples": sum(len(made["sizes"][s][c])
                                for s in range(n) for c in dispatched),
            # the PCA's matrix: every sample by the longest row of each
            # non-sex contig
            "pca_tile_samples": n * sum(
                max(len(made["sizes"][s][c]) for s in range(n))
                for c in dispatched
                if not reference.is_sex(made["contigs"][c][0], SEX))},
    }
    return ({"expected.bed": texts["bed"], "expected.roc": texts["roc"],
             "expected.ped": texts["ped"]}, meta)


def build(config: dict, seed: int, out: str) -> dict:
    """Write the job's input files into ``out``; the part of ``meta.json``
    that says what they are."""
    fx = config["fixture"]
    made = cohort(fx, seed)

    def write(s: int) -> int:
        data = bai_bytes(made["sizes"][s], made["per_contig_mapped"][s],
                         made["per_contig_unmapped"][s], fx["bin_table"])
        with open(f"{out}/{made['paths'][s]}", "wb") as fh:
            fh.write(data)
        return len(data)

    with cf.ThreadPoolExecutor(workers()) as pool:
        index_bytes = sum(pool.map(write, range(fx["samples"])))
    with open(f"{out}/ref.fa.fai", "w") as fh:
        offset = 0
        for name, length in made["contigs"]:
            offset += len(name) + 2
            fh.write(f"{name}\t{length}\t{offset}\t60\t61\n")
            offset += length + (length + 59) // 60
    probe = fx["probe_bam"]
    bam_reads.write_bam(f"{out}/{PROBE}", probe, "probe", seed, 0,
                        bam_reads.read_list(probe, seed, 0))
    return {"inputs": made["paths"], "native_probe": PROBE,
            "index_bytes": index_bytes}
