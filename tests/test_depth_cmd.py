"""depth engine functional tests: oracle comparison + tiling properties.

Mirrors the reference functional suite (depth/functional-test.sh): output
must exactly tile the target regions with no duplicates for many window
sizes, and windowed means must match a per-base oracle (here: brute-force
numpy over decoded records, the role samtools depth plays for the
reference; tolerance 0.5 per depth/test/cmp.py:12 — we assert %.4g-exact).
"""

import os

import numpy as np
import pytest

from goleft_tpu.commands.depth import run_depth
from goleft_tpu.io.bam import BamReader
from helpers import write_bam_and_bai, write_fasta, random_reads

REF_LEN = 61_234  # awkward length: partial tail windows
REF2_LEN = 8_000


def oracle_per_base(bam_path, ref_len, tid=0, mapq=1, cap=2500):
    depth = np.zeros(ref_len, dtype=np.int64)
    for rec in BamReader.from_file(bam_path):
        if rec.tid != tid or rec.flag & 0x704 or rec.mapq < mapq:
            continue
        for s, e in rec.aligned_blocks():
            depth[s:min(e, ref_len)] += 1
    return np.minimum(depth, cap)


def make_bam(tmp_path, n=800, seed=0, name="t.bam"):
    rng = np.random.default_rng(seed)
    reads = []
    for tid, rl in ((0, REF_LEN), (1, REF2_LEN)):
        rr = random_reads(rng, n if tid == 0 else n // 10, tid, rl)
        # sprinkle dup/secondary/low-mapq reads the filters must drop
        rr = [
            (t, p, c, rng.integers(0, 61),
             int(rng.choice([0, 0x400, 0x100], p=[0.8, 0.1, 0.1])))
            for (t, p, c, _, _) in rr
        ]
        reads.extend(rr)
    p = str(tmp_path / name)
    write_bam_and_bai(
        p, reads, ref_names=("chr1", "chr2"), ref_lens=(REF_LEN, REF2_LEN)
    )
    write_fasta(
        str(tmp_path / "ref.fa"),
        {"chr1": "ACGT" * (REF_LEN // 4 + 1), "chr2": "AC" * (REF2_LEN // 2)},
    )
    # write_fasta pads; regenerate with exact lengths
    from goleft_tpu.io.fai import write_fai
    seq1 = ("ACGT" * (REF_LEN // 4 + 1))[:REF_LEN]
    seq2 = ("AC" * (REF2_LEN // 2))[:REF2_LEN]
    write_fasta(str(tmp_path / "ref.fa"), {"chr1": seq1, "chr2": seq2})
    write_fai(str(tmp_path / "ref.fa"))
    return p, str(tmp_path / "ref.fa")


def read_bed(path):
    rows = []
    with open(path) as fh:
        for line in fh:
            t = line.rstrip("\n").split("\t")
            rows.append((t[0], int(t[1]), int(t[2])) + tuple(t[3:]))
    return rows


def assert_tiles(rows, chrom, length):
    """rows for chrom exactly tile [0, length) with no overlap/dup."""
    rs = [(s, e) for c, s, e, *_ in rows if c == chrom]
    assert rs == sorted(rs)
    assert rs[0][0] == 0
    assert rs[-1][1] == length
    for (s0, e0), (s1, e1) in zip(rs, rs[1:]):
        assert e0 == s1, f"gap/overlap at {e0}:{s1}"
        assert e0 > s0


@pytest.mark.parametrize("window", [13, 55, 100, 250, 2001, 10**9])
def test_depth_windows_tile_and_match_oracle(tmp_path, window):
    bam, ref = make_bam(tmp_path)
    dpath, cpath = run_depth(
        bam, str(tmp_path / f"w{window}"), reference=ref, window=window
    )
    rows = read_bed(dpath)
    assert_tiles(rows, "chr1", REF_LEN)
    assert_tiles(rows, "chr2", REF2_LEN)
    assert len(rows) == len(set((r[0], r[1], r[2]) for r in rows))
    oracle = oracle_per_base(bam, REF_LEN)
    for c, s, e, mean, *rest in rows:
        if c != "chr1":
            continue
        want = oracle[s:e].sum() / (e - s)
        assert f"{want:.4g}" == mean, (s, e, want, mean)


def test_callable_classes_vs_oracle(tmp_path):
    bam, ref = make_bam(tmp_path, n=300)
    _, cpath = run_depth(
        bam, str(tmp_path / "call"), reference=ref, min_cov=4,
        max_mean_depth=7,
    )
    rows = read_bed(cpath)
    assert_tiles(rows, "chr1", REF_LEN)
    oracle = oracle_per_base(bam, REF_LEN, cap=7 + 2500)
    classes = {"NO_COVERAGE": 0, "LOW_COVERAGE": 1, "CALLABLE": 2,
               "EXCESSIVE_COVERAGE": 3}
    for c, s, e, cls in rows:
        if c != "chr1":
            continue
        seg = oracle[s:e]
        if cls == "NO_COVERAGE":
            assert np.all(seg == 0)
        elif cls == "LOW_COVERAGE":
            assert np.all((seg > 0) & (seg < 4))
        elif cls == "CALLABLE":
            assert np.all((seg >= 4) & (seg < 7))
        else:
            assert np.all(seg >= 7)
    # adjacent runs have different classes (maximal runs)
    chr1 = [(s, e, cls) for c, s, e, cls in rows if c == "chr1"]
    for (_, _, c0), (_, _, c1) in zip(chr1, chr1[1:]):
        assert c0 != c1


def test_depth_mapq_filter(tmp_path):
    bam, ref = make_bam(tmp_path, n=400, seed=3)
    d20, _ = run_depth(bam, str(tmp_path / "q20"), reference=ref,
                       window=100, mapq=20)
    oracle = oracle_per_base(bam, REF_LEN, mapq=20)
    for c, s, e, mean, *_ in read_bed(d20):
        if c != "chr1":
            continue
        assert f"{oracle[s:e].sum() / (e - s):.4g}" == mean


def test_depth_empty_bam(tmp_path):
    p = str(tmp_path / "empty.bam")
    write_bam_and_bai(p, [], ref_names=("chr1",), ref_lens=(5000,))
    write_fasta(str(tmp_path / "e.fa"), {"chr1": "A" * 5000})
    dpath, cpath = run_depth(p, str(tmp_path / "e"),
                             reference=str(tmp_path / "e.fa"), window=1000)
    rows = read_bed(dpath)
    assert_tiles(rows, "chr1", 5000)
    assert all(r[3] == "0" for r in rows)
    crows = read_bed(cpath)
    assert crows == [("chr1", 0, 5000, "NO_COVERAGE")]


def test_depth_walks_each_nonempty_shard_once(tmp_path):
    """The mechanism's counter: a depth run walks each shard's BGZF
    stream once for its segments, so decode.segment_walks_total rises
    by depth.shards_total less the shards with no reads (a contig the
    index has nothing for is never walked)."""
    from goleft_tpu.io import native
    from goleft_tpu.obs import get_registry

    if native.get_lib() is None:
        pytest.skip("native toolchain unavailable")
    rng = np.random.default_rng(8)
    reads = random_reads(rng, 2000, 0, REF_LEN, mapq_lo=30) \
        + random_reads(rng, 200, 1, REF2_LEN, mapq_lo=30)
    p = str(tmp_path / "w.bam")
    write_bam_and_bai(p, reads, ref_names=("chr1", "chr2", "chr3"),
                      ref_lens=(REF_LEN, REF2_LEN, 4000))
    fa = write_fasta(str(tmp_path / "w.fa"),
                     {"chr1": "A" * REF_LEN, "chr2": "C" * REF2_LEN,
                      "chr3": "G" * 4000})
    reg = get_registry()
    walks = reg.counter("decode.segment_walks_total")
    shards = reg.counter("depth.shards_total")
    w0, s0 = walks.value, shards.value
    dpath, _ = run_depth(p, str(tmp_path / "w"), reference=fa,
                         window=500, mapq=20)
    assert shards.value - s0 == 3  # one region a contig
    assert walks.value - w0 == 2   # chr3 has no reads: no walk
    assert_tiles([r for r in read_bed(dpath) if r[0] == "chr3"],
                 "chr3", 4000)


def test_depth_bed_regions(tmp_path):
    bam, ref = make_bam(tmp_path, n=500, seed=5)
    bedfile = str(tmp_path / "regions.bed")
    with open(bedfile, "w") as fh:
        fh.write("chr1\t130\t1020\nchr1\t5000\t6000\nchr2\t0\t500\n")
    dpath, cpath = run_depth(bam, str(tmp_path / "breg"), bed=bedfile,
                             window=250)
    rows = read_bed(dpath)
    # windows absolute-aligned: first region → 130-250, 250-500, ...
    chr1_rows = [r for r in rows if r[0] == "chr1" and r[1] < 1020]
    assert (chr1_rows[0][1], chr1_rows[0][2]) == (130, 250)
    assert chr1_rows[-1][2] == 1020
    oracle = oracle_per_base(bam, REF_LEN)
    for c, s, e, mean, *_ in chr1_rows:
        assert f"{oracle[s:e].sum() / (e - s):.4g}" == mean
    # callable rows cover exactly the bed regions
    crows = [r for r in read_bed(cpath) if r[0] == "chr1"]
    assert crows[0][1] == 130
    assert max(r[2] for r in crows if r[1] < 1020) == 1020


def test_depth_stats_columns(tmp_path):
    bam, ref = make_bam(tmp_path, n=100, seed=6)
    dpath, _ = run_depth(bam, str(tmp_path / "st"), reference=ref,
                         window=1000, stats=True)
    rows = read_bed(dpath)
    chr1 = [r for r in rows if r[0] == "chr1"][0]
    # chrom s e mean gc cpg masked
    assert len(chr1) == 7
    assert float(chr1[4]) == pytest.approx(0.5, abs=0.01)  # ACGT repeat
    chr2 = [r for r in rows if r[0] == "chr2"][0]
    assert float(chr2[4]) == pytest.approx(0.5, abs=0.01)  # AC repeat gc=.5


def test_depth_scale_adds_shards_not_compiles(tmp_path):
    """A whole genome of uneven contigs through ``cli.main``: the depth
    program compiles once per segment bucket (one static length for the
    genome), so the compile count is bucket geometry, far below one per
    contig, and a warm repeat of every contig adds none."""
    from goleft_tpu import obs
    from goleft_tpu.cli import main as cli_main

    rng = np.random.default_rng(2)
    lens = [int(300_000 * (1 - 0.055 * i)) for i in range(6)]
    names = [f"chr{i + 1}" for i in range(len(lens))]
    reads = [r for tid, ln in enumerate(lens)
             for r in random_reads(rng, ln * 2 // 100, tid, ln)]
    bam = str(tmp_path / "wg.bam")
    write_bam_and_bai(
        bam, reads, ref_names=names, ref_lens=lens,
        header_text="@HD\tVN:1.6\tSO:coordinate\n" + "".join(
            f"@SQ\tSN:{n}\tLN:{ln}\n" for n, ln in zip(names, lens)))
    with open(tmp_path / "ref.fa.fai", "w") as fh:
        for n, ln in zip(names, lens):
            fh.write(f"{n}\t{ln}\t6\t60\t61\n")
    compiles = obs.get_registry().counter("xla.compiles_total")

    def run(tag):
        n0 = compiles.value
        assert cli_main(["depth", "--prefix", str(tmp_path / tag),
                         "-r", str(tmp_path / "ref.fa"), "-w", "250",
                         "-Q", "20", bam]) == 0
        return compiles.value - n0

    cold, warm = run("cold"), run("warm")
    assert cold <= len(lens) // 2
    assert warm == 0
    rows = read_bed(str(tmp_path / "warm.depth.bed"))
    for n, ln in zip(names, lens):
        assert_tiles(rows, n, ln)
    assert open(tmp_path / "cold.depth.bed").read() == \
        open(tmp_path / "warm.depth.bed").read()
