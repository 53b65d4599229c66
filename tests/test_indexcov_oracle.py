"""Integration oracle: recompute indexcov's bed.gz and ped values from the
raw .bai tile sizes with an independent sequential numpy implementation
of the reference semantics, and compare against run_indexcov's outputs."""

import gzip

import numpy as np

from goleft_tpu.commands.indexcov import run_indexcov
from goleft_tpu.io.bai import read_bai
from helpers import write_bam_and_bai, random_reads
from oracle_indexcov import oracle_cn, oracle_counters, oracle_normalized

REFS = ("chr1", "X")
LENS = (800_000, 300_000)


def _header(s):
    sq = "".join(f"@SQ\tSN:{n}\tLN:{l}\n" for n, l in zip(REFS, LENS))
    return f"@HD\tVN:1.6\tSO:coordinate\n{sq}@RG\tID:r\tSM:{s}\n"


def test_indexcov_pipeline_matches_sequential_oracle(tmp_path):
    rng = np.random.default_rng(0)
    paths = []
    for i in range(4):
        male = i % 2 == 0
        reads = random_reads(rng, 4000, 0, LENS[0])
        n_x = 4000 * LENS[1] // LENS[0]
        reads += random_reads(rng, n_x // 2 if male else n_x, 1, LENS[1])
        p = str(tmp_path / f"s{i}.bam")
        write_bam_and_bai(p, reads, ref_names=REFS, ref_lens=LENS,
                          header_text=_header(f"s{i}"))
        paths.append(p)

    res = run_indexcov(paths, str(tmp_path / "out"), sex="X",
                       write_html=False, write_png=False)

    # independent recomputation from the raw indexes
    per_sample = [oracle_normalized(read_bai(p + ".bai").sizes())
                  for p in paths]

    # bed.gz values must equal the %.3g-formatted oracle normalization
    with gzip.open(res["bed"], "rt") as fh:
        fh.readline()
        rows = [line.rstrip("\n").split("\t") for line in fh]
    for chrom_i, chrom in enumerate(REFS):
        crows = [r for r in rows if r[0] == chrom]
        longest = max(len(ps[chrom_i]) for ps in per_sample)
        assert len(crows) == longest
        for b, r in enumerate(crows):
            assert int(r[1]) == b * 16384
            for k in range(4):
                d = per_sample[k][chrom_i]
                want = "%.3g" % d[b] if b < len(d) else "0"
                assert r[3 + k] == want, (chrom, b, k)

    # ped CNX equals the sequential GetCN oracle
    with open(res["ped"]) as fh:
        hdr = fh.readline().rstrip("\n").split("\t")
        prows = [line.rstrip("\n").split("\t") for line in fh]
    cnx_col = hdr.index("CNX")
    for k in range(4):
        want = oracle_cn(per_sample[k][1])
        assert float(prows[k][cnx_col]) == float("%.2f" % want), k

    # counters recomputed: in/out/hi/low over autosome (chr1) bins
    longest = max(len(ps[0]) for ps in per_sample)
    for name in ("in", "out", "hi", "lo"):
        ci = hdr.index("bins." + name)
        for k in range(4):
            want = oracle_counters(per_sample[k][0], longest)[name]
            assert int(prows[k][ci]) == want, (name, k)
