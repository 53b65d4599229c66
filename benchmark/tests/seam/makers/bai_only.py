"""A maker that no file under ``benchmark/`` outside ``tests/`` knows:
three ``.bai`` indexes and a ``.fai``, and no BAM. The expected text is
handed in by the test (``fixture.expected_text_file``): the test is of
the harness's seams, not of ``indexcov``."""

import os

from makers import bam_reads

CONTROLS = ()
TILE = 16384


def build(config: dict, seed: int, out: str) -> dict:
    fx = config["fixture"]
    names = []
    for k in range(fx["samples"]):
        bam = f"{out}/s{k}.bam"
        bam_reads.write_bam(bam, fx, f"s{k}", seed, k,
                            bam_reads.read_list(fx, seed, k))
        os.remove(bam)  # indexcov reads the index alone
        names.append(f"s{k}.bam.bai")
    with open(f"{out}/ref.fa.fai", "w") as fh:
        fh.write(f"{fx['chrom']}\t{fx['contig_len']}\t6\t60\t61\n")
    return {"inputs": names, "native_probe": None}


def expected(config: dict, seed: int, break_guarantee=None):
    fx = config["fixture"]
    with open(fx["expected_text_file"]) as fh:
        text = fh.read()
    tiles = fx["contig_len"] // TILE  # whole tiles: what indexcov prints
    meta = {"job_bases": tiles * fx["samples"],
            "work": {"kind": "tiles", "tiles": tiles,
                     "samples": fx["samples"]}}
    return {"expected.bed": text}, meta
