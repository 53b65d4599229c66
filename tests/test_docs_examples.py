"""Every python block in docs/library.md runs verbatim.

The library page promises "if it is on this page, it runs" — this test
extracts each fenced ```python block and executes it in a namespace
seeded with the documented fixture names (bams, fai, rng)."""

import os
import re

import numpy as np

from goleft_tpu.io.fai import write_fai
from helpers import write_bam_and_bai, write_fasta, random_reads

DOC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "docs", "library.md")


def _blocks():
    text = open(DOC).read()
    return re.findall(r"```python\n(.*?)```", text, re.S)


def test_library_doc_examples_run(tmp_path):
    rng = np.random.default_rng(0)
    ref_len = 20_000
    fa = write_fasta(str(tmp_path / "r.fa"), {"chr1": "A" * ref_len})
    write_fai(fa)
    bams = []
    for i in range(3):
        p = str(tmp_path / f"s{i}.bam")
        write_bam_and_bai(p, random_reads(rng, 400, 0, ref_len),
                          ref_names=("chr1",), ref_lens=(ref_len,))
        bams.append(p)

    blocks = _blocks()
    assert len(blocks) >= 6, "library.md lost its examples"
    ns = {"bams": bams, "fai": fa + ".fai",
          "rng": np.random.default_rng(1)}
    for i, src in enumerate(blocks):
        exec(compile(src, f"{DOC}:block{i}", "exec"), ns)


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_docs_quote_no_rate_until_it_is_measured_on_the_chip():
    """One account of numbers: the README's Performance section says
    where the measurements are (BENCHMARK.json's cells, PERF.md, the
    ledger) and quotes no rate itself, so a stale or CPU number can
    never stand there under the name of a device metric; and no doc
    points at the bench stack that went (PR 31) or at its records."""
    readme = open(os.path.join(REPO, "README.md")).read()
    perf = readme[readme.index("## Performance"):
                  readme.index("## Running on the chip")]
    for name in ("PERF.md", "BENCHMARK.json", "PERF_LEDGER.jsonl",
                 "benchmark/run.py"):
        assert name in perf, name
    rate = re.search(
        r"\d+(\.\d+)?\s*(Gbases/s|GB/s|MB/s|GCUPS|windows/s)", perf)
    assert rate is None, f"a rate is quoted: {rate.group(0)!r}"
    docs = os.path.join(REPO, "docs")
    gone = re.compile(r"bench\.py|goleft-tpu bench|BASELINE_PINNED|"
                      r"BENCH_details|BENCH_r0\d|MULTICHIP_r0\d|<!--bench:")
    for name in ["README.md"] + sorted(
            os.path.join("docs", f) for f in os.listdir(docs)
            if f.endswith(".md")):
        hit = gone.search(open(os.path.join(REPO, name)).read())
        assert hit is None, (name, hit.group(0))
