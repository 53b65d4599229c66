"""That the harness's seams are enough for a configuration that is not a
BAM and not ``depth``: a throwaway one whose maker, comparator and work
module live under ``tests/seam``, on the lookup path only because this
test puts them there. ``indexcov`` over three ``.bai`` files and no BAM,
its BGZF output compared through a gunzip-then-lines comparator, through
``run.main`` to the last line. Run by hand, like ``test_benchmark.py``.
"""

import gzip
import importlib
import json
import os

import pytest

import run
import work

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
SEAM = os.path.join(HERE, "seam")
FIXTURE = {
    "maker": "bai_only", "chrom": "chr20", "contig_len": 2_000_000,
    "coverage": 1, "read_len": 150, "samples": 3, "mapq_max": 60,
    "duplicate_fraction": 0.02, "quality_values": [2, 12, 23, 37],
    "quality_probabilities": [0.01, 0.04, 0.07, 0.88]}
ARGV = ["indexcov", "--no-html", "-f", "{fai}", "-d", "{prefix}",
        "{inputs}"]
SEED = 2_147_483_693


def config(name: str, expected_text_file: str) -> dict:
    return {
        "name": name, "argv": ARGV,
        "outputs": [{"name": "bed_gz", "compare": "gz_lines",
                     "file": "{prefix}/{base}-indexcov.bed.gz",
                     "expected": "expected.bed"}],
        "work_unit": "16 kb tiles x samples of the indexes",
        "fixture": dict(FIXTURE, expected_text_file=expected_text_file)}


@pytest.fixture
def seam_path(monkeypatch):
    """The lookup path of this process and of the fixture child."""
    monkeypatch.syspath_prepend(SEAM)
    monkeypatch.setenv("PYTHONPATH", os.pathsep.join(
        [SEAM] + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    importlib.invalidate_caches()


@pytest.fixture
def throwaway_root(tmp_path, seam_path):
    """BENCHMARK.json with two throwaway configurations, ``idx3`` and
    ``idx3_wrong``: the expected text is the program's own output over
    the same three indexes, in the second with one line changed."""
    from goleft_tpu import cli
    from makers import bai_only

    first = tmp_path / "first"
    first.mkdir()
    made = bai_only.build(config("idx3", ""), SEED, str(first))
    assert not [f for f in os.listdir(first) if f.endswith(".bam")]
    out = str(first / "out")
    assert not cli.main([
        "indexcov", "--no-html", "-f", f"{first}/ref.fa.fai", "-d", out,
        *(f"{first}/{f}" for f in made["inputs"])])
    with gzip.open(f"{out}/out-indexcov.bed.gz", "rt") as fh:
        text = fh.read().splitlines(keepends=True)
    assert len(text) == 1 + 122  # a header, a line a whole 16 kb tile
    (tmp_path / "right.bed").write_text("".join(text))
    text[60] = text[60].replace("\t", "\t9", 1)
    (tmp_path / "wrong.bed").write_text("".join(text))

    with open(f"{ROOT}/BENCHMARK.json") as fh:
        bench = json.load(fh)
    bench["configs"], bench["workloads"] = [], []
    for name, bed in (("idx3", "right.bed"), ("idx3_wrong", "wrong.bed")):
        (tmp_path / f"{name}.json").write_text(
            json.dumps(config(name, str(tmp_path / bed))))
        bench["configs"].append({"name": name, "file": f"{name}.json"})
        bench["workloads"].append({"name": f"{name}.jobs", "config": name,
                                   "traffic": "jobs", "chips": 1})
    for m in bench["per_layer"]:
        m["workloads"] = ["idx3.jobs", "idx3_wrong.jobs"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


def run_cell(root, capfd, workload, trace):
    rc = run.main(["--workload", workload, "--seed", str(SEED),
                   "--seconds", "0.5", "--trace", str(trace)],
                  require_tpu=False, root=root)
    out = capfd.readouterr()
    return rc, json.loads(out.out.splitlines()[-1]), out


@pytest.mark.parametrize("trace", [0, 1])
def test_a_configuration_of_new_files_reaches_the_last_line(
        throwaway_root, capfd, trace):
    rc, line, out = run_cell(throwaway_root, capfd, "idx3.jobs", trace)
    assert rc == 0
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert line["compared"] == {
        "jobs_exit_nonzero": {"value": 0, "limit": 0},
        "bed_gz_lines_differ": {"value": 0, "limit": 0}}
    assert out.err.rstrip().endswith("correct: True")
    # no BAM for the probe: the library is loaded and its builder noted
    machine = next(json.loads(ln)["machine"] for ln in out.out.splitlines()
                   if ln.startswith('{"machine"'))
    assert machine["native_built_by"]
    fixture_dir = f"{throwaway_root}/benchmark/.fixtures/idx3-{SEED}"
    with open(f"{fixture_dir}/meta.json") as fh:
        meta = json.load(fh)
    assert meta["native_probe"] is None
    assert meta["inputs"] == [f"s{k}.bam.bai" for k in range(3)]
    assert sorted(os.listdir(fixture_dir)) == sorted(
        meta["inputs"] + ["expected.bed", "meta.json", "ref.fa.fai"])
    assert meta["work_unit"] == "16 kb tiles x samples of the indexes"
    # the work module is found by the name the fixture gives
    assert work.job_units(meta) == 122
    assert work.job_bytes(meta) == 2 * 4 * 122 * 3
    if trace:
        assert line["metrics"]["window_compiles"]["value"] == 0
    else:
        assert line["metrics"]["gbases_per_s"]["value"] == pytest.approx(
            line["attempted"] * 122 * 3 * 1e-9
            / sum(json.loads(ln)["seconds"] for ln in out.out.splitlines()
                  if ln.startswith('{"job"') and json.loads(ln)["job"] >= 0),
            rel=0.05)


def test_one_expected_line_changed_reads_not_correct(throwaway_root, capfd):
    rc, line, out = run_cell(throwaway_root, capfd, "idx3_wrong.jobs", 0)
    assert rc == 0
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] >= 1
    jobs = line["attempted"] + 1  # the warm-up job is compared too
    assert line["compared"]["bed_gz_lines_differ"] == {
        "value": jobs, "limit": 0}
    assert line["compared"]["jobs_exit_nonzero"]["value"] == 0
    assert out.err.rstrip().endswith("correct: False")


def test_every_counter_of_the_registry_reaches_the_reducers():
    """Not only ``xla.*``: a counter that a later PR brings is read by
    ``counter_per_gbase`` from a metric file alone."""
    from goleft_tpu.obs import get_registry
    from reducers import counter_per_gbase

    before = run.counters()
    get_registry().counter("seam.test_total").inc(3)
    counters = {"before": before, "after": run.counters()}
    assert counter_per_gbase.reduce(
        {"counter": "seam.test_total"},
        {"counters": counters, "gbases": 0.5}) == pytest.approx(6.0)
