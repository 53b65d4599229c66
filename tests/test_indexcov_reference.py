"""``indexcov`` against its plain reference (``benchmark/references``) at a
small size, through the benchmark's own maker and comparators: what the
cell ``indexcov500.jobs`` decides ``correct`` by, in seconds on the CPU.
Also that ``BENCHMARK.json``'s entries for the cell resolve to files.
"""

import gzip
import importlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from comparators import gz_lines, lines, ped_columns  # noqa: E402
from makers import bai_cohort  # noqa: E402
from references import indexcov as reference  # noqa: E402

with open(f"{ROOT}/BENCHMARK.json") as _fh:
    MANIFEST = json.load(_fh)
with open(f"{BENCH}/configs/indexcov500.json") as _fh:
    CONFIG = json.load(_fh)
CELL = "indexcov500.jobs"
# the configuration's own metrics, all named ix_; a metric of another
# layer listed for this cell alone (pca_offcpu_s_per_gbase) is not one
IX_METRICS = [m for m in MANIFEST["per_layer"]
              if m.get("workloads") == [CELL] and m["name"].startswith("ix_")]
SEED = 2_147_483_659
TILE = 16384
COMPARE = {"bed_gz": gz_lines, "roc": lines, "ped": ped_columns}
SPANS = [("host-decode", "stage"), ("device-compute", "stage"),
         ("pack", "transfer"), ("h2d", "transfer"),
         ("device-wait", "transfer"), ("d2h", "transfer"),
         ("unpack", "transfer"), ("pca", "stage"),
         ("write-output", "stage"), ("write-wait", "wait"),
         ("format", "output"), ("deflate", "output")]


def small_config() -> dict:
    """12 indexes x four short contigs with X and Y (and chrM, whose one
    deep tile is what the median's cap is for), a third of the rows
    short of their last tiles."""
    cfg = json.loads(json.dumps(CONFIG))
    cfg["fixture"].update(
        samples=12, arm_gains=1, short_tail_fraction=0.3,
        contigs=[["chr1", TILE * 2300 + 5], ["chrX", TILE * 120],
                 ["chrY", TILE * 40 + 9], ["chrM", 16569]])
    return cfg


@pytest.fixture(scope="module")
def job(tmp_path_factory):
    """One ``indexcov --no-html`` job over the small cohort through
    ``cli.main``, with the argv and the output names of the
    configuration: its files, the expected ones, the spans it recorded
    and what its counters grew by."""
    from goleft_tpu import cli, obs

    cfg = small_config()
    d = str(tmp_path_factory.mktemp("indexcov_reference"))
    meta = bai_cohort.build(cfg, SEED, d)
    texts, counted = bai_cohort.expected(cfg, SEED)
    meta.update(counted)
    for name, text in texts.items():
        with open(f"{d}/{name}", "w") as fh:
            fh.write(text)
    prefix = f"{d}/job0"
    places = {"prefix": prefix, "base": "job0", "fai": f"{d}/ref.fa.fai"}
    argv = []
    for tok in cfg["argv"]:
        argv += ([f"{d}/{f}" for f in meta["inputs"]] if tok == "{inputs}"
                 else [tok.format(**places)])
    before = obs.get_registry().counters()
    n_spans = len(obs.get_tracer().snapshot())
    assert not cli.main(argv)
    after = obs.get_registry().counters()
    return {
        "dir": d, "meta": meta, "config": cfg,
        "got": {o["name"]: o["file"].format(**places)
                for o in cfg["outputs"]},
        "want": {o["name"]: f"{d}/{o['expected']}" for o in cfg["outputs"]},
        "spans": obs.get_tracer().snapshot()[n_spans:],
        "grew": {k: after[k] - before.get(k, 0) for k in after}}


@pytest.mark.parametrize("kind", list(COMPARE))
def test_the_program_writes_what_the_reference_writes(job, kind):
    assert os.path.getsize(job["got"][kind]) > 0
    assert COMPARE[kind].differ(job["got"][kind], job["want"][kind]) == 0


@pytest.fixture(scope="module")
def control_readings(tmp_path_factory):
    """{control: {kind: lines that differ}}: the reference with one
    statement broken against itself, as ``benchmark/control.py`` reads
    it."""
    cfg = small_config()
    tmp = str(tmp_path_factory.mktemp("controls"))
    want, _ = bai_cohort.expected(cfg, SEED)
    out = {}
    for control in bai_cohort.CONTROLS:
        got, _ = bai_cohort.expected(cfg, SEED, control)
        out[control] = {}
        for o in cfg["outputs"]:
            for side, texts in (("got", got), ("want", want)):
                with open(f"{tmp}/{side}", "w") as fh:
                    fh.write(texts[o["expected"]])
            if o["compare"] == "gz_lines":
                with open(f"{tmp}/got", "rb") as src, \
                        gzip.open(f"{tmp}/got.gz", "wb") as dst:
                    shutil.copyfileobj(src, dst)
                os.replace(f"{tmp}/got.gz", f"{tmp}/got")
            out[control][o["name"]] = COMPARE[o["name"]].differ(
                f"{tmp}/got", f"{tmp}/want")
    return out


@pytest.mark.parametrize("control,kinds", [
    ("median_uncapped", ("bed_gz", "roc", "ped")),
    ("roc_slot_truncates", ("roc", "ped")),
    ("no_tail_bins", ("ped",)),
    ("pca_not_centred", ("ped",)),
    ("quantise_u8_wrap", ("ped",))])
def test_a_control_reads_above_the_limit(control_readings, control, kinds):
    assert reference.CONTROLS == bai_cohort.CONTROLS
    got = control_readings[control]
    assert {k for k, n in got.items() if n > 0} == set(kinds), got


def planted(job, tmp_path, kind, change):
    """The job's own output of ``kind`` with ``change`` applied to its
    lines, as a file of the same sort."""
    opener = gzip.open if kind == "bed_gz" else open
    with opener(job["got"][kind], "rt") as fh:
        text = change(fh.read().splitlines(keepends=True))
    path = str(tmp_path / "planted")
    with opener(path, "wt") as fh:
        fh.write("".join(text))
    return path


def ped_cell(rows, row, name, change):
    head = rows[0].rstrip("\n").split("\t")
    cells = rows[row].rstrip("\n").split("\t")
    cells[head.index(name)] = change(cells[head.index(name)])
    rows[row] = "\t".join(cells) + "\n"
    return rows


def flip_column(rows, name):
    for i in range(1, len(rows)):
        ped_cell(rows, i, name, lambda v: "%.2f" % -float(v))
    return rows


@pytest.mark.parametrize("kind,change,lines_differ", [
    ("bed_gz", lambda t: t[:60] + [t[60].replace("\t", "\t9", 1)] + t[61:],
     1),
    ("bed_gz", lambda t: t[:-2], 2),
    ("roc", lambda t: t + ["chrZ\t0.00\n"], 1),
    ("ped", lambda t: flip_column(t, "PC2"), 0),
    ("ped", lambda t: ped_cell(t, 3, "bins.lo", lambda v: v + "1"), 1),
    ("ped", lambda t: ped_cell(t, 3, "slope", lambda v: "9" + v), 1),
    ("ped", lambda t: ped_cell(flip_column(t, "PC4"), 5, "PC4",
                               lambda v: "%.2f" % (float(v) * 1.01)), 1),
    ("ped", lambda t: ped_cell(t, 7, "PC1", lambda v: "nan"), 1),
], ids=["bed-wrong-line", "bed-short", "roc-surplus-line", "ped-flipped-pc",
        "ped-wrong-count", "ped-wrong-slope", "ped-pc-off", "ped-pc-nan"])
def test_a_comparator_counts_what_is_planted(job, tmp_path, kind, change,
                                             lines_differ):
    path = planted(job, tmp_path, kind, change)
    assert COMPARE[kind].differ(path, job["want"][kind]) == lines_differ


@pytest.mark.parametrize("kind", list(COMPARE))
def test_a_missing_file_counts_as_all_its_lines(job, tmp_path, kind):
    with open(job["want"][kind]) as fh:
        n = len(fh.read().splitlines())
    assert n > 12
    assert COMPARE[kind].differ(str(tmp_path / "none"),
                                job["want"][kind]) == n


def test_a_plain_text_bed_counts_one_line_for_not_being_gzip(job, tmp_path):
    """What ``benchmark/control.py`` hands the comparator."""
    assert gz_lines.differ(job["want"]["bed_gz"], job["want"]["bed_gz"]) == 1
    (tmp_path / "empty").write_bytes(b"")
    with open(job["want"]["bed_gz"]) as fh:
        n = len(fh.read().splitlines())
    assert gz_lines.differ(str(tmp_path / "empty"),
                           job["want"]["bed_gz"]) == n + 1


def test_a_truncated_gzip_counts_as_all_its_lines(job, tmp_path):
    with open(job["got"]["bed_gz"], "rb") as fh:
        data = fh.read()
    (tmp_path / "cut.gz").write_bytes(data[:len(data) // 2])
    with open(job["want"]["bed_gz"]) as fh:
        n = len(fh.read().splitlines())
    assert gz_lines.differ(str(tmp_path / "cut.gz"),
                           job["want"]["bed_gz"]) == n


@pytest.mark.parametrize("name,category", SPANS)
def test_the_job_records_the_span_vocabulary(job, name, category):
    mine = [s for s in job["spans"] if s.name == name]
    assert mine and {s.category for s in mine} == {category}
    by_id = {s.span_id: s for s in job["spans"]}
    parents = {by_id[s.parent_id].name for s in mine
               if s.parent_id in by_id}
    if category == "transfer":
        assert parents <= {"device-compute", "pca"}, parents
    if name == "host-decode":  # one an index file, on the pool's threads
        assert len(mine) == job["config"]["fixture"]["samples"]
        assert parents == {"run.indexcov"}
    if category == "output":  # a bed block's two halves, on the bed pool
        assert parents == {"write-output"}
        assert all(s.thread_name.startswith("bedgz") for s in mine)
    if name == "write-output":
        # the bed blocks on their pool (chr1's two, one a contig else);
        # the ROC blocks and the .ped on the main thread
        pooled = [s for s in mine if s.thread_name.startswith("bedgz")]
        assert len(pooled) == 5 and len(mine) == 5 + 4 + 1
        assert parents == {"run.indexcov"}
    assert not {s.name for s in job["spans"]} & {
        "index_load", "qc_launch", "qc_fetch", "bed_gz", "roc",
        "pca_ped_html"}


def native_library() -> bool:
    from goleft_tpu.io import native

    return native.get_lib() is not None


def sent_bytes(job) -> int:
    """Bytes of the arrays a job places on the device: a float32 matrix
    and its mask a contig, at the padded width, and the PCA's uint16
    matrix."""
    from goleft_tpu.commands.indexcov import _width_bucket

    fx = job["config"]["fixture"]
    tiles = [length // TILE for _, length in fx["contigs"]]
    qc = sum(fx["samples"] * _width_bucket(t) * 5 for t in tiles)
    return qc + 2 * job["meta"]["work"]["pca_tile_samples"]


@pytest.mark.parametrize("counter,want", [
    ("indexcov.indexes_total", lambda j: j["meta"]["work"]["samples"]),
    ("indexcov.index_bytes_total", lambda j: j["meta"]["index_bytes"]),
    # every index of the cohort is a local .bai: the one-pass load's
    ("indexcov.index_native_loads_total",
     lambda j: j["meta"]["work"]["samples"] * native_library()),
    ("indexcov.tile_samples_total",
     lambda j: j["meta"]["work"]["tile_samples"]),
    ("indexcov.qc_dispatches_total",
     lambda j: j["meta"]["work"]["contigs"]),
    ("indexcov.bed_text_bytes_total",
     lambda j: os.path.getsize(j["want"]["bed_gz"])),
    ("indexcov.bed_blocks_pooled_total", lambda j: 2 + 1 + 1 + 1),
    ("xla.h2d_bytes_total", sent_bytes),
    ("xla.d2h_bytes_total",
     lambda j: 4 * (12 * 75 * 4 + 12 * 5 + 5)),
])
def test_the_job_counts_what_it_moved(job, counter, want):
    assert job["grew"][counter] == want(job)


def test_the_index_load_makes_at_most_a_pair_of_buffers_a_thread(job):
    """One read buffer and one scratch for each of the pool's 8 threads
    at most (none where an earlier job of the process left them)."""
    assert 0 <= job["grew"].get(
        "indexcov.index_buffer_grows_total", 0) <= 2 * 8


def test_the_stage_totals_keep_the_new_names(tmp_path, job):
    from goleft_tpu.commands.indexcov import run_indexcov

    d = job["dir"]
    res = run_indexcov([f"{d}/{f}" for f in job["meta"]["inputs"]],
                       str(tmp_path / "out"), fai=f"{d}/ref.fa.fai",
                       write_html=False, write_png=False)
    # "plots" is the report's, which --no-html leaves empty
    assert set(res["stages"]) - {"plots"} == {
        "host-decode", "device-compute", "pca", "write-output"}


def test_the_fast_formatter_prints_what_percent_g_prints():
    rng = np.random.default_rng(5)
    ties = [0.125, 0.375, 1.125, 1.375, 2.5, 10.25, 10.75, 100.5, 1000.5,
            0.0625, 0.00390625, 49999.9, 50000.0, 0.0, 1.0, 0.9995,
            0.99949998, 9.995, 99.95, 999.5, 1e-12, 1.005e-12]
    values = np.concatenate([
        np.array(ties), rng.lognormal(0, 1, 4000), rng.random(1000) * 1e-4,
        rng.random(1000) * 5e4]).astype(np.float32)
    values = values[:len(values) // 7 * 7].reshape(-1, 7)
    prefix = [f"c\t{i}\t" for i in range(len(values))]
    g3 = reference.G3()
    assert g3.rows(prefix, values) == g3._slow_rows(prefix, values)
    values[3, 2] = 2e5  # outside the table: the slow path, whole
    values[4, 4] = 1e-20
    assert g3.rows(prefix, values) == g3._slow_rows(prefix, values)


@pytest.mark.parametrize("centre", [True, False])
def test_the_gram_route_gives_the_svd_s_projection(centre):
    rng = np.random.default_rng(3)
    x = (rng.normal(size=(9, 400)) * 500 + 8192
         + np.outer(rng.normal(size=9), rng.normal(size=400)) * 900
         ).astype(np.uint16)
    got = reference.principal_components(reference.gram(x), 5, centre)
    xf = x.astype(np.float64)
    _, _, vt = np.linalg.svd(xf - xf.mean(axis=0) if centre else xf,
                             full_matrices=False)
    want = xf @ vt[:5].T
    sign = np.sign((got * want).sum(axis=0))
    np.testing.assert_allclose(got * sign, want, rtol=0, atol=1e-6)


def test_an_index_is_what_the_specification_says(job):
    """Parsed here by hand from SAM 5.2, not by the program's reader."""
    import struct

    cfg, d = job["config"], job["dir"]
    made = bai_cohort.cohort(cfg["fixture"], SEED)
    with open(f"{d}/{made['paths'][5]}", "rb") as fh:
        data = fh.read()
    assert data[:4] == b"BAI\x01"
    (n_ref,), at = struct.unpack_from("<i", data, 4), 8
    assert n_ref == len(made["contigs"])
    for c in range(n_ref):
        (n_bin,), at = struct.unpack_from("<i", data, at), at + 4
        bins = {}
        for _ in range(n_bin):
            b, n_chunk = struct.unpack_from("<Ii", data, at)
            bins[b] = struct.unpack_from(f"<{2 * n_chunk}Q", data, at + 8)
            at += 8 + 16 * n_chunk
        (n_intv,), at = struct.unpack_from("<i", data, at), at + 4
        offsets = np.frombuffer(data, "<u8", n_intv, at)
        at += 8 * n_intv
        sizes = made["sizes"][5][c]
        assert np.array_equal(np.diff(offsets.astype(np.int64)), sizes)
        assert bins.pop(37450)[2:] == (made["per_contig_mapped"][5][c],
                                       made["per_contig_unmapped"][5][c])
        assert sorted(bins) == [4681 + t for t in np.flatnonzero(sizes > 0)]
    assert at + 8 == len(data)


# ---- BENCHMARK.json's entries for the cell resolve (what
# benchmark/tests/test_manifest.py checks, which tier-1 does not run) ----

def load(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def test_the_configuration_has_its_file_keys_maker_and_comparators():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "indexcov500")
    assert entry["file"] == "benchmark/configs/indexcov500.json"
    assert [k for k in ("source", "deployment", "argv", "outputs",
                        "guarantees", "reduced", "assumed", "work_unit")
            if not CONFIG.get(k)] == []
    assert CONFIG["name"] == entry["name"]
    assert CONFIG["source"] == entry["source"] and len(entry["source"]) <= 200
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"])
    maker = importlib.import_module(f"makers.{CONFIG['fixture']['maker']}")
    assert callable(maker.build) and callable(maker.expected)
    for out in CONFIG["outputs"]:
        assert callable(importlib.import_module(
            f"comparators.{out['compare']}").differ)
    assert CONFIG["pc_tol"] == ped_columns.TOL <= 1e-3
    assert CONFIG["fixture"]["samples"] == 500
    tiles = [length // TILE for _, length in CONFIG["fixture"]["contigs"]]
    assert (len(tiles), sum(tiles), sum(tiles[:22])) == (25, 188_482, 175_466)


def test_the_cell_has_its_configuration_traffic_and_work_module():
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "indexcov500", "jobs", 1)
    mix = load(f"{BENCH}/traffic/{cell['traffic']}.json")
    assert os.path.exists(f"{BENCH}/drivers/{mix['driver']}.py")
    works = importlib.import_module("works.index_tiles")
    work = {"samples": 500, "contigs": 25, "slots": 70, "components": 5,
            "tile_samples": 94_000_000, "pca_tile_samples": 87_733_500}
    assert works.job_units(work) == 25
    assert works.job_bytes(work) == (4 * 94_000_000 + 2 * 87_733_500
                                     + 4 * 500 * 75 * 25 + 4 * 500 * 5)


@pytest.mark.parametrize("metric", IX_METRICS,
                         ids=[m["name"] for m in IX_METRICS])
def test_a_metric_of_the_cell_has_its_file_and_reducer(metric):
    assert metric["name"].startswith("ix_")
    spec = load(f"{BENCH}/metrics/{metric['name']}.json")
    assert spec["name"] == metric["name"] and isinstance(spec["args"], dict)
    reducer = importlib.import_module(f"reducers.{spec['reducer']}")
    # a program that lacks the span or counter, as the parent does, and an
    # untraced run: nothing to read, and no error
    empty = {"spans": [], "gbases": 1.0, "job_gbases": 1.0, "trace": None,
             "counters": {"before": {}, "after": {}}, "device": {},
             "meta": {"work": {"kind": "index_tiles", "samples": 500}}}
    assert reducer.reduce(spec["args"], empty) is None
    assert metric["moves"] in [m["name"] for m in MANIFEST["end_to_end"]]


def test_fourteen_metrics_and_nothing_of_theirs_imports_jax():
    assert len(IX_METRICS) == 14  # PR 29's thirteen and PR 30's write wait
    readers = sorted({load(f"{BENCH}/metrics/{m['name']}.json")["reducer"]
                      for m in IX_METRICS})
    code = (
        f"import sys, importlib; sys.path.insert(0, {BENCH!r}); "
        f"[importlib.import_module('reducers.' + r) for r in {readers}]; "
        "[importlib.import_module(m) for m in ('makers.bai_cohort', "
        "'references.indexcov', 'comparators.gz_lines', "
        "'comparators.ped_columns', 'works.index_tiles')]; "
        "sys.exit(any(m in sys.modules for m in ('jax', 'goleft_tpu')))")
    assert subprocess.run([sys.executable, "-c", code]).returncode == 0
