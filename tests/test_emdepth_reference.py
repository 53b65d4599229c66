"""``emdepth`` against its plain reference (``benchmark/references``) at a
small size, through the benchmark's own maker and comparator: what the
cell ``emdepth2504.jobs`` decides ``correct`` by, in seconds on the CPU.
The program runs in float32, as on the chip (the suite's float64 would
normalise the depths in another precision than the configuration's).
"""

import importlib
import io
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from comparators import lines  # noqa: E402
from makers import em_matrix  # noqa: E402
from references import emdepth as reference  # noqa: E402

with open(f"{BENCH}/configs/emdepth2504.json") as _fh:
    CONFIG = json.load(_fh)
SEED = 2_147_483_659
KINDS = {"calls": "expected.calls", "cn_matrix": "expected.cn.tsv"}
# (windows, EM_CHUNK): one chunk of the product's size, three chunks with
# the last padded, and one chunk through the same loop
SIZES = [(512, None), (300, 128), (128, 128)]
SPANS = [("host-decode", "stage"), ("normalize", "stage"),
         ("device-compute", "stage"), ("pack", "transfer"),
         ("h2d", "transfer"), ("device-wait", "transfer"),
         ("d2h", "transfer"), ("merge", "stage"), ("write-output", "stage")]


def small_config(windows: int = 512) -> dict:
    """96 samples; CNVs short enough that most of a sample's windows stay
    at CN2 in so short a region, as they do in the cell's."""
    cfg = json.loads(json.dumps(CONFIG))
    cfg["fixture"].update(windows=windows, samples=96, cnv_windows=[3, 24],
                          cnp_windows=[4, 12], cnp_regions=2)
    return cfg


@pytest.fixture
def float32(monkeypatch):
    from goleft_tpu.utils import dtypes

    monkeypatch.setattr(dtypes, "preferred_float", lambda: np.float32)


def run_job(cfg, seed, d, chunk=None):
    """One ``emdepth`` job through ``cli.main`` with the configuration's
    argv, its stdout caught: its files and the expected ones, the spans it
    recorded and what its counters grew by."""
    from goleft_tpu import cli, obs
    from goleft_tpu.commands import emdepth_cmd
    from goleft_tpu.utils import dtypes

    meta = em_matrix.build(cfg, seed, d)
    texts, counted = em_matrix.expected(cfg, seed)
    meta.update(counted)
    for name, text in texts.items():
        with open(f"{d}/{name}", "w") as fh:
            fh.write(text)
    prefix = f"{d}/job0"
    argv = []
    for tok in cfg["argv"]:
        argv += ([f"{d}/{f}" for f in meta["inputs"]] if tok == "{inputs}"
                 else [tok.format(prefix=prefix)])
    before = obs.get_registry().counters()
    n_spans = len(obs.get_tracer().snapshot())
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(dtypes, "preferred_float", lambda: np.float32)
        if chunk:
            mp.setattr(emdepth_cmd, "EM_CHUNK", chunk)
        out = io.StringIO()
        mp.setattr(sys, "stdout", out)
        assert not cli.main(argv)
    with open(f"{prefix}.stdout", "w") as fh:
        fh.write(out.getvalue())
    after = obs.get_registry().counters()
    return {"dir": d, "meta": meta,
            "got": {"calls": f"{prefix}.stdout",
                    "cn_matrix": f"{prefix}.cn.tsv"},
            "want": {k: f"{d}/{v}" for k, v in KINDS.items()},
            "spans": obs.get_tracer().snapshot()[n_spans:],
            "grew": {k: after[k] - before.get(k, 0) for k in after}}


@pytest.fixture(scope="module", params=SIZES,
                ids=[f"{w}w-chunk{c or 'product'}" for w, c in SIZES])
def job(request, tmp_path_factory):
    windows, chunk = request.param
    d = str(tmp_path_factory.mktemp(f"emdepth{windows}"))
    return run_job(small_config(windows), SEED, d, chunk)


@pytest.mark.parametrize("kind", list(KINDS))
def test_the_program_writes_what_the_reference_writes(job, kind):
    with open(job["want"][kind]) as fh:
        assert len(fh.read().splitlines()) > 10
    assert lines.differ(job["got"][kind], job["want"][kind]) == 0


@pytest.mark.parametrize("kind,change", [
    ("cn_matrix", lambda t: t[:40] + [t[40].replace("\t2", "\t3", 1)]
     + t[41:]),
    ("calls", lambda t: t[:3] + [t[3][:-2] + str((int(t[3][-2]) + 1) % 10)
                                 + "\n"] + t[4:]),
], ids=["wrong-cn-cell", "wrong-log2fc-digit"])
def test_a_planted_wrong_value_is_caught(job, tmp_path, kind, change):
    with open(job["got"][kind]) as fh:
        text = fh.read().splitlines(keepends=True)
    planted = change(text)
    assert planted != text
    path = tmp_path / "planted"
    path.write_text("".join(planted))
    assert lines.differ(str(path), job["want"][kind]) == 1


@pytest.fixture(scope="module")
def made():
    cfg = small_config()
    return cfg, em_matrix.made(cfg["fixture"], SEED)


def test_no_decision_lies_within_delta_after_the_maker(made):
    cfg, m = made
    d = reference.depths(m["raw"], np.float64)
    got, seen = em_matrix.watched_stages(d)
    assert not seen.cells.any(), seen.kinds
    assert got["cn"].tobytes() == reference.window_stages(
        d, workers=2)["cn"].tobytes()  # watching changes no answer
    calls = reference.merge(got["fc"], got["cn"], m["starts"], m["ends"])
    assert len(calls) > 20 and not len(em_matrix.text_ties(calls)[0])
    passes = m["conditioning"]["passes"]
    assert passes[0]["fragile"] > 0 and passes[-1]["fragile"] == 0
    assert m["conditioning"]["windows_redrawn"]


def test_the_float32_and_float64_references_agree(made):
    cfg, m = made
    args = (m["raw"], "chr20", m["starts"], m["ends"], m["samples"])
    assert (reference.emdepth(*args, dtype=np.float32, workers=2)
            == reference.emdepth(*args, dtype=np.float64, workers=2))


def test_a_drawn_again_cell_moves_no_median(made):
    cfg, m = made
    fx = cfg["fixture"]
    first = np.random.default_rng([SEED, 1]).poisson(
        em_matrix.plant(fx, SEED)["rate"])
    first = em_matrix.mean_depth(first, fx)
    assert not np.array_equal(first, m["raw"])
    assert np.array_equal(reference.medians(first)[0],
                          reference.medians(m["raw"])[0])


@pytest.fixture(scope="module")
def control_readings(tmp_path_factory):
    """{control: {kind: lines that differ}} on the conditioned fixture,
    as ``benchmark/control.py`` reads them."""
    import control

    tmp = str(tmp_path_factory.mktemp("controls"))
    return control.control_readings(small_config(), SEED, tmp)


# the two guarantees that no 30x cohort shows, on inputs where they show:
# coverage spread over x20 and a window deleted in most samples. The CN2
# preference only differs from the nearest lambda where l2 has fallen
# under 0.06 of the median (l0 above l1); the even-length quirk only moves
# the EM's start, which a 30x window's EM forgets
SHOWN_ELSEWHERE = {
    "textbook_even_median": [
        [419, 5, 36, 40, 8, 34, 12, 143, 11, 34],
        [438, 1, 45, 41, 14, 39, 3, 142, 16, 30],
        [412, 3, 33, 51, 8, 28, 6, 124, 17, 40],
        [410, 4, 33, 56, 8, 47, 8, 150, 17, 34],
        [451, 3, 34, 51, 6, 40, 6, 122, 17, 27],
        [1, 1, 0, 0, 2, 51, 0, 1, 17, 1],
        [398, 6, 39, 43, 12, 38, 14, 132, 12, 39],
        [454, 9, 33, 49, 10, 42, 8, 131, 9, 27],
        [433, 3, 51, 43, 8, 37, 8, 120, 16, 42],
        [438, 3, 30, 44, 9, 34, 11, 116, 13, 30],
        [437, 3, 30, 43, 10, 50, 6, 125, 12, 42],
        [408, 6, 32, 50, 11, 34, 9, 126, 14, 34]],
    "no_cn2_preference": [
        [16, 130, 21, 32, 13, 243, 288, 20, 59, 11],
        [22, 145, 25, 27, 14, 223, 300, 25, 42, 13],
        [19, 136, 27, 28, 16, 205, 275, 23, 48, 16],
        [23, 128, 33, 10, 7, 238, 280, 24, 58, 10],
        [15, 147, 35, 44, 19, 239, 311, 22, 69, 14],
        [0, 119, 22, 1, 11, 0, 1, 1, 62, 0],
        [14, 153, 24, 42, 19, 228, 296, 26, 54, 12],
        [16, 135, 19, 23, 19, 240, 305, 12, 55, 9],
        [15, 162, 33, 30, 14, 228, 315, 26, 75, 16],
        [24, 141, 27, 25, 16, 228, 289, 16, 52, 18],
        [20, 149, 27, 24, 13, 226, 273, 11, 56, 14],
        [22, 135, 33, 24, 11, 234, 292, 20, 41, 16]],
}


@pytest.mark.parametrize("control", reference.CONTROLS)
def test_a_control_reads_above_the_limit(control_readings, control):
    assert em_matrix.CONTROLS == reference.CONTROLS
    got = sum(control_readings[control].values())
    if control not in SHOWN_ELSEWHERE:
        assert got > 0, control_readings
        return
    assert got == 0  # PERF.md: what the conditioned cohort cannot show
    raw = np.array(SHOWN_ELSEWHERE[control], np.int16)
    starts = np.arange(len(raw)) * 1000
    args = (raw, "chr1", starts, starts + 1000,
            [f"s{i}" for i in range(raw.shape[1])])
    want = reference.emdepth(*args, workers=1)["cn_matrix"]
    broken = reference.emdepth(*args, break_guarantee=control,
                               workers=1)["cn_matrix"]
    assert lines.lines_differ(broken, want) > 0


@pytest.mark.parametrize("name,category", SPANS)
def test_the_job_records_the_span_vocabulary(job, name, category):
    mine = [s for s in job["spans"] if s.name == name]
    assert mine and {s.category for s in mine} == {category}
    by_id = {s.span_id: s for s in job["spans"]}
    parents = {by_id[s.parent_id].name for s in mine
               if s.parent_id in by_id}
    if category == "transfer":
        assert parents == {"device-compute"}, parents
    else:
        assert parents == {"run.emdepth"}, parents
    if name == "device-compute":  # one a chunk
        assert len(mine) == job["grew"]["emdepth.chunks_total"]


def known_matrix(path):
    """10 samples x 40 windows at 30x, sample 3 at a quarter of it in
    windows 5-9: one deletion, which the merge registers from its second
    window on (both neighbours aberrant, emdepth.go:339)."""
    depth = np.full((40, 10), 30)
    depth[5:10, 3] = 7
    with open(path, "w") as fh:
        fh.write("#chrom\tstart\tend\t"
                 + "\t".join(f"s{i}" for i in range(10)) + "\n")
        for w, row in enumerate(depth):
            fh.write(f"chr1\t{w * 1000}\t{w * 1000 + 1000}\t"
                     + "\t".join(map(str, row)) + "\n")


def planted_matrix(path):
    """64 samples x 300 windows at 30x over two chromosomes, seeded:
    deletions (CN 0 and 1) and duplications (CN 3 and 4) planted in a
    few samples, and a near-empty run of ten windows at 1x in 33 samples
    and 3x in 31, in which every sample opens a CNV at once (no sample
    lies in the CN2 bin, and lambda 2 falls between the two)."""
    rng = np.random.default_rng(39)
    depth = rng.poisson(30, size=(300, 64))
    for scale in (0.0, 0.5, 0.5, 1.5, 1.5, 2.0):
        s, w = int(rng.integers(64)), int(rng.integers(0, 280))
        n = int(rng.integers(4, 20))
        depth[w:w + n, s] = rng.poisson(30 * scale, size=min(n, 300 - w))
    depth[150:160, :33], depth[150:160, 33:] = 1, 3
    with open(path, "w") as fh:
        fh.write("#chrom\tstart\tend\t"
                 + "\t".join(f"s{i}" for i in range(64)) + "\n")
        for w, row in enumerate(depth):
            chrom, start = ("chr1", w * 1000) if w < 200 else (
                "chr2", (w - 200) * 1000)
            fh.write(f"{chrom}\t{start}\t{start + 1000}\t"
                     + "\t".join(map(str, row)) + "\n")


def run_cli(argv):
    """``cli.main(argv)`` with stdout caught: (stdout, counter growth)."""
    from goleft_tpu import cli, obs

    before = obs.get_registry().counters()
    with pytest.MonkeyPatch.context() as mp:
        out = io.StringIO()
        mp.setattr(sys, "stdout", out)
        assert not cli.main(argv)
    after = obs.get_registry().counters()
    return out.getvalue(), {k: after[k] - before.get(k, 0) for k in after}


@pytest.mark.parametrize("make", [known_matrix, planted_matrix],
                         ids=["known", "planted"])
def test_the_calls_are_the_same_with_and_without_the_cn_matrix(
        tmp_path, float32, make):
    make(tmp_path / "m.tsv")
    got = {}
    for with_cn in (False, True):
        d = tmp_path / str(with_cn)
        d.mkdir()
        argv = ["emdepth", "--vcf", str(d / "c.vcf"),
                "--candidates-out", str(d / "c.tsv")]
        if with_cn:
            argv += ["--matrix-out", str(d / "cn.tsv")]
        stdout, grew = run_cli(argv + [str(tmp_path / "m.tsv")])
        got[with_cn] = (stdout, (d / "c.vcf").read_bytes(),
                        (d / "c.tsv").read_bytes(), grew)
    calls = got[False][0].splitlines()[1:]
    assert len(calls) >= 1
    for i in range(3):
        assert got[True][i] == got[False][i]
    bare, with_cn = got[False][3], got[True][3]
    read = bare["emdepth.cn_dispatches_total"]
    assert read > 0 and bare["emdepth.cn_rows_from_chunk_total"] == 0
    assert with_cn["emdepth.cn_rows_from_chunk_total"] == read
    assert with_cn["emdepth.cn_dispatches_total"] == 0
    if make is planted_matrix:  # the near-empty run opened every sample
        run = {c.split("\t")[3] for c in calls
               if c.startswith("chr1\t") and int(c.split("\t")[1]) <= 159000
               and int(c.split("\t")[2]) >= 151000}
        assert len(run) == 64


@pytest.mark.parametrize("chunk,matrix_out,counter,want", [
    (None, True, "emdepth.windows_total", 40),
    (None, True, "emdepth.chunks_total", 1),
    (16, True, "emdepth.chunks_total", 3),
    (None, True, "emdepth.cn_dispatches_total", 0),
    (None, True, "emdepth.cn_rows_from_chunk_total", 4),
    (None, False, "emdepth.cn_dispatches_total", 4),
    (None, False, "emdepth.cn_rows_from_chunk_total", 0),
    (None, True, "emdepth.calls_total", 1),
    (None, True, "xla.h2d_bytes_total", 4 * 40 * 10),
    (16, True, "xla.h2d_bytes_total", 4 * 48 * 10),
    (None, True, "xla.d2h_bytes_total", 4 * 40 * 9 + 4 * 40 * 10),
], ids=["windows", "chunks", "chunks-padded", "cn-dispatches",
        "cn-rows-from-chunk", "cn-dispatches-no-matrix",
        "cn-rows-from-chunk-no-matrix", "calls", "h2d", "h2d-padded", "d2h"])
def test_the_counters_move_by_what_the_job_did(tmp_path, monkeypatch,
                                               float32, chunk, matrix_out,
                                               counter, want):
    from goleft_tpu.commands import emdepth_cmd

    known_matrix(tmp_path / "m.tsv")
    if chunk:
        monkeypatch.setattr(emdepth_cmd, "EM_CHUNK", chunk)
    argv = ["emdepth", str(tmp_path / "m.tsv")]
    if matrix_out:
        argv[1:1] = ["--matrix-out", str(tmp_path / "cn.tsv")]
    out, grew = run_cli(argv)
    assert grew[counter] == want
    if counter == "emdepth.calls_total":
        assert out.splitlines()[1].split("\t")[:4] == [
            "chr1", "6000", "10000", "s3"]


def test_the_configuration_and_cell_resolve():
    with open(f"{ROOT}/BENCHMARK.json") as fh:
        manifest = json.load(fh)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "emdepth2504")
    assert entry["file"] == "benchmark/configs/emdepth2504.json"
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"])
    cell = next(w for w in manifest["workloads"]
                if w["name"] == "emdepth2504.jobs")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "emdepth2504", "jobs", 1)
    fx = CONFIG["fixture"]
    assert (fx["windows"], fx["samples"], fx["window"]) == (16384, 2504, 1000)
    works = importlib.import_module("works.em_windows")
    work = {"windows": 16384, "samples": 2504, "em_chunk": 16384}
    assert works.job_units(work) == 1
    assert works.job_bytes(work) == 8 * 16384 * 2504 + 36 * 16384
    mine = [m for m in manifest["per_layer"]
            if m.get("workloads") == ["emdepth2504.jobs"]]
    assert len(mine) == 15
    for m in mine:
        with open(f"{BENCH}/metrics/{m['name']}.json") as fh:
            spec = json.load(fh)
        reducer = importlib.import_module(f"reducers.{spec['reducer']}")
        # the parent has none of the spans or counters: nothing to read
        empty = {"spans": [], "gbases": 1.0, "job_gbases": 1.0,
                 "trace": None, "counters": {"before": {}, "after": {}},
                 "device": {}, "meta": {"work": dict(work, kind="em_windows")}}
        assert reducer.reduce(spec["args"], empty) is None


def test_an_empty_matrix_puts_nothing_on_the_device():
    from goleft_tpu.commands import emdepth_cmd

    lam, cn = emdepth_cmd._batched_em(np.zeros((0, 5), np.int32))
    assert lam.shape == (0, 9) and cn.shape == (0, 5)
    assert cn.dtype == np.int32
    assert emdepth_cmd._batched_em(np.zeros((0, 5), np.float32),
                                   want_cn=False)[1] is None
