"""The ``.bed.gz`` stream (``goleft_tpu/io/bedgz.py``): blocks formatted and
BGZF-deflated on a bounded, ordered pool give the file the serial writer
gave, gunzipped byte for byte, as valid BGZF, within the bound on blocks in
flight; and a worker's failure reaches the caller, leaves the file without
its EOF member and no thread behind.
"""

import gzip
import io
import json
import os
import struct
import sys
import threading

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from comparators import gz_lines  # noqa: E402
from makers import bai_cohort  # noqa: E402

from goleft_tpu import obs  # noqa: E402
from goleft_tpu.io import bedgz, native  # noqa: E402
from goleft_tpu.io.bgzf import BGZF_EOF, BgzfReader, BgzfWriter  # noqa: E402
from goleft_tpu.utils.profiling import StageTimer  # noqa: E402

with open(f"{BENCH}/configs/indexcov500.json") as _fh:
    CONFIG = json.load(_fh)
SEED = 2_147_483_693
TILE = 16384
HEADER = b"#chrom\tstart\tend\ta\tb\n"
WIDTHS = [1, 8]


def members(data: bytes) -> list[tuple[int, int]]:
    """(BSIZE, ISIZE) of every BGZF member of ``data``, walked by the
    ``BC`` subfield alone; raises where a member has none."""
    out, off = [], 0
    while off < len(data):
        magic, flg, xlen = struct.unpack_from("<HxB6xH", data, off)
        assert magic == 0x8B1F and flg & 4
        si1, si2, slen, bsize_m1 = struct.unpack_from("<BBHH", data, off + 12)
        assert (si1, si2, slen, xlen) == (0x42, 0x43, 2, 6)
        bsize = bsize_m1 + 1
        out.append((bsize, struct.unpack_from("<I", data, off + bsize - 4)[0]))
        off += bsize
    assert off == len(data)
    return out


def gauge(name: str) -> float:
    return obs.get_registry().gauge(name).value


def counter(name: str) -> int:
    return obs.get_registry().counter(name).value


def bedgz_threads() -> list[str]:
    return [t.name for t in threading.enumerate()
            if t.name.startswith("bedgz")]


def cohort_matrix(samples=37, tiles=5000, seed=11):
    """A chromosome of more than two blocks whose samples end at
    different tiles, with values of every regime "%.3g" has."""
    rng = np.random.default_rng(seed)
    mat = rng.lognormal(0, 0.4, (samples, tiles + 120)).astype(np.float32)
    mat[:, ::41] = 0
    mat[1, 5:9] = [1e-6, 12345.678, 2.5, 0.125]
    mat[2, 200:204] = [999.5, 0.00010004, 1e5, 0.375]
    lengths = rng.integers(tiles * 5 // 9, tiles + 1, samples)
    lengths[0] = tiles
    valid = np.arange(mat.shape[1])[None, :] < lengths[:, None]
    return mat, valid, tiles


def serial_file(mat, valid, tiles) -> bytes:
    """What the serial writer made of the same blocks."""
    buf = io.BytesIO()
    with BgzfWriter(buf, level=1) as w:
        w.write(HEADER)
        for lo in range(0, tiles, 2048):
            hi = min(lo + 2048, tiles)
            w.write(bedgz.format_bed_rows(
                "chr7", lo, hi, mat[:, lo:hi], valid[:, lo:hi]))
    return buf.getvalue()


@pytest.fixture(scope="module")
def serial_text():
    return gzip.decompress(serial_file(*cohort_matrix()))


@pytest.fixture(params=[(w, n) for w in WIDTHS for n in ("native", "numpy")],
                ids=lambda p: f"width{p[0]}-{p[1]}")
def streamed(request, monkeypatch):
    """The stream's file of the same blocks, at one pool width, through
    the native formatter or without the library."""
    width, how = request.param
    if how == "native" and native.get_lib() is None:
        pytest.skip("no native library here")
    if how == "numpy":
        monkeypatch.setattr(native, "get_lib", lambda: None)
    monkeypatch.setattr(bedgz, "POOL_WIDTH", width)
    # small pieces: several deflate calls a block, several members a piece
    monkeypatch.setattr(bedgz, "PIECE_BYTES", 3 * 65280)
    obs.get_registry().gauge("indexcov.bed_blocks_inflight_max").set(0)
    before = {k: counter(k) for k in ("indexcov.bed_text_bytes_total",
                                      "indexcov.bed_blocks_pooled_total")}
    mat, valid, tiles = cohort_matrix()
    buf = io.BytesIO()
    timer = StageTimer()
    with bedgz.BedGzStream(buf, HEADER, timer) as bed:
        for lo in range(0, tiles, 2048):
            hi = min(lo + 2048, tiles)
            bed.submit("chr7", lo, hi, mat[:, lo:hi], valid[:, lo:hi])
    return {"file": buf.getvalue(), "blocks": -(-tiles // 2048),
            "timer": timer,
            "grew": {k: counter(k) - v for k, v in before.items()}}


def test_the_stream_s_file_gunzips_to_the_serial_writer_s(streamed,
                                                          serial_text):
    assert gzip.decompress(streamed["file"]) == serial_text


def test_the_stream_s_file_is_bgzf(streamed, serial_text):
    data = streamed["file"]
    walked = members(data)
    assert all(bsize <= 65536 and isize <= 65280 for bsize, isize in walked)
    assert data.endswith(BGZF_EOF) and walked[-1] == (28, 0)
    assert all(isize for _, isize in walked[:-1])  # one EOF, the last
    assert sum(isize for _, isize in walked) == len(serial_text)
    reader = BgzfReader(data)
    assert reader.read(len(serial_text) + 1) == serial_text


def test_the_stream_counts_its_text_and_blocks(streamed, serial_text):
    assert streamed["grew"] == {
        "indexcov.bed_text_bytes_total": len(serial_text),
        "indexcov.bed_blocks_pooled_total": streamed["blocks"]}
    assert streamed["timer"].counts["write-output"] == streamed["blocks"]


def test_the_stream_stays_within_its_bound(streamed):
    assert 1 <= gauge("indexcov.bed_blocks_inflight_max") <= min(
        bedgz.MAX_INFLIGHT, streamed["blocks"])
    assert not bedgz_threads()


@pytest.mark.parametrize("bound", [1, 2])
def test_a_submit_waits_at_the_bound(monkeypatch, bound):
    """With the workers held, the bound + 1st block waits; it records a
    ``write-wait`` span and no more than the bound are ever in flight."""
    monkeypatch.setattr(bedgz, "MAX_INFLIGHT", bound)
    obs.get_registry().gauge("indexcov.bed_blocks_inflight_max").set(0)
    gate = threading.Event()
    real = bedgz.BedGzStream._deflated_rows

    def held(self, *a):
        gate.wait(10)
        return real(self, *a)

    monkeypatch.setattr(bedgz.BedGzStream, "_deflated_rows", held)
    mat, valid, _ = cohort_matrix(samples=3, tiles=300)
    n_spans = len(obs.get_tracer().snapshot())
    buf = io.BytesIO()
    threading.Timer(0.2, gate.set).start()
    with bedgz.BedGzStream(buf, HEADER, StageTimer()) as bed:
        for lo in range(0, 300, 100):
            bed.submit("c", lo, lo + 100, mat[:, lo:lo + 100],
                       valid[:, lo:lo + 100])
    waits = [s for s in obs.get_tracer().snapshot()[n_spans:]
             if s.name == "write-wait"]
    assert waits and {s.category for s in waits} == {"wait"}
    assert gauge("indexcov.bed_blocks_inflight_max") == bound
    assert len(gzip.decompress(buf.getvalue()).splitlines()) == 301


def test_wait_through_returns_when_the_block_is_in_the_file():
    mat, valid, _ = cohort_matrix(samples=3, tiles=300)
    buf = io.BytesIO()
    with bedgz.BedGzStream(buf, HEADER, StageTimer()) as bed:
        bed.wait_through(bed.last_ticket)  # nothing handed over yet
        first = bed.submit("c", 0, 100, mat[:, :100], valid[:, :100])
        bed.submit("c", 100, 200, mat[:, 100:200], valid[:, 100:200])
        bed.wait_through(first)
        assert len(gzip.decompress(buf.getvalue()).splitlines()) >= 101


@pytest.mark.parametrize("stage", ["format", "deflate"])
def test_a_worker_s_exception_reaches_the_caller(monkeypatch, stage):
    if native.get_lib() is None:
        pytest.skip("no native library here")
    name = {"format": "format_float32_rows",
            "deflate": "bgzf_deflate_members"}[stage]
    real, calls = getattr(native, name), []

    def third_call_fails(*a, **k):
        calls.append(1)
        if len(calls) == 3:
            raise ValueError(f"planted in {stage}")
        return real(*a, **k)

    monkeypatch.setattr(native, name, third_call_fails)
    mat, valid, tiles = cohort_matrix()
    buf = io.BytesIO()
    with pytest.raises(ValueError, match=f"planted in {stage}"):
        with bedgz.BedGzStream(buf, HEADER, StageTimer()) as bed:
            for lo in range(0, tiles, 100):
                bed.submit("chr7", lo, lo + 100, mat[:, lo:lo + 100],
                           valid[:, lo:lo + 100])
    data = buf.getvalue()
    assert not data.endswith(BGZF_EOF)
    assert all(isize for _, isize in members(data))  # whole members only
    assert not bedgz_threads()


# ---- through the command, over a cohort from the benchmark's maker ----

def cohort_config() -> dict:
    """9 indexes x two contigs of more than 2,048 tiles (three blocks and
    two) and a short one, four in ten rows short of their last tiles."""
    cfg = json.loads(json.dumps(CONFIG))
    cfg["fixture"].update(
        samples=9, arm_gains=1, short_tail_fraction=0.4,
        contigs=[["chr1", TILE * 4200 + 77], ["chr2", TILE * 2049],
                 ["chrX", TILE * 130], ["chrY", TILE * 40 + 9]])
    return cfg


@pytest.fixture(scope="module")
def cohort(tmp_path_factory):
    cfg = cohort_config()
    d = str(tmp_path_factory.mktemp("bedgz_cohort"))
    meta = bai_cohort.build(cfg, SEED, d)
    texts, _ = bai_cohort.expected(cfg, SEED)
    with open(f"{d}/expected.bed", "w") as fh:
        fh.write(texts["expected.bed"])
    return {"dir": d, "inputs": [f"{d}/{f}" for f in meta["inputs"]],
            "fai": f"{d}/ref.fa.fai", "want": f"{d}/expected.bed",
            "blocks": 3 + 2 + 1 + 1}


@pytest.fixture(params=WIDTHS, ids=lambda w: f"width{w}")
def indexcov_job(request, cohort, tmp_path, monkeypatch):
    from goleft_tpu.commands.indexcov import run_indexcov

    monkeypatch.setattr(bedgz, "POOL_WIDTH", request.param)
    obs.get_registry().gauge("indexcov.bed_blocks_inflight_max").set(0)
    before = obs.get_registry().counters()
    res = run_indexcov(cohort["inputs"], str(tmp_path / "out"),
                       fai=cohort["fai"], write_html=False, write_png=False)
    after = obs.get_registry().counters()
    return {"bed": res["bed"], "want": cohort["want"],
            "blocks": cohort["blocks"],
            "grew": {k: after[k] - before.get(k, 0) for k in after}}


def test_indexcov_s_bed_is_the_reference_s(indexcov_job):
    assert gz_lines.differ(indexcov_job["bed"], indexcov_job["want"]) == 0


def test_indexcov_s_bed_is_bgzf_that_gzip_and_the_reader_read(indexcov_job):
    with open(indexcov_job["bed"], "rb") as fh:
        data = fh.read()
    walked = members(data)
    assert all(bsize <= 65536 for bsize, _ in walked)
    assert walked[-1] == (28, 0) and all(n for _, n in walked[:-1])
    text = gzip.decompress(data)
    assert BgzfReader(data).read(len(text) + 1) == text
    with open(indexcov_job["want"], "rb") as fh:
        assert text == fh.read()


def test_indexcov_counts_the_text_and_the_blocks(indexcov_job):
    assert indexcov_job["grew"]["indexcov.bed_text_bytes_total"] == \
        os.path.getsize(indexcov_job["want"])
    assert indexcov_job["grew"]["indexcov.bed_blocks_pooled_total"] == \
        indexcov_job["blocks"]


def test_indexcov_keeps_the_blocks_in_flight_within_the_bound(indexcov_job):
    # the blocks of two chromosomes at most: chr1's three and chr2's two
    assert 1 <= gauge("indexcov.bed_blocks_inflight_max") <= 5
    assert not bedgz_threads()


@pytest.mark.parametrize("stage", ["format", "deflate"])
def test_a_failing_worker_fails_the_job(cohort, tmp_path, monkeypatch,
                                        stage):
    """``run_indexcov`` raises, ``cli.main`` returns non-zero, the
    ``.bed.gz`` ends without its EOF member and no thread is left."""
    from goleft_tpu import cli
    from goleft_tpu.commands.indexcov import run_indexcov

    if native.get_lib() is None:
        pytest.skip("no native library here")
    name = {"format": "format_float32_rows",
            "deflate": "bgzf_deflate_members"}[stage]
    real, calls = getattr(native, name), []

    def second_call_fails(*a, **k):
        calls.append(1)
        if len(calls) % 6 == 2:
            raise ValueError(f"planted in {stage}")
        return real(*a, **k)

    monkeypatch.setattr(native, name, second_call_fails)
    with pytest.raises(ValueError, match="planted"):
        run_indexcov(cohort["inputs"], str(tmp_path / "a"),
                     fai=cohort["fai"], write_html=False, write_png=False)
    assert not bedgz_threads()
    out = tmp_path / "b"
    assert cli.main(["indexcov", "--no-html", "-f", cohort["fai"],
                     "-d", str(out), *cohort["inputs"]]) == 1
    assert not bedgz_threads()
    for d in (tmp_path / "a", out):
        with open(d / f"{d.name}-indexcov.bed.gz", "rb") as fh:
            data = fh.read()
        assert data and not data.endswith(BGZF_EOF)
        assert all(isize for _, isize in members(data))


def test_cohortscan_writes_through_the_same_stream(cohort, tmp_path):
    from goleft_tpu.cohort.scan import run_cohortscan

    before = counter("indexcov.bed_blocks_pooled_total")
    res = run_cohortscan(cohort["inputs"], str(tmp_path / "scan"),
                         fai=cohort["fai"], chunk_samples=4)
    assert counter("indexcov.bed_blocks_pooled_total") - before == \
        cohort["blocks"]
    assert gz_lines.differ(res["bed"], cohort["want"]) == 0
    assert not bedgz_threads()
