"""Native C++ io path: build, scan/inflate, decode parity vs pure Python."""

import numpy as np
import pytest

from goleft_tpu.io import native
from goleft_tpu.io.bam import BamReader, BamFile, open_bam, _PyBamAdapter
from goleft_tpu.io.bgzf import bgzf_decompress
from goleft_tpu.io.bai import build_bai, query_voffset

from helpers import write_bam, write_bam_and_bai, random_reads

needs_native = pytest.mark.skipif(
    native.get_lib() is None, reason="native toolchain unavailable"
)


@needs_native
@pytest.mark.native_io
def test_bgzf_scan_and_inflate(tmp_path):
    rng = np.random.default_rng(0)
    p = str(tmp_path / "t.bam")
    write_bam(p, random_reads(rng, 300, 0, 50_000))
    data = open(p, "rb").read()
    co, uo, total = native.bgzf_scan(data)
    body = native.bgzf_inflate(data, total)
    want = bgzf_decompress(data)
    assert bytes(body) == want
    assert uo[0] == 0 and co[0] == 0
    assert np.all(np.diff(co) > 0)


@needs_native
@pytest.mark.native_io
def test_bgzf_stream_inflate_only(tmp_path):
    """The decode-floor probe streams the exact product ring driver with
    a no-op walk: total uncompressed bytes must match the block scan,
    and corrupt payloads must still fail CRC."""
    rng = np.random.default_rng(1)
    p = str(tmp_path / "t.bam")
    write_bam(p, random_reads(rng, 500, 0, 80_000))
    comp = np.fromfile(p, dtype=np.uint8)
    _, _, total = native.bgzf_scan(comp)
    assert native.bgzf_stream_inflate_only(comp) == total
    assert native.bgzf_stream_inflate_only(comp, check_crc=False) == total
    # flip one payload byte mid-file: CRC mode must raise, no-CRC mode
    # either inflates garbage or reports a deflate error — never crashes
    bad = comp.copy()
    bad[len(bad) // 2] ^= 0xFF
    with pytest.raises(ValueError):
        native.bgzf_stream_inflate_only(bad)
    try:  # no-CRC mode: inflates garbage or reports a typed error,
        native.bgzf_stream_inflate_only(bad, check_crc=False)  # never
    except ValueError:  # crashes
        pass


@needs_native
@pytest.mark.native_io
def test_native_decode_matches_python(tmp_path):
    reads = [
        (0, 100, "100M", 60, 0),
        (0, 150, "50M10D50M", 30, 0),
        (0, 200, "10S90M", 20, 0x400),
        (0, 300, "20M5I30M2N40M", 50, 0),
        (1, 5, "100M", 60, 0),
    ]
    p = str(tmp_path / "t.bam")
    write_bam(p, reads)
    data = open(p, "rb").read()
    bf = BamFile(data)
    assert bf.native
    py = BamReader(data).read_columns()
    nat = bf.read_columns()
    for f in ("tid", "pos", "end", "mapq", "flag", "tlen", "read_len",
              "mate_pos", "seg_start", "seg_end", "seg_read"):
        np.testing.assert_array_equal(getattr(nat, f), getattr(py, f), f)
    np.testing.assert_array_equal(nat.single_m, py.single_m)


@needs_native
@pytest.mark.native_io
def test_native_region_decode(tmp_path):
    rng = np.random.default_rng(1)
    reads = random_reads(rng, 2000, 0, 200_000)
    p = str(tmp_path / "t.bam")
    write_bam_and_bai(p, reads, ref_names=("chr1",), ref_lens=(200_000,))
    data = open(p, "rb").read()
    bf = BamFile(data)
    idx = build_bai(p)
    start, end = 50_000, 60_000
    voff = query_voffset(idx, 0, start)
    nat = bf.read_columns(tid=0, start=start, end=end, voffset=voff)
    rdr = BamReader(data)
    rdr.seek_virtual(voff)
    py = rdr.read_columns(tid=0, start=start, end=end)
    np.testing.assert_array_equal(nat.pos, py.pos)
    np.testing.assert_array_equal(nat.seg_start, py.seg_start)
    assert nat.n_reads > 0


@pytest.mark.native_io
def test_get_lib_waits_for_the_build_in_progress(monkeypatch):
    """A thread that asks while another builds the library gets the
    library, not the pure-Python fallback (the first job of a fresh
    checkout loads its indexes 8 at a time)."""
    import concurrent.futures as cf
    import threading
    import time

    started = threading.Event()
    built = object()

    def slow_build():
        started.set()
        time.sleep(0.3)
        return built

    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.setattr(native, "_load_lib", slow_build)
    with cf.ThreadPoolExecutor(max_workers=4) as ex:
        first = ex.submit(native.get_lib)
        assert started.wait(timeout=10)
        late = [ex.submit(native.get_lib) for _ in range(3)]
        assert first.result(timeout=10) is built
        assert [f.result(timeout=10) for f in late] == [built] * 3


@pytest.mark.native_io
def test_open_bam_fallback(tmp_path, monkeypatch):
    rng = np.random.default_rng(2)
    p = str(tmp_path / "t.bam")
    write_bam(p, random_reads(rng, 50, 0, 10_000))
    data = open(p, "rb").read()
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", True)
    h = open_bam(data)
    assert isinstance(h, _PyBamAdapter)
    cols = h.read_columns()
    assert cols.n_reads == 50


@needs_native
def test_depth_cli_with_native(tmp_path):
    """depth CLI produces identical output with and without native io."""
    import os
    from goleft_tpu.commands.depth import run_depth
    from helpers import write_fasta
    from goleft_tpu.io.fai import write_fai

    rng = np.random.default_rng(3)
    reads = random_reads(rng, 800, 0, 60_000)
    p = str(tmp_path / "t.bam")
    write_bam_and_bai(p, reads, ref_names=("chr1",), ref_lens=(60_000,))
    fa = write_fasta(str(tmp_path / "r.fa"), {"chr1": "A" * 60_000})
    write_fai(fa)
    d1, c1 = run_depth(p, str(tmp_path / "nat"), reference=fa, window=500)
    os.environ["GOLEFT_TPU_NO_NATIVE"] = "1"
    try:
        native._lib, native._tried = None, False
        d2, c2 = run_depth(p, str(tmp_path / "pyf"), reference=fa,
                           window=500)
    finally:
        del os.environ["GOLEFT_TPU_NO_NATIVE"]
        native._lib, native._tried = None, False
    assert open(d1).read().replace("nat", "") == \
        open(d2).read().replace("pyf", "")
    assert open(c1).read() == open(c2).read()


@needs_native
@pytest.mark.native_io
def test_window_reduce_numpy_oracle(tmp_path):
    """Fused C++ decode+window-reduce vs a numpy transcription of the
    same math (no jax — runs under the ASan target)."""
    rng = np.random.default_rng(55)
    L = 50_000
    reads = []
    for s in np.sort(rng.integers(0, L - 300, size=1500)):
        cig = rng.choice(["100M", "40M20D40M", "10S90M", "25M5I70M"])
        mq = int(rng.integers(0, 61))
        fl = int(rng.choice([0, 0x400, 0x100]))
        reads.append((0, int(s), cig, mq, fl))
    p = str(tmp_path / "wr.bam")
    write_bam_and_bai(p, reads, ref_names=("chr1",), ref_lens=(L,))
    bf = BamFile.from_file(p, lazy=True)
    rs, re_, w0, window, cap, mapq = 7_003, 44_751, 7_000, 250, 30, 20
    length = ((re_ - w0) + window - 1) // window * window
    got = bf.window_reduce(0, rs, re_, w0, length, window, cap, mapq,
                           0x704)
    # numpy oracle over the pure-python decode
    from goleft_tpu.io.bam import BamReader

    cols = BamReader.from_file(p).read_columns(tid=0, start=rs, end=re_)
    ok = (cols.mapq >= mapq) & ((cols.flag & 0x704) == 0)
    keep = ok[cols.seg_read]
    delta = np.zeros(length + 1, np.int64)
    s = np.clip(np.maximum(cols.seg_start[keep], rs) - w0, 0, length)
    e = np.clip(np.minimum(cols.seg_end[keep], re_) - w0, 0, length)
    np.add.at(delta, s, 1)
    np.add.at(delta, e, -1)
    depth = np.minimum(np.cumsum(delta[:length]), cap)
    pos = np.arange(length) + w0
    depth = np.where((pos >= rs) & (pos < re_), depth, 0)
    want = depth.reshape(-1, window).sum(axis=1)
    np.testing.assert_array_equal(got, want)


@needs_native
@pytest.mark.native_io
def test_bai_scan_matches_python_parse(tmp_path, monkeypatch):
    """Native structure scan + lazy bins == eager pure-Python parse."""
    rng = np.random.default_rng(66)
    reads = random_reads(rng, 3000, 0, 90_000) + \
        random_reads(rng, 800, 1, 45_000)
    p = str(tmp_path / "b.bam")
    write_bam_and_bai(p, reads)
    from goleft_tpu.io.bai import read_bai

    fast = read_bai(p + ".bai")
    monkeypatch.setenv("GOLEFT_TPU_NO_NATIVE", "1")
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    slow = read_bai(p + ".bai")
    monkeypatch.setattr(native, "_tried", False)

    assert len(fast.refs) == len(slow.refs)
    assert fast.n_no_coor == slow.n_no_coor
    for rf, rs in zip(fast.refs, slow.refs):
        np.testing.assert_array_equal(rf.intervals, rs.intervals)
        assert rf.mapped == rs.mapped
        assert rf.unmapped == rs.unmapped
        assert rf.bins == rs.bins  # triggers the lazy parse
