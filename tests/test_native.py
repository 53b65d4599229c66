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


# ---- format_fixed2_rows: indexcov's ROC text, np.char.mod's byte for byte

def fixed2_want(prefix, labels, vals) -> bytes:
    cells = np.char.mod("%.2f", vals.T)
    return "".join(
        prefix + "\t" + labels[r] + "\t" + "\t".join(cells[r]) + "\n"
        for r in range(len(labels))).encode()


def fixed2_got(prefix, labels, vals, short_by: int = 0) -> bytes:
    out = np.empty(native.fixed2_rows_scratch_bytes(
        prefix, labels, vals.shape[0]) - short_by, dtype=np.uint8)
    return out[:native.format_fixed2_rows(out, prefix, labels,
                                          vals)].tobytes()


def as_rows(values, n_rows: int = 7) -> np.ndarray:
    """float32 values as an (n_cols, n_rows) matrix, zeros to fill."""
    v = np.asarray(values, dtype=np.float32).ravel()
    m = np.zeros(-(-len(v) // n_rows) * n_rows, dtype=np.float32)
    m[:len(v)] = v
    return m.reshape(-1, n_rows)


def quotients(n: int) -> np.ndarray:
    # what unpack_chrom_qc divides: float32 counts over a float32 total
    return np.arange(n + 1, dtype=np.float32) / np.float32(n)


def tie_neighbours() -> np.ndarray:
    """The float32 next to every x.xx5 in [0, 2] and two steps either
    side of it: 0.125, 0.375, ... are ties on the binary value itself
    (to even: 0.12, 0.38), the others lie just over or under theirs."""
    near = np.float32((np.arange(200) + 0.5) / 100)
    out = [near]
    lo = hi = near
    for _ in range(2):
        lo = np.nextafter(lo, np.float32(-1))
        hi = np.nextafter(hi, np.float32(3))
        out += [lo, hi]
    return np.concatenate(out)


def random_bit_patterns() -> np.ndarray:
    bits = np.random.default_rng(35).integers(
        0, 2**32, size=70_000, dtype=np.uint64).astype(np.uint32)
    return bits.view(np.float32)


NEG_NAN = np.array([0xFFC00000], dtype=np.uint32).view(np.float32)[0]
FIXED2_CASES = {
    "k-over-1": lambda: as_rows(quotients(1)),
    "k-over-7": lambda: as_rows(quotients(7)),
    "k-over-2048": lambda: as_rows(quotients(2048)),
    "k-over-15195": lambda: as_rows(quotients(15195)),
    "tie-neighbours": lambda: as_rows(tie_neighbours()),
    # 0/0 on x86 is a NaN with the sign bit set: Python prints "nan",
    # glibc's printf "-nan"
    "nan-of-both-signs-and-inf": lambda: as_rows(
        [np.nan, NEG_NAN, np.float32(0) / np.zeros(1, np.float32)[0],
         np.inf, -np.inf]),
    "signed-zero-and-round-up": lambda: as_rows(
        [-0.0, 0.0, -0.001, -0.004999, 0.995, 0.999, 0.9949, 9.995,
         9.999, 99.995, 1e-45, -1e-45]),
    "ten-and-over-and-negative": lambda: as_rows(
        [10.0, 12.345, -12.345, 1234567.0, 1e7, -1e7, 16777216.0,
         16777217.0, 8.9e13, 9.1e13, -9.1e13, 1e20, 3.4028235e38,
         -3.4028235e38, 2.0**100]),
    "random-bit-patterns": lambda: as_rows(random_bit_patterns(), 70),
    "transposed": lambda: as_rows(quotients(699), 70).T[:, :7],
    "column-sliced": lambda: as_rows(quotients(699), 70)[::3, 5:66:2],
    "reversed": lambda: as_rows(quotients(699), 70)[::-1, ::-1],
    "one-cell": lambda: np.full((1, 1), 0.5, dtype=np.float32),
}


@needs_native
@pytest.mark.native_io
@pytest.mark.parametrize("case", sorted(FIXED2_CASES))
def test_format_fixed2_rows_is_np_char_mod_byte_for_byte(case):
    with np.errstate(invalid="ignore", over="ignore"):
        vals = FIXED2_CASES[case]()
    assert vals.dtype == np.float32
    labels = ["%.2f" % (r / 46.7) for r in range(vals.shape[1])]
    want = fixed2_want("chr17_alt", labels, vals)
    assert fixed2_got("chr17_alt", labels, vals) == want
    if case.startswith("nan"):
        assert want.count(b"\tnan") == 3 and b"-nan" not in want


@needs_native
@pytest.mark.native_io
def test_format_fixed2_rows_scratch_one_byte_short_is_an_error():
    vals = as_rows(quotients(699), 70)
    labels = [str(r) for r in range(70)]
    assert fixed2_got("c", labels, vals) == fixed2_want("c", labels, vals)
    out = np.zeros(native.fixed2_rows_scratch_bytes("c", labels, 10) - 1,
                   dtype=np.uint8)
    with pytest.raises(ValueError, match="scratch too small"):
        native.format_fixed2_rows(out, "c", labels, vals)
    assert not out.any()  # not a line of it, let alone a truncated one


@needs_native
@pytest.mark.native_io
def test_format_fixed2_rows_takes_float32_only():
    # a float64 cast to float32 and then printed would round twice
    with pytest.raises(TypeError, match="float32"):
        native.format_fixed2_rows(np.empty(1 << 16, np.uint8), "c", ["0"],
                                  np.zeros((3, 1), np.float64))
    with pytest.raises(ValueError, match="a label a row"):
        native.format_fixed2_rows(np.empty(1 << 16, np.uint8), "c", ["0"],
                                  np.zeros((3, 2), np.float32))


def roc_block(samples: int = 9) -> np.ndarray:
    """A chromosome's ROC as unpack_chrom_qc makes it, one sample with
    no tile (0/0)."""
    from goleft_tpu.ops import indexcov_ops as ops

    rng = np.random.default_rng(3)
    top = np.sort(rng.integers(0, 2048, (samples, ops.SLOTS)),
                  axis=1)[:, ::-1].astype(np.float32)
    top[4] = 0
    with np.errstate(invalid="ignore"):
        return top / top[:, :1]


ROC_NATIVE = "indexcov.roc_native_blocks_total"


@pytest.mark.native_io
@pytest.mark.parametrize("path", ["native", "fallback", "float64"])
def test_write_roc_rows_bytes_and_counter(monkeypatch, path):
    """The same bytes with and without the library, and the counter
    says which wrote them: +1 a block on the native path, +0 on
    np.char.mod's."""
    import io

    from goleft_tpu import obs
    from goleft_tpu.commands import indexcov as ic
    from goleft_tpu.ops import indexcov_ops as ops

    rocs = roc_block()
    if path == "native" and native.get_lib() is None:
        pytest.skip("no native library here")
    if path == "fallback":
        monkeypatch.setattr(native, "get_lib", lambda: None)
    if path == "float64":  # exact in float64: the text does not change
        rocs = rocs.astype(np.float64)
    cov = ["%.2f" % (i / (ops.SLOTS * ops.SLOTS_MID))
           for i in range(ops.SLOTS)]
    want = fixed2_want("chrX", cov, rocs).decode()
    assert "\tnan" in want and "-nan" not in want
    counter = obs.get_registry().counter(ROC_NATIVE)
    before = counter.value
    fh = io.StringIO()
    ic.write_roc_rows(fh, "chrX", rocs)
    ic.write_roc_rows(fh, "chrX", rocs)
    assert fh.getvalue() == want * 2
    assert counter.value - before == (2 if path == "native" else 0)


@needs_native
@pytest.mark.native_io
def test_write_roc_rows_keeps_its_scratch_from_block_to_block():
    import io

    from goleft_tpu.commands import indexcov as ic

    rocs = roc_block()
    ic.write_roc_rows(io.StringIO(), "chr1", rocs)
    kept = ic._roc_scratch.out
    for name in ("chr2", "chr10", "chrUn_KI270302v1"):
        ic.write_roc_rows(io.StringIO(), name, rocs)
    assert ic._roc_scratch.out is kept
    wide = io.StringIO()  # a wider cohort outgrows it, once
    ic.write_roc_rows(wide, "chr1", np.tile(rocs, (200, 1)))
    assert ic._roc_scratch.out is not kept
    assert len(wide.getvalue().splitlines()[0].split("\t")) == 2 + 1800
