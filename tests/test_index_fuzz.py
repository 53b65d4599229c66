"""Corrupt-index fuzz: mutated .bai/.crai bytes must produce a typed
error or a clean parse — never a crash, hang, or unhandled low-level
exception. Exercises the C bai_scan bounds checks and the Python
fallbacks on the same bytes."""

import gzip

import numpy as np
import pytest

from goleft_tpu.io.bai import build_bai, read_bai, write_bai
from goleft_tpu.io import native
from helpers import write_bam_and_bai, random_reads

# the readers' contract: every corruption surfaces as ValueError
OK_ERRORS = (ValueError,)


@pytest.fixture(scope="module")
def bai_bytes(tmp_path_factory):
    d = tmp_path_factory.mktemp("ixfuzz")
    rng = np.random.default_rng(0)
    p = str(d / "t.bam")
    write_bam_and_bai(p, random_reads(rng, 2000, 0, 500_000),
                      ref_names=("chr1", "chr2"),
                      ref_lens=(500_000, 400_000))
    return open(p + ".bai", "rb").read()


def _mutations(data: bytes, rng, n: int):
    for _ in range(n):
        b = bytearray(data)
        kind = rng.integers(0, 3)
        if kind == 0:  # bit flip
            i = int(rng.integers(0, len(b)))
            b[i] ^= 1 << int(rng.integers(0, 8))
        elif kind == 1:  # truncate
            b = b[: int(rng.integers(0, len(b)))]
        else:  # int splice: overwrite 4 bytes with an extreme value
            i = int(rng.integers(0, max(len(b) - 4, 1)))
            b[i : i + 4] = int(rng.choice(
                [0x7FFFFFFF, 0xFFFFFFFF, 0x80000000])).to_bytes(
                    4, "little")
        yield bytes(b)


@pytest.mark.native_io
def test_bai_fuzz_python_and_native(bai_bytes):
    rng = np.random.default_rng(1)
    survived = crashed_cleanly = 0
    for mut in _mutations(bai_bytes, rng, 300):
        try:
            idx = read_bai(mut)
            # a successful parse must still yield a usable structure
            idx.sizes()
            survived += 1
        except OK_ERRORS:
            crashed_cleanly += 1
    assert survived + crashed_cleanly == 300
    assert crashed_cleanly > 0, "no mutation was ever detected"


@pytest.mark.native_io
def test_bai_scan_native_fuzz(bai_bytes):
    """The C scanner itself: must return n_ref or a negative error for
    any mutation (ctypes wrapper raises ValueError on negatives)."""
    if native.get_lib() is None:
        pytest.skip("native lib unavailable")
    rng = np.random.default_rng(2)
    for mut in _mutations(bai_bytes, rng, 300):
        try:
            native.bai_scan(np.frombuffer(mut, dtype=np.uint8))
        except OK_ERRORS:
            pass


def _outcome(load):
    """What a load of one index came to: its values, or the words of
    its typed error."""
    try:
        s = load()
    except OK_ERRORS as e:
        return ("refused", str(e))
    return ("read", [a.tolist() for a in s.sizes], s.mapped, s.unmapped,
            s.nbytes, s.median)


@pytest.mark.native_io
@pytest.mark.parametrize("seed", [1, 2, 4])  # the seeds of the tests above
def test_bai_fuzz_one_pass_load_agrees_with_read_bai(bai_bytes, tmp_path,
                                                     seed):
    """indexcov's one-pass load (native.bai_tile_sizes behind
    SampleIndex) on every mutation: the values read_bai, sizes() and
    median_size_per_tile give, or their ValueError word for word."""
    import types

    from goleft_tpu.commands.indexcov import SampleIndex
    from goleft_tpu.ops.indexcov_ops import median_size_per_tile

    if native.get_lib() is None:
        pytest.skip("native lib unavailable")

    def python_path(data):
        idx = read_bai(data)
        sizes = idx.sizes()
        return types.SimpleNamespace(
            sizes=sizes, mapped=idx.mapped_total,
            unmapped=idx.unmapped_total, nbytes=len(data),
            median=median_size_per_tile(sizes))

    path = str(tmp_path / "mut.bai")
    refused = 0
    for mut in [bai_bytes] + list(
            _mutations(bai_bytes, np.random.default_rng(seed), 300)):
        with open(path, "wb") as fh:
            fh.write(mut)
        want = _outcome(lambda: python_path(mut))
        assert _outcome(lambda: SampleIndex(path)) == want
        refused += want[0] == "refused"
    assert 0 < refused < 301


def test_crai_fuzz(tmp_path):
    from goleft_tpu.io.crai import read_crai

    lines = "".join(
        f"{tid}\t{s}\t{s + 999}\t{1000 + s}\t0\t500\n"
        for tid in (0, 1) for s in range(0, 50_000, 1000)
    )
    data = gzip.compress(lines.encode())
    rng = np.random.default_rng(3)
    survived = rejected = 0
    for mut in _mutations(data, rng, 200):
        try:
            read_crai(mut).sizes()
            survived += 1
        except OK_ERRORS:
            rejected += 1
    assert survived + rejected == 200
    assert rejected > 0


def test_indexcov_cli_corrupt_crai_clean_error(tmp_path, capsys):
    """A corrupt .crai through the indexcov CLI exits with a clean
    'indexcov: <file>: crai: ...' message, not a traceback."""
    from goleft_tpu.commands.indexcov import run_indexcov

    bad = str(tmp_path / "bad.crai")
    with open(bad, "wb") as fh:
        fh.write(gzip.compress(b"0\t0\t999\t100\t0\t50\n")[:20])
    fai = str(tmp_path / "r.fa.fai")
    with open(fai, "w") as fh:
        fh.write("chr1\t100000\t6\t60\t61\n")
    with pytest.raises(SystemExit) as ei:
        run_indexcov([bad], directory=str(tmp_path / "o"), fai=fai,
                     sex="")
    msg = str(ei.value)
    assert msg.startswith("indexcov: ") and "bad.crai" in msg
    assert "crai:" in msg


def test_bai_python_fallback_fuzz(bai_bytes, monkeypatch):
    """The pure-Python parser (hosts without the native lib) honors the
    same ValueError-only contract on the same mutations."""
    import goleft_tpu.io.native as native_mod

    # read_bai resolves native.bai_scan at call time, so this routes
    # every parse through the pure-Python branch
    monkeypatch.setattr(native_mod, "bai_scan", lambda *_: None)
    rng = np.random.default_rng(4)
    survived = rejected = 0
    for mut in _mutations(bai_bytes, rng, 300):
        try:
            read_bai(mut).sizes()
            survived += 1
        except OK_ERRORS:
            rejected += 1
    assert survived + rejected == 300
    assert rejected > 0


def test_crai_hostile_lines_bounded():
    """Hand-crafted hostile lines (huge seqID / span) must raise the
    typed error promptly instead of allocating unbounded lists — the
    random fuzz can't reach these because gzip CRC rejects most
    mutations."""
    import pytest

    from goleft_tpu.io.crai import read_crai

    for line in (b"99999999999\t0\t1\t0\t0\t1\n",          # huge seqID
                 b"0\t0\t" + str(2**50).encode() + b"\t0\t0\t1\n",
                 b"0\t" + str(10**400).encode() + b"\t1\t0\t0\t1\n",
                 b"0\tx\t1\t0\t0\t1\n"):                    # non-int
        with pytest.raises(ValueError):
            read_crai(gzip.compress(line)).sizes()


def test_text_parsers_typed_errors(tmp_path):
    """Corrupt .fai and .bed inputs surface as ValueError with
    file:line context — never IndexError/raw int() messages."""
    import pytest

    from goleft_tpu.commands.depth import gen_regions
    from goleft_tpu.io.fai import read_fai

    fai = str(tmp_path / "bad.fai")
    open(fai, "w").write("chr1\tnotanint\t6\t60\t61\n")
    with pytest.raises(ValueError, match=r"bad\.fai:1: not a \.fai"):
        read_fai(fai)
    open(fai, "w").write("chr1\t100\n")
    with pytest.raises(ValueError, match=r"bad\.fai:1"):
        read_fai(fai)

    bed = str(tmp_path / "bad.bed")
    open(bed, "w").write("chr1\t100\n")
    with pytest.raises(ValueError, match=r"bad\.bed:1: bed line"):
        gen_regions([], "", 500, bed)
    open(bed, "w").write("# ok\nchr1\tx\ty\n")
    with pytest.raises(ValueError, match=r"bad\.bed:2: non-integer"):
        gen_regions([], "", 500, bed)


def test_cli_valueerror_clean_surface(tmp_path, capsys, monkeypatch):
    """The dispatcher converts any parser ValueError into one clean
    stderr line + exit 1 — corrupt fai through the full CLI."""
    from goleft_tpu.cli import main as cli_main

    monkeypatch.setenv("GOLEFT_TPU_CPU", "1")
    fai = str(tmp_path / "bad.fai")
    open(fai, "w").write("chr1\tnope\t6\t60\t61\n")
    rc = cli_main(["cohortdepth", "--fai", fai, "missing.bam"])
    # cohortdepth validates the fai BEFORE opening any BAM, so the
    # nonexistent bam never matters and the error IS read_fai's
    err = capsys.readouterr().err
    assert rc == 1
    assert "goleft-tpu cohortdepth:" in err
    assert "not a .fai line" in err and "Traceback" not in err


def test_crai_sparse_high_seqid_is_cheap():
    """A legitimate sparse index (few lines, high seqID — e.g. a
    regionally-subsetted CRAM on a many-scaffold assembly) parses, and
    absent seqIDs share one sentinel list / one empty sizes array
    instead of allocating per-id objects (ADVICE r3)."""
    from goleft_tpu.io.crai import read_crai

    ix = read_crai(gzip.compress(b"5000000\t0\t16384\t0\t0\t100\n"))
    assert len(ix.slices) == 5000001
    assert ix.slices[0] is ix.slices[4999999]  # shared sentinel
    assert len(ix.slices[5000000]) == 1
    sz = ix.sizes()
    assert sz[0] is sz[1]  # shared empty array
    assert sz[5000000].tolist() == [610]  # 100000*100/16384 per base


@pytest.mark.native_io
@pytest.mark.parametrize("cap_hint", [None, 3],
                         ids=["default-cap", "grown-buffers"])
def test_segments_stream_corruption_fuzz(tmp_path, cap_hint):
    """The streaming segment extractor shares bgzf_stream_walk with
    the reduce paths, so every corruption class must surface as the
    module's typed ValueError — never a crash, hang, or silent wrong
    answer (single-byte flips across the whole stream). With a first
    capacity of 3 the collector has grown (up to 9 blocks for 800
    segments) before a flip late in the stream is met: the error exit
    must free every block (ASan, LeakSanitizer) and a clean stream
    must still decode in full."""
    from goleft_tpu.io.bam import BamFile
    from goleft_tpu.obs import get_registry

    if native.get_lib() is None:
        pytest.skip("native toolchain unavailable")
    rng = np.random.default_rng(7)
    p = str(tmp_path / "f.bam")
    write_bam_and_bai(p, random_reads(rng, 800, 0, 30_000),
                      ref_names=("chr1",), ref_lens=(30_000,))
    clean = open(p, "rb").read()
    h = BamFile.from_file(p, lazy=True)
    want = h.read_segments(0, 0, 30_000, 0, 0)
    assert len(want[0]) == 800
    grows = get_registry().counter("decode.segment_buffer_grows_total")
    # deterministic sweep of positions incl. headers, payloads, trailers
    n_rejected = n_rejected_after_growth = 0
    for off in range(0, len(clean), max(1, len(clean) // 150)):
        data = bytearray(clean)
        data[off] ^= 0xFF
        try:
            got = native.bam_segments_stream(
                np.frombuffer(bytes(data), np.uint8), 0,
                h._body_start, 0, 0, 30_000, 0, 0, check_crc=True,
                cap_hint=cap_hint)
        except ValueError:
            n_rejected += 1
            continue  # typed rejection: the contract
        # accepted: with CRC on, the payload must have been untouched
        # by the flip (e.g. header/extra fields) — results must match
        assert np.array_equal(got[0], want[0]) \
            and np.array_equal(got[1], want[1]), f"flip at {off}"
    assert n_rejected > 100
    if cap_hint is None:
        return
    # the same flips with CRC off reach the record walk itself: one in
    # a later BGZF block is met with the collector already grown, and
    # the call either raises the typed error or returns arrays (maybe
    # of other records: without the CRC a flipped base is not an error)
    g0 = grows.value
    for off in range(len(clean) // 2, len(clean),
                     max(1, len(clean) // 150)):
        data = bytearray(clean)
        data[off] ^= 0xFF
        try:
            got = native.bam_segments_stream(
                np.frombuffer(bytes(data), np.uint8), 0,
                h._body_start, 0, 0, 30_000, 0, 0, check_crc=False,
                cap_hint=cap_hint)
        except ValueError:
            n_rejected_after_growth += 1
            continue
        assert len(got[0]) == len(got[1])
    assert n_rejected_after_growth > 0
    # only successful walks report their growths; each started from 3
    assert grows.value > g0
