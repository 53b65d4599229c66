"""--no-crc fast mode: what is still caught, and what is not.

The BGZF payload CRC is a large share of per-sample decode cost.
``--no-crc`` trades it away for trusted local files. The contract these
tests pin down, corruption class by corruption class:

still caught without CRC          | by
----------------------------------|----------------------------------
truncated file                    | EOF / unterminated-record check
broken deflate stream             | inflate failure
inflated length != recorded isize | isize check (always on)
                                  |
NOT caught without CRC: a bit flip that happens to leave a valid
deflate stream of the right length (silent data change). That class is
exactly why CRC is the DEFAULT and the flag is opt-in for trusted
files — the reference's htslib path always verifies and has no such
flag (depth/depth.go:282-325 inherits biogo's always-on CRC).
"""

import shutil
import zlib

import numpy as np
import pytest

from goleft_tpu.cli import main as cli_main
from helpers import write_bam_and_bai, random_reads


@pytest.fixture
def cohort(tmp_path):
    rng = np.random.default_rng(11)
    ref_len = 120_000
    bam = str(tmp_path / "s0.bam")
    write_bam_and_bai(bam, random_reads(rng, 6000, 0, ref_len),
                      ref_names=("chr1",), ref_lens=(ref_len,))
    fai = str(tmp_path / "ref.fa.fai")
    with open(fai, "w") as fh:
        fh.write(f"chr1\t{ref_len}\t6\t60\t61\n")
    return bam, fai


def _bgzf_blocks(data: bytes):
    off, blocks = 0, []
    while off < len(data):
        bsize = int.from_bytes(data[off + 16:off + 18], "little") + 1
        blocks.append((off, bsize))
        off += bsize
    return blocks


def _mid_record_block(data: bytes):
    """A record block past the header inflate range (which always
    CRC-checks regardless of the flag)."""
    blocks = _bgzf_blocks(data)
    off, bsize = blocks[len(blocks) // 2]
    assert off > 20_000, "fixture too small to clear the header range"
    return off, bsize


def _copy_with(bam: str, out: str, mutate) -> None:
    data = bytearray(open(bam, "rb").read())
    mutate(data)
    with open(out, "wb") as fh:
        fh.write(bytes(data))
    shutil.copyfile(bam + ".bai", out + ".bai")


def _run(bam, fai, *flags):
    """cli return code; corrupt input surfaces as ValueError->rc=1 in
    the dispatcher or as SystemExit from open_bam_file — both are
    'caught loudly' for these tests."""
    try:
        return cli_main(["cohortdepth", "--fai", fai, "-w", "500",
                         *flags, bam])
    except SystemExit as e:
        # SystemExit(message) means exit code 1 (python semantics)
        return e.code if isinstance(e.code, int) else 1


@pytest.fixture(autouse=True)
def _clean_knob(monkeypatch):
    """apply_no_crc sets the env knob OUTSIDE monkeypatch's tracking
    (and delenv on an absent key records nothing to restore), so clean
    up explicitly on both sides — otherwise the knob leaks into every
    later test file in this pytest process."""
    import os

    monkeypatch.delenv("GOLEFT_TPU_SKIP_CRC", raising=False)
    yield
    os.environ.pop("GOLEFT_TPU_SKIP_CRC", None)


def test_no_crc_output_is_byte_identical(cohort, capsys):
    bam, fai = cohort
    assert _run(bam, fai) == 0
    strict = capsys.readouterr().out
    import os

    os.environ.pop("GOLEFT_TPU_SKIP_CRC", None)
    assert _run(bam, fai, "--no-crc") == 0
    assert capsys.readouterr().out == strict
    # the flag propagates through the env knob workers inherit
    assert os.environ.get("GOLEFT_TPU_SKIP_CRC") == "1"


def test_broken_stream_caught_without_crc(cohort, tmp_path, capsys):
    """Flipping a deflate header byte breaks the stream — inflate
    itself fails, CRC not needed."""
    bam, fai = cohort
    bad = str(tmp_path / "bad_stream.bam")

    def mutate(data):
        off, _ = _mid_record_block(bytes(data))
        data[off + 18] ^= 0xFF  # BFINAL/BTYPE bits -> invalid stream

    _copy_with(bam, bad, mutate)
    rc = _run(bad, fai, "--no-crc")
    capsys.readouterr()
    assert rc not in (0, None), "broken deflate stream went undetected"


def test_isize_mismatch_caught_without_crc(cohort, tmp_path, capsys):
    """The inflated-length-vs-isize check is independent of CRC."""
    bam, fai = cohort
    bad = str(tmp_path / "bad_isize.bam")

    def mutate(data):
        off, bsize = _mid_record_block(bytes(data))
        isize = int.from_bytes(data[off + bsize - 4:off + bsize],
                               "little")
        data[off + bsize - 4:off + bsize] = (isize + 8).to_bytes(
            4, "little")

    _copy_with(bam, bad, mutate)
    rc = _run(bad, fai, "--no-crc")
    capsys.readouterr()
    assert rc not in (0, None), "isize mismatch went undetected"


def test_truncation_caught_without_crc(cohort, tmp_path, capsys):
    bam, fai = cohort
    data = open(bam, "rb").read()
    blocks = _bgzf_blocks(data)
    cut = str(tmp_path / "cut.bam")
    # cut mid-way through the LAST record-carrying block (drops the
    # EOF sentinel too)
    off, bsize = blocks[-2]
    with open(cut, "wb") as fh:
        fh.write(data[:off + bsize // 2])
    shutil.copyfile(bam + ".bai", cut + ".bai")
    rc = _run(cut, fai, "--no-crc")
    capsys.readouterr()
    assert rc not in (0, None), "truncated bam went undetected"


def test_valid_stream_data_flip_needs_crc(cohort, tmp_path, capsys,
                                          monkeypatch):
    """The documented limit of the trade: a flip that leaves a VALID
    deflate stream of the right length changes data silently without
    CRC — and the default (CRC on) catches it. This is the test that
    keeps the --no-crc help text honest."""
    bam, fai = cohort
    data = bytearray(open(bam, "rb").read())
    off, bsize = _mid_record_block(bytes(data))
    payload = bytes(data[off + 18:off + bsize - 8])
    want_len = len(zlib.decompress(payload, wbits=-15))
    # find a flip the inflate survives (literal runs make these common
    # at level-1 compression; the seed is fixed, so this is stable)
    for pos in range((bsize - 26) // 2, bsize - 26):
        fl = bytearray(payload)
        fl[pos] ^= 0xFF
        try:
            out = zlib.decompress(bytes(fl), wbits=-15)
        except zlib.error:
            continue
        if len(out) == want_len and out != zlib.decompress(
                payload, wbits=-15):
            break
    else:
        pytest.skip("no stream-preserving flip in this block")
    bad = str(tmp_path / "bad_data.bam")

    def mutate(d):
        d[off + 18 + pos] ^= 0xFF

    _copy_with(bam, bad, mutate)
    # default (CRC on): caught
    rc = _run(bad, fai)
    capsys.readouterr()
    assert rc not in (0, None), "CRC default failed to catch data flip"
    # --no-crc: documented silent pass with CHANGED data
    monkeypatch.delenv("GOLEFT_TPU_SKIP_CRC", raising=False)
    assert _run(bam, fai, "--no-crc") == 0
    good_out = capsys.readouterr().out
    monkeypatch.setenv("GOLEFT_TPU_SKIP_CRC", "1")
    assert _run(bad, fai) == 0
    assert capsys.readouterr().out != good_out


def test_no_crc_identity_depth_and_covstats(cohort, tmp_path, capsys):
    """--no-crc is wired on every decode-heavy subcommand; depth and
    covstats must also produce byte-identical output with it."""
    import os

    bam, fai = cohort
    # the cohort fixture's fai already sits at ref.fa.fai; the stub
    # fasta body is never read (depth only needs lengths)
    ref = str(tmp_path / "ref.fa")
    with open(ref, "w") as fh:
        fh.write(">chr1\n" + "A" * 60 + "\n")

    def run_and_check_knob(argv, flags):
        os.environ.pop("GOLEFT_TPU_SKIP_CRC", None)
        rc = cli_main(argv + list(flags) + [bam])
        assert rc in (0, None)
        if "--no-crc" in flags:
            # the flag must have ENGAGED, or the comparison is
            # vacuously strict-vs-strict
            assert os.environ.get("GOLEFT_TPU_SKIP_CRC") == "1"

    def beds(prefix, *flags):
        run_and_check_knob(
            ["depth", "--prefix", str(tmp_path / prefix),
             "-r", ref, "-w", "500"], flags)
        return (open(f"{tmp_path / prefix}.depth.bed").read(),
                open(f"{tmp_path / prefix}.callable.bed").read())

    assert beds("strict") == beds("fast", "--no-crc")

    def covs(*flags):
        capsys.readouterr()  # drain: only THIS run's stdout compares
        run_and_check_knob(["covstats"], flags)
        return capsys.readouterr().out

    assert covs() == covs("--no-crc")
