"""Hybrid (C++ host decode+reduce) engine vs the device segment path.

The hybrid engine is the e2e-throughput design: per-read data never
crosses the host↔device link; only (windows × samples) matrices do.
These tests pin (a) bam_window_reduce against the jitted
shard_depth_pipeline on identical decoded segments, and (b) the full
cohortdepth matrix for engine=hybrid vs engine=device, byte-identical.
"""

import io

import numpy as np
import pytest

from goleft_tpu.io import native
from goleft_tpu.io.bam import BamFile
from goleft_tpu.commands.cohortdepth import run_cohortdepth
from goleft_tpu.ops.depth_pipeline import shard_depth_pipeline

from helpers import write_bam_and_bai, write_fasta, random_reads
from goleft_tpu.io.fai import write_fai

needs_native = pytest.mark.skipif(
    native.get_lib() is None, reason="native toolchain unavailable"
)


def _mixed_reads(rng, n, ref_len):
    """Coordinate-sorted reads on tid 0 with mixed CIGARs, every MAPQ
    and flags incl. skipped dup/secondary/QC-fail records: kept
    segments != reads != records."""
    reads = []
    for s in np.sort(rng.integers(0, ref_len - 1000, size=n)):
        cig = rng.choice(["100M", "40M20D40M", "30M10N60M", "10S80M",
                          "50M2I48M"])
        mq = int(rng.integers(0, 61))
        fl = int(rng.choice([0, 0, 0, 0x400, 0x100, 0x200]))
        reads.append((0, int(s), cig, mq, fl))
    return reads


@needs_native
@pytest.mark.parametrize("rs,re_", [(0, 100_000), (13_777, 61_003)])
def test_window_reduce_matches_device_pipeline(tmp_path, rs, re_):
    reads = _mixed_reads(np.random.default_rng(21), 3000, 100_000)
    p = str(tmp_path / "t.bam")
    write_bam_and_bai(p, reads, ref_names=("chr1",), ref_lens=(100_000,))
    bf = BamFile.from_file(p, lazy=True)

    window = 250
    w0 = rs // window * window
    length = ((re_ - w0) + window - 1) // window * window
    mapq_min, flag_mask, cap = 20, 0x704, 2500

    got = bf.window_reduce(0, rs, re_, w0, length, window, cap,
                           mapq_min, flag_mask)

    cols = bf.read_columns(tid=0, start=rs, end=re_)
    ok = (cols.mapq >= mapq_min) & ((cols.flag & flag_mask) == 0)
    keep = ok[cols.seg_read]
    want = np.asarray(shard_depth_pipeline(
        cols.seg_start, cols.seg_end, keep,
        np.int32(w0), np.int32(rs), np.int32(re_),
        np.int32(cap), np.int32(4), np.int32(0),
        length=length, window=window,
    )[0]).astype(np.int64)
    np.testing.assert_array_equal(got, want)


@needs_native
def test_cohortdepth_engines_identical(tmp_path):
    rng = np.random.default_rng(22)
    ref_len = 80_000
    fa = write_fasta(str(tmp_path / "r.fa"), {"chr1": "A" * ref_len})
    write_fai(fa)
    bams = []
    for i in range(5):
        reads = random_reads(rng, 2500, 0, ref_len)
        hdr = ("@HD\tVN:1.6\tSO:coordinate\n"
               f"@SQ\tSN:chr1\tLN:{ref_len}\n@RG\tID:r\tSM:h{i}\n")
        p = str(tmp_path / f"h{i}.bam")
        write_bam_and_bai(p, reads, ref_names=("chr1",),
                          ref_lens=(ref_len,), header_text=hdr)
        bams.append(p)
    outs = {}
    for eng in ("hybrid", "device"):
        buf = io.StringIO()
        run_cohortdepth(bams, reference=fa, window=500, out=buf,
                        engine=eng, mapq=10)
        outs[eng] = buf.getvalue()
    assert outs["hybrid"] == outs["device"]
    assert len(outs["hybrid"].splitlines()) == ref_len // 500 + 1


@needs_native
@pytest.mark.native_io
def test_format_matrix_rows_matches_python():
    rng = np.random.default_rng(30)
    n_rows, n_cols = 137, 7
    starts = np.arange(n_rows, dtype=np.int64) * 500
    ends = starts + 500
    vals = rng.integers(0, 10**12, size=(n_cols, n_rows)).astype(np.int64)
    vals[0, 0] = 0
    got = native.format_matrix_rows("chr10_random", starts, ends, vals)
    want = "".join(
        f"chr10_random\t{starts[i]}\t{ends[i]}\t"
        + "\t".join(str(v) for v in vals[:, i]) + "\n"
        for i in range(n_rows)
    ).encode()
    assert got == want


def test_packed_pipeline_matches_unpacked():
    """u16 delta+length wire format reconstructs identical results,
    including >65535 gaps (filler entries) and keep-filtering."""
    import jax
    from goleft_tpu.ops.coverage import bucket_size, pack_segments_u16
    from goleft_tpu.ops.depth_pipeline import (
        shard_depth_pipeline, shard_depth_pipeline_packed,
    )

    rng = np.random.default_rng(31)
    length, window = 1_024_000, 250
    n = 4000
    # sparse: forces gaps far beyond 65535
    s = np.sort(rng.integers(0, length - 200, size=n)).astype(np.int32)
    e = (s + rng.integers(1, 300, size=n)).astype(np.int32)
    keep = rng.random(n) < 0.7
    scalars = (np.int32(0), np.int32(1000), np.int32(length - 777),
               np.int32(2500), np.int32(4), np.int32(0))
    b = bucket_size(n)
    ss = np.zeros(b, np.int32); ee = np.zeros(b, np.int32)
    kk = np.zeros(b, bool)
    ss[:n], ee[:n], kk[:n] = s, e, keep
    want = shard_depth_pipeline(ss, ee, kk, *scalars,
                                length=length, window=window)
    d, l, base, n_ent = pack_segments_u16(s, e, keep)
    assert n_ent >= keep.sum()  # fillers present
    bp = bucket_size(max(n_ent, 1))
    dd = np.zeros(bp, np.uint16); ll = np.zeros(bp, np.uint16)
    dd[:n_ent] = d; ll[:n_ent] = l
    got = shard_depth_pipeline_packed(dd, ll, base, *scalars,
                                      length=length, window=window)
    for g, w, nm in zip(got, want, ("sums", "cls", "depth")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), nm)

    # ultra-long segment -> packer declines (caller falls back)
    e2 = e.copy(); e2[5] = s[5] + 100_000
    assert pack_segments_u16(s, e2, np.ones(n, bool)) is None


@needs_native
@pytest.mark.native_io
def test_native_depth_row_formatting_matches_python():
    rng = np.random.default_rng(33)
    n = 500
    starts = (np.arange(n, dtype=np.int64)) * 83
    ends = starts + 83
    ends[-1] = starts[-1] + 7
    # means spanning the %.4g regimes: 0, tiny, fractional, large, exp
    means = np.concatenate([
        np.zeros(20),
        rng.random(200) * 5,
        rng.random(200) * 5000,
        10 ** rng.uniform(4, 9, size=70),
        np.array([1e6, 0.1, 250.0, 1 / 3, 2500.0, 123456.789,
                  0.000123456, 9.9995, 9999.5, 1234.5]),
    ])
    got = native.format_depth_rows("chrX", starts, ends, means)
    want = "".join(
        f"chrX\t{starts[i]}\t{ends[i]}\t{means[i]:.4g}\n"
        for i in range(n)
    ).encode()
    assert got == want

    cls = rng.integers(0, 4, size=40).astype(np.uint8)
    cs = np.arange(40, dtype=np.int64) * 10
    ce = cs + 10
    from goleft_tpu.ops.coverage import CLASS_NAMES
    gotc = native.format_class_rows("chr2", cs, ce, cls)
    wantc = "".join(
        f"chr2\t{cs[i]}\t{ce[i]}\t{CLASS_NAMES[cls[i]]}\n"
        for i in range(40)
    ).encode()
    assert gotc == wantc


def test_cls_2bit_pack_roundtrip():
    import jax.numpy as jnp
    from goleft_tpu.ops.depth_pipeline import (
        _pack_cls_2bit, unpack_cls_2bit,
    )

    rng = np.random.default_rng(34)
    for length in (4, 7, 1024, 8301):
        cls = rng.integers(0, 4, size=length).astype(np.int8)
        packed = np.asarray(_pack_cls_2bit(jnp.asarray(cls), length))
        back = unpack_cls_2bit(packed, length)
        np.testing.assert_array_equal(back, cls)


@needs_native
def test_cohortdepth_engines_multichrom_divergent_dicts(tmp_path):
    """Two chromosomes; one sample's header lacks chr2 entirely (per-
    sample tid maps) — both engines must still agree byte-for-byte and
    the chr2 column for the missing sample must be all zeros."""
    rng = np.random.default_rng(41)
    lens = {"chr1": 60_000, "chr2": 35_000}
    fa = write_fasta(str(tmp_path / "r.fa"),
                     {k: "A" * v for k, v in lens.items()})
    write_fai(fa)
    bams = []
    for i in range(4):
        if i == 2:  # chr1-only reference dictionary
            reads = random_reads(rng, 1200, 0, lens["chr1"])
            hdr = ("@HD\tVN:1.6\tSO:coordinate\n"
                   f"@SQ\tSN:chr1\tLN:{lens['chr1']}\n"
                   f"@RG\tID:r\tSM:m{i}\n")
            p = str(tmp_path / f"m{i}.bam")
            write_bam_and_bai(p, reads, ref_names=("chr1",),
                              ref_lens=(lens["chr1"],), header_text=hdr)
        else:
            reads = random_reads(rng, 1200, 0, lens["chr1"]) + \
                random_reads(rng, 600, 1, lens["chr2"])
            hdr = ("@HD\tVN:1.6\tSO:coordinate\n"
                   f"@SQ\tSN:chr1\tLN:{lens['chr1']}\n"
                   f"@SQ\tSN:chr2\tLN:{lens['chr2']}\n"
                   f"@RG\tID:r\tSM:m{i}\n")
            p = str(tmp_path / f"m{i}.bam")
            write_bam_and_bai(p, reads,
                              ref_names=("chr1", "chr2"),
                              ref_lens=(lens["chr1"], lens["chr2"]),
                              header_text=hdr)
        bams.append(p)
    outs = {}
    for eng in ("hybrid", "device"):
        buf = io.StringIO()
        run_cohortdepth(bams, reference=fa, window=500, out=buf,
                        engine=eng)
        outs[eng] = buf.getvalue()
    assert outs["hybrid"] == outs["device"]
    lines = outs["hybrid"].splitlines()
    n_chr1 = lens["chr1"] // 500
    n_chr2 = lens["chr2"] // 500
    assert len(lines) == 1 + n_chr1 + n_chr2
    # chr2 rows: sample m2's column (index 3+2) must be 0
    for ln in lines[1 + n_chr1:]:
        t = ln.split("\t")
        assert t[0] == "chr2" and t[5] == "0", ln
    # other samples have nonzero chr2 coverage somewhere
    assert any(ln.split("\t")[4] != "0" for ln in lines[1 + n_chr1:])


@needs_native
@pytest.mark.native_io
def test_format_xy_json_valid_and_close():
    import json as _json

    rng = np.random.default_rng(77)
    x = np.concatenate([rng.uniform(0, 2.5e8, 500), [0.0, 1e-7, 3.0]])
    y = np.concatenate([rng.uniform(0, 50, 500), [np.nan, np.inf, 2.5]])
    out = native.format_xy_json(x, y)
    pts = _json.loads(out)
    assert len(pts) == len(x)
    for i, p in enumerate(pts):
        assert abs(p["x"] - x[i]) <= max(1e-9 * abs(x[i]), 1e-9)
        if np.isfinite(y[i]):
            # %.5g: half-step in the 5th significant digit
            assert abs(p["y"] - y[i]) <= max(5.1e-5 * abs(y[i]), 1e-9)
        else:
            assert p["y"] is None


@needs_native
def test_lean_acc_pileup_fallback_matches_dense(tmp_path):
    # NOT marked native_io: the device-pipeline comparison executes jax,
    # and ASan (which runs the native_io selection) crashes inside XLA
    """A pileup deeper than depth_cap forces the lean direct-window
    accumulation to fall back to the exact capped dense path: results
    must equal the device pipeline's capped sums either way."""
    # 300 reads stacked on one spot (cap=50 binds), plus sparse tail
    reads = [(0, 1000, "100M", 60, 0) for _ in range(300)]
    reads += [(0, int(p), "100M", 60, 0) for p in range(5000, 40000, 500)]
    p = str(tmp_path / "pile.bam")
    write_bam_and_bai(p, reads, ref_names=("chr1",), ref_lens=(100_000,))
    bf = BamFile.from_file(p, lazy=True)

    window, cap = 250, 50
    rs, re_ = 0, 50_000
    length = 50_000
    got = bf.window_reduce(0, rs, re_, 0, length, window, cap, 0, 0x704)

    cols = bf.read_columns(tid=0, start=rs, end=re_)
    keep = np.ones(len(cols.seg_start), bool)
    want = np.asarray(shard_depth_pipeline(
        cols.seg_start, cols.seg_end, keep,
        np.int32(0), np.int32(rs), np.int32(re_),
        np.int32(cap), np.int32(4), np.int32(0),
        length=length, window=window,
    )[0]).astype(np.int64)
    np.testing.assert_array_equal(got, want)
    # sanity: the cap actually binds (window at the pile is capped)
    assert got[1000 // window] == cap * 100  # 300-deep pile capped to 50


@needs_native
@pytest.mark.native_io
def test_lean_acc_reports_max_overlap(tmp_path):
    reads = [(0, 1000, "100M", 60, 0) for _ in range(7)]
    p = str(tmp_path / "seven.bam")
    write_bam_and_bai(p, reads, ref_names=("chr1",), ref_lens=(100_000,))
    bf = BamFile.from_file(p, lazy=True)
    out = native.bam_window_acc_stream(
        bf._comp, 0, bf._body_start, 0, 0, 10_000, 0, 10_000, 250, 0, 0)
    assert out["max_overlap"] == 7
    assert out["n_kept"] == 7
    assert out["wsums"][4] == 7 * 100  # window [1000,1250) holds all


@needs_native
@pytest.mark.native_io
def test_stream_window_one_uses_identity_division(tmp_path):
    """window=1 exercises the magic==0 branch of the Lemire division."""
    reads = [(0, 10, "20M", 60, 0), (0, 15, "20M", 60, 0)]
    p = str(tmp_path / "w1.bam")
    write_bam_and_bai(p, reads, ref_names=("chr1",), ref_lens=(100_000,))
    bf = BamFile.from_file(p, lazy=True)
    got = bf.window_reduce(0, 0, 64, 0, 64, 1, 2500, 0, 0x704)
    want = np.zeros(64, np.int64)
    want[10:30] += 1
    want[15:35] += 1
    want = np.minimum(want, 2500)[:64]
    np.testing.assert_array_equal(got, want)


@needs_native
@pytest.mark.native_io
def test_stream_truncated_bam_raises_cleanly(tmp_path):
    reads = [(0, int(p_), "100M", 60, 0) for p_ in range(0, 30000, 100)]
    p = str(tmp_path / "trunc.bam")
    write_bam_and_bai(p, reads, ref_names=("chr1",), ref_lens=(100_000,))
    raw = open(p, "rb").read()
    # cut at a BGZF block boundary (structurally valid stream) that lands
    # mid-record in the uncompressed body — only the record walk can
    # notice, and it must raise cleanly rather than loop or crash
    from goleft_tpu.io.native import bgzf_scan
    import numpy as _np
    co, uo, total = bgzf_scan(_np.frombuffer(raw, _np.uint8))
    cut_at = int(co[2 * len(co) // 3])
    cut = str(tmp_path / "cut.bam")
    with open(cut, "wb") as fh:
        fh.write(raw[:cut_at])
    bf = BamFile.from_file(cut, lazy=True)
    with pytest.raises(ValueError):
        bf.window_reduce(0, 0, 100_000, 0, 100_000, 250, 2500, 0, 0x704)


@needs_native
@pytest.mark.native_io
def test_stream_corrupt_crc_detected(tmp_path, monkeypatch):
    monkeypatch.delenv("GOLEFT_TPU_SKIP_CRC", raising=False)
    reads = [(0, int(p_), "100M", 60, 0) for p_ in range(0, 30000, 100)]
    p = str(tmp_path / "crc.bam")
    # compressed (level>0) so a payload flip can't also be a structural
    # failure of a stored block
    write_bam_and_bai(p, reads, ref_names=("chr1",), ref_lens=(100_000,),
                      level=6, block_size=4096)
    raw = bytearray(open(p, "rb").read())
    # flip one byte of the stored CRC field of a mid-file block: the
    # deflate stream stays valid, only crc verification can catch it
    from goleft_tpu.io.native import bgzf_scan
    import numpy as _np
    co, uo, total = bgzf_scan(_np.frombuffer(bytes(raw), _np.uint8))
    blk = int(co[len(co) // 2])
    # find block size from BC subfield to locate the crc (bsize-8)
    import struct
    xlen = struct.unpack_from("<H", raw, blk + 10)[0]
    bsize = None
    xo = blk + 12
    while xo < blk + 12 + xlen:
        si1, si2, slen = raw[xo], raw[xo + 1], struct.unpack_from(
            "<H", raw, xo + 2)[0]
        if si1 == 0x42 and si2 == 0x43:
            bsize = struct.unpack_from("<H", raw, xo + 4)[0] + 1
            break
        xo += 4 + slen
    raw[blk + bsize - 8] ^= 0xFF
    cut = str(tmp_path / "crcbad.bam")
    with open(cut, "wb") as fh:
        fh.write(bytes(raw))
    bf = BamFile.from_file(cut, lazy=True)
    with pytest.raises(ValueError, match="corrupt|CRC|crc"):
        bf.window_reduce(0, 0, 100_000, 0, 100_000, 250, 2500, 0, 0x704)


@needs_native
@pytest.mark.native_io
def test_stream_decoder_corruption_fuzz(tmp_path):
    """Byte-flip fuzz over a valid BAM through the streaming fused
    decoder: every mutation must either produce a result or raise a
    clean ValueError — never crash (the C++ bounds-checks all record
    geometry; this is the executable evidence, and the ASan target
    runs it with instrumentation)."""
    rng = np.random.default_rng(44)
    reads = [(0, int(p), "60M", 60, 0) for p in range(0, 20000, 50)]
    p = str(tmp_path / "f.bam")
    write_bam_and_bai(p, reads, ref_names=("chr1",), ref_lens=(50_000,),
                      level=6, block_size=4096)
    raw = np.fromfile(p, dtype=np.uint8)
    n_ok = n_err = 0
    for it in range(150):
        mut = raw.copy()
        i = int(rng.integers(0, len(mut)))
        mut[i] ^= int(rng.integers(1, 256))
        mp = str(tmp_path / "m.bam")
        mut.tofile(mp)
        try:
            bf = BamFile.from_file(mp, lazy=True)
            out = bf.window_reduce(0, 0, 50_000, 0, 50_000, 250, 2500,
                                   0, 0x704)
        except ValueError:
            n_err += 1
        else:
            # any decode that "succeeds" must be shape-correct
            assert len(out) == 200
            n_ok += 1
    # both outcomes occur across 150 flips (headers vs payload bytes)
    assert n_err > 0
    assert n_ok > 0


@needs_native
@pytest.mark.native_io
@pytest.mark.parametrize("rs,re_", [(0, 100_000), (13_777, 61_003),
                                    (99_000, 100_000)])
def test_read_segments_matches_filtered_columns(tmp_path, rs, re_):
    """read_segments (the device engine's streaming host stage) must
    emit exactly the filtered/clipped segment set that columns decode +
    host filter produces — on the C streaming path, the eager fallback,
    and through a BAI voffset."""
    reads = _mixed_reads(np.random.default_rng(21), 3000, 100_000)
    p = str(tmp_path / "t.bam")
    write_bam_and_bai(p, reads, ref_names=("chr1",),
                      ref_lens=(100_000,))

    lazy = BamFile.from_file(p, lazy=True)
    got_s, got_e = lazy.read_segments(0, rs, re_, 20, 0x704)

    cols = lazy.read_columns(tid=0, start=rs, end=re_)
    ok = (cols.mapq >= 20) & ((cols.flag & 0x704) == 0)
    kp = ok[cols.seg_read]
    want_s = np.clip(cols.seg_start[kp], rs, re_).astype(np.int32)
    want_e = np.clip(cols.seg_end[kp], rs, re_).astype(np.int32)
    nz = want_e > want_s
    want_s, want_e = want_s[nz], want_e[nz]
    assert np.array_equal(got_s, want_s)
    assert np.array_equal(got_e, want_e)

    # eager fallback path (no streaming C call) — same set
    eager = BamFile.from_file(p)
    fb_s, fb_e = eager.read_segments(0, rs, re_, 20, 0x704)
    assert np.array_equal(fb_s, got_s) and np.array_equal(fb_e, got_e)

    # voffset entry (how the device engine actually calls it)
    from goleft_tpu.io.bai import read_bai, query_voffset

    voff = query_voffset(read_bai(p + ".bai"), 0, rs)
    if voff is not None:
        vs, ve = lazy.read_segments(0, rs, re_, 20, 0x704,
                                    voffset=voff)
        assert np.array_equal(vs, got_s) and np.array_equal(ve, got_e)


@needs_native
@pytest.mark.native_io
def test_read_segments_buffer_grows(tmp_path):
    """A cap_hint smaller than the segment count is only a first
    capacity: the collector grows inside the one walk and still
    returns the full arrays."""
    from goleft_tpu.io import native

    rng = np.random.default_rng(3)
    reads = [(0, int(s), "100M", 60, 0)
             for s in np.sort(rng.integers(0, 9000, size=500))]
    p = str(tmp_path / "r.bam")
    write_bam_and_bai(p, reads, ref_names=("chr1",), ref_lens=(10_000,))
    h = BamFile.from_file(p, lazy=True)
    full_s, full_e = h.read_segments(0, 0, 10_000, 0, 0)
    tiny_s, tiny_e = native.bam_segments_stream(
        h._comp, 0, h._body_start, 0, 0, 10_000, 0, 0, cap_hint=7)
    assert len(full_s) == 500
    assert np.array_equal(full_s, tiny_s)
    assert np.array_equal(full_e, tiny_e)


# (reads, first capacity as a function of the kept-segment count)
_COLLECTOR_CASES = {
    "far-below": (6000, lambda kept: 16),
    "equal": (1500, lambda kept: kept),
    "one-less": (1500, lambda kept: kept - 1),
    "seven": (1500, lambda kept: 7),  # 7 against exactly 500: above
    "three-growths": (3000, lambda kept: kept // 5),
    "above": (1500, lambda kept: kept + 1000),
    "default-hint": (1500, lambda kept: None),
}


@needs_native
@pytest.mark.native_io
@pytest.mark.parametrize("case", list(_COLLECTOR_CASES))
def test_segment_collector_one_walk_any_capacity(tmp_path, case):
    """Whatever the first capacity is against the kept count, one call
    is ONE walk of the stream (decode.segment_walks_total up by exactly
    1), the capacity doubles just as often as it must, and the arrays
    equal the host reference (filter_clip_segments over read_columns)."""
    from goleft_tpu.io.bam import filter_clip_segments
    from goleft_tpu.obs import get_registry

    n_reads, cap_of = _COLLECTOR_CASES[case]
    ref_len, rs, re_ = 200_000, 1_234, 187_655
    reads = _mixed_reads(np.random.default_rng(11), n_reads, ref_len)
    p = str(tmp_path / "c.bam")
    write_bam_and_bai(p, reads, ref_names=("chr1",), ref_lens=(ref_len,))
    h = BamFile.from_file(p, lazy=True)
    want_s, want_e = filter_clip_segments(
        h.read_columns(tid=0, start=rs, end=re_), rs, re_, 20, 0x704)
    kept = len(want_s)
    assert kept >= 500
    cap = cap_of(kept)
    want_grows, c = 0, cap or 65536
    while c < kept:
        c, want_grows = 2 * c, want_grows + 1
    if case == "three-growths":
        assert want_grows == 3
    if case == "far-below":
        assert want_grows >= 8

    reg = get_registry()
    walks = reg.counter("decode.segment_walks_total")
    grows = reg.counter("decode.segment_buffer_grows_total")
    w0, g0 = walks.value, grows.value
    got_s, got_e = native.bam_segments_stream(
        h._comp, 0, h._body_start, 0, rs, re_, 20, 0x704, cap_hint=cap)
    assert walks.value - w0 == 1
    assert grows.value - g0 == want_grows
    assert got_s.dtype == np.int32 and got_e.dtype == np.int32
    assert np.array_equal(got_s, want_s)
    assert np.array_equal(got_e, want_e)
    # exact-size arrays that own their memory (the result pins
    # nothing capacity-sized)
    assert got_s.flags.owndata and got_e.flags.owndata
    assert len(got_s) == kept


@needs_native
@pytest.mark.native_io
def test_segment_collector_empty_region_and_read_segments_walks(tmp_path):
    """An empty region returns two empty int32 arrays after one walk,
    and BamFile.read_segments — the call every engine makes — is one
    walk a call even where its hint (the 65,536 floor on a 1 Mb tile)
    is under the kept count."""
    from goleft_tpu.io.bam import filter_clip_segments
    from goleft_tpu.obs import get_registry

    rng = np.random.default_rng(5)
    starts = np.sort(rng.integers(0, 29_000, size=70_000))
    reads = [(0, int(s), "50M", 60, 0) for s in starts]
    p = str(tmp_path / "deep.bam")
    write_bam_and_bai(p, reads, ref_names=("chr1", "chr2"),
                      ref_lens=(30_000, 30_000))
    h = BamFile.from_file(p, lazy=True)
    reg = get_registry()
    walks = reg.counter("decode.segment_walks_total")
    grows = reg.counter("decode.segment_buffer_grows_total")
    w0, g0 = walks.value, grows.value
    got_s, got_e = h.read_segments(0, 0, 30_000, 20, 0x704)
    assert (walks.value - w0, grows.value - g0) == (1, 1)
    want_s, want_e = filter_clip_segments(
        h.read_columns(tid=0, start=0, end=30_000), 0, 30_000, 20, 0x704)
    assert len(got_s) == 70_000 > 65_536
    assert np.array_equal(got_s, want_s) and np.array_equal(got_e, want_e)

    w0 = walks.value
    es, ee = h.read_segments(1, 0, 30_000, 20, 0x704)
    assert walks.value - w0 == 1
    assert es.dtype == np.int32 and ee.dtype == np.int32
    assert len(es) == 0 and len(ee) == 0


@needs_native
# NOT native_io: runs the jitted depth pipeline (XLA aborts under
# the ASan LD_PRELOAD the native_io selection is run with)
def test_depth_engine_packed_and_kp_none_paths(tmp_path):
    """run_segments must give identical results across all four
    combinations of {packed, unpacked} x {kp=None, explicit all-true}
    — the packed wire is OFF by default on few-core hosts, so this
    pins the multi-core-host configuration too."""
    from goleft_tpu.commands.depth import (
        DepthEngine, _decode_shard_segments,
    )
    from goleft_tpu.io.bai import read_bai

    rng = np.random.default_rng(9)
    reads = []
    for s in np.sort(rng.integers(0, 49_000, size=2000)):
        cig = rng.choice(["100M", "40M20D40M", "10S80M"])
        reads.append((0, int(s), cig, int(rng.integers(0, 61)),
                      int(rng.choice([0, 0, 0x400]))))
    p = str(tmp_path / "p.bam")
    write_bam_and_bai(p, reads, ref_names=("chr1",),
                      ref_lens=(50_000,))
    h = BamFile.from_file(p, lazy=True)
    bai = read_bai(p + ".bai")
    rs, re_ = 1_003, 48_777
    ss, ee = _decode_shard_segments(h, bai, 0, rs, re_, 20)
    assert len(ss) > 500
    outs = []
    for packed in (False, True):
        eng = DepthEngine(250, 4, 0, 20, max_span=re_, packed=packed)
        for kp in (None, np.ones(len(ss), bool)):
            st, en, sums, cls = eng.run_segments(ss, ee, kp, rs, re_)
            outs.append((st, en, sums, cls))
    for o in outs[1:]:
        for a, b in zip(outs[0], o):
            assert np.array_equal(a, b)
