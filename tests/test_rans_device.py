"""Device-resident rANS Nx16 decode (ops/rans_device.py).

The contract under test is byte-identity: the device decoder (XLA
scan path, and the Pallas kernel in interpret mode on this CPU-only
container) must produce EXACTLY the host decoder's bytes on every
supported flag combo — the full CRAM 3.1 method-5 matrix
ORDER0/ORDER1 × CAT × PACK × RLE × NOSZ × STRIPE, both N=4 and X32,
including empty / 1-byte / tail-heavy blocks, per-context table edge
cases and uneven stripe lanes — and the ``--decode-device`` cohort
path must emit byte-identical matrices with ZERO fallbacks on a
fully-supported cohort (fallback is reserved for corrupt/foreign
streams and bucket shapes past the signature cap).
"""

import io
import os

import numpy as np
import pytest

from goleft_tpu.io import rans_nx16 as rx
from goleft_tpu.obs import get_registry
from goleft_tpu.ops import rans_device as rd


@pytest.fixture(autouse=True)
def _fresh_signature_registry():
    """The signature registry is process-global (it bounds process-
    lifetime compiles); this suite deliberately explodes shapes, so
    each test starts with fresh admission — no test's fallback
    behavior may depend on shapes an earlier test admitted."""
    rd.reset_signature_registry()
    yield
    rd.reset_signature_registry()


def _corpus(rng, sizes, *, order=0, x32=False, rle=False, pack=False,
            alpha=None):
    out = []
    for sz in sizes:
        a = alpha if alpha is not None else int(rng.integers(1, 256))
        data = bytes(rng.integers(0, a, sz, dtype=np.uint8))
        enc = rx.encode(data, order=order, use_rle=rle, use_pack=pack,
                        x32=x32)
        out.append((data, enc))
    return out


def _strip_size(enc: bytes, out_len: int) -> bytes:
    """Rewrite a stream as NOSZ (size stripped, flag set)."""
    szlen = len(rx.write_uint7(out_len))
    return bytes([enc[0] | rx.F_NOSZ]) + enc[1 + szlen:]


# sizes chosen to hit empty, 1-byte, sub-state-seed (CAT), bucket
# boundaries and tail-heavy partial final rounds for both N=4 and X32
SIZES = [0, 1, 3, 17, 63, 64, 65, 127, 4095, 4097, 8191, 20000]


@pytest.mark.parametrize("x32", [False, True])
@pytest.mark.parametrize("rle,pack", [(False, False), (True, False),
                                      (False, True), (True, True)])
def test_scan_parity_flag_matrix(x32, rle, pack):
    rng = np.random.default_rng(0)
    cases = _corpus(rng, SIZES, x32=x32, rle=rle, pack=pack)
    if pack:  # force the packable alphabet too
        cases += _corpus(rng, SIZES[3:], x32=x32, rle=rle, pack=pack,
                         alpha=7)
    if rle:   # run-heavy tail (many marked symbols, long expansions)
        data = b"".join(
            bytes([int(s)]) * int(r) for s, r in
            zip(rng.integers(0, 6, 300), rng.integers(1, 50, 300)))
        cases.append((data, rx.encode(data, use_rle=True,
                                      use_pack=pack, x32=x32)))
    encs = [e for _, e in cases]
    lens = [len(d) for d, _ in cases]
    got = rd.decode_streams(encs, lens)
    for (data, enc), g in zip(cases, got):
        assert g is not None, "supported combo must not fall back"
        assert g == rx.decode(enc, len(data)) == data


def test_scan_parity_nosz():
    rng = np.random.default_rng(1)
    cases = []
    for x32 in (False, True):
        for data, enc in _corpus(rng, [0, 1, 500, 5000], x32=x32,
                                 rle=True):
            if enc[0] & rx.F_NOSZ:
                continue
            cases.append((data, _strip_size(enc, len(data))))
    encs = [e for _, e in cases]
    lens = [len(d) for d, _ in cases]
    got = rd.decode_streams(encs, lens)
    for (data, enc), g in zip(cases, got):
        assert g == rx.decode(enc, len(data)) == data


def _order1_corpus(rng, n=20000):
    """Delta-correlated bytes — the shape ORDER1 wins on (quality/
    name-like streams)."""
    deltas = rng.choice([0, 0, 0, 1, 2, 5], size=n)
    return bytes((np.cumsum(deltas) % 120).astype(np.uint8))


@pytest.mark.parametrize("x32", [False, True])
@pytest.mark.parametrize("rle,pack", [(False, False), (True, False),
                                      (False, True), (True, True)])
def test_scan_parity_order1_flag_matrix(x32, rle, pack):
    """ORDER1 through the full transform matrix: per-context slot
    gathers, carry-context lanes and the lane-sliced output mapping
    must be byte-identical to the host oracle, including tail-heavy
    (out_len % N != 0) and bucket-boundary sizes."""
    rng = np.random.default_rng(20)
    base = _order1_corpus(rng)
    cases = []
    for sz in (0, 1, 63, 127, 4095, 4097, 8191, 19997, 20000):
        data = base[:sz]
        if pack:  # packable alphabet (≤16 distinct)
            data = bytes((np.frombuffer(data, np.uint8) % 11)
                         .astype(np.uint8))
        enc = rx.encode(data, order=1, use_rle=rle, use_pack=pack,
                        x32=x32)
        cases.append((data, enc))
    assert any(e[0] & rx.F_ORDER1 for _, e in cases), \
        "fixture corpus must include genuinely-ORDER1 streams"
    encs = [e for _, e in cases]
    lens = [len(d) for d, _ in cases]
    got = rd.decode_streams(encs, lens)
    for (data, enc), g in zip(cases, got):
        assert g is not None, "supported combo must not fall back"
        assert g == rx.decode(enc, len(data)) == data


def test_order1_table_edge_cases():
    """Per-context table corners: skewed single-successor contexts
    (freq 4096 rows), tiny alphabets on the RAW table path, large
    alphabets on the order-0-compressed table path, and NOSZ."""
    rng = np.random.default_rng(21)
    cases = []
    # cyclic patterns: every context has exactly one successor, so
    # each row is one symbol at full 2^shift frequency
    for pat, reps in ((b"abc", 4000), (b"ab", 3000),
                      (b"\x00\xff", 2000)):
        data = bytes(pat * reps)
        enc = rx.encode(data, order=1)
        cases.append((data, enc))
    # two-symbol skew: one context dominates
    data = bytes((rng.random(12000) < 0.02).astype(np.uint8) + 65)
    cases.append((data, rx.encode(data, order=1)))
    # wide alphabet → table itself ships order-0-compressed
    wide = _order1_corpus(rng)
    ewide = rx.encode(wide, order=1)
    assert ewide[0] & rx.F_ORDER1
    head = ewide[1 + len(rx.write_uint7(len(wide)))]
    assert head & 1, "wide-alphabet table should be compressed"
    cases.append((wide, ewide))
    # small alphabet stays raw-table
    eab = rx.encode(bytes(b"abc" * 4000), order=1)
    hab = eab[1 + len(rx.write_uint7(12000))]
    assert not (hab & 1), "tiny table should stay raw"
    # NOSZ ORDER1
    enc = rx.encode(wide, order=1)
    cases.append((wide, _strip_size(enc, len(wide))))
    encs = [e for _, e in cases]
    lens = [len(d) for d, _ in cases]
    got = rd.decode_streams(encs, lens)
    for (data, enc), g in zip(cases, got):
        assert g is not None
        assert g == rx.decode(enc, len(data)) == data


def test_stripe_device_decode_uneven_lanes():
    """STRIPE containers: uneven sub-stream lengths (out_len not a
    multiple of N'), every lane its own complete stream (ORDER0,
    ORDER1 and X32 inner codecs), reassembled by the batched
    transpose-interleave gather byte-identically."""
    rng = np.random.default_rng(22)
    base = _order1_corpus(rng)
    cases = []
    for sz in (20000, 19999, 19998, 4097, 101, 7):
        for kw in (dict(stripe=4), dict(stripe=3),
                   dict(stripe=4, x32=True),
                   dict(stripe=2, order=1)):
            data = base[:sz]
            enc = rx.encode(data, **kw)
            assert enc[0] & rx.F_STRIPE
            cases.append((data, enc))
    encs = [e for _, e in cases]
    lens = [len(d) for d, _ in cases]
    got = rd.decode_streams(encs, lens)
    for (data, enc), g in zip(cases, got):
        assert g is not None, "stripe must decode on device"
        assert g == rx.decode(enc, len(data)) == data


def test_order1_corrupt_table_falls_back():
    """A corrupt ORDER1 table section parses to None (host handles it
    its own canonical way) and the CRAM block decoder counts the
    per-block fallback."""
    from goleft_tpu.io.cram import M_RANSNX16, RawBlock

    rng = np.random.default_rng(23)
    data = _order1_corpus(rng, 6000)
    enc = bytearray(rx.encode(data, order=1))
    assert enc[0] & rx.F_ORDER1
    # truncate inside the table section
    szlen = len(rx.write_uint7(len(data)))
    bad = bytes(enc[:1 + szlen + 40])
    assert rx.parse_nx16(bad, len(data)) is None
    with pytest.raises((ValueError, IndexError)):
        rx.decode(bad, len(data))
    # implausible claimed table size: same error class as host
    head_at = 1 + szlen
    assert enc[head_at] & 1, "fixture table should be compressed"
    big = bytes(enc[:head_at + 1]) + rx.write_uint7(1 << 23) \
        + bytes(enc[head_at + 1:])
    assert rx.parse_nx16(big, len(data)) is None
    with pytest.raises(ValueError, match="implausible o1 table"):
        rx.decode(big, len(data))
    # the block decoder degrades per-block, counted
    reg = get_registry()
    before = dict(reg.counters())
    dec = rd.DeviceBlockDecoder()
    good = bytes(enc)
    got = dec.decode_blocks(
        [RawBlock(M_RANSNX16, 4, 1, good, len(data))])
    assert got == [data]
    after = dict(reg.counters())
    assert after.get("decode.device_blocks_total", 0) \
        == before.get("decode.device_blocks_total", 0) + 1
    assert after.get("decode.device_fallback_total", 0) \
        == before.get("decode.device_fallback_total", 0)
    # the per-block fallback is byte-transparent: the corrupt block
    # fails with exactly the host codec's error class
    with pytest.raises((ValueError, IndexError)):
        dec.decode_blocks(
            [RawBlock(M_RANSNX16, 4, 1, bad, len(data))])
    assert dict(reg.counters()).get(
        "decode.device_fallback_total", 0) \
        == before.get("decode.device_fallback_total", 0) + 1


def test_order1_missing_context_diag():
    """A context lane pointing at an absent table row must raise the
    host's missing-context error from the device diag bit, not decode
    garbage silently."""
    rng = np.random.default_rng(24)
    data = _order1_corpus(rng, 4000)
    enc = rx.encode(data, order=1)
    p = rx.parse_nx16(enc, len(data))
    assert p is not None and p.order1
    # knock out a context row the stream actually uses
    used = np.flatnonzero(np.asarray(p.ctx_index) >= 0)
    p.ctx_index = p.ctx_index.copy()
    p.ctx_index[used[len(used) // 2]] = -1
    with pytest.raises(ValueError, match="missing order-1 context"):
        rd.decode_parsed([p])


def test_bucket_signature_cap_falls_back(caplog):
    """Past MAX_BUCKET_SIGNATURES, NEW block shapes decode on host
    (None from decode_streams, counted fallback from the block
    decoder) — never an error — and the trip logs one visible line."""
    import logging

    from goleft_tpu.io.cram import M_RANSNX16, RawBlock

    rng = np.random.default_rng(25)
    datas = [bytes(rng.integers(0, 40, n, dtype=np.uint8))
             for n in (300, 5000, 70000)]  # three distinct buckets
    encs = [rx.encode(d) for d in datas]
    old_cap = rd.MAX_BUCKET_SIGNATURES
    reg = get_registry()
    try:
        rd.reset_signature_registry()
        rd.MAX_BUCKET_SIGNATURES = 1
        before = dict(reg.counters())
        with caplog.at_level(logging.WARNING,
                             logger="goleft-tpu.ops.rans_device"):
            got = rd.decode_streams(encs, [len(d) for d in datas])
        assert got[0] == datas[0], "first shape is admitted"
        assert got[1] is None and got[2] is None, \
            "shapes past the cap fall back"
        after = dict(reg.counters())
        assert after.get("decode.bucket_signatures", 0) \
            == before.get("decode.bucket_signatures", 0) + 1
        assert any("bucket-signature cap" in r.message
                   for r in caplog.records)
        # same flow through the CRAM block decoder: host bytes, no
        # error, cap fallback counted
        dec = rd.DeviceBlockDecoder()
        raws = [RawBlock(M_RANSNX16, 4, 1, e, len(d))
                for e, d in zip(encs, datas)]
        got2 = dec.decode_blocks(raws)
        assert got2 == datas
        final = dict(reg.counters())
        assert final.get("decode.bucket_cap_fallback_total", 0) \
            >= before.get("decode.bucket_cap_fallback_total", 0) + 2
        assert final.get("decode.device_fallback_total", 0) \
            >= before.get("decode.device_fallback_total", 0) + 2
    finally:
        rd.MAX_BUCKET_SIGNATURES = old_cap
        rd.reset_signature_registry()


def test_host_vectorized_order1_loop_exactness():
    """The all-N-states-per-round ORDER1 numpy loop is byte-identical
    to the per-symbol scalar loop — lane-sliced output order, the
    intra-round renorm rank, the scalar tail and the missing-context
    raise — on clean AND mutated streams."""
    rng = np.random.default_rng(26)
    base = _order1_corpus(rng, 3000)
    for n_states in (4, 32):
        for cut in (0, 1, n_states - 1, n_states + 1):
            d = base[:len(base) - cut]
            enc = rx._encode_rans1(d, n_states)
            buf = memoryview(enc)
            head = buf[0]
            shift = head >> 4
            target = 1 << shift
            pos = 1
            if head & 1:
                ulen, pos = rx.read_uint7(buf, pos)
                clen, pos = rx.read_uint7(buf, pos)
                table = rx._decode_rans0(buf, pos, ulen, 4)
                pos += clen
                _, freqs, cums, luts, _ = rx._read_freqs1_rows(
                    memoryview(table), 0, target)
            else:
                _, freqs, cums, luts, pos = rx._read_freqs1_rows(
                    buf, pos, target)
            args = (buf, pos, len(d), n_states, shift, freqs, cums,
                    luts)
            assert rx._rans1_loop_vec(*args) \
                == rx._rans1_loop_scalar(*args) == d
            # mutated payload bytes: identical garbage or the same
            # host-class error from both loops
            for _ in range(15):
                mut = bytearray(enc)
                i = int(rng.integers(pos + 4 * n_states, len(mut)))
                mut[i] ^= int(rng.integers(1, 256))
                mb = memoryview(bytes(mut))
                am = (mb, pos, len(d), n_states, shift, freqs, cums,
                      luts)
                try:
                    want = rx._rans1_loop_scalar(*am)
                except ValueError as e:
                    with pytest.raises(ValueError,
                                       match="order-1 context"):
                        rx._rans1_loop_vec(*am)
                    assert "order-1 context" in str(e)
                else:
                    assert rx._rans1_loop_vec(*am) == want


def test_decode_order1_vectorized_product_gate():
    """rx.decode routes X32 ORDER1 through the vectorized loop and
    N=4 through the scalar loop (same measured crossover as ORDER0)
    — identical bytes either way."""
    rng = np.random.default_rng(27)
    data = _order1_corpus(rng, 9000)
    for x32 in (False, True):
        enc = rx.encode(data, order=1, x32=x32)
        assert enc[0] & rx.F_ORDER1
        old = rx.VEC_MIN_STATES
        try:
            rx.VEC_MIN_STATES = 1 << 30   # force scalar
            a = rx.decode(enc, len(data))
            rx.VEC_MIN_STATES = 1        # force vectorized
            b = rx.decode(enc, len(data))
        finally:
            rx.VEC_MIN_STATES = old
        assert a == b == data


def test_parse_nx16_rejects_inconsistencies():
    rng = np.random.default_rng(6)
    data = bytes(rng.integers(0, 50, 500, dtype=np.uint8))
    enc = rx.encode(data)
    # declared-size mismatch: host raises, parse defers to host
    assert rx.parse_nx16(enc, len(data) + 1) is None
    # NOSZ without an external size
    assert rx.parse_nx16(_strip_size(enc, len(data))) is None
    # truncation
    assert rx.parse_nx16(enc[:8], len(data)) is None
    p = rx.parse_nx16(enc, len(data))
    assert p is not None and p.final_len == len(data)
    assert p.table_bytes > 0


def test_order1_column_compaction_shrinks_table_with_parity():
    """ORDER1 context rows ship compacted on BOTH axes: a 40-ish
    symbol quality-like alphabet pays n_ctx² int16 cells instead of
    n_ctx·256 — ~5x less wire table — and the device decode stays
    byte-identical through the alphabet indirection."""
    rng = np.random.default_rng(20)
    data = bytes(rng.integers(33, 74, 6000, dtype=np.uint8))
    p = rx.parse_nx16(rx.encode(data, order=1))
    assert p is not None and p.order1
    assert p.ctx_freq.shape == (p.n_ctx, p.n_ctx)
    assert p.alphabet.shape == (p.n_ctx,)
    # every row maps back onto the full 256-wide matrix the host
    # decoder builds: column k is symbol alphabet[k]
    uncompacted_rows = p.n_ctx * 256 * 2 + 256 * 2
    assert p.table_bytes < uncompacted_rows // 4
    assert rd.decode_parsed([p]) == [data]


def test_host_vectorized_loop_exactness():
    """The all-N-states-per-round numpy loop is byte-identical to the
    per-symbol scalar loop — including the intra-round renorm order
    and the bytes-left guard — on clean AND mutated streams."""
    rng = np.random.default_rng(7)
    base = bytes(rng.integers(0, 30, 3000, dtype=np.uint8))
    for n_states in (4, 32):
        enc = rx._encode_rans0(base, n_states)
        buf = memoryview(enc)
        freqs, pos = rx._read_freqs0(buf, 0)
        cum = np.zeros(257, dtype=np.int64)
        np.cumsum(freqs, out=cum[1:])
        lut = rx._slot_lut(freqs, cum)
        args = (buf, pos, len(base), n_states, freqs, cum, lut)
        assert rx._rans0_loop_vec(*args) \
            == rx._rans0_loop_scalar(*args) == base
        # tail-heavy: out_len not a multiple of N exercises the
        # scalar-ordered final partial round
        for cut in (1, n_states - 1, n_states + 1):
            short = rx._encode_rans0(base[:len(base) - cut], n_states)
            b2 = memoryview(short)
            f2, p2 = rx._read_freqs0(b2, 0)
            c2 = np.zeros(257, dtype=np.int64)
            np.cumsum(f2, out=c2[1:])
            l2 = rx._slot_lut(f2, c2)
            a2 = (b2, p2, len(base) - cut, n_states, f2, c2, l2)
            assert rx._rans0_loop_vec(*a2) \
                == rx._rans0_loop_scalar(*a2)
        # mutated payload bytes: garbage in, IDENTICAL garbage out
        # (the vectorized loop must stay the oracle's twin even when
        # states leave the valid range — int64 keeps it exact)
        for _ in range(25):
            mut = bytearray(enc)
            i = int(rng.integers(pos + 4 * n_states, len(mut)))
            mut[i] ^= int(rng.integers(1, 256))
            mb = memoryview(bytes(mut))
            am = (mb, pos, len(base), n_states, freqs, cum, lut)
            assert rx._rans0_loop_vec(*am) \
                == rx._rans0_loop_scalar(*am)


def test_decode_vectorized_product_gate():
    """rx.decode routes X32 streams through the vectorized loop and
    N=4 through the scalar loop (the measured crossover) — both land
    on identical bytes either way."""
    rng = np.random.default_rng(8)
    data = bytes(rng.integers(0, 64, 9000, dtype=np.uint8))
    for x32 in (False, True):
        enc = rx.encode(data, x32=x32)
        old = rx.VEC_MIN_STATES
        try:
            rx.VEC_MIN_STATES = 1 << 30   # force scalar
            a = rx.decode(enc, len(data))
            rx.VEC_MIN_STATES = 1        # force vectorized
            b = rx.decode(enc, len(data))
        finally:
            rx.VEC_MIN_STATES = old
        assert a == b == data


def test_device_block_decoder_on_cram_container(tmp_path):
    """CramFile + DeviceBlockDecoder: identical columns, device/
    fallback counters move, wire bytes recorded, and the staging runs
    through the prefetch counters (compressed-size accounting)."""
    from goleft_tpu.io import cram
    from goleft_tpu.io.bam import parse_cigar

    rng = np.random.default_rng(9)
    ref_len = 30_000
    p = str(tmp_path / "t.cram")
    hdr = "@HD\tVN:1.6\tSO:coordinate\n@RG\tID:r\tSM:t\n"
    reads = sorted((0, int(rng.integers(0, ref_len - 200)), "100M",
                    60, 0) for _ in range(300))
    with open(p, "wb") as fh:
        with cram.CramWriter(fh, hdr, ["chr1"], [ref_len],
                             records_per_container=120,
                             block_method=cram.M_RANSNX16,
                             rans_order=0, minor=1) as w:
            for j, (tid, pos, cig, mq, fl) in enumerate(reads):
                w.write_record(tid, pos, parse_cigar(cig), mapq=mq,
                               flag=fl, name=f"r{j:04d}")
        w.write_crai(p + ".crai")

    host = cram.CramFile.from_file(p)
    cols_host = host.read_columns(0, 0, ref_len)

    reg = get_registry()
    before = dict(reg.counters())
    dev_h = cram.CramFile.from_file(p)
    dev_h.set_block_decoder(rd.DeviceBlockDecoder())
    cols_dev = dev_h.read_columns(0, 0, ref_len)
    after = dict(reg.counters())

    for f in ("pos", "end", "mapq", "flag", "seg_start", "seg_end",
              "seg_read"):
        np.testing.assert_array_equal(getattr(cols_host, f),
                                      getattr(cols_dev, f))

    def delta(name):
        return after.get(name, 0) - before.get(name, 0)

    assert delta("decode.device_blocks_total") > 0
    assert delta("decode.wire_bytes_compressed_total") > 0
    assert delta("decode.wire_bytes_uncompressed_total") > 0
    assert delta("prefetch.bytes_staged_total") > 0
    assert delta("prefetch.bytes_transferred_total") > 0


def _write_cram_cohort(tmp_path):
    from goleft_tpu.ops.decode_smoke import make_cram_cohort

    return make_cram_cohort(str(tmp_path))


def test_cohortdepth_decode_device_byte_identical(tmp_path):
    """The full cohort path: --decode-device matrices byte-identical
    to the default — and the ORDER1 + STRIPE samples that used to
    fire per-block fallbacks now decode on device, so the fallback
    counter must NOT move on this fully-supported cohort (the
    decode-smoke contract), while the ORDER1 table share lands in
    decode.table_bytes_total."""
    from goleft_tpu.commands.cohortdepth import run_cohortdepth

    crams, fai = _write_cram_cohort(tmp_path)
    reg = get_registry()
    a = io.StringIO()
    assert run_cohortdepth(crams, fai=fai, window=500, out=a) == 0
    before = dict(reg.counters())
    b = io.StringIO()
    assert run_cohortdepth(crams, fai=fai, window=500, out=b,
                           decode_device=True) == 0
    after = dict(reg.counters())
    assert a.getvalue() == b.getvalue()
    assert after.get("decode.device_blocks_total", 0) \
        > before.get("decode.device_blocks_total", 0)
    assert after.get("decode.device_fallback_total", 0) \
        == before.get("decode.device_fallback_total", 0)
    assert after.get("decode.table_bytes_total", 0) \
        > before.get("decode.table_bytes_total", 0)


def test_cohortdepth_decode_device_prefetched(tmp_path):
    """--decode-device composes with --prefetch-depth: the decode +
    compressed staging runs on the producer threads, bytes unchanged."""
    from goleft_tpu.commands.cohortdepth import run_cohortdepth

    crams, fai = _write_cram_cohort(tmp_path)
    a = io.StringIO()
    assert run_cohortdepth(crams, fai=fai, window=500, out=a) == 0
    b = io.StringIO()
    assert run_cohortdepth(crams, fai=fai, window=500, out=b,
                           decode_device=True, prefetch_depth=2) == 0
    assert a.getvalue() == b.getvalue()


def test_decode_site_transient_fault_retried(tmp_path):
    """The decode dispatch is a plan Step at the 'decode' fault site:
    an injected transient costs one retry, a permanent propagates."""
    from goleft_tpu.resilience import faults

    rng = np.random.default_rng(10)
    data = bytes(rng.integers(0, 50, 5000, dtype=np.uint8))
    enc = rx.encode(data)
    try:
        faults.install("decode:after=1:transient")
        dec = rd.DeviceBlockDecoder()
        from goleft_tpu.io.cram import M_RANSNX16, RawBlock

        raws = [RawBlock(M_RANSNX16, 4, 1, enc, len(data))]
        got = dec.decode_blocks(raws)
        assert got == [data]
        faults.install("decode:after=1:permanent")
        with pytest.raises(faults.InjectedPermanentFault):
            rd.DeviceBlockDecoder().decode_blocks(raws)
    finally:
        faults.install(None)


def test_bgzf_decompress_preallocated_multiblock():
    """Whole-file fallback inflation via the preallocated buffer:
    multi-block streams round-trip and the CRC/ISIZE guards still
    fire (the two-pass rewrite must not soften corruption checks)."""
    import struct
    import zlib

    from goleft_tpu.io.bgzf import BgzfWriter, bgzf_decompress

    rng = np.random.default_rng(11)
    payload = bytes(rng.integers(0, 256, 300_000, dtype=np.uint8))
    buf = io.BytesIO()
    with BgzfWriter(buf, block_size=4096) as w:
        w.write(payload)
    data = buf.getvalue()
    assert bgzf_decompress(data) == payload
    assert bgzf_decompress(b"") == b""
    # corrupt one compressed byte mid-stream: either inflate fails
    # (zlib.error) or the CRC guard catches it — never silence
    bad = bytearray(data)
    bad[len(bad) // 2] ^= 0xFF
    with pytest.raises((ValueError, zlib.error)):
        bgzf_decompress(bytes(bad))
    # corrupt an ISIZE trailer: the preallocation pass reads it, the
    # inflate pass must still validate it
    first_bsize = struct.unpack_from(
        "<H", data, 16)[0] + 1
    bad2 = bytearray(data)
    struct.pack_into("<I", bad2, first_bsize - 4, 0xDEADBEEF)
    with pytest.raises(ValueError, match="ISIZE|CRC"):
        bgzf_decompress(bytes(bad2))


def test_stage_block_arrays_counts_compressed_bytes():
    from goleft_tpu.parallel.prefetch import stage_block_arrays

    reg = get_registry()
    before = reg.counters().get("prefetch.bytes_staged_total", 0)
    arrs = {"payload": np.zeros(1000, np.uint8),
            "freq": np.zeros(256, np.int16)}
    out = stage_block_arrays(arrs)
    after = reg.counters().get("prefetch.bytes_staged_total", 0)
    assert after - before == 1000 + 512
    assert set(out) == {"payload", "freq"}
