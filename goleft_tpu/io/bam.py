"""BAM container codec, clean-room from the SAM/BAM specification (section 4).

Replaces what the reference vendors from biogo/hts/bam (SURVEY.md §2.4):
header + reference dictionary parsing and alignment-record decode. Unlike the
reference (which never decodes records itself — it pipes BAM through
``samtools depth`` and parses text, depth/depth.go:45), this decoder emits
**columnar numpy arrays** of read tuples and ref-aligned segments, the exact
feed format for the device coverage kernel (ops/coverage.py).

CIGAR op semantics (spec table): M/=/X consume query+ref, D/N consume ref
only, I/S consume query only, H/P consume neither. Depth counts only
query+ref-consuming ops (the ``samtools depth`` default the reference
inherits), so a record's coverage contribution is its list of M/=/X blocks.

A record writer is included for building hermetic test fixtures (the
reference ships tiny BAMs; we fabricate our own instead of copying them).
"""

from __future__ import annotations

import struct
from dataclasses import dataclass, field

import numpy as np

from .bgzf import BgzfReader, BgzfWriter

BAM_MAGIC = b"BAM\x01"

CIGAR_OPS = "MIDNSHP=X"
# ops that consume the reference
_CONSUMES_REF = np.array([1, 0, 1, 1, 0, 0, 0, 1, 1], dtype=np.int64)
# ops that consume the query
_CONSUMES_QUERY = np.array([1, 1, 0, 0, 1, 0, 0, 1, 1], dtype=np.int64)
# ops that count toward depth (query+ref aligned): M, =, X
_IS_ALIGNED = np.array([1, 0, 0, 0, 0, 0, 0, 1, 1], dtype=np.bool_)

SEQ_NT16 = "=ACMGRSVTWYHKDBN"
_NT16_CODE = {c: i for i, c in enumerate(SEQ_NT16)}

# flag bits
FLAG_PAIRED = 0x1
FLAG_PROPER_PAIR = 0x2
FLAG_UNMAPPED = 0x4
FLAG_MATE_UNMAPPED = 0x8
FLAG_REVERSE = 0x10
FLAG_MATE_REVERSE = 0x20
FLAG_READ1 = 0x40
FLAG_READ2 = 0x80
FLAG_SECONDARY = 0x100
FLAG_QCFAIL = 0x200
FLAG_DUP = 0x400
FLAG_SUPPLEMENTARY = 0x800

# samtools depth default skip mask: UNMAP | SECONDARY | QCFAIL | DUP
DEPTH_SKIP_FLAGS = FLAG_UNMAPPED | FLAG_SECONDARY | FLAG_QCFAIL | FLAG_DUP


@dataclass
class BamHeader:
    text: str
    ref_names: list[str]
    ref_lens: list[int]
    _name_to_tid: dict = field(default_factory=dict, repr=False)

    def __post_init__(self):
        self._name_to_tid = {n: i for i, n in enumerate(self.ref_names)}

    def tid(self, name: str) -> int:
        return self._name_to_tid[name]

    def sample_names(self) -> list[str]:
        """Unique SM tags from @RG lines, in first-seen order.

        Mirrors samplename.Names (reference samplename/samplename.go:14-37).
        """
        seen: list[str] = []
        for line in self.text.splitlines():
            if not line.startswith("@RG"):
                continue
            for tok in line.split("\t")[1:]:
                if tok.startswith("SM:"):
                    sm = tok[3:]
                    if sm and sm not in seen:
                        seen.append(sm)
        return seen


@dataclass
class BamRecord:
    """One decoded alignment (used by tests and covstats sampling)."""

    tid: int
    pos: int
    mapq: int
    flag: int
    mate_tid: int
    mate_pos: int
    tlen: int
    name: str
    cigar: list[tuple[int, int]]  # (oplen, opcode)
    seq: str
    qual: bytes

    @property
    def ref_end(self) -> int:
        n = self.pos
        for oplen, op in self.cigar:
            n += oplen * int(_CONSUMES_REF[op])
        return n

    @property
    def read_len(self) -> int:
        return len(self.seq)

    def aligned_blocks(self) -> list[tuple[int, int]]:
        out = []
        p = self.pos
        for oplen, op in self.cigar:
            if _IS_ALIGNED[op]:
                out.append((p, p + oplen))
            if _CONSUMES_REF[op]:
                p += oplen
        return out


@dataclass
class ReadColumns:
    """Columnar read tuples: the host→device wire format.

    ``seg_*`` arrays have one row per M/=/X CIGAR block; ``seg_read`` maps
    each segment back to its read row. Filtering by flag/mapq happens on
    device so changing thresholds costs no re-decode.
    """

    tid: np.ndarray  # int32  (n_reads,)
    pos: np.ndarray  # int32
    end: np.ndarray  # int32  ref end (pos + ref-consumed length)
    mapq: np.ndarray  # uint8
    flag: np.ndarray  # uint16
    tlen: np.ndarray  # int32
    read_len: np.ndarray  # int32
    mate_pos: np.ndarray  # int32
    single_m: np.ndarray  # bool: cigar is exactly one M op
    seg_tid: np.ndarray  # int32 (n_segs,)
    seg_start: np.ndarray  # int32
    seg_end: np.ndarray  # int32
    seg_read: np.ndarray  # int32 index into read rows

    _FIELDS = ("tid", "pos", "end", "mapq", "flag", "tlen", "read_len",
               "mate_pos", "single_m", "seg_tid", "seg_start", "seg_end")

    @property
    def n_reads(self) -> int:
        return len(self.pos)

    @staticmethod
    def empty() -> "ReadColumns":
        z32 = np.zeros(0, dtype=np.int32)
        return ReadColumns(
            z32, z32, z32,
            np.zeros(0, dtype=np.uint8), np.zeros(0, dtype=np.uint16),
            z32, z32, z32.copy(), np.zeros(0, dtype=bool),
            z32.copy(), z32.copy(), z32.copy(), z32.copy(),
        )

    @staticmethod
    def concat(parts: list["ReadColumns"]) -> "ReadColumns":
        parts = [p for p in parts if p.n_reads]
        if not parts:
            return ReadColumns.empty()
        offs = np.cumsum([0] + [p.n_reads for p in parts[:-1]])
        return ReadColumns(
            *[np.concatenate([getattr(p, f) for p in parts])
              for f in ReadColumns._FIELDS],
            np.concatenate(
                [p.seg_read + o for p, o in zip(parts, offs)]
            ).astype(np.int32),
        )


def _decode_record(buf: bytes, want_seq: bool = False) -> BamRecord:
    (tid, pos, l_rn, mapq, _bin, n_cig, flag, l_seq, mtid, mpos, tlen
     ) = struct.unpack_from("<iiBBHHHiiii", buf, 0)
    off = 32
    name = buf[off : off + l_rn - 1].decode()
    off += l_rn
    cigar = []
    for _ in range(n_cig):
        (v,) = struct.unpack_from("<I", buf, off)
        cigar.append((v >> 4, v & 0xF))
        off += 4
    seq = ""
    qual = b""
    if want_seq:
        nb = (l_seq + 1) // 2
        sq = buf[off : off + nb]
        chars = []
        for i in range(l_seq):
            b = sq[i // 2]
            code = (b >> 4) if i % 2 == 0 else (b & 0xF)
            chars.append(SEQ_NT16[code])
        seq = "".join(chars)
        qual = buf[off + nb : off + nb + l_seq]
    return BamRecord(tid, pos, mapq, flag, mtid, mpos, tlen, name, cigar,
                     seq, qual)


class BamReader:
    """Sequential + random-access BAM reader over an in-memory file."""

    def __init__(self, data: bytes):
        if data[:4] == b"CRAM":
            raise ValueError(
                "BamReader got CRAM bytes — open with io.cram.CramFile "
                "(open_bam_file routes automatically)"
            )
        self._r = BgzfReader(data)
        magic = self._r.read(4)
        if magic != BAM_MAGIC:
            raise ValueError("not a BAM file (bad magic)")
        (l_text,) = struct.unpack("<i", self._r.read(4))
        text = self._r.read(l_text).rstrip(b"\x00").decode()
        (n_ref,) = struct.unpack("<i", self._r.read(4))
        names, lens = [], []
        for _ in range(n_ref):
            (l_name,) = struct.unpack("<i", self._r.read(4))
            names.append(self._r.read(l_name)[:-1].decode())
            (l_ref,) = struct.unpack("<i", self._r.read(4))
            lens.append(l_ref)
        self.header = BamHeader(text, names, lens)
        self._body_voffset = self._r.tell_virtual()

    @classmethod
    def from_file(cls, path: str) -> "BamReader":
        with open(path, "rb") as fh:
            return cls(fh.read())

    def rewind(self) -> None:
        self._r.seek_virtual(self._body_voffset)

    def seek_virtual(self, voffset: int) -> None:
        self._r.seek_virtual(voffset)

    def __iter__(self):
        return self

    def __next__(self) -> BamRecord:
        rec = self.next_record(want_seq=True)
        if rec is None:
            raise StopIteration
        return rec

    def next_record(self, want_seq: bool = False) -> BamRecord | None:
        szb = self._r.read(4)
        if len(szb) < 4:
            return None
        (block_size,) = struct.unpack("<i", szb)
        if block_size < 32:
            raise ValueError("bam: malformed record geometry")
        buf = self._r.read(block_size)
        if len(buf) < block_size:
            raise ValueError("bam: truncated record")
        return _decode_record(buf, want_seq=want_seq)

    def read_columns(
        self,
        tid: int | None = None,
        start: int = 0,
        end: int | None = None,
        max_records: int | None = None,
    ) -> ReadColumns:
        """Decode records into columnar arrays.

        When ``tid`` is given, only records on that reference overlapping
        [start, end) are kept (the stream is still scanned sequentially from
        the current position; pair with a BAI region seek for random access).
        """
        tids, poss, ends, mapqs, flags, tlens, rlens = \
            [], [], [], [], [], [], []
        mposs, singlem = [], []
        seg_t, seg_s, seg_e, seg_r = [], [], [], []
        n = 0
        while True:
            szb = self._r.read(4)
            if len(szb) < 4:
                break
            (block_size,) = struct.unpack("<i", szb)
            if block_size < 32:
                raise ValueError("bam: malformed record geometry")
            buf = self._r.read(block_size)
            (rtid, pos, l_rn, mapq, _bin, n_cig, flag, l_seq
             ) = struct.unpack_from("<iiBBHHHi", buf, 0)
            if 32 + l_rn + 4 * n_cig > block_size:
                raise ValueError("bam: malformed record geometry")
            if tid is not None:
                if rtid > tid or rtid < 0:
                    break  # sorted BAM: past the target chromosome
                if rtid < tid:
                    continue
                if end is not None and pos >= end:
                    break
            mpos, tlen = struct.unpack_from("<ii", buf, 24)
            off = 32 + l_rn
            cig = np.frombuffer(buf, dtype=np.uint32, count=n_cig, offset=off)
            oplen = (cig >> 4).astype(np.int64)
            opc = (cig & 0xF).astype(np.int64)
            ref_len = int(np.sum(oplen * _CONSUMES_REF[opc]))
            rend = pos + ref_len
            if tid is not None and rend <= start:
                continue
            row = n
            n += 1
            tids.append(rtid)
            poss.append(pos)
            ends.append(rend)
            mapqs.append(mapq)
            flags.append(flag)
            tlens.append(tlen)
            # reference covstats measures read length from the CIGAR query
            # length (covstats.go rec.Cigar.Lengths()); BAM l_seq matches it
            # except when SEQ is omitted ('*', l_seq=0) — fall back then
            if l_seq > 0:
                rlens.append(l_seq)
            else:
                rlens.append(int(np.sum(oplen * _CONSUMES_QUERY[opc])))
            mposs.append(mpos)
            singlem.append(n_cig == 1 and (cig[0] & 0xF) == 0)
            # aligned blocks
            ref_steps = oplen * _CONSUMES_REF[opc]
            block_starts = pos + np.concatenate(
                ([0], np.cumsum(ref_steps[:-1]))
            )
            al = _IS_ALIGNED[opc]
            for bs, ln in zip(block_starts[al], oplen[al]):
                seg_t.append(rtid)
                seg_s.append(int(bs))
                seg_e.append(int(bs + ln))
                seg_r.append(row)
            if max_records is not None and n >= max_records:
                break
        return ReadColumns(
            np.asarray(tids, dtype=np.int32),
            np.asarray(poss, dtype=np.int32),
            np.asarray(ends, dtype=np.int32),
            np.asarray(mapqs, dtype=np.uint8),
            np.asarray(flags, dtype=np.uint16),
            np.asarray(tlens, dtype=np.int32),
            np.asarray(rlens, dtype=np.int32),
            np.asarray(mposs, dtype=np.int32),
            np.asarray(singlem, dtype=bool),
            np.asarray(seg_t, dtype=np.int32),
            np.asarray(seg_s, dtype=np.int32),
            np.asarray(seg_e, dtype=np.int32),
            np.asarray(seg_r, dtype=np.int32),
        )


def _cols_from_decode(out: dict) -> "ReadColumns":
    """Native bam_decode output dict → ReadColumns (shared by the one-shot
    and streaming paths so the column wiring can't drift apart)."""
    return ReadColumns(
        out["tid"], out["pos"], out["end"], out["mapq"],
        out["flag"], out["tlen"], out["read_len"],
        out["mate_pos"], out["single_m"].astype(bool),
        out["tid"][out["seg_read"]] if out["n_reads"] else
        np.zeros(0, np.int32),
        out["seg_start"], out["seg_end"], out["seg_read"],
    )


def _parse_header_buf(buf) -> tuple[BamHeader, int]:
    """Parse the BAM header block from an uncompressed buffer; returns
    (header, offset of first alignment record). Corrupt header geometry
    surfaces as ValueError — the module's one error type for bad input
    (raw struct/unicode errors would leak through every CLI)."""
    if bytes(buf[:4]) != BAM_MAGIC:
        raise ValueError("not a BAM file (bad magic)")
    try:
        (l_text,) = struct.unpack_from("<i", buf, 4)
        text = bytes(buf[8 : 8 + l_text]).rstrip(b"\x00").decode()
        off = 8 + l_text
        (n_ref,) = struct.unpack_from("<i", buf, off)
        off += 4
        if l_text < 0 or n_ref < 0:
            raise ValueError("bam: negative header length")
        names, lens = [], []
        for _ in range(n_ref):
            (l_name,) = struct.unpack_from("<i", buf, off)
            names.append(
                bytes(buf[off + 4 : off + 4 + l_name - 1]).decode())
            (l_ref,) = struct.unpack_from("<i", buf, off + 4 + l_name)
            lens.append(l_ref)
            off += 8 + l_name
    except (struct.error, UnicodeDecodeError) as e:
        raise ValueError(f"bam: corrupt header ({e})") from e
    return BamHeader(text, names, lens), off


class BamFile:
    """Native-decoded BAM with eager or lazy (region-streaming) modes.

    Eager: the compressed stream inflates ONCE and shard decodes run
    over the resident uncompressed body — best for full-file scans
    (covstats) of files that fit in RAM.

    Lazy: only the BGZF block table is built up front; each
    ``read_columns(voffset=...)`` inflates just the block range the
    region needs (C++ ``bgzf_inflate_range``), so host memory scales
    with the shard, not the file — the mode cohort tools use, over
    mmap-backed compressed bytes. The decode window self-extends until
    the decoder reports a clean stop.

    All native calls release the GIL, so shard decode threads scale.
    """

    def __init__(self, data, lazy: bool = False):
        from . import native
        from .bgzf import bgzf_decompress

        if bytes(data[:4]) == b"CRAM":
            raise ValueError(
                "BamFile got CRAM bytes — open with io.cram.CramFile "
                "(open_bam_file routes automatically)"
            )
        # the pure-Python fallback exists for hosts WITHOUT the native
        # toolchain — a scan error on a corrupt file must surface as the
        # module's clean error, not get retried (and fail with a raw
        # zlib.error) through the Python codec (found by the stream
        # corruption fuzz)
        scan = native.bgzf_scan(data)  # None only when native is absent
        if scan is None:
            import zlib

            try:
                raw = bgzf_decompress(
                    bytes(data) if not isinstance(data, bytes) else data
                )
            except zlib.error as e:
                raise ValueError(f"bgzf: corrupt deflate stream ({e})")
            self.body = np.frombuffer(raw, dtype=np.uint8)
            self._co = self._uo = None
            self._comp = None
            self.native = False
            self.lazy = False
        else:
            self._co, self._uo, self._total = scan
            self.native = True
            self.lazy = lazy
            if lazy:
                self._comp = native._as_u8(data)
                self.body = None
            else:
                self._comp = None
                self.body = native.bgzf_inflate(data, self._total)
        self.header, self._body_start = self._parse_header()

    def _parse_header(self):
        from . import native

        if self.body is not None:
            return _parse_header_buf(
                bytes(self.body[: min(len(self.body), 1 << 22)])
            )
        # lazy: inflate a growing block prefix until the header parses
        nb = len(self._co)
        k = min(8, nb)
        while True:
            c_end = int(self._co[k]) if k < nb else len(self._comp)
            cap = int(self._uo[k]) if k < nb else self._total
            buf = native.bgzf_inflate_range(self._comp, 0, c_end, cap)
            try:
                return _parse_header_buf(bytes(buf))
            except Exception:
                if k >= nb:
                    raise
                k = min(k * 4, nb)

    @classmethod
    def from_file(cls, path: str, lazy: bool = False) -> "BamFile":
        from . import remote

        if remote.is_remote(path):
            # no mmap over the network: stage the object once (the
            # fetch tier's block cache + read-ahead overlap the
            # round trips) and hand the codec plain bytes
            return cls(remote.fetch_bytes(path), lazy=lazy)
        if lazy:
            import mmap

            # POSIX mmap stays valid after the fd closes
            with open(path, "rb") as fh:
                mm = mmap.mmap(fh.fileno(), 0, access=mmap.ACCESS_READ)
            return cls(mm, lazy=True)
        with open(path, "rb") as fh:
            return cls(fh.read())

    def _block_of(self, voff: int) -> int:
        coff = voff >> 16
        if coff > int(self._co[-1]):
            # the index promises data past the last block — a truncated
            # file with its stale .bai would otherwise decode as silent
            # zero depth for every shard beyond the cut
            raise ValueError(
                "bam: virtual offset beyond file end (truncated file "
                "or stale index)"
            )
        blk = int(np.searchsorted(self._co, coff, side="right")) - 1
        return max(blk, 0)

    def voffset_to_offset(self, voff: int) -> int:
        if self._co is None:
            raise ValueError("no block table (python fallback)")
        blk = self._block_of(voff)
        return int(self._uo[blk]) + (voff & 0xFFFF)

    def _decode(self, offset, tid, start, end):
        from . import native

        return native.bam_decode(
            self.body, offset,
            -1 if tid is None else tid, start,
            -1 if end is None else end,
        )

    def read_columns(self, tid: int | None = None, start: int = 0,
                     end: int | None = None,
                     voffset: int | None = None,
                     end_voffset: int | None = None) -> "ReadColumns":
        from . import native

        if not self.native:
            raise RuntimeError("BamFile requires the native library; "
                               "use open_bam() for automatic fallback")
        if self.lazy:
            out = self._read_lazy(tid, start, end, voffset, end_voffset)
        else:
            if voffset is not None:
                offset = self.voffset_to_offset(voffset)
            else:
                offset = self._body_start
            out = self._decode(offset, tid, start, end)
        return _cols_from_decode(out)

    def stream_columns(self, window_bytes: int = 1 << 24):
        """Yield ReadColumns chunks over the whole record stream in order.

        Lazy mode inflates only the current BGZF block window, so peak host
        memory is O(window), not O(file) — the reference's streaming loop
        (covstats/covstats.go:122-220) has the same bound. Eager mode just
        walks the resident body in window-sized decode steps.
        """
        from . import native

        if not self.native:
            raise RuntimeError("stream_columns requires the native library")
        to_cols = _cols_from_decode

        if not self.lazy:
            off = self._body_start
            total = len(self.body)
            while off < total:
                lim = min(off + window_bytes, total)
                out = native.bam_decode(self.body[:lim], off, -1, 0, -1)
                if out["n_reads"]:
                    yield to_cols(out)
                if out["consumed"] == 0:
                    if lim >= total:
                        break  # truncated tail / EOF
                    window_bytes *= 2  # record larger than the window
                    continue
                off += out["consumed"]
            return

        nb = len(self._co)
        u_off = self._body_start  # absolute uncompressed cursor
        while u_off < self._total:
            b0 = int(np.searchsorted(self._uo, u_off, side="right")) - 1
            b0 = max(b0, 0)
            in_block = u_off - int(self._uo[b0])
            b1 = int(np.searchsorted(
                self._uo, int(self._uo[b0]) + in_block + window_bytes,
                side="left",
            ))
            b1 = min(max(b1, b0 + 1), nb)
            c0 = int(self._co[b0])
            c_end = int(self._co[b1]) if b1 < nb else len(self._comp)
            cap = (int(self._uo[b1]) if b1 < nb else self._total) \
                - int(self._uo[b0])
            body = native.bgzf_inflate_range(self._comp, c0, c_end, cap)
            out = native.bam_decode(body, in_block, -1, 0, -1)
            if out["n_reads"]:
                yield to_cols(out)
            if out["consumed"] == 0:
                if b1 >= nb:
                    break  # truncated tail / EOF
                window_bytes *= 2  # record larger than the window
                continue
            u_off += out["consumed"]

    def read_segments(self, tid: int, start: int, end: int,
                      min_mapq: int, flag_mask: int,
                      voffset: int | None = None):
        """(seg_start, seg_end) int32 arrays of the region's FILTERED
        clipped M/=/X segments — the device segment path's host stage.

        On lazy native handles this streams through the C walk shared
        with :meth:`window_reduce` (one ring pass, no column arrays, no
        uncompressed body materialization); elsewhere it falls back to
        :meth:`read_columns` + host-side filter/clip. Both paths emit
        the same segment set the reduce engines consume, so a depth
        pipeline fed from either is byte-identical."""
        from . import native

        if end is None or end < 0:
            raise ValueError("read_segments requires an explicit end")
        if self.native and self.lazy and native.get_lib() is not None:
            if voffset is not None:
                c_begin = int(self._co[self._block_of(voffset)])
                in_block = voffset & 0xFFFF
            else:
                c_begin = 0
                in_block = self._body_start
            # cap_hint is only the collector's first capacity: the C
            # walk grows it as it fills and never re-walks
            return native.bam_segments_stream(
                self._comp, c_begin, in_block, tid, start, end,
                min_mapq, flag_mask,
                cap_hint=max(65536, (end - start) // 16))
        cols = self.read_columns(tid=tid, start=start, end=end,
                                 voffset=voffset)
        return filter_clip_segments(cols, start, end, min_mapq,
                                    flag_mask)

    def window_reduce(self, tid: int, start: int, end: int,
                      w0: int, length: int, window: int,
                      depth_cap: int, min_mapq: int, flag_mask: int,
                      voffset: int | None = None,
                      end_voffset: int | None = None,
                      delta_scratch=None,
                      inflate_buf=None) -> np.ndarray:
        """Host-fused decode + per-window depth sums for one region.

        Returns int64 window sums over [w0, w0+length) — the O(windows)
        product that crosses to the device, instead of O(reads) segment
        endpoints (shard_depth_pipeline's exact semantics; see
        csrc/fastio.cpp::bam_window_reduce). Releases the GIL throughout,
        so per-sample reductions scale across decode threads.

        Lazy handles stream: the lean direct-window accumulation runs
        first (no O(length) scratch at all) and the exact capped dense
        path reruns the shard only when a pileup could reach
        ``depth_cap``. ``delta_scratch`` (zeroed int32 of length+1) is
        used by eager handles and the dense fallback — optional
        everywhere; ``end_voffset``/``inflate_buf`` are accepted for
        backward compatibility but ignored on the streaming path (the
        walk stops itself at the region's first record past ``end``).
        """
        from . import native

        if not self.native:
            raise RuntimeError("window_reduce requires the native library")
        args = (tid, start, end, w0, length, window, depth_cap,
                min_mapq, flag_mask)
        if not self.lazy:
            offset = self.voffset_to_offset(voffset) \
                if voffset is not None else self._body_start
            out = native.bam_window_reduce(
                self.body, offset, *args, delta_scratch=delta_scratch)
            return out["wsums"]
        # lazy: stream — inflate each BGZF block into a small recycled
        # ring inside the C call and walk its records cache-hot; the
        # shard's uncompressed body never materializes (end_voffset is
        # unnecessary: the walk stops at the region's first record past
        # ``end``, at most one block beyond it). First try the lean
        # direct-window accumulation (no O(length) dense scratch); its
        # max_overlap bound proves whether depth_cap could bind — only
        # then rerun with the exact capped dense path (rare pileups).
        del end_voffset, inflate_buf
        if voffset is not None:
            c_begin = int(self._co[self._block_of(voffset)])
            in_block = voffset & 0xFFFF
        else:
            c_begin = 0
            in_block = self._body_start
        acc = native.bam_window_acc_stream(
            self._comp, c_begin, in_block, tid, start, end, w0, length,
            window, min_mapq, flag_mask,
        )
        if acc["max_overlap"] <= depth_cap:
            return acc["wsums"]
        out = native.bam_window_reduce_stream(
            self._comp, c_begin, in_block, *args,
            delta_scratch=delta_scratch,
        )
        return out["wsums"]

    def _lazy_scan(self, voffset, end_voffset, decode_fn,
                   inflate_buf=None):
        """Inflate a BGZF block window and run ``decode_fn(body,
        in_block)``, growing the window until the decode reports a clean
        stop. Shared by the columnar and window-reduce lazy paths.

        A stop strictly inside the window is a genuine region break;
        consuming the whole window is ambiguous (the window may end
        exactly on a record boundary) — extend to be sure.
        """
        from . import native

        nb = len(self._co)
        if voffset is not None:
            b0 = self._block_of(voffset)
            in_block = voffset & 0xFFFF
        else:
            b0 = 0
            in_block = self._body_start  # header is in block 0's stream
        b1 = nb if end_voffset is None else min(
            self._block_of(end_voffset) + 4, nb
        )
        while True:
            c0 = int(self._co[b0])
            c_end = int(self._co[b1]) if b1 < nb else len(self._comp)
            cap = (int(self._uo[b1]) if b1 < nb else self._total) - int(
                self._uo[b0]
            )
            obuf = None
            if inflate_buf is not None:
                if inflate_buf[0] is None or len(inflate_buf[0]) < cap:
                    inflate_buf[0] = np.empty(max(cap, 1 << 24), np.uint8)
                obuf = inflate_buf[0]
            body = native.bgzf_inflate_range(self._comp, c0, c_end, cap,
                                             out=obuf)
            out = decode_fn(body, in_block)
            mid_stop = in_block + out["consumed"] < len(body)
            if (out["done"] and mid_stop) or b1 >= nb:
                return out
            b1 = min(b1 + max(b1 - b0, 64), nb)

    def _read_lazy(self, tid, start, end, voffset, end_voffset):
        from . import native

        return self._lazy_scan(
            voffset, end_voffset,
            lambda body, in_block: native.bam_decode(
                body, in_block,
                -1 if tid is None else tid, start,
                -1 if end is None else end,
            ),
        )


class _PyBamAdapter:
    """BamFile-compatible shard decoder over the pure-Python reader."""

    native = False
    lazy = False

    def __init__(self, data):
        self._data = data if isinstance(data, bytes) else bytes(data)
        self.header = BamReader(self._data).header

    def read_columns(self, tid=None, start=0, end=None, voffset=None,
                     end_voffset=None) -> "ReadColumns":
        rdr = BamReader(self._data)
        if voffset is not None:
            rdr.seek_virtual(voffset)
        return rdr.read_columns(tid=tid, start=start, end=end)

    def read_segments(self, tid: int, start: int, end: int,
                      min_mapq: int, flag_mask: int,
                      voffset: int | None = None):
        """Same contract as BamFile.read_segments (the device paths'
        host stage), over the pure-Python reader."""
        cols = self.read_columns(tid=tid, start=start, end=end,
                                 voffset=voffset)
        return filter_clip_segments(cols, start, end, min_mapq,
                                    flag_mask)

    def stream_columns(self, window_bytes: int = 1 << 24,
                       chunk_records: int = 1 << 18):
        """Chunked sequential decode; loops to EOF (not a fixed record
        cap), so consumers see the same stream the native path yields."""
        rdr = BamReader(self._data)
        while True:
            cols = rdr.read_columns(max_records=chunk_records)
            if cols.n_reads == 0:
                return
            yield cols


def read_header_only(path: str, initial: int = 1 << 20) -> BamHeader:
    """Parse just the BAM header, reading a growing file prefix — avoids
    pulling multi-GB files into memory for an SM-tag lookup. Remote
    URLs read the same growing prefix as ranged fetches — an SM-tag
    lookup against an object store costs a few round trips, not the
    object."""
    import os

    from . import remote

    if remote.is_remote(path):
        with remote.open_source(path) as src:
            size = src.length
            n = min(initial, size)
            while True:
                data = src.read(0, n)
                try:
                    return BamReader(data).header
                except Exception:
                    if n >= size:
                        raise
                    n = min(n * 4, size)
    size = os.path.getsize(path)
    n = min(initial, size)
    while True:
        with open(path, "rb") as fh:
            data = fh.read(n)
        try:
            return BamReader(data).header
        except Exception:
            if n >= size:
                raise
            n = min(n * 4, size)


def open_bam(data, lazy: bool = False):
    """Decoded-BAM handle: native fast path when available, else the
    pure-Python streaming adapter (same read_columns signature).

    Corrupt data raises ValueError from whichever codec runs — the
    Python path is a fallback for hosts WITHOUT the native library,
    never a retry for bytes the native codec rejected (retrying corrupt
    bytes through zlib leaked raw zlib.error; stream-fuzz finding)."""
    import zlib

    from . import native

    if native.get_lib() is not None:
        return BamFile(data, lazy=lazy)
    try:
        return _PyBamAdapter(data)
    except zlib.error as e:
        raise ValueError(f"bgzf: corrupt deflate stream ({e})")


def read_alignment_header(path: str) -> BamHeader:
    """Header of a BAM or CRAM file (magic-dispatched)."""
    from . import remote

    if remote.is_remote(path):
        magic = remote.read_range(path, 0, 4)
    else:
        with open(path, "rb") as fh:
            magic = fh.read(4)
    if magic == b"CRAM":
        from .cram import CramFile

        return CramFile.from_file(path).header
    return read_header_only(path)


def open_bam_file(path: str, lazy: bool = True):
    """Open from disk; lazy native handles mmap the compressed file so
    host residency stays proportional to the regions actually decoded,
    not the file (or its ~4x inflated body). CRAM files route to the
    clean-room CRAM 3.0 decoder (io/cram.py), which presents the same
    read_columns/stream_columns surface."""
    from . import native, remote

    if remote.is_remote(path):
        magic = remote.read_range(path, 0, 4)
    else:
        with open(path, "rb") as fh:
            magic = fh.read(4)
    if magic == b"CRAM":
        from .cram import CramFile

        try:
            return CramFile.from_file(path)
        except ValueError as e:
            raise SystemExit(f"{path}: CRAM open failed: {e}") from e
    try:
        if lazy and native.get_lib() is not None:
            return BamFile.from_file(path, lazy=True)
        return open_bam(remote.fetch_bytes(path), lazy=False)
    except ValueError as e:
        # clean CLI surface for corrupt/truncated input, mirroring the
        # CRAM branch above
        raise SystemExit(f"{path}: {e}") from e


def reg2bin(beg: int, end: int) -> int:
    """SAM spec section 5.3 bin number for [beg, end)."""
    end -= 1
    if beg >> 14 == end >> 14:
        return ((1 << 15) - 1) // 7 + (beg >> 14)
    if beg >> 17 == end >> 17:
        return ((1 << 12) - 1) // 7 + (beg >> 17)
    if beg >> 20 == end >> 20:
        return ((1 << 9) - 1) // 7 + (beg >> 20)
    if beg >> 23 == end >> 23:
        return ((1 << 6) - 1) // 7 + (beg >> 23)
    if beg >> 26 == end >> 26:
        return ((1 << 3) - 1) // 7 + (beg >> 26)
    return 0


class BamWriter:
    """Minimal BAM writer for fabricating hermetic test fixtures."""

    def __init__(self, fh, header_text: str, ref_names: list[str],
                 ref_lens: list[int], level: int = 6,
                 block_size: int = 0xFF00):
        self._w = BgzfWriter(fh, level=level, block_size=block_size)
        self.ref_names = ref_names
        text = header_text.encode()
        self._w.write(BAM_MAGIC + struct.pack("<i", len(text)) + text)
        self._w.write(struct.pack("<i", len(ref_names)))
        for nm, ln in zip(ref_names, ref_lens):
            nb = nm.encode() + b"\x00"
            self._w.write(struct.pack("<i", len(nb)) + nb +
                          struct.pack("<i", ln))

    def write_record(
        self,
        tid: int,
        pos: int,
        cigar: list[tuple[int, int]],
        mapq: int = 60,
        flag: int = 0,
        name: str = "r",
        seq: str | None = None,
        mate_tid: int = -1,
        mate_pos: int = -1,
        tlen: int = 0,
    ) -> None:
        if seq is None:
            qlen = sum(ln for ln, op in cigar if _CONSUMES_QUERY[op])
            seq = "A" * qlen
        l_seq = len(seq)
        nb = name.encode() + b"\x00"
        end = pos + sum(ln for ln, op in cigar if _CONSUMES_REF[op])
        body = struct.pack(
            "<iiBBHHHiiii", tid, pos, len(nb), mapq,
            reg2bin(pos, max(end, pos + 1)), len(cigar), flag, l_seq,
            mate_tid, mate_pos, tlen,
        )
        body += nb
        for ln, op in cigar:
            body += struct.pack("<I", (ln << 4) | op)
        packed = bytearray()
        for i in range(0, l_seq, 2):
            hi = _NT16_CODE.get(seq[i], 15) << 4
            lo = _NT16_CODE.get(seq[i + 1], 15) if i + 1 < l_seq else 0
            packed.append(hi | lo)
        body += bytes(packed) + b"\xff" * l_seq
        self._w.write(struct.pack("<i", len(body)) + body)

    def write_encoded(self, records: bytes) -> None:
        """Append records that are already BAM-encoded (each with its
        block_size prefix) — bulk fixtures build them as one array."""
        self._w.write(records)

    def close(self) -> None:
        self._w.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def filter_clip_segments(cols, start: int, end: int, min_mapq: int,
                         flag_mask: int):
    """The ONE definition of decoded-columns → (seg_start, seg_end)
    filtered/clipped segment arrays — the host reference semantics of
    the C streaming extractor (``bam_segments_stream``). Shared by
    BamFile.read_segments' fallback and the cohort device engine's
    CRAM branch so the container types cannot desynchronize."""
    n = len(cols.seg_start)
    if not n:
        z = np.empty(0, np.int32)
        return z, z.copy()
    ok = (cols.mapq >= min_mapq) & ((cols.flag & flag_mask) == 0)
    kp = ok[cols.seg_read]
    s = np.clip(cols.seg_start[kp], start, end).astype(np.int32)
    e = np.clip(cols.seg_end[kp], start, end).astype(np.int32)
    nz = e > s
    return s[nz], e[nz]


def parse_cigar(s: str) -> list[tuple[int, int]]:
    """'100M' → [(100, 0)]; convenience for tests."""
    out = []
    num = ""
    for ch in s:
        if ch.isdigit():
            num += ch
        else:
            out.append((int(num), CIGAR_OPS.index(ch)))
            num = ""
    return out
