"""BAM + BAI writer of the benchmark's fixtures: NumPy and zlib only.

Written from the SAM/BAM specification (sections 4.1, 4.2, 5.2), not
through ``goleft_tpu/io``: the inputs of a measurement must not change
when the program's own writer does. Every record has the same size, so a
BGZF block holds a whole number of records and each record's virtual
offset follows from its ordinal.
"""

from __future__ import annotations

import concurrent.futures as cf
import struct
import zlib

import numpy as np

READ_LEN = 150
RECORD = np.dtype([
    ("block_size", "<i4"), ("tid", "<i4"), ("pos", "<i4"),
    ("l_name", "u1"), ("mapq", "u1"), ("bin", "<u2"),
    ("n_cigar", "<u2"), ("flag", "<u2"), ("l_seq", "<i4"),
    ("mtid", "<i4"), ("mpos", "<i4"), ("tlen", "<i4"),
    ("name", "S2"), ("cigar", "<u4"),
    ("seq", "u1", ((READ_LEN + 1) // 2,)), ("qual", "u1", (READ_LEN,)),
])
RECORDS_PER_BLOCK = 240  # 240 * 271 B = 65,040 B, under BGZF's 65,280
BGZF_EOF = bytes.fromhex(
    "1f8b08040000000000ff0600424302001b0003000000000000000000")
LINEAR_SHIFT = 14


def reg2bin(beg: np.ndarray, end: np.ndarray) -> np.ndarray:
    """Spec 5.3, vectorized: the smallest bin holding [beg, end)."""
    end = end - 1
    out = np.zeros(len(beg), np.int64)
    done = np.zeros(len(beg), bool)
    for shift, first in ((14, 4681), (17, 585), (20, 73), (23, 9), (26, 1)):
        hit = ~done & ((beg >> shift) == (end >> shift))
        out[hit] = first + (beg[hit] >> shift)
        done |= hit
    return out


def bgzf_block(raw: bytes, level: int = 1) -> bytes:
    c = zlib.compressobj(level, zlib.DEFLATED, -15)
    body = c.compress(raw) + c.flush()
    size = len(body) + 26
    if size > 65536:
        raise ValueError("BGZF block does not fit 64 KiB compressed")
    return (b"\x1f\x8b\x08\x04\x00\x00\x00\x00\x00\xff\x06\x00BC\x02\x00"
            + struct.pack("<H", size - 1) + body
            + struct.pack("<II", zlib.crc32(raw), len(raw)))


def bam_header(text: str, ref_name: str, ref_len: int) -> bytes:
    t = text.encode()
    n = ref_name.encode() + b"\0"
    return (b"BAM\x01" + struct.pack("<i", len(t)) + t
            + struct.pack("<i", 1) + struct.pack("<i", len(n)) + n
            + struct.pack("<i", ref_len))


def encode_records(pos, mapq, flag, seq, qual) -> np.ndarray:
    """150M single-contig records (tid 0) as one structured array."""
    a = np.zeros(len(pos), RECORD)
    a["block_size"] = RECORD.itemsize - 4
    a["pos"] = pos
    a["l_name"] = 2
    a["mapq"] = mapq
    a["bin"] = reg2bin(pos, pos + READ_LEN)
    a["n_cigar"] = 1
    a["flag"] = flag
    a["l_seq"] = READ_LEN
    a["mtid"] = a["mpos"] = -1
    a["name"] = b"r"
    a["cigar"] = READ_LEN << 4  # 150M
    a["seq"] = seq
    a["qual"] = qual
    return a


class BamBaiWriter:
    """Streams record arrays into ``path`` and builds ``path + '.bai'``.

    Blocks are deflated on ``threads`` threads (zlib releases the GIL) and
    written in order, so the bytes do not depend on the thread count.
    """

    def __init__(self, path: str, header_text: str, ref_name: str,
                 ref_len: int, threads: int = 8, level: int = 1):
        self.path = path
        self.level = level
        self._fh = open(path, "wb")
        self._pool = cf.ThreadPoolExecutor(max_workers=threads)
        head = bgzf_block(bam_header(header_text, ref_name, ref_len), level)
        self._fh.write(head)
        self._coffset = len(head)
        self._block_offsets: list[int] = []  # of record blocks, in order
        self._pos: list[np.ndarray] = []
        self._short = False
        self._pending: list = []

    def write(self, records: np.ndarray) -> None:
        """Append records; all but the last call must pass a multiple of
        RECORDS_PER_BLOCK, so that blocks stay whole. The batch deflates
        while the caller prepares the next one."""
        if self._short:
            raise ValueError("a short batch must be the last one")
        self._short = len(records) % RECORDS_PER_BLOCK != 0
        self._pos.append(records["pos"].astype(np.int64))
        raw = records.tobytes()
        step = RECORDS_PER_BLOCK * RECORD.itemsize
        self._drain()
        self._pending = [
            self._pool.submit(bgzf_block, raw[i:i + step], self.level)
            for i in range(0, len(raw), step)]

    def _drain(self) -> None:
        for fut in self._pending:
            blk = fut.result()
            self._block_offsets.append(self._coffset)
            self._fh.write(blk)
            self._coffset += len(blk)
        self._pending = []

    def close(self) -> None:
        self._drain()
        self._pool.shutdown()
        end_coffset = self._coffset
        self._fh.write(BGZF_EOF)
        self._fh.close()
        pos = np.concatenate(self._pos)
        with open(self.path + ".bai", "wb") as fh:
            fh.write(build_bai(pos, np.asarray(self._block_offsets,
                                               np.int64),
                               end_coffset))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def build_bai(pos: np.ndarray, block_offsets: np.ndarray,
              end_coffset: int) -> bytes:
    """The .bai of one contig of sorted 150M records laid out
    RECORDS_PER_BLOCK to a block (at least one): bins with their chunks (a chunk is a run
    of consecutive records of one bin) and the 16 kb linear index."""
    n = len(pos)
    idx = np.arange(n, dtype=np.int64)
    blk, within = idx // RECORDS_PER_BLOCK, idx % RECORDS_PER_BLOCK
    voff = (block_offsets[blk] << 16) | (within * RECORD.itemsize)
    # where record i ends: the next record's offset, or the file's end
    vend = np.concatenate((voff[1:], [end_coffset << 16]))
    bins = reg2bin(pos, pos + READ_LEN)
    run_start = np.flatnonzero(np.concatenate(([True],
                                               bins[1:] != bins[:-1])))
    run_end = np.concatenate((run_start[1:], [n])) - 1
    run_bin = bins[run_start]
    order = np.argsort(run_bin, kind="stable")
    run_bin, beg, end = run_bin[order], voff[run_start][order], \
        vend[run_end][order]
    out = bytearray(b"BAI\x01" + struct.pack("<i", 1))
    uniq, first, count = np.unique(run_bin, return_index=True,
                                   return_counts=True)
    out += struct.pack("<i", len(uniq))
    for b, f, c in zip(uniq, first, count):
        out += struct.pack("<Ii", int(b), int(c))
        out += np.stack((beg[f:f + c], end[f:f + c]),
                        axis=1).astype("<u8").tobytes()
    # linear index: the first record (in file order) overlapping each
    # 16 kb window; records share one length, so sorted starts are
    # sorted ends and that record is the first whose end passes the
    # window's start. A window nothing overlaps takes its predecessor's.
    n_intv = (int(pos[-1]) + READ_LEN - 1 >> LINEAR_SHIFT) + 1
    lo = np.arange(n_intv, dtype=np.int64) << LINEAR_SHIFT
    first_rec = np.minimum(
        np.searchsorted(pos + READ_LEN, lo, side="right"), n - 1)
    ioff = voff[first_rec]
    for w in np.flatnonzero(pos[first_rec] >= lo + (1 << LINEAR_SHIFT)):
        ioff[w] = ioff[w - 1] if w else 0
    out += struct.pack("<i", n_intv) + ioff.astype("<u8").tobytes()
    out += struct.pack("<Q", 0)  # reads without coordinates
    return bytes(out)
