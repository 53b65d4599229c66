"""ctypes loader for the C++ host-IO fast path (csrc/fastio.cpp).

Builds libgoleftio.so lazily with g++ on first use and falls back to the
pure-Python codecs on any failure (missing toolchain, build error). The
native calls release the GIL, so the shard-decode thread pool scales.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

from ..obs.logging import get_logger
from ..obs.metrics import get_registry

log = get_logger("native")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False


def _root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _build(src: str, out: str) -> bool:
    try:
        os.makedirs(os.path.dirname(out), exist_ok=True)
        base = ["g++", "-O3", "-march=native", "-shared", "-fPIC", src]
        # libdeflate inflates BGZF 2-3x faster than zlib; fall back to a
        # zlib-only build where it isn't installed
        for extra in (["-lz", "-ldeflate"], ["-DNO_LIBDEFLATE", "-lz"]):
            r = subprocess.run(
                base + extra + ["-o", out],
                capture_output=True, text=True, timeout=120,
            )
            if r.returncode == 0:
                return True
        log.warning("native build failed: %s", r.stderr[-500:])
        return False
    except Exception as e:  # noqa: BLE001
        log.warning("native build unavailable: %s", e)
        return False


def get_lib() -> ctypes.CDLL | None:
    """The loaded native library, or None (pure-Python fallback)."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is None and not _tried:
            try:
                _lib = _load_lib()
            finally:
                # only now: a thread that arrives while another builds
                # waits on the lock, and does not take the fallback
                _tried = True
        return _lib


def _load_lib() -> ctypes.CDLL | None:
    if os.environ.get("GOLEFT_TPU_NO_NATIVE"):
        return None
    src = os.path.join(_root(), "csrc", "fastio.cpp")
    out = os.environ.get("GOLEFT_TPU_ASAN_LIB") or os.path.join(
        _root(), "build", "libgoleftio.so"
    )
    if not os.path.exists(out) or (
        os.path.exists(src)
        and os.path.getmtime(src) > os.path.getmtime(out)
    ):
        if not os.path.exists(src) or not _build(src, out):
            return None
    try:
        lib = ctypes.CDLL(out)
    except OSError as e:
        log.warning("native load failed: %s", e)
        return None
    try:
        _register_restypes(lib)
    except AttributeError as e:
        # stale prebuilt library missing a newer symbol: honor the
        # module contract (pure-Python fallback on ANY failure)
        log.warning("native library is stale (%s) — rebuild "
                    "build/libgoleftio.so; using Python codecs", e)
        return None
    return lib


def _register_restypes(lib) -> None:
        lib.bgzf_scan.restype = ctypes.c_long
        lib.bgzf_inflate_all.restype = ctypes.c_long
        lib.bgzf_inflate_range.restype = ctypes.c_long
        lib.bam_decode.restype = ctypes.c_long
        lib.bam_window_reduce.restype = ctypes.c_long
        lib.bam_window_reduce_stream.restype = ctypes.c_long
        lib.bam_window_acc_stream.restype = ctypes.c_long
        lib.bam_segments_stream.restype = ctypes.c_long
        lib.bam_segments_take.restype = None
        lib.bgzf_stream_inflate_only.restype = ctypes.c_long
        lib.bgzf_deflate_block.restype = ctypes.c_long
        lib.bgzf_deflate_members.restype = ctypes.c_long
        lib.rans4x8_decode.restype = ctypes.c_long
        lib.ransnx16_decode0.restype = ctypes.c_long
        lib.ransnx16_decode1.restype = ctypes.c_long
        lib.arith_decode_body.restype = ctypes.c_long
        lib.fqzcomp_decode.restype = ctypes.c_long
        lib.tok3_assemble.restype = ctypes.c_long
        lib.format_matrix_rows.restype = ctypes.c_long
        lib.format_depth_rows.restype = ctypes.c_long
        lib.format_class_rows.restype = ctypes.c_long
        lib.bai_scan.restype = ctypes.c_long
        lib.bai_tile_sizes.restype = ctypes.c_long
        lib.format_xy_json.restype = ctypes.c_long
        lib.format_float32_rows.restype = ctypes.c_long
        lib.format_fixed2_rows.restype = ctypes.c_long


def _as_u8(data) -> np.ndarray:
    """bytes / mmap / ndarray → zero-copy uint8 view."""
    if isinstance(data, np.ndarray):
        return data
    return np.frombuffer(data, dtype=np.uint8)


def _ptr(arr: np.ndarray, t=ctypes.c_ubyte):
    return arr.ctypes.data_as(ctypes.POINTER(t))


def bgzf_scan(data):
    """(coffsets, uoffsets, total_uncompressed) via the native scanner;
    None when native is unavailable. Accepts bytes or mmap-backed
    arrays."""
    lib = get_lib()
    if lib is None:
        return None
    buf = _as_u8(data)
    max_blocks = max(len(buf) // 28 + 2, 16)
    co = np.zeros(max_blocks, dtype=np.int64)
    uo = np.zeros(max_blocks, dtype=np.int64)
    total = ctypes.c_long(0)
    n = lib.bgzf_scan(
        _ptr(buf), ctypes.c_long(len(buf)),
        _ptr(co, ctypes.c_long), _ptr(uo, ctypes.c_long),
        ctypes.c_long(max_blocks), ctypes.byref(total),
    )
    if n < 0:
        raise ValueError(f"bgzf scan: {_err(n)}")
    return co[:n], uo[:n], int(total.value)


def bgzf_inflate(data, total: int) -> np.ndarray:
    lib = get_lib()
    if lib is None:
        return None
    buf = _as_u8(data)
    out = np.empty(total, dtype=np.uint8)
    r = lib.bgzf_inflate_all(
        _ptr(buf), ctypes.c_long(len(buf)), _ptr(out),
        ctypes.c_long(total),
    )
    if r < 0:
        raise ValueError(f"bgzf inflate: {_err(r)}")
    return out[:r]


def bgzf_inflate_range(data, c_begin: int, c_end: int,
                       cap: int, out: np.ndarray | None = None
                       ) -> np.ndarray:
    """Inflate only blocks with compressed offset in [c_begin, c_end).

    ``out`` lets hot callers reuse a thread-local buffer (the returned
    array is a view into it — consume before the next call)."""
    lib = get_lib()
    if lib is None:
        return None
    buf = _as_u8(data)
    if out is None or len(out) < cap:
        out = np.empty(cap, dtype=np.uint8)
    r = lib.bgzf_inflate_range(
        _ptr(buf), ctypes.c_long(len(buf)), ctypes.c_long(c_begin),
        ctypes.c_long(c_end), _ptr(out), ctypes.c_long(cap),
    )
    if r < 0:
        raise ValueError(
            f"bgzf: {_err(r)} (blocks at {c_begin}..{c_end})"
        )
    return out[:r]


_ERRS = {
    -1: "bad gzip magic",
    -2: "missing BC subfield (not BGZF)",
    -3: "output capacity exceeded",
    -4: "zlib init failed",
    -5: "corrupt deflate stream",
    -6: "truncated block",
    -7: "CRC mismatch (corrupt block)",
    -8: "corrupt block header geometry",
    -10: "bad gzip magic",
}

# bam_decode has its own error space (fastio.cpp bam_decode header)
_BAM_ERRS = {
    -1: "truncated record stream",
    -2: "capacity exceeded",
    -9: "malformed BAM record geometry",
}


def _err(code) -> str:
    return _ERRS.get(int(code), f"error {code}")


def _bam_err(code) -> str:
    return _BAM_ERRS.get(int(code), f"error {code}")


def _stream_err(code) -> str:
    """Streaming fused calls mix both error spaces: -1/-9 come from the
    record walk, everything else from the BGZF layer (so -2 is 'missing
    BC subfield' here, NOT bam_decode's 'capacity exceeded')."""
    code = int(code)
    if code in (-1, -9):
        return _BAM_ERRS[code]
    return _err(code)


def bam_decode(body: np.ndarray, offset: int, target_tid: int,
               start: int, end: int, cap_reads: int | None = None):
    """Decode records into columnar arrays; returns a dict of arrays plus
    consumed byte count, or None when native is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    remaining = len(body) - offset
    if cap_reads is None:
        cap_reads = max(remaining // 40 + 16, 1024)
    while True:
        cap_segs = cap_reads * 4
        a = {
            "tid": np.empty(cap_reads, np.int32),
            "pos": np.empty(cap_reads, np.int32),
            "end": np.empty(cap_reads, np.int32),
            "mapq": np.empty(cap_reads, np.uint8),
            "flag": np.empty(cap_reads, np.uint16),
            "tlen": np.empty(cap_reads, np.int32),
            "read_len": np.empty(cap_reads, np.int32),
            "mate_pos": np.empty(cap_reads, np.int32),
            "single_m": np.empty(cap_reads, np.uint8),
            "seg_start": np.empty(cap_segs, np.int32),
            "seg_end": np.empty(cap_segs, np.int32),
            "seg_read": np.empty(cap_segs, np.int32),
        }
        n_segs = ctypes.c_long(0)
        consumed = ctypes.c_long(0)
        done = ctypes.c_int32(0)

        def ptr(x, t):
            return a[x].ctypes.data_as(ctypes.POINTER(t))

        nr = lib.bam_decode(
            _ptr(body), ctypes.c_long(len(body)), ctypes.c_long(offset),
            ctypes.c_int(target_tid), ctypes.c_int(start),
            ctypes.c_int(end), ctypes.c_long(cap_reads),
            ctypes.c_long(cap_segs),
            ptr("tid", ctypes.c_int32), ptr("pos", ctypes.c_int32),
            ptr("end", ctypes.c_int32), ptr("mapq", ctypes.c_uint8),
            ptr("flag", ctypes.c_uint16), ptr("tlen", ctypes.c_int32),
            ptr("read_len", ctypes.c_int32),
            ptr("mate_pos", ctypes.c_int32),
            ptr("single_m", ctypes.c_uint8),
            ptr("seg_start", ctypes.c_int32),
            ptr("seg_end", ctypes.c_int32),
            ptr("seg_read", ctypes.c_int32),
            ctypes.byref(n_segs), ctypes.byref(consumed),
            ctypes.byref(done),
        )
        if nr == -2:
            cap_reads *= 2
            continue
        if nr < 0:
            raise ValueError(f"bam_decode: {_bam_err(nr)}")
        ns = int(n_segs.value)
        out = {k: v[: (ns if k.startswith("seg_") else nr)]
               for k, v in a.items()}
        out["n_reads"] = int(nr)
        out["consumed"] = int(consumed.value)
        out["done"] = bool(done.value)
        return out


def rans4x8_decode(data, pos: int, order: int,
                   out_len: int) -> bytes | None:
    """CRAM 4x8 rANS decode (orders 0/1) in C; None when native is
    unavailable (callers fall back to the pure-Python decoders).
    Raises ValueError on malformed streams / missing o1 contexts."""
    lib = get_lib()
    if lib is None:
        return None
    buf = _as_u8(data)
    out = np.empty(out_len, dtype=np.uint8)
    r = lib.rans4x8_decode(
        _ptr(buf), ctypes.c_long(len(buf)), ctypes.c_long(pos),
        ctypes.c_int(order), _ptr(out), ctypes.c_long(out_len),
    )
    if r == -9:
        raise ValueError("cram: rans missing order-1 context")
    if r < 0:
        raise ValueError("cram: malformed rans stream")
    return out.tobytes()


def ransnx16_decode0(data, pos: int, out_len: int,
                     n_states: int) -> bytes | None:
    """rANS-Nx16 order-0 decode in C; None when native is unavailable
    OR the stream needs the lenient pure-Python path (which also owns
    every error message) — callers always fall back on None."""
    lib = get_lib()
    if lib is None:
        return None
    buf = _as_u8(data)
    out = np.empty(out_len, dtype=np.uint8)
    r = lib.ransnx16_decode0(
        _ptr(buf), ctypes.c_long(len(buf)), ctypes.c_long(pos),
        _ptr(out), ctypes.c_long(out_len), ctypes.c_int(n_states),
    )
    return out.tobytes() if r == 0 else None


def arith_decode_body(data, pos: int, out_len: int, order: int,
                      rle: bool) -> bytes | None:
    """Adaptive-arithmetic coded-body decode in C (order 0/1, with or
    without the integrated RLE run models); None → fall back to the
    pure-Python decoder, which owns every error message."""
    lib = get_lib()
    if lib is None:
        return None
    buf = _as_u8(data)
    out = np.empty(out_len, dtype=np.uint8)
    r = lib.arith_decode_body(
        _ptr(buf), ctypes.c_long(len(buf)), ctypes.c_long(pos),
        _ptr(out), ctypes.c_long(out_len),
        ctypes.c_int(1 if order else 0), ctypes.c_int(1 if rle else 0),
    )
    return out.tobytes() if r == 0 else None


def fqzcomp_decode(data, out_len: int) -> bytes | None:
    """fqzcomp full-stream decode in C; None → fall back to the
    pure-Python decoder, which owns every error message (including
    the zero-length case, whose header checks C skips)."""
    lib = get_lib()
    if lib is None or out_len == 0:
        return None
    buf = _as_u8(data)
    out = np.empty(out_len, dtype=np.uint8)
    r = lib.fqzcomp_decode(
        _ptr(buf), ctypes.c_long(len(buf)), _ptr(out),
        ctypes.c_long(out_len),
    )
    return out.tobytes() if r == 0 else None


def tok3_assemble(streams: dict, n_names: int, sep: int,
                  out_len: int) -> bytes | None:
    """Name assembly over already-decompressed tok3 streams in C;
    ``streams`` maps (position, field) → raw bytes. None → fall back
    to the pure-Python assembly, which owns every error message."""
    lib = get_lib()
    if lib is None:
        return None
    # n_names/out_len come from attacker-controlled varints; absurd
    # values must fall back to the Python path's typed errors rather
    # than raise OverflowError from ctypes or MemoryError from the
    # allocation (every name contributes at least its separator, so
    # valid inputs satisfy n_names <= out_len)
    if not 0 <= n_names <= out_len or out_len > (1 << 40):
        return None
    offs = np.full(256 * 13, -1, dtype=np.int64)
    lens = np.zeros(256 * 13, dtype=np.int64)
    parts = []
    off = 0
    for (p, f), raw in streams.items():
        if not 0 <= p < 256 or not 0 <= f < 13:
            return None
        slot = p * 13 + f
        offs[slot] = off
        lens[slot] = len(raw)
        parts.append(raw)
        off += len(raw)
    blob = np.frombuffer(b"".join(parts), dtype=np.uint8) if parts \
        else np.empty(0, dtype=np.uint8)
    try:
        out = np.empty(out_len, dtype=np.uint8)
    except MemoryError:
        # a huge declared size the host cannot hold: the Python
        # assembly fails with its own typed error long before
        # allocating this much
        return None
    r = lib.tok3_assemble(
        _ptr(blob), offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ctypes.c_long(n_names), ctypes.c_ubyte(sep),
        _ptr(out), ctypes.c_long(out_len),
    )
    return out.tobytes() if r == 0 else None


def ransnx16_decode1(data, pos: int, table, table_pos: int,
                     table_inline: bool, shift: int, out_len: int,
                     n_states: int) -> bytes | None:
    """rANS-Nx16 order-1 decode in C (table either inline ahead of the
    states or in a separately decompressed buffer); None → fall back
    to the pure-Python decoder."""
    lib = get_lib()
    if lib is None:
        return None
    buf = _as_u8(data)
    tbl = buf if table_inline else _as_u8(table)
    out = np.empty(out_len, dtype=np.uint8)
    r = lib.ransnx16_decode1(
        _ptr(buf), ctypes.c_long(len(buf)), ctypes.c_long(pos),
        _ptr(tbl), ctypes.c_long(len(tbl)), ctypes.c_long(table_pos),
        ctypes.c_int(1 if table_inline else 0), ctypes.c_int(shift),
        _ptr(out), ctypes.c_long(out_len), ctypes.c_int(n_states),
    )
    return out.tobytes() if r == 0 else None


def bgzf_deflate_block(chunk: bytes, level: int) -> bytes | None:
    """One complete BGZF member (header + deflate + crc/isize) for
    ``chunk`` (≤ 65280 bytes) via libdeflate; None when native is
    unavailable (callers fall back to zlib)."""
    lib = get_lib()
    if lib is None:
        return None
    buf = _as_u8(chunk)
    # worst case up front: deflate expansion is bounded well under 2x
    # (~130KB max for a full 65280-byte block), so one call suffices
    cap = len(buf) * 2 + 4096
    out = np.empty(cap, dtype=np.uint8)
    n = lib.bgzf_deflate_block(
        _ptr(buf), ctypes.c_long(len(buf)), ctypes.c_int(level),
        _ptr(out), ctypes.c_long(cap),
    )
    if n < 0:
        return None  # fall back to the zlib path
    return out[:n].tobytes()


def bgzf_deflate_members(out: np.ndarray, text: np.ndarray,
                         level: int) -> int | None:
    """``text`` (uint8) as whole BGZF members of 65280 bytes (the last
    may be shorter) into the uint8 scratch ``out``, which needs
    ``bgzf_members_bound(len(text))`` bytes; the bytes written. None
    when native is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    n = lib.bgzf_deflate_members(
        _ptr(text), ctypes.c_long(len(text)), ctypes.c_int(level),
        _ptr(out), ctypes.c_long(len(out)),
    )
    if n < 0:
        raise ValueError(f"bgzf_deflate_members: error {n}")
    return n


def bgzf_members_bound(text_bytes: int) -> int:
    """Bytes the members of ``text_bytes`` of text can take: deflate
    grows incompressible input by a few bytes in ten thousand, and a
    member adds its 26."""
    return text_bytes + (text_bytes // 65280 + 1) * 256


def bgzf_stream_inflate_only(comp, check_crc: bool = True):
    """Total uncompressed bytes after streaming the whole BGZF file
    through the product ring driver with a no-op walk — isolates the
    inflate(+CRC) floor of the fused decode stage (a probe: no command
    calls it). None when native is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    buf = _as_u8(comp)
    total = ctypes.c_int64(0)
    r = lib.bgzf_stream_inflate_only(
        _ptr(buf), ctypes.c_long(len(buf)), ctypes.c_long(0),
        ctypes.c_long(0), ctypes.c_int(1 if check_crc else 0),
        ctypes.byref(total),
    )
    if r < 0:
        raise ValueError(f"bgzf stream inflate: {_stream_err(r)}")
    return int(total.value)


def _bai_max_ref(buf: np.ndarray) -> int:
    """The most references the C walker may accept for ``buf``: what the
    header claims, bound by what the bytes could possibly hold (every
    reference costs >= 8 bytes), so a corrupt header cannot demand a
    multi-GB allocation — genuinely oversized counts then fail in C with
    -3 (over max_ref)."""
    if len(buf) < 8:
        raise ValueError("bai: truncated or corrupt index (-2)")
    n_ref = max(int(np.frombuffer(buf[4:8], "<i4")[0]), 0)
    return min(n_ref, len(buf) // 8 + 1)


def _bai_raise(code: int) -> None:
    """The C walker's negative return, as the readers' typed error."""
    if code == -1:
        raise ValueError("not a BAI file (bad magic)")
    if code == -3:
        # same diagnostic as the pure-Python fallback's byte-derived
        # n_ref bound: the header claims more references than the
        # bytes could hold
        raise ValueError("bai: implausible n_ref (over what the bytes "
                         "can hold)")
    if code == -4:
        raise ValueError("bai: negative voffset delta in linear index")
    raise ValueError(f"bai: truncated or corrupt index ({code})")


def bai_scan(data):
    """Single-pass .bai structure scan → dict of per-ref arrays
    (bins_start, bins_end, n_intv, intv_off, mapped, unmapped), or None
    without native. Negative returns raise with a specific message."""
    lib = get_lib()
    if lib is None:
        return None
    buf = _as_u8(data)
    max_ref = _bai_max_ref(buf)  # exact: the header carries n_ref up front
    arrs = {k: np.empty(max_ref, np.int64)
            for k in ("bins_start", "bins_end", "n_intv", "intv_off",
                      "mapped", "unmapped")}
    n = lib.bai_scan(
        _ptr(buf), ctypes.c_long(len(buf)), ctypes.c_long(max_ref),
        *(_ptr(arrs[k], ctypes.c_int64)
          for k in ("bins_start", "bins_end", "n_intv", "intv_off",
                    "mapped", "unmapped")),
    )
    if n < 0:
        _bai_raise(n)
    return {k: v[:n] for k, v in arrs.items()}


def bai_tile_sizes_scratch(n_bytes: int) -> int:
    """int64 elements of scratch with which ``bai_tile_sizes`` cannot
    be short on a .bai of ``n_bytes``: a tile costs the file 8 bytes,
    and the call keeps the sizes and what its median looks into."""
    return 2 * (n_bytes // 8)


def bai_tile_sizes(buf: np.ndarray, scratch: np.ndarray):
    """What ``indexcov`` needs of the .bai whose bytes are ``buf``
    (uint8), in one GIL-free pass with no allocation but the result:
    (sizes, offsets, mapped, unmapped, median). ``sizes`` is one fresh
    int64 array of every reference's tile sizes, reference r's being
    ``sizes[offsets[r]:offsets[r + 1]]``; ``mapped`` / ``unmapped`` the
    per-reference pseudo-bin counts (-1 without one); ``median`` the
    scaling median, ``ops.indexcov_ops.median_size_per_tile``'s to the
    bit, None when the index has no tile. ``scratch`` is int64, of at
    least ``bai_tile_sizes_scratch(len(buf))``; the library has to be
    there (``get_lib()``). Corruption raises the
    readers' typed ValueError, as ``read_bai`` and ``BaiIndex.sizes``."""
    lib = get_lib()
    if bytes(buf[:4]) != b"BAI\x01":
        raise ValueError("not a BAI file (bad magic)")
    max_ref = _bai_max_ref(buf)
    if len(scratch) < bai_tile_sizes_scratch(len(buf)):
        raise ValueError("bai_tile_sizes: scratch too small")
    per_ref = np.empty((3, max_ref + 1), np.int64)
    offsets, mapped, unmapped = per_ref
    median = ctypes.c_double(0.0)
    n = lib.bai_tile_sizes(
        _ptr(buf), ctypes.c_long(len(buf)), ctypes.c_long(max_ref),
        _ptr(scratch, ctypes.c_int64), ctypes.c_long(len(scratch)),
        _ptr(offsets, ctypes.c_int64), _ptr(mapped, ctypes.c_int64),
        _ptr(unmapped, ctypes.c_int64), ctypes.byref(median))
    if n < 0:
        _bai_raise(n)
    # the walk accepted the header's n_ref, so max_ref is it
    sizes = np.empty(n, np.int64)  # the one block the caller keeps
    ctypes.memmove(sizes.ctypes.data, scratch.ctypes.data, 8 * n)
    return (sizes, offsets, mapped[:max_ref], unmapped[:max_ref],
            median.value if n else None)


def float_rows_scratch_bytes(chrom: str, n_cols: int,
                             text_bytes: int) -> int:
    """Size of a scratch in which ``format_float32_rows`` writes at
    least ``text_bytes`` of text a call (or all that is left): that
    much, one worst-case row (34 bytes a cell: "%.17g") and the
    gathered tile it keeps at the scratch's tail."""
    tile = 32 * 5 * n_cols  # ROWS_T cells a column, a float and a flag each
    return (text_bytes + len(chrom.encode()) + 2 * 21 + n_cols * 34 + 2
            + tile + 8)


def format_float32_rows(out: np.ndarray, chrom: str, starts: np.ndarray,
                        ends: np.ndarray, vals: np.ndarray,
                        valid: np.ndarray, row0: int = 0,
                        prec: int = 3) -> tuple[int, int] | None:
    """Float matrix bed rows (%.{prec}g; invalid cells → "0") from
    ``row0`` on into the uint8 scratch ``out``, for as long as a
    worst-case row fits: (bytes written, first row left); None without
    native. vals (float32) / valid (bool) are (n_cols, n_rows) and are
    read where they lie: a column slice of a wider matrix is not
    copied."""
    lib = get_lib()
    if lib is None:
        return None
    n_cols, n_rows = vals.shape
    if vals.dtype != np.float32 or (n_rows > 1
                                    and vals.strides[1] != 4):
        vals = np.ascontiguousarray(vals, dtype=np.float32)
    if valid.dtype.itemsize != 1 or (n_rows > 1
                                     and valid.strides[1] != 1):
        valid = np.ascontiguousarray(valid, dtype=np.uint8)
    starts = np.ascontiguousarray(starts, dtype=np.int64)
    ends = np.ascontiguousarray(ends, dtype=np.int64)
    cb = chrom.encode()
    next_row = ctypes.c_long(row0)
    w = lib.format_float32_rows(
        ctypes.c_char_p(cb), ctypes.c_long(len(cb)),
        _ptr(starts, ctypes.c_int64), _ptr(ends, ctypes.c_int64),
        _ptr(vals, ctypes.c_float), ctypes.c_long(vals.strides[0] // 4),
        _ptr(valid, ctypes.c_uint8), ctypes.c_long(valid.strides[0]),
        ctypes.c_long(row0), ctypes.c_long(n_rows),
        ctypes.c_long(n_cols), ctypes.c_int(prec),
        _ptr(out, ctypes.c_char), ctypes.c_long(len(out)),
        ctypes.byref(next_row),
    )
    if next_row.value == row0 < n_rows:
        raise ValueError("format_float32_rows: scratch holds no row")
    return w, next_row.value


# the widest "%.2f" of a float32 (csrc FIXED2_CELL_MAX)
FIXED2_CELL_MAX = 43


def fixed2_rows_scratch_bytes(prefix: str, labels, n_cols: int) -> int:
    """Size of the scratch ``format_fixed2_rows`` asks for: the block's
    worst case, every cell ``FIXED2_CELL_MAX`` wide."""
    return (len(labels) * (len(prefix.encode()) + 2
                           + n_cols * (FIXED2_CELL_MAX + 1))
            + sum(len(s.encode()) for s in labels))


def format_fixed2_rows(out: np.ndarray, prefix: str, labels,
                       vals: np.ndarray) -> int | None:
    """Fixed-point rows 'prefix\tlabels[r]\t%.2f...\n' into the uint8
    scratch ``out``: bytes written; None without native. vals is float32
    (n_cols, len(labels)), read where it lies whatever its strides, and
    the text is np.char.mod("%.2f", vals.T)'s byte for byte (a NaN of
    either sign is "nan"). It is formatted without printf, so there is
    no numeric locale to pin. A scratch under
    ``fixed2_rows_scratch_bytes`` raises, with nothing written."""
    lib = get_lib()
    if lib is None:
        return None
    if vals.dtype != np.float32 or vals.ndim != 2:
        # a float64 cast to float32 would round twice
        raise TypeError("format_fixed2_rows: vals must be 2-d float32")
    n_cols, n_rows = vals.shape
    if n_rows != len(labels):
        raise ValueError("format_fixed2_rows: a label a row")
    if vals.strides[0] % 4 or vals.strides[1] % 4:
        vals = np.ascontiguousarray(vals)
    pb = prefix.encode()
    lb = [s.encode() for s in labels]
    label_off = np.zeros(n_rows + 1, dtype=np.int32)
    np.cumsum([len(b) for b in lb], out=label_off[1:])
    w = lib.format_fixed2_rows(
        ctypes.c_char_p(pb), ctypes.c_long(len(pb)),
        ctypes.c_char_p(b"".join(lb)), _ptr(label_off, ctypes.c_int32),
        _ptr(vals, ctypes.c_float), ctypes.c_long(vals.strides[0] // 4),
        ctypes.c_long(vals.strides[1] // 4), ctypes.c_long(n_rows),
        ctypes.c_long(n_cols), _ptr(out, ctypes.c_char),
        ctypes.c_long(len(out)),
    )
    if w < 0:
        raise ValueError("format_fixed2_rows: scratch too small")
    return w


def format_xy_json(xs: np.ndarray, ys: np.ndarray, xprec: int = 10,
                   yprec: int = 5) -> bytes | None:
    """'[{"x":..,"y":..},...]' JSON bytes; None without native."""
    lib = get_lib()
    if lib is None:
        return None
    xs = np.ascontiguousarray(xs, dtype=np.float64)
    ys = np.ascontiguousarray(ys, dtype=np.float64)
    if len(xs) != len(ys):
        raise ValueError("format_xy_json: x/y length mismatch")
    n = len(xs)
    cap = n * 80 + 16
    out = np.empty(cap, dtype=np.uint8)
    w = lib.format_xy_json(
        _ptr(xs, ctypes.c_double), _ptr(ys, ctypes.c_double),
        ctypes.c_long(n), ctypes.c_int(xprec), ctypes.c_int(yprec),
        _ptr(out, ctypes.c_char), ctypes.c_long(cap),
    )
    if w < 0:
        raise ValueError("format_xy_json: capacity exceeded")
    return out[:w].tobytes()


def format_matrix_rows(chrom: str, starts: np.ndarray, ends: np.ndarray,
                       vals: np.ndarray) -> bytes | None:
    """'chrom\\tstart\\tend\\tv...' rows as one bytes blob; None without
    native. vals is (n_cols, n_rows) — cohortdepth's (samples, windows)
    layout, consumed column-major so no transpose happens anywhere."""
    lib = get_lib()
    if lib is None:
        return None
    n_cols, n_rows = vals.shape
    starts = np.ascontiguousarray(starts, dtype=np.int64)
    ends = np.ascontiguousarray(ends, dtype=np.int64)
    vals = np.ascontiguousarray(vals, dtype=np.int64)
    cb = chrom.encode()
    cap = n_rows * (len(cb) + 2 * 21 + n_cols * 21 + 2) + 16
    out = np.empty(cap, dtype=np.uint8)
    w = lib.format_matrix_rows(
        ctypes.c_char_p(cb), ctypes.c_long(len(cb)),
        _ptr(starts, ctypes.c_int64), _ptr(ends, ctypes.c_int64),
        _ptr(vals, ctypes.c_int64), ctypes.c_long(n_rows),
        ctypes.c_long(n_cols), _ptr(out, ctypes.c_char),
        ctypes.c_long(cap),
    )
    if w < 0:
        raise ValueError("format_matrix_rows: capacity exceeded")
    return out[:w].tobytes()


def format_depth_rows(chrom: str, starts: np.ndarray, ends: np.ndarray,
                      means: np.ndarray) -> bytes | None:
    """'chrom\\tstart\\tend\\t%.4g' rows; None without native."""
    lib = get_lib()
    if lib is None:
        return None
    starts = np.ascontiguousarray(starts, dtype=np.int64)
    ends = np.ascontiguousarray(ends, dtype=np.int64)
    means = np.ascontiguousarray(means, dtype=np.float64)
    cb = chrom.encode()
    n = len(starts)
    cap = n * (len(cb) + 2 * 21 + 44) + 16
    out = np.empty(cap, dtype=np.uint8)
    w = lib.format_depth_rows(
        ctypes.c_char_p(cb), ctypes.c_long(len(cb)),
        _ptr(starts, ctypes.c_int64), _ptr(ends, ctypes.c_int64),
        _ptr(means, ctypes.c_double), ctypes.c_long(n),
        _ptr(out, ctypes.c_char), ctypes.c_long(cap),
    )
    if w < 0:
        raise ValueError("format_depth_rows: capacity exceeded")
    return out[:w].tobytes()


def format_class_rows(chrom: str, starts: np.ndarray, ends: np.ndarray,
                      cls: np.ndarray) -> bytes | None:
    """'chrom\\tstart\\tend\\tCLASS_NAME' rows; None without native."""
    lib = get_lib()
    if lib is None:
        return None
    starts = np.ascontiguousarray(starts, dtype=np.int64)
    ends = np.ascontiguousarray(ends, dtype=np.int64)
    cls = np.ascontiguousarray(cls, dtype=np.uint8)
    cb = chrom.encode()
    n = len(starts)
    cap = n * (len(cb) + 2 * 21 + 24) + 16
    out = np.empty(cap, dtype=np.uint8)
    w = lib.format_class_rows(
        ctypes.c_char_p(cb), ctypes.c_long(len(cb)),
        _ptr(starts, ctypes.c_int64), _ptr(ends, ctypes.c_int64),
        _ptr(cls, ctypes.c_uint8), ctypes.c_long(n),
        _ptr(out, ctypes.c_char), ctypes.c_long(cap),
    )
    if w == -2:
        raise ValueError("format_class_rows: class id out of range")
    if w < 0:
        raise ValueError("format_class_rows: capacity exceeded")
    return out[:w].tobytes()


def bam_window_reduce(body: np.ndarray, offset: int, target_tid: int,
                      start: int, end: int, w0: int, length: int,
                      window: int, depth_cap: int, min_mapq: int,
                      flag_mask: int,
                      delta_scratch: np.ndarray | None = None):
    """Fused decode + per-window depth sums on the host (no per-read
    device traffic). Returns dict(wsums int64 (length//window,),
    n_kept, consumed, done) or None when native is unavailable.

    Mirrors shard_depth_pipeline semantics (clip to [start, end), capped
    cumsum, [w0, w0+length) window grid). ``end`` must be >= 0.
    """
    lib = get_lib()
    if lib is None:
        return None
    if end < 0:
        raise ValueError("bam_window_reduce requires an explicit end")
    if length % window:
        raise ValueError("length must be a multiple of window")
    n_win = length // window
    wsums = np.empty(n_win, dtype=np.int64)
    if delta_scratch is None or len(delta_scratch) < length + 1:
        # contract: the scratch arrives zeroed; the C side re-zeroes what
        # it touches, so reused buffers stay clean
        delta_scratch = np.zeros(length + 1, dtype=np.int32)
    consumed = ctypes.c_long(0)
    done = ctypes.c_int32(0)
    nk = lib.bam_window_reduce(
        _ptr(body), ctypes.c_long(len(body)), ctypes.c_long(offset),
        ctypes.c_int(target_tid), ctypes.c_int(start), ctypes.c_int(end),
        ctypes.c_long(w0), ctypes.c_long(length), ctypes.c_long(window),
        ctypes.c_int(depth_cap), ctypes.c_int(min_mapq),
        ctypes.c_int(flag_mask),
        _ptr(wsums, ctypes.c_int64),
        _ptr(delta_scratch, ctypes.c_int32),
        ctypes.byref(consumed), ctypes.byref(done),
    )
    if nk < 0:
        raise ValueError(f"bam_window_reduce: {_bam_err(nk)}")
    return {
        "wsums": wsums,
        "n_kept": int(nk),
        "consumed": int(consumed.value),
        "done": bool(done.value),
    }


def bam_window_reduce_stream(comp, c_begin: int, in_block: int,
                             target_tid: int, start: int, end: int,
                             w0: int, length: int, window: int,
                             depth_cap: int, min_mapq: int,
                             flag_mask: int,
                             delta_scratch: np.ndarray | None = None,
                             check_crc: bool | None = None):
    """Streaming fused inflate+decode+window-reduce over the raw BGZF
    bytes: each block inflates into a ~1MB recycled ring and its records
    are walked cache-hot — the shard's uncompressed body never
    materializes (the round-2 decode floor was DRAM-bound on exactly
    that round trip). Returns dict(wsums int64, n_kept) or None when
    native is unavailable.

    ``check_crc`` defaults to on; GOLEFT_TPU_SKIP_CRC=1 flips the
    default for trusted local files (the walk still bounds-checks every
    record, so corruption fails loudly, just without the crc32 pass).
    """
    lib = get_lib()
    if lib is None:
        return None
    if end < 0:
        raise ValueError("bam_window_reduce_stream requires an explicit "
                         "end")
    if length % window:
        raise ValueError("length must be a multiple of window")
    if check_crc is None:
        check_crc = not os.environ.get("GOLEFT_TPU_SKIP_CRC")
    buf = _as_u8(comp)
    n_win = length // window
    wsums = np.empty(n_win, dtype=np.int64)
    if delta_scratch is None or len(delta_scratch) < length + 1:
        delta_scratch = np.zeros(length + 1, dtype=np.int32)
    nk = lib.bam_window_reduce_stream(
        _ptr(buf), ctypes.c_long(len(buf)), ctypes.c_long(c_begin),
        ctypes.c_long(in_block),
        ctypes.c_int(target_tid), ctypes.c_int(start), ctypes.c_int(end),
        ctypes.c_long(w0), ctypes.c_long(length), ctypes.c_long(window),
        ctypes.c_int(depth_cap), ctypes.c_int(min_mapq),
        ctypes.c_int(flag_mask), ctypes.c_int(1 if check_crc else 0),
        _ptr(wsums, ctypes.c_int64),
        _ptr(delta_scratch, ctypes.c_int32),
    )
    if nk < 0:
        raise ValueError(f"bam_window_reduce_stream: {_stream_err(nk)}")
    return {"wsums": wsums, "n_kept": int(nk)}


def bam_segments_stream(comp, c_begin: int, in_block: int,
                        target_tid: int, start: int, end: int,
                        min_mapq: int, flag_mask: int,
                        check_crc: bool | None = None,
                        cap_hint: int | None = None):
    """Streaming extraction of the region's FILTERED clipped segment
    endpoints — the device segment path's host stage, sharing the
    reduce paths' walk/filters so the shipped set is identical by
    construction (csrc/fastio.cpp::bam_segments_stream). The C side
    walks the stream once, whatever the coverage, into blocks it adds
    as they fill; ``cap_hint`` is only the first block's size. Returns
    (seg_start, seg_end) int32 arrays (absolute, clipped to
    [start, end)), or None when native is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    if end < 0:
        raise ValueError("bam_segments_stream requires an explicit end")
    if check_crc is None:
        check_crc = not os.environ.get("GOLEFT_TPU_SKIP_CRC")
    buf = _as_u8(comp)
    collector = ctypes.c_void_p()
    n = ctypes.c_long(0)
    grows = ctypes.c_long(0)
    reg = get_registry()
    reg.counter("decode.segment_walks_total").inc()
    nk = lib.bam_segments_stream(
        _ptr(buf), ctypes.c_long(len(buf)),
        ctypes.c_long(c_begin), ctypes.c_long(in_block),
        ctypes.c_int(target_tid), ctypes.c_int(start),
        ctypes.c_int(end), ctypes.c_int(min_mapq),
        ctypes.c_int(flag_mask),
        ctypes.c_int(1 if check_crc else 0),
        ctypes.c_long(int(cap_hint) if cap_hint else 65536),
        ctypes.byref(collector), ctypes.byref(n), ctypes.byref(grows),
    )
    if nk < 0:  # the C side has freed its blocks
        raise ValueError(f"bam_segments_stream: {_stream_err(nk)}")
    reg.counter("decode.segment_buffer_grows_total").inc(grows.value)
    # exact-size arrays of our own: nothing capacity-sized stays pinned
    # across the cohort's per-sample result fan-out
    try:
        seg_s = np.empty(n.value, np.int32)
        seg_e = np.empty(n.value, np.int32)
    except MemoryError:
        lib.bam_segments_take(collector, None, None)  # just frees
        raise
    lib.bam_segments_take(collector, _ptr(seg_s, ctypes.c_int32),
                          _ptr(seg_e, ctypes.c_int32))
    return seg_s, seg_e


def bam_window_acc_stream(comp, c_begin: int, in_block: int,
                          target_tid: int, start: int, end: int,
                          w0: int, length: int, window: int,
                          min_mapq: int, flag_mask: int,
                          wcount: np.ndarray | None = None,
                          check_crc: bool | None = None):
    """Lean streaming accumulation: each aligned segment adds its clipped
    overlap directly to the 1-2 windows it spans — no dense per-base
    delta array, so the accumulators stay L2-resident and the shard
    costs no O(length) DRAM traffic. Sums are UNCAPPED; ``max_overlap``
    bounds the max pileup depth per window, so a caller enforcing
    ``depth_cap`` must fall back to :func:`bam_window_reduce_stream`
    when ``max_overlap > depth_cap`` (window_reduce does this
    automatically). Returns dict(wsums, n_kept, max_overlap) or None.
    """
    lib = get_lib()
    if lib is None:
        return None
    if end < 0:
        raise ValueError("bam_window_acc_stream requires an explicit end")
    if length % window:
        raise ValueError("length must be a multiple of window")
    if check_crc is None:
        check_crc = not os.environ.get("GOLEFT_TPU_SKIP_CRC")
    buf = _as_u8(comp)
    n_win = length // window
    wsums = np.empty(n_win, dtype=np.int64)
    if wcount is None or len(wcount) < n_win:
        wcount = np.empty(n_win, dtype=np.int32)
    mx = ctypes.c_long(0)
    nk = lib.bam_window_acc_stream(
        _ptr(buf), ctypes.c_long(len(buf)), ctypes.c_long(c_begin),
        ctypes.c_long(in_block),
        ctypes.c_int(target_tid), ctypes.c_int(start), ctypes.c_int(end),
        ctypes.c_long(w0), ctypes.c_long(length), ctypes.c_long(window),
        ctypes.c_int(min_mapq), ctypes.c_int(flag_mask),
        ctypes.c_int(1 if check_crc else 0),
        _ptr(wsums, ctypes.c_int64), _ptr(wcount, ctypes.c_int32),
        ctypes.byref(mx),
    )
    if nk < 0:
        raise ValueError(f"bam_window_acc_stream: {_stream_err(nk)}")
    return {"wsums": wsums, "n_kept": int(nk),
            "max_overlap": int(mx.value)}
