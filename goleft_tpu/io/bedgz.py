"""The ``.bed.gz`` stream of ``indexcov`` and ``cohortscan``: a bounded,
ordered pipeline.

A block of bed rows (2,048 tiles x every sample: 4.9 MB of "%.3g" text at
500 samples) is one task of a small pool: format the rows, deflate the
text into whole BGZF members. Finished blocks go to the file in the order
they were handed over, by whichever worker completes the head of the
line, so the thread that hands them over goes on with its own work and
waits only where the bound on blocks in flight is reached, where it asks
for a block to be in the file (``wait_through``) and, once, at ``close``.

The file is what the serial ``BgzfWriter(level=1)`` wrote, gunzipped byte
for byte: members of at most 65,280 bytes of text with their ``BC``
subfield, the EOF member last. Only the member boundaries differ (a
worker deflates its text a piece at a time, and each piece ends on a
short member).
"""

from __future__ import annotations

import collections
import concurrent.futures as cf
import mmap
import threading

import numpy as np

from .. import obs
from . import native
from .bai import TILE_WIDTH
from .bgzf import BGZF_EOF, WRITE_CHUNK, bgzf_member

# the pool's threads, as many as load the indexes (indexcov.py
# run_indexcov; that pool is closed by the time this one starts)
POOL_WIDTH = 8
# blocks handed over and not yet in the file: two a worker. One waiting
# for its turn holds its deflated members (1.6 MB at 500 samples), one
# not yet started only its place in the line
MAX_INFLIGHT = 16
# text a worker formats before it deflates it: 16 full members. It stays
# in the worker's cache between the two, and a worker's scratch is 2.2 MB
# at 500 samples whatever the block's size
PIECE_BYTES = 16 * WRITE_CHUNK
LEVEL = 1


def bgzf_members(data: bytes) -> list[bytes]:
    """``data`` as BGZF members, one per ``WRITE_CHUNK`` bytes."""
    return [bgzf_member(data[i:i + WRITE_CHUNK], LEVEL)
            for i in range(0, len(data), WRITE_CHUNK)]


def format_bed_rows(ref_name: str, lo: int, hi: int, mat_cols: np.ndarray,
                    valid_cols: np.ndarray) -> bytes:
    """Bed rows for bins [lo, hi) in NumPy alone: what the native
    formatter writes, and what runs where the library is not built."""
    idx = np.arange(lo, hi, dtype=np.int64)
    block = np.char.mod("%.3g", mat_cols.T)
    block[~valid_cols.T] = "0"
    starts_col = np.char.mod("%d", idx * TILE_WIDTH)
    ends_col = np.char.mod("%d", (idx + 1) * TILE_WIDTH)
    return "".join(
        ref_name + "\t" + starts_col[i] + "\t" + ends_col[i]
        + "\t" + "\t".join(block[i]) + "\n"
        for i in range(hi - lo)
    ).encode()


class BedGzStream:
    """``header`` and then blocks of bed rows into ``fh`` as BGZF.

    ``timer`` (a utils.profiling.StageTimer) takes a ``write-output``
    stage a block, on the pool thread that does the work, with a
    ``format`` and a ``deflate`` child span a piece; the handing thread's
    waits are ``write-wait`` spans (docs/observability.md). As a context
    manager it closes the file's stream on a clean exit and, on an
    exception, stops the pool and leaves the file without its EOF member.
    """

    def __init__(self, fh, header: bytes, timer):
        self._fh = fh
        self._timer = timer
        reg = obs.get_registry()
        self._text = reg.counter("indexcov.bed_text_bytes_total")
        self._pooled = reg.counter("indexcov.bed_blocks_pooled_total")
        self._inflight_max = reg.gauge("indexcov.bed_blocks_inflight_max")
        self._ctx = obs.capture()  # a bare pool does not carry the trace
        self._pool = cf.ThreadPoolExecutor(
            max_workers=POOL_WIDTH, thread_name_prefix="bedgz")
        self._cv = threading.Condition()
        self._done: dict[int, list[bytes]] = {}  # deflated, not their turn
        self._submitted = 0  # tickets given out
        self._written = 0    # blocks in the file; the next ticket to write
        self._error: BaseException | None = None
        # (text, members) scratch pairs not in use: a worker takes one for
        # a block and gives it back, so at most POOL_WIDTH ever exist
        self._scratch: collections.deque = collections.deque()
        fh.writelines(bgzf_members(header))
        self._text.inc(len(header))

    # ---- the handing thread ----

    def submit(self, ref_name: str, lo: int, hi: int, mat_cols: np.ndarray,
               valid_cols: np.ndarray) -> int:
        """Hand over the rows of bins [lo, hi) of one chromosome;
        ``mat_cols``/``valid_cols`` are the (samples, hi-lo) column slice,
        read where it lies until the block is in the file. Returns the
        block's ticket. Waits while ``MAX_INFLIGHT`` blocks are in flight.

        The ONE formatting path of both the monolithic ``indexcov`` loop
        and the chunked ``cohortscan`` engine: shorter samples print 0
        (indexcov.go:678-680, depthsFor :1038-1048); C++ formats the block
        where the native library is built, byte-identical to np.char.mod
        "%.3g". The text depends only on the slice values, never on how
        the caller blocked its rows."""
        with self._cv:
            self._wait(lambda: self._submitted - self._written
                       < MAX_INFLIGHT)
            ticket = self._submitted
            self._submitted += 1
            self._inflight_max.max(self._submitted - self._written)
        self._pooled.inc()
        self._pool.submit(self._work, ticket, ref_name, lo, hi, mat_cols,
                          valid_cols)
        return ticket

    @property
    def last_ticket(self) -> int:
        """The newest block's ticket; -1 before the first."""
        return self._submitted - 1

    def wait_through(self, ticket: int) -> None:
        """Wait until the block with ``ticket`` and all before it are in
        the file: nothing reads their matrices any more."""
        with self._cv:
            self._wait(lambda: self._written > ticket)

    def close(self) -> None:
        """Wait for every block, write the EOF member, end the pool. A
        worker's exception is raised here (or at the wait before it) and
        the file then ends without the EOF member."""
        try:
            with self._cv:
                # the drain is a span however short, so every job has one
                self._wait(lambda: self._written == self._submitted,
                           span_always=True)
            self._fh.write(BGZF_EOF)
        finally:
            self._stop()

    def _wait(self, ready, span_always: bool = False) -> None:
        """Under ``_cv``: return when ``ready()``; raise what a worker
        raised. A real wait records a ``write-wait`` span."""
        if span_always or (self._error is None and not ready()):
            with obs.span("write-wait", category="wait"):
                while self._error is None and not ready():
                    self._cv.wait()
        if self._error is not None:
            raise self._error

    def _stop(self) -> None:
        self._pool.shutdown(wait=True, cancel_futures=True)
        # unmapped now, not when the caller lets go of the stream
        self._scratch.clear()
        with self._cv:
            self._done.clear()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None:
            self.close()
            return
        with self._cv:  # running workers write nothing more
            if self._error is None:
                self._error = exc
        self._stop()

    # ---- the pool's threads ----

    def _work(self, ticket, ref_name, lo, hi, mat_cols, valid_cols) -> None:
        try:
            if self._error is not None:
                return
            with obs.attach(self._ctx), self._timer.stage("write-output"):
                parts, n_text = self._deflated_rows(
                    ref_name, lo, hi, mat_cols, valid_cols)
                self._text.inc(n_text)
                with self._cv:
                    self._done[ticket] = parts
                    # whoever completes the head of the line writes it and
                    # what waits behind it, under the lock: in order
                    while (self._error is None
                           and self._written in self._done):
                        self._fh.writelines(self._done.pop(self._written))
                        self._written += 1
                    self._cv.notify_all()
        except BaseException as e:  # noqa: BLE001 — raised by _wait
            with self._cv:
                if self._error is None:
                    self._error = e
                self._cv.notify_all()

    def _deflated_rows(self, ref_name, lo, hi, mat_cols,
                       valid_cols) -> tuple[list[bytes], int]:
        """(the block's BGZF members, the bytes of its text)."""
        if native.get_lib() is None:
            with obs.span("format", category="output"):
                text = format_bed_rows(ref_name, lo, hi, mat_cols, valid_cols)
            with obs.span("deflate", category="output"):
                return bgzf_members(text), len(text)
        idx = np.arange(lo, hi, dtype=np.int64)
        starts, ends = idx * TILE_WIDTH, (idx + 1) * TILE_WIDTH
        text, members = self._take_scratch(native.float_rows_scratch_bytes(
            ref_name, mat_cols.shape[0], PIECE_BYTES))
        parts, n_text, row = [], 0, 0
        try:
            while row < hi - lo:
                with obs.span("format", category="output"):
                    n, row = native.format_float32_rows(
                        text, ref_name, starts, ends, mat_cols, valid_cols,
                        row0=row)
                with obs.span("deflate", category="output"):
                    m = native.bgzf_deflate_members(members, text[:n], LEVEL)
                    parts.append(members[:m].tobytes())
                n_text += n
        finally:
            self._scratch.append((text, members))
        return parts, n_text

    def _take_scratch(self, text_bytes: int) -> tuple[np.ndarray, np.ndarray]:
        """A (text, members) pair of uint8 arrays, the first of at least
        ``text_bytes``: one a worker gave back, or a new one."""
        try:
            text, members = self._scratch.pop()
            if len(text) >= text_bytes:
                return text, members
        except IndexError:
            pass
        # rounded up, so that a longer chromosome name finds it large enough
        text_bytes = -(-text_bytes // WRITE_CHUNK) * WRITE_CHUNK
        # mapped, not malloc'd: a pool thread's arena would keep the pages
        # after the stream has closed, under the job's resident peak
        return tuple(
            np.frombuffer(mmap.mmap(-1, n), dtype=np.uint8)
            for n in (text_bytes, native.bgzf_members_bound(text_bytes)))
