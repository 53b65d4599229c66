"""Banded Smith-Waterman: the read-mapper's extension kernel.

Local alignment of a read against a bounded reference window — the
seed-and-extend mapper's "extend" half (GenPairX / PIM read-mapping in
PAPERS.md both reduce it to exactly this shape). Affine gaps, int32
scores, and the same anti-diagonal wavefront the pair-HMM forward
(ops/pairhmm.py) established: cell (i, j) depends only on diagonals
i+j-1 and i+j-2, so each of the R+W wavefront steps updates three
(R+1)-lane vectors with shifts and elementwise max — no sequential
cell loop, no within-step dependency (the classic affine "F-loop"
problem disappears because F's feeder cells all live on the previous
anti-diagonal).

    H[i,j] = max(0, H[i-1,j-1] + sub(i,j), E[i,j], F[i,j])
    E[i,j] = max(H[i,j-1] + open + ext, E[i,j-1] + ext)   (gap in read)
    F[i,j] = max(H[i-1,j] + open + ext, F[i-1,j] + ext)   (gap in ref)

Everything is exact int32 arithmetic — device scores match the NumPy
oracle (:func:`sw_oracle`) bit for bit, which is what the mapping
tests pin per bucket shape. Padding lanes are masked to the identity
(H=0, E=F=-inf) every step, so a pair's score, argmax cell and
direction bits are bitwise independent of its bucket shape and batch
neighbors — the property that lets the serve executor coalesce map
requests byte-identically.

The device emits per-pair (best score, best cell) plus a per-diagonal
direction-bit plane (2 bits of H-source, one E-extend bit, one
F-extend bit per cell); the traceback walks those bits on the host
(:func:`traceback`) — O(alignment length) host work per read, all the
O(R·W) DP on device. Tie-breaking is pinned on both sides: the best
cell is the lexicographically first (i+j, i) among maximal cells, H
prefers diagonal > E > F on ties, and E/F prefer extension on ties.

Length bucketing mirrors pairhmm: reads pad to ``BUCKET`` (32),
windows to ``WBUCKET`` (64), so arbitrary read cohorts compile
O(#buckets) programs; :func:`align_pairs` is the host entry the
mapping pipeline drives (the ``map`` fault site wraps it one level
up, in mapping/pipeline.py, with per-bucket quarantine).
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

from .pairhmm import encode_seq  # shared A=0 C=1 G=2 T=3, N=4 codes

BUCKET = 32    # read-length bucket granularity
WBUCKET = 64   # window-length bucket granularity
N_CODE = 4
#: "minus infinity" for int32 gap states: low enough to never win a
#: max, high enough that adding a gap penalty cannot wrap
NEG = np.int32(-(1 << 28))


class Scores(NamedTuple):
    """Integer alignment scores (penalties negative)."""

    match: int = 2
    mismatch: int = -4
    gap_open: int = -4   # charged once per gap, on top of gap_ext
    gap_ext: int = -2

    def astuple(self) -> tuple[int, int, int, int]:
        return (int(self.match), int(self.mismatch),
                int(self.gap_open), int(self.gap_ext))


DEFAULT_SCORES = Scores()


def _pad_up(n: int, to: int) -> int:
    return max(to, ((n + to - 1) // to) * to)


def bucket_shape(rlen: int, wlen: int) -> tuple[int, int]:
    """(r_pad, w_pad) signature for one read/window pair."""
    return _pad_up(rlen, BUCKET), _pad_up(wlen, WBUCKET)


def _sw_bucket_impl(reads_p, rlens, wins, wlens, scores):
    """One padded bucket through the wavefront; vmapped over pairs.

    reads_p: (B, R1) uint8 — read base at wavefront lane i (1-based;
             lane 0 is the boundary row), rlens (B,) int32
    wins:    (B, W) uint8 window bases (0-based), wlens (B,) int32
    scores:  (4,) int32 [match, mismatch, gap_open, gap_ext]

    Returns (best (B,) int32, bi (B,) int32, bj (B,) int32,
    dirs (B, steps, R1) uint8): per cell, bits 0-1 = H source
    (0 stop, 1 diag, 2 E, 3 F), bit 2 = E extended, bit 3 = F
    extended. Best cell tie-break: smallest i+j, then smallest i.
    """
    import jax
    import jax.numpy as jnp

    r1 = reads_p.shape[1]
    wcap = wins.shape[1]
    steps = r1 + wcap
    neg = jnp.int32(NEG)
    zero = jnp.int32(0)

    def one_pair(read, rlen, win, wlen):
        s_match, s_mis, s_open, s_ext = (scores[0], scores[1],
                                         scores[2], scores[3])
        ii = jnp.arange(r1, dtype=jnp.int32)

        def shift1(x):
            # x[i-1] with the boundary entering at lane 0
            return jnp.concatenate([x[:1] * 0 + neg, x[:-1]])

        def shift1h(x):
            # H boundary row/col is 0, not -inf
            return jnp.concatenate([x[:1] * 0, x[:-1]])

        def step(k, carry):
            h1, e1, f1, h2, best, bi, bj, dirs = carry
            jj = k - ii
            wb = jnp.where((jj >= 1) & (jj <= wlen),
                           win[jnp.clip(jj - 1, 0, wcap - 1)],
                           jnp.uint8(N_CODE))
            valid = ((ii >= 1) & (ii <= rlen)
                     & (jj >= 1) & (jj <= wlen))
            is_match = (read == wb) & (read != N_CODE) \
                & (wb != N_CODE)
            sub = jnp.where(is_match, s_match, s_mis)
            h_diag = shift1h(h2) + sub
            e_open = h1 + s_open + s_ext
            e_ext = e1 + s_ext
            e = jnp.maximum(e_open, e_ext)
            f_open = shift1h(h1) + s_open + s_ext
            f_ext = shift1(f1) + s_ext
            f = jnp.maximum(f_open, f_ext)
            h = jnp.maximum(jnp.maximum(zero, h_diag),
                            jnp.maximum(e, f))
            h = jnp.where(valid, h, zero)
            e = jnp.where(valid, e, neg)
            f = jnp.where(valid, f, neg)
            # direction bits, tie order diag > E > F > stop; E/F
            # prefer extension on ties (the oracle mirrors all three)
            src = jnp.where(
                h <= zero, 0,
                jnp.where(h == h_diag, 1, jnp.where(h == e, 2, 3)))
            d = (src.astype(jnp.uint8)
                 | ((e_ext >= e_open).astype(jnp.uint8) << 2)
                 | ((f_ext >= f_open).astype(jnp.uint8) << 3))
            d = jnp.where(valid, d, jnp.uint8(0))
            dirs = dirs.at[k].set(d)
            hv = jnp.where(valid, h, jnp.int32(-1))
            m = jnp.max(hv)
            am = jnp.argmax(hv).astype(jnp.int32)
            take = m > best  # strict: keeps the earliest diagonal
            best = jnp.where(take, m, best)
            bi = jnp.where(take, am, bi)
            bj = jnp.where(take, k - am, bj)
            return h, e, f, h1, best, bi, bj, dirs

        z = jnp.zeros(r1, jnp.int32)
        zneg = jnp.full(r1, neg, jnp.int32)
        init = (z, zneg, zneg, z, jnp.int32(0), jnp.int32(0),
                jnp.int32(0), jnp.zeros((steps, r1), jnp.uint8))
        h1, e1, f1, h2, best, bi, bj, dirs = jax.lax.fori_loop(
            1, steps, step, init)
        return best, bi, bj, dirs

    return jax.vmap(one_pair)(reads_p, rlens, wins, wlens)


_SW_JIT = None


def sw_bucket(reads_p, rlens, wins, wlens, scores):
    """Jitted wrapper; one compile per (B, r_pad, w_pad) geometry."""
    global _SW_JIT
    if _SW_JIT is None:
        import jax

        _SW_JIT = jax.jit(_sw_bucket_impl)
    return _SW_JIT(reads_p, rlens, wins, wlens, scores)


def _sw_jit_cache_size() -> int:
    if _SW_JIT is None:
        return 0
    return _SW_JIT._cache_size()


def sw_oracle(read_codes: np.ndarray, win_codes: np.ndarray,
              scores: Scores = DEFAULT_SCORES):
    """Exact NumPy reference: plain nested-loop affine-gap local DP.

    Independent of the wavefront formulation (row-major cell loop,
    no shifts, no masks) but pinned to the same int arithmetic and
    tie rules, so device output must match it bit for bit. Returns
    (best, bi, bj, dirs) in the device layout: dirs[k, i] holds the
    bits for cell (i, j=k-i) with i 1-based over the read.
    """
    s_match, s_mis, s_open, s_ext = scores.astuple()
    r = len(read_codes)
    w = len(win_codes)
    neg = int(NEG)
    H = np.zeros((r + 1, w + 1), dtype=np.int64)
    E = np.full((r + 1, w + 1), neg, dtype=np.int64)
    F = np.full((r + 1, w + 1), neg, dtype=np.int64)
    dirs = np.zeros((r + 1 + w, r + 1), dtype=np.uint8)
    for i in range(1, r + 1):
        rb = int(read_codes[i - 1])
        for j in range(1, w + 1):
            wb = int(win_codes[j - 1])
            sub = s_match if (rb == wb and rb != N_CODE
                              and wb != N_CODE) else s_mis
            h_diag = H[i - 1, j - 1] + sub
            e_open = H[i, j - 1] + s_open + s_ext
            e_ext = E[i, j - 1] + s_ext
            e = max(e_open, e_ext)
            f_open = H[i - 1, j] + s_open + s_ext
            f_ext = F[i - 1, j] + s_ext
            f = max(f_open, f_ext)
            h = max(0, h_diag, e, f)
            H[i, j], E[i, j], F[i, j] = h, e, f
            if h <= 0:
                src = 0
            elif h == h_diag:
                src = 1
            elif h == e:
                src = 2
            else:
                src = 3
            dirs[i + j, i] = (src | ((e_ext >= e_open) << 2)
                              | ((f_ext >= f_open) << 3))
    # best cell with the device's tie rule: among maximal cells the
    # lexicographically first (i+j, i) — strict improvement over
    # wavefront steps, first lane within a step
    best = int(max(H.max(), 0))
    bi = bj = 0
    if best > 0:
        cand = np.argwhere(H == best)
        order = np.lexsort((cand[:, 0], cand[:, 0] + cand[:, 1]))
        bi, bj = (int(cand[order[0], 0]), int(cand[order[0], 1]))
    return best, bi, bj, dirs


def traceback(dirs: np.ndarray, bi: int, bj: int):
    """Walk the direction bits back from the best cell.

    ``dirs`` is the (steps, R1) per-pair plane (device or oracle);
    (bi, bj) the 1-based best cell. Returns (read_start, read_end,
    win_start, win_end, cigar) with half-open 0-based spans and a
    SAM-style cigar over M/I/D (I consumes read, D consumes window).
    """
    i, j = int(bi), int(bj)
    if i == 0 and j == 0:
        return 0, 0, 0, 0, ""
    ops: list[tuple[str, int]] = []

    def push(op: str):
        if ops and ops[-1][0] == op:
            ops[-1] = (op, ops[-1][1] + 1)
        else:
            ops.append((op, 1))

    state = "H"
    while True:
        d = int(dirs[i + j, i])
        if state == "H":
            src = d & 3
            if src == 0:
                break
            if src == 1:
                push("M")
                i -= 1
                j -= 1
            elif src == 2:
                state = "E"
            else:
                state = "F"
        elif state == "E":
            push("D")  # gap in read: consumes a window base
            ext = (d >> 2) & 1
            j -= 1
            state = "E" if ext else "H"
        else:
            push("I")  # gap in window: consumes a read base
            ext = (d >> 3) & 1
            i -= 1
            state = "F" if ext else "H"
    cigar = "".join(f"{n}{op}" for op, n in reversed(ops))
    return i, int(bi), j, int(bj), cigar


class Alignment(NamedTuple):
    """One read↔window local alignment (spans 0-based half-open)."""

    score: int
    read_start: int
    read_end: int
    win_start: int
    win_end: int
    cigar: str


def _pack_bucket(idxs, reads, wins, r_pad, w_pad):
    """Pad one bucket's pairs into the kernel layout."""
    b = len(idxs)
    r1 = r_pad + 1
    reads_p = np.full((b, r1), N_CODE, dtype=np.uint8)
    rlens = np.zeros(b, dtype=np.int32)
    wins_p = np.full((b, w_pad), N_CODE, dtype=np.uint8)
    wlens = np.zeros(b, dtype=np.int32)
    for row, n in enumerate(idxs):
        r, w = reads[n], wins[n]
        reads_p[row, 1:len(r) + 1] = r
        rlens[row] = len(r)
        wins_p[row, :len(w)] = w
        wlens[row] = len(w)
    return reads_p, rlens, wins_p, wlens


def align_bucket(reads_p, rlens, wins_p, wlens,
                 scores: Scores = DEFAULT_SCORES):
    """One padded bucket → per-pair :class:`Alignment` list (host
    traceback over the device direction bits)."""
    sc = np.asarray(scores.astuple(), dtype=np.int32)
    best, bi, bj, dirs = sw_bucket(reads_p, rlens, wins_p, wlens, sc)
    best = np.asarray(best)
    bi = np.asarray(bi)
    bj = np.asarray(bj)
    dirs = np.asarray(dirs)
    out = []
    for n in range(len(best)):
        rs, re_, ws, we, cig = traceback(dirs[n], bi[n], bj[n])
        out.append(Alignment(int(best[n]), rs, re_, ws, we, cig))
    return out


def align_pairs(reads, wins, scores: Scores = DEFAULT_SCORES,
                dispatch=None) -> list[Alignment]:
    """Host entry: N (read, window) code pairs → N alignments.

    Pairs bucket by (r_pad, w_pad); each bucket is one vmapped
    wavefront dispatch. ``dispatch``, when given, wraps each bucket
    call — the mapping pipeline passes its plan-Step runner there so
    extension rides the ``map`` fault site with per-bucket
    quarantine; ``None`` dispatches directly (tests).
    """
    out: list[Alignment | None] = [None] * len(reads)
    groups: dict[tuple[int, int], list[int]] = {}
    for n, (r, w) in enumerate(zip(reads, wins)):
        groups.setdefault(bucket_shape(len(r), len(w)), []).append(n)
    for (r_pad, w_pad), idxs in sorted(groups.items()):
        packed = _pack_bucket(idxs, reads, wins, r_pad, w_pad)
        if dispatch is None:
            res = align_bucket(*packed, scores=scores)
        else:
            res = dispatch((r_pad, w_pad, len(idxs)),
                           lambda p=packed: align_bucket(
                               *p, scores=scores))
        for n, a in zip(idxs, res):
            out[n] = a
    return out  # type: ignore[return-value]


def oracle_align(read, win, scores: Scores = DEFAULT_SCORES
                 ) -> Alignment:
    """Oracle counterpart of one :func:`align_pairs` element."""
    r = encode_seq(read)
    w = encode_seq(win)
    best, bi, bj, dirs = sw_oracle(r, w, scores)
    rs, re_, ws, we, cig = traceback(dirs, bi, bj)
    return Alignment(best, rs, re_, ws, we, cig)
