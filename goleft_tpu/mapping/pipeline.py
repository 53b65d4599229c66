"""Seed-chain-extend pipeline: batched reads → mapped read tuples.

Three device stages per read batch, all pad-to-bucket compiled:

  1. **seed** — reads ship 2-bit packed (4 bases/byte + an N bitmask,
     a quarter of the H2D bytes of raw codes), unpack in-kernel,
     hash their (w,k)-minimizers with the same fmix32 the index used,
     probe the open-addressed table (fixed ``PROBE_MAX`` unrolled
     probe — the build guaranteed every chain fits), and gather up to
     ``max_occ`` reference positions per seed.
  2. **chain** — in the same dispatch: seed hits become diagonals
     (ref_pos − read_pos), are sorted per read, and a searchsorted
     band-count scan scores every diagonal by how many hits land
     within ±band of it — a vectorized stand-in for colinear
     chaining DP that needs no per-read loop. Both strands run (the
     reverse complement is re-derived in-kernel); the higher-support
     strand wins, forward on ties, smallest diagonal on ties within
     a strand.
  3. **extend** — the winning diagonal defines a reference window
     [diag − band, diag + rlen + band) clipped to its chromosome;
     read/window pairs go through the banded Smith-Waterman
     wavefront (ops/swalign.py) bucketed by (r_pad, w_pad).

Every device dispatch is a plan Step at the ``map`` fault site:
transient faults retry under the RetryPolicy, exhausted buckets fail
only their own reads (``allow_partial``) and surface in the returned
``failed`` map for the caller to quarantine (exit-3 contract, same as
cohort decode). Compiles are bounded by the rANS
``MAX_BUCKET_SIGNATURES`` discipline: past the cap, new bucket shapes
fall back to the host reference implementations (bit-identical by
construction — the host seeder IS the oracle the device tests pin)
and ``mapping.host_fallback_total`` counts them.
"""

from __future__ import annotations

import threading
from functools import lru_cache, partial
from typing import NamedTuple

import numpy as np

from ..obs import get_logger, get_registry
from ..ops import swalign
from ..ops.pairhmm import encode_seq
from ..ops.swalign import Scores, DEFAULT_SCORES
from .index import (
    DEFAULT_K, DEFAULT_MAX_OCC, DEFAULT_W, PROBE_MAX, MinimizerIndex,
    fmix32, kmer_codes, minimizer_mask,
)

log = get_logger("mapping")

BUCKET = swalign.BUCKET        # read-length bucket granularity
DEFAULT_BAND = 32
DEFAULT_MIN_SUPPORT = 2
#: compile-signature cap, same discipline (and sizing rationale) as
#: ops/rans_device.py: over the cap, new shapes fall back to host
MAX_BUCKET_SIGNATURES = 128
#: diagonal sentinel for invalid seed-hit lanes: far above any real
#: diagonal (references cap at 2^29 bases), low enough that +band
#: cannot wrap int32
_DIAG_INF = 1 << 29

_SIG_LOCK = threading.Lock()
_SEEN_SIGS: set[tuple] = set()
_CAP_TRIPPED = False


class MapParams(NamedTuple):
    """Mapping parameters — part of every content/group key."""

    k: int = DEFAULT_K
    w: int = DEFAULT_W
    max_occ: int = DEFAULT_MAX_OCC
    band: int = DEFAULT_BAND
    min_support: int = DEFAULT_MIN_SUPPORT
    scores: Scores = DEFAULT_SCORES

    def key(self) -> tuple:
        return (self.k, self.w, self.max_occ, self.band,
                self.min_support) + self.scores.astuple()


class MapResult(NamedTuple):
    """One batch through :func:`map_reads`."""

    tuples: list          # per input read: tuple row or None
    failed: dict          # input index -> exception (quarantinable)
    stats: dict


def reset_signature_registry() -> None:
    """Test hook: re-open compile-signature admission."""
    global _CAP_TRIPPED
    with _SIG_LOCK:
        _SEEN_SIGS.clear()
        _CAP_TRIPPED = False


def _admit(sig: tuple) -> bool:
    global _CAP_TRIPPED
    with _SIG_LOCK:
        if sig in _SEEN_SIGS:
            return True
        if len(_SEEN_SIGS) >= MAX_BUCKET_SIGNATURES:
            if not _CAP_TRIPPED:
                _CAP_TRIPPED = True
                log.warning(
                    "mapping: bucket-signature cap reached (%d); new "
                    "shapes fall back to the host implementations "
                    "(mapping.host_fallback_total counts them)",
                    MAX_BUCKET_SIGNATURES)
            return False
        _SEEN_SIGS.add(sig)
        return True


def _pad_up(n: int, to: int) -> int:
    return max(to, ((n + to - 1) // to) * to)


def _smax(r_pad: int, k: int, w: int) -> int:
    """Per-read seed capacity for a bucket: ~2x the expected 1/w
    minimizer density plus slack; degenerate (all-tie) reads overflow
    it and simply lose tail seeds — they were unmappable repeats."""
    n = r_pad - k + 1
    return max(4, min(n, 2 * (n // w) + 8))


def rc_codes(codes: np.ndarray) -> np.ndarray:
    """Reverse-complement of a 0..4 code array (N stays N)."""
    r = codes[::-1]
    return np.where(r < 4, 3 - r, 4).astype(codes.dtype)


# ---------------------------------------------------------------------------
# device seeding + chaining kernel

def _seed_bucket_impl(packed, nmask, rlens, ht_code, ht_start,
                      ht_cnt, pos, *, r_pad: int, k: int, w: int,
                      max_occ: int, band: int, smax: int):
    """One read bucket: 2-bit unpack → minimizers → table probe →
    gather → diagonal chain, both strands. vmapped over reads.

    packed (B, ceil(r_pad/4)) uint8, nmask (B, ceil(r_pad/8)) uint8
    (bit set = base is N or padding), rlens (B,) int32; table arrays
    as built by mapping.index. Returns (support (B,) int32 — −1 when
    no valid seed hit, diag (B,) int32 global, rev (B,) bool).
    """
    import jax
    import jax.numpy as jnp

    S = ht_code.shape[0]
    P = max(1, pos.shape[0])
    n = r_pad - k + 1
    INF = jnp.uint32(0xFFFFFFFF)

    def fmix(x):
        x = x.astype(jnp.uint32)
        x = x ^ (x >> 16)
        x = x * jnp.uint32(0x85EBCA6B)
        x = x ^ (x >> 13)
        x = x * jnp.uint32(0xC2B2AE35)
        x = x ^ (x >> 16)
        return x

    def seed_one(codes):
        """codes (r_pad,) int32 0..4 → (support, diag) one strand."""
        kc = jnp.zeros(n, jnp.uint32)
        valid = jnp.ones(n, bool)
        for t in range(k):
            c = codes[t:t + n]
            kc = (kc << 2) | jnp.minimum(c, 3).astype(jnp.uint32)
            valid = valid & (c < 4)
        h = jnp.where(valid, fmix(kc), INF)
        # symmetric windowed min, out-of-range neighbors = +inf —
        # the same rule minimizer_mask applied to the reference
        m = h
        for d in range(1, w):
            m = jnp.minimum(m, jnp.concatenate(
                [jnp.full((d,), INF, h.dtype), h[:-d]]))
            m = jnp.minimum(m, jnp.concatenate(
                [h[d:], jnp.full((d,), INF, h.dtype)]))
        sel = valid & (h == m)
        # compact selected positions (stable: position order) to smax
        order = jnp.argsort((~sel).astype(jnp.int32), stable=True)
        take = order[:smax]
        tvalid = sel[take]
        tcode = kc[take].astype(jnp.int32)  # codes < 2^30: cast safe
        # fixed-depth probe: the index build guaranteed every chain
        # fits PROBE_MAX, so a miss after PROBE_MAX means "absent"
        slot = (fmix(kc[take]) & jnp.uint32(S - 1)).astype(jnp.int32)
        fstart = jnp.zeros(smax, jnp.int32)
        fcnt = jnp.zeros(smax, jnp.int32)
        done = ~tvalid
        for t in range(PROBE_MAX):
            j = (slot + t) & (S - 1)
            c = ht_code[j]
            hit = (~done) & (c == tcode)
            fstart = jnp.where(hit, ht_start[j], fstart)
            fcnt = jnp.where(hit, ht_cnt[j], fcnt)
            done = done | hit | (c == -1)
        # gather ≤ max_occ reference positions per seed → diagonals
        lanes = jnp.arange(max_occ, dtype=jnp.int32)
        gidx = jnp.clip(fstart[:, None] + lanes[None, :], 0, P - 1)
        pv = pos[gidx]
        ok = lanes[None, :] < fcnt[:, None]
        ds = jnp.where(ok, pv - take[:, None].astype(jnp.int32),
                       jnp.int32(_DIAG_INF)).reshape(-1)
        # chain: sort diagonals, score each by hits within ±band
        ds = jnp.sort(ds)
        hi = jnp.searchsorted(ds, ds + jnp.int32(band), side="right")
        lo = jnp.searchsorted(ds, ds - jnp.int32(band), side="left")
        support = jnp.where(ds >= jnp.int32(_DIAG_INF),
                            jnp.int32(-1),
                            (hi - lo).astype(jnp.int32))
        b = jnp.argmax(support)  # first max → smallest diagonal
        return support[b], ds[b]

    def one_read(pk, nm, rlen):
        p = jnp.arange(r_pad, dtype=jnp.int32)
        code2 = ((pk[p // 4].astype(jnp.int32) >> (2 * (p % 4)))
                 & 3)
        nbit = (nm[p // 8].astype(jnp.int32) >> (p % 8)) & 1
        codes = jnp.where(nbit == 1, jnp.int32(4), code2)
        # reverse complement, rolled so the read re-starts at lane 0
        rcrev = jnp.where(codes[::-1] < 4, 3 - codes[::-1],
                          jnp.int32(4))
        rc = jnp.roll(rcrev, rlen - r_pad)
        sf, df = seed_one(codes)
        sr, dr = seed_one(rc)
        rev = sr > sf  # forward wins ties
        return (jnp.where(rev, sr, sf), jnp.where(rev, dr, df), rev)

    return jax.vmap(one_read)(packed, nmask, rlens)


@lru_cache(maxsize=None)
def _seed_jit(r_pad: int, k: int, w: int, max_occ: int, band: int,
              smax: int):
    import jax

    return jax.jit(partial(_seed_bucket_impl, r_pad=r_pad, k=k, w=w,
                           max_occ=max_occ, band=band, smax=smax))


def _seed_jit_cache_size() -> int:
    """Distinct seed-kernel geometries compiled in this process."""
    return _seed_jit.cache_info().currsize


def _pack_reads_2bit(idxs, codes_list, r_pad):
    """Bucket pack: 2-bit bases + N/padding bitmask + lengths."""
    b = len(idxs)
    pbytes = (r_pad + 3) // 4
    nbytes = (r_pad + 7) // 8
    pk = np.zeros((b, pbytes), np.uint8)
    nm = np.zeros((b, nbytes), np.uint8)
    rl = np.zeros(b, np.int32)
    shifts4 = np.arange(4, dtype=np.uint16) * 2
    shifts8 = np.arange(8, dtype=np.uint16)
    for row, ridx in enumerate(idxs):
        c = codes_list[ridx]
        L = len(c)
        rl[row] = L
        c4 = np.full(pbytes * 4, 0, np.uint16)
        c4[:L] = np.minimum(c, 3)
        pk[row] = (c4.reshape(pbytes, 4)
                   << shifts4).sum(axis=1).astype(np.uint8)
        nb = np.ones(nbytes * 8, np.uint16)
        nb[:L] = (np.asarray(c) >= 4)
        nm[row] = (nb.reshape(nbytes, 8)
                   << shifts8).sum(axis=1).astype(np.uint8)
    return pk, nm, rl


def seed_reads_host(index: MinimizerIndex, codes: np.ndarray,
                    band: int, smax: int) -> tuple[int, int, bool]:
    """Host reference seeding for ONE read: the oracle the device
    kernel is pinned against, and the over-cap fallback. Returns
    (support, diag, rev) with identical tie rules."""

    def one(c: np.ndarray) -> tuple[int, int]:
        kc, valid = kmer_codes(c.astype(np.uint8), index.k)
        if len(kc) == 0:
            return -1, _DIAG_INF
        sel = minimizer_mask(fmix32(kc), valid, index.w)
        seeds = np.nonzero(sel)[0][:smax]
        ds: list[int] = []
        size = index.table_size
        for p in seeds:
            code = np.int32(kc[p])
            s = int(fmix32(np.asarray([kc[p]]))[0]) & (size - 1)
            for t in range(PROBE_MAX):
                j = (s + t) & (size - 1)
                cj = index.ht_code[j]
                if cj == -1:
                    break
                if cj == code:
                    st, ct = (int(index.ht_start[j]),
                              int(index.ht_cnt[j]))
                    ds.extend(int(index.pos[st + u]) - int(p)
                              for u in range(ct))
                    break
        if not ds:
            return -1, _DIAG_INF
        arr = np.sort(np.asarray(ds, np.int64))
        hi = np.searchsorted(arr, arr + band, side="right")
        lo = np.searchsorted(arr, arr - band, side="left")
        support = (hi - lo).astype(np.int64)
        b = int(np.argmax(support))
        return int(support[b]), int(arr[b])

    sf, df = one(codes)
    sr, dr = one(rc_codes(codes))
    rev = sr > sf
    return (sr, dr, True) if rev else (sf, df, False)


# ---------------------------------------------------------------------------
# the batch pipeline

def map_reads(index: MinimizerIndex, records,
              params: MapParams = MapParams(), *, policy=None,
              allow_partial: bool = True) -> MapResult:
    """Map one batch of FASTQ records against ``index``.

    ``records`` is a sequence of objects with ``.name``/``.seq``
    (FastqRecord or equivalent). Returns per-read tuples
    ``(chrom, start, end, name, score, strand)`` — ``None`` for
    unmapped reads — plus a ``failed`` index→exception map for
    buckets whose dispatch exhausted retries (``allow_partial``;
    otherwise the exhaustion raises), and counters for the CLI/serve
    summaries. All device work rides plan Steps at the ``map`` fault
    site.
    """
    from ..obs.compiles import TRACKER
    from ..plan import Executor as PlanExecutor, Step
    from ..resilience.policy import DEFAULT_POLICY

    if policy is None:
        policy = DEFAULT_POLICY
    reg = get_registry()
    n_reads = len(records)
    reg.counter("mapping.reads_total").inc(n_reads)
    tuples: list = [None] * n_reads
    failed: dict[int, BaseException] = {}
    stats = {"reads": n_reads, "mapped": 0, "unmapped": 0,
             "failed": 0, "seed_buckets": 0, "extend_buckets": 0}
    if n_reads == 0:
        return MapResult(tuples, failed, stats)

    codes_list = [encode_seq(r.seq) for r in records]
    pex = PlanExecutor(policy=policy)

    # ---- stage 1+2: seed + chain, bucketed by padded read length
    support = np.full(n_reads, -1, np.int32)
    diag = np.full(n_reads, _DIAG_INF, np.int64)
    rev = np.zeros(n_reads, bool)
    groups: dict[int, list[int]] = {}
    for i, c in enumerate(codes_list):
        if len(c) < index.k:
            continue  # shorter than a seed: unmapped, not an error
        groups.setdefault(_pad_up(len(c), BUCKET), []).append(i)

    for r_pad, idxs in sorted(groups.items()):
        smax = _smax(r_pad, index.k, index.w)
        b = len(idxs)
        sig = ("map-seed", r_pad, index.table_size, len(index.pos),
               b)
        reg.counter("mapping.buckets_total").inc()
        stats["seed_buckets"] += 1
        if not _admit(sig):
            reg.counter("mapping.host_fallback_total").inc()
            for i in idxs:
                s, d, rv = seed_reads_host(index, codes_list[i],
                                           params.band, smax)
                support[i], diag[i], rev[i] = s, d, rv
            continue

        pk, nm, rl = _pack_reads_2bit(idxs, codes_list, r_pad)
        tables = index.device_tables()

        def thunk(pk=pk, nm=nm, rl=rl, r_pad=r_pad, smax=smax,
                  b=b):
            with TRACKER.observe(
                    "swalign",
                    signature={"stage": "seed", "r_pad": r_pad,
                               "table": index.table_size, "b": b},
                    cache_size_fn=_seed_jit_cache_size,
                    trigger="map_seed"):
                fn = _seed_jit(r_pad, index.k, index.w,
                               index.max_occ, params.band, smax)
                s, d, rv = fn(pk, nm, rl, *tables)
            return (np.asarray(s), np.asarray(d), np.asarray(rv))

        key = ("map-seed", index.ref_key, params.key(), r_pad, b)
        outcome = pex.run_step(Step(key=key, fn=thunk, site="map"))
        if outcome.error is not None:
            if not allow_partial:
                raise outcome.retries_exhausted
            reg.counter("mapping.buckets_failed_total").inc()
            for i in idxs:
                failed[i] = outcome.error
            continue
        s, d, rv = outcome.value
        ii = np.asarray(idxs)
        support[ii] = s
        diag[ii] = d
        rev[ii] = rv
    reg.counter("mapping.seed_hits_total").inc(
        int(support[support > 0].sum()))

    # ---- stage 3: extension windows for seeded reads
    ext_idx: list[int] = []
    ext_reads: list[np.ndarray] = []
    ext_wins: list[np.ndarray] = []
    ext_gstart: list[int] = []
    L = len(index.ref_codes)
    for i in range(n_reads):
        if i in failed or support[i] < params.min_support:
            continue
        rlen = len(codes_list[i])
        d = int(diag[i])
        center = min(max(d + rlen // 2, 0), max(L - 1, 0))
        cs, ce = index.chrom_bounds(center)
        ws = max(cs, d - params.band)
        we = min(ce, d + rlen + params.band)
        if we - ws < index.k:
            continue
        ext_idx.append(i)
        ext_reads.append(rc_codes(codes_list[i]) if rev[i]
                         else codes_list[i])
        ext_wins.append(index.ref_codes[ws:we])
        ext_gstart.append(ws)

    ext_failed: dict[tuple, BaseException] = {}

    def ext_dispatch(sig, thunk):
        r_pad, w_pad, b = sig
        reg.counter("mapping.buckets_total").inc()
        stats["extend_buckets"] += 1
        asig = ("map-extend", r_pad, w_pad, b)
        if not _admit(asig):
            # signal align_pairs to take no device path; the caller
            # oracle-aligns these pairs (bit-identical fallback)
            reg.counter("mapping.host_fallback_total").inc()
            ext_failed[(r_pad, w_pad)] = _HostFallback()
            return [None] * b

        def wrapped():
            with TRACKER.observe(
                    "swalign",
                    signature={"stage": "extend", "r_pad": r_pad,
                               "w_pad": w_pad, "b": b},
                    cache_size_fn=swalign._sw_jit_cache_size,
                    trigger="map_extend"):
                return thunk()

        key = ("map-extend", index.ref_key, params.key(), r_pad,
               w_pad, b)
        outcome = pex.run_step(Step(key=key, fn=wrapped, site="map"))
        if outcome.error is not None:
            if not allow_partial:
                raise outcome.retries_exhausted
            reg.counter("mapping.buckets_failed_total").inc()
            ext_failed[(r_pad, w_pad)] = outcome.error
            return [None] * b
        return outcome.value

    aligned = swalign.align_pairs(ext_reads, ext_wins,
                                  scores=params.scores,
                                  dispatch=ext_dispatch)
    for j, a in enumerate(aligned):
        i = ext_idx[j]
        if a is None:
            err = ext_failed.get(swalign.bucket_shape(
                len(ext_reads[j]), len(ext_wins[j])))
            if isinstance(err, _HostFallback):
                a = swalign.Alignment(*_oracle_one(
                    ext_reads[j], ext_wins[j], params.scores))
            else:
                failed[i] = err if err is not None else RuntimeError(
                    "map: extension dispatch lost")
                continue
        if a.score <= 0:
            continue
        gs = ext_gstart[j] + a.win_start
        ge = ext_gstart[j] + a.win_end
        chrom, local = index.chrom_of(gs)
        tuples[i] = (chrom, local, local + (ge - gs),
                     records[i].name, int(a.score),
                     "-" if rev[i] else "+")

    stats["failed"] = len(failed)
    stats["mapped"] = sum(1 for t in tuples if t is not None)
    stats["unmapped"] = (n_reads - stats["mapped"]
                         - stats["failed"])
    reg.counter("mapping.reads_mapped_total").inc(stats["mapped"])
    reg.counter("mapping.reads_unmapped_total").inc(
        stats["unmapped"])
    return MapResult(tuples, failed, stats)


class _HostFallback(Exception):
    """Internal marker: bucket refused admission, not a failure."""


def _oracle_one(read_codes, win_codes, scores):
    best, bi, bj, dirs = swalign.sw_oracle(np.asarray(read_codes),
                                           np.asarray(win_codes),
                                           scores)
    rs, re_, ws, we, cig = swalign.traceback(dirs, bi, bj)
    return best, rs, re_, ws, we, cig


# ---------------------------------------------------------------------------
# tuple stream + fused windowed depth

def format_tuples(tuples) -> bytes:
    """Mapped tuples → the TSV stream (`chrom start end name score
    strand`, 0-based half-open; unmapped rows are absent)."""
    out = []
    for t in tuples:
        if t is None:
            continue
        chrom, s, e, name, score, strand = t
        out.append(f"{chrom}\t{s}\t{e}\t{name}\t{score}\t{strand}\n")
    return "".join(out).encode()


def parse_tuples(data: bytes):
    """Inverse of :func:`format_tuples` (the ``--from-tuples`` path)."""
    out = []
    for lineno, line in enumerate(data.splitlines(), 1):
        if not line.strip():
            continue
        parts = line.split(b"\t")
        if len(parts) != 6:
            raise ValueError(
                f"tuple line {lineno}: expected 6 fields, got "
                f"{len(parts)}")
        out.append((parts[0].decode(), int(parts[1]), int(parts[2]),
                    parts[3].decode(), int(parts[4]),
                    parts[5].decode()))
    return out


def depth_bed_from_tuples(tuples, chrom_lengths: dict[str, int],
                          window: int) -> bytes:
    """Mapped tuples → windowed mean-depth bed, via the SAME coverage
    kernels the depth command runs (ops/coverage.py). One region per
    covered chromosome, windows absolute-aligned, rows formatted like
    depth shard output — so the fused ``map --depth-out`` path and a
    ``--from-tuples`` re-run are byte-identical by construction.
    """
    import jax.numpy as jnp

    from ..ops.coverage import (
        bucket_size, depth_from_segments, window_bounds,
        windowed_sums,
    )

    by_chrom: dict[str, list[tuple[int, int]]] = {}
    for t in tuples:
        if t is None:
            continue
        chrom, s, e = t[0], t[1], t[2]
        if e > s:
            by_chrom.setdefault(chrom, []).append((s, e))
    out: list[str] = []
    for chrom in sorted(by_chrom,
                        key=lambda c: (c not in chrom_lengths, c)):
        clen = int(chrom_lengths.get(
            chrom, max(e for _, e in by_chrom[chrom])))
        segs = by_chrom[chrom]
        cap = bucket_size(len(segs))
        ss = np.zeros(cap, np.int32)
        se = np.zeros(cap, np.int32)
        keep = np.zeros(cap, bool)
        ss[:len(segs)] = [s for s, _ in segs]
        se[:len(segs)] = [e for _, e in segs]
        keep[:len(segs)] = True
        depth = depth_from_segments(jnp.asarray(ss), jnp.asarray(se),
                                    jnp.asarray(keep), clen)
        starts, ends, lpad, rpad = window_bounds(0, clen, window)
        sums = np.asarray(windowed_sums(depth, clen, window, lpad,
                                        rpad), dtype=np.int64)
        spans = (ends - starts).astype(np.int64)
        for s, e, total, span in zip(starts, ends, sums, spans):
            m = total / span if span else 0.0
            out.append(f"{chrom}\t{s}\t{e}\t{m:.4g}\n")
    return "".join(out).encode()
