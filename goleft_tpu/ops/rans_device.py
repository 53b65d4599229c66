"""Device-resident rANS Nx16 entropy decode (CRAM 3.1 method 5).

Round-2 numbers put device-resident coverage compute at 51.7 Gbases/s
but only 0.155 Gbases/s over the packed wire: host entropy decode plus
H2D transfer is THE speed ceiling (ROADMAP "Close the wire gap"), and
GenPIP's (PAPERS.md) whole thesis is that fusing decode with compute
kills the data-movement wall. This module moves the CRAM block decode
next to the coverage kernels: *compressed* block bytes cross the wire
and the interleaved-rANS state machine runs on the device.

The decoder state machine as a ``lax.scan``
-------------------------------------------
An Nx16 stream decodes round-robin: out[i] advances state i mod N
(N = 4 or 32). One *round* therefore advances all N states — the N
lanes are data-independent within a round except for the shared renorm
byte stream. The scan runs over rounds with carry (R[N] states, read
pointer); each round is pure vector math plus gathers:

  - slot lookup: ``m = R & 0xFFF`` indexes the 4096-entry slot tables
    (symbol / freq / bias), expanded ON DEVICE from the shipped
    (freq[256], cum[257]) int32 arrays by a vectorized searchsorted —
    the wire carries ~2KB of table per block instead of the 48KB
    materialized slot arrays
  - 16-bit renorm as masked gathers: a lane whose next state drops
    below 2^15 reads a little-endian 16-bit word from the shared byte
    stream. Within a round the scalar decoder reads lanes in order, so
    lane j's word sits at ``pos + 2*rank(j)`` where rank counts
    earlier lanes renormalizing this round (an exclusive cumsum); the
    bytes-left guard truncates at the same lane the scalar loop stops
    at, because a denied lane leaves every later lane denied too.

ORDER1 fits the same scan shape: the per-context frequency rows
become a ``(ctx, slot)`` gather against a ``(n_ctx, 2^shift)`` slot
table expanded on device by the same searchsorted (one row per
context present in the shipped compact table — CRAM serializes these
tables themselves order-0-compressed; ``io/rans_nx16.py`` parses them
host-side, O(table) not O(payload)). Each of the N interleaved states
carries its PREVIOUS SYMBOL as a context lane in the scan carry, and
the N lanes decode contiguous output slices (lane j owns
``[j·F, (j+1)·F)`` with the last lane carrying the tail) exactly as
the host oracle walks them — the post-scan gather maps the
round-major scan output back to lane-sliced order. A context absent
from the table raises the host's missing-context error via a carried
diagnostic bit.

CAT blocks skip the scan (payload = literals); RLE and PACK expansion
run as vectorized gathers on the scan/CAT output (cumsum + searchsorted
for run expansion, shift/mask gathers for bit-unpacking). STRIPE
containers dispatch their N' byte-interleaved sub-streams through the
same bucketed machinery (each lane is a complete Nx16 stream), then a
batched transpose-interleave gather reassembles the container — one
call per stripe signature. Together: the full CRAM 3.1 method-5
matrix ORDER0/ORDER1 × CAT × PACK × RLE × NOSZ × STRIPE for both
N=4 and X32 decodes device-resident; only corrupt/foreign streams
fall back (``decode.device_fallback_total``).

Parallelism and compiles: one block is only N lanes wide, so the real
vector width comes from vmapping over many blocks at once. Blocks pad
to power-of-two bucket signatures (payload length, round count,
expansion caps) exactly like ops/pairhmm.py's length bucketing, so a
whole cohort compiles O(#buckets) programs, not O(#shapes). With
ORDER1 × STRIPE the signature space is wider, so a process-wide cap
(``MAX_BUCKET_SIGNATURES``) bounds total compiles: blocks whose NEW
signature would exceed it decode on host (a per-block fallback, not
an error), visible via ``decode.bucket_signatures`` /
``decode.bucket_cap_fallback_total`` and one log line when the cap
first trips.

``DeviceBlockDecoder`` is the CRAM-facing object: io/cram.py hands it
a container's raw (still compressed) blocks, supported rANS blocks
batch-decode on device through a content-keyed plan Step at the
``decode`` fault site (retry/quarantine compose exactly like every
other dispatch), everything else falls back per-block to the host
codecs, byte-identically.
"""

from __future__ import annotations

import threading

import numpy as np

from ..io import rans_nx16 as _rx
from ..io.rans_nx16 import ParsedNx16, parse_nx16
from ..obs import get_registry
from ..obs.logging import get_logger

TF_SHIFT = _rx.TF_SHIFT
TOTFREQ = _rx.TOTFREQ
RANS_LOW = _rx.RANS_LOW

log = get_logger("ops.rans_device")

#: minimum pad bucket for payload/output axes (pow-2 above, like
#: pairhmm's BUCKET: arbitrary block sizes compile O(#buckets))
MIN_BUCKET = 64

#: process-wide cap on DISTINCT compile signatures (decode buckets +
#: stripe interleave shapes). Each signature is one XLA program kept
#: for the process lifetime; ORDER1 adds (shift, n_ctx_cap) axes and
#: STRIPE multiplies by lane shapes, so an adversarial cohort could
#: otherwise force unbounded compiles. Blocks whose NEW signature
#: would exceed the cap decode on host — a per-block fallback, never
#: an error. Sizing: a real cohort's blocks share a writer, so its
#: shapes collapse to a handful of pow-2 buckets per (N, flags)
#: combo — the 4-sample mixed-matrix smoke cohort compiles ~50;
#: 128 leaves 2-3x headroom before the graceful degradation starts.
MAX_BUCKET_SIGNATURES = 128

_SIG_LOCK = threading.Lock()
_SEEN_SIGS: set[tuple] = set()
_CAP_TRIPPED = False


def bucket(n: int, minimum: int = MIN_BUCKET) -> int:
    b = minimum
    while b < n:
        b <<= 1
    return b


def reset_signature_registry() -> None:
    """Test hook: forget admitted signatures (the jit cache keeps its
    compiled programs — this only re-opens admission)."""
    global _CAP_TRIPPED
    with _SIG_LOCK:
        _SEEN_SIGS.clear()
        _CAP_TRIPPED = False


def _admit_signatures(sigs: list[tuple]) -> bool:
    """Admit a block's compile signatures against the process cap,
    all-or-nothing (a stripe block needs every lane signature plus its
    interleave shape). Over the cap, NEW signatures are refused and
    the block falls back to the host codec; already-seen signatures
    always pass (their programs exist)."""
    global _CAP_TRIPPED
    with _SIG_LOCK:
        # dict.fromkeys dedupes in the caller's deterministic order
        # (signatures mix tuple layouts, so they don't sort)
        fresh = [s for s in dict.fromkeys(sigs)
                 if s not in _SEEN_SIGS]
        if not fresh:
            return True
        if len(_SEEN_SIGS) + len(fresh) > MAX_BUCKET_SIGNATURES:
            if not _CAP_TRIPPED:
                _CAP_TRIPPED = True
                log.warning(
                    "decode: bucket-signature cap reached (%d); new "
                    "block shapes fall back to the host codec "
                    "(decode.bucket_cap_fallback_total counts them)",
                    MAX_BUCKET_SIGNATURES)
            return False
        _SEEN_SIGS.update(fresh)
        get_registry().counter("decode.bucket_signatures").inc(
            len(fresh))
        return True


# ------------------------------------------------------------ XLA path

# jax.jit is applied lazily in _jitted() — this module must import
# without jax (the jax-free fleet/router processes import the package)
def _decode_bucket_impl(payload, plen, states, freq, inner_len,
                        rle_tab, runs, rle_out, pmap, bits, final_len,
                        ctx_index, ctx_freq, alphabet, *, rounds,
                        n_states, cat, rle, pack, order1, shift,
                        n_ctx_cap, lit_cap, mid_cap, out_cap):
    """One padded bucket: (B, …) arrays → ((B, out_cap) uint8 bytes,
    (B, 4) int32 diagnostics [rle_total, marked_total, pack_vmax,
    missing_ctx]).

    Static flags (cat/rle/pack/order1) specialize the program per
    combo; the identity stages compile away. All shapes are the bucket
    caps, all true lengths are traced scalars — one compile per
    signature.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    N = n_states
    lanes = jnp.arange(max(N, 1), dtype=jnp.int32)
    ms = jnp.arange(TOTFREQ, dtype=jnp.int32)

    def one(payload, plen, R0, freq, inner_len, rle_tab, runs,
            rle_out, pmap, bits, final_len, ctx_index, ctx_freq,
            alphabet):
        P = payload.shape[0]
        bad_ctx = jnp.int32(0)
        if cat:
            lit = payload[:lit_cap]
        elif order1:
            # per-context slot tables: the shipped doubly compact
            # (n_ctx, n_ctx) rows (columns are alphabet positions,
            # not raw symbols) expand into (n_ctx_cap, 2^shift)
            # sym/freq/bias tables by the same searchsorted used for
            # ORDER0 — the slot lookup becomes a (ctx_row, slot)
            # gather, with the alphabet mapping the compact column
            # index back to the emitted byte. Each lane carries its
            # previous symbol; ctx_index maps it to its table row
            # (-1 = context absent from the alphabet → the host's
            # missing-context error, carried as a diag bit). Lane j
            # decodes the contiguous slice [j·F, (j+1)·F) with the
            # last lane carrying the tail, so the active mask is
            # per-lane-length, not round-robin.
            target = 1 << shift
            ms1 = jnp.arange(target, dtype=jnp.int32)
            cf = ctx_freq.astype(jnp.int32)
            cum1 = jnp.concatenate([
                jnp.zeros((n_ctx_cap, 1), jnp.int32),
                jnp.cumsum(cf, axis=1, dtype=jnp.int32)], axis=1)
            col1 = jnp.clip(jax.vmap(
                lambda c: jnp.searchsorted(c, ms1, side="right"))(
                    cum1).astype(jnp.int32) - 1, 0, n_ctx_cap - 1)
            freq1 = jnp.take_along_axis(cf, col1, axis=1) \
                .astype(jnp.uint32)
            bias1 = (ms1[None, :] - jnp.take_along_axis(
                cum1, col1, axis=1)).astype(jnp.uint32)
            sym1 = alphabet.astype(jnp.int32)[col1]
            ci = ctx_index.astype(jnp.int32)
            F = inner_len // N
            rem = inner_len - F * N
            lens = F + jnp.where(lanes == N - 1, rem, 0)

            def round1_fn(carry, r):
                R, pos, last, bad = carry
                active = r < lens
                row = ci[last]
                bad = bad | jnp.any(
                    active & (row < 0)).astype(jnp.int32)
                rowc = jnp.clip(row, 0, n_ctx_cap - 1)
                m = (R & jnp.uint32(target - 1)).astype(jnp.int32)
                s = sym1[rowc, m]
                x = freq1[rowc, m] * (R >> jnp.uint32(shift)) \
                    + bias1[rowc, m]
                want = active & (x < jnp.uint32(RANS_LOW))
                avail = jnp.maximum(jnp.int32(0), (plen - pos) // 2)
                wi = want.astype(jnp.int32)
                rank = jnp.cumsum(wi, dtype=jnp.int32) - wi
                need = want & (rank < avail)
                offs = pos + 2 * rank
                b0 = payload[jnp.clip(offs, 0, P - 1)] \
                    .astype(jnp.uint32)
                b1 = payload[jnp.clip(offs + 1, 0, P - 1)] \
                    .astype(jnp.uint32)
                xr = (x << jnp.uint32(16)) | b0 | (b1 << jnp.uint32(8))
                x = jnp.where(need, xr, x)
                R = jnp.where(active, x, R)
                pos = pos + 2 * jnp.sum(need, dtype=jnp.int32)
                last = jnp.where(active, s, last)
                return (R, pos, last, bad), s.astype(jnp.uint8)

            (_, _, _, bad_ctx), syms = lax.scan(
                round1_fn,
                (R0, jnp.int32(0),
                 jnp.zeros(N, jnp.int32), jnp.int32(0)),
                jnp.arange(rounds, dtype=jnp.int32))
            # syms[r, j] is out[j·F + r]: gather back to lane-sliced
            # linear order (position p belongs to lane
            # min(p // F, N-1) — every p ≥ (N-1)·F is the last lane's)
            pidx = jnp.arange(lit_cap, dtype=jnp.int32)
            jl = jnp.where(pidx < (N - 1) * F,
                           pidx // jnp.maximum(F, 1),
                           jnp.int32(N - 1))
            rr = pidx - jl * F
            lit = syms.reshape(rounds * N)[
                jnp.clip(rr * N + jl, 0, rounds * N - 1)]
        else:
            # the wire ships only the int16 frequency row (~0.5KB);
            # cum and the 4096-entry slot tables expand on device. The
            # largest s with cum[s] <= m is the scalar decoder's lut
            # for every normalized table (zero-freq symbols collapse
            # to equal cum entries, skipped by side="right")
            cum = jnp.concatenate([
                jnp.zeros(1, jnp.int32),
                jnp.cumsum(freq, dtype=jnp.int32)])
            sym = jnp.clip(
                jnp.searchsorted(cum, ms, side="right").astype(
                    jnp.int32) - 1, 0, 255)
            sfreq = freq[sym].astype(jnp.uint32)  # freq ≤ 4096: exact
            sbias = (ms - cum[sym]).astype(jnp.uint32)

            def round_fn(carry, r):
                R, pos = carry
                active = (r * N + lanes) < inner_len
                m = (R & jnp.uint32(TOTFREQ - 1)).astype(jnp.int32)
                s = sym[m]
                x = sfreq[m] * (R >> jnp.uint32(TF_SHIFT)) + sbias[m]
                want = active & (x < jnp.uint32(RANS_LOW))
                avail = jnp.maximum(jnp.int32(0), (plen - pos) // 2)
                wi = want.astype(jnp.int32)
                rank = jnp.cumsum(wi, dtype=jnp.int32) - wi
                need = want & (rank < avail)
                offs = pos + 2 * rank
                b0 = payload[jnp.clip(offs, 0, P - 1)] \
                    .astype(jnp.uint32)
                b1 = payload[jnp.clip(offs + 1, 0, P - 1)] \
                    .astype(jnp.uint32)
                xr = (x << jnp.uint32(16)) | b0 | (b1 << jnp.uint32(8))
                x = jnp.where(need, xr, x)
                R = jnp.where(active, x, R)
                pos = pos + 2 * jnp.sum(need, dtype=jnp.int32)
                return (R, pos), s.astype(jnp.uint8)

            (_, _), syms = lax.scan(
                round_fn, (R0, jnp.int32(0)),
                jnp.arange(rounds, dtype=jnp.int32))
            lit = syms.reshape(rounds * N)[:lit_cap]

        # ---- RLE expansion: each marked literal repeats 1 + its run
        # extension; output position p maps back to the literal whose
        # cumulative start covers it (searchsorted over the exclusive
        # cumsum — the vectorized form of the host's sequential walk)
        if rle:
            idx = jnp.arange(lit_cap, dtype=jnp.int32)
            in_range = idx < inner_len
            marked = rle_tab[lit.astype(jnp.int32)] & in_range
            mi = marked.astype(jnp.int32)
            rank = jnp.cumsum(mi, dtype=jnp.int32) - mi
            rcap = runs.shape[0]
            rep = jnp.where(
                in_range,
                1 + jnp.where(marked,
                              runs[jnp.clip(rank, 0, rcap - 1)], 0),
                0)
            starts = jnp.cumsum(rep, dtype=jnp.int32) - rep
            rle_total = starts[-1] + rep[-1]
            marked_total = jnp.sum(mi, dtype=jnp.int32)
            posn = jnp.arange(mid_cap, dtype=jnp.int32)
            src = jnp.clip(
                jnp.searchsorted(starts, posn, side="right").astype(
                    jnp.int32) - 1, 0, lit_cap - 1)
            mid = jnp.where(posn < rle_out, lit[src],
                            jnp.uint8(0))
            mid_len = rle_out
        else:
            mid = lit
            mid_len = inner_len
            rle_total = inner_len
            marked_total = jnp.int32(0)

        # ---- PACK expansion: shift/mask gathers (bits ∈ {0,1,2,4},
        # LSB-first like the host's _unpack)
        if pack:
            i = jnp.arange(out_cap, dtype=jnp.int32)
            per = 8 // jnp.maximum(bits, 1)
            idxp = jnp.clip(i // per, 0, mid_cap - 1)
            sh = bits * (i % per)
            maskb = (jnp.int32(1) << bits) - 1
            v = (mid[idxp].astype(jnp.int32) >> sh) & maskb
            vc = jnp.clip(v, 0, 15)
            outb = jnp.where(bits == 0, pmap[0], pmap[vc]) \
                .astype(jnp.uint8)
            vmax = jnp.max(jnp.where((i < final_len) & (bits > 0),
                                     v, 0))
        else:
            outb = mid
            vmax = jnp.int32(0)
        del mid_len
        diag = jnp.stack([rle_total.astype(jnp.int32),
                          marked_total, vmax, bad_ctx])
        return outb, diag

    return jax.vmap(one)(payload, plen, states, freq, inner_len,
                         rle_tab, runs, rle_out, pmap, bits,
                         final_len, ctx_index, ctx_freq, alphabet)


def _interleave_impl(lanes_arr, final_len, *, n_lanes, out_cap):
    """Batched STRIPE reassembly: (B, n_lanes, lane_cap) decoded lane
    bytes → (B, out_cap) interleaved output. Output position i comes
    from lane ``i mod N'`` at offset ``i // N'`` — the transpose-
    interleave the host does with strided assignment, as one gather
    per stripe signature."""
    import jax
    import jax.numpy as jnp

    idx = jnp.arange(out_cap, dtype=jnp.int32)

    def one(lanes_b, flen):
        out = lanes_b[idx % n_lanes, idx // n_lanes]
        return jnp.where(idx < flen, out, jnp.uint8(0)) \
            .astype(jnp.uint8)

    return jax.vmap(one)(lanes_arr, final_len)


_JIT_CACHE: dict = {}


def _jitted():
    fn = _JIT_CACHE.get("xla")
    if fn is None:
        import jax

        fn = jax.jit(_decode_bucket_impl, static_argnames=(
            "rounds", "n_states", "cat", "rle", "pack", "order1",
            "shift", "n_ctx_cap", "lit_cap", "mid_cap", "out_cap"))
        _JIT_CACHE["xla"] = fn
    return fn


def _jitted_interleave():
    fn = _JIT_CACHE.get("ilv")
    if fn is None:
        import jax

        fn = jax.jit(_interleave_impl,
                     static_argnames=("n_lanes", "out_cap"))
        _JIT_CACHE["ilv"] = fn
    return fn


# ---------------------------------------------------------- batch glue

def _signature(p: ParsedNx16) -> tuple:
    """Pad-to-bucket compile signature (pairhmm-style): every axis
    rounds up to a power of two so arbitrary cohorts stay O(#buckets)
    compiles. ORDER1 adds (shift, n_ctx_cap) axes and widens the
    round count by N-1 (the last lane's tail rounds beyond F)."""
    n = p.n_states
    lit_cap = bucket(max(p.inner_len, 1))
    if not p.cat:
        rounds = (lit_cap + n - 1) // n
        lit_cap = rounds * n
        if p.order1:
            # lane j needs F = inner//N rounds, the last lane F+rem
            # with rem < N; F ≤ lit_cap//N so this covers every block
            # in the bucket
            rounds += n - 1
    else:
        rounds = 0
    p_cap = bucket(max(p.payload.shape[0], 1))
    if p.cat:
        p_cap = max(p_cap, lit_cap)  # CAT payload IS the literals
    mid_cap = bucket(max(p.rle_out_len, 1)) if p.rle else lit_cap
    out_cap = bucket(max(p.final_len, 1)) if p.pack else mid_cap
    runs_cap = bucket(len(p.rle_runs) if p.rle_runs is not None
                      else 0, minimum=16)
    shift = p.shift if p.order1 else TF_SHIFT
    n_ctx_cap = bucket(max(p.n_ctx, 1), minimum=16) if p.order1 else 1
    return (n, p.cat, p.order1, shift, p.rle, p.pack, rounds, p_cap,
            lit_cap, mid_cap, out_cap, runs_cap, n_ctx_cap)


def _stripe_shape(p: ParsedNx16) -> tuple[int, int, int]:
    """(n_lanes, lane_cap, out_cap) of a stripe container's batched
    interleave dispatch."""
    lane_cap = bucket(max((p.final_len + p.n_lanes - 1) // p.n_lanes,
                          1))
    return (p.n_lanes, lane_cap, bucket(max(p.final_len, 1)))


def plan_signatures(p: ParsedNx16) -> list[tuple]:
    """Every compile signature decoding this block requires (a stripe
    container needs each lane's bucket plus its interleave shape) —
    the admission unit for the ``MAX_BUCKET_SIGNATURES`` cap."""
    if p.stripe:
        sigs = [_signature(ch) for ch in p.children or []]
        sigs.append(("ilv",) + _stripe_shape(p))
        return sigs
    return [_signature(p)]


def _decode_flat(plans: list[ParsedNx16], *, stage,
                 device_idx: set[int] | None = None) -> list:
    """The bucketed + vmapped dispatch over non-stripe plans.

    ``device_idx`` marks plan indices whose decoded output should stay
    device-resident: those entries come back as the bucket's (out_cap,)
    uint8 device row instead of host bytes (valid through the plan's
    ``final_len``; trailing lanes are whatever the kernel left there).
    STRIPE reassembly uses this so lane bytes feed the interleave
    gather without a device→host→device round-trip."""
    results: list = [None] * len(plans)
    groups: dict[tuple, list[int]] = {}
    for i, p in enumerate(plans):
        groups.setdefault(_signature(p), []).append(i)
    for sig in sorted(groups):
        idxs = groups[sig]
        (n, cat, order1, shift, rle, pack, rounds, p_cap, lit_cap,
         mid_cap, out_cap, runs_cap, n_ctx_cap) = sig
        grp = [plans[i] for i in idxs]
        B = len(grp)
        payload = np.zeros((B, p_cap), np.uint8)
        plen = np.zeros(B, np.int32)
        states = np.zeros((B, max(n, 1)), np.uint32)
        # freq ships int16 (≤ 4096 each); cum expands on device
        freq = np.zeros((B, 256), np.int16)
        inner = np.zeros(B, np.int32)
        rle_tab = np.zeros((B, 256), bool)
        runs = np.zeros((B, runs_cap), np.int32)
        rle_out = np.zeros(B, np.int32)
        pmap = np.zeros((B, 16), np.int32)
        bits = np.zeros(B, np.int32)
        final = np.zeros(B, np.int32)
        # ORDER1 doubly compact context rows (int16 on the wire,
        # ≤ 4096 each; columns are alphabet positions) + the ctx→row
        # map + the column→symbol alphabet; (B, 1, 1)/(B, 1) dummies
        # for ORDER0 groups so the jit signature stays uniform
        ctx_index = np.full((B, 256), -1, np.int16)
        ctx_freq = np.zeros((B, n_ctx_cap, n_ctx_cap), np.int16)
        alphabet = np.zeros((B, n_ctx_cap), np.int16)
        for j, p in enumerate(grp):
            payload[j, :p.payload.shape[0]] = p.payload
            plen[j] = p.payload.shape[0]
            inner[j] = p.inner_len
            final[j] = p.final_len
            if not cat:
                states[j] = p.states
                if order1:
                    ctx_index[j] = p.ctx_index
                    ctx_freq[j, :p.n_ctx, :p.n_ctx] = \
                        p.ctx_freq.astype(np.int16)
                    alphabet[j, :p.n_ctx] = p.alphabet
                else:
                    freq[j] = p.freq.astype(np.int16)
            if rle:
                rle_tab[j] = p.rle_tab
                runs[j, :len(p.rle_runs)] = p.rle_runs
                rle_out[j] = p.rle_out_len
            if pack:
                pmap[j] = p.pack_map
                bits[j] = p.pack_bits
        host = dict(payload=payload, plen=plen, states=states,
                    freq=freq, inner=inner, rle_tab=rle_tab,
                    runs=runs, rle_out=rle_out, pmap=pmap, bits=bits,
                    final=final, ctx_index=ctx_index,
                    ctx_freq=ctx_freq, alphabet=alphabet)
        if stage is None:
            import jax

            dev = {k: jax.device_put(v) for k, v in host.items()}
        else:
            dev = stage(host)
        from ..obs.compiles import TRACKER

        # exact per-bucket compile attribution against the shared jit
        # object's own cache (one geometry = one cache entry)
        jit_fn = _jitted()
        cache_size = jit_fn._cache_size
        with TRACKER.observe("rans", signature=sig,
                             cache_size_fn=cache_size,
                             trigger="rans_decode"):
            out, diag = jit_fn(
                dev["payload"], dev["plen"], dev["states"],
                dev["freq"], dev["inner"],
                dev["rle_tab"], dev["runs"], dev["rle_out"],
                dev["pmap"], dev["bits"], dev["final"],
                dev["ctx_index"], dev["ctx_freq"],
                dev["alphabet"],
                rounds=rounds, n_states=n, cat=cat, rle=rle,
                pack=pack, order1=order1, shift=shift,
                n_ctx_cap=n_ctx_cap, lit_cap=lit_cap,
                mid_cap=mid_cap, out_cap=out_cap)
        diag = np.asarray(diag)
        keep = device_idx or ()
        # bulk host fetch only when no row of this bucket stays on
        # device; mixed buckets fetch their host rows individually
        host_out = np.asarray(out) \
            if not any(i in keep for i in idxs) else None
        for j, (i, p) in enumerate(zip(idxs, grp)):
            if order1 and int(diag[j, 3]):
                raise ValueError(
                    "rans-nx16: missing order-1 context")
            if rle:
                if int(diag[j, 0]) != p.rle_out_len:
                    raise ValueError(
                        "rans-nx16: rle expansion length mismatch")
                if p.rle_runs is not None \
                        and int(diag[j, 1]) > len(p.rle_runs):
                    raise ValueError(
                        "rans-nx16: rle metadata exhausted")
            if pack and p.pack_bits > 0 \
                    and int(diag[j, 2]) >= p.pack_nsym:
                raise ValueError(
                    "rans-nx16: pack index out of range")
            if i in keep:
                results[i] = out[j]
            elif host_out is not None:
                results[i] = bytes(host_out[j, :p.final_len])
            else:
                results[i] = bytes(np.asarray(out[j, :p.final_len]))
    return results


def decode_parsed(plans: list[ParsedNx16], *,
                  stage=None) -> list[bytes]:
    """Decode parsed streams on device, bucketed + vmapped; returns
    bytes per stream, byte-identical to ``rans_nx16.decode``.

    STRIPE containers flatten into their lane sub-streams (decoded
    through the same buckets as standalone blocks), then reassemble
    via one batched transpose-interleave gather per stripe shape.
    Lane outputs stay device-resident between the decode buckets and
    the interleave dispatch — only the final interleaved block is
    fetched to the host (plain rows fetch as before).

    ``stage``: optional callable mapping a dict of host arrays to
    device arrays (parallel.prefetch.stage_block_arrays — the
    compressed-wire staging/accounting step); default stages without
    accounting.
    """
    flat: list[ParsedNx16] = []
    spec: list[tuple] = []
    lane_idx: set[int] = set()
    for p in plans:
        if p.stripe:
            idxs = []
            for ch in p.children or []:
                idxs.append(len(flat))
                lane_idx.add(len(flat))
                flat.append(ch)
            spec.append(("stripe", idxs, p))
        else:
            spec.append(("plain", len(flat), p))
            flat.append(p)
    decoded = _decode_flat(flat, stage=stage, device_idx=lane_idx)

    results: list[bytes | None] = [None] * len(plans)
    stripe_groups: dict[tuple, list[int]] = {}
    for i, entry in enumerate(spec):
        if entry[0] == "plain":
            results[i] = decoded[entry[1]]
        else:
            stripe_groups.setdefault(_stripe_shape(entry[2]),
                                     []).append(i)
    if stripe_groups:
        import jax.numpy as jnp
    for shape in sorted(stripe_groups):
        n_lanes, lane_cap, out_cap = shape
        members = stripe_groups[shape]
        B = len(members)
        rows = []
        flens = np.zeros(B, np.int32)
        for b, i in enumerate(members):
            _, idxs, p = spec[i]
            flens[b] = p.final_len
            for k in idxs:
                # device row from the lane's decode bucket: valid
                # through the lane's final_len, and the interleave
                # gather never reads past it for output positions
                # < final_len (lane j holds exactly ceil((flen-j)/N)
                # bytes), so pad/trim to lane_cap without re-zeroing
                r = decoded[k]
                if r.shape[0] >= lane_cap:
                    r = r[:lane_cap]
                else:
                    r = jnp.pad(r, (0, lane_cap - r.shape[0]))
                rows.append(r)
        lanes_arr = jnp.stack(rows).reshape(B, n_lanes, lane_cap)
        out = np.asarray(_jitted_interleave()(
            lanes_arr, flens, n_lanes=n_lanes, out_cap=out_cap))
        for b, i in enumerate(members):
            results[i] = bytes(out[b, :spec[i][2].final_len])
    return results


def decode_streams(datas: list[bytes],
                   expected_lens: list[int | None] | None = None
                   ) -> list[bytes | None]:
    """Parse + device-decode many standalone Nx16 streams; None marks
    a stream that stays host-side (unsupported/corrupt layout, or a
    new bucket shape past the signature cap — the caller falls back
    to ``rans_nx16.decode``). The fuzz-parity surface tests pin
    against the host oracle."""
    if expected_lens is None:
        expected_lens = [None] * len(datas)
    plans, order = [], []
    results: list[bytes | None] = [None] * len(datas)
    for i, (d, el) in enumerate(zip(datas, expected_lens)):
        p = parse_nx16(d, el)
        if p is not None and _admit_signatures(plan_signatures(p)):
            plans.append(p)
            order.append(i)
    decoded = decode_parsed(plans)
    for i, b in zip(order, decoded):
        results[i] = b
    return results


# ------------------------------------------------- CRAM block decoder

class DeviceBlockDecoder:
    """Per-container CRAM block decode with the entropy stage on
    device.

    io/cram.py hands :meth:`decode_blocks` one container's raw (still
    compressed) blocks. rANS-Nx16 blocks batch-decode in one bucketed
    vmapped dispatch — the full method-5 matrix (ORDER0/ORDER1 ×
    CAT/PACK/RLE/NOSZ/STRIPE, N=4/X32) — as a content-keyed plan Step
    at the ``decode`` fault site, so a transient device fault costs
    one backoff and the per-sample quarantine above composes
    unchanged. The fallback surface is now corrupt/foreign rANS
    streams and new bucket shapes past ``MAX_BUCKET_SIGNATURES``
    (``decode.device_fallback_total``; cap refusals additionally in
    ``decode.bucket_cap_fallback_total``); non-rANS methods decode on
    host as before (``decode.host_blocks_total``).

    Wire accounting (the point of the exercise): compressed payload
    plus the table arrays per block cross the link instead of the
    inflated bytes — ~0.5KB of table for ORDER0, ~(n_ctx+2)·0.5KB
    for ORDER1's compact context rows (``decode.table_bytes_total``
    isolates that share) — ``decode.wire_bytes_compressed_total`` vs
    ``decode.wire_bytes_uncompressed_total``; the staging itself runs
    through parallel.prefetch.stage_block_arrays so the existing
    prefetch byte counters and stage spans record it.
    """

    def __init__(self, policy=None):
        from ..plan import Executor
        from ..resilience.policy import DEFAULT_POLICY

        self._pex = Executor(policy=policy if policy is not None
                             else DEFAULT_POLICY)
        reg = get_registry()
        self._c_dev = reg.counter("decode.device_blocks_total")
        self._c_fall = reg.counter("decode.device_fallback_total")
        self._c_cap = reg.counter("decode.bucket_cap_fallback_total")
        self._c_host = reg.counter("decode.host_blocks_total")
        self._c_wire_c = reg.counter("decode.wire_bytes_compressed_total")
        self._c_wire_u = reg.counter(
            "decode.wire_bytes_uncompressed_total")
        self._c_table = reg.counter("decode.table_bytes_total")

    def _stage(self, host_arrays: dict) -> dict:
        from ..parallel.prefetch import stage_block_arrays

        return stage_block_arrays(host_arrays)

    def decode_blocks(self, raws) -> list[bytes]:
        """raw blocks (io.cram.RawBlock) → uncompressed bytes, in
        order; byte-identical to the host path for every block."""
        from ..io import cram as _cram

        results: list[bytes | None] = [None] * len(raws)
        plans: list[ParsedNx16] = []
        order: list[int] = []
        for i, rb in enumerate(raws):
            if rb.method == _cram.M_RANSNX16:
                p = parse_nx16(rb.raw, rb.rsize)
                if p is not None:
                    if _admit_signatures(plan_signatures(p)):
                        plans.append(p)
                        order.append(i)
                        continue
                    self._c_cap.inc()
                self._c_fall.inc()
            elif rb.method != _cram.M_RAW:
                self._c_host.inc()
            results[i] = _cram._decompress(rb.method, rb.raw,
                                           rb.rsize)
        if plans:
            from ..plan import Step

            table_b = sum(p.table_bytes for p in plans)
            wire_c = sum(p.payload_bytes for p in plans) + table_b
            wire_u = sum(p.final_len for p in plans)
            crc = tcrc = 0
            for p in plans:
                crc = p.payload_crc(crc)
                tcrc = p.table_crc(tcrc)
            # the table CRC joins the content key: same payload bytes
            # under a different table is a different decode
            key = ("decode", len(plans), wire_c, crc, tcrc)
            decoded = self._pex.run(Step(
                key=key, site="decode", span="decode.device",
                attrs={"blocks": len(plans), "wire_bytes": wire_c},
                fn=lambda: decode_parsed(plans, stage=self._stage)))
            self._c_dev.inc(len(plans))
            self._c_wire_c.inc(wire_c)
            self._c_wire_u.inc(wire_u)
            self._c_table.inc(table_b)
            for i, b in zip(order, decoded):
                results[i] = b
        return results
