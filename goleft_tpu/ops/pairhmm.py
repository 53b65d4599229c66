"""Pair-HMM forward likelihood: anti-diagonal wavefront on the device.

The genotype-likelihood kernel behind ``goleft-tpu pairhmm`` — the
GATK-class forward pass P(read | haplotype) that gpuPairHMM / Endeavor
(PAPERS.md) identify as the field's consensus bottleneck after
coverage. Three DP matrices over read (rows) × haplotype (cols):

    M[i,j] = prior(i,j)·(tMM·M[i-1,j-1] + tIM·I[i-1,j-1]
                                        + tDM·D[i-1,j-1])
    I[i,j] = tMI·M[i-1,j] + tII·I[i-1,j]
    D[i,j] = tMD·M[i,j-1] + tDD·D[i,j-1]

with the free-start first row (M=I=0, D[0,j]=1/|hap|), transitions
from phred gap-open/extend scores (δ=10^(-open/10), ε=10^(-ext/10);
tMM=1-2δ, tMI=tMD=δ, tIM=tDM=1-ε, tII=tDD=ε), emission priors from
per-base qualities (match 1-err, mismatch err/3, N always matches),
and L = Σ_j M[R,j] + I[R,j].

Cell (i,j) depends only on diagonals i+j-1 and i+j-2, so the sweep
runs over anti-diagonals: each of the R+H wavefront steps updates
three (R+1)-vectors with shifts and elementwise math — one vectorized
sweep per step instead of a sequential cell loop, which is what makes
the recurrence a device kernel at all. Batches vmap over the wavefront
with padded reads/haps; padding is masked to exact zeros every step,
so a pair's result is **bitwise independent** of its bucket shape and
batch neighbors (tests/test_pairhmm.py pins this — it is what lets
the serve executor coalesce requests byte-identically).

f32 with per-row rescaling (the gpuPairHMM/Endeavor trick that avoids
f64), adapted to the wavefront: the diagonal buffers are indexed by
read row, so each lane carries its own scale counter — lane i's
stored values are the true probabilities times 2^(30·shift[i]).
A single scale per diagonal cannot work here: one anti-diagonal mixes
0-emission boundary cells (constant 1/|hap|) with full-read-prefix
cells hundreds of decades smaller, far beyond f32's exponent range —
measured on a 400bp read, diagonal-global rescaling silently flushes
the dominant paths and loses ~4 log10. Per lane, whenever a row's
live magnitude leaves [2^-30, 2^30] it is renormalized by 2^∓30 and
its counter adjusts (symmetric, because a lane inherits its scale
from the sweep frontier before its own bulk values arrive, and the
two can disagree in either direction); recurrence terms crossing
lanes are reconciled by 2^(30·Δshift), with Δ self-bounding: scales
track each lane's live magnitude, adjacent rows' magnitudes are
within one emission+transition of each other, and a lane stops
renormalizing the moment a differently-scaled neighbor dominates it.
The kernel emits the O(R+H) per-step final-row contributions together
with their scales instead of accumulating on device; the host folds
them with an exact f64 log-sum-exp, so likelihoods far below f32's
range (a 400bp junk read is ~10^-400) come back accurate to ~1e-5
log10 with no running-accumulator scale state at all.

Length bucketing bounds recompiles: pairs group by lengths rounded up
to BUCKET (default 32), so a cohort of arbitrary read/hap lengths
compiles O(#buckets) programs, not O(#shapes). ``forward_pairs`` is
the host entry: encode → bucket → per-bucket dispatch (the
``pairhmm`` fault-injection site, retried under a RetryPolicy) →
scatter back to input order.
"""

from __future__ import annotations

import math

import numpy as np

from ..obs import get_registry

BUCKET = 32  # length-bucket granularity (pads lengths up to this)
#: f32 rescaling: a lane renormalizes by 2^±SCALE_EXP whenever its
#: live max leaves [2^-SCALE_EXP, 2^SCALE_EXP]. 30 keeps every
#: intermediate normal (worst one-step decay, a q93 mismatch times a
#: gap open, is ~2^-48 — the next step's boost catches up) while
#: leaving enough f32 exponent headroom that a cross-lane conversion
#: of up to 2^(30·3) applied to a ≤2^30-ish stored value stays finite.
SCALE_EXP = 30
#: cross-lane scale differences are self-bounding (see module
#: docstring); the clip only ever truncates factors applied to zeros
_DMIN, _DMAX = -4, 3
_LOG10_2 = math.log10(2.0)

# base codes: A C G T = 0..3, N/other = 4 (always treated as a match)
_ENCODE = np.full(256, 4, dtype=np.uint8)
for _i, _b in enumerate(b"ACGT"):
    _ENCODE[_b] = _i
    _ENCODE[ord(chr(_b).lower())] = _i
N_CODE = np.uint8(4)

DEFAULT_GAP_OPEN = 45.0  # phred; δ = 10^-4.5 ≈ 3.2e-5
DEFAULT_GAP_EXT = 10.0   # phred; ε = 0.1


def encode_seq(seq) -> np.ndarray:
    """str/bytes → uint8 base codes (A=0 C=1 G=2 T=3, other=N=4)."""
    if isinstance(seq, np.ndarray):
        return seq.astype(np.uint8)
    if isinstance(seq, str):
        seq = seq.encode("ascii")
    return _ENCODE[np.frombuffer(bytes(seq), dtype=np.uint8)]


def phred_to_err(quals) -> np.ndarray:
    """Phred qualities → base error probabilities, f64."""
    q = np.asarray(quals, dtype=np.float64)
    return np.power(10.0, -q / 10.0)


def transition_probs(gap_open: float = DEFAULT_GAP_OPEN,
                     gap_ext: float = DEFAULT_GAP_EXT) -> np.ndarray:
    """(5,) f64 [tMM, tMI=tMD, tIM=tDM, tII=tDD, delta-unused-pad] —
    computed once in f64; the bucket kernel casts to its compute
    dtype."""
    delta = 10.0 ** (-float(gap_open) / 10.0)
    eps = 10.0 ** (-float(gap_ext) / 10.0)
    return np.array([1.0 - 2.0 * delta, delta, 1.0 - eps, eps, delta],
                    dtype=np.float64)


def _forward_bucket_impl(reads_p, pm, px, rlens, haps, hlens, trans,
                         *, rescale: bool):
    """One padded bucket through the wavefront; vmapped over pairs.

    reads_p: (B, R1) uint8 — read base at diag index i (i is 1-based;
             index 0 is an N sentinel for the boundary row)
    pm/px:   (B, R1) match / mismatch emission priors per read index
    rlens:   (B,) int32 true read lengths
    haps:    (B, H) uint8, hlens (B,) int32
    trans:   (5,) transition probs in the compute dtype

    With ``rescale`` (the f32 path) each lane i — read row i of the
    wavefront — carries its own scale counter: stored = true ·
    2^(30·s[i]). Same-lane terms (the D recurrence) need no
    adjustment; cross-lane terms (M from row i-1 two diagonals back,
    I from row i-1 one back) are multiplied by 2^(30·(s[i]-s[i-1])).
    The difference is self-bounding — scales track each lane's live
    magnitude both up and down, and adjacent rows' magnitudes are
    within one emission·transition of each other — so the clip to
    [_DMIN, _DMAX] only ever truncates factors applied to zeros.
    All-zero lanes adopt their left neighbor's scale: the adoption
    ramp advances one lane per step, in sync with the frontier, so a
    lane enters the sweep at its feeder's scale instead of a stale 0.

    Returns (contribs, shifts): per wavefront step k, the final-row
    contribution M[R, k-R] + I[R, k-R] stored at scale 2^(30·shift) —
    the caller folds them into log10(L) on host with an exact f64
    log-sum-exp (no running-accumulator scale state on device).
    """
    import jax
    import jax.numpy as jnp

    dtype = pm.dtype
    r1 = reads_p.shape[1]
    hcap = haps.shape[1]
    steps = r1 + hcap
    t_mm, t_mi, t_im, t_ii = (trans[0], trans[1], trans[2], trans[3])
    below = jnp.asarray(2.0 ** -SCALE_EXP, dtype)
    above = jnp.asarray(2.0 ** SCALE_EXP, dtype)
    up = jnp.asarray(2.0 ** SCALE_EXP, dtype)
    down = jnp.asarray(2.0 ** -SCALE_EXP, dtype)
    one = jnp.asarray(1.0, dtype)
    zero = jnp.asarray(0.0, dtype)

    def one_pair(read, pmv, pxv, rlen, hap, hlen):
        ii = jnp.arange(r1, dtype=jnp.int32)
        inv_h = one / hlen.astype(dtype)

        def shift1(x):
            # x[i-1] with a zero entering at i=0
            return jnp.concatenate([x[:1] * 0, x[:-1]])

        def scale_fix(s_to, s_from):
            d = jnp.clip(s_to - s_from, _DMIN, _DMAX)
            return jnp.exp2((SCALE_EXP * d).astype(dtype))

        def step(k, carry):
            m1, i1, d1, s1, m2, i2, d2, s2, contribs, shifts = carry
            jj = k - ii
            hb = jnp.where(
                (jj >= 1) & (jj <= hlen),
                hap[jnp.clip(jj - 1, 0, hcap - 1)], N_CODE)
            valid = ((ii >= 1) & (ii <= rlen)
                     & (jj >= 1) & (jj <= hlen))
            is_match = (read == hb) | (read == N_CODE) | (hb == N_CODE)
            prior = jnp.where(is_match, pmv, pxv)
            mterm = (t_mm * shift1(m2) + t_im * shift1(i2)
                     + t_im * shift1(d2))
            iterm = t_mi * shift1(m1) + t_ii * shift1(i1)
            if rescale:
                mterm = mterm * scale_fix(s1, shift1(s2))
                iterm = iterm * scale_fix(s1, shift1(s1))
            mk = prior * mterm
            ik = iterm
            dk = t_mi * m1 + t_ii * d1
            mk = jnp.where(valid, mk, zero)
            ik = jnp.where(valid, ik, zero)
            dk = jnp.where(valid, dk, zero)
            # boundary row i=0: D[0, j] = 1/|hap| (free start), M=I=0.
            # Lane 0's magnitude never drops below 1/|hap| while the
            # boundary is live, so its scale counter stays 0 and the
            # injected constant needs no adjustment.
            d0 = jnp.where(k <= hlen, inv_h, zero)
            dk = dk.at[0].set(d0)
            # final-row contribution: cell (rlen, k-rlen) when in range
            live = (k - rlen >= 1) & (k - rlen <= hlen)
            contribs = contribs.at[k].set(
                jnp.where(live, mk[rlen] + ik[rlen], zero))
            if rescale:
                shifts = shifts.at[k].set(s1[rlen])
                mx = jnp.maximum(jnp.maximum(mk, ik), dk)
                grow = ((mx > zero) & (mx < below)).astype(jnp.int32)
                shrink = (mx > above).astype(jnp.int32)
                f = jnp.where(grow == 1, up,
                              jnp.where(shrink == 1, down, one))
                mk, ik, dk = mk * f, ik * f, dk * f
                s_base = s1 + grow - shrink
                # scale adoption: an all-zero lane's scale is
                # meaningless (0 stores true 0 at any scale), so it
                # tracks its left neighbor — the adoption ramp
                # advances one lane per step, in sync with the
                # wavefront frontier
                s_new = jnp.where(mx > zero, s_base, shift1(s_base))
            else:
                s_new = s1
            return mk, ik, dk, s_new, m1, i1, d1, s1, contribs, shifts

        z = jnp.zeros(r1, dtype)
        zi = jnp.zeros(r1, jnp.int32)
        d_init = z.at[0].set(inv_h)  # diag k=0: cell (0,0)
        init = (z, z, d_init, zi, z, z, z, zi,
                jnp.zeros(steps, dtype), jnp.zeros(steps, jnp.int32))
        out = jax.lax.fori_loop(1, steps, step, init)
        return out[8], out[9]

    return jax.vmap(one_pair)(reads_p, pm, px, rlens, haps, hlens)


def _fold_contribs(contribs: np.ndarray, shifts: np.ndarray
                   ) -> np.ndarray:
    """(B, steps) per-step contributions at per-step scales →
    (B,) log10 likelihood, folded on host in f64 (exact log-sum-exp;
    a pair with no surviving mass comes back -inf)."""
    c = np.asarray(contribs, dtype=np.float64)
    s = np.asarray(shifts, dtype=np.float64)
    with np.errstate(divide="ignore"):
        logv = np.where(c > 0.0,
                        np.log10(np.where(c > 0.0, c, 1.0))
                        - s * (SCALE_EXP * _LOG10_2),
                        -np.inf)
    m = np.max(logv, axis=1)
    safe_m = np.where(np.isfinite(m), m, 0.0)
    tot = np.sum(np.where(np.isfinite(logv),
                          np.power(10.0, logv - safe_m[:, None]), 0.0),
                 axis=1)
    with np.errstate(divide="ignore"):
        return np.where(np.isfinite(m), safe_m + np.log10(tot),
                        -np.inf)


_FORWARD_JIT = None


def _forward_bucket(*args, rescale: bool):
    global _FORWARD_JIT
    if _FORWARD_JIT is None:
        import jax

        _FORWARD_JIT = jax.jit(_forward_bucket_impl,
                               static_argnames=("rescale",))
    return _FORWARD_JIT(*args, rescale=rescale)


def _pad_up(n: int, to: int = BUCKET) -> int:
    return max(to, ((n + to - 1) // to) * to)


def bucket_pairs(reads, haps, bucket: int = BUCKET):
    """Group (read, qual, hap) triples by padded-length signature.

    Returns {(r_pad, h_pad): [indices]} — each bucket compiles one
    program geometry, so arbitrary cohorts cost O(#buckets) compiles.
    """
    groups: dict[tuple[int, int], list[int]] = {}
    for n, (r, h) in enumerate(zip(reads, haps)):
        key = (_pad_up(len(r), bucket), _pad_up(len(h), bucket))
        groups.setdefault(key, []).append(n)
    return groups


def _pack_bucket(idxs, reads, errs, haps, r_pad, h_pad, dtype):
    """Pad one bucket's pairs into the kernel's array layout."""
    b = len(idxs)
    r1 = r_pad + 1  # diag index 0 is the boundary row
    reads_p = np.full((b, r1), N_CODE, dtype=np.uint8)
    pm = np.zeros((b, r1), dtype=dtype)
    px = np.zeros((b, r1), dtype=dtype)
    rlens = np.zeros(b, dtype=np.int32)
    haps_p = np.full((b, h_pad), N_CODE, dtype=np.uint8)
    hlens = np.zeros(b, dtype=np.int32)
    for row, n in enumerate(idxs):
        r, e, h = reads[n], errs[n], haps[n]
        rl, hl = len(r), len(h)
        reads_p[row, 1:rl + 1] = r
        pm[row, 1:rl + 1] = (1.0 - e).astype(dtype)
        px[row, 1:rl + 1] = (e / 3.0).astype(dtype)
        rlens[row] = rl
        haps_p[row, :hl] = h
        hlens[row] = hl
    return reads_p, pm, px, rlens, haps_p, hlens


def forward_pairs(reads, quals, haps, *,
                  gap_open: float = DEFAULT_GAP_OPEN,
                  gap_ext: float = DEFAULT_GAP_EXT,
                  dtype=np.float32, bucket: int = BUCKET,
                  policy=None) -> np.ndarray:
    """log10 P(read|hap) for N (read, qual, hap) triples → (N,) f64.

    reads/haps: sequences (str or uint8 codes), quals: per-base phred
    arrays (or a scalar applied to the whole read). Pairs are length-
    bucketed, each bucket runs one vmapped wavefront dispatch — the
    ``pairhmm`` fault-injection site, executed under ``policy`` (a
    resilience.RetryPolicy; None = the default retry-once policy) so
    transient device faults are re-attempted. A permanently
    failing bucket raises resilience.RetriesExhausted with NaN left in
    its slots only if ``policy`` is given with ``allow_partial`` via
    :func:`forward_pairs_partial` (the quarantine path callers use).
    """
    vals, failed = forward_pairs_partial(
        reads, quals, haps, gap_open=gap_open, gap_ext=gap_ext,
        dtype=dtype, bucket=bucket, policy=policy, allow_partial=False)
    return vals


def forward_pairs_partial(reads, quals, haps, *,
                          gap_open: float = DEFAULT_GAP_OPEN,
                          gap_ext: float = DEFAULT_GAP_EXT,
                          dtype=np.float32, bucket: int = BUCKET,
                          policy=None, allow_partial: bool = True):
    """Like :func:`forward_pairs` but returns ``(log10 (N,) f64,
    failed_error_by_index dict)``: when ``allow_partial`` and a
    bucket's dispatch fails permanently (retries exhausted), its
    pairs' slots hold NaN and map to the causing exception — the
    caller (models/genotype.py) quarantines the affected windows
    instead of losing the whole run.
    """
    from ..plan import Executor as PlanExecutor, Step
    from ..resilience.policy import DEFAULT_POLICY

    if not (len(reads) == len(quals) == len(haps)):
        raise ValueError(
            f"forward_pairs: {len(reads)} reads, {len(quals)} quals, "
            f"{len(haps)} haps — lengths must match")
    n = len(reads)
    out = np.full(n, np.nan, dtype=np.float64)
    failed: dict[int, BaseException] = {}
    if n == 0:
        return out, failed
    enc_reads, errs, enc_haps = [], [], []
    for r, q, h in zip(reads, quals, haps):
        er = encode_seq(r)
        if len(er) == 0:
            raise ValueError("forward_pairs: empty read")
        eh = encode_seq(h)
        if len(eh) == 0:
            raise ValueError("forward_pairs: empty haplotype")
        e = phred_to_err(np.broadcast_to(np.asarray(q), (len(er),)))
        enc_reads.append(er)
        errs.append(e)
        enc_haps.append(eh)

    dtype = np.dtype(dtype)
    rescale = dtype == np.float32
    trans = transition_probs(gap_open, gap_ext).astype(dtype)
    if policy is None:
        policy = DEFAULT_POLICY
    reg = get_registry()
    reg.counter("pairhmm.pairs_total").inc(n)

    pex = PlanExecutor(policy=policy)
    groups = bucket_pairs(enc_reads, enc_haps, bucket)
    for (r_pad, h_pad), idxs in sorted(groups.items()):
        packed = _pack_bucket(idxs, enc_reads, errs, enc_haps,
                              r_pad, h_pad, dtype)
        key = ("pairhmm", r_pad, h_pad, len(idxs))

        def thunk(packed=packed, r_pad=r_pad, h_pad=h_pad,
                  b=len(idxs)):
            from ..obs.compiles import TRACKER

            # exact per-bucket compile attribution: the jit object's
            # own cache size is the ground truth for this geometry
            with TRACKER.observe(
                    "pairhmm",
                    signature={"r_pad": r_pad, "h_pad": h_pad,
                               "b": b, "rescale": rescale,
                               "dtype": dtype.name},
                    cache_size_fn=lambda: _FORWARD_JIT._cache_size()
                    if _FORWARD_JIT is not None else 0,
                    trigger="pairhmm_forward"):
                contribs, shifts = _forward_bucket(
                    *packed, trans, rescale=rescale)
            return np.asarray(contribs), np.asarray(shifts)

        reg.counter("pairhmm.buckets_total").inc()
        # one bucket dispatch = one plan Step at the 'pairhmm' fault
        # site, retried under the policy like every other dispatch
        outcome = pex.run_step(Step(key=key, fn=thunk,
                                    site="pairhmm"))
        if outcome.error is not None:
            if not allow_partial:
                raise outcome.retries_exhausted
            for i in idxs:
                failed[i] = outcome.error
            reg.counter("pairhmm.buckets_failed_total").inc()
            continue
        contribs, shifts = outcome.value
        out[np.asarray(idxs)] = _fold_contribs(contribs, shifts)
    return out, failed
