"""indexcov numerics: normalization, ROC, bin counters, copy number, PCA.

Device (JAX, float32 — matching the reference's float32 math) kernels for
the per-bin work that dominates a cohort run, vmapped over the sample axis;
the tiny integer-exact per-sample median init stays on host in int64 numpy
(bit-exact vs the reference's int64 sort/cumsum at indexcov/indexcov.go:
104-124, where ragged chromosome lists make device layout pointless).

Reference semantics reproduced (citations into /root/reference):
  - median size per tile: sort sizes, cap at the 98th percentile, take the
    value where the capped cumsum first exceeds total/2
    (indexcov/indexcov.go:104-124)
  - NormalizedDepth: float32 size/median, capped at 50000 (":129-151")
  - CountsAtDepth: slot = trunc(d * (70 * float32(2/3)) + 0.5) clipped to
    [0, 70) (":153-177")
  - CountsROC: reverse cumulative counts / total (":181-193")
  - counter: in = depth in (0.85, 1.15); low < 0.15; hi > 1.15; bins missing
    past a sample's end count as out+low (":1050-1078")
  - GetCN: drop zero bins; if >30% of all bins are (nonzero) < 0.02 also
    drop those; CN = Ploidy * sorted[0.4*len] (":957-991")
  - cross-sample normalization + 7-tap smoothing, sequentially dependent on
    previously-normalized columns → lax.scan (":549-597")
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

SLOTS = 70
SLOTS_MID = 2.0 / 3.0
MAX_CN = 8.0
PLOIDY = 2
DEPTH_CAP = 50000.0


def median_size_per_tile(sizes: list[np.ndarray]) -> float:
    """Host, int64-exact (indexcov/indexcov.go:96-124)."""
    flat = np.concatenate([np.asarray(s, dtype=np.int64) for s in sizes]) \
        if sizes else np.zeros(0, dtype=np.int64)
    if flat.size < 1:
        raise ValueError("indexcov: no usable chromosomes in index")
    flat = np.sort(flat)
    n98 = flat[int(0.98 * len(flat))]
    capped = np.minimum(flat, n98)
    cumsum = np.cumsum(capped)
    total = int(cumsum[-1])
    idx = int(np.searchsorted(cumsum, total // 2, side="right"))
    idx = min(idx, len(flat) - 1)
    return float(flat[idx])


def normalized_depth(sizes: np.ndarray, median: float) -> np.ndarray:
    """float32 scaled depth, capped at 50000 (indexcov.go:129-151)."""
    if median == 0:
        return np.zeros(0, dtype=np.float32)
    d = (np.asarray(sizes, dtype=np.float64) / median).astype(np.float32)
    return np.minimum(d, np.float32(DEPTH_CAP))


_SCALE = np.float32(SLOTS * np.float32(SLOTS_MID))  # 46.666668 in f32


@jax.jit
def counts_at_depth(depths: jax.Array, valid: jax.Array) -> jax.Array:
    """(n_samples, n_bins) → (n_samples, SLOTS) int32 histogram."""
    idx = jnp.clip(
        (depths * _SCALE + jnp.float32(0.5)).astype(jnp.int32), 0, SLOTS - 1
    )
    idx = jnp.where(valid, idx, SLOTS)  # dropped slot for padding
    one = jnp.ones_like(idx, dtype=jnp.int32)

    def hist(i, o):
        return jnp.zeros(SLOTS, jnp.int32).at[i].add(o, mode="drop")

    return jax.vmap(hist)(idx, one)


def counts_from_top(counts: jax.Array) -> jax.Array:
    """Tiles at or above each slot: the reverse running sum of the
    histogram (indexcov.go:181-193). counts: (..., SLOTS)."""
    return jnp.cumsum(counts[..., ::-1], axis=-1)[..., ::-1]


@jax.jit
def counts_roc(counts: jax.Array) -> jax.Array:
    """Reverse-cumulative proportion (indexcov.go:181-193), divided on
    the device. The TPU's float32 quotient is not always the correctly
    rounded one (PR 29, v5e: 35% of k/n for n under 15,196 are an ulp
    off, and 7 ROC lines of a 500-index job printed another "%.2f"), so
    ``chrom_qc`` hands the counts to the host instead
    (:func:`unpack_chrom_qc`)."""
    totals = counts_from_top(counts)
    return totals.astype(jnp.float32) / totals[..., :1].astype(jnp.float32)


@jax.jit
def bin_counters(
    depths: jax.Array, valid: jax.Array, longest: jax.Array
) -> dict:
    """Per-sample in/out/low/hi counts (indexcov.go:1050-1078).

    ``longest`` is the bin count of the longest sample for this chromosome;
    missing tail bins count as out+low.
    """
    d = depths
    inside = valid & (d >= 0.85) & (d <= 1.15)
    out = valid & ((d < 0.85) | (d > 1.15))
    hi = valid & (d > 1.15)
    low = valid & (d < 0.15)
    n_valid = valid.sum(axis=-1)
    tail = jnp.maximum(longest - n_valid, 0)
    return {
        "in": inside.sum(axis=-1).astype(jnp.int32),
        "out": (out.sum(axis=-1) + tail).astype(jnp.int32),
        "hi": hi.sum(axis=-1).astype(jnp.int32),
        "low": (low.sum(axis=-1) + tail).astype(jnp.int32),
    }


@functools.partial(jax.jit, static_argnames=("ploidy",))
def get_cn(depths: jax.Array, valid: jax.Array, ploidy: int = PLOIDY
           ) -> jax.Array:
    """Per-sample copy number of one chromosome (indexcov.go:957-991).

    depths: (n_samples, n_bins) padded; valid masks real bins.
    """

    def one(d, v):
        nz = v & (d != 0)
        k = nz.sum()
        lows = (nz & (d < 0.02)).sum()
        n_total = v.sum()
        p_lo = lows.astype(jnp.float32) / jnp.maximum(
            n_total, 1
        ).astype(jnp.float32)
        # ascending sort of nonzero values; invalid/zero → +inf tail
        vals = jnp.sort(jnp.where(nz, d, jnp.inf))
        base = jnp.where(p_lo > 0.3, lows, 0)
        m = k - base
        # reference index: int(float64(m)*0.4) — exactly (2m)//5 for every
        # representable m (0.4 rounds up in binary, so the product can only
        # sit just above an exact multiple), computed in integers so TPU
        # (no f64) matches the f64 semantics bit-for-bit
        idx = base + (m * 2) // 5
        med = jnp.where(
            m > 0,
            jnp.float32(ploidy) * vals[jnp.clip(idx, 0, d.shape[0] - 1)],
            0.0,
        )
        return jnp.where(k > 0, med, jnp.float32(-0.1))

    return jax.vmap(one)(depths, valid)


def normalize_across_samples(
    depths: jax.Array, lengths: jax.Array
) -> jax.Array:
    """Cross-sample normalization + 7-tap smoothing (indexcov.go:549-597).

    Column j is divided by the cohort mean of its 3-bin neighborhood —
    where columns < j were already normalized+smoothed — then smoothed with
    a 7-tap window mixing processed (j-3..j) and still-raw (j+1..j+3)/m
    values.

    Since PR 17 this lowers onto the streaming two-pass form
    (:mod:`goleft_tpu.cohort.streaming`): a host f64 per-length-class
    statistics pass yields the per-bin cohort scalars — the reference
    accumulates this neighborhood mean in float64 (indexcov.go:560-581),
    which the host pass now honors on every backend, TPU included —
    then a jitted per-sample scan applies them. The monolithic call here
    and the chunked cohort path share both passes, so chunked output is
    byte-identical to this function on any chunking of the sample axis.

    depths: (n_samples, n_bins) zero-padded; lengths: per-sample bin counts.
    Returns processed depths (same shape).
    """
    from ..cohort.streaming import NormStats, apply_normalization

    d = np.asarray(depths, dtype=np.float32)
    n_samples, n_bins = d.shape
    if n_samples < 5:
        return depths
    lengths_np = np.asarray(lengths, dtype=np.int64)
    stats = NormStats()
    stats.accumulate(d, lengths_np)
    m, skip = stats.finalize(n_bins)
    return apply_normalization(
        d, lengths_np.astype(np.int32), m, skip)


def quantize_depths(
    depths: np.ndarray, bug_compat_u8: bool = False
) -> np.ndarray:
    """PCA input quantization.

    The reference computes ``uint8(65535/MaxCN*dp+0.5)`` (indexcov.go:698)
    — a uint16-scale value truncated into a uint8, which wraps mod 256 for
    nearly all depths. We default to a non-wrapping uint16 quantization
    (documented divergence: same intent, no wraparound); set
    ``bug_compat_u8`` to reproduce the wrapped values exactly.
    """
    d = np.minimum(np.asarray(depths, dtype=np.float32), np.float32(MAX_CN))
    q = (np.float32(65535.0 / MAX_CN) * d + np.float32(0.5))
    if bug_compat_u8:
        return q.astype(np.uint16).astype(np.uint8)
    return q.astype(np.uint16)


@functools.partial(jax.jit, static_argnames=("k",))
def _pca_project_jit(mat: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    x = mat.astype(jnp.float32)
    centered = x - x.mean(axis=0, keepdims=True)
    _, s, vt = jnp.linalg.svd(centered, full_matrices=False)
    n = x.shape[0]
    vars_ = (s * s) / jnp.float32(max(n - 1, 1))
    frac = vars_ / vars_.sum()
    # float32 all the way: at the TPU's default precision the product
    # rounds its inputs to bfloat16 (PR 29, 500 x 175,467 on a v5e: PC5
    # off by 2.2e-4 of its largest value against 1.9e-5 so, at the same
    # 65 ms)
    proj = jnp.matmul(x, vt[:k].T, precision=jax.lax.Precision.HIGHEST)
    return proj, frac[:k]


def pca_project(mat, k: int = 5) -> tuple[jax.Array, jax.Array]:
    """Principal-component projection (indexcov.go:773-807).

    gonum's stat.PC column-centers the matrix for the SVD; the reference
    then projects the *raw* matrix onto the top-k right singular vectors.
    Returns (proj (n, k), variance fractions (k,)).

    This is the small-cohort oracle; biobank-scale cohorts go through
    :func:`goleft_tpu.cohort.pca.sharded_pca`, which never materializes
    the full matrix. Degenerate requests fail here with a clear error
    instead of a backend-dependent solver failure: ``k`` may not exceed
    the sample count (the SVD has no k-th right singular vector to
    project onto), and a single-sample cohort has no cross-sample
    variance to decompose.
    """
    n_samples = int(np.asarray(mat.shape[0]))
    if n_samples < 2:
        raise ValueError(
            f"pca: need at least 2 samples, got {n_samples} — a "
            "single-sample cohort has no cross-sample variance")
    if k > n_samples:
        raise ValueError(
            f"pca: k={k} components exceed n_samples={n_samples}; "
            "pass k <= n_samples (indexcov clamps to min(5, n_samples))")
    return _pca_project_jit(mat, k)


@jax.jit
def chrom_qc(depths: jax.Array, valid: jax.Array,
             longest: jax.Array) -> jax.Array:
    """One fused per-chromosome QC program returning ONE packed f32
    vector: [tiles at or above each slot (S·SLOTS)] [in|out|hi|low
    (4·S)] [cn (S)].

    The per-call device→host latency of a slow link dominates when ROC,
    counters, and CN fetch separately (~6 round trips per chromosome);
    this packs everything the host needs into a single transfer. All
    values are integers (or f32 already) well under 2**24, so the f32
    packing is exact. The ROC's quotients are the host's to take
    (:func:`unpack_chrom_qc`): they are printed "%.2f" and have to
    round as IEEE float32 division does.
    """
    counts = counts_at_depth(depths, valid)
    cnt = bin_counters(depths, valid, longest)
    cn = get_cn(depths, valid)
    return jnp.concatenate([
        counts_from_top(counts).astype(jnp.float32).ravel(),
        cnt["in"].astype(jnp.float32),
        cnt["out"].astype(jnp.float32),
        cnt["hi"].astype(jnp.float32),
        cnt["low"].astype(jnp.float32),
        cn.astype(jnp.float32),
    ])


def unpack_chrom_qc(packed: np.ndarray, n_samples: int):
    """Host split of chrom_qc's packed vector →
    (rocs (S, SLOTS) f32, counters dict of int64 (S,), cn f32 (S,))."""
    S = n_samples
    from_top = packed[: S * SLOTS].reshape(S, SLOTS)
    with np.errstate(invalid="ignore"):  # a sample with no tile: NaN
        rocs = from_top / from_top[:, :1]
    off = S * SLOTS
    cnt = {}
    for k in ("in", "out", "hi", "low"):
        cnt[k] = packed[off:off + S].astype(np.int64)
        off += S
    cn = packed[off:off + S]
    return rocs, cnt, cn


def update_slopes(rocs: np.ndarray, scalar: float) -> np.ndarray:
    """Per-sample ROC drop between 1±0.15 scaled depth, chromosome-length
    weighted (indexcov.go:739-750). rocs: (n_samples, SLOTS)."""
    n = 0.1
    ilo = int(0.5 + (SLOTS_MID - n) * SLOTS)
    ihi = int(0.5 + (SLOTS_MID + n) * SLOTS)
    return (rocs[:, ilo] - rocs[:, ihi]) * np.float32(scalar)
