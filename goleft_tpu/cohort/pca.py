"""Sharded on-device PCA over sample chunks.

``ops.indexcov_ops.pca_project`` — the small-cohort oracle — runs one
SVD over the full (samples × autosome-bins) matrix, which is exactly
the matrix the cohort plane refuses to materialize. This module
computes the same projection by block power iteration on the Gram
operator: every touch of the data is a chunk-local matmul

    partial = Cᵀ (C Q)        (C = centered chunk, Q the iterate)

summed across chunks — so peak memory is O(chunk × bins) + O(bins × k),
and each matmul runs on device (sharded over the sample axis via
``shard_map`` + psum when the process has several devices, a single
jitted kernel otherwise), accumulating in f64 where the backend allows
(``preferred_float``: CPU/x64 — TPUs accumulate f32).

Semantics match the oracle: column-center for the decomposition,
project the *raw* matrix onto the top-k right singular vectors, report
variance fractions against the TOTAL variance ‖C‖²_F/(n-1) (the oracle
divides by the full spectrum's sum, which is the same quantity). Power
iteration is iterative, so the sharded projection agrees with the
oracle to a tolerance, not byte-for-byte — ``cohortscan`` therefore
uses the oracle below ``--pca-exact-max`` samples (where byte-parity
with one-shot ``indexcov`` is pinned) and this path above it
(docs/cohort.md).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.dtypes import preferred_float


def _check_dims(n_samples: int, k: int) -> None:
    if n_samples < 2:
        raise ValueError(
            f"pca: need at least 2 samples, got {n_samples} — a "
            "single-sample cohort has no cross-sample variance")
    if k > n_samples:
        raise ValueError(
            f"pca: k={k} components exceed n_samples={n_samples}; "
            "pass k <= n_samples")


@jax.jit
def _chunk_stats(chunk: jax.Array):
    """(col_sum f64-where-possible, squared Frobenius norm) of one raw
    chunk — the pass-0 moments behind the mean and total variance."""
    acc_t = preferred_float()
    c = chunk.astype(acc_t)
    return c.sum(axis=0), (c * c).sum()


def _chunk_gram_impl(chunk: jax.Array, mean: jax.Array, q: jax.Array):
    """One chunk's contribution Cᵀ(CQ) to the Gram–iterate product."""
    acc_t = preferred_float()
    c = chunk.astype(acc_t) - mean.astype(acc_t)[None, :]
    w = c @ q.astype(acc_t)
    return c.T @ w


_chunk_gram = jax.jit(_chunk_gram_impl)


def _sharded_gram_fn(mesh):
    """shard_map'd version of the Gram step: rows split over the
    ``data`` axis, partials psummed on device — one collective instead
    of a host gather per chunk."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def body(chunk, mean, q):
        g = _chunk_gram_impl(chunk, mean, q)
        return jax.lax.psum(g, "data")

    return jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P("data", None), P(None), P(None, None)),
        out_specs=P(None, None),
    ))


class ShardedPCA:
    """Fitted sharded PCA: top-k right singular directions + variance
    fractions, with a per-chunk projection (never the full matrix)."""

    def __init__(self, components: np.ndarray, frac: np.ndarray,
                 mean: np.ndarray, iters: int):
        self.components_ = components  # (n_bins, k) f32
        self.frac_ = frac              # (k,) f32
        self.mean_ = mean
        self.iters_ = iters

    def project(self, chunk: np.ndarray) -> np.ndarray:
        """Raw-matrix projection of one sample chunk — the oracle's
        ``x @ vt[:k].T`` semantics (indexcov.go:773-807)."""
        x = np.asarray(chunk, np.float32)
        return np.asarray(x @ self.components_, np.float32)


def sharded_pca(chunks_fn, k: int = 5, *, iters: int = 32,
                seed: int = 1, mesh=None) -> ShardedPCA:
    """Fit top-k principal directions by chunked block power iteration.

    ``chunks_fn`` is a zero-arg callable yielding the sample chunks
    (each (chunk, n_bins) float32, all the same width) in cohort order;
    it is called ``iters + 1`` times, so chunks should be cheap to
    re-materialize (the scan engine mmap-reads its spill files).
    """
    # ---- pass 0: mean + total variance ----
    n = 0
    col_sum = None
    sumsq = 0.0
    n_bins = None
    for chunk in chunks_fn():
        chunk = np.asarray(chunk, np.float32)
        if n_bins is None:
            n_bins = chunk.shape[1]
            col_sum = np.zeros(n_bins, np.float64)
        s, ss = _chunk_stats(chunk)
        col_sum += np.asarray(s, np.float64)
        sumsq += float(ss)
        n += chunk.shape[0]
    if n_bins is None:
        raise ValueError("pca: empty cohort")
    _check_dims(n, k)
    k_eff = min(k, n, n_bins)
    mean = (col_sum / n).astype(np.float64)
    # ‖C‖²_F = Σ‖x‖² − n‖mean‖² (f64 throughout: catastrophic
    # cancellation here would poison every variance fraction)
    total_var = max(sumsq - n * float(mean @ mean), 0.0) \
        / max(n - 1, 1)

    mean32 = mean.astype(np.float32)
    gram = _chunk_gram
    if mesh is None and len(jax.local_devices()) > 1:
        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(jax.local_devices()), ("data",))
    if mesh is not None and np.prod(mesh.devices.shape) > 1:
        sharded = _sharded_gram_fn(mesh)
        n_dev = int(np.prod(mesh.devices.shape))

        def gram(chunk, mean_a, q):  # noqa: F811 — sharded override
            rows = chunk.shape[0]
            pad = (-rows) % n_dev
            if pad:
                # pad with mean rows: centered contribution is zero
                chunk = np.concatenate(
                    [chunk, np.broadcast_to(mean_a, (pad,) +
                                            mean_a.shape)], axis=0)
            return sharded(chunk, mean_a, q)

    # ---- block power iteration on the Gram operator ----
    rng = np.random.default_rng(seed)
    q = np.linalg.qr(
        rng.standard_normal((n_bins, k_eff)).astype(np.float64))[0]
    q = q.astype(np.float32)
    for _ in range(iters):
        acc = np.zeros((n_bins, k_eff), np.float64)
        for chunk in chunks_fn():
            acc += np.asarray(
                gram(np.asarray(chunk, np.float32), mean32, q),
                np.float64)
        q = np.linalg.qr(acc)[0].astype(np.float32)

    # ---- Rayleigh–Ritz rotation inside the converged subspace ----
    g = np.zeros((k_eff, k_eff), np.float64)
    for chunk in chunks_fn():
        w = np.asarray(_chunk_w(np.asarray(chunk, np.float32),
                                mean32, q), np.float64)
        g += w.T @ w
    evals, evecs = np.linalg.eigh(g)  # ascending
    order = np.argsort(evals)[::-1]
    evals = np.maximum(evals[order], 0.0)
    comp = (q.astype(np.float64) @ evecs[:, order]).astype(np.float32)
    # deterministic sign: largest-|loading| entry of each component
    # positive (SVD signs are arbitrary; pin them so re-runs and
    # resumes agree)
    for i in range(comp.shape[1]):
        j = int(np.argmax(np.abs(comp[:, i])))
        if comp[j, i] < 0:
            comp[:, i] = -comp[:, i]
    vars_ = evals / max(n - 1, 1)
    frac = (vars_ / total_var if total_var > 0
            else np.zeros_like(vars_)).astype(np.float32)
    return ShardedPCA(comp, frac[:k_eff], mean32, iters)


@jax.jit
def _chunk_w(chunk: jax.Array, mean: jax.Array, q: jax.Array):
    acc_t = preferred_float()
    c = chunk.astype(acc_t) - mean.astype(acc_t)[None, :]
    return c @ q.astype(acc_t)
