"""Fused per-shard depth pipeline: segments → per-base depth → window sums
+ callable classes, one jit compile per (padded length, window, bucket).

Shards are computed relative to w0 = floor(region_start/W)*W so the window
grid is always aligned and lpad never varies — the dynamic region bounds
(rs, re) arrive as traced scalars and only mask, never reshape. This keeps
XLA compilations to a handful for a whole-genome run (one per segment
bucket), where a naive per-region shape would compile per chromosome tail.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..obs import InstrumentedDispatch as _InstrumentedDispatch


def _pipeline_body(seg_start, seg_end, keep, w0, region_start,
                   region_end, depth_cap, min_cov, max_mean_depth,
                   length, window):
    s = jnp.clip(jnp.maximum(seg_start, region_start) - w0, 0, length)
    e = jnp.clip(jnp.minimum(seg_end, region_end) - w0, 0, length)
    s = jnp.where(keep, s, length)
    e = jnp.where(keep, e, length)
    delta = jnp.zeros(length + 1, dtype=jnp.int32)
    delta = delta.at[s].add(1).at[e].add(-1)
    depth = jnp.cumsum(delta[:length])
    depth = jnp.minimum(depth, depth_cap)
    pos = jnp.arange(length, dtype=jnp.int32) + w0
    in_region = (pos >= region_start) & (pos < region_end)
    depth = jnp.where(in_region, depth, 0)

    # f32 window sums are exact while window*depth_cap < 2**24 (every
    # partial sum an exact int), which covers the reference defaults
    # (W=250, cap=2500 → 625000); beyond that relative error ≤ 1e-7 is
    # far below the 0.5-absolute oracle tolerance (depth/test/cmp.py:12).
    window_sums = depth.astype(jnp.float32).reshape(-1, window).sum(axis=1)

    cls = jnp.where(
        depth == 0,
        0,
        jnp.where(
            depth < min_cov,
            1,
            jnp.where(
                (max_mean_depth > 0) & (depth >= max_mean_depth), 3, 2
            ),
        ),
    ).astype(jnp.int8)
    return window_sums, cls, depth


@functools.partial(jax.jit, static_argnames=("length", "window"))
def shard_depth_pipeline(
    seg_start: jax.Array,
    seg_end: jax.Array,
    keep: jax.Array,
    w0: jax.Array,
    region_start: jax.Array,
    region_end: jax.Array,
    depth_cap: jax.Array,
    min_cov: jax.Array,
    max_mean_depth: jax.Array,
    length: int,
    window: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (window_sums f32, per-base classes i8, per-base depth i32)
    over [w0, w0+length); bases outside [region_start, region_end) are
    zeroed (samtools -r only counts in-region bases).

    length must be a multiple of window and ≥ region_end - w0.
    """
    return _pipeline_body(seg_start, seg_end, keep, w0, region_start,
                          region_end, depth_cap, min_cov,
                          max_mean_depth, length, window)


def _pack_cls_2bit(cls: jax.Array, length: int) -> jax.Array:
    """int8 classes (values 0..3) → 2-bit packed uint8 — quarters the
    device→host transfer of the per-base class array (the depth CLI's
    D2H bottleneck on slow links).

    Planar: byte i holds positions i, i+n, i+2n, i+3n (n = packed
    length), lowest bits first, so the four operands are contiguous
    quarters of the array. Interleaving neighbours instead (a
    ``reshape(-1, 4)``, minor dimension 4) cost the TPU compiler 190 s
    for one 10 Mb shard program against 11 s for this layout.
    """
    pad = (-length) % 4
    if pad:
        cls = jnp.concatenate([cls, jnp.zeros(pad, cls.dtype)])
    c4 = cls.reshape(4, -1).astype(jnp.uint8)
    return c4[0] | (c4[1] << 2) | (c4[2] << 4) | (c4[3] << 6)


def unpack_cls_2bit(packed: "np.ndarray", length: int):
    """Host inverse of _pack_cls_2bit → int8 (length,)."""
    import numpy as np

    bits = (packed[None, :] >> np.array([[0], [2], [4], [6]],
                                        np.uint8)) & 3
    return bits.reshape(-1)[:length].astype(np.int8)


@functools.partial(jax.jit, static_argnames=("length", "window"))
def shard_depth_pipeline_cls_packed(
    seg_start: jax.Array,
    seg_end: jax.Array,
    keep: jax.Array,
    w0: jax.Array,
    region_start: jax.Array,
    region_end: jax.Array,
    depth_cap: jax.Array,
    min_cov: jax.Array,
    max_mean_depth: jax.Array,
    length: int,
    window: int,
) -> tuple[jax.Array, jax.Array]:
    """(window_sums, 2-bit packed classes) — the depth CLI's fetch set."""
    sums, cls, _ = _pipeline_body(seg_start, seg_end, keep, w0,
                                  region_start, region_end, depth_cap,
                                  min_cov, max_mean_depth, length, window)
    return sums, _pack_cls_2bit(cls, length)


def _unpack_wire(deltas, lens, base):
    """u16 wire (sorted start deltas + lengths) → absolute endpoints +
    keep mask; zero-length entries are padding/gap fillers."""
    seg_start = base + jnp.cumsum(deltas.astype(jnp.int32))
    lens32 = lens.astype(jnp.int32)
    return seg_start, seg_start + lens32, lens32 > 0


@functools.partial(jax.jit, static_argnames=("length", "window"))
def shard_depth_pipeline_packed_cls_packed(
    deltas: jax.Array,
    lens: jax.Array,
    base: jax.Array,
    w0: jax.Array,
    region_start: jax.Array,
    region_end: jax.Array,
    depth_cap: jax.Array,
    min_cov: jax.Array,
    max_mean_depth: jax.Array,
    length: int,
    window: int,
) -> tuple[jax.Array, jax.Array]:
    """Packed u16 wire in, 2-bit packed classes out."""
    s, e, keep = _unpack_wire(deltas, lens, base)
    sums, cls, _ = _pipeline_body(s, e, keep, w0, region_start,
                                  region_end, depth_cap, min_cov,
                                  max_mean_depth, length, window)
    return sums, _pack_cls_2bit(cls, length)


@functools.partial(jax.jit, static_argnames=("length", "window"))
def shard_depth_pipeline_packed(
    deltas: jax.Array,
    lens: jax.Array,
    base: jax.Array,
    w0: jax.Array,
    region_start: jax.Array,
    region_end: jax.Array,
    depth_cap: jax.Array,
    min_cov: jax.Array,
    max_mean_depth: jax.Array,
    length: int,
    window: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Same pipeline fed by the packed u16 wire format (4 bytes/segment
    instead of 9: sorted start deltas + lengths, see
    ops/coverage.py::pack_segments_u16) — host→device traffic halves and
    the absolute endpoints are reconstructed on device with one cumsum.
    """
    s, e, keep = _unpack_wire(deltas, lens, base)
    return _pipeline_body(s, e, keep, w0, region_start, region_end,
                          depth_cap, min_cov, max_mean_depth, length,
                          window)


# The module's dispatch boundaries are proxies that run each call under
# the compile and memory observers, async dispatch intact. Jit
# attributes (_cache_size, lower, …) forward through — the compile
# observatory and the AOT compile tests read them — and calls made
# INSIDE a jax trace (the vmapped wrappers in commands/depth.py and
# commands/cohortdepth.py close over these names) pass straight
# through untouched.
shard_depth_pipeline = _InstrumentedDispatch(
    shard_depth_pipeline, "shard_depth_pipeline")
shard_depth_pipeline_cls_packed = _InstrumentedDispatch(
    shard_depth_pipeline_cls_packed, "shard_depth_pipeline_cls_packed")
shard_depth_pipeline_packed_cls_packed = _InstrumentedDispatch(
    shard_depth_pipeline_packed_cls_packed,
    "shard_depth_pipeline_packed_cls_packed")
shard_depth_pipeline_packed = _InstrumentedDispatch(
    shard_depth_pipeline_packed, "shard_depth_pipeline_packed")
