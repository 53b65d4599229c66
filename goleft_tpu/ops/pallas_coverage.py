"""Pallas TPU kernel: per-base depth from segment endpoints.

Alternative to the XLA scatter+cumsum path (ops/depth_pipeline.py) that
avoids the HBM scatter entirely. The genome splits into TILE-base tiles;
the host buckets segment endpoints per tile (sorted, padded with an
int32-max sentinel). The kernel runs a sequential grid over tiles:

    depth[p] = carry + #(starts ≤ p) − #(ends ≤ p)        (p in tile)

computed as vectorized compare-reductions over the tile's endpoint
buckets in VMEM, with the running carry (reads entering from the left)
held in SMEM scratch across grid steps — the TPU grid is sequential, so
this IS the segmented prefix sum, one pass over HBM: endpoints in,
depth out, no 40MB delta array written and re-read.

Windowed sums / callable classes stay in XLA (cheap fused elementwise on
the kernel's output).

STATUS: EXPERIMENTAL — parked, not a product path, and not measured
on the chip: it compiles for the v5e (tests/test_tpu_compile.py) and
has run in no cell. The XLA path it would replace reads 44.116 ms a
10 Mb shard in ``depth30x.jobs``, 0.26% of the HBM roofline (ledger,
PR 30), so there is room; this kernel's cost is O(endpoints/tile)
vector compares per position. One run inside ``cohort4x.jobs`` decides
whether it becomes the product path or goes (ROADMAP.md, Design 3).
Kept tested (tests/test_pallas_coverage.py) until then.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 1024  # positions per grid step, laid out (8, 128)
SENTINEL = np.int32(2**31 - 1)
_CHUNK = 128  # endpoints compared per VMEM-resident block


def _kernel(starts_ref, ends_ref, out_ref, carry_ref):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        carry_ref[0] = 0

    base = t * TILE
    row = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)
    pos = base + row * 128 + col  # global position of each lane

    p_cap = starts_ref.shape[1]
    n_chunks = p_cap // _CHUNK

    def body(i, acc):
        # endpoints live on the SUBLANE axis ((P, 1) layout) so the
        # broadcast against lane-major positions needs no transpose
        s = starts_ref[0, pl.ds(i * _CHUNK, _CHUNK), :]  # (CHUNK, 1)
        e = ends_ref[0, pl.ds(i * _CHUNK, _CHUNK), :]
        s3 = s[:, :, None]  # (CHUNK, 1, 1)
        e3 = e[:, :, None]
        ds = jnp.sum(
            (s3 <= pos[None]).astype(jnp.int32)
            - (e3 <= pos[None]).astype(jnp.int32),
            axis=0, dtype=jnp.int32,
        )
        return acc + ds

    rel = jax.lax.fori_loop(
        0, n_chunks, body, jnp.zeros((8, 128), jnp.int32)
    )
    carry = carry_ref[0]
    out_ref[0] = carry + rel
    carry_ref[0] = carry + rel[7, 127]


@functools.partial(jax.jit, static_argnames=("n_tiles", "interpret"))
def pallas_depth(starts_tiled: jax.Array, ends_tiled: jax.Array,
                 n_tiles: int, interpret: bool = False) -> jax.Array:
    """(n_tiles, P) sorted per-tile endpoints (SENTINEL-padded) →
    (n_tiles*TILE,) int32 per-base depth."""
    p_cap = starts_tiled.shape[1]
    assert p_cap % _CHUNK == 0
    # (n_tiles, P, 1): endpoints on the sublane axis (see _kernel), and
    # the block's trailing two dims exactly match the array dims (TPU
    # BlockSpec tiling requirement)
    starts3 = starts_tiled.reshape(n_tiles, p_cap, 1)
    ends3 = ends_tiled.reshape(n_tiles, p_cap, 1)
    out = pl.pallas_call(
        _kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((1, p_cap, 1), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, p_cap, 1), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 8, 128), lambda t: (t, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_tiles, 8, 128), jnp.int32),
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        interpret=interpret,
    )(starts3, ends3)
    return out.reshape(n_tiles * TILE)


def bucket_endpoints(seg_start: np.ndarray, seg_end: np.ndarray,
                     keep: np.ndarray, length: int,
                     p_cap: int | None = None):
    """Host-side tiling: endpoints sorted and bucketed per TILE-base tile,
    padded to a fixed per-tile capacity with SENTINEL.

    Endpoints ≥ length are dropped (same semantics as clipping at the
    global end). Returns (starts_tiled, ends_tiled, n_tiles).
    """
    n_tiles = (length + TILE - 1) // TILE
    ss = np.sort(seg_start[keep])
    ee = np.sort(seg_end[keep])
    ss = ss[(ss >= 0) & (ss < length)]
    ee = ee[(ee >= 0) & (ee < length)]
    bounds = np.arange(n_tiles + 1, dtype=np.int64) * TILE
    s_off = np.searchsorted(ss, bounds)
    e_off = np.searchsorted(ee, bounds)
    max_n = int(max(np.diff(s_off).max(initial=0),
                    np.diff(e_off).max(initial=0), 1))
    if p_cap is None:
        p_cap = _CHUNK
        while p_cap < max_n:
            p_cap *= 2
    elif max_n > p_cap:
        raise ValueError(f"p_cap {p_cap} < densest tile {max_n}")
    st = np.full((n_tiles, p_cap), SENTINEL, dtype=np.int32)
    et = np.full((n_tiles, p_cap), SENTINEL, dtype=np.int32)
    # vectorized scatter: each sorted endpoint's tile is value//TILE and
    # its slot is its rank within the tile (position minus the tile's
    # searchsorted offset) — no per-tile Python loop
    if len(ss):
        qs = ss // TILE
        st[qs, np.arange(len(ss)) - s_off[qs]] = ss
    if len(ee):
        qe = ee // TILE
        et[qe, np.arange(len(ee)) - e_off[qe]] = ee
    return st, et, n_tiles
