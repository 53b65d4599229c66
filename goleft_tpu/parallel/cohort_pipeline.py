"""The flagship end-to-end cohort step: sharded coverage → scaled depth →
batched EM copy number, as ONE jitted program over the device mesh.

This is the TPU composition of the reference's whole pipeline
(depth → depthwed → emdepth, SURVEY.md §3.1/§3.5): genome axis sharded
(``seq``), samples data-parallel (``data``); the only cross-device
traffic is the segmented-cumsum carry all_gather inside
sharded_coverage and the resharding between the coverage layout
(samples × genome) and the EM layout (windows × samples).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.emdepth import em_depth_batch, cn_batch
from ..obs import InstrumentedDispatch as _InstrumentedDispatch
from .sharded_coverage import sharded_depth_fn


def _normalize_and_em(mesh: Mesh, wmeans):
    """The SHIPPING normalization + EM tail, shared by the monolithic
    step and the chunked finalize so both compile the same op sequence —
    identical to what `cnv` runs (commands/emdepth_cmd.py::call_cnvs,
    per the emdepth contract that inputs are pre-normalized comparable
    depths, emdepth/emdepth.go:117-138): round-half-up integer window
    means (the depthwed matrix values), each sample scaled to its global
    median, rescaled by the cohort median-of-medians. The genome axis is
    sharded, so the medians are cross-shard reductions XLA lowers onto
    ICI."""
    vals = jnp.floor(wmeans + 0.5)
    med = jnp.median(vals, axis=1)  # per-sample global median
    med = jnp.where(med == 0, 1.0, med)
    scaled = vals / med[:, None] * jnp.median(med)
    # reshard: EM wants (windows, samples) with windows on 'seq'
    wm = jax.lax.with_sharding_constraint(
        scaled.T, NamedSharding(mesh, P("seq", "data"))
    )
    lambdas = em_depth_batch(wm)
    cn = cn_batch(lambdas, wm)
    return lambdas, cn


def build_cohort_step(mesh: Mesh, shard_len: int, window: int,
                      carry_mode: str = "all_gather"):
    """Returns jitted fn(seg_s, seg_e, keep) → dict(depth, wmeans, lambdas,
    cn). Input arrays (S, n_seq*per) laid out for P('data','seq').
    ``carry_mode`` selects the inter-shard prefix collective (see
    sharded_depth_fn): all_gather for small seq axes, the log-step
    ppermute scan for large meshes."""
    coverage = sharded_depth_fn(mesh, shard_len, window,
                                carry_mode=carry_mode)

    def step(seg_s, seg_e, keep):
        depth, wsums = coverage(seg_s, seg_e, keep)
        wmeans = wsums / window  # (S, n_win)
        lambdas, cn = _normalize_and_em(mesh, wmeans)
        return {
            "depth": depth,
            "wmeans": wmeans,
            "lambdas": lambdas,
            "cn": cn,
        }

    in_shard = NamedSharding(mesh, P("data", "seq"))
    # dispatch boundary: the jitted call under the compile and memory
    # observers
    return _InstrumentedDispatch(
        jax.jit(step, in_shardings=(in_shard,) * 3), "cohort_step")


def build_chunked_cohort_step(mesh: Mesh, shard_len: int, window: int,
                              carry_mode: str = "all_gather",
                              donate: bool | None = None):
    """Chunked variant of :func:`build_cohort_step` for the prefetch
    staging pipeline (parallel/prefetch.py): the genome is fed as a
    sequence of chunks of ``n_seq * shard_len`` positions, each staged
    and transferred while the previous chunk computes.

    Returns ``(chunk_fn, finalize_fn, in_sharding, carry_sharding)``:

      - ``chunk_fn(seg_s, seg_e, keep, carry) → (depth, wsums, carry')``
        runs the sharded coverage on one chunk's endpoint arrays
        (chunk-relative coordinates, laid out like the monolithic
        step's inputs) and threads ``carry`` — the (S,) int32 running
        depth at the chunk boundary — so per-base depth and window sums
        stay bit-identical to the monolithic program: a segment
        straddling a chunk boundary contributes its +1 to one chunk and
        its −1 to the next, exactly like shard boundaries inside one
        program. ``carry'`` is the depth at this chunk's last position.
      - ``finalize_fn(wsums) → dict(wmeans, lambdas, cn)`` takes the
        host-concatenated (S, n_win_total) window sums and runs the one
        shipping normalization + EM tail over the whole cohort extent.

    On non-CPU backends (or with ``donate=True``) the chunk step
    donates its segment-endpoint input buffers: the consumed device
    staging buffers are recycled into the outputs, bounding device
    memory at O(prefetch_depth) chunks instead of O(n_chunks).
    """
    coverage = sharded_depth_fn(mesh, shard_len, window,
                                carry_mode=carry_mode)

    def chunk(seg_s, seg_e, keep, carry):
        depth, wsums = coverage(seg_s, seg_e, keep)
        depth = depth + carry[:, None]
        # adding ``carry`` to every base of a window adds carry*window
        # to its sum — exact in f32 within the same < 2**24 bound the
        # monolithic window sums already rely on
        wsums = wsums + (carry.astype(wsums.dtype) * window)[:, None]
        return depth, wsums, depth[:, -1]

    def finalize(wsums):
        wmeans = wsums / window  # (S, n_win_total)
        lambdas, cn = _normalize_and_em(mesh, wmeans)
        return {"wmeans": wmeans, "lambdas": lambdas, "cn": cn}

    in_shard = NamedSharding(mesh, P("data", "seq"))
    carry_shard = NamedSharding(mesh, P("data"))
    if donate is None:
        # donation is a no-op (with a warning) on CPU; only ask for it
        # where the runtime can actually alias buffers
        donate = next(iter(mesh.devices.flat)).platform != "cpu"
    chunk_fn = _InstrumentedDispatch(jax.jit(
        chunk,
        in_shardings=(in_shard,) * 3 + (carry_shard,),
        donate_argnums=(0, 1, 2) if donate else (),
    ), "cohort_chunk")
    finalize_fn = _InstrumentedDispatch(
        jax.jit(finalize, in_shardings=(in_shard,)),
        "cohort_finalize")
    return chunk_fn, finalize_fn, in_shard, carry_shard
