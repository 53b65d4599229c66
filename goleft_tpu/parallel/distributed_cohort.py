"""Multi-host cohort decode: shard the SAMPLES across jax.distributed
processes, assemble the matrix over the collective fabric.

The cohort pipeline's wall clock is the host decode stage (fused C++
BGZF+record walk); within one host it scales across decode threads
(utils/decode_scaling). This module scales it across HOSTS: process i
decodes ``bams[i::P]`` with the ordinary cohort machinery, then one
``process_allgather`` moves the (windows × local-samples) int32 means
over DCN and every process reassembles the full matrix in original
sample order. Decode wall time divides by the process count; the
gathered payload is the O(windows × samples) matrix — the same reduced
product the single-host hierarchy ships over the device link, never
per-read data.

The reference has no multi-machine story at all (its parallelism is one
process pool per invocation, depth/depth.go:392-394; SURVEY.md §2.5);
this is the rebuild's answer at the cohort-tool level, riding the same
jax.distributed world that mesh.init_distributed brings up.
"""

from __future__ import annotations

import contextlib
import os
import sys

import numpy as np

_NAME_BYTES = 256  # fixed-width utf-8 slot per sample name for the gather


@contextlib.contextmanager
def _stdout_to_stderr():
    """Divert fd 1 to stderr (fd-level: catches native prints too)."""
    sys.stdout.flush()
    saved = os.dup(1)
    try:
        os.dup2(2, 1)
        yield
    finally:
        sys.stdout.flush()
        os.dup2(saved, 1)
        os.close(saved)


def cohort_coords(fai_path: str, chrom: str, window: int,
                  bed: str | None = None):
    """(chroms, starts, ends) for every window of the cohort matrix,
    derived from the .fai alone — exactly the coordinates
    cohort_matrix_blocks emits (same gen_regions shards, same
    window_bounds), so a process holding zero local samples can still
    label the gathered matrix."""
    from ..commands.cohortdepth import cohort_regions
    from ..io.fai import read_fai
    from ..ops.coverage import window_bounds

    regions = cohort_regions(read_fai(fai_path), chrom, window, bed)
    ch, st, en = [], [], []
    for c, s, e in regions:
        starts, ends, _, _ = window_bounds(s, e, window)
        ch.extend([c] * len(starts))
        st.append(starts)
        en.append(ends)
    if not st:
        return np.empty(0, object), np.empty(0, np.int64), \
            np.empty(0, np.int64)
    return (np.array(ch, dtype=object), np.concatenate(st),
            np.concatenate(en))


def _local_matrix(local_bams, n_win, reference, fai, window, mapq,
                  chrom, processes, engine, bed, prefetch_depth=0,
                  stage_timer=None):
    """Drain cohort_matrix_blocks for this process's sample shard into
    an int32 (n_win, n_local) matrix of round-half-up window means."""
    from ..commands.cohortdepth import cohort_matrix_blocks

    if not local_bams:
        return [], np.zeros((n_win, 0), dtype=np.int32)
    names, total, blocks = cohort_matrix_blocks(
        local_bams, reference=reference, fai=fai, window=window,
        mapq=mapq, chrom=chrom, processes=processes, engine=engine,
        bed=bed, prefetch_depth=prefetch_depth,
        stage_timer=stage_timer,
    )
    assert total == n_win, (total, n_win)
    mat = np.empty((n_win, len(names)), dtype=np.int32)
    row = 0
    for _, starts, _, vals in blocks:
        k = len(starts)
        mat[row : row + k] = vals.T
        row += k
    assert row == n_win, (row, n_win)
    return names, mat


def _pack_names(names, pad_to: int) -> np.ndarray:
    out = np.zeros((pad_to, _NAME_BYTES), dtype=np.uint8)
    for i, nm in enumerate(names):
        b = nm.encode("utf-8")[:_NAME_BYTES]
        # a hard byte cut can split a multi-byte codepoint and make
        # _unpack_name's decode raise mid-assembly; re-truncate on a
        # codepoint boundary instead
        b = b.decode("utf-8", errors="ignore").encode("utf-8")
        out[i, : len(b)] = np.frombuffer(b, dtype=np.uint8)
    return out


def _unpack_name(row: np.ndarray) -> str:
    return bytes(row[row != 0]).decode("utf-8")


def distributed_cohort_matrix(
    bams: list[str],
    reference: str | None = None,
    fai: str | None = None,
    window: int = 250,
    mapq: int = 1,
    chrom: str = "",
    processes: int = 8,
    engine: str = "auto",
    bed: str | None = None,
    prefetch_depth: int = 0,
    stage_timer=None,
):
    """(names, chroms, starts, ends, matrix) with matrix int32
    (n_windows, n_samples) of round-half-up window means, identical to
    a single-process cohortdepth run over the same BAMs.

    Every process returns the full assembled result (process_allgather
    is symmetric), so callers can write output on process 0 and use the
    arrays everywhere else.

    ``prefetch_depth`` >= 1 routes each process's LOCAL shard loop
    through the async staging pipeline (parallel/prefetch.py) — the
    host-decode/device-compute stages land in this process's
    ``stage_timer``; the DCN gather is unaffected (it moves the
    already-reduced matrix).
    """
    import jax

    from ..io.fai import write_fai

    fai_path = fai or (reference + ".fai" if reference else None)
    if fai_path is None:
        raise SystemExit("cohortdepth: need -r reference or --fai")
    P = jax.process_count()
    pid = jax.process_index()
    if not os.path.exists(fai_path) and reference:
        # shared-FS race: only process 0 may generate the index; the
        # barrier keeps the others from reading a half-written file
        # (and from every host writing the same path at once)
        if pid == 0:
            write_fai(reference)
        if P > 1:
            from jax.experimental import multihost_utils

            with _stdout_to_stderr():
                multihost_utils.sync_global_devices(
                    "goleft_tpu_fai_ready")
    chroms, starts, ends = cohort_coords(fai_path, chrom, window,
                                         bed=bed)
    n_win = len(starts)
    if P == 1:
        names, mat = _local_matrix(bams, n_win, reference, fai_path,
                                   window, mapq, chrom, processes,
                                   engine, bed, prefetch_depth,
                                   stage_timer)
        return names, chroms, starts, ends, mat

    local = bams[pid::P]
    names_l, mat_l = _local_matrix(local, n_win, reference, fai_path,
                                   window, mapq, chrom, processes,
                                   engine, bed, prefetch_depth,
                                   stage_timer)
    # fixed-shape padding: allgather needs identical shapes everywhere
    pad = (len(bams) + P - 1) // P
    mat_pad = np.zeros((n_win, pad), dtype=np.int32)
    mat_pad[:, : mat_l.shape[1]] = mat_l

    from jax.experimental import multihost_utils

    # the CPU collective backend (gloo) prints a connection banner to
    # STDOUT on its first collective — which would corrupt the matrix
    # a piped `cohortdepth > m.tsv` is writing there. Divert fd 1 to
    # stderr for the gathers (all output writing happens after).
    with _stdout_to_stderr():
        g_mat = np.asarray(
            multihost_utils.process_allgather(mat_pad)
        )  # (P, n_win, pad)
        g_names = np.asarray(
            multihost_utils.process_allgather(_pack_names(names_l, pad))
        )  # (P, pad, NAME_BYTES)

    # global sample k was decoded by process k % P at local slot k // P
    n = len(bams)
    mat = np.empty((n_win, n), dtype=np.int32)
    names = []
    for k in range(n):
        mat[:, k] = g_mat[k % P, :, k // P]
        names.append(_unpack_name(g_names[k % P, k // P]))
    return names, chroms, starts, ends, mat
