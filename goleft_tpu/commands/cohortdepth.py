"""cohortdepth: windowed depth matrix for many BAMs in one device pass.

The reference reaches a cohort matrix by running ``goleft depth`` once
per sample and matricizing with ``depthwed`` (SURVEY.md §3.1, BASELINE
config 3). This command fuses the whole path: per shard, all samples'
read segments decode in parallel threads (native C++, GIL-free) and the
depth pipeline runs vmapped over the sample axis on device, emitting the
``#chrom start end sample...`` matrix directly — the per-sample bed files
and the depthwed re-aggregation pass disappear.

Output values are round-half-up integer window means, exactly what
depthwed produces from %.4g bed rows (depthwed.go:94-106) for whole
windows.
"""

from __future__ import annotations

import argparse
import functools
import os
import sys

import jax
import numpy as np

from ..io.bai import read_bai, query_voffset
from ..io.bam import open_bam_file
from .depth import _decode_shard_segments
from ..io.fai import read_fai, write_fai
from .. import obs
from ..ops.coverage import bucket_size, window_bounds
from ..utils.decode_scaling import auto_processes, effective_cores
from ..ops.depth_pipeline import shard_depth_pipeline
from . import depth as _depth
from .depth import DEPTH_CAP_EXTRA, gen_regions
from .indexcov import get_short_name


def cohort_regions(fai_records, chrom: str, window: int,
                   bed: str | None):
    """Shard list for the cohort engines.

    The fai path is gen_regions' STEP-sized shards. Bed intervals are
    additionally (a) filtered by ``chrom`` when both are given (plain
    gen_regions ignores -c for beds) and (b) split at absolute
    multiples of the STEP-aligned shard size, so a whole-chromosome bed
    line costs the same bounded per-shard memory as the fai path —
    interior split points land on window boundaries, so the emitted
    windows are identical to an unsplit run."""
    regions = gen_regions(fai_records, chrom, window, bed)
    if not bed:
        return regions
    if chrom:
        regions = [r for r in regions if r[0] == chrom]
    step = max(1, _depth.STEP // window) * window
    out = []
    for c, s, e in regions:
        lo = s
        while lo < e:
            hi = min(e, (lo // step + 1) * step)
            out.append((c, lo, hi))
            lo = hi
    return out


def _batched_pipeline(seg_s, seg_e, keep, w0, rs, re, cap, length, window):
    fn = functools.partial(
        shard_depth_pipeline, length=length, window=window,
    )
    return jax.vmap(
        lambda a, b, c: fn(a, b, c, w0, rs, re, cap, np.int32(4),
                           np.int32(0))[0]
    )(seg_s, seg_e, keep)


def cohort_matrix_blocks(
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
    checkpoint=None,
    quarantine=None,
    policy=None,
    decode_device: bool = False,
):
    """(sample_names, total_windows, block generator) for the cohort
    depth matrix. ``bed`` restricts to the file's regions (the cohort
    analog of ``depth -b``); each bed interval becomes a shard whose
    windows tile it on absolute window-aligned coordinates.

    Each block is (chrom, starts, ends, vals) with vals an int64
    (samples, n_windows) array of round-half-up window means — the same
    numbers the text matrix carries, minus the ASCII. ``run_cohortdepth``
    formats them; ``cnv`` consumes the arrays directly (no temp-TSV hop,
    round-1 VERDICT weak #2). ``total_windows`` (the sum of block widths,
    known up front from the regions) lets consumers preallocate.

    ``engine``:
      - "hybrid" (the "auto" default when the native library is up):
        fused C++ decode + window reduction per (sample, shard) on
        GIL-free threads — nothing per-read crosses the host↔device
        link; the device consumes only the resulting (windows × samples)
        matrix for the cohort math downstream. This hierarchical
        reduction makes cohort e2e link-bandwidth-independent.
      - "device": ship segment endpoints and run the vmapped
        scatter+cumsum pipeline on the chip (the multi-chip sample-
        sharded path; also the fallback without native io).
    The engines produce identical matrices (tested) whenever
    window × depth_cap < 2**24 — the device path sums windows in f32
    (exact ints below 2**24; see depth_pipeline), the hybrid path in
    int64. Beyond that bound the hybrid values are the exact ones.

    ``prefetch_depth`` >= 1 routes the shard loop through the async
    staging pipeline (parallel/prefetch.py): up to that many shards are
    decoded, packed and (device engine) transferred ahead of the shard
    being computed. ``0`` is today's serial path; both produce
    identical matrices, and the device engine records the same spans on
    either: ``host-decode`` (one a sample-shard) and ``device-compute``
    stages into ``stage_timer`` (a utils.profiling.StageTimer), the
    consumer's ``decode-wait``, and ``pack``/``h2d``/``device-wait``/
    ``d2h``/``unpack`` transfer spans (docs/observability.md).

    Resilience (goleft_tpu/resilience/, all optional):
      - ``checkpoint`` (CheckpointStore): each region's per-sample
        int64 window-sum columns are committed atomically after the
        region computes, keyed by (file_key(bam), window, mapq,
        region) — a stale input invalidates only its own shards. A
        region whose every sample column is already committed is
        *resumed*: no decode, no compute, the block re-emits from the
        store byte-identically (counted in
        ``checkpoint.shards_resumed_total``). Works identically under
        every engine/prefetch variant because the skip happens at the
        region list.
      - ``quarantine`` + ``policy`` (Quarantine, RetryPolicy): each
        per-sample decode/reduce runs under the policy; a sample
        failing at OPEN (corrupt file/index) is quarantined before any
        output and its column disappears from the matrix, a sample
        failing permanently mid-run is quarantined and zero-fills its
        remaining shards. Without a quarantine, failures raise as
        before.
    """
    import concurrent.futures as cf
    import os
    import threading

    # resolve regions FIRST: a bad fai/bed/chrom must fail before the
    # (potentially huge) cohort of BAM handles is opened
    from ..io import remote

    fai_path = fai or (reference + ".fai" if reference else None)
    if fai_path is None:
        raise SystemExit("cohortdepth: need -r reference or --fai")
    if not remote.exists(fai_path) and reference \
            and not remote.is_remote(reference):
        write_fai(reference)
    fai_records = read_fai(fai_path)
    regions = cohort_regions(fai_records, chrom, window, bed)
    if not regions:
        raise SystemExit(
            "cohortdepth: no regions ("
            + (f"bed {bed!r} has no usable intervals"
               + (f" on chromosome {chrom!r}" if chrom else "")
               if bed else
               f"chromosome {chrom!r} not in {fai_path}?")
            + ")"
        )

    handles = []
    bais = []
    names = []
    bam_paths = []

    # a bare pool does not carry the submitting thread's trace
    load_ctx = obs.capture()

    def load(b):
        # lazy mmap-backed handles: residency scales with the shard
        # being decoded, not sum-of-BAM-sizes
        with obs.attach(load_ctx), obs.span("open-inputs",
                                            category="stage"):
            h = open_bam_file(b, lazy=True)
            if getattr(h, "is_cram", False):
                return h, None, get_short_name(b)
            bai_p = b + ".bai" if remote.exists(b + ".bai") else \
                b[:-4] + ".bai"
            return h, read_bai(bai_p), get_short_name(b)

    def _fallback_name(b):
        base = b.rsplit("/", 1)[-1]
        return base.rsplit(".", 1)[0]

    with cf.ThreadPoolExecutor(max_workers=processes) as ex:
        if quarantine is None:
            for b, (h, bai, nm) in zip(bams, ex.map(load, bams)):
                handles.append(h)
                bais.append(bai)
                names.append(nm)
                bam_paths.append(b)
        else:
            # open-phase quarantine: a sample whose file/index cannot
            # even be opened is dropped BEFORE any output — the run
            # proceeds exactly as if it had not been given that BAM
            futs = [ex.submit(load, b) for b in bams]
            for b, f in zip(bams, futs):
                try:
                    h, bai, nm = f.result()
                except (Exception, SystemExit) as e:  # noqa: BLE001
                    quarantine.add(("open", b), _fallback_name(b), b,
                                   e, classification="permanent",
                                   phase="open")
                    continue
                handles.append(h)
                bais.append(bai)
                names.append(nm)
                bam_paths.append(b)
            if not handles:
                raise SystemExit(
                    "cohortdepth: every input failed to open — "
                    + "; ".join(
                        f"{e['source']}: {e['error']}"
                        for e in quarantine.summary()["quarantined"]))
    if decode_device:
        # device-resident entropy decode for the CRAM-backed cohort
        # path: compressed block bytes + table arrays cross the wire,
        # the rANS Nx16 state machine runs next to the coverage
        # kernels, unsupported flag combos (ORDER1/STRIPE) fall back
        # per-block to host decode (decode.device_fallback_total) —
        # matrix bytes identical either way (docs/decode.md)
        from ..obs import get_logger
        from ..ops.rans_device import DeviceBlockDecoder

        dec = DeviceBlockDecoder(policy=policy)
        n_cram = 0
        for h in handles:
            if getattr(h, "is_cram", False):
                h.set_block_decoder(dec)
                n_cram += 1
        if n_cram == 0:
            get_logger("cohortdepth").warning(
                "--decode-device: no CRAM inputs in this cohort — "
                "BAM/BGZF inflate stays host-side (ROADMAP wire-gap "
                "item); flag is a no-op")
    max_span = max(e - (s // window) * window for _, s, e in regions)
    length = (max_span + window - 1) // window * window
    cap = np.int32(DEPTH_CAP_EXTRA)
    # tid is per-sample: reference dictionaries may order contigs
    # differently (or miss some) across BAMs
    tid_maps = [
        {n: i for i, n in enumerate(h.header.ref_names)} for h in handles
    ]
    S = len(handles)

    def _fused(h):
        # BamFile with the native lib, or a CRAM handle (its
        # window_reduce is Python-orchestrated over the C codec ports)
        return getattr(h, "native", False) or getattr(h, "is_cram",
                                                      False)

    if engine == "auto":
        engine = "hybrid" if all(_fused(h) for h in handles) \
            else "device"
    if engine == "hybrid" and not all(_fused(h) for h in handles):
        raise SystemExit("cohortdepth: engine=hybrid needs the native io")

    # multi-chip: shard the sample axis across all devices (data
    # parallelism — XLA partitions the vmapped pipeline, no collectives
    # needed); single chip runs the same code unsharded. Device discovery
    # is deferred to the device engine: the hybrid engine is pure host
    # work and must not block on (or pay for) accelerator bring-up.
    sharding = None
    S_pad = S
    if engine != "hybrid":
        devs = jax.devices()
        n_dev = len(devs)
        if n_dev > 1:
            from jax.sharding import Mesh, NamedSharding, \
                PartitionSpec as P

            mesh = Mesh(np.array(devs), ("data",))
            sharding = NamedSharding(mesh, P("data", None))
            S_pad = ((S + n_dev - 1) // n_dev) * n_dev

    # the plan layer: per-sample decode/reduce and the per-region
    # checkpoint/fault boundary both lower into Steps run by this one
    # Executor, so retry/quarantine/checkpoint compose here exactly as
    # they do for the scheduler and serve paths
    from ..plan import Executor as PlanExecutor, Step
    from ..utils.profiling import StageTimer

    pex = PlanExecutor(policy=policy, quarantine=quarantine,
                       checkpoint=checkpoint)
    timer = stage_timer if stage_timer is not None else StageTimer()

    def _guard_sample(i, key, thunk, fallback):
        """Per-sample resilience boundary: retry under the policy,
        quarantine on exhaustion (zero-filling via ``fallback``),
        transparent when the resilience layer is off."""
        return pex.run(Step(key=key, fn=thunk,
                            quarantine_key=i,
                            quarantine_name=names[i],
                            quarantine_source=bam_paths[i],
                            fallback=fallback))

    def decode(args):
        """(seg_start, seg_end) already filtered/clipped for the device
        segment path — the ONE shared decode helper depth/multidepth
        use (BamFile streams through the C walk; CRAM falls back to
        columns + the shared filter/clip)."""
        i, h, bai, tid, s, e = args
        empty = np.zeros(0, np.int32)
        with timer.stage("host-decode"):
            return _guard_sample(
                i, (names[i], s, e),
                lambda: _decode_shard_segments(h, bai, tid, s, e, mapq),
                lambda: (empty, empty))

    def submit_decodes(ex, c, s, e):
        # a bare pool does not carry the submitting thread's trace
        ctx = obs.capture()

        def task(args):
            with obs.attach(ctx):
                return decode(args)

        return [
            ex.submit(task, (i, h, b, tm.get(c, -1), s, e))
            for i, (h, b, tm) in enumerate(zip(handles, bais,
                                               tid_maps))
        ]

    # hybrid engine: fused C++ decode+reduce per (sample, region); one
    # thread-local delta scratch per worker
    _tl = threading.local()

    def reduce_task(i, h, bai, tid, s, e, w0, length_r):
        n_win_r = length_r // window

        def fallback():
            return np.zeros(n_win_r, np.int64)

        def body():
            if tid < 0:
                return fallback()
            if bai is None:  # CRAM handle: .crai-driven access inside
                return h.window_reduce(tid, s, e, w0, length_r, window,
                                       int(cap), mapq, 0x704)
            voff = query_voffset(bai, tid, s)
            if voff is None:
                return fallback()
            # no scratch passed: the lean streaming path needs none,
            # and the rare dense fallback (pileups past depth_cap)
            # allocates its own
            return h.window_reduce(
                tid, s, e, w0, length_r, window, int(cap), mapq,
                0x704, voffset=voff,
            )

        return _guard_sample(i, (names[i], s, e), body, fallback)

    def submit_reduces(ex, c, s, e):
        w0 = s // window * window
        length_r = ((e - w0) + window - 1) // window * window
        return [
            ex.submit(reduce_task, i, h, b, tm.get(c, -1), s, e, w0,
                      length_r)
            for i, (h, b, tm) in enumerate(zip(handles, bais,
                                               tid_maps))
        ]

    def emit_block(c, s, e, sums):
        """Shared window-mean → round-half-up int conversion: the one
        place that defines the matrix's values for BOTH engines."""
        starts, ends, _, _ = window_bounds(s, e, window)
        spans = (ends - starts).astype(np.float64)
        means = sums[:, : len(starts)] / spans[None, :]
        vals = (0.5 + means).astype(np.int64)
        return c, starts, ends, vals

    # ---- checkpoint keying: content identity per (sample, region).
    # A region whose every sample column is committed is skipped
    # entirely (no decode, no compute) — regardless of engine or
    # prefetch variant, because the skip removes it from the region
    # list the generators see.
    resumed: set = set()
    region_keys = None
    if checkpoint is not None:
        from ..parallel.scheduler import file_key

        fkeys = [file_key(b) for b in bam_paths]

        def region_keys(r):  # noqa: F811 — the real binding
            return [("cohortdepth", fk, window, mapq, tuple(r))
                    for fk in fkeys]

        for r in regions:
            if all(checkpoint.has(k) for k in region_keys(r)):
                resumed.add(tuple(r))
    compute_regions = [r for r in regions if tuple(r) not in resumed]

    def blocks_hybrid():
        if processes <= 1 or effective_cores() <= 1:
            # single core: thread churn only costs (the native calls
            # release the GIL but there is no second core to take them)
            for c, s, e in compute_regions:
                w0 = s // window * window
                length_r = ((e - w0) + window - 1) // window * window
                sums = np.stack([
                    reduce_task(i, h, b, tm.get(c, -1), s, e, w0,
                                length_r)
                    for i, (h, b, tm) in enumerate(zip(handles, bais,
                                                       tid_maps))
                ])
                yield emit_block(c, s, e, sums)
            return
        with cf.ThreadPoolExecutor(max_workers=processes) as ex:
            pending = submit_reduces(ex, *compute_regions[0])
            for ri, (c, s, e) in enumerate(compute_regions):
                sums = np.stack([f.result() for f in pending])
                if ri + 1 < len(compute_regions):
                    pending = submit_reduces(ex, *compute_regions[ri + 1])
                yield emit_block(c, s, e, sums)

    def pack_segblock(segs):
        """The device engine's staging step: padded endpoint arrays —
        the ONE packing used by the serial and prefetched paths."""
        n_max = max((len(ss) for ss, _ in segs), default=0)
        b = bucket_size(max(n_max, 1))
        seg_s = np.zeros((S_pad, b), dtype=np.int32)
        seg_e = np.zeros((S_pad, b), dtype=np.int32)
        keep = np.zeros((S_pad, b), dtype=bool)
        for i, (ss, ee) in enumerate(segs):
            n = len(ss)
            if not n:
                continue
            seg_s[i, :n] = ss
            seg_e[i, :n] = ee
            keep[i, :n] = True  # pre-filtered in decode()
        return seg_s, seg_e, keep

    def transfer_device(args, region=None):
        """Place one packed batch on the device (across the mesh's
        devices when there are several, and then say in the metrics
        where it landed: how many devices hold a shard and how many
        sample rows each holds; S_pad rows on every one would be
        copies, not a split)."""
        args = obs.h2d(args, sharding)
        if sharding is not None:
            shards = args[0].addressable_shards
            reg = obs.get_registry()
            reg.gauge("cohortdepth.batch_devices").set(
                len({sh.device for sh in shards}))
            reg.gauge("cohortdepth.batch_shard_rows").set(
                shards[0].data.shape[0])
        return args

    def run_pipeline(args, c, s, e):
        w0 = s // window * window
        (sums,) = obs.fetch(_batched_pipeline(
            *args, np.int32(w0), np.int32(s),
            np.int32(e), cap, length, window,
        ))
        with obs.span("unpack", category="transfer"):
            return emit_block(c, s, e, sums[:S])

    def blocks():
        with cf.ThreadPoolExecutor(max_workers=processes) as ex:
            # double-buffer: while the device chews shard k, threads
            # decode shard k+1 (native decode releases the GIL)
            pending = submit_decodes(ex, *compute_regions[0])
            for ri, (c, s, e) in enumerate(compute_regions):
                with obs.span("decode-wait", category="wait"):
                    segs = [f.result() for f in pending]
                if ri + 1 < len(compute_regions):
                    pending = submit_decodes(ex, *compute_regions[ri + 1])
                with timer.stage("device-compute"):
                    with obs.span("pack", category="transfer"):
                        args = pack_segblock(segs)
                    blk = run_pipeline(transfer_device(args), c, s, e)
                yield blk

    # ---- prefetched variants: the async staging pipeline ----
    # (parallel/prefetch.py). The producer unit is a whole shard (all
    # samples, decoded serially on one worker); parallelism comes from
    # prefetch_depth shards in flight across the decode pool — vs the
    # serial paths' one-region lookahead. Identical matrices either way.

    def produce_device(region):
        c, s, e = region
        segs = [decode((i, h, b2, tm.get(c, -1), s, e))
                for i, (h, b2, tm) in enumerate(zip(handles, bais,
                                                    tid_maps))]
        with obs.span("pack", category="transfer"):
            return pack_segblock(segs)

    def blocks_prefetched():
        from ..parallel.prefetch import ChunkPrefetcher

        # transfer_device runs on the producer thread: the H2D copy of
        # shard k+1 (and the wait for it) overlaps shard k's compute
        with ChunkPrefetcher(compute_regions, produce_device,
                             depth=prefetch_depth,
                             transfer=transfer_device,
                             processes=processes) as pf:
            chunks = iter(pf)
            while True:
                with obs.span("decode-wait", category="wait"):
                    ch = next(chunks, None)
                if ch is None:
                    return
                with timer.stage("device-compute"):
                    blk = run_pipeline(ch.value, *ch.meta)
                yield blk

    def produce_hybrid(region):
        c, s, e = region
        w0 = s // window * window
        length_r = ((e - w0) + window - 1) // window * window
        with timer.stage("decode"):
            return np.stack([
                reduce_task(i, h, b2, tm.get(c, -1), s, e, w0,
                            length_r)
                for i, (h, b2, tm) in enumerate(zip(handles, bais,
                                                    tid_maps))
            ])

    def blocks_hybrid_prefetched():
        from ..parallel.prefetch import ChunkPrefetcher

        with ChunkPrefetcher(compute_regions, produce_hybrid,
                             depth=prefetch_depth,
                             processes=processes) as pf:
            for ch in pf:
                with timer.stage("compute"):
                    blk = emit_block(*ch.meta, ch.value)
                yield blk

    total_windows = sum(
        (e - s // window * window + window - 1) // window
        for _, s, e in regions
    )
    if prefetch_depth > 0:
        gen = (blocks_hybrid_prefetched() if engine == "hybrid"
               else blocks_prefetched())
    else:
        gen = blocks_hybrid() if engine == "hybrid" else blocks()

    from ..resilience import faults as _faults

    def _region_step(r, it):
        """One region as a plan Step: the 'shard' fault site fires per
        computed region — exactly between journal commits, which is
        what the chaos smoke's mid-flight kill exercises — and a fully
        committed region restores from the store byte-identically
        (no decode, no compute). ``retry=False``: the region advance
        wraps the engines' own per-sample Steps, which carry the
        policy; a region-level failure propagates raw as before."""
        c, s, e = r

        def restore(cols):
            starts, ends, _, _ = window_bounds(s, e, window)
            return c, starts, ends, np.stack(cols)

        def commit(blk):
            vals = blk[3]
            return [(k, vals[i])
                    for i, k in enumerate(region_keys(r))
                    if quarantine is None or i not in quarantine]

        return Step(key=tuple(r), fn=lambda: next(it), site="shard",
                    retry=False,
                    checkpoint_keys=(region_keys(r)
                                     if checkpoint is not None
                                     else None),
                    restore=restore, commit=commit)

    def _with_resilience(inner):
        """Interleave resumed blocks (from the checkpoint store, in
        region order) with freshly computed ones, committing each
        computed region's per-sample columns in one journal commit —
        all through the plan Executor."""
        it = iter(inner)
        for r in regions:
            yield pex.run(_region_step(r, it))

    if checkpoint is not None or _faults.get_plan() is not None:
        gen = _with_resilience(gen)
    return names, total_windows, gen


def run_cohortdepth(
    bams: list[str],
    reference: str | None = None,
    fai: str | None = None,
    window: int = 250,
    mapq: int = 1,
    chrom: str = "",
    processes: int = 8,
    out=None,
    engine: str = "auto",
    bed: str | None = None,
    prefetch_depth: int = 0,
    stage_timer=None,
    checkpoint_dir: str | None = None,
    resume: bool = False,
    resilient: bool = True,
    decode_device: bool = False,
):
    """Returns the process exit code: 0 on a clean run, 3 when the
    cohort completed degraded (one or more samples quarantined — the
    partial matrix was written and the quarantine manifest records
    who/why)."""
    out = out or sys.stdout
    if jax.process_count() > 1:
        # multi-host world (mesh.init_distributed): samples shard
        # across processes, decode wall time divides by the process
        # count, the matrix assembles over DCN; process 0 writes
        from ..parallel.distributed_cohort import (
            distributed_cohort_matrix,
        )

        names, chroms_a, starts_a, ends_a, mat = \
            distributed_cohort_matrix(
                bams, reference=reference, fai=fai, window=window,
                mapq=mapq, chrom=chrom, processes=processes,
                engine=engine, bed=bed,
                prefetch_depth=prefetch_depth,
                stage_timer=stage_timer,
            )
        if jax.process_index() != 0:
            return

        def chrom_blocks():
            lo = 0
            for hi in range(1, len(chroms_a) + 1):
                if hi == len(chroms_a) or chroms_a[hi] != chroms_a[lo]:
                    yield (chroms_a[lo], starts_a[lo:hi],
                           ends_a[lo:hi],
                           mat[lo:hi].T.astype(np.int64))
                    lo = hi

        blocks = chrom_blocks()
        quarantine = checkpoint = None
    else:
        from .. import resilience
        from ..resilience import CheckpointStore, Quarantine, \
            RetryPolicy

        # the multi-host path above runs without the resilience layer
        # (collectives make per-sample isolation a different problem);
        # the single-host flagship path gets quarantine + retry by
        # default and checkpointing when asked
        quarantine = Quarantine() if resilient else None
        policy = RetryPolicy() if resilient else None
        checkpoint = None
        if checkpoint_dir:
            checkpoint = CheckpointStore(checkpoint_dir, resume=resume)
        resilience.set_run_state(quarantine=quarantine,
                                 checkpoint=checkpoint)
        names, _, blocks = cohort_matrix_blocks(
            bams, reference=reference, fai=fai, window=window,
            mapq=mapq, chrom=chrom, processes=processes, engine=engine,
            bed=bed, prefetch_depth=prefetch_depth,
            stage_timer=stage_timer, checkpoint=checkpoint,
            quarantine=quarantine, policy=policy,
            decode_device=decode_device,
        )
    from ..io import native

    try:
        out.write("#chrom\tstart\tend\t" + "\t".join(names) + "\n")
        use_native_fmt = native.get_lib() is not None
        for c, starts, ends, vals in blocks:
            # the block's format-and-write, not the generator's next()
            with obs.span("write-output", category="stage"):
                if use_native_fmt:
                    buf = native.format_matrix_rows(c, starts, ends,
                                                    vals)
                    out.write(buf.decode("ascii"))
                else:
                    lines = [
                        f"{c}\t{starts[i]}\t{ends[i]}\t"
                        + "\t".join(str(v) for v in vals[:, i]) + "\n"
                        for i in range(len(starts))
                    ]
                    out.write("".join(lines))
    finally:
        if checkpoint is not None:
            checkpoint.close()
    if quarantine:
        if checkpoint_dir:
            quarantine.write(
                os.path.join(checkpoint_dir, "quarantine.json"))
        print(quarantine.exit_summary(), file=sys.stderr)
        return 3
    return 0


def main(argv=None):
    p = argparse.ArgumentParser(
        "goleft-tpu cohortdepth",
        description="windowed depth matrix for a cohort in one "
                    "device-batched pass",
    )
    p.add_argument("-w", "--windowsize", type=int, default=250)
    p.add_argument("-Q", "--mapq", type=int, default=1)
    p.add_argument("-c", "--chrom", default="")
    p.add_argument("-b", "--bed", default=None,
                   help="restrict to regions in this bed (cohort "
                        "analog of depth -b)")
    p.add_argument("-r", "--reference", default=None)
    p.add_argument("--fai", default=None)
    p.add_argument("-p", "--processes", type=int, default=None,
                   help="decode threads (default: one per effective "
                        "core, capped at 8 — on a 1-core host that is "
                        "1, which takes the serial no-churn path)")
    p.add_argument("--engine", choices=("auto", "hybrid", "device"),
                   default="auto",
                   help="hybrid: fused C++ host reduction (default when "
                        "native io is available); device: per-read "
                        "segments to the chip")
    p.add_argument("--prefetch-depth", type=int, default=0,
                   help="async staging pipeline depth: decode/pack/"
                        "transfer up to N shards ahead of the shard "
                        "being computed (0 = serial path, identical "
                        "output)")
    p.add_argument("--decode-device", action="store_true",
                   help="CRAM inputs: ship compressed rANS-Nx16 block "
                        "bytes + table arrays over the wire and run "
                        "the entropy decode on the device next to the "
                        "coverage kernels (ORDER1/STRIPE blocks fall "
                        "back to host decode per-block; output bytes "
                        "identical — docs/decode.md)")
    p.add_argument("--checkpoint-dir", default=None,
                   help="atomic sharded checkpoint store: per-region "
                        "per-sample column blocks + fsync'd journal "
                        "(docs/resilience.md); with --resume a killed "
                        "run restarts from its committed shards with "
                        "byte-identical output")
    p.add_argument("--resume", action="store_true",
                   help="replay the checkpoint journal and skip "
                        "committed shards (requires --checkpoint-dir)")
    from . import add_no_crc_flag, apply_no_crc

    add_no_crc_flag(p)
    p.add_argument("bams", nargs="+")
    a = p.parse_args(argv)
    apply_no_crc(a.no_crc)
    if a.resume and not a.checkpoint_dir:
        p.error("--resume requires --checkpoint-dir")
    from ..parallel.mesh import init_distributed

    init_distributed()  # idempotent; the CLI dispatcher already ran it
    return run_cohortdepth(
        a.bams, reference=a.reference, fai=a.fai, window=a.windowsize,
        mapq=a.mapq, chrom=a.chrom,
        processes=(auto_processes() if a.processes is None
                   else a.processes),
        engine=a.engine, bed=a.bed, prefetch_depth=a.prefetch_depth,
        checkpoint_dir=a.checkpoint_dir, resume=a.resume,
        decode_device=a.decode_device,
    )


if __name__ == "__main__":
    main()
