"""Warm batch executors: one coalesced device pass per request batch.

Each executor owns one request kind. ``group_key(req)`` is the
compatibility signature the micro-batcher groups on (same parameters →
same regions → same program geometry); ``run(reqs)`` executes the
whole batch and returns one response dict per request, in order.

Coalescing is genuine device-level batching, not loop fusion:

  - depth: every sample (one per request) joins a single vmapped
    ``shard_depth_pipeline_cls_packed`` dispatch per shard region
    (DepthEngine.run_segments_batch) — a burst of B requests costs the
    device one pass per region instead of B
  - indexcov: all requests' samples stack into ONE ``chrom_qc`` call
    per chromosome; the only cross-sample term (the missing-tail-bin
    count, relative to the cohort's longest sample) is corrected back
    to each request's own cohort on host, exactly, so responses are
    independent of what else was in the batch
  - cohortdepth: requests' cohorts concatenate into one
    ``cohort_matrix_blocks`` run (window means are per-sample
    independent) and each response slices its own sample columns
  - pairhmm: all requests' windows flatten into ONE bucketed
    wavefront batch (read×hap pairs are independent and the forward
    is bitwise padding-invariant, so coalescing cannot change any
    request's bytes); each response formats its own windows' rows —
    byte-identical to the one-shot ``goleft-tpu pairhmm`` CLI

Executors run on the batcher's single dispatcher thread: device passes
are serialized, and all jitted programs stay warm in the process-wide
compile cache across requests — the service's whole point.
"""

from __future__ import annotations

import concurrent.futures as cf
import contextlib
import io
import os
import re
from typing import Sequence

import numpy as np

from .. import obs
from ..io import remote as _remote

# the data-plane existence check: _exists for paths, an
# identity probe for http(s)/s3 URLs — what lets every executor accept
# remote inputs wherever it accepted a path
_exists = _remote.exists


class BadRequest(ValueError):
    """Malformed/unsupported request payload (HTTP 400)."""


def _stage(metrics, name: str):
    """metrics.timer span, or a no-op when running without metrics."""
    if metrics is None:
        return contextlib.nullcontext()
    return metrics.timer.stage(name)


def _dispatch(metrics, name: str, fn, retry: bool = True, key=None,
              count_passes: bool = False, signature=None, **attrs):
    """The executors' dispatch boundary, lowered through the plan
    layer (plan/executor.py run_device_step): the shared ``compute``
    stage wall-clock PLUS a device-event span carrying backend/
    platform attributes, with the ``device`` fault site fired per
    attempt and transient failures retried under the default
    RetryPolicy — a transient device fault costs one backoff instead
    of failing every request that shared the batch. The wrapped calls
    fetch their results to host numpy before returning, so the span's
    extent already fences on the device work.

    ``key``: content identity of the pass (every input's file_key +
    the canonical parameters + the batch order) — it seeds the retry
    policy's deterministic jitter and labels injected faults with
    WHAT was being computed, not just where. Dispatches do NOT join
    the in-flight dedup table: batches are serialized on the one
    dispatcher thread, so two executor steps are never genuinely
    concurrent — except a watchdog-abandoned straggler, which a
    re-queued pass must NOT join (the retry exists to escape it).
    Cross-request dedup lives at the request boundary instead
    (ServeApp._handle), where handler threads really are concurrent.
    ``count_passes=True`` moves the ``device_passes_total`` inc into
    run_device_step, which only counts genuinely executed steps.

    Failures that survive the retry budget raise out of the executor;
    the batcher's bisect-and-retry isolation (serve/batcher.py) then
    narrows them to the poisoned request instead of 500ing the whole
    coalesced batch."""
    from ..plan.executor import run_device_step

    return run_device_step(name, fn, metrics=metrics, retry=retry,
                           key=key, count_passes=count_passes,
                           signature=signature, **attrs)


def _require(req: dict, field: str):
    v = req.get(field)
    if not v:
        raise BadRequest(f"missing required field {field!r}")
    return v


def _resolve_fai(req: dict) -> str:
    """reference/fai resolution shared by depth and cohortdepth —
    the same rules run_depth applies (reference implies reference.fai,
    written on demand when only the fasta exists)."""
    fai = req.get("fai")
    reference = req.get("reference")
    fai_path = fai or (reference + ".fai" if reference else None)
    if fai_path is None:
        raise BadRequest("need 'reference' (with .fai) or 'fai'")
    if not _exists(fai_path):
        if reference and not _remote.is_remote(reference) \
                and os.path.exists(reference):
            from ..io.fai import write_fai

            write_fai(reference)
        else:
            raise BadRequest(f"fasta index not found: {fai_path}")
    return fai_path


class DepthExecutor:
    """`/v1/depth`: one BAM/CRAM per request → the depth.bed +
    callable.bed bytes the one-shot CLI writes, byte-identical."""

    kind = "depth"

    def __init__(self, processes: int = 4, metrics=None):
        self.processes = processes
        self.metrics = metrics

    def validate(self, req: dict) -> None:
        bam = _require(req, "bam")
        if not _exists(bam):
            raise BadRequest(f"no such file: {bam}")
        if not req.get("bed"):
            _resolve_fai(req)

    def group_key(self, req: dict) -> tuple:
        return (self.kind, int(req.get("window", 250)),
                int(req.get("mincov", 4)),
                int(req.get("maxmeandepth", 0)),
                int(req.get("mapq", 1)), req.get("chrom", "") or "",
                req.get("bed") or None,
                None if req.get("bed") else _resolve_fai(req))

    def cache_files(self, req: dict) -> list[str]:
        return [req["bam"]]

    def run(self, reqs: Sequence[dict]) -> list[dict]:
        from ..commands.depth import (
            DepthEngine, _decode_shard_segments, gen_regions,
            write_shard_output,
        )
        from ..io.bai import read_bai
        from ..io.bam import open_bam_file
        from ..io.fai import read_fai
        from ..parallel.scheduler import file_key

        p0 = reqs[0]
        window = int(p0.get("window", 250))
        mapq = int(p0.get("mapq", 1))
        bed = p0.get("bed") or None
        chrom = p0.get("chrom", "") or ""
        fai_records = [] if bed else read_fai(_resolve_fai(p0))
        regions = gen_regions(fai_records, chrom, window, bed)
        max_span = max((e - (s // window) * window
                        for _, s, e in regions), default=1)
        mincov = int(p0.get("mincov", 4))
        maxmeandepth = int(p0.get("maxmeandepth", 0))
        engine = DepthEngine(window, mincov, maxmeandepth, mapq,
                             max_span=max_span)
        # content identity of one region pass: every parameter the
        # engine reads, the region source (bed or fai — their CONTENT
        # shapes the regions), and each batch member's BAM identity in
        # order — the dedup key a concurrent identical dispatch joins
        base_key = ("serve.depth", window, mincov, maxmeandepth, mapq,
                    chrom, file_key(bed) if bed
                    else file_key(_resolve_fai(p0)),
                    tuple(file_key(r["bam"]) for r in reqs))

        def _open(req):
            handle = open_bam_file(req["bam"], lazy=True)
            if getattr(handle, "is_cram", False):
                bai = None
            else:
                b = req["bam"]
                bai = read_bai(b + ".bai" if _exists(b + ".bai")
                               else b[:-4] + ".bai")
            tid_of = {n: i
                      for i, n in enumerate(handle.header.ref_names)}
            return handle, bai, tid_of

        opened = [_open(r) for r in reqs]
        outs = [(io.StringIO(), io.StringIO()) for _ in reqs]
        try:
            with cf.ThreadPoolExecutor(
                    max_workers=max(1, self.processes)) as ex:
                for c, s, e in regions:
                    def _dec(o, c=c, s=s, e=e):
                        handle, bai, tid_of = o
                        return _decode_shard_segments(
                            handle, bai, tid_of.get(c, -1), s, e, mapq)

                    with _stage(self.metrics, "decode"):
                        segs = list(ex.map(_dec, opened))
                    from ..ops.coverage import bucket_size

                    starts, ends, sums, cls = _dispatch(
                        self.metrics, "serve.depth.dispatch",
                        lambda: engine.run_segments_batch(segs, s, e),
                        key=base_key + (c, s, e), count_passes=True,
                        # the compiled program's full geometry — what
                        # serve --warmup needs to recreate this
                        # compile from a manifest entry
                        signature={
                            "b": len(segs),
                            "bucket": bucket_size(max(
                                max((len(ss) for ss, _ in segs),
                                    default=0), 1)),
                            "length": engine.length,
                            "window": engine.w_eff,
                        },
                        batch=len(segs), region=f"{c}:{s}-{e}")
                    with _stage(self.metrics, "format"):
                        for i, (dout, cout) in enumerate(outs):
                            write_shard_output(c, starts, ends,
                                               sums[i], cls[i], s,
                                               dout, cout, None)
        finally:
            for handle, _, _ in opened:
                close = getattr(handle, "close", None)
                if close is not None:
                    try:
                        close()
                    except Exception:  # noqa: BLE001 — best-effort
                        pass
        return [{
            "depth_bed": d.getvalue(),
            "callable_bed": c.getvalue(),
            "shards": len(regions),
        } for d, c in outs]


class IndexcovExecutor:
    """`/v1/indexcov`: index-only cohort QC — per-sample copy number
    and bin counters per chromosome, one fused chrom_qc device call per
    chromosome for the WHOLE batch."""

    kind = "indexcov"

    def __init__(self, processes: int = 8, metrics=None):
        self.processes = processes
        self.metrics = metrics

    def validate(self, req: dict) -> None:
        for p in _require(req, "bams"):
            if not _exists(p):
                raise BadRequest(f"no such file: {p}")
        fai = _require(req, "fai")  # batching needs one shared ref dict
        if not _exists(fai):
            raise BadRequest(f"no such file: {fai}")

    def group_key(self, req: dict) -> tuple:
        from ..commands.indexcov import DEFAULT_EXCLUDE

        return (self.kind, req["fai"], req.get("chrom", "") or "",
                req.get("excludepatt", DEFAULT_EXCLUDE))

    def cache_files(self, req: dict) -> list[str]:
        return list(req["bams"])

    def run(self, reqs: Sequence[dict]) -> list[dict]:
        from ..commands.indexcov import (
            DEFAULT_EXCLUDE, SampleIndex, _pad_rows, get_short_name,
            references,
        )
        from ..ops import indexcov_ops as ops
        from ..parallel.scheduler import file_key

        p0 = reqs[0]
        refs = references([], p0["fai"], p0.get("chrom", "") or "")
        patt = p0.get("excludepatt", DEFAULT_EXCLUDE)
        exclude = re.compile(patt) if patt else None
        # content identity of one chrom_qc pass: the reference dict,
        # the filter params and every batch member's input identity in
        # order — the INDEX file (what normalized_depth actually
        # reads) alongside the named path, so a rebuilt .bai/.crai
        # changes the key even when the bam itself did not move
        def _input_keys(p):
            keys = [file_key(p)] if _exists(p) else [p]
            for ext in (".bai", ".crai"):
                if _exists(p + ext):
                    keys.append(file_key(p + ext))
            return tuple(keys)

        base_key = ("serve.indexcov", file_key(p0["fai"]),
                    p0.get("chrom", "") or "", patt,
                    tuple(_input_keys(p)
                          for r in reqs for p in r["bams"]))

        with cf.ThreadPoolExecutor(
                max_workers=max(1, self.processes)) as ex:
            idxs = list(ex.map(SampleIndex,
                               [p for r in reqs for p in r["bams"]]))
            names = list(ex.map(get_short_name,
                                [p for r in reqs for p in r["bams"]]))
        # sample-index ranges per request into the combined cohort
        bounds = np.cumsum([0] + [len(r["bams"]) for r in reqs])
        S = len(idxs)
        out = [{"samples": names[lo:hi], "chroms": [], "cn": {},
                "bin_counters": {k: [0] * (hi - lo)
                                 for k in ("in", "out", "hi", "low")}}
               for lo, hi in zip(bounds, bounds[1:])]

        for ref_id, ref_name, _len in refs:
            if exclude is not None and exclude.search(ref_name):
                continue
            rows = [idx.normalized_depth(ref_id) for idx in idxs]
            mat, valid, lengths = _pad_rows(rows)
            longest = int(lengths.max())
            if longest == 0:
                continue
            packed = _dispatch(
                self.metrics, "serve.indexcov.dispatch",
                lambda: np.asarray(
                    ops.chrom_qc(mat, valid, np.int32(longest))),
                key=base_key + (int(ref_id), ref_name),
                count_passes=True, samples=S, chrom=ref_name)
            _rocs, counters, cn = ops.unpack_chrom_qc(packed, S)
            for r, (lo, hi) in zip(out, zip(bounds, bounds[1:])):
                # tail bins count vs the LONGEST sample; that was the
                # batch-wide longest on device — correct out/low back
                # to this request's own cohort so the response is
                # independent of what else rode the batch (exact: the
                # tail term is additive integer arithmetic)
                own_longest = int(lengths[lo:hi].max())
                if own_longest == 0:
                    continue
                delta = longest - own_longest
                r["chroms"].append(ref_name)
                r["cn"][ref_name] = [round(float(v), 4)
                                     for v in cn[lo:hi]]
                for k in ("in", "hi"):
                    for j, v in enumerate(counters[k][lo:hi]):
                        r["bin_counters"][k][j] += int(v)
                for k in ("out", "low"):
                    for j, v in enumerate(counters[k][lo:hi]):
                        r["bin_counters"][k][j] += int(v) - delta
        return out


class PairhmmExecutor:
    """`/v1/pairhmm`: windows JSON (+ optional candidates file) →
    the genotype-likelihood table bytes the one-shot CLI writes,
    byte-identical. The first compute-dense executor: decode cost is
    trivial, the coalesced wavefront dispatch is the work."""

    kind = "pairhmm"

    def __init__(self, processes: int = 4, metrics=None):
        self.processes = processes
        self.metrics = metrics

    def validate(self, req: dict) -> None:
        path = _require(req, "input")
        if not _exists(path):
            raise BadRequest(f"no such file: {path}")
        cand = req.get("candidates")
        if cand and not _exists(cand):
            raise BadRequest(f"no such file: {cand}")
        # parse up front: a malformed document is this request's 400,
        # never a 500 poisoning everyone who shared its batch
        from ..commands.pairhmm_cmd import read_windows
        from ..models.candidates import read_candidates

        try:
            read_windows(path)
            if cand:
                read_candidates(cand)
        except ValueError as e:
            raise BadRequest(str(e)) from None

    def group_key(self, req: dict) -> tuple:
        # only the numeric model parameters gate compatibility: each
        # request's windows are selected before coalescing, and the
        # forward is padding-invariant, so any same-parameter requests
        # may share a batch
        return (self.kind, float(req.get("gap_open", 45.0)),
                float(req.get("gap_ext", 10.0)),
                bool(req.get("f64", False)))

    def cache_files(self, req: dict) -> list[str]:
        files = [req["input"]]
        if req.get("candidates"):
            files.append(req["candidates"])
        return files

    def run(self, reqs: Sequence[dict]) -> list[dict]:
        from ..commands.pairhmm_cmd import read_windows, select_windows
        from ..models import genotype
        from ..parallel.scheduler import file_key

        p0 = reqs[0]
        with _stage(self.metrics, "decode"):
            per_req = [select_windows(read_windows(r["input"]),
                                      r.get("candidates") or None)
                       for r in reqs]
        windows = [w for ws in per_req for w in ws]
        bounds = np.cumsum([0] + [len(ws) for ws in per_req])
        n_pairs = sum(len(w["reads"]) * len(w["haps"])
                      for w in windows)
        # content identity of the coalesced wavefront pass: the model
        # parameters plus each batch member's (windows doc, candidate
        # file) identities in order — a concurrent identical dispatch
        # joins this pass through the in-flight step table
        step_key = ("serve.pairhmm",
                    float(p0.get("gap_open", 45.0)),
                    float(p0.get("gap_ext", 10.0)),
                    bool(p0.get("f64", False)),
                    tuple((file_key(r["input"]),
                           file_key(r["candidates"])
                           if r.get("candidates") else None)
                          for r in reqs))
        results, n_bad = _dispatch(
            self.metrics, "serve.pairhmm.dispatch",
            lambda: genotype.score_windows(
                windows,
                gap_open=float(p0.get("gap_open", 45.0)),
                gap_ext=float(p0.get("gap_ext", 10.0)),
                dtype=np.float64 if p0.get("f64") else np.float32),
            key=step_key, count_passes=True,
            windows=len(windows), pairs=n_pairs)
        with _stage(self.metrics, "format"):
            return [{
                "likelihoods_tsv": genotype.format_table(
                    results[lo:hi]),
                "windows": int(hi - lo),
            } for lo, hi in zip(bounds, bounds[1:])]


class CohortdepthExecutor:
    """`/v1/cohortdepth`: requests' cohorts concatenate into one
    cohort_matrix_blocks pass; each response carries its own
    byte-identical `#chrom start end sample…` matrix.

    ``checkpoint: true`` (needs the daemon's ``--checkpoint-root``)
    runs the pass against a persistent CheckpointStore: each region's
    per-sample columns commit as they compute, keyed by content
    identity (file_key per BAM + window/mapq/region — independent of
    batch composition), so a long request re-issued after a daemon
    crash/restart resumes from the committed shards byte-identically
    instead of starting over."""

    kind = "cohortdepth"

    def __init__(self, processes: int = 4, metrics=None,
                 checkpoint_root: str | None = None):
        self.processes = processes
        self.metrics = metrics
        self.checkpoint_root = checkpoint_root

    def validate(self, req: dict) -> None:
        if req.get("checkpoint") and not self.checkpoint_root:
            raise BadRequest(
                "checkpoint: true needs the daemon started with "
                "--checkpoint-root")
        for p in _require(req, "bams"):
            if not _exists(p):
                raise BadRequest(f"no such file: {p}")
        _resolve_fai(req)

    def group_key(self, req: dict) -> tuple:
        return (self.kind, _resolve_fai(req),
                int(req.get("window", 250)), int(req.get("mapq", 1)),
                req.get("chrom", "") or "", req.get("bed") or None,
                req.get("engine", "auto"),
                bool(req.get("checkpoint")),
                bool(req.get("decode_device")))

    def cache_files(self, req: dict) -> list[str]:
        return list(req["bams"])

    def _iter_blocks(self, blocks):
        """Advance the lazy block generator under the dispatch span:
        each block's decode + vmapped device pass happens inside
        ``next()``, so this is the cohortdepth executor's device-event
        boundary (the values arrive as host numpy — already fenced).
        ``retry=False``: a half-consumed generator is not safely
        re-attemptable — failures go straight to the batcher's bisect
        isolation, which re-runs whole sub-batches from scratch."""
        done = object()
        it = iter(blocks)
        i = 0
        while True:
            def _advance():
                try:
                    return next(it)
                except StopIteration:
                    return done

            blk = _dispatch(self.metrics,
                            "serve.cohortdepth.dispatch", _advance,
                            retry=False, block=i)
            if blk is done:
                return
            i += 1
            yield blk

    #: journal-batching factor under serve load: one fsync'd journal
    #: append per this many region commits (blocks stay immediate and
    #: atomic — a crash recomputes at most this many regions on
    #: resume, byte-identically). The chaos smoke's mid-flight kill
    #: (shard:after=5) lands one region past the first flush.
    JOURNAL_FLUSH_EVERY = 4

    def _open_store(self, reqs):
        """The persistent store for ``checkpoint: true`` requests —
        always opened with ``resume=True`` so commits accumulate
        across requests AND daemon restarts (content-keyed: stale
        inputs simply stop matching; entries for them go inert).
        Wrapped in :class:`DeferredCommits` so the region steps'
        journal writes spill through one batched ``put_many`` commit
        per :data:`JOURNAL_FLUSH_EVERY` dispatches instead of one
        fsync pair per step."""
        if not (self.checkpoint_root
                and any(r.get("checkpoint") for r in reqs)):
            return None
        from ..resilience.checkpoint import (
            CheckpointStore, DeferredCommits,
        )

        return DeferredCommits(
            CheckpointStore(
                os.path.join(self.checkpoint_root, "cohortdepth"),
                resume=True),
            flush_every=self.JOURNAL_FLUSH_EVERY)

    def run(self, reqs: Sequence[dict]) -> list[dict]:
        from ..commands.cohortdepth import cohort_matrix_blocks
        from ..io import native

        p0 = reqs[0]
        all_bams = [p for r in reqs for p in r["bams"]]
        bounds = np.cumsum([0] + [len(r["bams"]) for r in reqs])
        store = self._open_store(reqs)
        try:
            names, total_windows, blocks = cohort_matrix_blocks(
                all_bams, fai=_resolve_fai(p0),
                window=int(p0.get("window", 250)),
                mapq=int(p0.get("mapq", 1)),
                chrom=p0.get("chrom", "") or "",
                processes=max(1, self.processes),
                engine=p0.get("engine", "auto"),
                bed=p0.get("bed") or None,
                stage_timer=self.metrics.timer if self.metrics
                else None,
                checkpoint=store,
                decode_device=bool(p0.get("decode_device")),
            )
            use_native_fmt = native.get_lib() is not None
            bufs = [io.StringIO() for _ in reqs]
            for buf, (lo, hi) in zip(bufs, zip(bounds, bounds[1:])):
                buf.write("#chrom\tstart\tend\t"
                          + "\t".join(names[lo:hi]) + "\n")
            for c, starts, ends, vals in self._iter_blocks(blocks):
                if self.metrics:
                    self.metrics.inc("device_passes_total")
                for buf, (lo, hi) in zip(bufs, zip(bounds,
                                                   bounds[1:])):
                    sub = vals[lo:hi]
                    if use_native_fmt:
                        buf.write(native.format_matrix_rows(
                            c, starts, ends, sub).decode("ascii"))
                    else:
                        buf.write("".join(
                            f"{c}\t{starts[i]}\t{ends[i]}\t"
                            + "\t".join(str(v) for v in sub[:, i])
                            + "\n"
                            for i in range(len(starts))
                        ))
        finally:
            if store is not None:
                store.close()
        return [{
            "matrix_tsv": b.getvalue(),
            "samples": names[lo:hi],
            "windows": int(total_windows),
        } for b, (lo, hi) in zip(bufs, zip(bounds, bounds[1:]))]


class CohortscanExecutor:
    """`/v1/cohortscan`: the streaming incremental cohort QC scan —
    the indexcov artifact surface (bed.gz/.roc/.ped, byte-identical)
    produced with O(chunk × bins) peak memory and per-(sample,
    chromosome) content-keyed checkpoints.

    Requests are NOT coalesced across each other: a cohortscan is
    already one whole-cohort device pipeline, and mixing two cohorts
    would change each one's normalization scalars. ``run`` therefore
    loops requests (the batcher's bisect isolation still applies).

    ``checkpoint: true`` (needs the daemon's ``--checkpoint-root``)
    pins the scan's checkpoint store + manifest under a directory
    keyed by the scan *parameters* — NOT the sample list — so a
    re-issued request resumes byte-identically after a daemon restart,
    and an appended cohort (same params, +k samples) computes exactly
    the k new samples' QC blocks: the per-sample blocks are keyed by
    each input's own content identity (file_key / remote ETag), so
    old samples keep matching and a changed input invalidates only
    itself. Without the flag each request scans into a throwaway
    store."""

    kind = "cohortscan"

    def __init__(self, processes: int = 8, metrics=None,
                 checkpoint_root: str | None = None):
        self.processes = processes
        self.metrics = metrics
        self.checkpoint_root = checkpoint_root

    def validate(self, req: dict) -> None:
        if req.get("checkpoint") and not self.checkpoint_root:
            raise BadRequest(
                "checkpoint: true needs the daemon started with "
                "--checkpoint-root")
        for p in _require(req, "bams"):
            if not _exists(p):
                raise BadRequest(f"no such file: {p}")
        fai = _require(req, "fai")  # URL inputs carry no local .fai
        if not _exists(fai):
            raise BadRequest(f"no such file: {fai}")
        cs = req.get("chunk_samples")
        if cs is not None and int(cs) < 1:
            raise BadRequest("chunk_samples must be >= 1")

    def group_key(self, req: dict) -> tuple:
        from ..commands.indexcov import DEFAULT_EXCLUDE

        return (self.kind, req["fai"], req.get("chrom", "") or "",
                req.get("excludepatt", DEFAULT_EXCLUDE),
                req.get("sex", "X,Y"),
                bool(req.get("extranormalize")),
                bool(req.get("checkpoint")))

    def cache_files(self, req: dict) -> list[str]:
        return list(req["bams"])

    def _scan_dir(self, req: dict) -> tuple[str, str | None, bool]:
        """(output directory, checkpoint_dir, resume) for one request.

        Persistent mode keys the store directory by the canonical scan
        parameters + the reference identity — deliberately NOT the
        sample list, so append-k re-requests land in the same store
        and resume every previously committed sample."""
        import hashlib
        import json as _json
        import tempfile

        from ..commands.indexcov import DEFAULT_EXCLUDE

        if not (req.get("checkpoint") and self.checkpoint_root):
            return tempfile.mkdtemp(prefix="cohortscan-"), None, False
        from ..parallel.scheduler import file_key

        ident = _json.dumps([
            "serve.cohortscan", list(file_key(req["fai"])),
            req.get("chrom", "") or "",
            req.get("excludepatt", DEFAULT_EXCLUDE),
            req.get("sex", "X,Y"), bool(req.get("extranormalize")),
        ], sort_keys=True)
        digest = hashlib.sha256(ident.encode()).hexdigest()[:24]
        root = os.path.join(self.checkpoint_root, "cohortscan", digest)
        out_dir = os.path.join(root, "out")
        os.makedirs(out_dir, exist_ok=True)
        return out_dir, os.path.join(root, "ck"), True

    def run(self, reqs: Sequence[dict]) -> list[dict]:
        import base64
        import shutil

        from ..cohort.scan import run_cohortscan
        from ..commands.indexcov import DEFAULT_EXCLUDE

        out = []
        for req in reqs:
            out_dir, ck_dir, resume = self._scan_dir(req)
            try:
                res = _dispatch(
                    self.metrics, "serve.cohortscan.dispatch",
                    lambda: run_cohortscan(
                        list(req["bams"]), out_dir,
                        sex=req.get("sex", "X,Y"),
                        exclude_patt=req.get("excludepatt",
                                             DEFAULT_EXCLUDE),
                        chrom=req.get("chrom", "") or "",
                        fai=req["fai"],
                        extra_normalize=bool(
                            req.get("extranormalize")),
                        include_gl=bool(req.get("includegl")),
                        chunk_samples=int(
                            req.get("chunk_samples", 256)),
                        resume=resume, checkpoint_dir=ck_dir,
                        pca_mode=req.get("pca", "auto"),
                    ),
                    # a half-finished scan is not safely re-attemptable
                    # in-place; failures go to the batcher's bisect
                    # isolation (and a checkpointed re-request resumes)
                    retry=False, count_passes=True,
                    samples=len(req["bams"]))
                with open(res["bed"], "rb") as f:
                    bed_b64 = base64.b64encode(f.read()).decode("ascii")
                with open(res["roc"]) as f:
                    roc = f.read()
                with open(res["ped"]) as f:
                    ped = f.read()
                out.append({
                    "bed_gz_b64": bed_b64,
                    "roc": roc,
                    "ped": ped,
                    "samples": len(req["bams"]),
                    "chroms": res["chrom_names"],
                    "qc": res["qc"],
                    "diff": {k: len(v)
                             for k, v in res["diff"].items()},
                })
            finally:
                if ck_dir is None:  # throwaway scan: no resume value
                    shutil.rmtree(out_dir, ignore_errors=True)
        return out


class MapExecutor:
    """`/v1/map`: FASTQ path/URL + reference → the mapped read-tuple
    stream, byte-identical to the ``goleft-tpu map`` CLI.

    Coalescing: requests sharing (reference identity, mapping
    parameters) share the minimizer index (one build + one device
    upload per reference, process-cached) and their reads run through
    the same per-process seed/extend compile caches; each request's
    reads are seeded and extended independently, so a response's
    bytes cannot depend on what else shared the batch — the pipeline's
    padding invariance is pinned by the swalign bucket tests."""

    kind = "map"

    def __init__(self, processes: int = 4, metrics=None):
        self.processes = processes
        self.metrics = metrics

    def validate(self, req: dict) -> None:
        fastq = _require(req, "fastq")
        if not _exists(fastq):
            raise BadRequest(f"no such file: {fastq}")
        ref = _require(req, "reference")
        if not _exists(ref):
            raise BadRequest(f"no such file: {ref}")
        for field in ("k", "w", "max_occ", "min_support", "band",
                      "window"):
            v = req.get(field)
            if v is not None and (not isinstance(v, int) or v <= 0):
                raise BadRequest(f"{field} must be a positive int")

    def _params(self, req: dict):
        from ..mapping import MapParams
        from ..mapping.index import (
            DEFAULT_K, DEFAULT_MAX_OCC, DEFAULT_W,
        )
        from ..mapping.pipeline import (
            DEFAULT_BAND, DEFAULT_MIN_SUPPORT,
        )

        return MapParams(
            k=int(req.get("k", DEFAULT_K)),
            w=int(req.get("w", DEFAULT_W)),
            max_occ=int(req.get("max_occ", DEFAULT_MAX_OCC)),
            band=int(req.get("band", DEFAULT_BAND)),
            min_support=int(req.get("min_support",
                                    DEFAULT_MIN_SUPPORT)))

    def group_key(self, req: dict) -> tuple:
        from ..parallel.scheduler import file_key

        try:
            ref_id = tuple(file_key(req["reference"]))
        except OSError:
            ref_id = (req["reference"],)
        return (self.kind, ref_id) + self._params(req).key()

    def cache_files(self, req: dict) -> list[str]:
        return [req["fastq"], req["reference"]]

    def run(self, reqs: Sequence[dict]) -> list[dict]:
        from ..io.fastq import FastqError, read_fastq
        from ..mapping import get_index, map_reads
        from ..mapping.pipeline import (
            depth_bed_from_tuples, format_tuples,
        )
        from ..parallel.scheduler import file_key

        p0 = reqs[0]
        params = self._params(p0)
        index = get_index(p0["reference"], k=params.k, w=params.w,
                          max_occ=params.max_occ)
        with _stage(self.metrics, "decode"):
            per_req = []
            for r in reqs:
                try:
                    per_req.append(read_fastq(r["fastq"]))
                except FastqError as e:
                    # a corrupt FASTQ is this request's 400, never a
                    # 500 poisoning everyone who shared its batch
                    raise BadRequest(str(e)) from None
        out = []
        for r, records in zip(reqs, per_req):
            try:
                fq_id = tuple(file_key(r["fastq"]))
            except OSError:
                fq_id = (r["fastq"],)
            # the whole per-request pipeline (its seed + extend plan
            # Steps ride the 'map' fault site internally) under one
            # compute-stage step keyed by (fastq, reference, params)
            res = _dispatch(
                self.metrics, "serve.map.dispatch",
                lambda idx=index, recs=records: map_reads(
                    idx, recs, params),
                retry=False, count_passes=True,
                key=("serve.map", fq_id) + tuple(self.group_key(r)),
                reads=len(records))
            resp = {
                "tuples_tsv": format_tuples(res.tuples).decode(),
                "reads": res.stats["reads"],
                "mapped": res.stats["mapped"],
                "unmapped": res.stats["unmapped"],
                "failed": res.stats["failed"],
            }
            if r.get("window"):
                lengths = {
                    n: int(index.chrom_starts[i + 1]
                           - index.chrom_starts[i])
                    for i, n in enumerate(index.chrom_names)}
                resp["depth_bed"] = depth_bed_from_tuples(
                    [t for t in res.tuples if t is not None],
                    lengths, int(r["window"])).decode()
            out.append(resp)
        return out
