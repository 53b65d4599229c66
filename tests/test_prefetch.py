"""Async prefetch & staging subsystem (parallel/prefetch.py).

Covers the subsystem's hard guarantees — deterministic ordering under
out-of-order producer completion, backpressure at the configured depth,
worker-exception propagation at the failing chunk's ordered position,
cancellation on early exit — plus the end-to-end contracts: the chunked
carry-threaded cohort step is bit-identical to the monolithic program,
and ``--prefetch-depth 0`` output is byte-identical to the serial
cohort path on the golden depth fixture (all CPU-pinned via conftest).
"""

import io
import threading
import time

import numpy as np
import pytest

from goleft_tpu.parallel.prefetch import (
    ChunkPrefetcher,
    PrefetchWorkerError,
    run_prefetched_cohort,
)


def _collect(pf):
    return [(c.index, c.meta, c.value) for c in pf]


def test_ordered_delivery_under_out_of_order_completion():
    """Early chunks sleep longest: workers finish 3,2,1,0 but the
    consumer must still see 0,1,2,3 with their own payloads."""
    n = 4

    def produce(i):
        time.sleep(0.05 * (n - i))
        return i * 10

    with ChunkPrefetcher(range(n), produce, depth=n,
                         processes=n) as pf:
        got = _collect(pf)
    assert got == [(i, i, i * 10) for i in range(n)]


def test_transfer_runs_on_worker_and_chains_value():
    seen = []

    def produce(i):
        return i

    def transfer(v, meta):
        seen.append(threading.current_thread().name)
        return v + 100

    with ChunkPrefetcher(range(3), produce, depth=2,
                         transfer=transfer, processes=2) as pf:
        got = [c.value for c in pf]
    assert got == [100, 101, 102]
    assert all(name.startswith("goleft-prefetch") for name in seen)


def test_backpressure_bounds_in_flight_chunks():
    """With depth=2, a stalled consumer must never have more than the
    delivered chunk + 2 in-flight chunks produced — chunk 4 and 5 of 6
    may not start until the consumer drains."""
    started = []
    lock = threading.Lock()

    def produce(i):
        with lock:
            started.append(i)
        return i

    pf = ChunkPrefetcher(range(6), produce, depth=2, processes=4)
    it = iter(pf)
    first = next(it)
    assert first.index == 0
    time.sleep(0.2)  # give any (wrongly) eager submissions time to run
    with lock:
        snapshot = sorted(started)
    # delivered chunk 0 + at most depth=2 refilled behind it
    assert snapshot == [0, 1, 2], snapshot
    rest = [c.index for c in it]
    assert rest == [1, 2, 3, 4, 5]
    assert sorted(started) == list(range(6))


def test_worker_error_propagates_at_ordered_position():
    """Chunks before the failure arrive intact; the failure surfaces
    as PrefetchWorkerError at its ordinal slot with the original
    exception chained; chunks beyond the backpressure window are never
    produced after the error closes the pipeline."""
    started = []

    def produce(i):
        started.append(i)
        if i == 2:
            raise ValueError("decode worker blew up")
        return i

    delivered = []
    with pytest.raises(PrefetchWorkerError) as ei:
        with ChunkPrefetcher(range(6), produce, depth=2,
                             processes=2) as pf:
            for c in pf:
                delivered.append(c.index)
    assert delivered == [0, 1]
    assert ei.value.index == 2
    assert ei.value.meta == 2
    assert isinstance(ei.value.cause, ValueError)
    assert isinstance(ei.value.__cause__, ValueError)
    # 4 and 5 were outside the depth-2 window when the error hit
    assert 5 not in started and 4 not in started


def test_cancellation_on_early_exit_stops_producers():
    started = []
    ev = threading.Event()

    def produce(i):
        started.append(i)
        ev.wait(0.02)
        return i

    pf = ChunkPrefetcher(range(50), produce, depth=2, processes=2)
    for c in pf:
        break  # abandon mid-run
    pf.close()
    ev.set()
    time.sleep(0.1)
    n_started = len(started)
    time.sleep(0.1)
    assert len(started) == n_started  # nothing new after close
    assert n_started <= 5  # never ran ahead of the window


def test_depth_zero_rejected_and_bad_depth_message():
    with pytest.raises(ValueError, match="serial path"):
        ChunkPrefetcher([1], lambda x: x, depth=0)


def test_chunked_cohort_step_bit_identical_to_monolithic():
    """The carry-threaded chunked program (what the staging pipeline
    feeds) must reproduce the monolithic cohort step bit for bit —
    including across chunk-straddling segments."""
    from goleft_tpu.parallel.cohort_pipeline import build_cohort_step
    from goleft_tpu.parallel.mesh import make_mesh
    from goleft_tpu.parallel.sharded_coverage import partition_segments

    rng = np.random.default_rng(11)
    n_seq, shard_len, window = 4, 1024, 128
    l_chunk = n_seq * shard_len
    n_chunks = 3
    total = n_chunks * l_chunk
    S, n = 8, 3000
    starts = np.sort(
        rng.integers(0, total - 400, size=(S, n))).astype(np.int32)
    # long segments guarantee chunk-boundary straddlers
    ends = (starts + rng.integers(50, 3000, size=(S, n))).astype(
        np.int32)
    keep = rng.random((S, n)) < 0.9

    mesh = make_mesh(8, prefer_seq=n_seq)
    # monolithic reference: same mesh, shards covering the full extent
    step = build_cohort_step(mesh, total // n_seq, window)
    seg_s, seg_e, kp = partition_segments(starts, ends, keep, n_seq,
                                          total // n_seq)
    ref = step(seg_s, seg_e, kp)

    def decode_chunk(ci):
        lo = ci * l_chunk
        return starts - lo, ends - lo, keep

    for depth in (0, 2):
        out = run_prefetched_cohort(
            mesh, shard_len, window, list(range(n_chunks)),
            decode_chunk, S, prefetch_depth=depth)
        np.testing.assert_array_equal(out["depth"],
                                      np.asarray(ref["depth"]))
        np.testing.assert_array_equal(np.asarray(out["wmeans"]),
                                      np.asarray(ref["wmeans"]))
        np.testing.assert_array_equal(np.asarray(out["lambdas"]),
                                      np.asarray(ref["lambdas"]))
        np.testing.assert_array_equal(np.asarray(out["cn"]),
                                      np.asarray(ref["cn"]))
        # the final carry is the depth at the last base
        np.testing.assert_array_equal(
            out["carry"], np.asarray(ref["depth"])[:, -1])


def test_prefetched_cohort_spans_recorded():
    from goleft_tpu.parallel.mesh import make_mesh
    from goleft_tpu.utils.profiling import StageTimer

    rng = np.random.default_rng(3)
    n_seq, shard_len, window = 4, 512, 64
    l_chunk = n_seq * shard_len
    S, n = 4, 500
    starts = rng.integers(0, 2 * l_chunk - 100,
                          size=(S, n)).astype(np.int32)
    ends = (starts + 80).astype(np.int32)
    keep = np.ones((S, n), bool)
    mesh = make_mesh(8, prefer_seq=n_seq)

    tm = StageTimer()
    run_prefetched_cohort(
        mesh, shard_len, window, [0, 1],
        lambda ci: (starts - ci * l_chunk, ends - ci * l_chunk, keep),
        S, prefetch_depth=2, timer=tm)
    d = tm.as_dict()
    assert set(d) == {"decode", "stage", "transfer", "compute"}
    assert d["decode"]["calls"] == 2
    assert d["transfer"]["calls"] == 2
    assert d["compute"]["calls"] == 3  # 2 chunks + finalize


def _golden_cohort(tmp_path):
    """The golden depth fixture BAM (hand-derived read list from
    tests/golden/README.md) duplicated into a 3-sample cohort."""
    import shutil

    from test_golden_depth import _build_fixture

    fa, bam = _build_fixture(tmp_path)
    bams = [bam]
    for i in (1, 2):
        p = str(tmp_path / f"g{i}.bam")
        shutil.copyfile(bam, p)
        shutil.copyfile(bam + ".bai", p + ".bai")
        bams.append(p)
    return fa, bams


def test_prefetch_depth_zero_byte_identical_on_golden_fixture(
        tmp_path, monkeypatch):
    """--prefetch-depth 0 must produce the exact bytes of today's
    serial cohort path on the golden depth fixture, and depth >= 2
    must match both — across multiple shards (STEP shrunk so the
    fixture spans several regions)."""
    from goleft_tpu.commands import depth as depth_mod
    from goleft_tpu.commands.cohortdepth import run_cohortdepth

    fa, bams = _golden_cohort(tmp_path)
    monkeypatch.setattr(depth_mod, "STEP", 500)  # 2000bp -> 4 shards

    def run(**kw):
        buf = io.StringIO()
        run_cohortdepth(bams, reference=fa, window=100, out=buf,
                        engine="device", processes=2, **kw)
        return buf.getvalue()

    serial = run()
    assert serial.count("\n") == 21  # header + 20 windows x 100bp
    assert run(prefetch_depth=0) == serial
    assert run(prefetch_depth=2) == serial
    assert run(prefetch_depth=5) == serial


def test_scheduler_producer_role_retry_and_error_isolation():
    """scheduler.iter_prefetched: the decode pool's shard semantics
    (retry-once, errors as .error results, task ordering) delivered
    through the prefetcher's bounded queue."""
    from goleft_tpu.parallel.scheduler import iter_prefetched

    calls = {}

    def fn(i):
        calls[i] = calls.get(i, 0) + 1
        if i == 1 and calls[i] == 1:
            raise RuntimeError("transient")  # retry-once recovers
        if i == 3:
            raise RuntimeError("permanent")  # both attempts fail
        return i * 2

    results = list(iter_prefetched([(i,) for i in range(5)], fn,
                                   depth=2, processes=2, retries=1))
    assert [r.key for r in results] == [(i,) for i in range(5)]
    assert [r.value for r in results] == [0, 2, 4, None, 8]
    assert results[1].attempts == 2  # recovered on retry
    assert results[3].error is not None and calls[3] == 2
    assert results[4].error is None  # later shards kept running
