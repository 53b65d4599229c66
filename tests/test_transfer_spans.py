"""The span vocabulary of ``depth`` and ``cohortdepth --engine device``
(docs/observability.md): four stages, one wait, five transfer spans
under ``device-compute``, two byte counters, and the same names in a
``jax.profiler`` trace."""

import glob
import io

import numpy as np
import pytest

from goleft_tpu import obs
from goleft_tpu.commands.cohortdepth import run_cohortdepth
from goleft_tpu.commands.depth import DepthEngine, run_depth
from goleft_tpu.io.fai import write_fai
from helpers import random_reads, write_bam_and_bai, write_fasta

STAGES = ("host-decode", "device-compute", "write-output")
TRANSFERS = ("pack", "h2d", "device-wait", "d2h", "unpack")
REFS = {"chr1": 100_000, "chr2": 50_000}  # helpers.HEADER_TEXT's
N_SAMPLES = 3


@pytest.fixture(scope="module")
def cohort(tmp_path_factory):
    d = tmp_path_factory.mktemp("cohort")
    fa = write_fasta(str(d / "r.fa"),
                     {c: "A" * n for c, n in REFS.items()})
    write_fai(fa)
    rng = np.random.default_rng(26)
    bams = []
    for i in range(N_SAMPLES):
        reads = [r for tid, n in enumerate(REFS.values())
                 for r in random_reads(rng, 400, tid, n)]
        bams.append(write_bam_and_bai(str(d / f"s{i}.bam"), reads))
    return fa, bams, d


def traced(fn):
    """Run ``fn`` under a CLI-style root; (root, the spans it caused or
    that were recorded beside it)."""
    tracer = obs.get_tracer()
    with obs.trace("run.test", kind="cli") as root:
        fn()
    return root, [s for s in tracer.snapshot()
                  if s.span_id > root.span_id]


def run_cohort(cohort, prefetch_depth):
    fa, bams, _ = cohort
    out = io.StringIO()
    root, spans = traced(lambda: run_cohortdepth(
        bams, reference=fa, window=500, out=out, engine="device",
        processes=4, prefetch_depth=prefetch_depth))
    return out.getvalue(), root, spans


def run_depth_job(cohort):
    fa, bams, d = cohort
    return traced(lambda: run_depth(bams[0], str(d / "one"),
                                    reference=fa, window=500))


@pytest.mark.parametrize("prefetch_depth", [0, 2])
def test_cohortdepth_device_records_stage_spans(cohort, prefetch_depth):
    text, root, spans = run_cohort(cohort, prefetch_depth)
    assert len(text.splitlines()) == 1 + sum(
        -(-n // 500) for n in REFS.values())
    mine = [s for s in spans if s.name in STAGES + TRANSFERS
            or s.name == "decode-wait"]
    # pool threads included: nothing fell out of the run's trace
    assert {s.trace_id for s in mine} == {root.trace_id}
    assert all(s.parent_id is not None for s in mine)
    by_name = {}
    for s in mine:
        by_name.setdefault(s.name, []).append(s)
    regions = len(REFS)
    assert len(by_name["host-decode"]) == N_SAMPLES * regions
    assert len(by_name["device-compute"]) == regions
    assert len(by_name["write-output"]) == regions
    assert len(by_name["decode-wait"]) >= regions
    assert {s.category for n in STAGES for s in by_name[n]} == {"stage"}
    assert {s.category for s in by_name["decode-wait"]} == {"wait"}
    # decode runs off the consumer's thread, the rest on it
    consumer = {s.thread_id for s in by_name["device-compute"]}
    assert consumer == {s.thread_id for s in by_name["write-output"]}
    assert not consumer & {s.thread_id for s in by_name["host-decode"]}
    # the old names of the prefetched path are gone
    assert not {"decode", "stage", "transfer", "compute"} & {
        s.name for s in spans}


@pytest.mark.parametrize("prefetch_depth", [0, 2])
def test_cohortdepth_transfer_spans_have_category_transfer(
        cohort, prefetch_depth):
    _, _, spans = run_cohort(cohort, prefetch_depth)
    for name in TRANSFERS:
        got = [s for s in spans if s.name == name]
        assert len(got) == len(REFS), name
        assert {s.category for s in got} == {"transfer"}, name


@pytest.mark.parametrize("command", ["depth", "cohortdepth"])
def test_transfer_spans_lie_inside_device_compute(cohort, command):
    _, spans = (run_depth_job(cohort) if command == "depth"
                else run_cohort(cohort, 0)[1:])
    compute = {s.span_id: s for s in spans if s.name == "device-compute"}
    assert len(compute) == len(REFS)
    for name in TRANSFERS:
        got = [s for s in spans if s.name == name]
        assert len(got) == len(compute), name
        for s in got:
            assert s.category == "transfer"
            parent = compute[s.parent_id]
            assert parent.t0 <= s.t0 <= s.t1 <= parent.t1, name
    # one of each a dispatch, in the order of the hop
    for parent in compute.values():
        kids = sorted((s for s in spans if s.parent_id == parent.span_id
                       and s.name in TRANSFERS), key=lambda s: s.t0)
        assert tuple(s.name for s in kids) == TRANSFERS


@pytest.mark.parametrize("packed,h2d_bytes", [
    (True, 2 * 1024 * 2),        # u16 deltas + u16 lengths, bucket 1024
    (False, 1024 * (4 + 4 + 1)),  # i32 starts + i32 ends + bool keep
])
def test_transfer_byte_counters_one_tiny_shard(packed, h2d_bytes):
    eng = DepthEngine(100, 4, 0, 1, max_span=10_000, packed=packed)
    seg_s = np.arange(0, 3000, 10, dtype=np.int32)
    reg = obs.get_registry()
    before = reg.counters("xla.")
    eng.run_segments(seg_s, seg_s + 50, None, 0, 10_000)
    after = reg.counters("xla.")

    def grew(name):
        return after[name] - before.get(name, 0)

    assert grew("h2d_bytes_total") == h2d_bytes
    # 100 f32 window sums + 10,000 2-bit classes
    assert grew("d2h_bytes_total") == 100 * 4 + 10_000 // 4


def test_depth_profile_trace_holds_the_stage_spans(cohort, tmp_path):
    from jax.profiler import ProfileData

    fa, bams, _ = cohort
    run_depth(bams[0], str(tmp_path / "p"), reference=fa, window=500,
              profile_dir=str(tmp_path / "prof"))
    (path,) = glob.glob(str(tmp_path / "prof" / "**" / "*.xplane.pb"),
                        recursive=True)
    host = {e.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}
    assert set(STAGES + TRANSFERS) <= host


def test_obs_import_stays_jax_free():
    import subprocess
    import sys

    code = ("import sys, goleft_tpu.obs as o; "
            "assert 'jax' not in sys.modules; "
            "assert not hasattr(o, 'maybe_span'); "
            "assert not hasattr(o, 'device_events_enabled')\n"
            "with o.span('no-jax'): pass")
    subprocess.run([sys.executable, "-c", code], check=True)
