"""The harness at a tiny size on the CPU. Run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Not part of the tier-1 suite. The sizes here are the real configurations'
with the contig cut to 10-and-a-bit Mb (two shards, the second short, the
last window clipped) and the coverage to 1-2x.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import control
import fixtures
import reference
import run
import work
from comparators import lines
from makers import bam_reads
from works import depth_shards

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
TINY = {
    "depth30x": dict(contig_len=10_500_250, coverage=2),
    "cohort4x": dict(contig_len=10_200_000, coverage=1, distinct_samples=2,
                     samples=4),
}


def tiny_config(name: str) -> dict:
    with open(f"{BENCH}/configs/{name}.json") as fh:
        cfg = json.load(fh)
    cfg["fixture"].update(TINY[name])
    cfg["reference"]["region"] = [0, TINY[name]["contig_len"]]
    return cfg


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout's shape in a temporary directory: BENCHMARK.json whose
    configurations are the tiny ones (those of ``TINY``: one that a later
    PR brings comes with tests of its own); fixtures and runs land beside
    it."""
    root = tmp_path_factory.mktemp("root")
    with open(f"{ROOT}/BENCHMARK.json") as fh:
        bench = json.load(fh)
    bench["configs"] = [c for c in bench["configs"] if c["name"] in TINY]
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["config"] in TINY]
    for c in bench["configs"]:
        c["file"] = f"{c['name']}.json"
        (root / c["file"]).write_text(json.dumps(tiny_config(c["name"])))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def run_cell(root, capfd, workload, trace=0, seed=2_147_483_659):
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "1", "--trace", str(trace)],
                  require_tpu=False, root=root)
    out = capfd.readouterr()
    return rc, json.loads(out.out.splitlines()[-1]), out.err


def test_without_a_tpu_the_command_ends_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, f"{BENCH}/run.py", "--workload", "depth30x.jobs",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == run.NO_CHIP
    assert '"correct"' not in p.stdout
    assert "needs 1 TPU chip" in p.stderr


@pytest.mark.parametrize("workload,kinds", [
    ("depth30x.jobs", ["depth_bed", "callable_bed"]),
    ("cohort4x.jobs", ["matrix"])])
@pytest.mark.parametrize("trace", [0, 1])
def test_a_run_up_to_the_last_line(tiny_root, capfd, workload, kinds, trace):
    rc, line, err = run_cell(tiny_root, capfd, workload, trace)
    assert rc == 0
    assert list(line)[:3] == ["correct", "attempted", "failed"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert line["device"]["platform"] == "cpu"  # hence never a chip number
    assert set(line["compared"]) == {"jobs_exit_nonzero"} | {
        f"{k}_lines_differ" for k in kinds}
    assert err.rstrip().endswith("correct: True")
    with open(f"{ROOT}/BENCHMARK.json") as fh:
        bench = json.load(fh)
    if trace:
        # no device plane in a CPU trace: the trace's readers return
        # nothing and their metrics are left out, never reported as 0
        assert "window_compiles" in line["metrics"]
        assert "kernel_hbm_roofline" not in line["metrics"]
        assert "device_idle" not in line["metrics"]
        assert line["metrics"]["window_compiles"]["value"] == 0
    else:
        assert set(line["metrics"]) == {m["name"]
                                        for m in bench["end_to_end"]}
        assert all(m["value"] > 0 for m in line["metrics"].values())


JOB_NOTE = {"job", "seconds", "rc", "ru_utime", "ru_stime", "ru_minflt",
            "ru_majflt", "ru_nvcsw", "ru_nivcsw", "main_thread_cpu_s",
            "loadavg1", "span_s"}
END_TO_END = ["gbases_per_s", "peak_rss_gb", "setup_s"]
PER_LAYER = [
    "host_decode_s_per_gbase", "dispatch_s_per_gbase", "write_s_per_gbase",
    "window_compiles", "kernel_ms_per_shard", "kernel_hbm_roofline",
    "device_idle", "device_peak_gb", "rss_growth_mb_per_job",
    "decode_wait_s_per_gbase", "host_stage_s_per_gbase", "h2d_s_per_gbase",
    "d2h_s_per_gbase", "h2d_mb_per_gbase", "d2h_mb_per_gbase",
    "ix_index_load_s_per_gbase", "ix_dispatch_s_per_gbase",
    "ix_pca_s_per_gbase", "ix_write_s_per_gbase", "ix_h2d_mb_per_gbase",
    "ix_bed_text_mb_per_gbase", "ix_window_compiles",
    "ix_qc_kernel_ms_per_contig", "ix_pca_kernel_ms_per_job",
    "ix_kernel_hbm_roofline", "ix_device_idle", "ix_device_peak_gb",
    "ix_rss_growth_mb_per_job", "ix_write_wait_s_per_gbase"]


def test_the_host_notes_ride_on_the_job_lines_and_nowhere_else(
        tiny_root, capfd):
    """ISSUE 36, step 1: what the process used over each job and what the
    machine was are notes; the result object and the manifest's metrics
    are what they were."""
    rc = run.main(["--workload", "depth30x.jobs", "--seed", "2147483671",
                   "--seconds", "1", "--trace", "0"],
                  require_tpu=False, root=tiny_root)
    out = capfd.readouterr().out.splitlines()
    assert rc == 0
    notes = [json.loads(ln) for ln in out[:-1] if ln.startswith("{")]
    jobs = [n for n in notes if "job" in n]
    line = json.loads(out[-1])
    assert [j["job"] for j in jobs] == [-1, *range(line["attempted"])]
    for j in jobs:
        assert set(j) == JOB_NOTE
        assert j["ru_utime"] > 0 and j["ru_minflt"] >= 0
        assert 0 < j["main_thread_cpu_s"] <= j["ru_utime"] + j["ru_stime"]
        # the job's own stage spans, summed by name
        assert j["span_s"]["host-decode"] > 0
        assert j["span_s"]["run.depth"] <= j["seconds"]
    machine, = (n["machine"] for n in notes if "machine" in n)
    assert machine["fixture"] == "built"
    assert machine["affinity"] == len(os.sched_getaffinity(0))
    assert machine["id"] and machine["fixture_disk_free_bytes"] > 0
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert list(line["metrics"]) == END_TO_END
    with open(f"{ROOT}/BENCHMARK.json") as fh:
        bench = json.load(fh)
    # what the manifest had it has (a later PR adds, and edits none)
    assert END_TO_END == [m["name"] for m in bench["end_to_end"]]
    assert all("workloads" not in m for m in bench["end_to_end"])
    assert set(PER_LAYER) <= {m["name"] for m in bench["per_layer"]}
    assert {("depth30x.jobs", 1), ("cohort4x.jobs", 1),
            ("indexcov500.jobs", 1)} <= {
        (w["name"], w["chips"]) for w in bench["workloads"]}
    assert bench["run_seconds"] == 10


def test_a_new_seed_leaves_one_fixture_a_configuration_and_no_output(
        tiny_root, capfd):
    fixtures_dir = f"{tiny_root}/benchmark/.fixtures"
    os.makedirs(f"{fixtures_dir}/cohort4x-5")  # another configuration's
    os.makedirs(f"{fixtures_dir}/depth30x-7.tmp123")  # a build cut short
    for seed in (2147483672, 2147483673):
        rc, line, _ = run_cell(tiny_root, capfd, "depth30x.jobs", seed=seed)
        assert rc == 0 and line["correct"] is True
        assert sorted(d for d in os.listdir(fixtures_dir)
                      if d.startswith("depth30x-")) == [f"depth30x-{seed}"]
        assert os.listdir(f"{tiny_root}/benchmark/.runs") == []
    assert os.path.isdir(f"{fixtures_dir}/cohort4x-5")
    os.rmdir(f"{fixtures_dir}/cohort4x-5")


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


@pytest.mark.parametrize("job_s,jobs,elapsed", [
    (3.0, 4, 12.0), (2.5, 4, 10.0), (0.5, 20, 10.0), (11.0, 1, 11.0)])
def test_the_window_ends_with_a_whole_job_and_counts_all_its_seconds(
        monkeypatch, job_s, jobs, elapsed):
    """A fake clock under the driver: ``--seconds`` alone decides how many
    whole jobs a window holds, and the rate is every completed job's bases
    over all its seconds."""
    import types

    from drivers import closed_loop_jobs as drv

    clock = FakeClock()

    def fake_job(ctx, index):
        t0 = clock.now
        clock.now += job_s
        return {"index": index, "t0": t0, "t1": clock.now, "rc": 0,
                "outputs": {}, "host": {}}

    monkeypatch.setattr(drv, "time", types.SimpleNamespace(perf_counter=clock))
    monkeypatch.setattr(drv, "run_job", fake_job)
    ctx = types.SimpleNamespace(
        mix={"warmup_jobs": 1, "traced_job": 1}, config={},
        meta={"job_bases": 2e9}, seconds=10.0, trace=False,
        setup_done=lambda: None)
    got = drv.run(ctx)
    assert [j["index"] for j in got["warmup"]] == [-1]
    assert [j["index"] for j in got["jobs"]] == list(range(jobs))
    assert got["t_close"] == got["jobs"][-1]["t1"]  # ends with a whole job
    assert got["t_close"] - got["t_open"] == pytest.approx(elapsed)
    assert got["end_to_end"] == {
        "gbases_per_s": pytest.approx(jobs * 2.0 / elapsed)}


def alter_a_window_sum(monkeypatch):
    from goleft_tpu.commands import depth

    real = depth.DepthEngine.run_segments

    def broken(self, *a, **k):
        starts, ends, sums, cls = real(self, *a, **k)
        sums = np.array(sums)
        sums[len(sums) // 2] += self.window
        return starts, ends, sums, cls

    monkeypatch.setattr(depth.DepthEngine, "run_segments", broken)


def drop_the_mapq_filter(monkeypatch):
    from goleft_tpu.commands import depth

    real = depth._decode_shard_segments
    monkeypatch.setattr(
        depth, "_decode_shard_segments",
        lambda bam, bai, tid, s, e, mapq, *a: real(bam, bai, tid, s, e, 0, *a))


def leave_out_half_the_batch(monkeypatch):
    from goleft_tpu.commands import cohortdepth

    real = cohortdepth._batched_pipeline

    def broken(seg_s, seg_e, keep, *a):
        keep = np.array(keep)
        keep[len(keep) // 2:] = False
        return real(seg_s, seg_e, keep, *a)

    monkeypatch.setattr(cohortdepth, "_batched_pipeline", broken)


def alter_a_matrix_answer(monkeypatch):
    from goleft_tpu.commands import cohortdepth

    real = cohortdepth._batched_pipeline

    def broken(*a):
        sums = np.array(real(*a))
        sums[0, 7] += 500
        return sums

    monkeypatch.setattr(cohortdepth, "_batched_pipeline", broken)


@pytest.mark.parametrize("workload,fault", [
    ("depth30x.jobs", alter_a_window_sum),
    ("depth30x.jobs", drop_the_mapq_filter),
    ("cohort4x.jobs", leave_out_half_the_batch),
    ("cohort4x.jobs", alter_a_matrix_answer)])
def test_a_fault_under_the_timed_path_reads_not_correct(
        tiny_root, capfd, monkeypatch, workload, fault):
    fault(monkeypatch)
    rc, line, err = run_cell(tiny_root, capfd, workload)
    assert rc == 0
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] >= 1
    assert any(n["value"] > n["limit"] for n in line["compared"].values())
    assert err.rstrip().endswith("correct: False")


@pytest.mark.parametrize("name", ["depth30x", "cohort4x"])
def test_every_control_fails_the_comparison(name, tmp_path):
    """The reference with a guarantee broken (bf16 window sums, no MAPQ
    filter, one wrong window sum) may not pass for the reference."""
    cfg = tiny_config(name)
    # at the cell's own coverage: 1x window sums fit bf16's 8 bits exactly
    with open(f"{BENCH}/configs/{name}.json") as fh:
        cfg["fixture"]["coverage"] = json.load(fh)["fixture"]["coverage"]
    readings = control.control_readings(cfg, 11, str(tmp_path))
    assert set(readings) == set(reference.CONTROLS) == set(
        bam_reads.CONTROLS)
    for ctl, numbers in readings.items():
        assert max(numbers.values()) > 0, ctl
    kind = "depth_bed" if name == "depth30x" else "matrix"
    assert readings["wrong_window_sum"][f"{kind}_lines_differ"] == 1


def test_bf16_rounding_keeps_eight_bits():
    import ml_dtypes

    x = np.array([1.0, 257.0, 259.0, 15000.0, 1300.0, 9811.0], "f4")
    got = reference.to_bf16(x)
    assert got[:4].tolist() == [1.0, 256.0, 260.0, 14976.0]
    assert got.tolist() == x.astype(ml_dtypes.bfloat16).astype("f4").tolist()


def test_fixture_is_byte_stable_for_a_seed_and_differs_between_seeds(
        tmp_path):
    cfg = tiny_config("cohort4x")
    for d, seed in (("a", 5), ("b", 5), ("c", 6)):
        fixtures.build(cfg, seed, str(tmp_path / d))

    def blob(d):
        return b"".join((tmp_path / d / f).read_bytes()
                        for f in ("d0.bam", "d0.bam.bai", "d1.bam",
                                  "expected.matrix.tsv"))

    assert blob("a") == blob("b")
    assert blob("a") != blob("c")
    meta = json.loads((tmp_path / "a" / "meta.json").read_text())
    assert len(meta["inputs"]) == 4 and meta["job_reads"] == 4 * 68_000
    assert meta["native_probe"] == "c000.bam"
    assert meta["job_bases"] == 4 * 68_000 * 150
    assert meta["work_unit"] == cfg["work_unit"]
    # the work module is found by the kind the maker wrote: 4 samples x 2
    assert work.job_units(meta) == 8
    # hard links, not copies
    assert os.stat(tmp_path / "a" / "c000.bam").st_nlink == 3


def test_fixture_payload_has_entropy(tmp_path):
    """Bases and qualities are drawn, not constant: BGZF deflates a
    271-byte record to no less than a quarter."""
    cfg = tiny_config("depth30x")
    cfg["fixture"].update(contig_len=1_000_000, coverage=3)
    cfg["reference"]["region"] = [0, 1_000_000]
    meta = fixtures.build(cfg, 3, str(tmp_path / "f"))
    assert meta["bam_bytes"] > meta["job_reads"] * 271 / 4


def test_work_bytes_of_one_hand_worked_shard():
    # 1.3 M kept segments over a 10 Mb span, 500 bp windows, classes out:
    # 8 * 1.3e6 + 2 * 4 * 1e7 + 4 * 1e7 / 500 + 1e7 / 4
    assert depth_shards.shard_bytes(1_300_000, 10_000_000, 500, True) == \
        10_400_000 + 80_000_000 + 80_000 + 2_500_000
    assert depth_shards.shard_bytes(0, 1000, 500, False) == 8000 + 8
    meta = {"work": {
        "kind": "depth_shards", "window": 500, "classes_out": False,
        "shards": [{"start": 0, "end": 1000, "kept_segments": [1, 2]},
                   {"start": 1000, "end": 1500, "kept_segments": [0, 0]}]}}
    assert work.job_units(meta) == 4
    assert work.job_bytes(meta) == 2 * 8008 + 24 + 2 * 4004
    assert work.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        work.peak("TPU v9", "hbm_bytes_per_s")


def test_lines_differ_counts_changed_missing_and_surplus_lines():
    assert lines.lines_differ("a\nb\n", "a\nb\n") == 0
    assert lines.lines_differ("a\nx\n", "a\nb\n") == 1
    assert lines.lines_differ("a\n", "a\nb\nc\n") == 2
    assert lines.lines_differ("", "a\n") == 1


def test_the_lines_comparator_reads_files_and_needs_the_expected_one(
        tmp_path):
    (tmp_path / "want").write_text("a\nb\n")
    (tmp_path / "got").write_text("a\nx\n")
    want, got = str(tmp_path / "want"), str(tmp_path / "got")
    assert lines.differ(got, want) == 1
    # a job that wrote nothing wrote no line
    assert lines.differ(str(tmp_path / "none"), want) == 2
    # an expected file that is missing can never read as "nothing differs"
    with pytest.raises(OSError):
        lines.differ(got, str(tmp_path / "none"))


TRACE = f"{BENCH}/tests/data/depth30x_job.xplane.pb"


def test_device_trace_reducer_on_a_recorded_trace():
    """One traced job of depth30x.jobs on a TPU v5 lite (my chip run,
    PR 25): three runs of one executable, 44.1 ms each, at the job's end."""
    from reducers import device_trace

    window_s = 2.1570851620000013  # the job's host-clock seconds
    t = device_trace.summarize(TRACE, window_s, chips=1)
    assert t["structure"]["/device:TPU:0"] == {
        "XLA Modules": 3, "XLA Ops": 210, "Async XLA Ops": 12,
        "TC Overlay": 0}
    # busy: the union of the op intervals, a hair under the modules' sum
    assert t["busy_s"] == pytest.approx(0.132339457, rel=1e-9)
    (name, seconds), = t["modules"]
    assert name.startswith("jit_shard_depth_pipeline_packed_cls_packed(")
    assert seconds == pytest.approx(0.132348932, rel=1e-9)
    assert t["busy_s"] < seconds
    # the anchor the driver wrapped the job in, and the gaps inside it
    a0, a1 = t["anchor"]
    assert a1 - a0 == pytest.approx(2.157164879, rel=1e-9)
    assert sum(e - s for s, e in t["gaps"]) + t["busy_s"] == \
        pytest.approx(a1 - a0, rel=1e-9)
    g0, g1 = t["gaps"][0]  # the longest: the host decodes, the chip waits
    assert (g0, g1 - g0) == (a0, pytest.approx(1.975878210, rel=1e-6))

    meta = {"work": {
        "kind": "depth_shards", "window": 500, "classes_out": True,
        "shards": [{"start": s, "end": s + 10_000_000,
                    "kept_segments": [1_300_000]}
                   for s in (0, 10_000_000, 20_000_000)]}}
    run_ = {"trace": t, "meta": meta, "device": {"kind": "TPU v5 lite"}}

    def reduce(**args):
        return device_trace.reduce(args, run_)

    assert reduce(quantity="idle_percent") == pytest.approx(
        100 * (1 - 0.132339457 / window_s))
    assert reduce(quantity="module_ms_per_sample_shard",
                  module_regex=".*") == pytest.approx(44.116310667)
    assert reduce(quantity="module_ms_per_sample_shard",
                  module_regex="depth_pipeline") == pytest.approx(44.116310667)
    # 3 x 92.98 MB at 819 GB/s is 0.3406 ms of the 132.3 ms
    assert reduce(quantity="hbm_roofline_percent") == pytest.approx(
        100 * (3 * 92_980_000 / 819e9) / 0.132348932)
    # nothing to read: nothing returned, never 0
    assert reduce(quantity="hbm_roofline_percent",
                  module_regex="no_such_kernel") is None
    assert device_trace.reduce({"quantity": "idle_percent"},
                               dict(run_, trace=None)) is None


def test_intervals_merge_and_complement():
    from reducers.device_trace import complement, merge

    assert merge([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert complement([(0, 2), (3, 4)], -1, 5) == [(-1, 0), (2, 3), (4, 5)]
    assert complement([(0, 2)], 0, 2) == []
