"""``emdepth2504.jobs`` through the harness at a tiny size on the CPU. Run
by hand, as the rest of this directory:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

The configuration's own file with the region cut to 256 windows of 48
samples, and its CNVs to lengths that leave most of a sample's windows
at CN2 (the cell's 5-200 windows would make some samples' medians CNV
depths in so short a region).
"""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "emdepth2504.jobs"
SEED = 2_190_000_011
TINY = dict(windows=256, samples=48, cnv_windows=[3, 16],
            cnp_windows=[4, 10], cnp_regions=2)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout's shape in a temporary directory whose BENCHMARK.json
    holds the one tiny configuration and its cell."""
    root = tmp_path_factory.mktemp("root")
    with open(f"{ROOT}/BENCHMARK.json") as fh:
        bench = json.load(fh)
    with open(f"{BENCH}/configs/emdepth2504.json") as fh:
        cfg = json.load(fh)
    cfg["fixture"].update(TINY)
    bench["configs"] = [dict(c, file="emdepth2504.json")
                        for c in bench["configs"]
                        if c["name"] == "emdepth2504"]
    bench["workloads"] = [w for w in bench["workloads"] if w["name"] == CELL]
    (root / "emdepth2504.json").write_text(json.dumps(cfg))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def run_cell(root, trace=0):
    """In a child: the emdepth counters a run leaves in this process's
    registry would reach the cells ``test_seams.py`` gives every metric."""
    code = (f"import sys; sys.path[:0] = [{BENCH!r}, {ROOT!r}]; import run; "
            f"sys.exit(run.main(['--workload', {CELL!r}, '--seed', "
            f"'{SEED}', '--seconds', '1', '--trace', '{trace}'], "
            f"require_tpu=False, root={root!r}))")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       timeout=600)
    return p.returncode, json.loads(p.stdout.splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_a_run_is_correct_and_reports_its_metrics(tiny_root, trace):
    rc, line = run_cell(tiny_root, trace)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert set(line["compared"]) == {
        "jobs_exit_nonzero", "calls_lines_differ", "cn_matrix_lines_differ"}
    assert line["device"]["platform"] == "cpu"  # never a chip number
    if trace:
        # no device plane in a CPU trace: the trace's readers find
        # nothing; the spans and counters are there
        got = line["metrics"]
        assert got["em_window_compiles"]["value"] == 0
        for name in ("em_parse_s_per_gbase", "em_merge_s_per_gbase",
                     "em_write_s_per_gbase", "em_cn_dispatches_per_gbase",
                     "em_h2d_mb_per_gbase", "em_normalize_s_per_gbase",
                     "em_d2h_mb_per_gbase"):
            assert got[name]["value"] > 0, name
        # one float32 matrix up a job, per Gbase of 1,000 x 256 x 48 bases
        assert got["em_h2d_mb_per_gbase"]["value"] == pytest.approx(
            4 * 256 * 48 * 1e-6 / (1000 * 256 * 48 * 1e-9))
        # and its lambdas and int32 CN matrix down
        assert got["em_d2h_mb_per_gbase"]["value"] == pytest.approx(
            (36 * 256 + 4 * 256 * 48) * 1e-6 / (1000 * 256 * 48 * 1e-9))
        assert "em_device_idle" not in got
    else:
        assert set(line["metrics"]) == {"gbases_per_s", "peak_rss_gb",
                                        "setup_s"}


def test_a_changed_expected_line_makes_the_run_incorrect(tiny_root):
    rc, line = run_cell(tiny_root)  # builds or reuses the fixture
    assert line["correct"] is True
    path = os.path.join(tiny_root, "benchmark", ".fixtures",
                        f"emdepth2504-{SEED}", "expected.calls")
    with open(path) as fh:
        text = fh.read().splitlines(keepends=True)
    fields = text[1].split("\t")
    fields[4] = str(int(fields[4]) + 1)  # one call's CN
    with open(path, "w") as fh:
        fh.write("".join(text[:1] + ["\t".join(fields)] + text[2:]))
    rc, line = run_cell(tiny_root)
    assert rc == 0 and line["correct"] is False
    jobs = line["attempted"] + 1  # the warm-up job is compared too
    assert line["compared"]["calls_lines_differ"]["value"] == jobs
    assert line["compared"]["cn_matrix_lines_differ"]["value"] == 0
