"""bench.py's entry builders that stay: the pinned baseline, the
device-vs-hybrid cohort side-by-side, the whole-genome depth compile
geometry, the full-shape host checks — and what its ``main`` does
about the backend (utils/device_guard.take_backend, like the CLI)."""

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "goleft_bench", os.path.join(REPO, "bench.py"))
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)


def test_pinned_baseline_committed_and_preferred(tmp_path, monkeypatch):
    """vs_baseline must divide by the PINNED constant
    (BASELINE_PINNED.json) so cross-round ratios are comparable by
    construction — the live measurement swung 2x between rounds."""
    with open(os.path.join(REPO, "BASELINE_PINNED.json")) as fh:
        pin = json.load(fh)
    assert pin["numpy_kernel_gbases_per_sec"] > 0
    prov = pin["provenance"]
    assert prov["ts"] and len(prov["runs_seconds"]) >= 5
    assert prov["workload"]["ref_bp"] == 10_000_000

    monkeypatch.chdir(tmp_path)
    cohort = {"numpy_kernel_gbases_per_sec": 0.999}
    v, info = bench._baseline_block(cohort)  # no pin file here
    assert v == 0.999 and info["pinned"] is False
    with open(tmp_path / "BASELINE_PINNED.json", "w") as fh:
        json.dump(pin, fh)
    v, info = bench._baseline_block(cohort)
    assert v == pin["numpy_kernel_gbases_per_sec"]
    assert info["pinned"] is True
    assert info["measured_this_run_gbases_per_sec"] == 0.999


def test_cohort_e2e_device_entry_shape_and_identity():
    """The device-engine side-by-side entry: both engines run, outputs byte-identical, crossover stated from
    measured rates (real small-scale measurement, ~3s on cpu)."""
    e = bench.bench_cohort_device(6, 400_000, 2)
    assert "error" not in e, e
    assert e["identical_output"] is True
    assert e["hybrid_gbases_per_sec"] > 0
    assert e["device_gbases_per_sec"] > 0
    co = e["crossover"]
    assert co["chips_needed_to_beat_hybrid"] >= 1
    assert "statement" in co and "chip" in co["statement"]
    assert set(e["stage_seconds"]) == {"host_segment_extract",
                                      "pack_transfer_compute"}


def test_depth_wholegenome_entry_no_recompile():
    """BASELINE config 2 shape: whole-genome depth over uneven chromosomes compiles once per segment bucket, and a
    warm repeat of the WHOLE genome adds zero compiles — scale adds
    shards, not compiles (real small-scale run, ~3s on cpu)."""
    e = bench.bench_depth_wholegenome(True)
    assert "error" not in e, e
    assert e["chromosomes"] >= 6
    assert e["no_recompile_across_chroms"] is True
    assert e["xla_compiles_warm_repeat"] == 0
    # compile count is bucket geometry: far below one per chromosome
    assert 1 <= e["xla_compiles_cold"] <= e["chromosomes"] // 2
    assert set(e["stage_seconds"]) >= {"host-decode", "device-compute",
                                       "write-output"}
    assert e["gbases_per_sec_warm"] > 0


def test_host_scale_validation_entries():
    """Configs 4-5 must be provably executable on the host backend
    (chip-less rounds need SOME committed record of them). Shapes are
    shrunk here; the bench always runs the full BASELINE shapes."""
    ran = {}

    def emit(d):
        ran.update(d)

    out = bench.host_scale_validation(emit=emit, ix_shape=(50, 4096),
                                      em_samples=64, em_windows=256)
    assert set(out) == {"indexcov_cohort_hostcheck",
                        "emdepth_em_hostcheck"}
    for e in out.values():
        assert "error" not in e, e
        assert e["platform"] == "cpu"
        assert "validation" in e["note"]
        assert e["seconds_incl_compile"] >= 0
    assert out["emdepth_em_hostcheck"]["windows"] == 256
    assert ran == out


def test_chip_limits_refuses_an_unknown_accelerator(monkeypatch):
    """A device_kind missing from the table of published peaks is an
    error, not a roofline without a roof; the installed libtpu's name
    for the v5e is in it; the CPU (asked for) claims no peak."""
    import jax

    class Dev:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    kind, lim = bench.chip_limits()
    assert kind == "TPU v5 lite" and lim["hbm_gbps"] == 819.0
    Dev.device_kind = "TPU v9 imaginary"
    with pytest.raises(KeyError, match="TPU v9 imaginary"):
        bench.chip_limits()
    Dev.platform, Dev.device_kind = "cpu", "cpu"
    assert bench.chip_limits() == ("cpu", None)


def test_worker_children_entries_are_pinned_and_labelled_cpu():
    """bench.py may hold the chip when it starts serve workers: every
    environment it hands a child asks for the CPU, and the two fleet
    recovery entries say platform cpu, not the parent's backend."""
    import ast

    src = open(os.path.join(REPO, "bench.py")).read()
    envs = [n for n in ast.walk(ast.parse(src))
            if isinstance(n, ast.Call)
            and getattr(n.func, "id", "") == "dict" and n.args
            and ast.unparse(n.args[0]) == "os.environ"]
    assert len(envs) >= 3
    for call in envs:
        kw = {k.arg: ast.unparse(k.value) for k in call.keywords}
        assert kw.get("JAX_PLATFORMS") == "'cpu'", ast.unparse(call)
    for fn in ("_fleet_restart_recovery_entry",
               "_fleet_failover_recovery_entry"):
        body = src[src.index(f"def {fn}("):]
        body = body[:body.index("\ndef ", 1)]
        assert '"platform": "cpu"' in body, fn
