"""The backend policy (utils/device_guard.take_backend), the compile
cache it places, and the programs that must come through it: the CLI's
device commands, __graft_entry__.py — plus chip_smoke.py's
outer contract and the two fallbacks this PR turned into failures."""

import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

from goleft_tpu.utils import device_guard

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Dev:
    def __init__(self, platform):
        self.platform = platform
        self.device_kind = platform


@pytest.fixture
def no_env(monkeypatch):
    for k in ("GOLEFT_TPU_CPU", "JAX_PLATFORMS",
              "JAX_COMPILATION_CACHE_DIR"):
        monkeypatch.delenv(k, raising=False)


@pytest.fixture
def config_updates(monkeypatch):
    """jax.config.update calls made while the test runs, not applied."""
    seen = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.append((k, v)))
    return seen


# ----------------------------------------------------- which platform

@pytest.mark.parametrize("env,asked", [
    ({}, False),
    ({"GOLEFT_TPU_CPU": "1"}, True),
    ({"JAX_PLATFORMS": "cpu"}, True),
    ({"JAX_PLATFORMS": " CPU "}, True),
    ({"JAX_PLATFORMS": "tpu,cpu"}, False),  # the chip machine's own
    ({"JAX_PLATFORMS": "tpu"}, False),
    ({"GOLEFT_TPU_CPU": ""}, False),
    ({"GOLEFT_TPU_CPU": "0"}, False),  # only "1" asks, as the docs say
])
def test_cpu_only_when_asked(no_env, monkeypatch, env, asked):
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    assert device_guard.cpu_requested() is asked


@pytest.mark.parametrize("env", [{}, {"GOLEFT_TPU_CPU": "0"}])
def test_unasked_cpu_exits_with_the_one_line(no_env, monkeypatch,
                                             config_updates, env):
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("cpu")])
    with pytest.raises(SystemExit) as e:
        device_guard.take_backend()
    msg = str(e.value)
    assert "\n" not in msg
    assert "GOLEFT_TPU_CPU=1" in msg and "JAX_PLATFORMS=cpu" in msg
    assert ("jax_platforms", "cpu") not in config_updates


def test_accelerator_is_taken_in_this_process(no_env, monkeypatch,
                                              config_updates):
    devs = [_Dev("tpu")]
    monkeypatch.setattr(jax, "devices", lambda: devs)
    assert device_guard.take_backend() is devs
    assert ("jax_platforms", "cpu") not in config_updates


@pytest.mark.parametrize("env,pins", [
    ({"GOLEFT_TPU_CPU": "1"}, True),
    ({"JAX_PLATFORMS": "cpu"}, False),  # jax reads that one itself
])
def test_asked_cpu_is_given(no_env, monkeypatch, config_updates, env,
                            pins):
    monkeypatch.setenv(*next(iter(env.items())))
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("cpu")])
    assert device_guard.take_backend()[0].platform == "cpu"
    assert (("jax_platforms", "cpu") in config_updates) is pins


def test_no_child_process_is_ever_started(no_env, monkeypatch,
                                          config_updates):
    def boom(*a, **k):
        raise AssertionError("the policy started a child process")

    monkeypatch.setattr(subprocess, "Popen", boom)
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("tpu")])
    device_guard.take_backend()
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("cpu")])
    with pytest.raises(SystemExit):
        device_guard.take_backend()


# ---------------------------------------------------- the compile cache

def test_cache_dir_set_from_outside_is_left_alone(no_env, monkeypatch,
                                                  config_updates,
                                                  tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("tpu")])
    device_guard.take_backend()
    assert config_updates == []


def test_cache_dir_defaults_to_the_checkout(no_env, monkeypatch,
                                            config_updates):
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("tpu")])
    device_guard.take_backend()
    assert config_updates == [("jax_compilation_cache_dir",
                               os.path.join(REPO, ".jax_cache"))]


def test_cache_path_is_fixed():
    """A run finds what the last one compiled only at the same path:
    nothing in it may change from run to run — no temp directory, pid
    or clock."""
    import tempfile

    assert device_guard.CACHE_DIR == os.path.join(REPO, ".jax_cache")
    assert tempfile.gettempdir() not in device_guard.CACHE_DIR
    assert str(os.getpid()) not in device_guard.CACHE_DIR
    import ast

    tree = ast.parse(open(device_guard.__file__).read())
    used = {n.id for n in ast.walk(tree) if isinstance(n, ast.Name)} | {
        n.attr for n in ast.walk(tree) if isinstance(n, ast.Attribute)} | {
        a.name for n in ast.walk(tree)
        if isinstance(n, (ast.Import, ast.ImportFrom)) for a in n.names}
    assert not used & {"tempfile", "getpid", "time", "datetime", "uuid"}
    assert ".jax_cache/" in open(os.path.join(REPO, ".gitignore")).read()


@pytest.mark.parametrize("program", ["__graft_entry__.py",
                                     "goleft_tpu/cli.py"])
def test_every_entry_point_comes_through_the_policy(program):
    """The CLI and __graft_entry__.py place the cache and take
    the backend through take_backend, and set neither on their own."""
    src = open(os.path.join(REPO, program)).read()
    assert "take_backend()" in src
    assert "jax_compilation_cache_dir" not in src
    assert 'update("jax_platforms"' not in src


# ------------------------------------------------------ through the CLI

def test_device_command_on_unasked_cpu_exits_nonzero(no_env, monkeypatch,
                                                     config_updates):
    from goleft_tpu import cli

    monkeypatch.setattr(jax, "devices", lambda: [_Dev("cpu")])
    with pytest.raises(SystemExit) as e:
        cli.main(["depth", "--prefix", "x", "/nonexistent.bam"])
    assert e.value.code not in (0, None)
    assert e.value.code == device_guard.NO_ACCELERATOR


def test_non_device_command_never_imports_jax(tmp_path):
    """A fresh interpreter through a non-device command: jax stays out
    (no backend, no chip taken, nothing to fail without one)."""
    code = ("import sys\n"
            "from goleft_tpu import cli\n"
            "try:\n"
            "    cli.main(['samplename', '/nonexistent.bam'])\n"
            "except FileNotFoundError:\n"
            "    pass\n"
            "print('jax' in sys.modules)\n")
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "GOLEFT_TPU_CPU")}
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=60)
    assert out.stdout.strip() == "False", out.stderr[-500:]


def test_device_command_counts_its_compiles_in_the_manifest(tmp_path):
    """What chip_smoke.py reads per phase: every compile of a device
    command is in its manifest — count and backend-compile seconds from
    jax's own monitoring, seam or no seam around the jit — next to the
    platform it ran on."""
    import json

    m = tmp_path / "m.tsv"
    rng = np.random.default_rng(0)
    with open(m, "w") as fh:
        fh.write("#chrom\tstart\tend\t" + "\t".join(
            f"s{j}" for j in range(8)) + "\n")
        for b in range(16):
            fh.write(f"chr1\t{b * 500}\t{b * 500 + 500}\t" + "\t".join(
                map(str, rng.integers(25, 35, 8))) + "\n")
    out = subprocess.run(
        [sys.executable, "-m", "goleft_tpu", "emdepth", str(m),
         "--metrics-out", str(tmp_path / "run.json")],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-800:]
    doc = json.load(open(tmp_path / "run.json"))
    assert doc["backend"]["platform"] == "cpu"
    # the manifest names the compile cache the run used
    assert doc["backend"]["compile_cache_dir"] == (
        os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or os.path.join(REPO, ".jax_cache"))
    c = doc["metrics"]["counters"]
    assert c["xla.compiles_total"] >= 1
    assert c["xla.compile_seconds_total"] > 0


# ------------------------------------------- jax 0.9: work, or raise

def test_private_jit_cache_size_works_on_the_installed_jax():
    """obs/compiles' exact detector reads jit._cache_size() with no
    default: this is the proof it exists here (an upgrade that drops
    it fails this test and every seam, instead of going quiet)."""
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1)
    assert f._cache_size() == 0
    f(jnp.ones(3))
    assert f._cache_size() == 1


def test_compile_hook_sees_an_unseamed_compile():
    """The jax.monitoring detector on the installed jax: a jit compiled
    outside any observe() seam still lands in the tracker."""
    from goleft_tpu.obs import compiles

    assert compiles.ensure_compile_hook()
    n0 = compiles.TRACKER.compiles_total

    def unseamed_program(x):
        return x * 3 + 1

    jax.jit(unseamed_program)(np.arange(7))
    assert compiles.TRACKER.compiles_total == n0 + 1
    last = compiles.TRACKER.recent_events(1)[0]
    assert last["family"] == "unattributed"
    assert "unseamed_program" in last["names"][0]


def test_observe_raises_when_the_cache_size_probe_breaks():
    from goleft_tpu.obs import compiles

    def gone():
        raise AttributeError("_cache_size")

    with pytest.raises(AttributeError):
        with compiles.observe("x", cache_size_fn=gone):
            pass


def test_pca_raises_when_the_sharded_step_raises(monkeypatch):
    """cohort/pca.py used to swap in the unsharded Gram step on ANY
    exception from the sharded one; now the failure is the caller's."""
    from goleft_tpu.cohort import pca

    def broken(mesh):
        raise RuntimeError("sharded gram step refused")

    monkeypatch.setattr(pca, "_sharded_gram_fn", broken)
    x = np.random.default_rng(0).normal(size=(16, 32)).astype(np.float32)
    assert len(jax.local_devices()) > 1  # conftest: 8 virtual devices
    with pytest.raises(RuntimeError, match="sharded gram step refused"):
        pca.sharded_pca(lambda: iter([x]), k=2, iters=2)


def test_mesh_raises_on_an_accelerator_when_topology_fails(monkeypatch):
    """parallel/mesh.make_mesh: a create_device_mesh failure on a
    non-CPU platform is raised, not turned into enumeration order."""
    from jax.experimental import mesh_utils

    from goleft_tpu.parallel import mesh

    def refuse(*a, **k):
        raise ValueError("no such topology")

    monkeypatch.setattr(mesh_utils, "create_device_mesh", refuse)
    assert mesh.make_mesh(4).devices.size == 4  # CPU: plain reshape

    class Chip:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda: [Chip()] * 4)
    with pytest.raises(ValueError, match="no such topology"):
        mesh.make_mesh(4)


# ------------------------------------------------------- native library

def test_native_library_is_rebuilt_when_missing(tmp_path, monkeypatch):
    """get_lib() builds csrc/fastio.cpp on this machine when the
    library is not there (chip_smoke.py removes it first, so that a
    -march=native build from another machine is never loaded)."""
    import shutil

    from goleft_tpu.io import native

    if shutil.which("g++") is None:
        pytest.skip("no g++ here")
    root = tmp_path / "checkout"
    (root / "csrc").mkdir(parents=True)
    shutil.copy(os.path.join(REPO, "csrc", "fastio.cpp"), root / "csrc")
    monkeypatch.setattr(native, "_root", lambda: str(root))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.delenv("GOLEFT_TPU_NO_NATIVE", raising=False)
    monkeypatch.delenv("GOLEFT_TPU_ASAN_LIB", raising=False)
    assert not (root / "build" / "libgoleftio.so").exists()
    assert native.get_lib() is not None
    assert (root / "build" / "libgoleftio.so").exists()


# ----------------------------------------------------- chip_smoke.py

def _smoke(*args, **env):
    e = {k: v for k, v in os.environ.items()
         if k not in ("JAX_PLATFORMS", "GOLEFT_TPU_CPU")}
    e.update(env)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        env=e, capture_output=True, text=True, timeout=120)


@pytest.mark.parametrize("env", [{"JAX_PLATFORMS": "cpu"},
                                 {"GOLEFT_TPU_CPU": "1"}])
def test_chip_smoke_fails_where_the_cpu_is_asked_for(env):
    r = _smoke(**env)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


def test_chip_smoke_help_lists_its_options():
    r = _smoke("--help")
    assert r.returncode == 0
    for opt in ("--multichip", "--seed", "--out", "--size"):
        assert opt in r.stdout


def test_chip_smoke_parent_stays_off_jax():
    """Importing chip_smoke.py (what its oracle workers do too) pulls in
    no jax, and its source starts the chip's users only as children."""
    code = ("import sys, importlib.util\n"
            f"spec = importlib.util.spec_from_file_location('cs', "
            f"{os.path.join(REPO, 'chip_smoke.py')!r})\n"
            "m = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(m)\n"
            "print('jax' in sys.modules)\n")
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=60)
    assert out.stdout.strip() == "False", out.stderr[-500:]
    src = open(os.path.join(REPO, "chip_smoke.py")).read()
    assert not re.search(r"^\s*(import|from) jax\b", src, re.M)
