#!/usr/bin/env python3
"""The benchmark's one command:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell of ``BENCHMARK.json``. The cell's configuration,
traffic mix and per-layer metrics are data files found by name under
``benchmark/configs``, ``benchmark/traffic`` and ``benchmark/metrics``; a
mix names its driver (``benchmark/drivers``), a metric its reducer
(``benchmark/reducers``), a configuration its maker (``benchmark/makers``)
and each output's comparator (``benchmark/comparators``), and the
fixture's ``meta.json`` the module that counts its device work
(``benchmark/works``). Fixtures and the plain reference are made by a
JAX-free child (``fixtures.py``), and ``meta.json`` is all this process
reads of them; the system under test is driven in-process through
``goleft_tpu.cli.main``.

The last line of stdout is the result object; earlier lines are JSON
notes (fixture, machine, compile counters, and for each job its seconds,
what the process used over it and its seconds by span name). A run
that finds no TPU, or fewer chips than the cell asks for, exits 3 with
no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
NO_CHIP = 3
MIN_GAP_S = 1e-3  # shorter idle gaps lie between back-to-back operations


def note(**rec) -> None:
    print(json.dumps(rec), flush=True)


def load(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def by_name(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"benchmark: no {what} named {name!r} in BENCHMARK.json")


def applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


class Ctx:
    """What a traffic driver gets: the cell's data, where its jobs write,
    and the two calls back into the harness (``setup_done``,
    ``profiler``).

    Placeholders of the configuration's ``argv`` and ``outputs[].file``:
    ``{prefix}`` (one job's own path, nothing there yet), ``{base}`` (its
    last component: ``-d {prefix}`` writes ``{prefix}/{base}-indexcov.*``),
    ``{dir}`` (the fixture's directory) and ``{ref}``, ``{fai}``, ``{bed}``
    (``{dir}/ref.fa``, ``ref.fa.fai``, ``region.bed``). The argv token
    ``{inputs}`` becomes one argument for each of ``meta["inputs"]``."""

    def __init__(self, config, mix, meta, fixture_dir, out_dir, seconds,
                 trace):
        self.config, self.mix, self.meta = config, mix, meta
        self.fixture_dir, self.out_dir = fixture_dir, out_dir
        self.seconds, self.trace = seconds, bool(trace)
        self.output_files = {o["name"]: o["file"] for o in config["outputs"]}
        self.t_setup_done = self.counters_at_setup_done = None
        self.rss_at_setup_done = None
        self.trace_dir = os.path.join(out_dir, "trace")
        self._places = {
            "dir": fixture_dir,
            "ref": f"{fixture_dir}/ref.fa", "fai": f"{fixture_dir}/ref.fa.fai",
            "bed": f"{fixture_dir}/region.bed"}

    def _fill(self, text: str, prefix: str) -> str:
        return text.format(prefix=prefix, base=os.path.basename(prefix),
                           **self._places)

    def job_argv(self, prefix: str) -> list[str]:
        argv = []
        for tok in self.config["argv"]:
            if tok == "{inputs}":
                argv += [f"{self.fixture_dir}/{f}"
                         for f in self.meta["inputs"]]
            else:
                argv.append(self._fill(tok, prefix))
        return argv

    def job_outputs(self, prefix: str) -> dict[str, str]:
        return {kind: (f"{prefix}.stdout" if f == "stdout"
                       else self._fill(f, prefix))
                for kind, f in self.output_files.items()}

    def setup_done(self) -> None:
        """The driver calls this when every program the window will use is
        loaded: set-up ends here."""
        self.t_setup_done = time.perf_counter()
        self.counters_at_setup_done = counters()
        self.rss_at_setup_done = peak_rss_bytes()

    @contextlib.contextmanager
    def profiler(self, anchor: str):
        """A device trace around the body, host and Python tracers low so
        that it stays small; ``anchor`` marks the body on the trace's own
        clock."""
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(anchor):
                yield
        finally:
            jax.profiler.stop_trace()


def make_fixture(config_path: str, seed: int,
                 out: str) -> tuple[dict, float, str]:
    """Run the JAX-free child; (meta, the child's wall seconds, whether it
    ``built`` the fixture or ``reused`` one)."""
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "fixtures.py"),
         "--config", config_path, "--seed", str(seed), "--out", out],
        stdout=subprocess.PIPE, text=True)
    if proc.returncode:
        raise SystemExit(f"benchmark: fixtures.py exited {proc.returncode}")
    seconds = time.perf_counter() - t0
    said = json.loads(proc.stdout.splitlines()[-1])
    note(**said, child_seconds=seconds)
    return load(f"{out}/meta.json"), seconds, said["fixture"]


def drop_other_seeds(fixture_dir: str, config_name: str) -> None:
    """One seed's fixture a configuration: a run on a new seed removes
    what the configuration's earlier seeds left (3.5 GB a seed of
    ``indexcov500``), whole or half-made. Runs of one configuration in one
    checkout are serial, as the check makes them: a second run beside
    this one would lose the fixture it is making or reading."""
    parent, keep = os.path.split(fixture_dir)
    other = re.compile(rf"{re.escape(config_name)}-\d+(\.tmp\d+)?$")
    for d in os.listdir(parent):
        if d != keep and other.match(d):
            shutil.rmtree(os.path.join(parent, d), ignore_errors=True)


# The program builds its decoder with ``g++ -march=native`` on first use. On
# the chip machine of PR 25 that build held AVX-512VL instructions the CPU
# refused (SIGILL in bam_segments_stream; PERF.md section 7, row 0), so the
# build is tried in a child first and, only where the child dies of a
# signal, made again from the same source for a fixed baseline.
PROBE = """
import sys
from goleft_tpu.io import native
from goleft_tpu.io.bai import query_voffset, read_bai
from goleft_tpu.io.bam import open_bam_file
if native.get_lib() is None:
    sys.exit(2)
if len(sys.argv) < 2:  # a fixture with nothing to decode: loading is all
    sys.exit(0)
bam = sys.argv[1]
voff = query_voffset(read_bai(bam + ".bai"), 0, 0)
starts, _ = open_bam_file(bam, lazy=True).read_segments(
    0, 0, 200_000, 0, 0, voffset=voff)
sys.exit(0 if len(starts) else 3)
"""
PORTABLE_BUILD = ["g++", "-O3", "-march=x86-64-v3", "-shared", "-fPIC",
                  "csrc/fastio.cpp", "-lz", "-ldeflate",
                  "-o", "build/libgoleftio.so"]


def native_library(bam: str | None):
    """(the loaded library, who built it). The run fails where the
    program has no native decoder. ``bam`` is the file the probe decodes
    200 kb of; without one the probe only builds and loads."""
    def probe() -> int:
        return subprocess.run(
            [sys.executable, "-c", PROBE, *([bam] if bam else [])], cwd=ROOT,
            env=dict(os.environ, PYTHONPATH=ROOT)).returncode

    marker = os.path.join(BENCH, ".native_rebuilt")
    rc, built = probe(), "program"
    if rc < 0:
        subprocess.run(PORTABLE_BUILD, cwd=ROOT, check=True)
        with open(marker, "w") as fh:
            fh.write(f"benchmark, {PORTABLE_BUILD[2]}: the program's own "
                     f"build died of signal {-rc} decoding 200 kb")
        rc = probe()
    if os.path.exists(marker):  # also a later run's, which finds that build
        with open(marker) as fh:
            built = fh.read()
    from goleft_tpu.io import native

    lib = native.get_lib()
    if rc or lib is None:
        raise SystemExit("benchmark: no working native library from "
                         f"csrc/fastio.cpp on this machine (probe: {rc})")
    return lib, built


def machine_id() -> str:
    """What tells one machine (one lease) from the next: the kernel's boot
    id, else the host's name."""
    try:
        with open("/proc/sys/kernel/random/boot_id") as fh:
            return fh.read().strip()
    except OSError:
        return os.uname().nodename


def peak_rss_bytes() -> int:
    """This process's resident high-water mark: ``VmHWM`` where the kernel
    gives it, else ``ru_maxrss`` (the chip machine's gVisor kernel has no
    ``VmHWM``; its ``ru_maxrss`` is a true high-water mark and stays up
    when memory is freed)."""
    import resource

    with open("/proc/self/status") as fh:
        for line in fh:
            if line.startswith("VmHWM:"):
                return int(line.split()[1]) * 1024
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def program_spans(t0: float, t1: float) -> list[dict]:
    from goleft_tpu.obs import get_tracer

    return [{"name": s.name, "category": s.category, "t0": s.t0, "t1": s.t1}
            for s in get_tracer().snapshot()
            if s.t1 is not None and s.t0 >= t0 and s.t1 <= t1]


def span_seconds(spans: list[dict], job: dict) -> dict:
    """One job's seconds by span name (thread-seconds where threads share
    a name), for the notes: which stage a slow job was slow in."""
    sums: dict[str, float] = {}
    for s in spans:
        if s["t0"] >= job["t0"] and s["t1"] <= job["t1"]:
            sums[s["name"]] = sums.get(s["name"], 0.0) + s["t1"] - s["t0"]
    return {k: round(v, 4) for k, v in sorted(sums.items())}


def counters() -> dict:
    from goleft_tpu.obs import get_registry

    return get_registry().counters()


def label_gaps(trace: dict, job: dict, spans: list[dict]) -> list:
    """The longest idle gaps of the traced job, each named by the program
    stage span that covers most of it (the program annotates nothing in
    the profiler's trace, so its own spans are laid over the trace's clock
    at the anchor)."""
    gaps = [g for g in trace["gaps"][:10] if g[1] - g[0] >= MIN_GAP_S]
    if not trace["anchor"]:
        return [["job", e - s] for s, e in gaps]
    shift = job["t0"] - trace["anchor"][0]
    stages = [s for s in spans if s["category"] == "stage"]
    out = []
    for g0, g1 in gaps:
        p0, p1 = g0 + shift, g1 + shift
        cover: dict[str, float] = {}
        for s in stages:
            ov = min(p1, s["t1"]) - max(p0, s["t0"])
            if ov > 0:
                cover[s["name"]] = cover.get(s["name"], 0.0) + ov
        name = max(cover, key=cover.get) if cover else "no stage span"
        out.append([f"job: {name}", g1 - g0])
    return out


def main(argv=None, require_tpu: bool = True, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)

    bench = load(os.path.join(root, "BENCHMARK.json"))
    cell = by_name(bench["workloads"], a.workload, "workload")
    config_path = os.path.join(
        root, by_name(bench["configs"], cell["config"], "config")["file"])
    config = load(config_path)
    mix = load(os.path.join(BENCH, "traffic", f"{cell['traffic']}.json"))
    for d in (ROOT, BENCH):
        if d not in sys.path:
            sys.path.insert(0, d)

    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu"
                        or len(devs) < cell["chips"]):
        print(f"benchmark: {a.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devs)} x {devs[0].platform}", file=sys.stderr)
        return NO_CHIP
    devs = devs[:cell["chips"]]
    rss_backend = peak_rss_bytes()
    t_backend = time.perf_counter()

    # what runs leave behind stays under <root>/benchmark (git-ignored)
    work = os.path.join(root, "benchmark")
    fixture_dir = os.path.join(work, ".fixtures",
                               f"{cell['config']}-{a.seed}")
    os.makedirs(os.path.dirname(fixture_dir), exist_ok=True)
    drop_other_seeds(fixture_dir, cell["config"])
    meta, fixture_s, fixture_was = make_fixture(config_path, a.seed,
                                                fixture_dir)
    t_fixture = time.perf_counter()
    probe = meta["native_probe"]
    lib, built = native_library(probe and f"{fixture_dir}/{probe}")
    # where set-up goes, for the notes: imports and backend, the native
    # library's probe (and build), then the driver's warm-up
    phases = {"backend_s": t_backend - T_START,
              "native_probe_s": time.perf_counter() - t_fixture}
    note(machine={"cpu_count": os.cpu_count(),
                  "affinity": len(os.sched_getaffinity(0)),
                  "id": machine_id(), "fixture": fixture_was,
                  "fixture_disk_free_bytes":
                      shutil.disk_usage(fixture_dir).free,
                  "native_built_by": built,
                  "native_inflate": "libdeflate" if hasattr(
                      lib, "libdeflate_alloc_decompressor") else "zlib",
                  "jax": jax.__version__})

    out_dir = os.path.join(work, ".runs", f"{a.workload}-{a.seed}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    ctx = Ctx(config, mix, meta, fixture_dir, out_dir, a.seconds, a.trace)
    driver = importlib.import_module(f"drivers.{mix['driver']}")
    try:
        return measure(a, bench, cell, ctx, driver, devs, fixture_s,
                       rss_backend, phases)
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)


def short_op(name: str) -> str:
    """An HLO instruction's name, first result shape and opcode, without
    layouts and operands: what a reader of the ledger can take in."""
    lhs, eq, rhs = name.partition(" = ")
    shape = re.search(r"[a-z0-9]+\[[^\]]*\]", rhs)
    opcode = re.search(r" ([a-z][\w\-]*)\(", rhs)
    if not (eq and shape and opcode):
        return name[:96]
    tuple_ = ", ..." if rhs.startswith("(") else ""
    return f"{lhs} = {shape[0]}{tuple_} {opcode[1]}"


def read_trace(ctx, job: dict, chips: int) -> dict | None:
    from reducers import device_trace

    for dirpath, _, files in os.walk(ctx.trace_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                path = os.path.join(dirpath, f)
                note(trace_file_bytes=os.path.getsize(path))
                if os.environ.get("BENCH_TRACE_COPY"):  # for a look by hand
                    shutil.copy(path, os.environ["BENCH_TRACE_COPY"])
                return device_trace.summarize(path, job["t1"] - job["t0"],
                                              chips)
    return None


def per_layer(bench, cell, run: dict) -> dict:
    """Each per-layer metric of the cell through its own reader; one that
    finds nothing to read is left out."""
    metrics = {}
    for m in bench["per_layer"]:
        if applies(m, cell["name"]):
            spec = load(os.path.join(BENCH, "metrics", f"{m['name']}.json"))
            reducer = importlib.import_module(f"reducers.{spec['reducer']}")
            value = reducer.reduce(spec["args"], run)
            if value is not None:
                metrics[m["name"]] = value
    return metrics


def measure(a, bench, cell, ctx, driver, devs, fixture_s,
            rss_backend, phases) -> int:
    import compare

    got = driver.run(ctx)
    after = counters()
    # memory first, before this process reads the expected texts
    rss_peak = peak_rss_bytes()
    device_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devs)
    setup_s = ctx.t_setup_done - T_START - fixture_s
    note(setup={"setup_s": setup_s, "fixture_child_s": fixture_s, **phases,
                "rss_gb": {"backend_up": rss_backend * 1e-9,
                           "setup_done": ctx.rss_at_setup_done * 1e-9,
                           "window_closed": rss_peak * 1e-9},
                "counters_after_warmup": ctx.counters_at_setup_done})
    every_job = got["warmup"] + got["jobs"]
    spans = program_spans(min((j["t0"] for j in every_job), default=0.0),
                          max((j["t1"] for j in every_job), default=0.0))
    for j in every_job:
        note(job=j["index"], seconds=j["t1"] - j["t0"], rc=j["rc"],
             **j.get("host", {}), span_s=span_seconds(spans, j))

    numbers = compare.compare_jobs(every_job, ctx.config["outputs"],
                                   ctx.fixture_dir)
    correct = bool(got["jobs"]) and all(
        n["value"] <= n["limit"] for n in numbers.values())

    result = {"correct": correct, "attempted": len(got["jobs"]),
              "failed": sum(not j["ok"] for j in got["jobs"])}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": device_peak}
    breakdown = None
    if not a.trace:
        values = dict(got["end_to_end"], setup_s=setup_s,
                      # one whole job in a fresh process, as a user's is
                      peak_rss_gb=(ctx.rss_at_setup_done - rss_backend) * 1e-9)
        metrics = {m["name"]: values[m["name"]] for m in bench["end_to_end"]
                   if applies(m, cell["name"])}
    else:
        job = got["traced_job"]
        trace = read_trace(ctx, job, len(devs))
        metrics = per_layer(bench, cell, {
            "spans": program_spans(got["t_open"], got["t_close"]),
            "gbases": got["work_done"] * 1e-9,
            "job_gbases": ctx.meta["job_bases"] * 1e-9,
            "counters": {"before": ctx.counters_at_setup_done,
                         "after": after},
            "meta": ctx.meta, "trace": trace,
            "device": {"kind": devs[0].device_kind,
                       "device_peak_bytes": device_peak,
                       "rss_growth_bytes_per_job":
                           (rss_peak - ctx.rss_at_setup_done)
                           / max(1, len(got["jobs"]))}})
        if trace:
            note(trace_structure=trace["structure"],
                 modules=trace["modules"][:10])
            device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
            breakdown = {
                "device_ops": [[short_op(n), s] for n, s in trace["ops"][:10]],
                "idle_gaps": label_gaps(
                    trace, job, program_spans(job["t0"], job["t1"]))}
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    result["metrics"] = {k: {"value": v, "unit": units[k]}
                         for k, v in metrics.items()}
    result["device"] = device
    if breakdown:
        result["breakdown"] = breakdown
    result["compared"] = numbers
    for name, n in numbers.items():
        print(f"compared {name}: {n['value']} (limit {n['limit']})",
              file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
