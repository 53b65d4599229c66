"""Unified tracing & metrics subsystem (goleft_tpu.obs).

Pins the PR-3 observability contracts: the Perfetto/Chrome trace-event
export schema (golden-file round-trip — a schema drift breaks loading
in Perfetto silently, so the exact normalized shape is committed),
concurrent cross-thread span recording under the prefetch pool,
metrics-registry snapshot determinism, the serve daemon's /metrics
being derived solely from the unified registry (byte-for-byte), the
StageTimer's totals and counts, p99/max percentiles, each work span's
CPU seconds and off-CPU counter, the run manifest schema,
and the CLI's global --trace-out/--metrics-out/--log-level/-v flags.
"""

import json
import os
import threading

import numpy as np
import pytest

from goleft_tpu import obs
from goleft_tpu.obs.manifest import REQUIRED_KEYS, load_manifest
from goleft_tpu.obs.metrics import MetricsRegistry
from goleft_tpu.obs.tracing import Tracer
from helpers import write_bam_and_bai, random_reads

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "golden", "obs_trace_golden.json")


# ---------------- trace export: golden-file round-trip ----------------


def _golden_span_script(tracer: Tracer) -> None:
    """The fixed span scenario the golden file pins: a CLI-style root,
    two sequential stages (one carrying device attrs), and one span
    recorded from a worker thread under an attached context."""
    with tracer.trace("run.golden", kind="cli", argv="golden") as root:
        assert root.trace_id.startswith("cli-")
        with tracer.span("decode", category="stage", shard=0):
            pass
        with tracer.span("compute", category="device", platform="cpu"):
            pass
        ctx = tracer.capture()

        def worker():
            with tracer.attach(ctx):
                with tracer.span("stage", category="stage"):
                    pass

        t = threading.Thread(target=worker, name="goleft-prefetch-0")
        t.start()
        t.join(timeout=30)


def _normalize(doc: dict) -> dict:
    """Strip the volatile fields (timestamps, pids, tids, id values)
    while preserving the schema AND the id topology (which span
    parents which, which spans share a thread/trace)."""
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    tid_map: dict = {}
    span_map: dict = {}
    for e in xs:
        tid_map.setdefault(e["tid"], f"T{len(tid_map)}")
        span_map.setdefault(e["args"]["span_id"],
                            f"S{len(span_map)}")
    events = []
    for e in xs:
        args = dict(e["args"])
        args["span_id"] = span_map[args["span_id"]]
        if "parent_id" in args:
            args["parent_id"] = span_map[args["parent_id"]]
        args["trace_id"] = "TRACE"
        if "cpu_s" in args:  # a work span's CPU seconds: there, not equal
            args["cpu_s"] = "CPU"
        events.append({
            "name": e["name"], "cat": e["cat"], "ph": "X",
            "ts": 0, "dur": 0, "pid": "PID",
            "tid": tid_map[e["tid"]], "args": args,
        })
    meta = [
        {"name": "thread_name", "ph": "M", "pid": "PID", "tid": t}
        for t in sorted(set(tid_map.values()))
    ]
    return {"traceEvents": meta + events,
            "displayTimeUnit": doc["displayTimeUnit"],
            "otherData": {
                "producer": doc["otherData"]["producer"],
                "spans_dropped": doc["otherData"]["spans_dropped"],
            }}


def test_perfetto_export_schema_matches_golden():
    tracer = Tracer()
    _golden_span_script(tracer)
    got = _normalize(tracer.to_chrome_trace())
    with open(GOLDEN) as fh:
        want = json.load(fh)
    assert got == want, (
        "Chrome trace-event export schema drifted from the golden "
        "file — if intentional, regenerate tests/golden/"
        "obs_trace_golden.json (see this test's module docstring)")


def test_perfetto_export_round_trips_and_validates(tmp_path):
    from goleft_tpu.obs.smoke import validate_trace

    tracer = Tracer()
    _golden_span_script(tracer)
    p = str(tmp_path / "t.json")
    tracer.write_chrome_trace(p)
    doc = validate_trace(p)  # the smoke's schema checks
    # round-trip: export → parse → same normalized document
    assert _normalize(doc) == _normalize(tracer.to_chrome_trace())
    # the cross-thread span parents under the captured root span
    by_name = {e["name"]: e for e in doc["traceEvents"]
               if e.get("ph") == "X"}
    root = by_name["run.golden"]
    stage = by_name["stage"]
    assert stage["args"]["parent_id"] == root["args"]["span_id"]
    assert stage["args"]["trace_id"] == root["args"]["trace_id"]
    assert stage["tid"] != root["tid"]  # genuinely another thread
    assert by_name["compute"]["args"]["platform"] == "cpu"


# ---------------- cross-thread recording under the prefetch pool ----


def test_concurrent_spans_under_prefetch_pool():
    """Producer-thread spans land on the shared tracer under the
    consumer's trace, completely and race-free, while the consumer
    records its own compute spans concurrently."""
    from goleft_tpu.parallel.prefetch import ChunkPrefetcher
    from goleft_tpu.utils.profiling import StageTimer

    tracer = obs.get_tracer()
    timer = StageTimer()
    n = 24

    def produce(i):
        with timer.stage("decode"):
            return i * 2

    with obs.trace("run.prefetch-test", kind="cli") as root:
        trace_id = root.trace_id
        got = []
        with ChunkPrefetcher(range(n), produce, depth=4,
                             processes=4) as pf:
            for ch in pf:
                with timer.stage("compute"):
                    got.append(ch.value)
    assert got == [i * 2 for i in range(n)]
    assert timer.counts["decode"] == n
    assert timer.counts["compute"] == n
    mine = [sp for sp in tracer.snapshot()
            if sp.trace_id == trace_id]
    by_name = {}
    for sp in mine:
        by_name.setdefault(sp.name, []).append(sp)
    assert len(by_name["decode"]) == n
    assert len(by_name["compute"]) == n
    # decode spans really ran on pool threads, attached to the
    # consumer's trace and parented under its root
    root_sp = by_name["run.prefetch-test"][0]
    consumer_tid = root_sp.thread_id
    assert all(sp.parent_id == root_sp.span_id
               for sp in by_name["decode"])
    assert any(sp.thread_id != consumer_tid
               for sp in by_name["decode"])
    # prefetch populated the unified registry
    snap = obs.get_registry().snapshot()
    assert snap["counters"]["prefetch.chunks_total"] >= n


# ---------------- registry snapshot determinism ----------------


def _populate(reg: MetricsRegistry, order):
    for name in order:
        reg.counter(f"c.{name}").inc(ord(name[0]))
    reg.gauge("g.depth").set(3)
    for v in (0.1, 0.2, 0.3):
        reg.histogram("h.lat").observe(v)


def test_registry_snapshot_deterministic():
    a, b = MetricsRegistry(), MetricsRegistry()
    _populate(a, ["x", "y", "z"])
    _populate(b, ["z", "x", "y"])  # creation order must not matter
    assert json.dumps(a.snapshot()) == json.dumps(b.snapshot())
    # and a re-snapshot of unchanged state is byte-identical
    assert json.dumps(a.snapshot()) == json.dumps(a.snapshot())
    snap = a.snapshot()
    assert snap["counters"]["c.x"] == ord("x")
    assert snap["histograms"]["h.lat"]["count"] == 3
    assert snap["histograms"]["h.lat"]["max"] == 0.3


def test_histogram_count_outlives_window():
    reg = MetricsRegistry()
    h = reg.histogram("h", maxlen=4)
    for i in range(10):
        h.observe(i)
    s = h.summary()
    assert s["count"] == 10       # all-time
    assert s["max"] == 9.0        # window holds the recent 6,7,8,9
    assert s["p50"] >= 6.0


# ---------------- serve /metrics: solely the unified registry -------


def test_serve_metrics_snapshot_is_registry_derived_byte_for_byte():
    """Rebuild the /metrics body from NOTHING but the public registry
    API (+ the shared StageTimer and start time) and require the
    daemon's own snapshot to serialize byte-identically — proving no
    bespoke counter state is left."""
    from goleft_tpu.serve.metrics import ServeMetrics

    m = ServeMetrics()
    m.inc("requests_total.depth")
    m.inc("requests_total.depth")
    m.inc("device_passes_total", 3)
    m.observe_batch(4)
    m.observe_batch(4)
    m.observe_batch(1)
    m.observe_latency("depth", 0.25)
    m.observe_latency("indexcov", 0.5)
    with m.timer.stage("compute"):
        pass

    got = m.snapshot(queue_depth=2, cache_stats={"hits": 1})

    reg = m.registry
    counters = {n: v for n, v in reg.counters("serve.").items()
                if not n.startswith(("batch_size.", "latency_s."))}
    rebuilt = {
        "uptime_s": got["uptime_s"],  # wall clock, not metric state
        "counters": counters,
        "batch_size_hist": {
            str(k): v for k, v in sorted(
                (int(n), v) for n, v in
                reg.counters("serve.batch_size.").items())},
        "latency_s": reg.histograms("serve.latency_s."),
        "latency_windows": reg.histogram_windows("serve.latency_s."),
        "stage_seconds": m.timer.as_dict(),
        "stage_spans_dropped": obs.get_tracer().spans_dropped,
        "queue_depth": 2,
        "cache": {"hits": 1},
    }
    assert json.dumps(got) == json.dumps(rebuilt)
    # legacy shape intact: the serve tests' key contract
    assert got["batch_size_hist"] == {"1": 1, "4": 2}
    assert got["counters"]["batched_requests_total"] == 9
    lat = got["latency_s"]["depth"]
    assert lat["count"] == 1 and "p99" in lat and "max" in lat


def test_serve_app_uses_private_registry_by_default():
    from goleft_tpu.serve.server import ServeApp

    app = ServeApp(batch_window_s=0.0, max_batch=1)
    try:
        assert app.metrics.registry is not obs.get_registry()
    finally:
        app.close()


# ---------------- StageTimer + percentiles ----------------


def test_stagetimer_keeps_totals_and_counts_not_spans():
    from goleft_tpu.utils.profiling import StageTimer

    tm = StageTimer()
    for _ in range(10):
        with tm.stage("s"):
            pass
    assert tm.counts["s"] == 10
    assert tm.as_dict()["s"]["calls"] == 10
    assert tm.totals["s"] > 0.0
    # the spans live in the tracer's ring alone
    assert not hasattr(tm, "spans") and not hasattr(tm, "spans_dropped")


def test_percentiles_include_p99_and_max():
    from goleft_tpu.utils.profiling import percentiles

    vals = [i / 100.0 for i in range(1, 101)]
    out = percentiles(vals)
    assert out["p50"] == 0.5
    assert out["p95"] == 0.95
    assert out["p99"] == 0.99
    assert out["max"] == 1.0
    assert percentiles([]) == {"count": 0}


# ---------------- busy or waiting: a work span's CPU seconds ----------


def _seconds(name: str) -> tuple[float, float]:
    """(wall, cpu) seconds the registry holds for spans named ``name``."""
    got = obs.get_registry().counters()
    return (got.get(f"span.wall_seconds_total.{name}", 0.0),
            got.get(f"span.cpu_seconds_total.{name}", 0.0))


def _offcpu(name: str) -> float:
    wall, cpu = _seconds(name)
    return wall - cpu


def _sleep():
    import time

    time.sleep(0.05)


def _spin():
    import time

    t0 = time.perf_counter()
    while time.perf_counter() - t0 < 0.05:
        pass


@pytest.mark.parametrize("body,category", [
    (_sleep, "stage"),       # asleep: off the CPU
    (_spin, "stage"),        # busy: on it
    (_sleep, "wait"),        # a wait reads no clock at all
    (_spin, "transfer"),     # nor does a transfer
], ids=["sleep", "busy-loop", "wait-category", "transfer-category"])
def test_a_span_counts_its_seconds_off_the_cpu(body, category, request):
    name = f"offcpu-{request.node.callspec.id}"
    before = _offcpu(name)
    with obs.span(name, category=category) as sp:
        body()
    grew = _offcpu(name) - before
    if category != "stage":
        assert "cpu_s" not in sp.attrs
        counters = obs.get_registry().counters()
        assert f"span.wall_seconds_total.{name}" not in counters
        assert f"span.cpu_seconds_total.{name}" not in counters
        return
    if body is _sleep:
        assert grew >= 0.04
        assert sp.attrs["cpu_s"] < 0.02
    else:
        # against the span's own readings, not a fixed limit: preemption
        # on a loaded host or a coarse thread clock may move either
        assert sp.attrs["cpu_s"] > sp.duration() / 2
    # what is not on the CPU of the span's wall seconds is off it, with
    # nothing clipped
    assert grew == pytest.approx(sp.duration() - sp.attrs["cpu_s"],
                                 abs=1e-9)


def test_a_stage_opened_not_to_read_the_cpu_counts_nothing():
    from goleft_tpu.utils.profiling import StageTimer

    tm = StageTimer()
    with tm.stage("offcpu-not-read", read_cpu=False):
        pass
    sp = next(s for s in obs.get_tracer().snapshot()
              if s.name == "offcpu-not-read")
    assert sp.category == "stage" and "cpu_s" not in sp.attrs
    assert _seconds("offcpu-not-read") == (0.0, 0.0)
    assert tm.counts["offcpu-not-read"] == 1


def test_spans_on_two_threads_each_count_their_own_threads_cpu():
    """One thread spins while the other sleeps, side by side: each span
    reads its own thread's CPU clock, not the process's."""
    got = {}
    start = threading.Barrier(2)

    def work(name, body):
        start.wait()
        with obs.span(name, category="stage") as sp:
            body()
        got[name] = sp

    threads = [threading.Thread(target=work, args=("two-threads-busy",
                                                   _spin)),
               threading.Thread(target=work, args=("two-threads-idle",
                                                   _sleep))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    assert got["two-threads-busy"].attrs["cpu_s"] > 0.03
    assert got["two-threads-idle"].attrs["cpu_s"] < 0.02
    assert got["two-threads-busy"].thread_id != \
        got["two-threads-idle"].thread_id


def test_offcpu_counter_loses_no_update_across_threads():
    """More threads than cores close spans of one name at once, with the
    interpreter switching threads as often as it can: the counter holds
    every span's wall and CPU seconds."""
    import sys

    name = "offcpu-stress"
    before = _seconds(name)
    done = []
    lock = threading.Lock()

    def work():
        mine = []
        for _ in range(200):
            with obs.span(name, category="stage") as sp:
                pass
            mine.append((sp.duration(), sp.attrs["cpu_s"]))
        with lock:
            done.extend(mine)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work)
                   for _ in range(2 * (os.cpu_count() or 1) + 2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert len(done) == 200 * len(threads)
    after = _seconds(name)
    for i in (0, 1):  # wall, cpu
        assert after[i] - before[i] == pytest.approx(
            sum(d[i] for d in done), rel=1e-9, abs=1e-9)


def test_cli_runs_total_grows_by_one_per_main(tmp_path, capsys):
    from goleft_tpu.cli import main as cli_main

    bam = write_bam_and_bai(
        str(tmp_path / "s.bam"),
        random_reads(np.random.default_rng(40), 10, 0, 1_000),
        ref_names=("chr1",), ref_lens=(1_000,),
        header_text="@HD\tVN:1.6\tSO:coordinate\n"
                    "@SQ\tSN:chr1\tLN:1000\n@RG\tID:r\tSM:s40\n")

    def runs():
        return obs.get_registry().counters().get("cli.runs_total", 0)

    for _ in range(2):
        before = runs()
        assert cli_main(["samplename", bam]) == 0
        assert capsys.readouterr().out.strip() == "s40"
        assert runs() == before + 1


# ---------------- the dispatch seam ----------------


def test_instrumented_dispatch_forwards_jit_attrs():
    from goleft_tpu.ops import depth_pipeline as dp

    # the compile observatory's cache-size probe depends on these resolving
    assert isinstance(dp.shard_depth_pipeline._cache_size(), int)
    assert dp.shard_depth_pipeline.__name__ == "shard_depth_pipeline"


# ---------------- manifest ----------------


def test_manifest_schema_and_load(tmp_path):
    from goleft_tpu.obs.manifest import write_manifest

    reg = MetricsRegistry()
    reg.counter("x.total").inc(2)
    tracer = Tracer()
    with tracer.trace("run.m", kind="cli"):
        pass
    p = str(tmp_path / "run.json")
    doc = write_manifest(p, tracer=tracer, registry=reg,
                         argv=["goleft-tpu m"],
                         extra={"command": "m", "exit_code": 0})
    for k in REQUIRED_KEYS:
        assert k in doc
    loaded = load_manifest(p)
    assert loaded["metrics"]["counters"]["x.total"] == 2
    assert loaded["spans"]["run.m"]["calls"] == 1
    assert loaded["command"] == "m" and loaded["exit_code"] == 0
    assert loaded["backend"].get("platform") == "cpu"
    assert "device_kind" in loaded["backend"]
    # a manifest missing required keys must not load
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as fh:
        json.dump({"schema": "x"}, fh)
    with pytest.raises(ValueError, match="missing keys"):
        load_manifest(bad)


# ---------------- CLI global flags ----------------


def test_extract_global_flags_anywhere():
    from goleft_tpu.cli import _extract_global_flags

    opts, rest = _extract_global_flags(
        ["--trace-out", "t.json", "depth", "--metrics-out=m.json",
         "-v", "--prefix", "o", "x.bam"])
    assert opts["trace_out"] == "t.json"
    assert opts["metrics_out"] == "m.json"
    assert opts["verbose"] == 1
    assert rest == ["depth", "--prefix", "o", "x.bam"]
    with pytest.raises(ValueError, match="needs a value"):
        _extract_global_flags(["depth", "--trace-out"])
    with pytest.raises(ValueError, match="unknown log level"):
        _extract_global_flags(["--log-level", "loud"])


def test_cli_version_dash_v_still_wins(capsys):
    from goleft_tpu.cli import main as cli_main

    assert cli_main(["-v"]) == 0  # historical: version, not verbosity
    out = capsys.readouterr().out
    assert out.strip()  # printed a version string


def test_cli_bad_log_level_exits_one(capsys):
    from goleft_tpu.cli import main as cli_main

    assert cli_main(["--log-level", "loud", "samplename", "x"]) == 1
    assert "unknown log level" in capsys.readouterr().err


def test_configure_logging_idempotent():
    import logging

    obs.configure_logging("info")
    obs.configure_logging("debug")
    root = logging.getLogger("goleft-tpu")
    assert sum(1 for h in root.handlers
               if getattr(h, "_goleft_cli", False)) == 1
    assert root.level == logging.DEBUG
    assert obs.get_logger("serve").name == "goleft-tpu.serve"
    obs.configure_logging("warning")  # restore the default


# ---------------- CLI end-to-end: depth --trace-out --metrics-out ---


def test_depth_cli_writes_trace_and_manifest(tmp_path, monkeypatch):
    """Acceptance: `goleft-tpu depth --trace-out t.json --metrics-out
    m.json` produces a valid Chrome-trace-event file and a manifest
    whose backend provenance names the device jax computed on."""
    import jax

    from goleft_tpu.cli import main as cli_main
    from goleft_tpu.obs.smoke import validate_trace

    rng = np.random.default_rng(5)
    ref_len = 20_000
    bam = str(tmp_path / "t.bam")
    write_bam_and_bai(bam, random_reads(rng, 300, 0, ref_len,
                                        mapq_lo=20),
                      ref_names=("chr1",), ref_lens=(ref_len,),
                      header_text="@HD\tVN:1.6\tSO:coordinate\n"
                                  f"@SQ\tSN:chr1\tLN:{ref_len}\n"
                                  "@RG\tID:r\tSM:s1\n")
    with open(tmp_path / "ref.fa.fai", "w") as fh:
        fh.write(f"chr1\t{ref_len}\t6\t60\t61\n")
    t_out = str(tmp_path / "t.json")
    m_out = str(tmp_path / "m.json")
    rc = cli_main(["depth", "--trace-out", t_out, "--metrics-out",
                   m_out, "--prefix", str(tmp_path / "out"),
                   "-r", str(tmp_path / "ref.fa"), bam])
    assert rc == 0
    assert os.path.exists(str(tmp_path / "out.depth.bed"))

    doc = validate_trace(t_out)
    names = {e["name"] for e in doc["traceEvents"]
             if e.get("ph") == "X"}
    assert {"run.depth", "host-decode", "device-compute",
            "write-output"} <= names
    # --trace-out fences nothing: what the timeline shows for the
    # device is where the host waited for it and fetched from it
    assert {"device-wait", "d2h"} <= names
    man = load_manifest(m_out)
    # (the file holds the process's whole ring, earlier tests' too)
    assert not any(e["name"].startswith("device.")
                   for e in doc["traceEvents"] if e.get("ph") == "X"
                   and e["args"]["trace_id"] == man["trace_id"])

    assert man["command"] == "depth" and man["exit_code"] == 0
    assert man["trace_id"] and man["trace_id"].startswith("cli-")
    assert "host-decode" in man["spans"]
    assert man["metrics"]["counters"]["depth.shards_total"] >= 1
    dev = jax.devices()[0]
    assert man["backend"]["platform"] == dev.platform
    assert man["backend"]["device_kind"] == dev.device_kind
    # each stage span carries its thread's CPU seconds, and the run its
    # counters of the stages' wall and CPU seconds
    decode = next(e for e in doc["traceEvents"] if e.get("ph") == "X"
                  and e["name"] == "host-decode"
                  and e["args"]["trace_id"] == man["trace_id"])
    assert 0.0 <= decode["args"]["cpu_s"]
    counters = man["metrics"]["counters"]
    assert counters["cli.runs_total"] >= 1
    assert "span.wall_seconds_total.host-decode" in counters
    assert "span.cpu_seconds_total.host-decode" in counters


def test_cohortdepth_opens_each_input_under_a_span(tmp_path, capsys):
    """The cohort's opening (a BAM handle and its index a sample, on the
    load pool) is one ``open-inputs`` stage span a BAM, under the run's
    trace, before the first ``host-decode``."""
    from goleft_tpu.cli import main as cli_main
    from test_prefetch import _golden_cohort

    fa, bams = _golden_cohort(tmp_path)
    assert cli_main(["cohortdepth", "--engine", "device", "-w", "100",
                     "-r", fa, *bams]) == 0
    assert capsys.readouterr().out.count("\n") == 21
    spans = obs.get_tracer().snapshot()
    root = [s for s in spans if s.name == "run.cohortdepth"][-1]
    run = [s for s in spans if s.trace_id == root.trace_id]
    opened = [s for s in run if s.name == "open-inputs"]
    assert len(opened) == len(bams) == 3
    assert {s.category for s in opened} == {"stage"}
    assert all(s.parent_id == root.span_id and "cpu_s" in s.attrs
               for s in opened)
    first_decode = min(s.t0 for s in run if s.name == "host-decode")
    assert max(s.t1 for s in opened) <= first_decode
