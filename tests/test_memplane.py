"""The memory plane (obs/memplane.py + its consumers): host sampling,
the pressure band, fleet merge arithmetic over real HTTP, the
supervisor's drain-and-recycle, and the cohortscan chunk auto-sizer.

The acceptance property mirrors the PR-13 rollup discipline: the
router's ``/fleet/memory`` counters must equal the ARITHMETIC SUM of
the workers' ``/debug/memory`` bodies — pinned here in both the JSON
and the ``?format=prom`` encodings, over real stub HTTP workers."""

import json
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from goleft_tpu.obs.memplane import (
    MEMORY_SCHEMA, MemorySampler, MemoryTracker, PressureController,
    auto_chunk_samples, flatten_merged, merge_memory,
    merge_merged_memory, quick_rss, read_host_memory,
    register_controller, under_pressure, unregister_controller,
)
from goleft_tpu.obs.metrics import MetricsRegistry


# ---------------------------------------------- host collection

def test_read_host_memory_fields():
    h = read_host_memory()
    assert h["source"] == "procfs"
    assert h["rss_bytes"] > 0
    assert h["rss_peak_bytes"] >= h["rss_bytes"] // 2
    assert h["pss_bytes"] > 0  # smaps_rollup present on this kernel
    # the periodic tick skips the ~1.5ms smaps_rollup VMA walk
    cheap = read_host_memory(pss=False)
    assert cheap["rss_bytes"] > 0
    assert cheap["pss_bytes"] == 0


def test_quick_rss_matches_statm():
    rss = quick_rss()
    assert rss > 0
    assert abs(rss - read_host_memory()["rss_bytes"]) < 64 << 20


# ---------------------------------------------- pressure band

def test_pressure_two_sided_hysteresis():
    ctl = PressureController(high_water_bytes=1000,
                             low_water_bytes=800)
    assert ctl.enabled
    assert ctl.update(900) == "ok"       # below high: stays ok
    assert ctl.update(1001) == "pressure"
    # the hysteresis: between low and high it must NOT flap back
    assert ctl.update(900) == "pressure"
    assert ctl.update(801) == "pressure"
    assert ctl.update(800) == "ok"       # at/below low: recovers
    assert ctl.update(900) == "ok"       # and stays recovered
    assert ctl.should_shed() is False
    d = ctl.to_dict()
    assert d["state"] == "ok" and d["high_water_bytes"] == 1000


def test_pressure_disabled_default_low_and_inverted_band():
    off = PressureController()
    assert not off.enabled
    assert off.update(1 << 60) == "ok"
    assert off.to_dict()["low_water_bytes"] == 0
    dflt = PressureController(high_water_bytes=1000)
    assert dflt.low_water_bytes == 800  # 0.8 * high
    with pytest.raises(ValueError, match="band inverted"):
        PressureController(high_water_bytes=100, low_water_bytes=200)


def test_under_pressure_reads_registered_controllers():
    ctl = PressureController(high_water_bytes=10)
    register_controller(ctl)
    try:
        assert under_pressure() is False
        ctl.update(11)
        assert under_pressure() is True
        ctl.update(0)
        assert under_pressure() is False
    finally:
        unregister_controller(ctl)


# ---------------------------------------------- sampler lifecycle

def test_disabled_sampler_spawns_nothing_but_snapshot_answers():
    reg = MetricsRegistry()
    s = MemorySampler(registry=reg,
                      tracker=MemoryTracker(registry=reg))
    assert not s.enabled
    s.start()
    assert s._thread is None
    doc = s.snapshot()  # /debug/memory on an unsampled worker
    assert doc["schema"] == MEMORY_SCHEMA
    assert doc["enabled"] is False
    assert doc["gauges"]["memory.rss_bytes"] > 0
    assert doc["counters"]["memory.samples_total"] == 1  # on demand
    s.close()
    s.close()  # idempotent
    with pytest.raises(ValueError, match="interval"):
        MemorySampler(interval_s=-1)


def test_sampler_thread_publishes_gauges_and_counters():
    reg = MetricsRegistry()
    s = MemorySampler(interval_s=0.01, registry=reg,
                      tracker=MemoryTracker(registry=reg)).start()
    try:
        deadline = time.monotonic() + 30
        while reg.counter("memory.samples_total").value < 3 \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert reg.counter("memory.samples_total").value >= 3
        assert reg.gauge("memory.rss_bytes").value > 0
        assert reg.gauge("memory.rss_peak_bytes").value > 0
        assert reg.gauge("memory.pressure_state").value == 0.0
    finally:
        s.close()
    assert s._thread is None


def test_span_mem_attrs_ride_exactly_while_sampler_runs():
    from goleft_tpu.obs.tracing import Tracer

    trc = Tracer()
    reg = MetricsRegistry()
    with trc.span("before.any.sampler") as sp:
        pass
    assert "mem_delta_bytes" not in sp.attrs  # goldens byte-stable
    s = MemorySampler(interval_s=0.05, registry=reg, tracer=trc,
                      tracker=MemoryTracker(registry=reg)).start()
    try:
        with trc.span("while.sampling") as sp:
            blk = np.ones(4 << 20 >> 3)  # 4MB, touched
            blk.sum()
        assert "mem_delta_bytes" in sp.attrs
        assert sp.attrs["mem_peak_bytes"] > 0
        del blk
    finally:
        s.close()
    with trc.span("after.close") as sp:
        pass
    assert "mem_delta_bytes" not in sp.attrs  # probe disarmed


def test_sample_tick_cost_within_one_percent_duty_cycle():
    """The leak sentinel's overhead pin: one periodic tick must cost
    <= 1% of the 0.1s operational cadence."""
    reg = MetricsRegistry()
    s = MemorySampler(interval_s=0.1, registry=reg,
                      tracker=MemoryTracker(registry=reg))
    s.sample_once()  # warm the gauge objects
    t0 = time.perf_counter()
    for _ in range(100):
        s.sample_once()
    per_tick = (time.perf_counter() - t0) / 100
    assert per_tick <= 0.001, \
        f"sampling tick {per_tick * 1e6:.0f}us > 1% of 0.1s interval"
    s.close()


def test_device_attribution_returns_to_baseline():
    import jax

    reg = MetricsRegistry()
    tracker = MemoryTracker(registry=reg)
    with tracker.observe("unarmed"):
        pass  # bare yield until armed: no live_arrays walk
    assert not tracker._attr
    tracker.armed = True
    payload = np.arange(8192, dtype=np.float32)
    with tracker.observe("memtest"):
        buf = jax.device_put(payload)
        buf.block_until_ready()
    doc = tracker.device_doc()
    assert doc["by_family"]["memtest"] >= payload.nbytes
    assert reg.gauge("memory.device_live_bytes_total").value \
        >= payload.nbytes
    del buf
    import gc

    gc.collect()
    doc = tracker.device_doc()
    assert doc["by_family"]["memtest"] == 0  # dead family reports 0


def test_manifest_section_none_until_the_plane_is_touched():
    reg = MetricsRegistry()
    s = MemorySampler(registry=reg,
                      tracker=MemoryTracker(registry=reg))
    assert s.manifest_section() is None  # manifest unchanged
    s.sample_once()
    sect = s.manifest_section()
    assert sect["host"]["rss_bytes"] > 0
    assert sect["pressure"]["state"] == "ok"
    s.close()


# ---------------------------------------------- merge arithmetic

def _mem_body(samples, sheds, rss, peak, dev_total=0, families=None,
              pressure="ok", enabled=True):
    return {
        "schema": MEMORY_SCHEMA, "enabled": enabled,
        "interval_s": 0.05, "pid": 4242,
        "host": {"rss_bytes": rss, "rss_peak_bytes": peak,
                 "pss_bytes": 0, "source": "procfs"},
        "device": {"total_bytes": dev_total, "by_device": {},
                   "by_family": dict(families or {}),
                   "buffers_dropped": 0},
        "pressure": {"state": pressure,
                     "high_water_bytes": 1 << 30,
                     "low_water_bytes": 1 << 29,
                     "retry_after_s": 1.0},
        "counters": {"memory.samples_total": samples,
                     "memory.sheds_total": sheds},
        "gauges": {"memory.rss_bytes": rss,
                   "memory.rss_peak_bytes": peak,
                   "memory.device_live_bytes_total": dev_total,
                   "memory.pressure_state":
                       1.0 if pressure == "pressure" else 0.0},
    }


def test_merge_memory_exact_sums_minmax_and_skips():
    bodies = [
        _mem_body(3, 1, 100, 150, dev_total=10,
                  families={"depth": 10}),
        _mem_body(7, 0, 300, 400, dev_total=32,
                  families={"depth": 2, "pca": 30},
                  pressure="pressure"),
        "mid-restart garbage",          # non-dict: skipped
        {"error": "connection refused"},  # no host: skipped
    ]
    m = merge_memory(bodies)
    assert m["workers"] == 2
    assert m["workers_in_pressure"] == 1
    assert m["counters"]["memory.samples_total"] == 3 + 7
    assert m["counters"]["memory.sheds_total"] == 1
    g = m["gauges"]["memory.rss_bytes"]
    assert g == {"min": 100, "max": 300, "sum": 400}
    assert m["device_by_family"] == {"depth": 12, "pca": 30}


def test_merge_merged_memory_composes_associatively():
    """The federation guarantee: merging two fleet documents equals
    one flat merge over all four workers."""
    ws = [_mem_body(1, 0, 100, 110), _mem_body(2, 1, 200, 220),
          _mem_body(4, 0, 400, 440, families={"pca": 8}),
          _mem_body(8, 2, 800, 880, families={"pca": 16})]
    flat = merge_memory(ws)
    tiered = merge_merged_memory(
        [merge_memory(ws[:2]), merge_memory(ws[2:]),
         "down fleet", {"error": "?"}])
    assert tiered["workers"] == flat["workers"] == 4
    assert tiered["counters"] == flat["counters"]
    assert tiered["gauges"] == flat["gauges"]
    assert tiered["device_by_family"] == flat["device_by_family"]


def test_flatten_merged_renders_grammar_valid_prometheus():
    from goleft_tpu.obs import prometheus

    m = merge_memory([_mem_body(3, 1, 100, 150),
                      _mem_body(7, 0, 300, 400,
                                families={"pca": 30})])
    snap = flatten_merged(m)
    assert snap["counters"]["memory.samples_total"] == 10
    assert snap["gauges"]["memory.rss_bytes.sum"] == 400
    assert snap["gauges"]["memory.fleet_workers"] == 2
    assert snap["gauges"]["memory.device_live_bytes.pca.sum"] == 30
    text = prometheus.render(snap)
    assert "memory_samples_total 10" in text
    for line in text.splitlines():
        if line.startswith("#") or not line:
            continue
        name = line.split("{")[0].split(" ")[0]
        assert prometheus._NAME_OK.match(name), name


# ---------------------------------------------- fleet HTTP surface

class _MemStubHandler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, *a):
        pass

    def _json(self, code, body):
        data = json.dumps(body).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        self.send_header("Connection", "close")
        self.end_headers()
        self.wfile.write(data)
        self.close_connection = True

    def do_GET(self):  # noqa: N802
        s = self.server.state
        if self.path == "/healthz":
            self._json(200, {"status": "ok"})
        elif self.path.startswith("/debug/memory"):
            if s.get("fail"):
                self._json(500, {"error": "worker exploded"})
            else:
                self._json(200, s["memory"])
        elif self.path.startswith("/fleet/memory"):
            self._json(200, s["memory"])
        else:
            self._json(404, {"error": "?"})


class _MemStub:
    def __init__(self, memory, fail=False):
        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0),
                                         _MemStubHandler)
        self.httpd.state = {"memory": memory, "fail": fail}
        self._t = threading.Thread(target=self.httpd.serve_forever,
                                   kwargs={"poll_interval": 0.02},
                                   daemon=True)
        self._t.start()
        host, port = self.httpd.server_address[:2]
        self.url = f"http://{host}:{port}"

    def kill(self):
        self.httpd.shutdown()
        self.httpd.server_close()
        self._t.join(timeout=10)


def _get(url, accept=None):
    req = urllib.request.Request(
        url, headers={"Accept": accept} if accept else {})
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, dict(r.headers), r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read().decode()


def test_fleet_memory_counters_equal_worker_sum_over_http(tmp_path):
    """THE acceptance pin: /fleet/memory == arithmetic sum of the
    worker /debug/memory bodies, in JSON and in ?format=prom; a dead
    worker is reported per-worker but cannot veto the merge."""
    from goleft_tpu.fleet.router import RouterApp, RouterThread

    b0 = _mem_body(3, 1, 100 << 20, 150 << 20, dev_total=1 << 20,
                   families={"depth": 1 << 20})
    b1 = _mem_body(7, 2, 200 << 20, 280 << 20, dev_total=3 << 20,
                   families={"depth": 1 << 20, "pca": 2 << 20},
                   pressure="pressure")
    stubs = [_MemStub(b0), _MemStub(b1), _MemStub({}, fail=True)]
    app = RouterApp([s.url for s in stubs],
                    poll_interval_s=0.2, down_after=1)
    try:
        with RouterThread(app) as url:
            status, _, body = _get(url + "/fleet/memory")
            assert status == 200
            doc = json.loads(body)
            assert doc["schema"] == MEMORY_SCHEMA
            assert doc["workers"] == 2
            assert doc["workers_in_pressure"] == 1
            # the pinned arithmetic, counter by counter
            assert doc["counters"]["memory.samples_total"] == 3 + 7
            assert doc["counters"]["memory.sheds_total"] == 1 + 2
            g = doc["gauges"]["memory.rss_bytes"]
            assert g["min"] == 100 << 20
            assert g["max"] == 200 << 20
            assert g["sum"] == 300 << 20
            assert doc["device_by_family"] == {
                "depth": 2 << 20, "pca": 2 << 20}
            # the dead worker: reported, counted, not merged
            pw = doc["per_worker"]
            assert "error" in pw[stubs[2].url]
            assert pw[stubs[0].url]["rss_bytes"] == 100 << 20
            assert pw[stubs[1].url]["pressure"] == "pressure"
            snap = app.registry.snapshot()["counters"]
            assert snap["fleet.memory.worker_errors_total"] >= 1
            # the SAME sums in the prometheus encoding
            status, hdrs, text = _get(
                url + "/fleet/memory?format=prom")
            assert status == 200
            assert hdrs["Content-Type"].startswith(
                "text/plain; version=0.0.4")
            assert "memory_samples_total 10" in text
            assert "memory_sheds_total 3" in text
            # gauges ride as floats (repr), counters stay ints
            assert f"memory_rss_bytes_sum {float(300 << 20)!r}" \
                in text
            assert "memory_fleet_workers 2" in text
            from goleft_tpu.obs import prometheus

            for line in text.splitlines():
                if line.startswith("#") or not line:
                    continue
                name = line.split("{")[0].split(" ")[0]
                assert prometheus._NAME_OK.match(name), name
    finally:
        for s in stubs:
            s.kill()


def test_federation_memory_merges_fleet_documents(tmp_path):
    """One tier up: the federation merges already-merged fleet
    documents and its counters stay the flat worker sums."""
    from goleft_tpu.fleet import federation as fd

    f0 = merge_memory([_mem_body(3, 1, 100, 150),
                       _mem_body(7, 0, 300, 400)])
    f1 = merge_memory([_mem_body(10, 4, 500, 600,
                                 families={"pca": 64},
                                 pressure="pressure")])
    stubs = [_MemStub(f0), _MemStub(f1)]
    app = fd.FederationRouter([s.url for s in stubs],
                              poll_interval_s=30.0, down_after=2)
    try:
        doc = app.fleet_memory()
        assert doc["workers"] == 3
        assert doc["workers_in_pressure"] == 1
        assert doc["counters"]["memory.samples_total"] == 3 + 7 + 10
        assert doc["counters"]["memory.sheds_total"] == 5
        g = doc["gauges"]["memory.rss_bytes"]
        assert g == {"min": 100, "max": 500, "sum": 900}
        assert doc["device_by_family"] == {"pca": 64}
        pf = doc["per_fleet"]
        assert pf[stubs[0].url]["workers"] == 2
        assert pf[stubs[1].url]["workers_in_pressure"] == 1
    finally:
        app.close()
        for s in stubs:
            s.kill()


# ---------------------------------------------- serve admission

def test_serve_sheds_posts_under_pressure_then_recovers(tmp_path):
    from goleft_tpu.serve.server import ServeApp

    app = ServeApp(batch_window_s=0.0, max_batch=1,
                   mem_high_water_bytes=1000,
                   mem_low_water_bytes=800)
    try:
        ctl = app.memplane.pressure
        assert under_pressure() is False  # registered, not tripped
        ctl.update(2000)
        assert under_pressure() is True
        code, body = app._handle("depth", {})
        assert code == 503
        assert body["retry_after_s"] == ctl.retry_after_s
        assert "memory pressure" in body["error"]
        assert app.metrics.registry.counter(
            "memory.sheds_total").value == 1
        ctl.update(800)  # recovered at the low water mark
        code, body = app._handle("depth", {"bam": "/nope.bam"})
        assert code != 503  # admitted again (fails later on the bam)
    finally:
        app.close()
    assert under_pressure() is False  # close() unregisters


def test_prefetch_clamps_depth_to_one_under_pressure():
    from goleft_tpu.parallel.prefetch import ChunkPrefetcher

    ctl = PressureController(high_water_bytes=10)
    ctl.update(11)  # tripped
    register_controller(ctl)
    try:
        p = ChunkPrefetcher(range(8), produce=lambda m: m, depth=4,
                            processes=2)
        p._top_up()
        assert len(p._pending) == 1  # clamped: no new staging
        ctl.update(0)  # recovered
        p._top_up()
        assert len(p._pending) == 4  # configured depth restored
        assert [c.value for c in p] == list(range(8))  # none lost
    finally:
        unregister_controller(ctl)


# ---------------------------------------------- supervisor recycle

_MEM_STUB = r"""
import json, sys
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

class H(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    def log_message(self, *a):
        pass
    def do_GET(self):
        if self.path.startswith("/debug/memory"):
            body = {"host": {"rss_bytes": 1 << 30}}
        else:
            body = {"status": "ok"}
        data = json.dumps(body).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

srv = ThreadingHTTPServer(("127.0.0.1", 0), H)
print(f"stub: listening on http://127.0.0.1:{srv.server_address[1]}",
      flush=True)
srv.serve_forever()
"""


def test_supervisor_recycles_runaway_without_crash_penalty(tmp_path):
    """A healthy worker whose RSS exceeds --mem-recycle-mb is drained
    and recycled as MAINTENANCE: memory_recycle in the journal, the
    counter bumped, and — deliberately — no death in the crash
    window, so a leaky worker never quarantines its slot."""
    from test_supervisor import _drive, _supervisor

    script = tmp_path / "memhog.py"
    script.write_text(_MEM_STUB)
    journal = tmp_path / "events.jsonl"
    sup = _supervisor(str(script), min_workers=1,
                      mem_recycle_bytes=512 << 20,
                      events_journal=str(journal))
    try:
        sup.spawn_initial(1)
        slot = sup.slots()[0]
        _drive(sup,
               lambda: sup.registry.counter(
                   "memory.recycles_total").value >= 1
               and slot.restarts >= 1,
               what="a memory recycle plus the respawn")
        assert slot.deaths == []  # maintenance, not a crash
        evs = [e for e in sup.events.block()["recent"]
               if e["type"] == "memory_recycle"]
        assert evs
        assert evs[0]["rss_bytes"] == 1 << 30
        assert evs[0]["cap_bytes"] == 512 << 20
    finally:
        sup.close()
    # the fsync'd journal replays through the real events CLI
    from goleft_tpu.commands.fleet import events_main

    assert events_main(["--journal", str(journal),
                        "--type", "memory_recycle", "--json"]) == 0


def test_event_types_includes_memory_recycle():
    from goleft_tpu.obs.events import EVENT_TYPES

    assert "memory_recycle" in EVENT_TYPES


def test_fleet_events_cli_filters_memory_recycle(tmp_path, capsys):
    from goleft_tpu.commands.fleet import events_main
    from goleft_tpu.obs.events import EventJournal, EventLog

    log = EventLog(EventJournal(str(tmp_path / "ev.jsonl")),
                   registry=MetricsRegistry())
    log.emit("restart", slot=0, worker="http://w0")
    log.emit("memory_recycle", slot=0, worker="http://w0",
             pid=99, rss_bytes=2 << 30, cap_bytes=1 << 30)
    rc = events_main(["--journal", str(tmp_path / "ev.jsonl"),
                      "--type", "memory_recycle", "--json"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["schema"] == "goleft-tpu.fleet-events/1"
    assert doc["count"] == 1
    ev = doc["events"][0]
    assert ev["type"] == "memory_recycle"
    assert ev["rss_bytes"] == 2 << 30
    assert ev["cap_bytes"] == 1 << 30


# ---------------------------------------------- chunk auto-sizing

def test_auto_chunk_samples_clamps_and_falls_back():
    # budget/per_sample, clamped into [minimum, min(maximum, n)]
    assert auto_chunk_samples(1 << 20, 256 << 20, 10_000) == 256
    assert auto_chunk_samples(1 << 20, 256 << 20, 100) == 100
    assert auto_chunk_samples(1 << 30, 256 << 20, 10_000) == 8
    assert auto_chunk_samples(64, 256 << 20, 10_000_000) == 4096
    # no evidence -> no constraint (the maximum, bounded by n)
    assert auto_chunk_samples(0, 256 << 20, 50) == 50
    assert auto_chunk_samples(1 << 20, 0, 50) == 50
    assert auto_chunk_samples(0, 256 << 20, 3) == 8


def test_checkpoint_meta_notes_replay_with_later_lines_winning(
        tmp_path):
    from goleft_tpu.resilience.checkpoint import CheckpointStore

    d = str(tmp_path / "ck")
    st = CheckpointStore(d)
    st.note(chunk_peak_bytes=100, per_sample_bytes=7)
    st.note(chunk_peak_bytes=250)
    st.close()
    back = CheckpointStore(d, resume=True)
    assert back.meta["chunk_peak_bytes"] == 250  # later line wins
    assert back.meta["per_sample_bytes"] == 7
    back.close()
    fresh = CheckpointStore(d, resume=False)  # truncates
    assert fresh.meta == {}
    fresh.close()
