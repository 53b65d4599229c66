"""Sampling profiler: bounded tables, deterministic collapse, the
pinned ≤2%-at-100Hz overhead budget, and the /fleet/profile rollup's
exact arithmetic sums over stub workers (the PR-13 discipline).
"""

import json
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from goleft_tpu.obs.metrics import MetricsRegistry
from goleft_tpu.obs.profiler import (
    PROFILE_SCHEMA, SamplingProfiler, collapse_frame, diff_profiles,
    merge_profiles, to_collapsed,
)
from goleft_tpu.obs.tracing import Tracer


# ---------------- stub frames (collapse reads only f_code.co_name,
# f_globals["__name__"], f_lineno, f_back) ----------------


class _Code:
    def __init__(self, name):
        self.co_name = name


class _Frame:
    def __init__(self, mod, func, line, back=None):
        self.f_code = _Code(func)
        self.f_globals = {"__name__": mod}
        self.f_lineno = line
        self.f_back = back


def _stack(*frames):
    """Build a leaf frame from (mod, func, line) tuples, root first."""
    back = None
    for mod, func, line in frames:
        back = _Frame(mod, func, line, back=back)
    return back


# ---------------- collapse ----------------


def test_collapse_is_root_first_and_deterministic():
    leaf = _stack(("app", "main", 10), ("app.mod", "work", 22))
    memo = {}
    assert collapse_frame(leaf, memo) == \
        "app:main:10;app.mod:work:22"
    # memoized second pass yields the identical key
    assert collapse_frame(leaf, memo) == \
        "app:main:10;app.mod:work:22"


def test_collapse_truncates_runaway_recursion():
    leaf = _stack(*[("m", "f", i) for i in range(200)])
    out = collapse_frame(leaf, max_depth=16)
    assert out.startswith("~truncated~;")
    assert out.count(";") == 16


# ---------------- sampling semantics ----------------


def test_sample_aggregates_identical_stacks():
    leaf = _stack(("app", "main", 10), ("app", "work", 22))
    p = SamplingProfiler(hz=100, registry=MetricsRegistry(),
                         frames_provider=lambda: {1234: leaf})
    p._sample_once()
    p._sample_once()
    snap = p.snapshot()
    assert snap["schema"] == PROFILE_SCHEMA
    assert snap["samples_total"] == 2
    assert snap["stacks"] == {"app:main:10;app:work:22": 2}
    assert to_collapsed(snap) == "app:main:10;app:work:22 2\n"


def test_table_cap_drops_new_stacks_and_counts_them():
    reg = MetricsRegistry()
    state = {"i": 0}

    def frames():
        state["i"] += 1
        return {7: _stack(("m", "f", state["i"]))}  # all distinct

    p = SamplingProfiler(hz=100, max_stacks=3, registry=reg,
                         frames_provider=frames)
    for _ in range(10):
        p._sample_once()
    snap = p.snapshot()
    assert len(snap["stacks"]) == 3  # bounded
    assert snap["stacks_dropped"] == 7
    r = reg.snapshot()["counters"]
    assert r["profiler.samples_total"] == 10
    assert r["profiler.stacks_dropped_total"] == 7


def test_disabled_profiler_takes_zero_samples():
    p = SamplingProfiler(hz=0.0, registry=MetricsRegistry())
    assert not p.enabled
    p.start()
    assert p._thread is None  # no thread was spawned
    doc = p.collect(0.5)  # returns immediately: nothing to wait for
    assert doc["enabled"] is False
    assert doc["samples_total"] == 0 and doc["stacks"] == {}
    p.close()


def test_collect_window_is_a_delta_under_stub_clock():
    clk = {"t": 0.0}

    def clock():
        clk["t"] += 0.1  # each check advances: the window terminates
        return clk["t"]

    leaf = _stack(("goleft_tpu.x", "decode", 5))
    p = SamplingProfiler(hz=100, registry=MetricsRegistry(),
                         clock=clock,
                         frames_provider=lambda: {9: leaf})
    p._sample_once()  # before the window: excluded from the delta
    before = p.snapshot()
    p._sample_once()
    p._sample_once()
    after = p.snapshot()
    doc = diff_profiles(before, after)
    assert doc["samples_total"] == 2
    assert doc["stacks"] == {"goleft_tpu.x:decode:5": 2}
    # and the collect() path terminates deterministically on the stub
    # clock (no real sleeping beyond the stop-event poll)
    win = p.collect(0.3)
    assert win["schema"] == PROFILE_SCHEMA


def test_real_thread_sampling_and_trace_id_tagging():
    tracer = Tracer()
    p = SamplingProfiler(hz=200, registry=MetricsRegistry(),
                         tracer=tracer)
    stop = threading.Event()

    def busy():
        with tracer.trace("request.depth", kind="serve") as root:
            busy.trace_id = root.trace_id
            ready.set()
            while not stop.wait(0.001):
                sum(i * i for i in range(200))

    ready = threading.Event()
    th = threading.Thread(target=busy, name="busy-worker")
    th.start()
    try:
        assert ready.wait(5.0)
        p.start()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if p.snapshot()["samples_total"] >= 5:
                break
            time.sleep(0.01)
    finally:
        stop.set()
        p.close()
        th.join(timeout=10)
    snap = p.snapshot()
    assert snap["samples_total"] >= 5
    assert any("test_profiler" in s for s in snap["stacks"])
    # samples taken inside the traced request carry its trace id
    assert busy.trace_id in snap["trace_ids"]


def test_profiler_thread_is_joined_on_close():
    p = SamplingProfiler(hz=50, registry=MetricsRegistry()).start()
    t = p._thread
    assert t is not None and t.is_alive()
    p.close()
    assert not t.is_alive()
    assert p._thread is None
    p.close()  # idempotent


# ---------------- the pinned overhead budget ----------------


def test_overhead_at_100hz_is_within_two_percent():
    """The ISSUE's bound: 100 Hz sampling costs ≤ 2% of wall on the
    depth pipeline. 2% at 100 Hz means one sample may cost at most
    200µs; the memoized collapse makes a warm sample ~10µs, so this
    pins with a 10x margin while real worker threads run. The cost is
    the least of five wall-clock batches: a wait for the GIL or for a
    core only ever adds to a batch, and a sampler that blocks does so
    in every one."""
    stop = threading.Event()

    def busy():
        while not stop.is_set():
            sum(i * i for i in range(500))

    threads = [threading.Thread(target=busy, daemon=True)
               for _ in range(4)]
    for t in threads:
        t.start()
    p = SamplingProfiler(hz=100, registry=MetricsRegistry())
    try:
        for _ in range(50):
            p._sample_once()  # warm the key memo
        n = 200
        batches = []
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(n):
                p._sample_once()
            batches.append(time.perf_counter() - t0)
        per_sample = min(batches) / n
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
    # fraction of wall clock spent sampling at 100 Hz
    assert per_sample * 100.0 <= 0.02, \
        f"100 Hz sampling costs {per_sample * 100.0:.2%} > 2%"


# ---------------- merge semantics ----------------


def test_merge_profiles_is_exact_arithmetic_sum():
    a = {"schema": PROFILE_SCHEMA, "enabled": True, "hz": 50.0,
         "samples_total": 10, "stacks_dropped": 1,
         "stacks": {"m:f:1": 6, "m:g:2": 4},
         "trace_ids": {"serve-1-1": 2}}
    b = {"schema": PROFILE_SCHEMA, "enabled": True, "hz": 100.0,
         "samples_total": 7, "stacks_dropped": 0,
         "stacks": {"m:f:1": 3, "m:h:9": 7},
         "trace_ids": {"serve-1-1": 1, "serve-2-4": 5}}
    m = merge_profiles([a, b, {"not": "a profile"}])
    assert m["stacks"] == {"m:f:1": 9, "m:g:2": 4, "m:h:9": 7}
    assert m["samples_total"] == 17
    assert m["stacks_dropped"] == 1
    assert m["hz"] == 100.0
    assert m["trace_ids"] == {"serve-1-1": 3, "serve-2-4": 5}


# ---------------- /fleet/profile over stub workers ----------------


class _StubHandler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, *a):
        pass

    def do_GET(self):  # noqa: N802
        if self.path.startswith("/debug/profile"):
            body = self.server.profile_doc
        elif self.path == "/healthz":
            body = {"status": "ok"}
        elif self.path.startswith("/metrics"):
            body = {}
        else:
            body = {"error": "?"}
        data = json.dumps(body).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        self.send_header("Connection", "close")
        self.end_headers()
        self.wfile.write(data)
        self.close_connection = True


def _stub_worker(profile_doc):
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _StubHandler)
    httpd.profile_doc = profile_doc
    t = threading.Thread(target=httpd.serve_forever,
                         kwargs={"poll_interval": 0.02}, daemon=True)
    t.start()
    host, port = httpd.server_address[:2]
    return httpd, t, f"http://{host}:{port}"


def test_fleet_profile_sums_worker_stacks_exactly():
    from goleft_tpu.fleet.router import RouterApp

    doc_a = {"schema": PROFILE_SCHEMA, "enabled": True, "hz": 50.0,
             "samples_total": 12, "stacks_dropped": 0,
             "stacks": {"goleft_tpu.a:f:1": 8, "m:g:2": 4},
             "trace_ids": {}}
    doc_b = {"schema": PROFILE_SCHEMA, "enabled": True, "hz": 50.0,
             "samples_total": 5, "stacks_dropped": 2,
             "stacks": {"goleft_tpu.a:f:1": 2, "m:h:3": 3},
             "trace_ids": {"serve-9-1": 1}}
    wa = _stub_worker(doc_a)
    wb = _stub_worker(doc_b)
    # a third, dead worker must not veto the merge
    app = RouterApp([wa[2], wb[2], "http://127.0.0.1:1"],
                    poll_interval_s=30.0, down_after=1)
    try:
        merged = app.fleet_profile(seconds=0.2)
        # the pinned arithmetic: merged counter == sum over workers
        assert merged["stacks"] == {"goleft_tpu.a:f:1": 10,
                                    "m:g:2": 4, "m:h:3": 3}
        assert merged["samples_total"] == 17
        assert merged["stacks_dropped"] == 2
        assert merged["trace_ids"] == {"serve-9-1": 1}
        pw = merged["per_worker"]
        assert pw[wa[2]]["samples_total"] == 12
        assert "error" in pw["http://127.0.0.1:1"]
        r = app.registry.snapshot()["counters"]
        assert r["fleet.profile.requests_total"] == 1
        assert r["fleet.profile.worker_errors_total"] == 1
    finally:
        app.close()
        for httpd, t, _ in (wa, wb):
            httpd.shutdown()
            httpd.server_close()
            t.join(timeout=10)


def test_debug_profile_endpoint_end_to_end():
    from goleft_tpu.serve.server import ServeApp, ServerThread

    app = ServeApp(batch_window_s=0.0, max_batch=1, profile_hz=200.0)
    stop = threading.Event()

    def busy():
        while not stop.is_set():
            sum(i * i for i in range(200))

    th = threading.Thread(target=busy, name="busy", daemon=True)
    th.start()
    try:
        with ServerThread(app) as url:
            with urllib.request.urlopen(
                    url + "/debug/profile?seconds=0.3",
                    timeout=30) as r:
                doc = json.loads(r.read().decode())
            assert doc["schema"] == PROFILE_SCHEMA
            assert doc["enabled"] is True
            assert doc["samples_total"] >= 1
            assert doc["stacks"]  # the busy thread was seen
            with urllib.request.urlopen(
                    url + "/debug/profile?seconds=nope", timeout=30) \
                    as r:
                pytest.fail("expected 400")
    except urllib.error.HTTPError as e:
        assert e.code == 400
    finally:
        stop.set()
        th.join(timeout=10)
    # close() (via ServerThread.__exit__) joined the sampler
    assert app.profiler._thread is None
