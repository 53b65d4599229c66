"""End-to-end memory-plane leak sentinel: ``make memory-smoke``.

Three legs, because the memory plane's promises span three layers:

  1. **no leak under steady work**: an in-process sampler watches >= 3
     sampling windows while allocate/free rounds churn — RSS stays
     bounded (the allocator gives mmap'd blocks back), and a device
     buffer attributed to a family via the ``observe()`` seam returns
     that family's live bytes to 0 once the buffer dies.
  2. **pressure sheds and recovers**: a real serve daemon with an
     armed band takes a deliberate numpy hog, trips to ``pressure``,
     503s a POST admission with ``retry_after_s``, then recovers below
     the low water mark when the hog is freed and admits again — the
     two-sided hysteresis, observed through real HTTP.
  3. **the supervisor recycles a runaway**: a subprocess
     ``goleft-tpu fleet`` with ``--mem-recycle-mb`` far below the
     worker's baseline drains and recycles it, and the
     ``memory_recycle`` event is visible through the real
     ``goleft-tpu fleet events --json`` CLI (journal replay).

Run directly::

    python -m goleft_tpu.obs.memory_smoke
"""

from __future__ import annotations

import gc
import json
import os
import signal
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

import numpy as np

HOG_BYTES = 256 * 1024 * 1024
ROUND_BYTES = 32 * 1024 * 1024
RSS_SLACK_BYTES = 96 * 1024 * 1024


def _wait_until(pred, timeout_s: float, what: str,
                interval_s: float = 0.1):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(interval_s)
    raise RuntimeError(f"timed out waiting for {what}")


def _get_json(url: str, timeout_s: float = 30.0) -> dict:
    req = urllib.request.Request(
        url, headers={"Accept": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout_s) as r:
        return json.loads(r.read().decode())


def _post_json(url: str, body: dict,
               timeout_s: float = 30.0) -> tuple[int, dict]:
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout_s) as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode() or "{}")


def _leg_bounded_and_device_baseline(verbose):
    """Leg 1: RSS bounded across allocate/free rounds over >= 3
    sampling windows; a family's device bytes return to 0 when its
    buffer dies."""
    from .metrics import MetricsRegistry
    from .memplane import MemorySampler, get_tracker, quick_rss

    reg = MetricsRegistry()
    sampler = MemorySampler(interval_s=0.05, registry=reg).start()
    try:
        # warm the allocator once so the baseline includes the pool
        np.ones(ROUND_BYTES // 8).sum()
        baseline = quick_rss()
        for _ in range(5):
            block = np.ones(ROUND_BYTES // 8)
            block.sum()
            del block
        _wait_until(
            lambda: reg.counter("memory.samples_total").value >= 3,
            30.0, ">= 3 memory sampling windows")
        rss = quick_rss()
        if rss > baseline + RSS_SLACK_BYTES:
            raise RuntimeError(
                f"RSS leaked across allocate/free rounds: "
                f"{baseline} -> {rss} bytes")
        snap = sampler.snapshot()
        if snap["gauges"]["memory.rss_bytes"] <= 0:
            raise RuntimeError(f"host sampling returned no RSS: "
                               f"{snap['host']}")

        # device attribution round-trip through the observe() seam
        import jax

        tracker = get_tracker()
        payload = np.arange(512 * 1024, dtype=np.float32)  # 2MB
        with tracker.observe("memsmoke"):
            buf = jax.device_put(payload)
            buf.block_until_ready()
        doc = tracker.device_doc()
        got = doc["by_family"].get("memsmoke", 0)
        if got < payload.nbytes:
            raise RuntimeError(
                f"device attribution missed the smoke buffer: "
                f"memsmoke={got} < {payload.nbytes} "
                f"(families: {doc['by_family']})")
        del buf
        gc.collect()
        after = tracker.device_doc()["by_family"].get("memsmoke", 0)
        if after != 0:
            raise RuntimeError(
                f"device family bytes did not return to baseline "
                f"after the buffer died: memsmoke={after}")
        if verbose:
            print("memory-smoke: RSS bounded over "
                  f"{reg.counter('memory.samples_total').value} "
                  f"windows (+{rss - baseline} bytes); device family "
                  f"attribution {got} bytes -> 0 at baseline")
    finally:
        sampler.close()


def _leg_pressure_shed_and_recover(verbose):
    """Leg 2: a deliberate hog trips the band, POST admissions shed
    503 + retry_after_s, freeing the hog recovers admission."""
    from ..serve.server import ServeApp, ServerThread
    from .memplane import quick_rss

    app = ServeApp(batch_window_s=0.0, max_batch=1,
                   mem_sample_interval_s=0.02)
    with ServerThread(app) as url:
        _get_json(url + "/debug/memory")  # settle the daemon
        rss0 = quick_rss()
        # arm the band relative to the settled process: the hog is
        # 2x the headroom, so the trip and the recovery are both
        # deterministic
        ctl = app.memplane.pressure
        ctl.low_water_bytes = rss0 + HOG_BYTES // 4
        ctl.high_water_bytes = rss0 + HOG_BYTES // 2

        hog = np.ones(HOG_BYTES // 8)  # touched -> resident
        try:
            _wait_until(
                lambda: _get_json(url + "/debug/memory")
                ["pressure"]["state"] == "pressure",
                30.0, "the pressure band to trip")
            code, body = _post_json(url + "/v1/depth", {})
            if code != 503 or "retry_after_s" not in body:
                raise RuntimeError(
                    f"hogged worker admitted a POST: {code} {body}")
        finally:
            del hog
        gc.collect()
        _wait_until(
            lambda: _get_json(url + "/debug/memory")
            ["pressure"]["state"] == "ok",
            60.0, "RSS to recover below the low water mark")
        code, body = _post_json(url + "/v1/depth", {})
        if code == 503:
            raise RuntimeError(
                f"recovered worker still shedding: {code} {body}")
        snap = _get_json(url + "/debug/memory")
        sheds = snap["counters"]["memory.sheds_total"]
        if sheds < 1:
            raise RuntimeError(
                f"memory.sheds_total never incremented: {sheds}")
        if verbose:
            print("memory-smoke: pressure tripped -> 503 with "
                  f"retry_after_s, recovered -> {code} "
                  f"(sheds={sheds})")


def _leg_supervisor_recycle(verbose):
    """Leg 3: a fleet with --mem-recycle-mb below the worker's
    baseline recycles it; the memory_recycle event survives into the
    journal and the real events CLI."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("GOLEFT_TPU_FAULTS", None)
    cap_mb = 64.0  # far below any live worker's baseline
    with tempfile.TemporaryDirectory(prefix="goleft_memsmk_") as d:
        journal = os.path.join(d, "events.jsonl")
        router = subprocess.Popen(
            [sys.executable, "-m", "goleft_tpu", "fleet",
             "--port", "0", "--workers", "1",
             "--poll-interval-s", "0.3", "--down-after", "1",
             "--supervise-interval-s", "0.2",
             "--hang-timeout-s", "10", "--restart-limit", "8",
             "--mem-recycle-mb", str(cap_mb),
             "--events-journal", journal,
             "--worker-args=--no-warmup"],
            stdout=subprocess.PIPE, text=True, env=env)
        try:
            line = router.stdout.readline()
            if "listening on " not in line:
                raise RuntimeError(f"router never announced: {line!r}")
            url = line.rsplit("listening on ", 1)[1].strip()

            def _recycled() -> bool:
                try:
                    m = _get_json(url + "/metrics")
                except Exception:  # noqa: BLE001 — mid-drain 503s
                    return False
                return m["counters"].get(
                    "memory.recycles_total", 0) >= 1
            _wait_until(_recycled, 180.0,
                        "the supervisor to recycle the worker")

            cp = subprocess.run(
                [sys.executable, "-m", "goleft_tpu", "fleet",
                 "events", "--journal", journal,
                 "--type", "memory_recycle", "--json"],
                capture_output=True, text=True, timeout=120)
            if cp.returncode != 0:
                raise RuntimeError(
                    f"fleet events failed rc={cp.returncode}: "
                    f"{cp.stderr[-500:]}")
            doc = json.loads(cp.stdout)
            evs = [e for e in doc.get("events") or []
                   if e.get("type") == "memory_recycle"]
            if not evs:
                raise RuntimeError(
                    f"no memory_recycle event in the journal: {doc}")
            ev = evs[0]
            if ev.get("rss_bytes", 0) <= ev.get("cap_bytes", 1 << 62):
                raise RuntimeError(
                    f"recycle event does not show rss over cap: {ev}")
            if verbose:
                print("memory-smoke: supervisor recycled worker at "
                      f"rss={ev['rss_bytes']} > cap={ev['cap_bytes']} "
                      f"({len(evs)} event(s) via fleet events --json)")
        finally:
            if router.poll() is None:
                router.send_signal(signal.SIGTERM)
                try:
                    router.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    router.kill()
                    router.wait(timeout=10)
            if router.stdout is not None:
                router.stdout.close()


def run_smoke(timeout_s: float = 600.0, verbose: bool = True) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    t0 = time.monotonic()
    _leg_bounded_and_device_baseline(verbose)
    _leg_pressure_shed_and_recover(verbose)
    _leg_supervisor_recycle(verbose)
    if time.monotonic() - t0 > timeout_s:
        raise RuntimeError(
            f"memory-smoke exceeded its {timeout_s:g}s budget")
    if verbose:
        print(f"memory-smoke: PASS ({time.monotonic() - t0:.1f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(run_smoke())
