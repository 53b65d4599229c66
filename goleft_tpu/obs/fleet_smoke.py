"""End-to-end fleet observability smoke: the ``make fleet-obs-smoke`` body.

Real subprocess daemons all the way down — one ``goleft-tpu fleet``
router process SUPERVISING two real serve workers (three OS
processes), because the whole point of the fleet plane is evidence
that crosses process boundaries:

  1. **one request, one stitched trace**: a depth request through the
     router with a client-minted ``x-goleft-trace`` id yields ONE
     stitched tree from ``GET /fleet/trace/<id>`` containing spans
     from >= 2 processes — the router's ``fleet.request``/
     ``fleet.forward`` spans parenting the worker's ``request.depth``
     → ``plan.step.depth`` → ``batch.depth`` →
     ``serve.depth.dispatch`` chain — and the Perfetto export carries
     distinct process tracks. The ``goleft-tpu trace`` CLI renders the
     same tree (subprocess, proving registration).
  2. **fleet counters are worker sums**: after a burst of requests,
     ``/fleet/metrics`` counters equal the arithmetic sum of the live
     workers' own ``/metrics`` counters, in JSON and in the
     Prometheus encoding.
  3. **lifecycle events are durable and queryable**: a worker
     SIGKILLed mid-fleet produces death → backoff → restart events
     visible in ``goleft-tpu fleet events --json`` (the fsync'd
     events.jsonl) and in the router ``/metrics`` ``fleet.events``
     block, while the fleet heals itself.

Run directly::

    python -m goleft_tpu.obs.fleet_smoke
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request


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


def _walk(node):
    yield node
    for c in node.get("children", ()):
        yield from _walk(c)


def _leg_stitched_trace(router_url, bam, fai, d, verbose):
    from ..serve.client import ServeClient

    client = ServeClient(router_url, timeout_s=120.0, retries=2,
                         retry_cap_s=2.0, trace=True)
    r = client.depth(bam, fai=fai, window=200)
    if not r.get("depth_bed"):
        raise RuntimeError("routed depth request returned no bed")
    tid = client.last_trace_id
    if not tid:
        raise RuntimeError("client minted no trace id")
    doc = client.fleet_trace(tid)
    if doc["trace_id"] != tid:
        raise RuntimeError("stitched trace id mismatch")
    if len(doc["processes"]) < 2:
        raise RuntimeError(
            f"stitched trace spans {len(doc['processes'])} "
            f"process(es), want >= 2: {sorted(doc['processes'])}")
    names = {n["name"]: n.get("process") for n in _walk(doc["tree"])}
    for want in ("fleet.request.depth", "fleet.forward.depth",
                 "request.depth", "plan.step.depth", "batch.depth",
                 "serve.depth.dispatch"):
        if want not in names:
            raise RuntimeError(
                f"stitched trace is missing the {want!r} span "
                f"(has: {sorted(names)})")
    if not any(str(p).startswith("worker:")
               for p in names.values()):
        raise RuntimeError("no span attributed to a worker process")
    # graft shape: the worker request tree sits UNDER the router's
    # forward span, and the device dispatch under the batch tree
    tree = doc["tree"]
    fwd = next(n for n in _walk(tree)
               if n["name"] == "fleet.forward.depth")
    if not any(c["name"] == "request.depth"
               for c in fwd["children"]):
        raise RuntimeError(
            "worker request tree not grafted under the router "
            "forward span")
    # Perfetto export: distinct process tracks, loadable shape
    perf = doc["perfetto"]
    procs = [e["args"]["name"] for e in perf["traceEvents"]
             if e.get("ph") == "M"
             and e.get("name") == "process_name"]
    if len(procs) < 2:
        raise RuntimeError(
            f"Perfetto export has {len(procs)} process track(s), "
            "want >= 2")
    if not any(e.get("ph") == "X" for e in perf["traceEvents"]):
        raise RuntimeError("Perfetto export has no complete events")
    # the CLI renders the same tree (subprocess: registration proof)
    out = os.path.join(d, "trace.perfetto.json")
    cp = subprocess.run(
        [sys.executable, "-m", "goleft_tpu", "trace", tid,
         "--router", router_url, "--perfetto", out],
        capture_output=True, text=True, timeout=120)
    if cp.returncode != 0:
        raise RuntimeError(
            f"goleft-tpu trace failed rc={cp.returncode}: "
            f"{cp.stderr[-500:]}")
    if "serve.depth.dispatch" not in cp.stdout \
            or "fleet.forward.depth" not in cp.stdout:
        raise RuntimeError("goleft-tpu trace output missing spans")
    with open(out) as fh:
        if not json.load(fh).get("traceEvents"):
            raise RuntimeError("--perfetto wrote an empty export")
    if verbose:
        print("fleet-obs-smoke: one request -> ONE stitched trace "
              f"across {len(doc['processes'])} processes (router "
              "forward -> worker request -> plan step -> device "
              "dispatch), Perfetto tracks distinct, CLI renders it")
    return tid


def _leg_counter_rollup(router_url, bams, fai, poll_s, verbose):
    from ..serve.client import ServeClient

    client = ServeClient(router_url, timeout_s=120.0, retries=2,
                         retry_cap_s=2.0)
    for i, bam in enumerate(bams):
        client.depth(bam, fai=fai, window=190 + i)
    worker_urls = sorted(_get_json(router_url + "/metrics")
                         ["workers"])
    if len(worker_urls) < 2:
        raise RuntimeError(f"fleet has {len(worker_urls)} worker(s)")
    # let every worker's NEXT jittered scrape land
    time.sleep(2 * poll_s + 0.5)

    def sums_match():
        fleet = _get_json(router_url + "/fleet/metrics")
        per = [_get_json(u + "/metrics") for u in worker_urls]
        want = sum(p["counters"].get("requests_total.depth", 0)
                   for p in per)
        got = fleet["counters"].get("requests_total.depth", 0)
        return want > 0 and got == want, want, got

    _wait_until(lambda: sums_match()[0], 30.0,
                "fleet counters to equal the worker sum")
    _ok, want, _got = sums_match()
    # same number through the Prometheus encoding
    req = urllib.request.Request(
        router_url + "/fleet/metrics?format=prom",
        headers={"Accept": "text/plain"})
    with urllib.request.urlopen(req, timeout=30) as r:
        prom = r.read().decode()
    needle = f"fleet_worker_requests_total_depth {want}"
    if needle not in prom:
        raise RuntimeError(
            f"prometheus rollup missing {needle!r}")
    if "fleet_slo_burn_rate" not in prom:
        raise RuntimeError("prometheus rollup missing burn gauges")
    if verbose:
        print("fleet-obs-smoke: /fleet/metrics counters == "
              f"sum over {len(worker_urls)} live workers "
              f"(requests_total.depth = {want}), both encodings")


def _leg_events_journal(router_url, journal, verbose):
    snap = _get_json(router_url + "/metrics")
    slots = snap["supervisor"]["slots"]
    victim = next(s for s in slots if s["state"] == "healthy")
    os.kill(victim["pid"], signal.SIGKILL)

    def restarted():
        m = _get_json(router_url + "/metrics")
        return m["counters"].get("fleet.restarts_total", 0) >= 1 \
            and m["supervisor"]["capacity"] >= 2
    _wait_until(restarted, 180.0, "supervisor to heal the SIGKILL")
    # the events CLI replays the fsync'd journal (subprocess)
    cp = subprocess.run(
        [sys.executable, "-m", "goleft_tpu", "fleet", "events",
         "--journal", journal, "--json"],
        capture_output=True, text=True, timeout=60)
    if cp.returncode != 0:
        raise RuntimeError(
            f"fleet events failed rc={cp.returncode}: "
            f"{cp.stderr[-500:]}")
    doc = json.loads(cp.stdout)
    if doc["schema"] != "goleft-tpu.fleet-events/1":
        raise RuntimeError("fleet events --json schema drifted")
    types = [e["type"] for e in doc["events"]]
    for want in ("spawn", "death", "backoff", "restart"):
        if want not in types:
            raise RuntimeError(
                f"events journal missing {want!r} (has {types})")
    if not types.index("death") < types.index("restart"):
        raise RuntimeError("event order broken (death !< restart)")
    death = next(e for e in doc["events"] if e["type"] == "death")
    if death.get("slot") != victim["index"] \
            or death.get("pid") != victim["pid"]:
        raise RuntimeError("death event lost slot/pid identity")
    # and the same story in the router /metrics fleet.events block
    m = _get_json(router_url + "/metrics")
    block = m.get("fleet.events") or {}
    recent = [e["type"] for e in block.get("recent", [])]
    if "restart" not in recent:
        raise RuntimeError(
            f"/metrics fleet.events block missing restart: {recent}")
    if m["counters"].get("fleet.events_total.death", 0) < 1:
        raise RuntimeError("fleet.events_total.death not counted")
    if verbose:
        print("fleet-obs-smoke: SIGKILLed worker -> death/backoff/"
              "restart replayable from events.jsonl (fleet events "
              "--json schema-stable) and visible in /metrics")


def run_smoke(timeout_s: float = 600.0, verbose: bool = True) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    env = dict(os.environ,
               JAX_PLATFORMS="cpu")     # CI has no accelerator
    env.pop("GOLEFT_TPU_FAULTS", None)  # hermetic
    from ..resilience.smoke import _make_cohort

    t0 = time.monotonic()
    poll_s = 0.3
    with tempfile.TemporaryDirectory(prefix="goleft_fobs_") as d:
        bams, fai, _bed = _make_cohort(d, ref_len=20_000)
        journal = os.path.join(d, "events.jsonl")
        router = subprocess.Popen(
            [sys.executable, "-m", "goleft_tpu", "fleet",
             "--port", "0", "--workers", "2",
             "--events-journal", journal,
             "--poll-interval-s", str(poll_s),
             "--down-after", "1",
             "--supervise-interval-s", "0.1",
             "--hang-timeout-s", "2", "--restart-limit", "8",
             "--worker-args=--no-warmup"],
            stdout=subprocess.PIPE, text=True, env=env)
        try:
            line = router.stdout.readline()
            if "listening on " not in line:
                raise RuntimeError(
                    f"router never announced: {line!r}")
            url = line.rsplit("listening on ", 1)[1].strip()

            def _healthy() -> int:
                try:
                    return _get_json(url + "/healthz").get(
                        "healthy", 0)
                except Exception:  # noqa: BLE001 — 503 while degraded
                    return -1

            _wait_until(lambda: _healthy() == 2, 120.0,
                        "both workers healthy")
            _leg_stitched_trace(url, bams[0], fai, d, verbose)
            _leg_counter_rollup(url, bams, fai, poll_s, verbose)
            _leg_events_journal(url, journal, verbose)
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
        if time.monotonic() - t0 > timeout_s:
            raise RuntimeError(
                f"fleet-obs-smoke exceeded its {timeout_s:g}s budget")
    if verbose:
        print(f"fleet-obs-smoke: PASS "
              f"({time.monotonic() - t0:.1f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(run_smoke())
