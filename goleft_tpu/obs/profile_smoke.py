"""End-to-end compile-observatory + profiler smoke: ``make profile-smoke``.

Real subprocess daemons — one ``goleft-tpu fleet`` router supervising
one real serve worker started with ``--profile-hz 50`` — because the
whole point of PR 18 is that "where did the time go" survives process
boundaries:

  1. **the profiler sees real work**: while traced depth requests
     flow, ``GET /fleet/profile?seconds=N`` returns a non-empty merged
     profile whose stacks include a ``goleft_tpu`` frame (the worker
     sampled its own serving threads and the router merged the
     window).
  2. **the compile observatory caught the cold dispatch**: the
     worker's ``GET /debug/compiles`` carries >= 1 depth-family
     signature with a compile tally (the worker runs ``--no-warmup``,
     so the first request's dispatch IS the cache miss).
  3. **the warmup manifest round-trips through the real CLI**:
     ``goleft-tpu warmup export`` (subprocess) writes a manifest that
     ``validate_warmup_manifest`` accepts, whose top signature is the
     depth family the run actually hammered.
  4. **the manifest predicts the restart miss**: the sole worker is
     SIGKILLed, the supervisor restarts it, and the fresh worker's
     ``/debug/compiles`` shows NO depth compile for the exported top
     signature — exactly the cold start a prewarmer would spend the
     manifest preventing (this leg is the control for leg 5).
  5. **the prewarmer prevents it**: a second fleet starts with
     ``--warmup <manifest>`` forwarded to its worker; before ANY
     request the worker's ``/debug/compiles`` already holds the top
     signature compiled (trigger ``warmstart``), and after replaying
     the same depth traffic its compile tally has NOT grown while its
     hits have — the restarted-worker cold miss of leg 4, eliminated.

Run directly::

    python -m goleft_tpu.obs.profile_smoke
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
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


def _worker_urls(router_url: str) -> list[str]:
    return sorted(_get_json(router_url + "/metrics")["workers"])


def _leg_profile_window(router_url, bam, fai, verbose):
    from ..serve.client import ServeClient

    client = ServeClient(router_url, timeout_s=120.0, retries=2,
                         retry_cap_s=2.0, trace=True)
    # first (cold) request compiles the depth program on the worker
    r = client.depth(bam, fai=fai, window=200)
    if not r.get("depth_bed"):
        raise RuntimeError("routed depth request returned no bed")

    # keep the worker busy while the profile window is open
    stop = threading.Event()

    def hammer():
        i = 0
        while not stop.is_set():
            i += 1
            try:
                client.depth(bam, fai=fai, window=200 + (i % 3))
            except Exception:  # noqa: BLE001 — load, not correctness
                if stop.is_set():
                    return
                time.sleep(0.1)

    t = threading.Thread(target=hammer, name="smoke-hammer")
    t.start()
    try:
        doc = _get_json(router_url + "/fleet/profile?seconds=2",
                        timeout_s=60.0)
        # the CLI renders the same merged window as flamegraph
        # collapsed format (subprocess: proves registration too)
        cp = subprocess.run(
            [sys.executable, "-m", "goleft_tpu", "profile",
             "--router", router_url, "--seconds", "1",
             "--collapsed", "-"],
            capture_output=True, text=True, timeout=120)
    finally:
        stop.set()
        t.join(timeout=60)
    if cp.returncode != 0:
        raise RuntimeError(
            f"goleft-tpu profile failed rc={cp.returncode}: "
            f"{cp.stderr[-500:]}")
    lines = [ln for ln in cp.stdout.splitlines() if ln]
    if not lines or not all(
            ln.rsplit(" ", 1)[-1].isdigit() for ln in lines):
        raise RuntimeError(
            "profile --collapsed output is not 'stack count' lines: "
            f"{lines[:3]}")
    if doc.get("schema") != "goleft-tpu.profile/1":
        raise RuntimeError(f"profile schema drifted: {doc.get('schema')!r}")
    if not doc.get("enabled"):
        raise RuntimeError(
            "--profile-hz 50 worker reported profiling disabled")
    if doc.get("samples_total", 0) < 1 or not doc.get("stacks"):
        raise RuntimeError(
            f"merged /fleet/profile window is empty: "
            f"samples={doc.get('samples_total')} "
            f"stacks={len(doc.get('stacks') or {})}")
    if not any("goleft_tpu" in s for s in doc["stacks"]):
        raise RuntimeError(
            "no goleft_tpu frame in the merged profile stacks")
    per = doc.get("per_worker") or {}
    if not any(w.get("samples_total", 0) > 0 for w in per.values()
               if isinstance(w, dict)):
        raise RuntimeError(f"per_worker attribution empty: {per}")
    if verbose:
        print("profile-smoke: /fleet/profile merged "
              f"{doc['samples_total']} samples over "
              f"{len(doc['stacks'])} stacks (goleft_tpu frames "
              "present) while depth requests flowed")


def _leg_compile_observatory(router_url, verbose):
    (worker_url,) = _worker_urls(router_url)
    doc = _get_json(worker_url + "/debug/compiles")
    if doc.get("schema") != "goleft-tpu.warmup-manifest/1":
        raise RuntimeError(
            f"/debug/compiles schema drifted: {doc.get('schema')!r}")
    depth = [s for s in doc.get("signatures") or []
             if s["family"] == "depth" and s["compiles"] >= 1]
    if not depth:
        raise RuntimeError(
            "no depth-family compile in /debug/compiles after a cold "
            f"request (families: "
            f"{sorted({s['family'] for s in doc.get('signatures') or []})})")
    if doc.get("compiles_total", 0) < 1:
        raise RuntimeError("compiles_total never incremented")
    if not any(e.get("family") == "depth"
               for e in doc.get("events") or []):
        raise RuntimeError("no depth CompileEvent in the event ring")
    if verbose:
        print("profile-smoke: /debug/compiles shows "
              f"{len(depth)} depth-family signature(s), "
              f"compiles_total={doc['compiles_total']}")
    return doc


def _leg_warmup_export(router_url, d, verbose):
    from .compiles import load_warmup_manifest

    (worker_url,) = _worker_urls(router_url)
    out = os.path.join(d, "warmup-manifest.json")
    cp = subprocess.run(
        [sys.executable, "-m", "goleft_tpu", "warmup", "export",
         "--url", worker_url, "--out", out],
        capture_output=True, text=True, timeout=120)
    if cp.returncode != 0:
        raise RuntimeError(
            f"warmup export failed rc={cp.returncode}: "
            f"{cp.stderr[-500:]}")
    manifest = load_warmup_manifest(out)  # validates or raises
    if not manifest["signatures"]:
        raise RuntimeError("exported manifest has no signatures")
    top = manifest["signatures"][0]
    # the run's hot bucket IS the top-ranked signature
    if top["family"] != "depth" or top["compiles"] < 1:
        raise RuntimeError(
            f"top manifest signature is not the hot depth bucket: "
            f"{top}")
    if verbose:
        print("profile-smoke: warmup export wrote a valid manifest, "
              f"top signature depth/{top['signature']} "
              f"(hits={top['hits']}, "
              f"compile_seconds={top['compile_seconds']:.2f})")
    return top


def _leg_restart_would_miss(router_url, top, verbose):
    snap = _get_json(router_url + "/metrics")
    victim = next(s for s in snap["supervisor"]["slots"]
                  if s["state"] == "healthy")
    os.kill(victim["pid"], signal.SIGKILL)

    def healed():
        try:
            m = _get_json(router_url + "/metrics")
        except Exception:  # noqa: BLE001 — router mid-heal
            return False
        return m["counters"].get("fleet.restarts_total", 0) >= 1 \
            and m["supervisor"]["capacity"] >= 1
    _wait_until(healed, 180.0, "supervisor to restart the worker")
    (worker_url,) = _worker_urls(router_url)

    def fresh_doc():
        try:
            return _get_json(worker_url + "/debug/compiles")
        except Exception:  # noqa: BLE001 — worker still warming
            return None
    _wait_until(lambda: fresh_doc() is not None, 60.0,
                "restarted worker /debug/compiles")
    doc = fresh_doc()
    hits = [s for s in doc.get("signatures") or []
            if s["family"] == top["family"]
            and s["signature"] == top["signature"]
            and s["compiles"] >= 1]
    if hits:
        raise RuntimeError(
            "restarted worker already holds the exported top "
            f"signature — the cold-miss prediction is vacuous: {hits}")
    if verbose:
        print("profile-smoke: restarted worker has no compile for "
              f"{top['family']}/{top['signature']} — the exported "
              "manifest predicts exactly this cold miss")


def _find_sig(doc: dict, top: dict) -> dict | None:
    for s in doc.get("signatures") or []:
        if s["family"] == top["family"] \
                and s["signature"] == top["signature"]:
            return s
    return None


def _leg_prewarm_no_cold_miss(manifest_path, top, bam, fai, env,
                              verbose):
    """A fresh fleet started with --warmup holds the top signature
    compiled BEFORE any request, and real traffic then hits it warm
    (compiles flat, hits growing) — leg 4's cold miss, eliminated."""
    from ..serve.client import ServeClient

    router = subprocess.Popen(
        [sys.executable, "-m", "goleft_tpu", "fleet",
         "--port", "0", "--workers", "1",
         "--poll-interval-s", "0.3", "--down-after", "1",
         "--supervise-interval-s", "0.1",
         "--hang-timeout-s", "5", "--restart-limit", "8",
         "--warmup", manifest_path,
         "--worker-args=--no-warmup"],
        stdout=subprocess.PIPE, text=True, env=env)
    try:
        line = router.stdout.readline()
        if "listening on " not in line:
            raise RuntimeError(
                f"prewarm router never announced: {line!r}")
        url = line.rsplit("listening on ", 1)[1].strip()

        def _healthy() -> int:
            try:
                return _get_json(url + "/healthz").get("healthy", 0)
            except Exception:  # noqa: BLE001 — 503 while degraded
                return -1

        _wait_until(lambda: _healthy() == 1, 180.0,
                    "the prewarmed worker healthy")
        (worker_url,) = _worker_urls(url)
        before = _find_sig(
            _get_json(worker_url + "/debug/compiles"), top)
        # the whole point: compiled at startup, before ANY request
        if before is None or before["compiles"] < 1:
            raise RuntimeError(
                "prewarmed worker does not hold the top signature "
                f"before traffic: {before} (want "
                f"{top['family']}/{top['signature']} compiled)")
        client = ServeClient(url, timeout_s=120.0, retries=2,
                             retry_cap_s=2.0)
        # replay the exact traffic shape that minted the signature
        for w in (200, 201, 202):
            r = client.depth(bam, fai=fai, window=w)
            if not r.get("depth_bed"):
                raise RuntimeError(
                    "prewarmed depth request returned no bed")
        after = _find_sig(
            _get_json(worker_url + "/debug/compiles"), top)
        if after["compiles"] != before["compiles"]:
            raise RuntimeError(
                "prewarmed worker COLD-MISSED the top signature: "
                f"compiles {before['compiles']} -> "
                f"{after['compiles']}")
        if after["hits"] <= before["hits"]:
            raise RuntimeError(
                "replayed traffic never hit the prewarmed "
                f"signature (hits {before['hits']} -> "
                f"{after['hits']}) — the no-cold-miss assertion "
                "would be vacuous")
        if verbose:
            print("profile-smoke: --warmup worker held "
                  f"{top['family']}/{top['signature']} compiled "
                  "before any request and served "
                  f"{after['hits'] - before['hits']} warm hit(s) "
                  "with zero new compiles — the leg-4 cold miss, "
                  "eliminated")
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
    env = dict(os.environ,
               JAX_PLATFORMS="cpu")     # CI has no accelerator
    env.pop("GOLEFT_TPU_FAULTS", None)  # hermetic
    from ..resilience.smoke import _make_cohort

    t0 = time.monotonic()
    with tempfile.TemporaryDirectory(prefix="goleft_prof_") as d:
        bams, fai, _bed = _make_cohort(d, ref_len=20_000)
        router = subprocess.Popen(
            [sys.executable, "-m", "goleft_tpu", "fleet",
             "--port", "0", "--workers", "1",
             "--poll-interval-s", "0.3", "--down-after", "1",
             "--supervise-interval-s", "0.1",
             "--hang-timeout-s", "5", "--restart-limit", "8",
             "--worker-args=--no-warmup --profile-hz 50"],
            stdout=subprocess.PIPE, text=True, env=env)
        try:
            line = router.stdout.readline()
            if "listening on " not in line:
                raise RuntimeError(f"router never announced: {line!r}")
            url = line.rsplit("listening on ", 1)[1].strip()

            def _healthy() -> int:
                try:
                    return _get_json(url + "/healthz").get(
                        "healthy", 0)
                except Exception:  # noqa: BLE001 — 503 while degraded
                    return -1

            _wait_until(lambda: _healthy() == 1, 120.0,
                        "the worker healthy")
            _leg_profile_window(url, bams[0], fai, verbose)
            _leg_compile_observatory(url, verbose)
            top = _leg_warmup_export(url, d, verbose)
            _leg_restart_would_miss(url, top, verbose)
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
        # leg 5 runs on its own fleet (started WITH --warmup), after
        # the control fleet is fully torn down
        _leg_prewarm_no_cold_miss(
            os.path.join(d, "warmup-manifest.json"), top, bams[0],
            fai, env, verbose)
        if time.monotonic() - t0 > timeout_s:
            raise RuntimeError(
                f"profile-smoke exceeded its {timeout_s:g}s budget")
    if verbose:
        print(f"profile-smoke: PASS ({time.monotonic() - t0:.1f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(run_smoke())
