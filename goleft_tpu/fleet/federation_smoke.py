"""End-to-end federation chaos: the ``make federation-chaos`` body.

Real subprocess tiers all the way down — a federation router fronting
TWO real ``goleft-tpu fleet`` processes (each a supervised fleet of
one serve worker), because the federation's contracts are precisely
about whole-process failure domains:

  1. **tenant-scoped overload isolation**: a flooding tenant
     (``mallory``, best-effort priority, hammering a fleet-level
     quota) drives its ``federation.tenant.burn_rate.mallory`` gauge
     over the threshold and is SHED at the federation front door
     (429, ``shed: tenant-burn``, honest ``retry_after_s``) — while a
     quiet tenant's (``alice``) concurrent requests ALL land with
     byte-identical bodies. Isolation by contract, not side effect.
  2. **whole-fleet failover**: SIGKILL of the affinity home fleet's
     ROUTER (the fleet's single point of failure) mid-flight yields
     byte-identical 200s through the surviving fleet, within the
     client's retry budget — capacity degrades, availability does
     not.
  3. **half-open rejoin + key migration home**: the killed fleet's
     router is restarted (attach mode, fronting the worker that
     survived it), the federation's poller half-opens it, and the
     next request for its affinity key routes HOME again —
     byte-identically, with the probe/rejoin counters telling the
     story.

Run directly::

    python -m goleft_tpu.fleet.federation_smoke
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
import urllib.error
import urllib.request


def _wait_until(pred, timeout_s: float, what: str,
                interval_s: float = 0.1):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(interval_s)
    raise RuntimeError(f"timed out waiting for {what}")


def _get_json(url: str, timeout_s: float = 30.0):
    req = urllib.request.Request(
        url, headers={"Accept": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout_s) as r:
        return json.loads(r.read().decode())


def _post(url: str, body: dict, timeout_s: float = 120.0):
    """(status, parsed body) — non-2xx included, no retries."""
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json",
                 "Accept": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout_s) as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        raw = e.read()
        try:
            return e.code, json.loads(raw.decode())
        except ValueError:
            return e.code, {}


def _spawn(args: list[str], env: dict) -> tuple:
    """Spawn a goleft-tpu subcommand, return (proc, announced url)."""
    proc = subprocess.Popen(
        [sys.executable, "-m", "goleft_tpu", *args],
        stdout=subprocess.PIPE, text=True, env=env)
    line = ""
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line or "listening on " in line:
            break
    if "listening on " not in line:
        proc.kill()
        proc.wait(timeout=10)
        raise RuntimeError(
            f"{args[0]} never announced (last line {line!r})")
    return proc, line.rsplit("listening on ", 1)[1].strip()


def _kill(proc, sig=signal.SIGTERM, timeout_s: float = 60.0):
    if proc is None:
        return
    if proc.poll() is None:
        proc.send_signal(sig)
        try:
            proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)
    if proc.stdout is not None:
        proc.stdout.close()


def _leg_tenant_shed(fed_url, bam, fai, verbose):
    baseline = _post(fed_url + "/v1/depth",
                     {"bam": bam, "fai": fai, "tenant": "alice"})
    if baseline[0] != 200 or not baseline[1].get("depth_bed"):
        raise RuntimeError(f"baseline depth failed: {baseline}")
    base_bed = baseline[1]["depth_bed"]

    mallory_codes: list[tuple] = []

    def flood():
        for _ in range(14):
            code, body = _post(
                fed_url + "/v1/depth",
                {"bam": bam, "fai": fai, "tenant": "mallory",
                 "priority": 1}, timeout_s=120.0)
            mallory_codes.append((code, body))

    t = threading.Thread(target=flood)
    t.start()
    alice_beds = []
    for _ in range(3):
        code, body = _post(fed_url + "/v1/depth",
                           {"bam": bam, "fai": fai,
                            "tenant": "alice"})
        if code != 200:
            raise RuntimeError(
                f"quiet tenant alice got {code} during the flood: "
                f"{body}")
        alice_beds.append(body.get("depth_bed"))
    t.join(timeout=300)
    if any(bed != base_bed for bed in alice_beds):
        raise RuntimeError(
            "quiet tenant's responses were not byte-identical "
            "during the flood")
    sheds = [b for c, b in mallory_codes
             if c == 429 and b.get("shed") == "tenant-burn"]
    if not sheds:
        raise RuntimeError(
            "flooding tenant was never federation-shed: "
            f"{[(c, b.get('error', '')[:40]) for c, b in mallory_codes]}")
    if any(not isinstance(b.get("retry_after_s"), (int, float))
           or b["retry_after_s"] <= 0 for b in sheds):
        raise RuntimeError("a tenant shed carried no honest "
                           "retry_after_s")
    m = _get_json(fed_url + "/metrics")
    burn = m["gauges"].get("federation.tenant.burn_rate.mallory", 0)
    if burn <= 2.0:
        raise RuntimeError(
            f"mallory burn gauge {burn} not breaching in JSON")
    if m["counters"].get(
            "federation.tenant_shed_total.mallory", 0) < 1:
        raise RuntimeError("tenant shed counter missing")
    if "federation.tenant_shed_total.alice" in m["counters"]:
        raise RuntimeError("quiet tenant was shed")
    # the same gauge through the Prometheus encoding
    req = urllib.request.Request(
        fed_url + "/metrics?format=prom",
        headers={"Accept": "text/plain"})
    with urllib.request.urlopen(req, timeout=30) as r:
        prom = r.read().decode()
    if "federation_tenant_burn_rate_mallory" not in prom:
        raise RuntimeError("burn gauge missing from prom encoding")
    if verbose:
        print("federation-chaos: flooding mallory shed at the "
              f"federation ({len(sheds)} sheds, burn {burn:.1f}) "
              "while alice's 3 concurrent requests all landed "
              "byte-identical, gauges in both encodings")
    return base_bed


def _leg_fleet_failover(fed_url, fleets, bam, fai, base_bed,
                        verbose):
    plan = _post(fed_url + "/fleet/plan",
                 {"kind": "depth", "bam": bam, "fai": fai})[1]
    home_url = plan["candidates"][0]
    home = fleets[home_url]

    results: list = []

    def inflight():
        results.append(_post(fed_url + "/v1/depth",
                             {"bam": bam, "fai": fai,
                              "tenant": "alice"}, timeout_s=180.0))

    t = threading.Thread(target=inflight)
    t.start()
    time.sleep(0.05)
    # SIGKILL the ENTIRE fleet's router — the fleet tier's single
    # point of failure (its supervisor and worker die with... no:
    # the worker survives as an orphan; the fleet as a SERVING unit
    # is gone, which is exactly the failure domain under test)
    home["proc"].kill()
    home["proc"].wait(timeout=30)
    t.join(timeout=300)
    code, body = results[0]
    if code != 200 or body.get("depth_bed") != base_bed:
        raise RuntimeError(
            f"in-flight request over the SIGKILL was not a "
            f"byte-identical 200 (code {code})")
    # and a fresh request after the kill fails over identically
    code, body = _post(fed_url + "/v1/depth",
                       {"bam": bam, "fai": fai, "tenant": "alice"},
                       timeout_s=180.0)
    if code != 200 or body.get("depth_bed") != base_bed:
        raise RuntimeError(
            f"post-kill request not byte-identical 200 ({code})")
    m = _get_json(fed_url + "/metrics")
    if m["counters"].get("federation.fleet_down_total", 0) < 1:
        raise RuntimeError("fleet_down_total never counted")
    h = _get_json(fed_url + "/healthz")
    if h["fleets_up"] >= h["fleets"]:
        raise RuntimeError("healthz does not report the lost fleet")
    if verbose:
        print("federation-chaos: home fleet router SIGKILLed "
              "mid-flight -> byte-identical 200s via the surviving "
              f"fleet (fleets_up={h['fleets_up']}/{h['fleets']})")
    return home_url


def _leg_rejoin_routes_home(fed_url, fleets, home_url, bam, fai,
                            base_bed, env, verbose):
    home = fleets[home_url]
    port = home_url.rsplit(":", 1)[-1]
    # restart the fleet ROUTER on its old port, attaching the worker
    # that survived the router's death (attach mode: the healed
    # fleet fronts the same warm worker)
    proc, url = _spawn(["fleet", "--port", port,
                        "--worker", home["worker_url"],
                        "--poll-interval-s", "0.3",
                        "--down-after", "1",
                        *home["quota_args"]], env)
    if url.rstrip("/") != home_url:
        raise RuntimeError(f"restarted fleet landed at {url}, "
                           f"want {home_url}")
    fleets[home_url]["proc"] = proc
    rejoins0 = _get_json(fed_url + "/metrics")["counters"].get(
        "federation.fleet_rejoin_total", 0)

    def half_open():
        m = _get_json(fed_url + "/metrics")
        return m["fleets"][home_url]["state"] in ("probe", "up")

    _wait_until(half_open, 60.0, "federation to half-open the "
                                 "healed fleet")
    # the next request for the fleet's affinity key is the probe —
    # and it must route HOME, byte-identically
    code, body = _post(fed_url + "/v1/depth",
                       {"bam": bam, "fai": fai, "tenant": "alice"},
                       timeout_s=180.0)
    if code != 200 or body.get("depth_bed") != base_bed:
        raise RuntimeError(
            f"post-rejoin request not byte-identical 200 ({code})")
    m = _get_json(fed_url + "/metrics")
    if m["fleets"][home_url]["state"] != "up":
        raise RuntimeError(
            f"healed fleet not UP after the probe: "
            f"{m['fleets'][home_url]}")
    if m["counters"].get("federation.fleet_rejoin_total",
                         0) <= rejoins0:
        raise RuntimeError("rejoin never counted")
    routed = m["counters"].get(
        f"federation.routed_total.{port}.depth", 0)
    if routed < 1:
        raise RuntimeError(
            f"request did not route home after rejoin "
            f"(routed_total.{port}.depth={routed})")
    plan = _post(fed_url + "/fleet/plan",
                 {"kind": "depth", "bam": bam, "fai": fai})[1]
    if plan["candidates"][0] != home_url:
        raise RuntimeError("affinity plan no longer homes the key")
    if verbose:
        print("federation-chaos: healed fleet half-open probed, "
              "rejoined, and its affinity key routed home "
              "byte-identically")


def run_smoke(timeout_s: float = 600.0, verbose: bool = True) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    env = dict(os.environ,
               JAX_PLATFORMS="cpu")     # CI has no accelerator
    env.pop("GOLEFT_TPU_FAULTS", None)  # hermetic
    from ..resilience.smoke import _make_cohort

    t0 = time.monotonic()
    quota_args = ["--quota", "mallory=1:1"]
    fleets: dict[str, dict] = {}
    fed = None
    with tempfile.TemporaryDirectory(prefix="goleft_fedc_") as d:
        bams, fai, _bed = _make_cohort(d, ref_len=20_000)
        bam = bams[0]
        try:
            for i in range(2):
                proc, url = _spawn(
                    ["fleet", "--port", "0", "--workers", "1",
                     "--poll-interval-s", "0.3", "--down-after", "1",
                     "--supervise-interval-s", "0.1",
                     *quota_args, "--worker-args=--no-warmup"], env)
                url = url.rstrip("/")
                slots = _get_json(url + "/metrics")["supervisor"][
                    "slots"]
                fleets[url] = {"proc": proc,
                               "worker_url": slots[0]["url"],
                               "worker_pid": slots[0]["pid"],
                               "quota_args": quota_args}
                if verbose:
                    print(f"federation-chaos: fleet {i} at {url} "
                          f"(worker {slots[0]['url']})")
            fed, fed_url = _spawn(
                ["federation", "--port", "0",
                 *[a for u in fleets for a in ("--fleet", u)],
                 "--poll-interval-s", "0.3", "--down-after", "1",
                 "--tenant-burn-threshold", "2.0",
                 "--tenant-shed-min", "4"], env)

            def fleets_up():
                try:
                    return _get_json(fed_url + "/healthz")[
                        "fleets_up"] == 2
                except Exception:  # noqa: BLE001 — 503 while down
                    return False

            _wait_until(fleets_up, 120.0, "both fleets up")
            base_bed = _leg_tenant_shed(fed_url, bam, fai, verbose)
            home_url = _leg_fleet_failover(fed_url, fleets, bam,
                                           fai, base_bed, verbose)
            _leg_rejoin_routes_home(fed_url, fleets, home_url, bam,
                                    fai, base_bed, env, verbose)
        finally:
            _kill(fed)
            for rec in fleets.values():
                _kill(rec["proc"])
            # the failover leg's SIGKILL orphans that fleet's worker
            # (the restarted router attaches but does not own it) —
            # reap by pid so the smoke leaves nothing behind
            for rec in fleets.values():
                try:
                    os.kill(rec["worker_pid"], signal.SIGKILL)
                except (OSError, ProcessLookupError):
                    pass
        if time.monotonic() - t0 > timeout_s:
            raise RuntimeError(
                f"federation-chaos exceeded its {timeout_s:g}s "
                "budget")
    if verbose:
        print(f"federation-chaos: PASS "
              f"({time.monotonic() - t0:.1f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(run_smoke())
