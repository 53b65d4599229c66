"""Object-store data plane end-to-end: the ``make dataplane-smoke``
body.

The same hermetic cohorts every other smoke builds, staged twice —
once on the local filesystem and once in a loopback
:mod:`~goleft_tpu.io.remote_stub` object store — and driven through
real subprocess tiers, proving ``https://`` inputs are a drop-in for
paths at every layer:

  1. **CLI byte-identity**: ``cohortdepth`` (plain, and with
     ``--prefetch-depth``/``--decode-device`` composing), ``depth``
     and ``indexcov`` produce byte-identical output over stub-remote
     URLs vs local paths.
  2. **fetch fault site**: an injected transient fault
     (``GOLEFT_TPU_FAULTS=fetch:...``) is retried to byte-identical
     output; a PERMANENT failure (404'd object) quarantines only the
     affected sample — the cohort completes degraded with the
     standard exit-3 contract.
  3. **staleness**: the object flipping contents mid-run (new ETag)
     is detected as a stale input and quarantined — never silently
     mixed into the matrix.
  4. **serve parity**: a real serve worker returns byte-identical
     ``matrix_tsv`` for local paths vs URLs (``decode_device``
     composing).
  5. **cache replication failover**: two real fleets with DISTINCT
     ``--shared-cache`` dirs behind a federation with
     ``--cache-sync-interval``; after one warm request the entry
     replicates to the idle fleet, the home fleet is SIGKILLed, and
     the survivor serves the SAME request byte-identically from the
     replicated entry with ``serve_device_passes_total == 0`` —
     failover is cache replay, not recompute.

Run directly::

    python -m goleft_tpu.io.dataplane_smoke
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


def _run(args, env, timeout_s=240.0, expect_rc=0):
    rc = subprocess.run(
        [sys.executable, "-m", "goleft_tpu", *args], env=env,
        timeout=timeout_s, capture_output=True, text=True)
    if expect_rc is not None and rc.returncode != expect_rc:
        raise RuntimeError(
            f"goleft-tpu {' '.join(args[:1])} exited "
            f"{rc.returncode}, want {expect_rc}:\n{rc.stderr}")
    return rc


def _stage(srv, paths: list[str], prefix: str = "") -> list[str]:
    """Upload files into the stub store under their basenames
    (optionally namespaced by ``prefix/``); returns the URLs in the
    same order."""
    urls = []
    for p in paths:
        name = (prefix + "/" if prefix else "") + os.path.basename(p)
        with open(p, "rb") as fh:
            urls.append(srv.put(name, fh.read()))
    return urls


def _leg_cli_identity(d, crams, fai, cram_urls, fai_url, env,
                      verbose):
    base = ["cohortdepth", "--fai", fai, "-w", "500", *crams]
    local = _run(base, env).stdout
    rem = ["cohortdepth", "--fai", fai_url, "-w", "500", *cram_urls]
    if _run(rem, env).stdout != local:
        raise RuntimeError("cohortdepth over URLs != local paths")
    composed = ["cohortdepth", "--fai", fai_url, "-w", "500",
                "--prefetch-depth", "2", "--decode-device",
                *cram_urls]
    if _run(composed, env).stdout != local:
        raise RuntimeError("cohortdepth --prefetch-depth "
                           "--decode-device over URLs != local")
    if verbose:
        rows = local.count("\n") - 1
        print("dataplane-smoke: cohortdepth byte-identical over "
              f"URLs, prefetch+device composing ({rows} windows)")
    return local


def _leg_cli_depth_indexcov(d, bams, fai2, bed, bam_urls, fai2_url,
                            env, verbose):
    pl = os.path.join(d, "dl")
    pr = os.path.join(d, "dr")
    _run(["depth", "--prefix", pl, "-b", bed, "-w", "100", bams[0]],
         env)
    _run(["depth", "--prefix", pr, "-b", bed, "-w", "100",
          bam_urls[0]], env)
    for suffix in (".depth.bed", ".callable.bed"):
        with open(pl + suffix, "rb") as fl, \
                open(pr + suffix, "rb") as fr:
            if fl.read() != fr.read():
                raise RuntimeError(
                    f"depth {suffix} over a URL != local")
    outs = []
    for tag, inputs, f in (("L", bams, fai2),
                           ("R", bam_urls, fai2_url)):
        od = os.path.join(d, tag, "ix")
        os.makedirs(od)
        _run(["indexcov", "-d", od, "--fai", f, "--no-html",
              *inputs], env)
        outs.append(od)
    files = sorted(os.listdir(outs[0]))
    if files != sorted(os.listdir(outs[1])) or not files:
        raise RuntimeError("indexcov output sets differ")
    for name in files:
        with open(os.path.join(outs[0], name), "rb") as fl, \
                open(os.path.join(outs[1], name), "rb") as fr:
            if fl.read() != fr.read():
                raise RuntimeError(
                    f"indexcov {name} over URLs != local")
    if verbose:
        print("dataplane-smoke: depth + indexcov byte-identical "
              f"over URLs ({len(files)} indexcov artifacts)")


def _leg_fetch_faults(srv, crams, local_out, cram_urls, fai_url,
                      env, verbose):
    # transient: one injected failure at the fetch site is retried
    # through the same RetryPolicy every dispatch boundary uses
    fenv = dict(env, GOLEFT_TPU_FAULTS="fetch:after=2:transient")
    rem = ["cohortdepth", "--fai", fai_url, "-w", "500", *cram_urls]
    if _run(rem, fenv).stdout != local_out:
        raise RuntimeError(
            "transient fetch fault not retried to identical bytes")
    # permanent: one object 404s — ONLY that sample quarantines, the
    # cohort completes degraded under the standard exit-3 contract
    victim = os.path.basename(crams[0])
    srv.store.delete(victim)
    try:
        rc = _run(rem, env, expect_rc=3)
    finally:
        with open(crams[0], "rb") as fh:
            srv.store.put(victim, fh.read())
    if "quarantined" not in rc.stderr:
        raise RuntimeError(
            f"exit-3 run carried no quarantine summary: {rc.stderr}")
    if not rc.stdout.startswith("#chrom"):
        raise RuntimeError("degraded cohort wrote no partial matrix")
    for other in crams[1:]:
        sample = os.path.basename(other)[:-5]  # crN.cram -> crN
        if sample not in rc.stdout.splitlines()[0]:
            raise RuntimeError(
                f"healthy sample {sample} missing from the degraded "
                "matrix header")
    if verbose:
        print("dataplane-smoke: transient fetch fault retried to "
              "identical bytes; 404'd object quarantined only its "
              "own sample (exit 3)")


def _leg_stale_detection(srv, crams, cram_urls, fai_url, env,
                         verbose):
    victim = os.path.basename(crams[0])
    with open(crams[0], "rb") as fh:
        original = fh.read()
    # the next request pins the identity (HEAD); the flip lands
    # before the first ranged GET, so the pinned ETag can never match
    # again (the threshold is RELATIVE — earlier legs already counted
    # requests against this name)
    seen = srv.store.request_counts.get(victim, 0)
    srv.store.flip_after(victim, seen + 2, original + b"\x00drifted")
    try:
        rc = _run(["cohortdepth", "--fai", fai_url, "-w", "500",
                   *cram_urls], env, expect_rc=3)
    finally:
        srv.store.put(victim, original)
    blob = (rc.stderr + rc.stdout).lower()
    if "stale" not in blob:
        raise RuntimeError(
            "mid-run ETag drift was not surfaced as a stale input:\n"
            + rc.stderr)
    if verbose:
        print("dataplane-smoke: mid-run ETag drift detected as "
              "stale-input and quarantined — never silently mixed")


def _leg_serve_parity(crams, fai, cram_urls, fai_url, env, local_out,
                      verbose):
    from ..fleet.federation_smoke import _kill, _post, _spawn

    proc = None
    try:
        proc, url = _spawn(["serve", "--port", "0", "--no-warmup"],
                           env)
        code, a = _post(url + "/v1/cohortdepth",
                        {"bams": crams, "fai": fai, "window": 500,
                         "decode_device": True})
        if code != 200:
            raise RuntimeError(f"serve local cohortdepth: {code} {a}")
        code, b = _post(url + "/v1/cohortdepth",
                        {"bams": cram_urls, "fai": fai_url,
                         "window": 500, "decode_device": True})
        if code != 200:
            raise RuntimeError(f"serve URL cohortdepth: {code} {b}")
        if a["matrix_tsv"] != b["matrix_tsv"] \
                or a["matrix_tsv"] != local_out:
            raise RuntimeError(
                "serve matrix over URLs != local paths / CLI bytes")
    finally:
        _kill(proc)
    if verbose:
        print("dataplane-smoke: serve worker byte-identical over "
              "URLs (decode_device composing, == CLI bytes)")


def _prom_counter(prom: str, name: str) -> float:
    for line in prom.splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    return 0.0


def _leg_federation_cache_failover(d, cram_urls, fai_url, env,
                                   local_out, verbose):
    from ..fleet.federation_smoke import (
        _get_json, _kill, _post, _spawn, _wait_until,
    )

    fleets: dict[str, dict] = {}
    fed = None
    try:
        for i in range(2):
            cache_dir = os.path.join(d, f"cache{i}")  # DISTINCT dirs
            proc, url = _spawn(
                ["fleet", "--port", "0", "--workers", "1",
                 "--poll-interval-s", "0.3", "--down-after", "1",
                 "--supervise-interval-s", "0.1",
                 "--shared-cache", cache_dir,
                 "--worker-args=--no-warmup"], env)
            url = url.rstrip("/")
            slots = _get_json(url + "/metrics")["supervisor"]["slots"]
            fleets[url] = {"proc": proc, "cache_dir": cache_dir,
                           "worker_url": slots[0]["url"],
                           "worker_pid": slots[0]["pid"]}
        fed, fed_url = _spawn(
            ["federation", "--port", "0",
             *[a for u in fleets for a in ("--fleet", u)],
             "--poll-interval-s", "0.3", "--down-after", "1",
             "--cache-sync-interval", "0.5"], env)

        def fleets_up():
            try:
                return _get_json(
                    fed_url + "/healthz")["fleets_up"] == 2
            except Exception:  # noqa: BLE001 — 503 while settling
                return False

        _wait_until(fleets_up, 120.0, "both fleets up")
        req = {"bams": cram_urls, "fai": fai_url, "window": 500,
               "tenant": "alice"}
        home_url = _post(fed_url + "/fleet/plan",
                         {"kind": "cohortdepth",
                          **req})[1]["candidates"][0].rstrip("/")
        survivor_url = next(u for u in fleets if u != home_url)
        code, warm = _post(fed_url + "/v1/cohortdepth", req,
                           timeout_s=300.0)
        if code != 200 or warm["matrix_tsv"] != local_out:
            raise RuntimeError(
                f"warm federation request not byte-identical ({code})")

        def replicated():
            try:
                body = _get_json(survivor_url + "/fleet/cache/")
                return len(body["entries"]) >= 1
            except Exception:  # noqa: BLE001 — not yet
                return False

        _wait_until(replicated, 60.0,
                    "cachesync to replicate onto the idle fleet")
        fleets[home_url]["proc"].kill()
        fleets[home_url]["proc"].wait(timeout=30)

        def home_down():
            try:
                return _get_json(
                    fed_url + "/healthz")["fleets_up"] == 1
            except Exception:  # noqa: BLE001 — poll raced the kill
                return False

        _wait_until(home_down, 60.0, "federation to mark the home "
                                     "fleet down")
        code, cold = _post(fed_url + "/v1/cohortdepth", req,
                           timeout_s=300.0)
        if code != 200 or cold["matrix_tsv"] != local_out:
            raise RuntimeError(
                "survivor's failover response not byte-identical "
                f"({code})")
        if not cold.get("cached"):
            raise RuntimeError(
                "failover response was not a replicated-cache hit")
        wreq = urllib.request.Request(
            fleets[survivor_url]["worker_url"]
            + "/metrics?format=prom",
            headers={"Accept": "text/plain"})
        with urllib.request.urlopen(wreq, timeout=30) as r:
            prom = r.read().decode()
        passes = _prom_counter(prom, "serve_device_passes_total")
        if passes != 0:
            raise RuntimeError(
                f"survivor recomputed on the device "
                f"(serve_device_passes_total={passes:g}) despite the "
                "replicated cache")
        fedm = _get_json(fed_url + "/metrics")["counters"]
        if fedm.get("cachesync.entries_replicated_total", 0) < 1:
            raise RuntimeError("cachesync counters never moved")
        if verbose:
            print("dataplane-smoke: home fleet SIGKILLed — survivor "
                  "served byte-identically from the REPLICATED cache "
                  "(0 device passes, "
                  f"{fedm['cachesync.entries_replicated_total']:g} "
                  "entries replicated)")
    finally:
        _kill(fed)
        for rec in fleets.values():
            _kill(rec["proc"])
        for rec in fleets.values():
            # the SIGKILLed fleet's worker is orphaned — reap by pid
            try:
                os.kill(rec["worker_pid"], signal.SIGKILL)
            except (OSError, ProcessLookupError):
                pass


def run_smoke(timeout_s: float = 900.0, verbose: bool = True) -> int:
    """Returns 0 on success; raises on any failed leg."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",     # CI has no accelerator
               # cache replication is authenticated (pushes carry an
               # HMAC keyed by the shared fleet secret); the fleets
               # and the federation all inherit this env
               GOLEFT_TPU_FLEET_SECRET="dataplane-smoke")
    env.pop("GOLEFT_TPU_FAULTS", None)  # hermetic (leg 2 adds it)
    from ..ops.decode_smoke import make_cram_cohort
    from ..resilience.smoke import _make_cohort
    from .remote_stub import StubServer

    t0 = time.monotonic()
    with tempfile.TemporaryDirectory(prefix="goleft_dp_") as d, \
            StubServer() as srv:
        dc = os.path.join(d, "cramset")
        db = os.path.join(d, "bamset")
        os.makedirs(dc)
        os.makedirs(db)
        crams, fai = make_cram_cohort(dc)
        cram_urls = _stage(srv, crams)
        for c in crams:
            _stage(srv, [c + ".crai"])
        fai_url = _stage(srv, [fai])[0]
        bams, fai2, bed = _make_cohort(db, ref_len=20_000)
        bam_urls = _stage(srv, bams, prefix="bamset")
        for b in bams:
            _stage(srv, [b + ".bai"], prefix="bamset")
        fai2_url = _stage(srv, [fai2], prefix="bamset")[0]

        local_out = _leg_cli_identity(d, crams, fai, cram_urls,
                                      fai_url, env, verbose)
        _leg_cli_depth_indexcov(d, bams, fai2, bed, bam_urls,
                                fai2_url, env, verbose)
        _leg_fetch_faults(srv, crams, local_out, cram_urls, fai_url,
                          env, verbose)
        _leg_stale_detection(srv, crams, cram_urls, fai_url, env,
                             verbose)
        _leg_serve_parity(crams, fai, cram_urls, fai_url, env,
                          local_out, verbose)
        _leg_federation_cache_failover(d, cram_urls, fai_url, env,
                                       local_out, verbose)
        if time.monotonic() - t0 > timeout_s:
            raise RuntimeError(
                f"dataplane-smoke exceeded its {timeout_s:g}s budget")
    if verbose:
        print(f"dataplane-smoke: PASS ({time.monotonic() - t0:.1f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(run_smoke())
