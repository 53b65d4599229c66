"""End-to-end smoke for the serve daemon: the `make serve-smoke` body.

Spawns a REAL ``goleft-tpu serve`` subprocess on an ephemeral port
(scraping the printed listen line), posts one depth request through
the client, verifies the response carries output, checks the
observability surface (the /metrics SLO block + Prometheus encoding,
the flight recorder at /debug/flight, a SIGUSR1 flight dump that
round-trips through ``json.load``), sends SIGTERM, and asserts a
clean drain (exit 0). Run directly::

    python -m goleft_tpu.serve.smoke

Fabricates its own fixture (the tests' hermetic-BAM approach); the
child is pinned to the host platform with the probe skipped so the
smoke passes on accelerator-less CI in seconds, not after a probe
timeout.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import tempfile
import time


def _make_fixture(d: str, n_reads: int = 400,
                  ref_len: int = 20_000) -> tuple[str, str]:
    """(bam, fai): a tiny coordinate-sorted BAM + matching .fai."""
    import numpy as np

    from ..io.bai import build_bai, write_bai
    from ..io.bam import BamWriter

    rng = np.random.default_rng(7)
    starts = np.sort(rng.integers(0, ref_len - 100, size=n_reads))
    bam = os.path.join(d, "smoke.bam")
    with open(bam, "wb") as fh:
        with BamWriter(
            fh, "@HD\tVN:1.6\tSO:coordinate\n@SQ\tSN:chr1\tLN:"
            f"{ref_len}\n@RG\tID:r\tSM:smoke\n", ["chr1"], [ref_len],
            level=1,
        ) as w:
            for i, s in enumerate(starts):
                w.write_record(0, int(s), [(100, 0)], mapq=60,
                               name=f"r{i}")
    write_bai(build_bai(bam), bam + ".bai")
    fai = os.path.join(d, "ref.fa.fai")
    with open(fai, "w") as fh:
        fh.write(f"chr1\t{ref_len}\t6\t60\t61\n")
    return bam, fai


def run_smoke(timeout_s: float = 120.0, verbose: bool = True) -> int:
    """Returns 0 on success; raises on any failed step."""
    from .client import ServeClient

    env = dict(os.environ,
               JAX_PLATFORMS="cpu")     # CI has no accelerator
    deadline = time.monotonic() + timeout_s
    with tempfile.TemporaryDirectory(prefix="goleft_smoke_") as d:
        bam, fai = _make_fixture(d)
        flight_dir = os.path.join(d, "flight")
        os.makedirs(flight_dir)
        child = subprocess.Popen(
            [sys.executable, "-m", "goleft_tpu", "serve", "--port",
             "0", "--cache", os.path.join(d, "cache"),
             "--flight-dir", flight_dir],
            stdout=subprocess.PIPE, text=True, env=env,
        )
        try:
            line = child.stdout.readline()  # "... listening on URL"
            if "listening on " not in line:
                raise RuntimeError(
                    f"serve did not announce its port: {line!r}")
            url = line.rsplit("listening on ", 1)[1].strip()
            if verbose:
                print(f"serve-smoke: daemon up at {url}")
            client = ServeClient(url, timeout_s=60.0)
            assert client.healthz()["status"] == "ok"
            r = client.depth(bam, fai=fai, window=250)
            if not r["depth_bed"] or "chr1\t" not in r["depth_bed"]:
                raise RuntimeError(f"empty depth response: {r!r}")
            m = client.metrics()
            if verbose:
                print("serve-smoke: depth ok "
                      f"({r['shards']} shard(s)); batches="
                      f"{m['counters'].get('batches_total')}")
            if "slo" not in m or "availability" not in m["slo"]:
                raise RuntimeError(f"/metrics missing SLO block: "
                                   f"{sorted(m)}")
            prom = client.metrics_prometheus()
            for needle in ("# TYPE serve_requests_total_depth "
                           "counter",
                           "# TYPE serve_slo_availability gauge"):
                if needle not in prom:
                    raise RuntimeError(
                        f"prometheus body missing {needle!r}")
            fl = client.flight()
            roots = [rec["name"] for rec in fl["records"]]
            if "request.depth" not in roots:
                raise RuntimeError(
                    f"/debug/flight has no request.depth tree "
                    f"(roots: {roots})")
            if verbose:
                print(f"serve-smoke: observability ok (slo block, "
                      f"prometheus body, {fl['count']} flight "
                      "record(s))")
            # SIGUSR1 → a timestamped dump file that parses
            child.send_signal(signal.SIGUSR1)
            dump = None
            for _ in range(100):
                found = sorted(os.listdir(flight_dir))
                if found:
                    dump = os.path.join(flight_dir, found[-1])
                    break
                time.sleep(0.1)
            if dump is None:
                raise RuntimeError("SIGUSR1 produced no flight dump")
            import json

            with open(dump) as fh:
                doc = json.load(fh)
            if not doc.get("records"):
                raise RuntimeError(f"flight dump {dump} is empty")
            if verbose:
                print(f"serve-smoke: SIGUSR1 dump ok "
                      f"({os.path.basename(dump)}, "
                      f"{doc['count']} record(s))")
            child.send_signal(signal.SIGTERM)
            rc = child.wait(timeout=max(5.0,
                                        deadline - time.monotonic()))
            if rc != 0:
                raise RuntimeError(f"serve exited {rc}, want 0")
            if verbose:
                print("serve-smoke: clean SIGTERM drain, exit 0")
            return 0
        finally:
            if child.poll() is None:
                child.kill()
                child.wait(timeout=10.0)
            child.stdout.close()


if __name__ == "__main__":
    sys.exit(run_smoke())
