"""Observability smoke: the ``make obs-smoke`` body.

Runs a REAL ``goleft-tpu depth`` subprocess with ``--trace-out`` and
``--metrics-out`` on a fabricated fixture, then validates both
artifacts: the trace must be Chrome-trace-event JSON (the exact schema
Perfetto loads — ph/ts/dur/pid/tid on every span event) containing the
run's root and stage spans, and the manifest must parse with every
required provenance key (obs/manifest.py::REQUIRED_KEYS) and a backend
block naming a platform. Run directly::

    python -m goleft_tpu.obs.smoke
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile


def _make_fixture(d: str, n_reads: int = 400,
                  ref_len: int = 20_000) -> tuple[str, str]:
    """(bam, fai): a tiny coordinate-sorted BAM + matching .fai
    (the serve smoke's hermetic-fixture approach)."""
    import numpy as np

    from ..io.bai import build_bai, write_bai
    from ..io.bam import BamWriter

    rng = np.random.default_rng(11)
    starts = np.sort(rng.integers(0, ref_len - 100, size=n_reads))
    bam = os.path.join(d, "obs.bam")
    with open(bam, "wb") as fh:
        with BamWriter(
            fh, "@HD\tVN:1.6\tSO:coordinate\n@SQ\tSN:chr1\tLN:"
            f"{ref_len}\n@RG\tID:r\tSM:obs\n", ["chr1"], [ref_len],
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


def validate_trace(path: str) -> dict:
    """Parse + schema-check a ``--trace-out`` artifact; returns the
    document. Raises on anything Perfetto would choke on."""
    with open(path) as fh:
        doc = json.load(fh)
    events = doc.get("traceEvents")
    if not isinstance(events, list) or not events:
        raise ValueError(f"{path}: no traceEvents")
    span_events = [e for e in events if e.get("ph") == "X"]
    if not span_events:
        raise ValueError(f"{path}: no complete ('X') span events")
    for e in span_events:
        missing = {"name", "ph", "ts", "dur", "pid", "tid"} - set(e)
        if missing:
            raise ValueError(
                f"{path}: span event missing {sorted(missing)}: {e}")
        if not (isinstance(e["ts"], (int, float))
                and isinstance(e["dur"], (int, float))
                and e["dur"] >= 0):
            raise ValueError(f"{path}: bad ts/dur in {e}")
    return doc


def run_smoke(timeout_s: float = 180.0, verbose: bool = True) -> int:
    """Returns 0 on success; raises on any failed step."""
    from .manifest import load_manifest

    env = dict(os.environ,
               JAX_PLATFORMS="cpu")     # CI has no accelerator
    with tempfile.TemporaryDirectory(prefix="goleft_obs_") as d:
        bam, fai = _make_fixture(d)
        trace_p = os.path.join(d, "trace.json")
        manifest_p = os.path.join(d, "run.json")
        cmd = [sys.executable, "-m", "goleft_tpu", "depth",
               "--trace-out", trace_p, "--metrics-out", manifest_p,
               "--prefix", os.path.join(d, "out"), "-r",
               os.path.join(d, "ref.fa"), bam]
        rc = subprocess.run(cmd, env=env, timeout=timeout_s,
                            capture_output=True, text=True)
        if rc.returncode != 0:
            raise RuntimeError(
                f"depth run failed ({rc.returncode}):\n{rc.stderr}")
        if not os.path.exists(os.path.join(d, "out.depth.bed")):
            raise RuntimeError("depth produced no output bed")

        doc = validate_trace(trace_p)
        names = {e["name"] for e in doc["traceEvents"]
                 if e.get("ph") == "X"}
        for want in ("run.depth", "host-decode", "device-compute"):
            if want not in names:
                raise RuntimeError(
                    f"trace is missing the {want!r} span "
                    f"(has: {sorted(names)[:12]}...)")
        if verbose:
            n = sum(1 for e in doc["traceEvents"]
                    if e.get("ph") == "X")
            print(f"obs-smoke: trace ok ({n} spans, "
                  f"{len(names)} distinct)")

        man = load_manifest(manifest_p)
        backend = man["backend"]
        if "error" not in backend:
            for key in ("platform", "device_kind", "device_count"):
                if key not in backend:
                    raise RuntimeError(
                        f"manifest backend block missing {key!r}")
        if not man["spans"]:
            raise RuntimeError("manifest has no span summary")
        if "host-decode" not in man["spans"]:
            raise RuntimeError(
                "manifest span summary is missing the pipeline "
                f"stages (has {sorted(man['spans'])[:12]})")
        if verbose:
            print(f"obs-smoke: manifest ok (platform="
                  f"{backend.get('platform', 'n/a')}, "
                  f"{len(man['spans'])} span names, "
                  f"{len(man['metrics']['counters'])} counters)")
            print("obs-smoke: PASS")
    return 0


if __name__ == "__main__":
    sys.exit(run_smoke())
