"""End-to-end smoke for the pair-HMM stack: `make pairhmm-smoke`.

The full candidate → likelihood pipeline as real subprocesses:

  1. ``goleft-tpu emdepth --candidates-out`` on a fabricated depth
     matrix with a planted deletion → a machine-readable candidates
     file naming the aberrant interval
  2. ``goleft-tpu pairhmm --candidates`` on a windows document whose
     reads support the alternate haplotype → the PL table, with the
     off-candidate window filtered out
  3. a real ``goleft-tpu serve`` daemon: the ``/v1/pairhmm`` response
     must be byte-identical to the CLI stdout for the same request
  4. chaos: the same CLI run under an injected transient fault at the
     ``pairhmm`` site (``--inject-faults pairhmm:after=1:...``) must
     retry and produce byte-identical output, exit 0

Host-pinned with the probe skipped, like the other smokes. Run::

    python -m goleft_tpu.models.pairhmm_smoke
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import time


def _write_matrix(path: str) -> None:
    """depthwed-style matrix: 8 samples at depth ~50, sample s3
    halved (a heterozygous deletion) over windows 10-15 of chr1."""
    import numpy as np

    rng = np.random.default_rng(5)
    samples = [f"s{i}" for i in range(8)]
    with open(path, "w") as fh:
        fh.write("#chrom\tstart\tend\t" + "\t".join(samples) + "\n")
        for w in range(40):
            start, end = w * 500, (w + 1) * 500
            row = rng.normal(50, 2, size=8)
            if 10 <= w < 16:
                row[3] *= 0.5
            fh.write(f"chr1\t{start}\t{end}\t"
                     + "\t".join(f"{v:.1f}" for v in row) + "\n")


def _write_windows(path: str) -> None:
    """Two windows: one inside the planted deletion (reads split
    between ref and alt haplotypes — a het site), one far away (the
    candidates filter must drop it)."""
    import numpy as np

    rng = np.random.default_rng(6)
    bases = list("ACGT")
    ref = "".join(rng.choice(bases, 60))
    alt = ref[:29] + ("A" if ref[29] != "A" else "C") + ref[30:]
    reads = []
    for i in range(8):
        src = ref if i % 2 else alt
        start = int(rng.integers(0, 10))
        reads.append({"seq": src[start:start + 40], "quals": 35})
    doc = {"schema": "goleft-tpu.pairhmm-windows/1",
           "windows": [
               {"chrom": "chr1", "start": 6100, "end": 6400,
                "haplotypes": [ref, alt], "reads": reads},
               {"chrom": "chr1", "start": 19_500, "end": 19_600,
                "haplotypes": [ref], "reads": reads[:2]},
           ]}
    with open(path, "w") as fh:
        json.dump(doc, fh)


def run_smoke(timeout_s: float = 180.0, verbose: bool = True) -> int:
    from ..models.candidates import read_candidates
    from ..serve.client import ServeClient

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    deadline = time.monotonic() + timeout_s

    def run_cli(*args):
        r = subprocess.run(
            [sys.executable, "-m", "goleft_tpu", *args],
            capture_output=True, text=True, env=env,
            timeout=max(5.0, deadline - time.monotonic()))
        return r

    with tempfile.TemporaryDirectory(prefix="goleft_phmm_") as d:
        matrix = os.path.join(d, "matrix.tsv")
        cand = os.path.join(d, "cand.bed")
        windows = os.path.join(d, "windows.json")
        _write_matrix(matrix)
        _write_windows(windows)

        # 1. emdepth exports machine-readable candidates
        r = run_cli("emdepth", "--candidates-out", cand, matrix)
        if r.returncode != 0:
            raise RuntimeError(f"emdepth failed: {r.stderr}")
        cands = read_candidates(cand)
        hits = [c for c in cands if c["sample"] == "s3"
                and c["start"] < 6400 and 6100 < c["end"]]
        if not hits:
            raise RuntimeError(
                f"emdepth candidates missed the planted deletion: "
                f"{cands}")
        if verbose:
            print(f"pairhmm-smoke: emdepth flagged the deletion "
                  f"({hits[0]['chrom']}:{hits[0]['start']}-"
                  f"{hits[0]['end']} CN{hits[0]['cn']})")

        # 2. pairhmm scores the candidate window (and only it)
        r = run_cli("pairhmm", "--candidates", cand, windows)
        if r.returncode != 0:
            raise RuntimeError(f"pairhmm failed: {r.stderr}")
        table = r.stdout
        lines = [ln for ln in table.splitlines() if ln]
        if len(lines) != 2 or not lines[0].startswith("#chrom"):
            raise RuntimeError(
                f"pairhmm table shape wrong (want header + the one "
                f"candidate window): {table!r}")
        cols = lines[1].split("\t")
        if cols[5] != "0/1":
            raise RuntimeError(
                f"expected het genotype 0/1 at the planted site, "
                f"got {cols[5]} (row: {lines[1]!r})")
        pls = [int(v) for v in cols[7].split(",")]
        if len(pls) != 3 or min(pls) != 0:
            raise RuntimeError(f"malformed PL vector: {cols[7]!r}")
        if verbose:
            print(f"pairhmm-smoke: CLI genotyped the site "
                  f"{cols[5]} GQ={cols[6]} PL={cols[7]}")

        # 3. serve round-trip: byte-identical to the CLI
        child = subprocess.Popen(
            [sys.executable, "-m", "goleft_tpu", "serve", "--port",
             "0", "--no-warmup"],
            stdout=subprocess.PIPE, text=True, env=env)
        try:
            line = child.stdout.readline()
            if "listening on " not in line:
                raise RuntimeError(
                    f"serve did not announce its port: {line!r}")
            url = line.rsplit("listening on ", 1)[1].strip()
            client = ServeClient(url, timeout_s=60.0)
            resp = client.pairhmm(windows, candidates=cand)
            if resp["likelihoods_tsv"] != table:
                raise RuntimeError(
                    "serve pairhmm response is not byte-identical "
                    f"to the CLI:\nCLI: {table!r}\nserve: "
                    f"{resp['likelihoods_tsv']!r}")
            if verbose:
                print("pairhmm-smoke: serve /v1/pairhmm response "
                      "byte-identical to the CLI")
            child.send_signal(signal.SIGTERM)
            rc = child.wait(timeout=max(
                5.0, deadline - time.monotonic()))
            if rc != 0:
                raise RuntimeError(f"serve exited {rc}, want 0")
        finally:
            if child.poll() is None:
                child.kill()
                child.wait(timeout=10.0)
            child.stdout.close()

        # 4. chaos: injected transient at the pairhmm site → retried,
        # byte-identical, exit 0
        r = run_cli("--inject-faults",
                    "pairhmm:after=1:times=1:transient",
                    "pairhmm", "--candidates", cand, windows)
        if r.returncode != 0:
            raise RuntimeError(
                f"pairhmm under injected transient fault exited "
                f"{r.returncode}: {r.stderr}")
        if r.stdout != table:
            raise RuntimeError(
                "retried run's output differs from the clean run")
        if verbose:
            print("pairhmm-smoke: injected transient retried to "
                  "byte-identical output, exit 0")
            print("pairhmm-smoke: OK")
        return 0


if __name__ == "__main__":
    sys.exit(run_smoke())
