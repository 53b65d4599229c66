"""End-to-end smoke for the read mapper: the `make mapper-smoke` body.

Real subprocess CLIs + a real serve daemon over a synthetic reference
and 10k simulated 100-150bp reads:

  1. ``goleft-tpu map --depth-out`` maps >= 95% of the reads to
     within +-5bp of their simulated origin (strand included);
  2. the fused depth bed is byte-identical to a ``--from-tuples``
     re-derivation from the written tuple stream;
  3. a serve daemon's POST /v1/map response carries the CLI's exact
     tuple and depth bytes;
  4. an injected transient fault at the ``map`` site is retried to a
     byte-identical tuple stream (exit 0);
  5. a FASTQ corrupted mid-stream maps everything before the bad
     record, quarantines the file, and exits 3.

Run directly::

    python -m goleft_tpu.mapping.smoke

Host-pinned like the other smokes (CI has no accelerator).
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import tempfile
import time

N_READS = 10_000
ACCURACY = 0.95
SLOP_BP = 5
WINDOW = 250


def _make_fixture(d: str) -> tuple[str, str, list]:
    """(ref.fa, reads.fastq, truth) — truth[i] = (chrom, start,
    rev) for read ``r<i>``."""
    import numpy as np

    rng = np.random.default_rng(97)
    bases = b"ACGT"
    chroms = [("chr1", 120_000), ("chr2", 80_000)]
    seqs = {n: bytes(rng.choice(list(bases), size=ln).tolist())
            for n, ln in chroms}
    ref = os.path.join(d, "ref.fa")
    with open(ref, "wb") as fh:
        for n, _ in chroms:
            fh.write(f">{n}\n".encode())
            s = seqs[n]
            for i in range(0, len(s), 60):
                fh.write(s[i:i + 60] + b"\n")
    fastq = os.path.join(d, "reads.fastq")
    truth = []
    comp = bytes.maketrans(b"ACGT", b"TGCA")
    with open(fastq, "wb") as fh:
        for i in range(N_READS):
            cname, clen = chroms[int(rng.integers(0, len(chroms)))]
            rlen = int(rng.integers(100, 151))
            s = int(rng.integers(0, clen - rlen))
            frag = bytearray(seqs[cname][s:s + rlen])
            for _ in range(2):  # ~1.5% divergence
                j = int(rng.integers(0, rlen))
                frag[j] = bases[int(rng.integers(0, 4))]
            rev = bool(rng.random() < 0.5)
            if rev:
                frag = bytearray(bytes(frag).translate(comp)[::-1])
            fh.write(b"@r%d\n%s\n+\n%s\n"
                     % (i, bytes(frag), b"I" * rlen))
            truth.append((cname, s, rev))
    return ref, fastq, truth


def _run(args: list, env: dict, timeout_s: float):
    return subprocess.run(
        [sys.executable, "-m", "goleft_tpu"] + args,
        capture_output=True, env=env, timeout=timeout_s)


def _say(verbose: bool, msg: str) -> None:
    if verbose:
        print(f"mapper-smoke: {msg}", flush=True)


def run_smoke(timeout_s: float = 480.0, verbose: bool = True) -> int:
    """Returns 0 on success; raises on any failed step."""
    from ..mapping.pipeline import parse_tuples

    t_start = time.monotonic()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    with tempfile.TemporaryDirectory(prefix="goleft_mapsmk_") as d:
        ref, fastq, truth = _make_fixture(d)
        tuples_p = os.path.join(d, "tuples.tsv")
        bed_p = os.path.join(d, "depth.bed")

        # ---- leg 1: map accuracy over 10k simulated reads
        r = _run(["map", ref, fastq, "-o", tuples_p, "--depth-out",
                  bed_p, "--window", str(WINDOW)], env, timeout_s)
        if r.returncode != 0:
            raise RuntimeError(
                f"map failed rc={r.returncode}:\n{r.stderr.decode()}")
        with open(tuples_p, "rb") as f:
            tuples_bytes = f.read()
        with open(bed_p, "rb") as f:
            bed_bytes = f.read()
        rows = parse_tuples(tuples_bytes)
        ok = 0
        for chrom, start, end, name, score, strand in rows:
            tc, ts, trev = truth[int(name[1:])]
            if (chrom == tc and abs(start - ts) <= SLOP_BP
                    and strand == ("-" if trev else "+")):
                ok += 1
        frac = ok / N_READS
        if frac < ACCURACY:
            raise RuntimeError(
                f"accuracy {frac:.4f} < {ACCURACY} "
                f"({ok}/{N_READS} within +-{SLOP_BP}bp)")
        _say(verbose, f"mapped {len(rows)}/{N_READS} reads, "
                      f"{frac:.1%} within +-{SLOP_BP}bp of their "
                      f"simulated origin (gate {ACCURACY:.0%})")

        # ---- leg 2: fused depth == --from-tuples re-derivation
        bed2_p = os.path.join(d, "depth2.bed")
        r = _run(["map", ref, "--from-tuples", tuples_p,
                  "--depth-out", bed2_p, "--window", str(WINDOW)],
                 env, timeout_s)
        if r.returncode != 0:
            raise RuntimeError(
                f"--from-tuples failed:\n{r.stderr.decode()}")
        with open(bed2_p, "rb") as f:
            if f.read() != bed_bytes:
                raise RuntimeError(
                    "--from-tuples bed differs from the fused bed")
        _say(verbose, "fused --depth-out byte-identical to the "
                      "--from-tuples re-derivation")

        # ---- leg 3: serve /v1/map == the CLI bytes
        child = subprocess.Popen(
            [sys.executable, "-m", "goleft_tpu", "serve", "--port",
             "0"], stdout=subprocess.PIPE, text=True, env=env)
        try:
            line = child.stdout.readline()
            if "listening on " not in line:
                raise RuntimeError(
                    f"serve did not announce its port: {line!r}")
            url = line.rsplit("listening on ", 1)[1].strip()
            from ..serve.client import ServeClient

            client = ServeClient(url, timeout_s=timeout_s)
            resp = client.map(fastq, ref, window=WINDOW)
            if resp["tuples_tsv"].encode() != tuples_bytes:
                raise RuntimeError(
                    "serve /v1/map tuple stream differs from the CLI")
            if resp["depth_bed"].encode() != bed_bytes:
                raise RuntimeError(
                    "serve /v1/map depth bed differs from the CLI")
            if resp["reads"] != N_READS:
                raise RuntimeError(
                    f"serve counted {resp['reads']} reads")
        finally:
            child.send_signal(signal.SIGTERM)
            try:
                child.wait(timeout=30)
            except subprocess.TimeoutExpired:
                child.kill()
        _say(verbose, "serve /v1/map tuple + depth bytes identical "
                      "to the CLI")

        # ---- leg 4: transient fault at the map site retried to
        # byte-identical output
        tuples3_p = os.path.join(d, "tuples3.tsv")
        r = _run(["map", ref, fastq, "-o", tuples3_p,
                  "--inject-faults", "map:after=1:transient"],
                 env, timeout_s)
        if r.returncode != 0:
            raise RuntimeError(
                f"faulted map failed rc={r.returncode}:\n"
                f"{r.stderr.decode()}")
        with open(tuples3_p, "rb") as f:
            if f.read() != tuples_bytes:
                raise RuntimeError(
                    "retried map output differs (fault not "
                    "transparent)")
        _say(verbose, "injected transient fault at the map site "
                      "retried to byte-identical tuples")

        # ---- leg 5: corruption mid-stream -> quarantine + exit 3
        bad_p = os.path.join(d, "bad.fastq")
        with open(fastq, "rb") as f:
            head = f.read()
        with open(bad_p, "wb") as f:
            f.write(head + b"@broken\nACGTACGTACGTAC\n+\nIII\n")
        r = _run(["map", ref, bad_p, "-o",
                  os.path.join(d, "tuples4.tsv")], env, timeout_s)
        if r.returncode != 3:
            raise RuntimeError(
                f"corrupt FASTQ exited {r.returncode}, want 3:\n"
                f"{r.stderr.decode()}")
        with open(os.path.join(d, "tuples4.tsv"), "rb") as f:
            if f.read() != tuples_bytes:
                raise RuntimeError(
                    "reads before the corruption did not all map")
        if b"quarantine" not in r.stderr.lower():
            raise RuntimeError(
                f"no quarantine summary on stderr:\n"
                f"{r.stderr.decode()}")
        _say(verbose, "mid-stream FASTQ corruption: prior reads "
                      "mapped byte-identically, file quarantined, "
                      "exit 3")

    _say(verbose, f"PASS ({time.monotonic() - t_start:.1f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(run_smoke())
