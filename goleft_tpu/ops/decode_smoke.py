"""Device-decode smoke: the ``make decode-smoke`` body.

Real ``goleft-tpu cohortdepth`` subprocesses over a hermetic CRAM
cohort whose blocks are rANS-Nx16 spanning the full method-5 matrix —
ORDER0, ORDER1 (per-context tables), and STRIPE samples, ALL
device-decodable since the ORDER1/STRIPE scan landed:

  1. the default run and the ``--decode-device`` run produce
     BYTE-IDENTICAL matrices (the tentpole's contract: the wire format
     changed, the bytes did not);
  2. the ``--decode-device`` run's ``--metrics-out`` manifest carries
     the decode counters — device blocks > 0, fallbacks == 0 (the
     ORDER1 sample that used to force per-block host fallbacks now
     decodes on device; any fallback is a matrix regression), wire
     byte counters and the ORDER1 table share
     (``decode.table_bytes_total``) visible (on tiny fixture blocks
     the per-block table floor dominates — the ratio only wins at
     CRAM-typical block sizes, not measured on the chip);
  3. an injected transient fault at the ``decode`` site is retried
     under the RetryPolicy to the same byte-identical output (the
     decode step is a real plan Step, not a bare device call).

Run directly::

    python -m goleft_tpu.ops.decode_smoke
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile


def make_cram_cohort(d: str, ref_len: int = 50_000,
                     n_reads: int = 400) -> tuple[list[str], str]:
    """(cram paths, fai): four single-chromosome CRAMs with .crai,
    rANS-Nx16 blocks spanning the method-5 matrix — two ORDER0, one
    ORDER1 (per-context tables, order-0-compressed on the wire) and
    one STRIPE (4 byte-interleaved lanes per block), so
    --decode-device exercises every device decode shape."""
    import numpy as np

    from ..io import cram
    from ..io.bam import parse_cigar

    rng = np.random.default_rng(7)
    paths = []
    for i, (order, stripe) in enumerate(
            ((0, 0), (0, 0), (1, 0), (0, 4))):
        hdr = f"@HD\tVN:1.6\tSO:coordinate\n@RG\tID:r\tSM:cr{i}\n"
        p = os.path.join(d, f"cr{i}.cram")
        reads = sorted(
            (0, int(rng.integers(0, ref_len - 200)), "100M", 60, 0)
            for _ in range(n_reads))
        with open(p, "wb") as fh:
            with cram.CramWriter(fh, hdr, ["chr1"], [ref_len],
                                 records_per_container=150,
                                 block_method=cram.M_RANSNX16,
                                 rans_order=order, minor=1,
                                 rans_stripe=stripe) as w:
                for j, (tid, pos, cig, mq, fl) in enumerate(reads):
                    w.write_record(tid, pos, parse_cigar(cig),
                                   mapq=mq, flag=fl, name=f"r{j:04d}")
            w.write_crai(p + ".crai")
        paths.append(p)
    fai = os.path.join(d, "ref.fa.fai")
    with open(fai, "w") as fh:
        fh.write(f"chr1\t{ref_len}\t6\t60\t61\n")
    return paths, fai


def _run(args, env, timeout_s):
    rc = subprocess.run(args, env=env, timeout=timeout_s,
                        capture_output=True, text=True)
    if rc.returncode != 0:
        raise RuntimeError(
            f"{' '.join(args[-6:])} failed ({rc.returncode}):\n"
            f"{rc.stderr}")
    return rc.stdout


def run_smoke(timeout_s: float = 240.0, verbose: bool = True) -> int:
    """Returns 0 on success; raises on any failed step."""
    env = dict(os.environ,
               JAX_PLATFORMS="cpu")     # CI has no accelerator
    with tempfile.TemporaryDirectory(prefix="goleft_dec_") as d:
        crams, fai = make_cram_cohort(d)
        base_cmd = [sys.executable, "-m", "goleft_tpu", "cohortdepth",
                    "--fai", fai, "-w", "500"] + crams

        plain = _run(base_cmd, env, timeout_s)
        manifest_p = os.path.join(d, "run.json")
        dev_cmd = [sys.executable, "-m", "goleft_tpu", "cohortdepth",
                   "--metrics-out", manifest_p, "--fai", fai,
                   "-w", "500", "--decode-device"] + crams
        on_device = _run(dev_cmd, env, timeout_s)
        if plain != on_device:
            raise RuntimeError(
                "--decode-device matrix differs from the default path")
        if verbose:
            rows = plain.count("\n") - 1
            print(f"decode-smoke: byte-identical matrices ({rows} "
                  "windows)")

        with open(manifest_p) as fh:
            man = json.load(fh)
        counters = man["metrics"]["counters"]
        dev = counters.get("decode.device_blocks_total", 0)
        fall = counters.get("decode.device_fallback_total", 0)
        wire_c = counters.get("decode.wire_bytes_compressed_total", 0)
        wire_u = counters.get(
            "decode.wire_bytes_uncompressed_total", 0)
        table_b = counters.get("decode.table_bytes_total", 0)
        if dev <= 0:
            raise RuntimeError(
                "manifest shows no device-decoded blocks "
                f"(counters: {sorted(counters)[:12]})")
        if fall != 0:
            raise RuntimeError(
                f"{fall} host fallbacks on a fully-supported cohort "
                "— the ORDER1/STRIPE device matrix regressed")
        if not (0 < wire_c and 0 < wire_u):
            raise RuntimeError("wire byte counters missing")
        if table_b <= 0:
            raise RuntimeError(
                "decode.table_bytes_total missing — ORDER1 table "
                "wire accounting not recorded")
        if verbose:
            print(f"decode-smoke: manifest ok (device blocks={dev}, "
                  f"fallbacks={fall}, wire {wire_c}B compressed / "
                  f"{wire_u}B inflated, {table_b}B tables)")

        fault_env = dict(env,
                         GOLEFT_TPU_FAULTS="decode:after=1:transient")
        retried = _run(base_cmd[:-len(crams)] + ["--decode-device"]
                       + crams, fault_env, timeout_s)
        if retried != plain:
            raise RuntimeError(
                "injected transient decode fault was not retried to "
                "byte-identical output")
        if verbose:
            print("decode-smoke: injected decode fault retried, "
                  "bytes identical")
            print("decode-smoke: PASS")
    return 0


if __name__ == "__main__":
    sys.exit(run_smoke())
