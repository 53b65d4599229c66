"""Multi-host cohort decode: 2 real processes, samples sharded across
them, matrix assembled over the jax.distributed fabric — byte-identical
to the single-process cohortdepth run (incl. a cohort smaller than the
world, where one process decodes nothing and only gathers)."""

import os
import socket
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
sys.path.insert(0, os.environ["GOLEFT_REPO"])
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)  # match the pytest conftest
from goleft_tpu.parallel.mesh import init_distributed
from goleft_tpu.commands.cohortdepth import run_cohortdepth
from goleft_tpu.commands.cnv import run_cnv

init_distributed()
assert jax.process_count() == 2
d = os.environ["GOLEFT_WORK"]
bams = sorted(
    os.path.join(d, f) for f in os.listdir(d) if f.endswith(".bam")
)

class Sink:
    def __init__(self): self.parts = []
    def write(self, s): self.parts.append(s)

# full cohort (odd count: uneven shards exercise the padding)
sink = Sink()
r = run_cohortdepth(bams, fai=os.path.join(d, "ref.fa.fai"),
                    window=500, out=sink)
text = "".join(sink.parts)
if jax.process_index() == 0:
    assert text, "process 0 must produce the matrix"
    open(os.path.join(d, "dist_full.tsv"), "w").write(text)
else:
    assert text == "", "only process 0 writes output"

# cohort smaller than the world: process 1 has zero local samples
sink = Sink()
run_cohortdepth(bams[:1], fai=os.path.join(d, "ref.fa.fai"),
                window=500, out=sink)
if jax.process_index() == 0:
    open(os.path.join(d, "dist_one.tsv"), "w").write(
        "".join(sink.parts))

# full CNV pipeline on the sharded decode: EM + merge on process 0
sink = Sink()
res = run_cnv(bams, fai=os.path.join(d, "ref.fa.fai"), window=2000,
              out=sink)
if jax.process_index() == 0:
    open(os.path.join(d, "dist_cnv.tsv"), "w").write(
        "".join(sink.parts))
else:
    assert res == [] and not sink.parts

print("DISTCOHORT_OK", jax.process_index(), flush=True)
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _attempt(port: int, work: str):
    procs = []
    for pid in range(2):
        env = dict(
            os.environ,
            GOLEFT_REPO=REPO,
            GOLEFT_WORK=work,
            GOLEFT_TPU_COORDINATOR=f"127.0.0.1:{port}",
            GOLEFT_TPU_NUM_PROCESSES="2",
            GOLEFT_TPU_PROCESS_ID=str(pid),
        )
        env.pop("JAX_PLATFORMS", None)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _WORKER], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        ))
    outs = []
    for pid, pr in enumerate(procs):
        try:
            out, err = pr.communicate(timeout=240)
            outs.append((pr.returncode, out, err))
        except subprocess.TimeoutExpired:
            for p2 in procs:
                p2.kill()
            outs.append((-1, "", f"process {pid} timed out"))
    return outs


def test_distributed_cohortdepth_matches_single_process(tmp_path):
    from goleft_tpu.commands.cohortdepth import run_cohortdepth
    from goleft_tpu.io.fai import write_fai
    from helpers import write_bam_and_bai, write_fasta

    rng = np.random.default_rng(5)
    ref_len = 80_000
    fa = write_fasta(str(tmp_path / "ref.fa"), {"chr1": "A" * ref_len})
    write_fai(fa)
    bams = []
    for i in range(5):
        starts = np.sort(rng.integers(0, ref_len - 100, size=1500))
        if i == 2:  # planted drop so the distributed cnv run calls it
            m = ((starts >= 30_000) & (starts < 50_000)
                 & (rng.random(len(starts)) < 0.65))
            starts = starts[~m]
        reads = [(0, int(s), "100M", 60, 0) for s in starts]
        hdr = ("@HD\tVN:1.6\tSO:coordinate\n"
               f"@SQ\tSN:chr1\tLN:{ref_len}\n@RG\tID:r\tSM:w{i}\n")
        p = str(tmp_path / f"w{i}.bam")
        write_bam_and_bai(p, reads, ref_names=("chr1",),
                          ref_lens=(ref_len,), header_text=hdr)
        bams.append(p)

    # single-process expected outputs (this process: world of 1)
    class Sink:
        def __init__(self):
            self.parts = []

        def write(self, s):
            self.parts.append(s)

    want_full = Sink()
    run_cohortdepth(bams, fai=fa + ".fai", window=500, out=want_full)
    want_one = Sink()
    run_cohortdepth(bams[:1], fai=fa + ".fai", window=500,
                    out=want_one)
    from goleft_tpu.commands.cnv import run_cnv

    want_cnv = Sink()
    cnv_results = run_cnv(bams, fai=fa + ".fai", window=2000,
                          out=want_cnv)
    assert any(r[3] == "w2" and r[4] < 2 for r in cnv_results), \
        cnv_results  # the planted drop must actually be called

    for attempt in range(3):
        outs = _attempt(_free_port(), str(tmp_path))
        if all(rc == 0 for rc, _, _ in outs):
            break
    for pid, (rc, out, err) in enumerate(outs):
        assert rc == 0, f"proc {pid} rc={rc}\n{err[-2000:]}"
        assert f"DISTCOHORT_OK {pid}" in out, (pid, out, err[-500:])

    got_full = open(tmp_path / "dist_full.tsv").read()
    assert got_full == "".join(want_full.parts)
    got_one = open(tmp_path / "dist_one.tsv").read()
    assert got_one == "".join(want_one.parts)
    got_cnv = open(tmp_path / "dist_cnv.tsv").read()
    assert got_cnv == "".join(want_cnv.parts)


def test_pack_names_truncates_on_codepoint_boundary():
    """A >256-byte utf-8 name whose byte cut lands inside a multi-byte
    codepoint must still round-trip through pack/unpack without a
    UnicodeDecodeError (ADVICE r3)."""
    from goleft_tpu.parallel.distributed_cohort import (
        _pack_names, _unpack_name,
    )

    name = "€" * 100  # 300 utf-8 bytes; 256 % 3 == 1 splits a codepoint
    packed = _pack_names([name, "plain"], pad_to=2)
    got = _unpack_name(packed[0])
    assert got == "€" * 85  # 255 bytes: cut back to the boundary
    assert _unpack_name(packed[1]) == "plain"
