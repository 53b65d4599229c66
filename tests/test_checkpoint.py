"""resilience/checkpoint.py + the --checkpoint-dir/--resume wiring:
store atomicity and journal replay, cohortdepth byte-identity across
engines/prefetch, the SIGKILL crash-resume satellite, mid-stream
quarantine, indexcov and run_prefetched_cohort resume."""

import io
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import goleft_tpu
from goleft_tpu.commands import cohortdepth as cd
from goleft_tpu.commands import depth as depth_mod
from goleft_tpu.io import native
from goleft_tpu.io.bam import BamFile
from goleft_tpu.io.fai import write_fai
from goleft_tpu.obs import get_registry
from goleft_tpu.resilience.checkpoint import (
    CheckpointCorrupt, CheckpointStore,
)
from helpers import write_bam_and_bai, write_fasta, random_reads

REPO = os.path.dirname(os.path.dirname(
    os.path.abspath(goleft_tpu.__file__)))


# ---- store semantics ----

def test_store_roundtrip_and_journal(tmp_path):
    d = str(tmp_path / "ck")
    with CheckpointStore(d) as st:
        assert not st.has(("k", 1))
        assert st.get(("k", 1), default="dflt") == "dflt"
        st.put(("k", 1), {"a": np.arange(3)})
        st.put_many([(("k", 2), "two"), (("k", 3), "three")])
        assert st.has(("k", 1)) and st.has(("k", 3))
        assert st.completed_count == 3
    lines = [json.loads(x) for x in
             open(os.path.join(d, "journal.jsonl"))]
    assert len(lines) == 3 and all("k" in r and "f" in r
                                   for r in lines)
    with CheckpointStore(d, resume=True) as st:
        assert st.completed_count == 3
        np.testing.assert_array_equal(st.get(("k", 1))["a"],
                                      np.arange(3))
        assert st.get(("k", 2)) == "two"


def test_store_fresh_open_truncates_journal(tmp_path):
    d = str(tmp_path / "ck")
    with CheckpointStore(d) as st:
        st.put(("k",), 1)
    with CheckpointStore(d) as st:  # no resume: cold run
        assert st.completed_count == 0
        assert not st.has(("k",))
    with CheckpointStore(d, resume=True) as st:
        assert st.completed_count == 0  # journal was truncated


def test_store_replay_tolerates_torn_tail_and_missing_blocks(
        tmp_path):
    d = str(tmp_path / "ck")
    with CheckpointStore(d) as st:
        st.put(("a",), 1)
        st.put(("b",), 2)
        st.put(("c",), 3)
        b_path = os.path.join(
            d, st._completed[
                __import__("goleft_tpu.resilience.checkpoint",
                           fromlist=["key_digest"]).key_digest(("b",))])
    os.remove(b_path)  # block vanished out from under the journal
    with open(os.path.join(d, "journal.jsonl"), "a") as fh:
        fh.write('{"k": "torn')  # crash mid-append
    with CheckpointStore(d, resume=True) as st:
        assert st.has(("a",)) and st.has(("c",))
        assert not st.has(("b",))  # dropped, recomputes


def test_store_corrupt_block_raises_clearly(tmp_path):
    d = str(tmp_path / "ck")
    with CheckpointStore(d) as st:
        st.put(("k",), 1)
        path = os.path.join(d, st._completed[next(iter(st._completed))])
    with open(path, "wb") as fh:
        fh.write(b"not a pickle")
    with CheckpointStore(d, resume=True) as st:
        with pytest.raises(CheckpointCorrupt, match="--resume"):
            st.get(("k",))


def test_store_tmp_unlinked_on_failed_write(tmp_path):
    d = str(tmp_path / "ck")
    with CheckpointStore(d) as st:
        with pytest.raises(Exception):
            st.put(("k",), lambda: None)  # unpicklable
        assert not st.has(("k",))
    blocks = os.listdir(os.path.join(d, "blocks"))
    assert blocks == []


# ---- cohortdepth wiring ----

def _cohort(tmp_path, n=3, ref_len=4000, seed=0):
    rng = np.random.default_rng(seed)
    fa = write_fasta(str(tmp_path / "r.fa"), {"chr1": "A" * ref_len})
    write_fai(fa)
    bams = []
    for i in range(n):
        hdr = ("@HD\tVN:1.6\tSO:coordinate\n"
               f"@SQ\tSN:chr1\tLN:{ref_len}\n@RG\tID:r\tSM:s{i}\n")
        p = str(tmp_path / f"s{i}.bam")
        write_bam_and_bai(p, random_reads(rng, 400, 0, ref_len),
                          ref_names=("chr1",), ref_lens=(ref_len,),
                          header_text=hdr)
        bams.append(p)
    return fa, bams


def _run_cd(bams, fa, **kw):
    buf = io.StringIO()
    rc = cd.run_cohortdepth(bams, reference=fa, window=200, out=buf,
                            processes=2, **kw)
    return rc, buf.getvalue()


def test_cohortdepth_checkpoint_resume_byte_identical(tmp_path,
                                                      monkeypatch):
    monkeypatch.setattr(depth_mod, "STEP", 1000)  # 4 regions
    fa, bams = _cohort(tmp_path)
    rc, cold = _run_cd(bams, fa)
    assert rc == 0 and cold.count("\n") == 21

    ck = str(tmp_path / "ck")
    rc, ckpt = _run_cd(bams, fa, checkpoint_dir=ck)
    assert rc == 0 and ckpt == cold

    # resume must not decode anything: every shard replays
    calls = {"n": 0}
    real = cd._decode_shard_segments

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(cd, "_decode_shard_segments", counting)
    resumed_before = get_registry().counter(
        "checkpoint.shards_resumed_total").value
    rc, res = _run_cd(bams, fa, checkpoint_dir=ck, resume=True)
    assert rc == 0 and res == cold
    assert calls["n"] == 0
    assert get_registry().counter(
        "checkpoint.shards_resumed_total").value \
        == resumed_before + 4 * 3  # regions x samples


def test_cohortdepth_resume_with_prefetch_and_partial_store(
        tmp_path, monkeypatch):
    """A partially-committed store resumes the committed regions and
    computes the rest — identically under the prefetched variant."""
    monkeypatch.setattr(depth_mod, "STEP", 1000)
    fa, bams = _cohort(tmp_path, seed=2)
    rc, cold = _run_cd(bams, fa)

    ck = str(tmp_path / "ck")
    store = CheckpointStore(ck)
    store.close()
    # commit only the FIRST region by running with a store, then
    # dropping the later journal lines
    rc, _ = _run_cd(bams, fa, checkpoint_dir=ck)
    jp = os.path.join(ck, "journal.jsonl")
    lines = open(jp).read().splitlines(keepends=True)
    with open(jp, "w") as fh:
        fh.writelines(lines[:3])  # one region x 3 samples
    rc, res = _run_cd(bams, fa, checkpoint_dir=ck, resume=True,
                      prefetch_depth=2)
    assert rc == 0 and res == cold


def test_cohortdepth_stale_input_invalidates_only_its_shards(
        tmp_path, monkeypatch):
    monkeypatch.setattr(depth_mod, "STEP", 1000)
    fa, bams = _cohort(tmp_path, seed=3)
    ck = str(tmp_path / "ck")
    rc, cold = _run_cd(bams, fa, checkpoint_dir=ck)
    # rewrite sample 1 with DIFFERENT content: its file_key changes,
    # its columns recompute; a full-region resume is impossible but
    # the others' committed columns still match their keys
    rng = np.random.default_rng(99)
    hdr = ("@HD\tVN:1.6\tSO:coordinate\n"
           "@SQ\tSN:chr1\tLN:4000\n@RG\tID:r\tSM:s1\n")
    write_bam_and_bai(bams[1], random_reads(rng, 300, 0, 4000),
                      ref_names=("chr1",), ref_lens=(4000,),
                      header_text=hdr)
    rc, fresh = _run_cd(bams, fa, checkpoint_dir=ck, resume=True)
    assert rc == 0
    rc, ref = _run_cd(bams, fa)
    assert fresh == ref  # correct values for the new content
    assert fresh != cold


@pytest.mark.parametrize("engine", ["device", "hybrid"])
def test_cohortdepth_midstream_failure_quarantines_and_zero_fills(
        tmp_path, monkeypatch, capsys, engine):
    """Each engine named (``auto`` picks by whether the native library
    loaded), and each failing in its own decode entry."""
    if engine == "hybrid" and native.get_lib() is None:
        pytest.skip("native toolchain unavailable")
    monkeypatch.setattr(depth_mod, "STEP", 1000)
    fa, bams = _cohort(tmp_path, seed=4)
    rc, cold = _run_cd(bams, fa, engine=engine)

    # the segment decode of the device engine takes the region's start
    # fourth, the hybrid engine's fused decode+reduce second (after
    # self, for both)
    owner, name, s_at = {
        "device": (cd, "_decode_shard_segments", 3),
        "hybrid": (BamFile, "window_reduce", 2),
    }[engine]
    real = getattr(owner, name)

    def failing(*a, **kw):
        if a[s_at] >= 2000:  # regions 3+4: corruption past the midpoint
            raise ValueError("simulated mid-stream corruption")
        return real(*a, **kw)

    monkeypatch.setattr(owner, name, failing)
    ck = str(tmp_path / "ck")
    rc, out = _run_cd(bams, fa, checkpoint_dir=ck, engine=engine)
    assert rc == 3
    # the matrix still has every row and every column (zero-filled
    # tails — a streamed matrix cannot unwrite columns)
    assert out.count("\n") == cold.count("\n")
    assert len(out.splitlines()[0].split("\t")) == 3 + 3
    # the healthy half is identical to the cold run's
    assert out.splitlines()[:11] == cold.splitlines()[:11]
    assert out.splitlines()[11].split("\t")[3:] == ["0", "0", "0"]
    q = json.load(open(os.path.join(ck, "quarantine.json")))
    assert len(q["quarantined"]) == 3
    assert {e["phase"] for e in q["quarantined"]} == {"decode"}
    assert "corruption" in q["quarantined"][0]["error"]
    assert "quarantined" in capsys.readouterr().err
    # quarantined columns are NOT checkpointed: a resume recomputes
    # regions 3+4, fails again, and degrades identically
    rc2, out2 = _run_cd(bams, fa, checkpoint_dir=ck, resume=True,
                        engine=engine)
    assert rc2 == 3 and out2 == out


def test_cohortdepth_quarantine_exit3_under_prefetch(
        tmp_path, monkeypatch, capsys):
    """The exit-3 quarantine contract holds on the PREFETCHED path
    (PR 5 only proved it serial): an open-phase-corrupt sample is
    dropped, the partial cohort is byte-identical to a healthy-only
    run at the same --prefetch-depth AND to the serial one, and the
    manifest names the culprit."""
    monkeypatch.setattr(depth_mod, "STEP", 1000)
    fa, bams = _cohort(tmp_path, seed=5)
    with open(bams[1], "r+b") as fh:
        fh.write(b"\x00" * 64)  # trash the BGZF header
    ck = str(tmp_path / "ck")
    rc, out = _run_cd(bams, fa, prefetch_depth=2, checkpoint_dir=ck)
    assert rc == 3
    rc_s, healthy_serial = _run_cd([bams[0], bams[2]], fa)
    rc_p, healthy_pf = _run_cd([bams[0], bams[2]], fa,
                               prefetch_depth=2)
    assert rc_s == 0 and rc_p == 0
    assert healthy_pf == healthy_serial
    assert out == healthy_serial
    q = json.load(open(os.path.join(ck, "quarantine.json")))
    assert [e["source"] for e in q["quarantined"]] == [bams[1]]
    assert "quarantined" in capsys.readouterr().err


def test_quarantine_json_survives_resume(tmp_path, monkeypatch):
    """--resume over a degraded run re-quarantines the still-corrupt
    sample: exit 3 again, byte-identical partial cohort (here under
    --prefetch-depth 2), and quarantine.json still names it."""
    monkeypatch.setattr(depth_mod, "STEP", 1000)
    fa, bams = _cohort(tmp_path, seed=6)
    with open(bams[2], "r+b") as fh:
        fh.write(b"\xff" * 64)
    ck = str(tmp_path / "ck")
    rc, out = _run_cd(bams, fa, checkpoint_dir=ck)
    assert rc == 3
    qp = os.path.join(ck, "quarantine.json")
    assert [e["source"]
            for e in json.load(open(qp))["quarantined"]] == [bams[2]]
    rc2, out2 = _run_cd(bams, fa, checkpoint_dir=ck, resume=True,
                        prefetch_depth=2)
    assert rc2 == 3 and out2 == out
    assert [e["source"]
            for e in json.load(open(qp))["quarantined"]] == [bams[2]]


def test_cohortdepth_resume_flag_requires_checkpoint_dir():
    with pytest.raises(SystemExit):
        cd.main(["--resume", "x.bam"])


def test_cohortdepth_sigkill_crash_resume_subprocess(tmp_path):
    """The crash-resume satellite: SIGKILL a checkpointed cohortdepth
    subprocess between journal commits (deterministic injected kill),
    resume, assert byte-identical output and that the journal replay
    skipped the committed shards (via the run-manifest counters)."""
    fa, bams = _cohort(tmp_path, ref_len=6000, seed=5)
    bed = str(tmp_path / "regions.bed")
    with open(bed, "w") as fh:
        for lo in range(0, 6000, 1000):
            fh.write(f"chr1\t{lo}\t{lo + 1000}\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("GOLEFT_TPU_FAULTS", None)
    base = [sys.executable, "-m", "goleft_tpu", "cohortdepth",
            "-r", fa, "-w", "200", "-b", bed, "-p", "2"]
    cold = subprocess.run(base + bams, env=env, capture_output=True,
                          timeout=120)
    assert cold.returncode == 0 and cold.stdout

    ck = str(tmp_path / "ck")
    kill = subprocess.run(
        base + ["--checkpoint-dir", ck, "--inject-faults",
                "shard:after=4:kill"] + bams,
        env=env, capture_output=True, timeout=120)
    assert kill.returncode in (-9, 137), kill.stderr.decode()
    committed = sum(1 for _ in open(os.path.join(ck, "journal.jsonl")))
    assert committed == 3 * 3  # 3 regions x 3 samples, then the kill

    manifest = str(tmp_path / "resume.json")
    res = subprocess.run(
        base + ["--checkpoint-dir", ck, "--resume", "--metrics-out",
                manifest] + bams,
        env=env, capture_output=True, timeout=120)
    assert res.returncode == 0, res.stderr.decode()
    assert res.stdout == cold.stdout  # byte-identical after the crash
    counters = json.load(open(manifest))["metrics"]["counters"]
    assert counters["checkpoint.shards_resumed_total"] == committed
    assert counters["checkpoint.journal_entries_replayed"] == committed
    assert counters["checkpoint.shards_written_total"] == 3 * 3


# ---- indexcov wiring ----

def test_indexcov_checkpoint_resume_byte_identical(tmp_path,
                                                   monkeypatch):
    from goleft_tpu.commands.indexcov import run_indexcov
    from goleft_tpu.ops import indexcov_ops as ops

    rng = np.random.default_rng(6)
    ref_len = 200_000
    bams = []
    for i in range(3):
        hdr = ("@HD\tVN:1.6\tSO:coordinate\n"
               f"@SQ\tSN:chr1\tLN:{ref_len}\n"
               f"@SQ\tSN:chr2\tLN:{ref_len // 2}\n"
               f"@RG\tID:r\tSM:ix{i}\n")
        p = str(tmp_path / f"ix{i}.bam")
        reads = random_reads(rng, 3000, 0, ref_len)
        write_bam_and_bai(p, reads, ref_names=("chr1", "chr2"),
                          ref_lens=(ref_len, ref_len // 2),
                          header_text=hdr)
        bams.append(p)

    def run(parent, **kw):
        # same basename everywhere: the output filenames embed it
        d = str(tmp_path / parent / "out")
        r = run_indexcov(bams, d, sex="", exclude_patt="",
                         write_html=False, write_png=False, **kw)
        return {ext: open(r[ext], "rb").read()
                for ext in ("bed", "roc", "ped")}

    cold = run("a")
    ck = str(tmp_path / "ck")
    warm = run("b", checkpoint_dir=ck)
    assert warm == cold

    calls = {"n": 0}
    real_qc = ops.chrom_qc

    def counting_qc(*a, **kw):
        calls["n"] += 1
        return real_qc(*a, **kw)

    monkeypatch.setattr(ops, "chrom_qc", counting_qc)
    resumed = run("c", checkpoint_dir=ck, resume=True)
    assert resumed == cold  # byte-identical artifacts
    assert calls["n"] == 0  # zero QC dispatches on resume


# ---- run_prefetched_cohort wiring ----

def test_run_prefetched_cohort_checkpoint_resumes_prefix():
    from goleft_tpu.parallel.mesh import make_mesh
    from goleft_tpu.parallel.prefetch import run_prefetched_cohort

    rng = np.random.default_rng(8)
    n_seq, shard_len, window = 4, 512, 64
    l_chunk = n_seq * shard_len
    n_chunks, S, n = 4, 4, 400
    total = n_chunks * l_chunk
    starts = rng.integers(0, total - 100, size=(S, n)).astype(np.int32)
    ends = (starts + 90).astype(np.int32)
    keep = np.ones((S, n), bool)
    mesh = make_mesh(8, prefer_seq=n_seq)

    decoded = []

    def decode_chunk(ci):
        decoded.append(ci)
        lo = ci * l_chunk
        return starts - lo, ends - lo, keep

    ref = run_prefetched_cohort(mesh, shard_len, window,
                                list(range(n_chunks)), decode_chunk,
                                S, prefetch_depth=0)

    class Dies(Exception):
        pass

    def dying_decode(ci):
        if ci >= 2:
            raise Dies(f"killed at chunk {ci}")
        return decode_chunk(ci)

    import tempfile

    d = tempfile.mkdtemp(prefix="goleft_ckpf_")
    store = CheckpointStore(d)
    with pytest.raises(Dies):
        run_prefetched_cohort(mesh, shard_len, window,
                              list(range(n_chunks)), dying_decode, S,
                              prefetch_depth=0, checkpoint=store)
    store.close()
    assert store.completed_count == 2

    decoded.clear()
    store = CheckpointStore(d, resume=True)
    out = run_prefetched_cohort(mesh, shard_len, window,
                                list(range(n_chunks)), decode_chunk,
                                S, prefetch_depth=0, checkpoint=store)
    store.close()
    assert decoded == [2, 3]  # the committed prefix never re-decodes
    for k in ("depth", "wmeans", "lambdas", "cn", "carry"):
        np.testing.assert_array_equal(np.asarray(out[k]),
                                      np.asarray(ref[k]))


# ---- DeferredCommits: journal batching under serve load ----


def test_deferred_commits_batches_journal_fsyncs(tmp_path):
    """The regression the serve executors rely on: N region commits
    through DeferredCommits(flush_every=4) cost ceil(N/4) journal
    commits instead of N, while every flushed shard resumes."""
    from goleft_tpu.resilience.checkpoint import DeferredCommits

    commits = get_registry().counter(
        "checkpoint.journal_commits_total")

    # the per-step baseline: one journal commit per put_many group
    base = CheckpointStore(str(tmp_path / "plain"))
    before = commits.value
    for i in range(8):
        base.put_many([((("k", i, s)), i * 10 + s)
                       for s in range(3)])
    base.close()
    assert commits.value - before == 8

    # batched: blocks written immediately, ONE journal commit per 4
    # groups (+ the close() flush for the tail)
    store = CheckpointStore(str(tmp_path / "batched"))
    dc = DeferredCommits(store, flush_every=4)
    before = commits.value
    for i in range(10):
        dc.put_many([((("k", i, s)), i * 10 + s) for s in range(3)])
        # same-process readers see their own unflushed writes
        assert dc.has(("k", i, 0))
        assert dc.get(("k", i, 1)) == i * 10 + 1
    dc.close()
    assert commits.value - before == 3  # 4 + 4 + tail(2)

    # everything flushed is durably committed and resumes intact
    back = CheckpointStore(str(tmp_path / "batched"), resume=True)
    for i in range(10):
        for s in range(3):
            assert back.get(("k", i, s)) == i * 10 + s
    back.close()


def test_deferred_commits_crash_loses_only_unflushed_tail(tmp_path):
    """Dropping the wrapper without flush (a crash) loses at most the
    buffered tail: flushed groups replay, the tail recomputes — the
    exact trade the batching makes."""
    from goleft_tpu.resilience.checkpoint import DeferredCommits

    store = CheckpointStore(str(tmp_path / "ck"))
    dc = DeferredCommits(store, flush_every=3)
    for i in range(5):  # flush fires at group 3; 4-5 stay buffered
        dc.put(("r", i), f"block-{i}")
    store.close()  # crash: no dc.flush()/dc.close()

    back = CheckpointStore(str(tmp_path / "ck"), resume=True)
    assert [back.has(("r", i)) for i in range(5)] == \
        [True, True, True, False, False]
    assert back.get(("r", 1)) == "block-1"
    back.close()


def test_deferred_commits_resumed_serve_matrix_byte_identical(
        tmp_path, monkeypatch):
    """End-to-end through the serve cohortdepth path (which wraps its
    store in DeferredCommits): a request computed fresh against a
    checkpoint root, then re-issued against a NEW app on the same
    root, restores every region and returns byte-identical bytes."""
    from goleft_tpu.serve.server import ServeApp

    monkeypatch.setattr(depth_mod, "STEP", 1000)  # several regions
    fa, bams = _cohort(tmp_path)
    root = str(tmp_path / "serve-ck")
    req = {"bams": bams, "fai": fa + ".fai", "window": 200,
           "checkpoint": True}

    app1 = ServeApp(batch_window_s=0.0, checkpoint_root=root,
                    watchdog_s=None)
    try:
        code, cold = app1.handle("cohortdepth", dict(req))
        assert code == 200
    finally:
        app1.close()

    resumed_before = get_registry().counter(
        "checkpoint.shards_resumed_total").value
    app2 = ServeApp(batch_window_s=0.0, checkpoint_root=root,
                    watchdog_s=None)
    try:
        code, warm = app2.handle("cohortdepth", dict(req))
        assert code == 200
    finally:
        app2.close()
    assert warm["matrix_tsv"] == cold["matrix_tsv"]
    assert get_registry().counter(
        "checkpoint.shards_resumed_total").value > resumed_before
