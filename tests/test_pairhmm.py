"""Pair-HMM subsystem: wavefront forward vs a NumPy log-space oracle,
genotype PLs, candidate export/consumption, serve byte-identity,
fault-injection retry/quarantine, and the Pallas variant.

The oracle is a deliberately dumb row-major log-space forward
(np.logaddexp per cell) — slow, obviously correct, immune to
underflow. The f64 wavefront must match it to fp noise; the
rescaled-f32 wavefront must stay within 1e-4 log10 on randomized
pairs AND on under/overflow edge reads far outside f32's exponent
range.
"""

import io
import json
import os

import numpy as np
import pytest

from goleft_tpu.ops import pairhmm as ph


# ---------------------------------------------------------------------------
# oracle

def oracle_log10(read, quals, hap, gap_open=45.0, gap_ext=10.0):
    """Pure-NumPy log-space forward (natural-log cells, result in
    log10)."""
    r = ph.encode_seq(read)
    h = ph.encode_seq(hap)
    err = ph.phred_to_err(np.broadcast_to(np.asarray(quals),
                                          (len(r),)))
    delta = 10.0 ** (-gap_open / 10.0)
    eps = 10.0 ** (-gap_ext / 10.0)
    l_mm = np.log(1 - 2 * delta)
    l_gap_open = np.log(delta)       # M→I and M→D
    l_gap_to_m = np.log1p(-eps)      # I→M and D→M
    l_gap_ext = np.log(eps)          # I→I and D→D
    R, H = len(r), len(h)
    M = np.full((R + 1, H + 1), -np.inf)
    I = np.full((R + 1, H + 1), -np.inf)
    D = np.full((R + 1, H + 1), -np.inf)
    D[0, :] = -np.log(H)
    lse = np.logaddexp
    for i in range(1, R + 1):
        lm = np.log1p(-err[i - 1])
        lx = np.log(err[i - 1] / 3.0)
        for j in range(1, H + 1):
            match = (r[i - 1] == h[j - 1]) or r[i - 1] == 4 \
                or h[j - 1] == 4
            prior = lm if match else lx
            M[i, j] = prior + lse(
                l_mm + M[i - 1, j - 1],
                lse(l_gap_to_m + I[i - 1, j - 1],
                    l_gap_to_m + D[i - 1, j - 1]))
            I[i, j] = lse(l_gap_open + M[i - 1, j],
                          l_gap_ext + I[i - 1, j])
            D[i, j] = lse(l_gap_open + M[i, j - 1],
                          l_gap_ext + D[i, j - 1])
    tot = -np.inf
    for j in range(1, H + 1):
        tot = lse(tot, lse(M[R, j], I[R, j]))
    return tot / np.log(10.0)


_BASES = list("ACGT")


def _random_pairs(n, rng, max_r=32, max_h=48, q_lo=5, q_hi=41):
    reads, quals, haps = [], [], []
    for _ in range(n):
        rl = int(rng.integers(3, max_r))
        hl = int(rng.integers(5, max_h))
        hap = "".join(rng.choice(_BASES, hl))
        start = int(rng.integers(0, max(1, hl - rl))) if hl > rl else 0
        rd = list(hap[start:start + rl].ljust(rl, "A"))
        for k in range(rl):
            if rng.random() < 0.1:
                rd[k] = _BASES[int(rng.integers(4))]
        reads.append("".join(rd))
        quals.append(rng.integers(q_lo, q_hi, rl))
        haps.append(hap)
    return reads, quals, haps


# ---------------------------------------------------------------------------
# forward kernel vs oracle

def test_forward_f64_exact_on_small_cases():
    """The non-rescaled f64 wavefront reproduces the oracle to f64
    noise — the recurrence itself is exact."""
    rng = np.random.default_rng(1)
    reads, quals, haps = _random_pairs(12, rng)
    want = [oracle_log10(r, q, h)
            for r, q, h in zip(reads, quals, haps)]
    got = ph.forward_pairs(reads, quals, haps, dtype=np.float64)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-12)


def test_forward_f32_rescaled_vs_oracle_100_random_pairs():
    """Acceptance criterion: >=100 randomized read×hap pairs, the
    rescaled-f32 wavefront within 1e-4 log10 of the log-space
    oracle."""
    rng = np.random.default_rng(2)
    reads, quals, haps = _random_pairs(110, rng)
    want = np.array([oracle_log10(r, q, h)
                     for r, q, h in zip(reads, quals, haps)])
    got = ph.forward_pairs(reads, quals, haps, dtype=np.float32)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-4)


@pytest.mark.parametrize("qual", [4, 35, 93])
def test_forward_f32_underflow_edge_reads(qual):
    """A 300bp junk read's likelihood (~1e-300, far below f32's
    exponent range) survives the per-row rescaling to 1e-4 log10 —
    without rescaling f32 would flush to 0/-inf. q4 additionally
    drives the scale ramp in the opposite direction (slow bulk decay
    vs fast frontier decay), the overflow edge of the scheme."""
    rng = np.random.default_rng(3)
    read = "".join(rng.choice(_BASES, 300))
    hap = "".join(rng.choice(_BASES, 360))
    q = np.full(300, qual)
    want = oracle_log10(read, q, hap)
    got = ph.forward_pairs([read], [q], [hap], dtype=np.float32)[0]
    assert want < -100  # genuinely out of f32 linear range
    assert abs(got - want) < 1e-4


def test_forward_f32_overflow_side_stays_finite():
    """Near-certain alignments (likelihood ~1/|hap|, the top of the
    probability range) and read-longer-than-hap geometries stay
    finite and accurate."""
    hap = "ACGTACGTACGTACGTACGTACGTACGTAC"
    read = hap[2:26]
    got = ph.forward_pairs([read], [40], [hap], dtype=np.float32)[0]
    want = oracle_log10(read, 40, hap)
    assert abs(got - want) < 1e-4
    rng = np.random.default_rng(4)
    long_read = "".join(rng.choice(_BASES, 90))
    short_hap = "".join(rng.choice(_BASES, 30))
    got2 = ph.forward_pairs([long_read], [np.full(90, 30)],
                            [short_hap], dtype=np.float32)[0]
    want2 = oracle_log10(long_read, np.full(90, 30), short_hap)
    assert abs(got2 - want2) < 1e-4


def test_padding_and_bucketing_invariance_bitwise():
    """A pair's result is BITWISE identical computed alone, in a
    mixed-length batch (different bucket pad), or alongside any other
    pairs — the property the serve executor's cross-request
    coalescing rests on."""
    rng = np.random.default_rng(5)
    reads, quals, haps = _random_pairs(20, rng, max_r=40, max_h=70)
    batch = ph.forward_pairs(reads, quals, haps, dtype=np.float32)
    for i in (0, 7, 19):
        alone = ph.forward_pairs([reads[i]], [quals[i]], [haps[i]],
                                 dtype=np.float32)[0]
        assert alone == batch[i]
    # a coarser bucket granularity (more padding) changes nothing
    fat = ph.forward_pairs(reads, quals, haps, dtype=np.float32,
                           bucket=128)
    np.testing.assert_array_equal(fat, batch)


def test_bucket_pairs_geometry():
    reads = [np.zeros(5, np.uint8), np.zeros(33, np.uint8),
             np.zeros(20, np.uint8)]
    haps = [np.zeros(10, np.uint8), np.zeros(64, np.uint8),
            np.zeros(10, np.uint8)]
    groups = ph.bucket_pairs(reads, haps)
    assert groups == {(32, 32): [0, 2], (64, 64): [1]}


def test_forward_pairs_input_validation():
    with pytest.raises(ValueError, match="empty read"):
        ph.forward_pairs([""], [30], ["ACGT"])
    with pytest.raises(ValueError, match="empty haplotype"):
        ph.forward_pairs(["ACGT"], [30], [""])
    with pytest.raises(ValueError, match="lengths must match"):
        ph.forward_pairs(["ACGT"], [30, 30], ["ACGT", "ACGT"])


# ---------------------------------------------------------------------------
# genotype likelihoods

def test_genotype_pl_ordering_and_het_call():
    """Two haps, reads split between them → 0/1 with the PL vector in
    VCF order (0/0, 0/1, 1/1) and min PL = 0."""
    from goleft_tpu.models.genotype import genotype_likelihoods

    # 4 reads: 2 strongly ref (hap 0), 2 strongly alt (hap 1)
    ll = np.array([[-1.0, -9.0], [-1.0, -9.0],
                   [-9.0, -1.0], [-9.0, -1.0]])
    g = genotype_likelihoods(ll)
    assert g["best"] == (0, 1)
    assert g["pl"][1] == 0 and g["pl"][0] > 0 and g["pl"][2] > 0
    # hand-check 0/0: sum log10((10^la+10^lb)/2) with a == b == hap0
    want_00 = np.sum(ll[:, 0])
    assert g["gl"][0] == pytest.approx(want_00)
    # symmetric data → symmetric PLs
    assert g["pl"][0] == g["pl"][2]
    assert 0 < g["gq"] <= 99


def test_genotype_hom_and_no_reads():
    from goleft_tpu.models.genotype import genotype_likelihoods

    hom = genotype_likelihoods(np.array([[-1.0, -20.0]] * 5))
    assert hom["best"] == (0, 0)
    nil = genotype_likelihoods(np.zeros((0, 2)))
    assert list(nil["pl"]) == [0, 0, 0] and nil["gq"] == 0


def test_load_windows_validation():
    from goleft_tpu.models.genotype import load_windows

    ok = {"schema": "goleft-tpu.pairhmm-windows/1",
          "windows": [{"chrom": "c", "start": 0, "end": 9,
                       "haplotypes": ["ACGT"],
                       "reads": [{"seq": "AC", "quals": [30, 31]}]}]}
    ws = load_windows(ok)
    assert len(ws) == 1 and len(ws[0]["reads"]) == 1
    np.testing.assert_array_equal(ws[0]["reads"][0][1], [30, 31])
    with pytest.raises(ValueError, match="unsupported schema"):
        load_windows({"schema": "nope", "windows": []})
    bad = json.loads(json.dumps(ok))
    bad["windows"][0]["reads"][0]["quals"] = [30]
    with pytest.raises(ValueError, match="quals length"):
        load_windows(bad)
    bad2 = json.loads(json.dumps(ok))
    bad2["windows"][0]["haplotypes"] = []
    with pytest.raises(ValueError, match="non-empty"):
        load_windows(bad2)
    # phred+33 string quals decode
    s = json.loads(json.dumps(ok))
    s["windows"][0]["reads"][0]["quals"] = "I5"
    ws = load_windows(s)
    np.testing.assert_array_equal(ws[0]["reads"][0][1], [40, 20])


# ---------------------------------------------------------------------------
# candidates export / consumption

def _emdepth_matrix(path, n_windows=40, cnv_sample=3,
                    cnv_lo=10, cnv_hi=16):
    rng = np.random.default_rng(5)
    samples = [f"s{i}" for i in range(8)]
    with open(path, "w") as fh:
        fh.write("#chrom\tstart\tend\t" + "\t".join(samples) + "\n")
        for w in range(n_windows):
            row = rng.normal(50, 2, size=8)
            if cnv_lo <= w < cnv_hi:
                row[cnv_sample] *= 0.5
            fh.write(f"chr1\t{w * 500}\t{(w + 1) * 500}\t"
                     + "\t".join(f"{v:.1f}" for v in row) + "\n")


def test_emdepth_candidates_out_bed_and_json(tmp_path):
    from goleft_tpu.commands.emdepth_cmd import run_emdepth
    from goleft_tpu.models.candidates import read_candidates

    matrix = str(tmp_path / "m.tsv")
    _emdepth_matrix(matrix)
    bed = str(tmp_path / "c.bed")
    jsn = str(tmp_path / "c.json")
    run_emdepth(matrix, out=io.StringIO(), candidates_out=bed)
    run_emdepth(matrix, out=io.StringIO(), candidates_out=jsn)
    cb = read_candidates(bed)
    cj = read_candidates(jsn)
    assert cb == cj  # same records either encoding
    hit = [c for c in cb if c["sample"] == "s3"]
    assert hit and hit[0]["log2fc"] < -0.5
    assert json.load(open(jsn))["schema"].startswith(
        "goleft-tpu.cnv-candidates/1")


def test_dcnv_candidates_from_matrix_merges_runs():
    from goleft_tpu.models.candidates import candidates_from_matrix

    chroms = np.array(["chr1"] * 6 + ["chr2"] * 2)
    starts = np.array([0, 500, 1000, 40_000, 40_500, 41_000, 0, 500])
    ends = starts + 500
    norm = np.ones((8, 2))
    norm[0:3, 0] = 0.5    # chr1 run one (CN1)
    norm[3:5, 0] = 0.5    # chr1 run two, >30kb away → separate
    norm[6, 1] = 1.6      # chr2 single-window gain in sample 2
    recs = candidates_from_matrix(chroms, starts, ends, norm,
                                  ["a", "b"])
    a = [r for r in recs if r["sample"] == "a"]
    assert [(r["start"], r["end"]) for r in a] == \
        [(0, 1500), (40_000, 41_000)]
    assert all(r["cn"] == 1 for r in a)
    b = [r for r in recs if r["sample"] == "b"]
    assert b == [{"chrom": "chr2", "start": 0, "end": 500,
                  "sample": "b", "cn": 3,
                  "log2fc": pytest.approx(np.log2(1.6))}]


def test_candidates_bad_inputs(tmp_path):
    from goleft_tpu.models.candidates import read_candidates

    p = tmp_path / "x.bed"
    p.write_text("chr1\t0\t10\n")
    with pytest.raises(ValueError, match="not a goleft-tpu"):
        read_candidates(str(p))
    p2 = tmp_path / "x.json"
    p2.write_text('{"schema": "other/1"}')
    with pytest.raises(ValueError, match="unsupported schema"):
        read_candidates(str(p2))


# ---------------------------------------------------------------------------
# CLI + serve executor

def _windows_doc(path):
    rng = np.random.default_rng(6)
    ref = "".join(rng.choice(_BASES, 60))
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


def test_pairhmm_cli_scores_and_filters(tmp_path):
    from goleft_tpu.commands.pairhmm_cmd import run_pairhmm
    from goleft_tpu.models.candidates import write_candidates

    wpath = str(tmp_path / "w.json")
    _windows_doc(wpath)
    buf = io.StringIO()
    assert run_pairhmm(wpath, out=buf) == 0
    lines = buf.getvalue().splitlines()
    assert lines[0].startswith("#chrom\tstart\tend")
    assert len(lines) == 3
    het = lines[1].split("\t")
    assert het[5] == "0/1" and het[7].count(",") == 2
    # candidate filter drops the far window
    cand = str(tmp_path / "c.bed")
    write_candidates(cand, [{"chrom": "chr1", "start": 6000,
                             "end": 7000, "sample": "s", "cn": 1,
                             "log2fc": -1.0}], "test")
    buf2 = io.StringIO()
    assert run_pairhmm(wpath, candidates=cand, out=buf2) == 0
    assert len(buf2.getvalue().splitlines()) == 2


def test_serve_executor_coalesced_byte_identity(tmp_path):
    """Two requests coalesced into ONE executor batch return exactly
    the bytes each one-shot CLI run writes — the serve contract."""
    from goleft_tpu.commands.pairhmm_cmd import run_pairhmm
    from goleft_tpu.models.candidates import write_candidates
    from goleft_tpu.serve.executors import PairhmmExecutor

    w1 = str(tmp_path / "w1.json")
    w2 = str(tmp_path / "w2.json")
    _windows_doc(w1)
    _windows_doc(w2)
    cand = str(tmp_path / "c.bed")
    write_candidates(cand, [{"chrom": "chr1", "start": 6000,
                             "end": 7000, "sample": "s", "cn": 1,
                             "log2fc": -1.0}], "test")
    cli = {}
    for name, kwargs in (("plain", {}), ("cand", {"candidates": cand})):
        buf = io.StringIO()
        assert run_pairhmm(w1, out=buf, **kwargs) == 0
        cli[name] = buf.getvalue()
    ex = PairhmmExecutor()
    out = ex.run([{"input": w1}, {"input": w2},
                  {"input": w1, "candidates": cand}])
    assert out[0]["likelihoods_tsv"] == cli["plain"]
    assert out[1]["likelihoods_tsv"] == cli["plain"]  # same doc bytes
    assert out[2]["likelihoods_tsv"] == cli["cand"]
    assert out[0]["windows"] == 2 and out[2]["windows"] == 1


def test_serve_pairhmm_validation(tmp_path):
    from goleft_tpu.serve.server import ServeApp

    app = ServeApp(batch_window_s=0.001)
    try:
        code, body = app.handle("pairhmm", {})
        assert code == 400 and "input" in body["error"]
        code, body = app.handle("pairhmm", {"input": "/nope.json"})
        assert code == 400
        bad = tmp_path / "bad.json"
        bad.write_text('{"schema": "x"}')
        code, body = app.handle("pairhmm", {"input": str(bad)})
        assert code == 400 and "schema" in body["error"]
    finally:
        app.close()


# ---------------------------------------------------------------------------
# resilience: the pairhmm fault site

def test_injected_transient_fault_is_retried(tmp_path):
    """The chaos contract for the new dispatch path: a transient
    fault at the ``pairhmm`` site is retried by the RetryPolicy and
    the run's output is byte-identical to a clean run."""
    from goleft_tpu.commands.pairhmm_cmd import run_pairhmm
    from goleft_tpu.obs import get_registry
    from goleft_tpu.resilience import faults

    wpath = str(tmp_path / "w.json")
    _windows_doc(wpath)
    clean = io.StringIO()
    assert run_pairhmm(wpath, out=clean) == 0
    before = get_registry().counter("resilience.retries_total").value
    faults.install("pairhmm:after=1:times=1:transient")
    try:
        injected = io.StringIO()
        assert run_pairhmm(wpath, out=injected) == 0
    finally:
        faults.install(None)
    assert injected.getvalue() == clean.getvalue()
    assert get_registry().counter(
        "resilience.retries_total").value == before + 1
    assert get_registry().counter(
        "resilience.faults_injected.pairhmm_total").value >= 1


def test_injected_permanent_fault_quarantines_window(tmp_path):
    """A permanently-failing bucket quarantines exactly its windows:
    the rest of the table is emitted and the run exits 3 (the
    cohortdepth degraded-run contract)."""
    from goleft_tpu.commands.pairhmm_cmd import run_pairhmm
    from goleft_tpu.resilience import faults

    wpath = str(tmp_path / "w.json")
    _windows_doc(wpath)
    qpath = str(tmp_path / "q.json")
    faults.install("pairhmm:every=1:permanent:times=99")
    try:
        buf = io.StringIO()
        rc = run_pairhmm(wpath, out=buf, quarantine_out=qpath)
    finally:
        faults.install(None)
    assert rc == 3
    # both windows share one bucket here → both quarantined; only the
    # header remains, and the manifest names them
    assert buf.getvalue().startswith("#chrom")
    doc = json.load(open(qpath))
    assert doc["quarantined"] and \
        doc["quarantined"][0]["phase"] == "pairhmm"
