"""Remote data plane tests: ByteSource semantics, identity parity,
staleness, fault classification, and io-layer parsing over the stub
object store.

Everything runs against :mod:`goleft_tpu.io.remote_stub` on loopback
— tier-1-cheap (no jax wake-up for the transport-layer tests; the
CRAM/BAM parse-parity tests use the same hermetic fixtures the
decode smoke builds).
"""

import gzip
import os

import pytest

from goleft_tpu.io import remote
from goleft_tpu.io.remote import StaleRemoteInput
from goleft_tpu.io.remote_stub import ObjectStore, StubServer
from goleft_tpu.resilience.policy import RetryPolicy


@pytest.fixture()
def srv():
    with StubServer() as s:
        yield s


@pytest.fixture(autouse=True)
def _fresh_identity_cache():
    remote.invalidate_identity()
    yield
    remote.invalidate_identity()
    # every test's server has a port of its own, so the idle keep-alive
    # connections to it are never reused: hang them up, or each holds a
    # handler thread of the stopped server for the rest of the process
    remote._POOL.clear()


DATA = bytes(range(256)) * 2048  # 512 KiB


# ---------------- scheme handling ----------------


def test_is_remote():
    assert remote.is_remote("http://x/y")
    assert remote.is_remote("https://x/y")
    assert remote.is_remote("s3://bucket/key")
    assert not remote.is_remote("/plain/path")
    assert not remote.is_remote("relative/path.bam")
    assert not remote.is_remote("ftp://x/y")
    assert not remote.is_remote(None)


def test_s3_maps_through_endpoint(monkeypatch):
    monkeypatch.setenv("GOLEFT_TPU_S3_ENDPOINT",
                       "http://127.0.0.1:1/")
    assert remote.resolve_url("s3://bucket/a/b.bam") == \
        "http://127.0.0.1:1/bucket/a/b.bam"
    monkeypatch.delenv("GOLEFT_TPU_S3_ENDPOINT")
    with pytest.raises(ValueError):
        remote.resolve_url("s3://bucket/a/b.bam")


def test_s3_reads_through_gateway(srv, monkeypatch):
    srv.put("bucket/obj.bin", DATA)
    monkeypatch.setenv("GOLEFT_TPU_S3_ENDPOINT", srv.url)
    assert remote.fetch_bytes("s3://bucket/obj.bin") == DATA


# ---------------- ByteSource semantics ----------------


def test_ranged_reads_byte_identical(srv):
    url = srv.put("obj.bin", DATA)
    with remote.open_source(url) as src:
        assert src.length == len(DATA)
        for off, n in ((0, 1), (17, 100), (1000, 65536),
                       (len(DATA) - 5, 50), (len(DATA), 10)):
            assert src.read(off, n) == DATA[off:off + n]
        assert src.read_all() == DATA


def test_local_source_same_interface(tmp_path):
    p = tmp_path / "f.bin"
    p.write_bytes(DATA)
    with remote.open_source(str(p)) as src:
        assert src.length == len(DATA)
        assert src.read(10, 20) == DATA[10:30]
        assert src.read_all() == DATA
        assert src.key()[1] == len(DATA)


def test_block_cache_and_readahead(srv, monkeypatch):
    monkeypatch.setenv("GOLEFT_TPU_FETCH_BLOCK", "4096")
    monkeypatch.setenv("GOLEFT_TPU_FETCH_READAHEAD", "2")
    url = srv.put("obj.bin", DATA)
    with remote.open_source(url) as src:
        src.read(0, 4096)       # miss: fetches blocks 0..2 coalesced
        n_after_first = srv.store.request_counts["obj.bin"]
        src.read(4096, 8192)    # blocks 1,2: both read-ahead hits
        assert srv.store.request_counts["obj.bin"] == n_after_first
        assert src.read(0, len(DATA)) == DATA


def test_range_ignoring_server_still_correct(srv):
    srv.store.ignore_range("obj.bin")
    url = srv.put("obj.bin", DATA)
    with remote.open_source(url) as src:
        assert src.read(100, 200) == DATA[100:300]
        assert src.read_all() == DATA


def test_read_range_and_fetch_bytes_local_remote(tmp_path, srv):
    p = tmp_path / "f.bin"
    p.write_bytes(DATA)
    url = srv.put("f.bin", DATA)
    assert remote.read_range(str(p), 7, 9) == \
        remote.read_range(url, 7, 9) == DATA[7:16]
    assert remote.fetch_bytes(str(p)) == remote.fetch_bytes(url)


def test_exists(tmp_path, srv):
    p = tmp_path / "f.bin"
    p.write_bytes(b"x")
    url = srv.put("f.bin", b"x")
    assert remote.exists(str(p))
    assert remote.exists(url)
    assert not remote.exists(str(tmp_path / "missing"))
    assert not remote.exists(srv.url + "/missing.bin")


# ---------------- identity ----------------


def test_remote_file_key_shape_mirrors_local(tmp_path, srv):
    p = tmp_path / "f.bin"
    p.write_bytes(DATA)
    url = srv.put("f.bin", DATA)
    from goleft_tpu.parallel.scheduler import file_key

    lk = file_key(str(p))
    rk = file_key(url)
    assert len(lk) == len(rk) == 3
    assert rk[0] == url
    assert rk[1] == len(DATA) == lk[1]
    assert rk[2].startswith("etag:")


def test_etag_change_is_new_identity(srv):
    url = srv.put("f.bin", DATA)
    k1 = remote.remote_file_key(url)
    srv.store.put("f.bin", DATA[:-1] + b"\x00")  # same length!
    remote.invalidate_identity(url)
    k2 = remote.remote_file_key(url)
    assert k1 != k2
    assert k1[1] == k2[1]  # only the etag token moved


def test_identity_ttl_caches_probes(srv):
    url = srv.put("f.bin", DATA)
    remote.remote_file_key(url)
    n = srv.store.request_counts["f.bin"]
    remote.remote_file_key(url)
    remote.remote_file_key(url)
    assert srv.store.request_counts["f.bin"] == n  # TTL cache hit


def test_file_key_parity_local_and_remote(tmp_path, srv):
    """Satellite: scheduler.file_key and the router's jax-free
    _file_key mirror produce IDENTICAL identities for local paths AND
    remote URLs — and an ETag change flows through both as a new
    identity (cache/checkpoint invalidation)."""
    from goleft_tpu.fleet.router import _file_key
    from goleft_tpu.parallel.scheduler import file_key

    p = tmp_path / "f.bin"
    p.write_bytes(DATA)
    url = srv.put("f.bin", DATA)
    assert _file_key(str(p)) == file_key(str(p))
    assert _file_key(url) == file_key(url)
    k1 = file_key(url)
    srv.store.put("f.bin", b"rewritten " + DATA)
    remote.invalidate_identity(url)
    assert file_key(url) != k1
    assert _file_key(url) == file_key(url)


def test_routing_file_key_parity_on_success(srv):
    """The routing-budget probe returns the SAME identity tuple as
    the full-budget one — fleet affinity stays parity-pinned."""
    url = srv.put("f.bin", DATA)
    assert remote.routing_file_key(url) == remote.remote_file_key(url)


def test_routing_probe_failure_is_negative_cached():
    """A dead endpoint costs routing one short probe per TTL: the
    failure is negative-cached, so subsequent probes raise without
    touching the network — and invalidate_identity clears it."""
    url = "http://127.0.0.1:1/nope.bam"
    with pytest.raises(OSError):
        remote.routing_file_key(url)
    assert url in remote._identity_neg
    with pytest.raises(OSError) as exc:
        remote.routing_file_key(url)
    assert "negative-cached" in str(exc.value)
    remote.invalidate_identity(url)
    assert url not in remote._identity_neg


def test_identity_cache_is_bounded(srv, monkeypatch):
    """Long-lived routers/workers touching many distinct URLs must
    not grow the identity cache without bound."""
    monkeypatch.setenv("GOLEFT_TPU_FETCH_IDENTITY_CACHE", "16")
    for i in range(40):
        remote.remote_file_key(srv.put(f"many/{i}.bin", b"x" * i))
    assert len(remote._identity_cache) <= 16


def test_affinity_key_survives_unreachable_url(monkeypatch):
    """Routing degrades to the raw path for a URL nobody answers —
    never a 500 out of the affinity computation."""
    monkeypatch.setenv("GOLEFT_TPU_FETCH_RETRIES", "0")
    monkeypatch.setenv("GOLEFT_TPU_FETCH_TIMEOUT_S", "0.2")
    from goleft_tpu.fleet.router import request_affinity_key

    url = "http://127.0.0.1:1/nope.bam"
    key = request_affinity_key("depth", {"bam": url})
    assert url in key


# ---------------- staleness + fault classification ----------------


def test_stale_mid_read_raises_not_mixes(srv, monkeypatch):
    monkeypatch.setenv("GOLEFT_TPU_FETCH_BLOCK", "4096")
    monkeypatch.setenv("GOLEFT_TPU_FETCH_READAHEAD", "0")
    url = srv.put("f.bin", DATA)
    src = remote.open_source(url)
    src.read(0, 10)
    srv.store.put("f.bin", b"v2" * (len(DATA) // 2))
    with pytest.raises(StaleRemoteInput):
        src.read(len(DATA) - 10, 10)  # uncached block: fresh request


def test_stale_classified_permanent():
    policy = RetryPolicy()
    exc = StaleRemoteInput("http://x/f", "etag:a", "etag:b")
    assert policy.classify(exc) == "permanent"
    assert isinstance(exc, ValueError)


def test_404_is_file_not_found(srv):
    with pytest.raises(FileNotFoundError):
        remote.fetch_bytes(srv.url + "/missing.bin")


def test_403_is_permission_error(srv):
    srv.put("f.bin", DATA)
    srv.store.fail("f.bin", times=3, status=403)
    with pytest.raises(PermissionError):
        remote.fetch_bytes(srv.url + "/f.bin")


def test_transient_503_retried_to_identical_bytes(srv):
    url = srv.put("f.bin", DATA)
    srv.store.fail("f.bin", times=1, status=503)
    assert remote.fetch_bytes(url) == DATA


def test_injected_fetch_fault_retried(srv):
    """The ``fetch`` fault site composes with GOLEFT_TPU_FAULTS like
    every other dispatch boundary."""
    from goleft_tpu.resilience import faults

    url = srv.put("f.bin", DATA)
    faults.install("fetch:after=1:transient")
    try:
        assert remote.fetch_bytes(url) == DATA
    finally:
        faults.install(None)


# ---------------- io-layer parsing over URLs ----------------


def test_fai_and_faidx_over_urls(tmp_path, srv):
    from goleft_tpu.io.fai import Faidx, read_fai, write_fai

    fa = tmp_path / "ref.fa"
    fa.write_text(">chr1\n" + "ACGT" * 25 + "\n" + "ACGT" * 25 + "\n")
    write_fai(str(fa))
    fa_url = srv.put("ref.fa", fa.read_bytes())
    srv.put("ref.fa.fai", (tmp_path / "ref.fa.fai").read_bytes())
    rl = read_fai(str(fa) + ".fai")
    rr = read_fai(fa_url + ".fai")
    assert [(r.name, r.length, r.offset) for r in rl] == \
        [(r.name, r.length, r.offset) for r in rr]
    with Faidx(str(fa)) as fl, Faidx(fa_url) as fr:
        assert fl.fetch("chr1", 10, 90) == fr.fetch("chr1", 10, 90)
        assert fl.names() == fr.names()


def test_bai_crai_over_urls(tmp_path, srv):
    from goleft_tpu.io.bai import read_bai
    from goleft_tpu.io.crai import read_crai

    from helpers import write_bam_and_bai

    bam = tmp_path / "s.bam"
    write_bam_and_bai(str(bam), [(0, pos, "50M", 60, 0)
                                 for pos in (10, 500, 900)],
                      ref_names=["chr1"], ref_lens=[10_000])
    bai_url = srv.put("s.bam.bai",
                      (tmp_path / "s.bam.bai").read_bytes())
    il = read_bai(str(bam) + ".bai")
    ir = read_bai(bai_url)
    assert il.mapped_total == ir.mapped_total
    crai_text = b"0\t1\t999\t100\t0\t500\n"
    crai_url = srv.put("s.cram.crai", gzip.compress(crai_text))
    local = tmp_path / "s.cram.crai"
    local.write_bytes(gzip.compress(crai_text))
    assert [a.tolist() for a in read_crai(str(local)).sizes()] == \
        [a.tolist() for a in read_crai(crai_url).sizes()]


def test_alignment_header_over_url(tmp_path, srv):
    from goleft_tpu.io.bam import read_alignment_header

    from helpers import write_bam

    bam = tmp_path / "s.bam"
    write_bam(str(bam), [(0, 10, "50M", 60, 0)],
              ref_names=["chr1"], ref_lens=[10_000])
    url = srv.put("s.bam", bam.read_bytes())
    assert read_alignment_header(url).ref_names == \
        read_alignment_header(str(bam)).ref_names


def test_open_bam_file_over_url_decodes_identically(tmp_path, srv):
    import numpy as np

    from goleft_tpu.io.bam import open_bam_file

    from helpers import write_bam_and_bai

    bam = tmp_path / "s.bam"
    write_bam_and_bai(str(bam), [(0, pos, "50M", 60, 0)
                                 for pos in (10, 500, 900)],
                      ref_names=["chr1"], ref_lens=[10_000])
    url = srv.put("s.bam", bam.read_bytes())
    srv.put("s.bam.bai", (tmp_path / "s.bam.bai").read_bytes())
    cl = open_bam_file(str(bam)).read_columns(tid=0, start=0,
                                              end=10_000)
    cr = open_bam_file(url).read_columns(tid=0, start=0, end=10_000)
    assert cl.n_reads == cr.n_reads == 3
    assert np.array_equal(cl.pos, cr.pos)


# ---------------- stub store contract ----------------


def test_stub_flip_after_is_deterministic():
    store = ObjectStore()
    store.put("f", b"v1")
    store.flip_after("f", 3, b"v2")
    with StubServer(store) as s:
        url = s.url + "/f"
        import urllib.request

        assert urllib.request.urlopen(url).read() == b"v1"
        assert urllib.request.urlopen(url).read() == b"v1"
        assert urllib.request.urlopen(url).read() == b"v2"


def test_stub_range_semantics():
    store = ObjectStore()
    store.put("f", DATA)
    with StubServer(store) as s:
        import urllib.request

        req = urllib.request.Request(
            s.url + "/f", headers={"Range": "bytes=10-19"})
        with urllib.request.urlopen(req) as r:
            assert r.status == 206
            assert r.headers["Content-Range"] == \
                f"bytes 10-19/{len(DATA)}"
            assert r.read() == DATA[10:20]
        req = urllib.request.Request(
            s.url + "/f",
            headers={"Range": f"bytes={len(DATA) + 5}-"})
        try:
            urllib.request.urlopen(req)
            raise AssertionError("416 expected")
        except urllib.error.HTTPError as e:
            assert e.code == 416
