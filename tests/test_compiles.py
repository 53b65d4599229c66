"""Compile observatory: observe() accounting, the warmup manifest
round-trip (schema accept/reject, merge monotonicity, SIGKILL-proof
atomic writes), the nested xla.compile span, and the HTTP surface.

All jax-free: compiles are detected via injected cache_size_fn /
synthetic log feeds, so the tracker's contracts are provable in
milliseconds — the real-serve story is `make profile-smoke`.
"""

import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

import pytest

from goleft_tpu.obs.compiles import (
    WARMUP_SCHEMA, CompileTracker, build_warmup_manifest,
    canonical_signature, family_of_dispatch, load_warmup_manifest,
    merge_warmup_docs, save_warmup_manifest, validate_warmup_manifest,
)
from goleft_tpu.obs.metrics import MetricsRegistry
from goleft_tpu.obs.tracing import Tracer


def _tracker():
    return CompileTracker(registry=MetricsRegistry(), tracer=Tracer())


# ---------------- observe() accounting ----------------


def test_observe_counts_hits_and_cache_delta_compiles():
    t = _tracker()
    cache = {"n": 0}
    with t.observe("depth", signature=(64, 128),
                   cache_size_fn=lambda: cache["n"], trigger="test"):
        cache["n"] += 1  # a cold dispatch grew the jit cache
    with t.observe("depth", signature=(64, 128),
                   cache_size_fn=lambda: cache["n"], trigger="test"):
        pass  # warm: no growth
    (key, rec), = t.stats().items()
    assert key[0] == "depth" and key[1] == "[64,128]"
    assert rec["hits"] == 2
    assert rec["compiles"] == 1
    assert rec["compile_seconds"] > 0
    assert t.compiles_total == 1 and t.events_total == 1
    (ev,) = t.recent_events()
    assert ev["family"] == "depth" and ev["compiles"] == 1
    assert ev["pid"] == os.getpid() and ev["trigger"] == "test"


def test_observe_dedups_log_and_cache_detectors():
    # one compile seen by BOTH detectors must count once (max, not sum)
    t = _tracker()
    cache = {"n": 0}
    with t.observe("rans", signature="sig",
                   cache_size_fn=lambda: cache["n"]):
        cache["n"] += 1
        t._on_compile_log("jit(_decode_bucket_impl)")
    (_, rec), = t.stats().items()
    assert rec["compiles"] == 1
    (ev,) = t.recent_events()
    assert ev["names"] == ["jit(_decode_bucket_impl)"]


def test_unattributed_compile_log_still_lands():
    t = _tracker()
    t._on_compile_log("jit(warmup_thing)")
    (key, rec), = t.stats().items()
    assert key[0] == "unattributed"
    assert rec["compiles"] == 1
    snap = t._reg().snapshot()
    assert snap["counters"]["xla.compiles_total"] == 1


def test_observe_exception_still_records_the_compile():
    t = _tracker()
    cache = {"n": 0}
    with pytest.raises(RuntimeError):
        with t.observe("depth", cache_size_fn=lambda: cache["n"]):
            cache["n"] += 1
            raise RuntimeError("dispatch failed after compiling")
    (_, rec), = t.stats().items()
    assert rec["compiles"] == 1


def test_family_and_signature_canonicalization():
    assert family_of_dispatch("serve.depth.dispatch") == "depth"
    assert family_of_dispatch("pairhmm_forward") == "pairhmm_forward"
    assert canonical_signature(None) == ""
    assert canonical_signature("raw") == "raw"
    # tuples and lists canonicalize identically; dict keys sort
    assert canonical_signature((1, 2)) == canonical_signature([1, 2])
    assert canonical_signature({"b": 1, "a": (2,)}) == \
        '{"a":[2],"b":1}'


def test_compile_metrics_and_nested_span():
    reg = MetricsRegistry()
    tracer = Tracer()
    t = CompileTracker(registry=reg, tracer=tracer)
    cache = {"n": 0}
    with tracer.trace("batch.depth", kind="serve-batch"):
        with tracer.span("device.depth.dispatch", category="device"):
            with t.observe("depth", signature=(256,),
                           cache_size_fn=lambda: cache["n"]):
                cache["n"] += 2  # e.g. two engine variants compiled
    snap = reg.snapshot()
    assert snap["counters"]["compile.events_total.depth"] == 2
    assert snap["counters"]["compile.seconds_total.depth"] > 0
    assert snap["gauges"]["compile.signatures_live"] == 1
    spans = tracer.snapshot()
    comp = [s for s in spans if s.name == "xla.compile.depth"]
    assert len(comp) == 1
    dev = next(s for s in spans if s.name == "device.depth.dispatch")
    # the post-hoc compile span nests under the device dispatch span
    assert comp[0].parent_id == dev.span_id
    assert comp[0].category == "compile"
    assert comp[0].attrs["compiles"] == 2
    assert comp[0].attrs["signature"] == "[256]"


def test_manifest_section_omitted_until_a_compile_happens():
    t = _tracker()
    with t.observe("depth"):
        pass  # hit only
    assert t.manifest_section() is None
    with t.observe("depth", cache_size_fn=iter([0, 1]).__next__):
        pass
    sec = t.manifest_section()
    assert sec["compiles_total"] == 1
    assert sec["signatures"][0]["family"] == "depth"


# ---------------- warmup manifest ----------------


def _stats_one(family="depth", sig="[64]", backend="cpu", hits=3,
               compiles=1, seconds=0.5):
    return {(family, sig, backend): {
        "hits": hits, "compiles": compiles,
        "compile_seconds": seconds}}


def test_warmup_manifest_round_trip(tmp_path):
    doc = build_warmup_manifest(_stats_one())
    assert doc["schema"] == WARMUP_SCHEMA
    assert validate_warmup_manifest(doc) is doc
    p = str(tmp_path / "warm.json")
    save_warmup_manifest(p, doc)
    assert load_warmup_manifest(p)["signatures"] == doc["signatures"]


def test_warmup_manifest_ranking_is_hits_times_cost():
    stats = {
        ("depth", "[64]", "cpu"):
            {"hits": 100, "compiles": 1, "compile_seconds": 0.1},
        ("rans", "[0]", "cpu"):
            {"hits": 2, "compiles": 1, "compile_seconds": 30.0},
        ("depth", "[9999]", "cpu"):  # hit-only tail: ranks last
            {"hits": 500, "compiles": 0, "compile_seconds": 0.0},
    }
    sigs = build_warmup_manifest(stats)["signatures"]
    assert [s["family"] for s in sigs] == ["rans", "depth", "depth"]
    assert [s["rank"] for s in sigs] == [1, 2, 3]
    assert sigs[-1]["signature"] == "[9999]"


@pytest.mark.parametrize("mutate", [
    lambda d: d.update(schema="goleft-tpu.warmup-manifest/2"),
    lambda d: d.pop("signatures"),
    lambda d: d["signatures"].append("not-an-object"),
    lambda d: d["signatures"][0].pop("family"),
    lambda d: d["signatures"][0].update(hits="3"),
    lambda d: d["signatures"][0].update(hits=True),
    lambda d: d["signatures"][0].update(compiles=-1),
    lambda d: d["signatures"][0].update(compile_seconds=-0.5),
])
def test_warmup_manifest_schema_rejects(mutate):
    doc = build_warmup_manifest(_stats_one())
    mutate(doc)
    with pytest.raises(ValueError):
        validate_warmup_manifest(doc)


def test_merge_warmup_docs_is_monotone():
    a = build_warmup_manifest(_stats_one(hits=3, compiles=1,
                                         seconds=0.5))
    b = build_warmup_manifest({
        **_stats_one(hits=5, compiles=2, seconds=1.0),
        ("rans", "[7]", "cpu"):
            {"hits": 1, "compiles": 1, "compile_seconds": 2.0},
    })
    merged = merge_warmup_docs(a, b)
    by_key = {(s["family"], s["signature"]): s
              for s in merged["signatures"]}
    depth = by_key[("depth", "[64]")]
    assert depth["hits"] == 8 and depth["compiles"] == 3
    assert depth["compile_seconds"] == pytest.approx(1.5)
    # monotone: every merged tally >= its value in every input
    for doc in (a, b):
        for s in doc["signatures"]:
            m = by_key[(s["family"], s["signature"])]
            for k in ("hits", "compiles", "compile_seconds"):
                assert m[k] >= s[k]


def test_save_merges_into_existing_manifest(tmp_path):
    p = str(tmp_path / "warm.json")
    save_warmup_manifest(p, build_warmup_manifest(_stats_one(hits=2)))
    save_warmup_manifest(p, build_warmup_manifest(_stats_one(hits=3)))
    assert load_warmup_manifest(p)["signatures"][0]["hits"] == 5


def test_save_replaces_corrupt_predecessor(tmp_path):
    p = tmp_path / "warm.json"
    p.write_text("{torn garbage")
    save_warmup_manifest(str(p), build_warmup_manifest(_stats_one()))
    assert load_warmup_manifest(str(p))["signatures"][0]["hits"] == 3


_KILL_SCRIPT = """
import sys
from goleft_tpu.obs.compiles import (
    build_warmup_manifest, save_warmup_manifest)
path = sys.argv[1]
print("ready", flush=True)
i = 0
while True:  # rewrite forever until SIGKILLed mid-write
    i += 1
    save_warmup_manifest(path, build_warmup_manifest({
        ("depth", "[{}]".format(i % 7), "cpu"):
            {"hits": i, "compiles": 1, "compile_seconds": 0.01}}))
"""


def test_atomic_write_survives_sigkill(tmp_path):
    """The checkpoint torn-tail discipline, applied to the manifest:
    a writer SIGKILLed at a random instant leaves a parseable, valid
    document — tmp + fsync + rename can never tear it."""
    path = str(tmp_path / "warm.json")
    proc = subprocess.Popen(
        [sys.executable, "-c", _KILL_SCRIPT, path],
        stdout=subprocess.PIPE, cwd="/root/repo")
    try:
        assert proc.stdout.readline().strip() == b"ready"
        deadline = time.monotonic() + 10.0
        while not os.path.exists(path) \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.2)  # let a few hundred rewrites race
    finally:
        proc.kill()  # SIGKILL — no cleanup handlers run
        proc.wait(timeout=10)
    doc = load_warmup_manifest(path)  # parseable AND schema-valid
    assert doc["signatures"][0]["hits"] >= 1


# ---------------- HTTP surface ----------------


def test_debug_compiles_endpoint_serves_the_manifest():
    from goleft_tpu.serve.server import ServeApp, ServerThread

    app = ServeApp(batch_window_s=0.0, max_batch=1)
    # feed the PROCESS tracker (the endpoint serves the singleton)
    cache = {"n": 0}
    with app.compiles.observe("depth", signature=(64,),
                              cache_size_fn=lambda: cache["n"]):
        cache["n"] += 1
    try:
        with ServerThread(app) as url:
            with urllib.request.urlopen(url + "/debug/compiles",
                                        timeout=30) as r:
                doc = json.loads(r.read().decode())
        assert doc["schema"] == WARMUP_SCHEMA
        fams = [s["family"] for s in doc["signatures"]]
        assert "depth" in fams
        assert doc["compiles_total"] >= 1
        assert doc["pid"] == os.getpid()
        assert any(e["family"] == "depth" for e in doc["events"])
    finally:
        app.compiles.reset()
