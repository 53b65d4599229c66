"""The compile observatory: every jit cache miss is a recorded event.

Serve workers re-jit their whole bucketed program portfolio on every
restart; this module says what that costs and which signatures are
hot, by making compilation a first-class, mergeable signal:

  - :class:`CompileTracker` (one per process, :data:`TRACKER`) is fed
    by the existing dispatch seams — ``obs.InstrumentedDispatch``,
    the pairhmm/rANS bucket dispatches, the serve executors' device
    stage (``plan/executor.py run_device_step``) — through
    :meth:`CompileTracker.observe`, a context manager around one
    dispatch;
  - a miss is detected two independent ways: a ``_cache_size()``
    delta on the wrapped jit (exact, when the seam holds the jit
    object) and jax's own ``jax.monitoring`` event for every
    executable built (its ``fun_name`` and seconds), attributed to the
    innermost active observation on the emitting thread — jax
    compiles synchronously on the dispatching thread, so thread-local
    attribution is sound. A compile seen by both detectors is counted
    once (``max``, not sum);
  - every miss becomes a structured :class:`CompileEvent` (program
    family, bucket signature, backend, wall duration, pid, trigger
    context), flows into the registry
    (``compile.events_total.<family>``,
    ``compile.seconds_total.<family>``, gauge
    ``compile.signatures_live``), and is recorded post-hoc as an
    ``xla.compile.<family>`` span nested inside whatever span was
    open at the dispatch — so stitched traces and flight trees show
    compile storms inline;
  - the accumulated (family, signature, backend) table is the
    **warmup manifest** (``goleft-tpu.warmup-manifest/1``): hot
    signatures ranked by hit count x compile cost, written atomically
    (tmp + fsync + rename) and merged-on-update — the exact artifact
    the ROADMAP "Elastic warm-start" item pre-compiles from. Served
    live at ``GET /debug/compiles``; exported/merged by ``goleft-tpu
    warmup export``.

The ``jax.monitoring`` listeners are registered lazily by the first
``observe()`` that runs with jax already imported (this module never
imports jax itself — the jax-free router/fleet processes import it);
``GOLEFT_TPU_NO_COMPILE_HOOK=1`` registers nothing, degrading
detection to the cache-delta path. A compile with no active
observation is still recorded (family ``unattributed``) — the
observatory is process-wide, not seam-wide.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field

from .metrics import get_registry
from .tracing import get_tracer

#: warmup-manifest schema id. /1 is the first version; a consumer
#: (the future warm-start path) must reject other majors loudly.
WARMUP_SCHEMA = "goleft-tpu.warmup-manifest/1"

#: bounded structured-event ring (a long-lived serve daemon must not
#: grow per-compile state; compiles are rare after warmup anyway)
MAX_EVENTS = 512

#: bounded distinct-signature table — same spirit as the rANS
#: MAX_BUCKET_SIGNATURES cap: beyond this the long tail is dropped
#: (and counted), never the process's memory
MAX_SIGNATURES = 1024


def family_of_dispatch(name: str) -> str:
    """Map a dispatch-span name onto its program family:
    ``serve.depth.dispatch`` -> ``depth``; anything else (a jit's own
    name like ``shard_depth_pipeline_cls_packed``) passes through."""
    fam = name
    if fam.startswith("serve."):
        fam = fam[len("serve."):]
    if fam.endswith(".dispatch"):
        fam = fam[:-len(".dispatch")]
    return fam


def canonical_signature(sig) -> str:
    """One stable string per bucket signature: JSON with tuples
    lowered to lists, sorted keys — the content key the warmup
    manifest and the merge are keyed by. ``None`` -> "" (a seam with
    no bucket geometry, e.g. a wrapped jit observed only by name)."""
    if sig is None:
        return ""
    if isinstance(sig, str):
        return sig

    def lower(x):
        if isinstance(x, (list, tuple)):
            return [lower(v) for v in x]
        if isinstance(x, dict):
            return {str(k): lower(v) for k, v in sorted(x.items())}
        if isinstance(x, (int, float, bool)) or x is None:
            return x
        return str(x)

    return json.dumps(lower(sig), sort_keys=True,
                      separators=(",", ":"))


@dataclass
class CompileEvent:
    """One detected jit cache miss (one observation window may carry
    several compiles — ``compiles`` counts them; the wall duration is
    the observation's, which a cold dispatch is dominated by)."""

    family: str
    signature: str
    backend: str
    duration_s: float
    compiles: int
    pid: int
    trigger: str
    ts: float  # epoch seconds
    names: tuple = ()  # jit names from the monitoring feed, bounded

    def to_dict(self) -> dict:
        return {
            "family": self.family, "signature": self.signature,
            "backend": self.backend,
            "duration_s": round(self.duration_s, 6),
            "compiles": self.compiles, "pid": self.pid,
            "trigger": self.trigger, "ts": round(self.ts, 3),
            "names": list(self.names),
        }


class _Observation:
    """The thread-local record of one in-flight observe() window."""

    __slots__ = ("family", "signature", "trigger", "names")

    def __init__(self, family: str, signature: str, trigger: str):
        self.family = family
        self.signature = signature
        self.trigger = trigger
        self.names: list[str] = []


class _ObsStack(threading.local):
    def __init__(self):
        self.stack: list[_Observation] = []


class CompileTracker:
    """Process-wide compile accounting: the observe() seam, the
    structured event ring, the (family, signature, backend) stats
    table behind /debug/compiles and the warmup manifest."""

    def __init__(self, registry=None, tracer=None):
        self._lock = threading.Lock()
        self._ctx = _ObsStack()
        self._events: deque[CompileEvent] = deque(maxlen=MAX_EVENTS)
        # (family, signature, backend) -> {hits, compiles, seconds}
        self._stats: dict[tuple, dict] = {}
        self.events_total = 0
        self.compiles_total = 0
        self.signatures_dropped = 0
        self._registry = registry
        self._tracer = tracer
        self._backend: str | None = None

    # the registry/tracer default to the process-wide singletons but
    # resolve lazily so a test tracker can inject private ones
    def _reg(self):
        return self._registry if self._registry is not None \
            else get_registry()

    def _trc(self):
        return self._tracer if self._tracer is not None \
            else get_tracer()

    # ---- backend provenance (cached once; jax is loaded by the time
    # a compile can happen) ----

    def _backend_name(self) -> str:
        if self._backend is None:
            if "jax" not in sys.modules:
                return ""  # not cached: jax may load later
            try:
                import jax

                self._backend = jax.devices()[0].platform
            except Exception:  # noqa: BLE001 — provenance must never
                self._backend = "unknown"  # fail the dispatch
        return self._backend

    # ---- the observe() seam ----

    @contextlib.contextmanager
    def observe(self, family: str, signature=None, cache_size_fn=None,
                trigger: str = ""):
        """Wrap ONE dispatch: always counts a hit for (family,
        signature); when a compile is detected (cache-size delta
        and/or attributed monitoring events), records the
        CompileEvent, the registry counters and the nested
        ``xla.compile.<family>`` span. Exceptions pass through untouched — a failed dispatch
        that compiled first still cost the compile."""
        ensure_compile_hook()
        ob = _Observation(family, canonical_signature(signature),
                          trigger or family)
        size0 = None
        if cache_size_fn is not None:
            # jit._cache_size is private jax API: if an upgrade drops
            # it this raises, it never goes quiet
            size0 = int(cache_size_fn())
        self._ctx.stack.append(ob)
        t0 = time.perf_counter()
        try:
            yield ob
        finally:
            t1 = time.perf_counter()
            self._ctx.stack.pop()
            delta = 0
            if size0 is not None:
                delta = max(0, int(cache_size_fn()) - size0)
            # one compile seen by both detectors is ONE compile
            n = max(delta, len(ob.names))
            self._record(ob, n, t0, t1)

    def _record(self, ob: _Observation, n: int, t0: float,
                t1: float) -> None:
        key = (ob.family, ob.signature, self._backend_name())
        wall = t1 - t0
        with self._lock:
            rec = self._stats.get(key)
            if rec is None:
                if len(self._stats) >= MAX_SIGNATURES:
                    self.signatures_dropped += 1
                    if n == 0:
                        return
                    # a COMPILING signature always lands (evict
                    # nothing: compiles are the signal; the cap
                    # protects against hit-only cardinality)
                self._stats[key] = rec = {
                    "hits": 0, "compiles": 0, "compile_seconds": 0.0}
            rec["hits"] += 1
            if n:
                rec["compiles"] += n
                rec["compile_seconds"] += wall
                self.events_total += 1
                self.compiles_total += n
                ev = CompileEvent(
                    family=ob.family, signature=ob.signature,
                    backend=key[2], duration_s=wall, compiles=n,
                    pid=os.getpid(), trigger=ob.trigger,
                    ts=time.time(), names=tuple(ob.names[:8]))
                self._events.append(ev)
                live = sum(1 for r in self._stats.values()
                           if r["compiles"] > 0)
        if not n:
            return
        reg = self._reg()
        reg.counter(f"compile.events_total.{ob.family}").inc(n)
        reg.counter(f"compile.seconds_total.{ob.family}").inc(
            round(wall, 6))
        reg.gauge("compile.signatures_live").set(live)
        # the post-hoc span: parented under whatever span is open on
        # this thread RIGHT NOW — observe() runs inside the device
        # dispatch span, so flight trees and stitched traces show the
        # compile nested where the time actually went
        self._trc().record_span(
            f"xla.compile.{ob.family}", t0, t1, category="compile",
            family=ob.family, signature=ob.signature,
            compiles=n, backend=key[2], trigger=ob.trigger)

    # ---- the jax.monitoring feed ----

    def _on_compile_log(self, name: str, seconds: float = 0.0) -> None:
        """Log one executable built (compiled, or fetched from the
        persistent cache): attribute it to the emitting thread's
        innermost observation, or record it unattributed — the
        observatory misses nothing either way."""
        reg = self._reg()
        reg.counter("xla.compiles_total").inc()
        if seconds:
            reg.counter("xla.compile_seconds_total").inc(
                round(seconds, 6))
        stack = self._ctx.stack
        if stack:
            stack[-1].names.append(name)
            return
        # no seam around this compile (warmup pass, a direct jit):
        # synthesize an observation as long as the compile so it
        # still lands in the stats/events/counters
        ob = _Observation("unattributed", "", name)
        ob.names.append(name)
        t = time.perf_counter()
        self._record(ob, 1, t - seconds, t)

    # ---- inspection / export ----

    def stats(self) -> dict[tuple, dict]:
        with self._lock:
            return {k: dict(v) for k, v in self._stats.items()}

    def recent_events(self, n: int = 64) -> list[dict]:
        with self._lock:
            evs = list(self._events)[-n:]
        return [e.to_dict() for e in evs]

    def to_doc(self) -> dict:
        """The ``GET /debug/compiles`` body: the ranked warmup
        manifest plus the recent structured events and totals."""
        doc = build_warmup_manifest(self.stats())
        with self._lock:
            doc.update(
                events_total=self.events_total,
                compiles_total=self.compiles_total,
                signatures_dropped=self.signatures_dropped,
                pid=os.getpid(),
            )
        doc["events"] = self.recent_events()
        return doc

    def manifest_section(self) -> dict | None:
        """The run manifest's ``compiles`` block (omitted when the
        run never compiled anything — most warm-path invocations)."""
        stats = self.stats()
        if not any(r["compiles"] for r in stats.values()):
            return None
        top = build_warmup_manifest(stats)["signatures"][:16]
        with self._lock:
            return {
                "events_total": self.events_total,
                "compiles_total": self.compiles_total,
                "seconds_total": round(
                    sum(r["compile_seconds"]
                        for r in stats.values()), 4),
                "signatures": top,
            }

    def reset(self) -> None:
        """Tests only."""
        with self._lock:
            self._events.clear()
            self._stats.clear()
            self.events_total = 0
            self.compiles_total = 0
            self.signatures_dropped = 0


#: the process-wide tracker every dispatch seam feeds
TRACKER = CompileTracker()


def get_tracker() -> CompileTracker:
    return TRACKER


@contextlib.contextmanager
def observe(family: str, signature=None, cache_size_fn=None,
            trigger: str = ""):
    """Module-level convenience over :data:`TRACKER`."""
    with TRACKER.observe(family, signature=signature,
                         cache_size_fn=cache_size_fn,
                         trigger=trigger) as ob:
        yield ob


# ------------------------------------------------ jax.monitoring feed

#: jax's own event for one executable built — compiled, or fetched
#: from the persistent cache. It fires on the dispatching thread, with
#: the jit's name as ``fun_name`` ("jit(name)") and the seconds spent
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "xla.cache_hits_total",
    "/jax/compilation_cache/cache_misses": "xla.cache_misses_total",
}

_HOOK_LOCK = threading.Lock()
_HOOK = False


def _on_duration(event, duration, fun_name="", **_kw):
    if event == _COMPILE_EVENT:
        TRACKER._on_compile_log(fun_name, duration)


def _on_event(event, **_kw):
    counter = _CACHE_EVENTS.get(event)
    if counter:
        TRACKER._reg().counter(counter).inc()


def ensure_compile_hook() -> bool:
    """Register the process-wide ``jax.monitoring`` listeners once jax
    is loaded: every compile's name and seconds, and whether the
    persistent cache answered.

    Never imports jax itself (jax-free routers call observe()-guarded
    paths too): a no-op until ``jax`` appears in sys.modules. A jax
    without these listeners raises here, it never goes quiet.
    ``GOLEFT_TPU_NO_COMPILE_HOOK=1`` opts out entirely."""
    global _HOOK
    if _HOOK:
        return True
    if os.environ.get("GOLEFT_TPU_NO_COMPILE_HOOK"):
        return False
    if "jax" not in sys.modules:
        return False
    with _HOOK_LOCK:
        if not _HOOK:
            import jax

            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            jax.monitoring.register_event_listener(_on_event)
            _HOOK = True
    return True


# ---------------------------------------------------- warmup manifest

def _rank_key(entry: dict):
    # hot first: hits x compile cost, compile count and hits as
    # tiebreakers, then the content key for full determinism
    return (-entry["hits"] * entry["compile_seconds"],
            -entry["compiles"], -entry["hits"],
            entry["family"], entry["signature"], entry["backend"])


def build_warmup_manifest(stats: dict[tuple, dict]) -> dict:
    """Rank a tracker stats table into the warmup-manifest document.
    Hit-only entries (never compiled in this process) are kept — a
    restarted worker WILL pay them — but rank below anything with a
    measured compile cost at equal hits."""
    sigs = []
    for (family, signature, backend), rec in stats.items():
        sigs.append({
            "family": family, "signature": signature,
            "backend": backend, "hits": int(rec["hits"]),
            "compiles": int(rec["compiles"]),
            "compile_seconds": round(
                float(rec["compile_seconds"]), 6),
        })
    sigs.sort(key=_rank_key)
    for i, s in enumerate(sigs):
        s["rank"] = i + 1
    return {"schema": WARMUP_SCHEMA, "signatures": sigs}


def validate_warmup_manifest(doc: dict) -> dict:
    """Schema-check a warmup manifest (load + merge + the smoke all
    go through here). Raises ValueError with a precise message."""
    if not isinstance(doc, dict):
        raise ValueError("warmup manifest: not a JSON object")
    if doc.get("schema") != WARMUP_SCHEMA:
        raise ValueError(
            f"warmup manifest: schema {doc.get('schema')!r}, want "
            f"{WARMUP_SCHEMA!r}")
    sigs = doc.get("signatures")
    if not isinstance(sigs, list):
        raise ValueError("warmup manifest: 'signatures' must be a "
                         "list")
    for s in sigs:
        if not isinstance(s, dict):
            raise ValueError("warmup manifest: signature entries "
                             "must be objects")
        for k, typ in (("family", str), ("signature", str),
                       ("backend", str), ("hits", int),
                       ("compiles", int),
                       ("compile_seconds", (int, float))):
            if not isinstance(s.get(k), typ) \
                    or isinstance(s.get(k), bool):
                raise ValueError(
                    f"warmup manifest: entry missing/bad {k!r}: "
                    f"{s.get(k)!r}")
        if s["hits"] < 0 or s["compiles"] < 0 \
                or s["compile_seconds"] < 0:
            raise ValueError(
                "warmup manifest: negative tallies are impossible "
                f"(entry {s['family']}/{s['signature']})")
    return doc


def merge_warmup_docs(*docs: dict) -> dict:
    """Merge-on-update: sum hits/compiles/seconds per (family,
    signature, backend) key and re-rank — every tally in the merge is
    >= its value in any input (monotonicity, pinned by test), so
    repeated exports only ever sharpen the manifest."""
    acc: dict[tuple, dict] = {}
    for doc in docs:
        validate_warmup_manifest(doc)
        for s in doc["signatures"]:
            key = (s["family"], s["signature"], s["backend"])
            rec = acc.setdefault(key, {
                "hits": 0, "compiles": 0, "compile_seconds": 0.0})
            rec["hits"] += s["hits"]
            rec["compiles"] += s["compiles"]
            rec["compile_seconds"] += s["compile_seconds"]
    return build_warmup_manifest(acc)


def save_warmup_manifest(path: str, doc: dict) -> dict:
    """Atomic + durable write (tmp, fsync, rename): a SIGKILL at any
    instant leaves either the previous manifest or the new one —
    never a torn document. When ``path`` already holds a valid
    manifest the new doc is MERGED into it first (merge-on-update);
    an unreadable existing file is replaced, not crashed on."""
    validate_warmup_manifest(doc)
    try:
        doc = merge_warmup_docs(load_warmup_manifest(path), doc)
    except (OSError, ValueError):
        pass  # no/invalid predecessor: this doc IS the manifest
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w") as fh:
        json.dump(doc, fh, indent=1, sort_keys=True)
        fh.write("\n")
        fh.flush()
        os.fsync(fh.fileno())
    os.replace(tmp, path)
    return doc


def load_warmup_manifest(path: str) -> dict:
    with open(path) as fh:
        return validate_warmup_manifest(json.load(fh))


# the run manifest's `compiles` section: a run that compiled anything
# documents what and how long (None -> omitted for warm runs)
from .manifest import register_section  # noqa: E402 — import cycle
# guard: manifest.py imports only metrics/provenance/tracing

register_section("compiles", lambda: TRACKER.manifest_section())
