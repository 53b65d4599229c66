"""Run-scoped hierarchical tracing: the one span model every path uses.

Every CLI invocation and every serve request runs under a *trace* — a
string id grouping all the spans that invocation caused, across every
thread it touched. A *span* is one named wall-clock interval with
attributes and a parent: the CLI's run span parents the shard spans,
a shard's decode span parents nothing further, the serve batcher's
batch span parents the executors' decode/compute/format stages.

Design constraints (why this is not a logging framework):

  - recording must be cheap enough for the hot paths that already use
    ``StageTimer`` (one perf_counter pair + one lock-guarded append; a
    ``stage`` span adds one ``thread_time`` pair and two counter
    increments);
  - spans cross threads: the prefetch producers and the serve
    dispatcher record work on behalf of a consumer/request that lives
    on another thread, so the ambient context is thread-local but
    explicitly *portable* (:meth:`Tracer.capture` /
    :meth:`Tracer.attach`);
  - the buffer is bounded: a long-lived serve daemon must not grow
    per-request state, so the span ring drops oldest-first and counts
    what it dropped (``spans_dropped``);
  - export is Chrome trace-event JSON (the ``traceEvents`` array
    format) so ``--trace-out`` artifacts load directly in Perfetto /
    chrome://tracing next to the XLA profiler's own dumps.

Busy or waiting: a span of category ``stage`` (:data:`CPU_CATEGORIES`)
also reads its thread's CPU clock (``time.thread_time``) at open and
close. The CPU seconds ride as the ``cpu_s`` attribute and add to the
registry counter ``span.cpu_seconds_total.<span name>``; the span's
wall seconds add to ``span.wall_seconds_total.<span name>``. A reader
takes the off-CPU seconds (blocked on a lock, I/O, the device, or not
scheduled) as the growth of the second less that of the first: both
counters only grow, and a span whose CPU clock steps past its wall
clock (a kernel that ticks the thread clock in 10 ms steps) keeps its
excess in the sum, where the steps average out. ``transfer``, ``output``,
``wait`` and ``device`` spans and the run roots read no clock; nor does
a span opened with ``read_cpu=False``, which a call site passes where
its spans are too many and too short for two system calls each (under
a user-space kernel such as gVisor a ``thread_time`` is a system call
of some 6 us). There is no
switch: the reading is always on.

Stdlib-only; jax never imports here (device attributes are the
caller's business — see obs/provenance.py). A process that has already
imported jax also gets every open span as a ``TraceAnnotation`` of the
same name, so a ``jax.profiler`` trace (``depth --profile``) shows the
program's spans on the profiler's own clock beside the device's ops.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field

from .metrics import REGISTRY

# perf_counter gives monotonic durations; the offset maps them onto the
# epoch so exported timestamps line up across processes (and with the
# jax profiler's traces, which use epoch-based clocks)
_EPOCH_OFFSET = time.time() - time.perf_counter()

#: span categories whose spans read their thread's CPU clock
CPU_CATEGORIES = frozenset({"stage"})
#: prefixes of the per-span-name counters of those spans' CPU and wall
#: seconds: the off-CPU seconds are the second's growth less the first's
CPU_PREFIX = "span.cpu_seconds_total."
WALL_PREFIX = "span.wall_seconds_total."


def _profiler_annotation(name: str):
    """``jax.profiler.TraceAnnotation(name)`` where jax is already in
    the process (costs ~0.4 us while no profiler runs), else a no-op:
    this module never imports jax itself."""
    cls = getattr(getattr(sys.modules.get("jax"), "profiler", None),
                  "TraceAnnotation", None)
    return cls(name) if cls is not None else contextlib.nullcontext()


@dataclass
class Span:
    """One finished (or in-flight) named interval."""

    name: str
    span_id: int
    parent_id: int | None
    trace_id: str
    t0: float  # perf_counter seconds
    t1: float | None = None
    attrs: dict = field(default_factory=dict)
    thread_id: int = 0
    thread_name: str = ""
    category: str = ""

    def duration(self) -> float:
        return (self.t1 if self.t1 is not None
                else time.perf_counter()) - self.t0


class _Context(threading.local):
    """Per-thread ambient state: the active trace id and span stack."""

    def __init__(self):
        self.trace_id: str | None = None
        self.stack: list[Span] = []


@dataclass(frozen=True)
class SpanContext:
    """A portable snapshot of (trace, parent span) — what a worker
    thread attaches to record on behalf of the thread that captured
    it."""

    trace_id: str | None
    parent_id: int | None


class Tracer:
    """Process-wide span recorder with a bounded ring buffer.

    One instance (:data:`TRACER`) serves the whole process; tests may
    build private ones. All methods are thread-safe; the ambient
    context (current trace + span stack) is thread-local.
    """

    def __init__(self, max_spans: int = 100_000):
        self._spans: deque[Span] = deque(maxlen=max_spans)
        self.spans_dropped = 0
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._trace_ids = itertools.count(1)
        self._ctx = _Context()
        # completed-span listeners (the serve flight recorder): a plain
        # tuple read without the lock — empty for every process that
        # never registers one, so the hot path pays one truth test
        self._listeners: tuple = ()
        # when the memory plane arms it (obs.memplane.MemorySampler.
        # start), a zero-arg callable returning current RSS bytes:
        # every span then carries mem_delta_bytes / mem_peak_bytes
        # attributes (manifest 1.3). None — the default — keeps spans
        # byte-identical to every earlier round: the Perfetto goldens
        # of unsampled runs must not change.
        self.mem_probe = None
        # thread ident -> trace id for threads currently inside
        # trace(): the sampling profiler reads this to tag stacks
        # taken during a traced request with that request's id
        self._active_traces: dict[int, str] = {}

    # ---- trace scoping ----

    def new_trace_id(self, kind: str = "run") -> str:
        return f"{kind}-{os.getpid()}-{next(self._trace_ids)}"

    @contextlib.contextmanager
    def trace(self, name: str, kind: str = "run",
              trace_id: str | None = None,
              remote_parent: int | None = None, **attrs):
        """Run-scoped root: sets this thread's trace id and opens the
        root span; yields the root :class:`Span` (its ``trace_id`` is
        the invocation's id).

        ``trace_id``/``remote_parent`` adopt a REMOTE context (the
        ``x-goleft-trace`` header): the root joins the caller's trace
        instead of minting one, and the foreign parent span id is
        recorded as the ``remote_parent`` attribute — NOT as
        ``parent_id``, which stays process-local (a foreign id in the
        local parent chain could alias a local span; the fleet
        stitcher resolves ``remote_parent`` against the remote
        process's tree instead)."""
        prev = self._ctx.trace_id
        self._ctx.trace_id = trace_id if trace_id \
            else self.new_trace_id(kind)
        if remote_parent is not None:
            attrs = dict(attrs, remote_parent=remote_parent)
        ident = threading.get_ident()
        with self._lock:
            self._active_traces[ident] = self._ctx.trace_id
        try:
            with self.span(name, **attrs) as root:
                yield root
        finally:
            with self._lock:
                if prev is not None:
                    self._active_traces[ident] = prev
                else:
                    self._active_traces.pop(ident, None)
            self._ctx.trace_id = prev

    def current_trace_id(self) -> str | None:
        return self._ctx.trace_id

    def active_traces(self) -> dict[int, str]:
        """Snapshot of {thread ident: trace id} for every thread
        currently inside :meth:`trace` — how the sampling profiler
        ties a stack sample back to the request it interrupted."""
        with self._lock:
            return dict(self._active_traces)

    # ---- span recording ----

    @contextlib.contextmanager
    def span(self, name: str, category: str = "", read_cpu: bool = True,
             **attrs):
        """Open a child of this thread's innermost open span (or a
        trace root when the stack is empty). A span of one of
        :data:`CPU_CATEGORIES` records its thread's CPU seconds as
        ``cpu_s`` and counts them and its wall seconds by name, unless
        ``read_cpu`` is false."""
        th = threading.current_thread()
        parent = self._ctx.stack[-1] if self._ctx.stack else None
        # captured once: close() may disarm the probe mid-span, and a
        # delta needs both readings from the same probe
        probe = self.mem_probe
        rss0 = probe() if probe is not None else 0
        cpu0 = (time.thread_time()
                if read_cpu and category in CPU_CATEGORIES else None)
        sp = Span(
            name=name,
            span_id=next(self._ids),
            parent_id=parent.span_id if parent is not None else None,
            trace_id=self._ctx.trace_id or f"proc-{os.getpid()}",
            t0=time.perf_counter(),
            attrs=dict(attrs) if attrs else {},
            thread_id=th.ident or 0,
            thread_name=th.name,
            category=category,
        )
        self._ctx.stack.append(sp)
        try:
            with _profiler_annotation(name):
                yield sp
        finally:
            sp.t1 = time.perf_counter()
            if cpu0 is not None:
                cpu = time.thread_time() - cpu0
                sp.attrs["cpu_s"] = cpu
                REGISTRY.counter(CPU_PREFIX + name).inc(cpu)
                REGISTRY.counter(WALL_PREFIX + name).inc(sp.t1 - sp.t0)
            if probe is not None:
                rss1 = probe()
                # boundary-observed: delta across the span, peak of
                # the two readings (a spike inside the span shows in
                # the sampler's rss_peak gauge, not here)
                sp.attrs["mem_delta_bytes"] = rss1 - rss0
                sp.attrs["mem_peak_bytes"] = max(rss0, rss1)
            self._ctx.stack.pop()
            with self._lock:
                if len(self._spans) == self._spans.maxlen:
                    self.spans_dropped += 1
                self._spans.append(sp)
            for cb in self._listeners:
                try:
                    cb(sp)
                except Exception:  # noqa: BLE001 — a broken listener
                    pass           # must never fail the traced work

    def record_span(self, name: str, t0: float, t1: float,
                    category: str = "", **attrs) -> Span:
        """Record an already-measured interval as a completed span.

        The compile observatory discovers a compile only after the
        fact (cache-size delta / jax log record at observation exit),
        so it cannot open a ``with span()`` around it; this records
        the measured [t0, t1] perf_counter window post hoc, parented
        to this thread's innermost open span — the compile lands
        inside the device stage that triggered it in the flight tree.
        """
        th = threading.current_thread()
        parent = self._ctx.stack[-1] if self._ctx.stack else None
        sp = Span(
            name=name,
            span_id=next(self._ids),
            parent_id=parent.span_id if parent is not None else None,
            trace_id=self._ctx.trace_id or f"proc-{os.getpid()}",
            t0=t0,
            t1=t1,
            attrs=dict(attrs) if attrs else {},
            thread_id=th.ident or 0,
            thread_name=th.name,
            category=category,
        )
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self.spans_dropped += 1
            self._spans.append(sp)
        for cb in self._listeners:
            try:
                cb(sp)
            except Exception:  # noqa: BLE001 — a broken listener
                pass           # must never fail the recorded work
        return sp

    # ---- completed-span listeners ----

    def add_listener(self, cb) -> None:
        """Register ``cb(span)`` to run after every span completes
        (outside the ring lock, on the recording thread)."""
        with self._lock:
            if cb not in self._listeners:
                self._listeners = self._listeners + (cb,)

    def remove_listener(self, cb) -> None:
        # equality, not identity: a bound method is a fresh object at
        # every attribute access, but compares equal to itself
        with self._lock:
            self._listeners = tuple(
                c for c in self._listeners if c != cb)

    # ---- cross-thread propagation ----

    def capture(self) -> SpanContext:
        """Snapshot this thread's (trace, innermost span) for a worker
        thread to attach — how prefetch producers and the serve
        dispatcher parent their spans under the submitting request."""
        parent = self._ctx.stack[-1] if self._ctx.stack else None
        return SpanContext(
            trace_id=self._ctx.trace_id,
            parent_id=parent.span_id if parent is not None else None)

    @contextlib.contextmanager
    def attach(self, ctx: SpanContext | None):
        """Adopt a captured context on the current thread: spans
        recorded inside parent under ``ctx`` (a synthetic stack entry
        carries the foreign parent id)."""
        if ctx is None:
            yield
            return
        prev_trace = self._ctx.trace_id
        pushed = False
        if ctx.trace_id is not None:
            self._ctx.trace_id = ctx.trace_id
        if ctx.parent_id is not None and not self._ctx.stack:
            # a placeholder open span carrying only identity: children
            # parent to it, it is never itself recorded
            self._ctx.stack.append(Span(
                name="<attached>", span_id=ctx.parent_id,
                parent_id=None,
                trace_id=ctx.trace_id or f"proc-{os.getpid()}",
                t0=time.perf_counter()))
            pushed = True
        try:
            yield
        finally:
            if pushed:
                self._ctx.stack.pop()
            self._ctx.trace_id = prev_trace

    # ---- inspection / export ----

    def snapshot(self) -> list[Span]:
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self.spans_dropped = 0

    def summary(self, trace_id: str | None = None) -> dict:
        """{name: {seconds, calls}} totals over the buffered spans —
        the manifest's spans block (StageTimer.as_dict's shape)."""
        out: dict[str, dict] = {}
        for sp in self.snapshot():
            if trace_id is not None and sp.trace_id != trace_id:
                continue
            rec = out.setdefault(sp.name, {"seconds": 0.0, "calls": 0})
            rec["seconds"] += sp.duration()
            rec["calls"] += 1
        return {k: {"seconds": round(v["seconds"], 4),
                    "calls": v["calls"]}
                for k, v in sorted(out.items())}

    def to_chrome_trace(self, trace_id: str | None = None,
                        epoch_offset: float | None = None) -> dict:
        """The Chrome trace-event JSON object (Perfetto-loadable).

        Spans become ``ph: "X"`` complete events (ts/dur in
        microseconds); per-thread ``thread_name`` metadata events name
        the rows. ``trace_id`` filters to one invocation's spans (a
        serve daemon's ring holds many); attributes land in ``args``.
        """
        off = _EPOCH_OFFSET if epoch_offset is None else epoch_offset
        pid = os.getpid()
        events = []
        threads: dict[int, str] = {}
        for sp in self.snapshot():
            if trace_id is not None and sp.trace_id != trace_id:
                continue
            threads.setdefault(sp.thread_id, sp.thread_name)
            args = {"trace_id": sp.trace_id, "span_id": sp.span_id}
            if sp.parent_id is not None:
                args["parent_id"] = sp.parent_id
            args.update(sp.attrs)
            events.append({
                "name": sp.name,
                "cat": sp.category or "span",
                "ph": "X",
                "ts": round((sp.t0 + off) * 1e6, 3),
                "dur": round(sp.duration() * 1e6, 3),
                "pid": pid,
                "tid": sp.thread_id,
                "args": args,
            })
        events.sort(key=lambda e: e["ts"])
        meta = [{
            "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
            "args": {"name": nm or f"thread-{tid}"},
        } for tid, nm in sorted(threads.items())]
        # truncation is part of the evidence: a metadata event carries
        # the ring's drop count INSIDE traceEvents (Perfetto surfaces
        # event args; otherData is not reachable from the UI), so a
        # short trace says it is short instead of looking complete
        meta.append({
            "name": "spans_dropped", "ph": "M", "pid": pid, "tid": 0,
            "args": {"spans_dropped": self.spans_dropped},
        })
        return {
            "traceEvents": meta + events,
            "displayTimeUnit": "ms",
            "otherData": {"producer": "goleft-tpu obs",
                          "spans_dropped": self.spans_dropped},
        }

    def write_chrome_trace(self, path: str,
                           trace_id: str | None = None) -> None:
        doc = self.to_chrome_trace(trace_id=trace_id)
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as fh:
            json.dump(doc, fh)
        os.replace(tmp, path)


#: the process-wide tracer every module records into
TRACER = Tracer()


def get_tracer() -> Tracer:
    return TRACER
