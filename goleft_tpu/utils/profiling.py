"""Profiling/observability: JAX profiler traces + per-stage wall clocks.

The reference's only profiling hook is an unconditional CPU pprof dump in
the cnveval CLI (cnveval/cmd/cnveval/cnveval.go:41-46, SURVEY.md §5); the
TPU rebuild gets first-class hooks: a ``trace(dir)`` context manager
around any pipeline (view with TensorBoard / xprof) and a ``StageTimer``
whose report shows where host decode vs device compute time goes.

``StageTimer`` is now a compatibility shim over the unified tracing
subsystem (:mod:`goleft_tpu.obs`): every ``stage`` use still feeds the
local totals/counts/spans this module always kept, AND records a real
hierarchical span on the process tracer — so a ``--trace-out`` run
shows the same stages on the Perfetto timeline that ``--profile``
logs as totals, in the right parent/thread rows.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import defaultdict, deque

from ..obs import get_tracer
from ..obs.logging import get_logger

log = get_logger("profile")


@contextlib.contextmanager
def trace(trace_dir: str | None):
    """jax.profiler trace context; no-op when trace_dir is falsy."""
    if not trace_dir:
        yield
        return
    import jax

    with jax.profiler.trace(trace_dir):
        yield
    log.info("profiler trace written to %s", trace_dir)


class StageTimer:
    """Accumulating wall-clock timers keyed by stage name.

    Thread-safe: the prefetch staging pipeline records spans from
    decode-pool worker threads concurrently with the consumer's compute
    spans. Every ``stage`` use also appends a ``(name, t0, t1)`` span
    (perf_counter seconds) so overlap between stages can be measured,
    not just per-stage totals — and mirrors the same interval onto the
    process tracer (:mod:`goleft_tpu.obs`), where it lands under the
    caller's current trace/span context.

    The span list is a RING: a long-lived holder (the serve daemon
    keeps one timer for its whole life) retains only the most recent
    ``max_spans`` intervals, counting evictions in ``spans_dropped``.
    ``totals``/``counts`` are unaffected by the bound — they accumulate
    forever — and ``wall()`` measures the retained window's extent.
    """

    def __init__(self, max_spans: int = 8192):
        self.totals: dict[str, float] = defaultdict(float)
        self.counts: dict[str, int] = defaultdict(int)
        self.spans: deque[tuple[str, float, float]] = \
            deque(maxlen=max_spans)
        self.spans_dropped = 0
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def stage(self, name: str):
        with get_tracer().span(name, category="stage"):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                t1 = time.perf_counter()
                with self._lock:
                    self.totals[name] += t1 - t0
                    self.counts[name] += 1
                    if len(self.spans) == self.spans.maxlen:
                        self.spans_dropped += 1
                    self.spans.append((name, t0, t1))

    def as_dict(self, ndigits: int = 4) -> dict:
        """{stage: {"seconds", "calls"}} snapshot (serve's /metrics
        ``stage_seconds``)."""
        with self._lock:
            return {
                name: {
                    "seconds": round(self.totals[name], ndigits),
                    "calls": self.counts[name],
                }
                for name in sorted(self.totals)
            }

    def wall(self) -> float:
        """Span-extent wall clock: last span end minus first span start
        over the RETAINED ring (0.0 when nothing was recorded)."""
        with self._lock:
            if not self.spans:
                return 0.0
            return (max(t1 for _, _, t1 in self.spans)
                    - min(t0 for _, t0, _ in self.spans))

    def report(self) -> str:
        lines = []
        for name in sorted(self.totals, key=self.totals.get, reverse=True):
            lines.append(
                f"{name:<24} {self.totals[name]:8.3f}s "
                f"({self.counts[name]} calls)"
            )
        return "\n".join(lines)

    def log_report(self) -> None:
        for line in self.report().splitlines():
            log.info("%s", line)


def percentiles(values, qs=(50, 95, 99), ndigits: int = 4) -> dict:
    """{"p50": ..., "p95": ..., "p99": ..., "max": ..., "count": n}
    nearest-rank percentiles over a sequence of seconds — the latency
    summary the serve daemon's /metrics endpoint and the obs registry's
    histograms share.
    Empty input returns {"count": 0} (no fabricated zeros)."""
    vals = sorted(float(v) for v in values)
    out: dict = {"count": len(vals)}
    if not vals:
        return out
    import math

    for q in qs:
        rank = max(1, min(len(vals), math.ceil(q / 100.0 * len(vals))))
        out[f"p{q:g}"] = round(vals[rank - 1], ndigits)
    out["max"] = round(vals[-1], ndigits)
    return out


def overlap_efficiency(timer: StageTimer, wall: float | None = None,
                       compute_stage: str = "compute") -> float | None:
    """How much of the non-compute pipeline work was hidden behind
    ``compute_stage``, in [0, 1].

    With per-stage totals summing to T and a measured wall clock W, the
    pipeline hid ``T - W`` seconds of work by overlapping stages; the
    maximum hideable is the total of every stage except compute (a
    perfectly overlapped pipeline's wall equals its compute total,
    assuming compute dominates). Returns None when nothing hideable was
    recorded (no producer-side spans). ``wall`` defaults to the timer's
    span extent.
    """
    totals = dict(timer.totals)
    hideable = sum(v for k, v in totals.items() if k != compute_stage)
    if hideable <= 0.0:
        return None
    if wall is None:
        wall = timer.wall()
    hidden = sum(totals.values()) - wall
    return max(0.0, min(1.0, hidden / hideable))
