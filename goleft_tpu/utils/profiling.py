"""Profiling/observability: JAX profiler traces + per-stage wall clocks.

The reference's only profiling hook is an unconditional CPU pprof dump in
the cnveval CLI (cnveval/cmd/cnveval/cnveval.go:41-46, SURVEY.md §5); the
TPU rebuild gets first-class hooks: a ``trace(dir)`` context manager
around any pipeline (view with TensorBoard / xprof) and a ``StageTimer``
whose report shows where host decode vs device compute time goes.

``StageTimer`` is totals and counts over the unified tracing subsystem
(:mod:`goleft_tpu.obs`): every ``stage`` use records a real hierarchical
span on the process tracer and adds that span's seconds to the stage's
total — so a ``--trace-out`` run shows the same stages on the Perfetto
timeline that ``--profile`` logs as totals, in the right parent/thread
rows. The spans themselves live in the tracer's bounded ring alone.
"""

from __future__ import annotations

import contextlib
import threading
from collections import defaultdict

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
    """Accumulating wall-clock totals and call counts keyed by stage
    name.

    Thread-safe: the prefetch staging pipeline records stages from
    decode-pool worker threads concurrently with the consumer's compute
    stages. Each ``stage`` use is one ``stage`` span on the process
    tracer (:mod:`goleft_tpu.obs`), under the caller's current
    trace/span context; its seconds, from the span's own clock pair, go
    to ``totals``. ``totals``/``counts`` accumulate forever, so a
    long-lived holder (the serve daemon keeps one timer for its whole
    life) holds one number pair a stage name.
    """

    def __init__(self):
        self.totals: dict[str, float] = defaultdict(float)
        self.counts: dict[str, int] = defaultdict(int)
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def stage(self, name: str, read_cpu: bool = True):
        sp = None
        try:
            with get_tracer().span(name, category="stage",
                                   read_cpu=read_cpu) as sp:
                yield
        finally:
            if sp is not None:
                with self._lock:
                    self.totals[name] += sp.t1 - sp.t0
                    self.counts[name] += 1

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
