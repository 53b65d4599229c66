"""goleft_tpu.obs — the unified tracing & metrics subsystem.

One observability layer for every execution path (CLI one-shot,
prefetched cohort, warm serve batch):

  - :mod:`~goleft_tpu.obs.tracing` — run-scoped hierarchical spans
    with cross-thread propagation + Chrome/Perfetto export
    (``--trace-out``)
  - :mod:`~goleft_tpu.obs.metrics` — the process-wide registry of
    counters/gauges/histograms (``--metrics-out``, serve /metrics)
  - :mod:`~goleft_tpu.obs.provenance` — the one backend/platform
    answer the manifest and the device spans share
  - :mod:`~goleft_tpu.obs.manifest` — the per-run evidence document
  - :mod:`~goleft_tpu.obs.logging` — ``goleft-tpu.*`` logger tree +
    the CLI's ``--log-level`` config
  - :mod:`~goleft_tpu.obs.prometheus` — text-exposition rendering of
    a registry snapshot (the serve daemon's ``/metrics?format=prom``)

Import is jax-free and cheap (the CLI touches this before backend
bring-up); anything needing jax resolves it lazily per call.
"""

from __future__ import annotations

from .logging import configure as configure_logging, get_logger
from .metrics import (  # noqa: F401 — public API
    Counter, Gauge, Histogram, MetricsRegistry, REGISTRY, get_registry,
)
from .provenance import (  # noqa: F401
    backend_provenance, device_span_attrs, env_provenance,
)
from .tracing import (  # noqa: F401
    Span, SpanContext, TRACER, Tracer, get_tracer,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "REGISTRY",
    "Span", "SpanContext", "TRACER", "Tracer",
    "backend_provenance", "configure_logging", "capture", "attach",
    "device_span", "device_span_attrs", "env_provenance",
    "fetch", "get_logger", "get_registry", "get_tracer", "h2d", "span",
    "trace",
]


# ---- ambient-tracer conveniences (the process tracer) ----

def span(name: str, category: str = "", **attrs):
    """Context manager: a span on the process tracer."""
    return TRACER.span(name, category=category, **attrs)


def trace(name: str, kind: str = "run", trace_id: str | None = None,
          remote_parent: int | None = None, **attrs):
    """Context manager: a run-scoped root span + fresh trace id (or an
    ADOPTED one — ``trace_id``/``remote_parent`` attach the remote
    context a forwarded ``x-goleft-trace`` header carries)."""
    return TRACER.trace(name, kind=kind, trace_id=trace_id,
                        remote_parent=remote_parent, **attrs)


def capture() -> "SpanContext":
    return TRACER.capture()


def attach(ctx: "SpanContext | None"):
    return TRACER.attach(ctx)


# ---- device spans and the dispatch seam ----

def device_span(name: str, **attrs):
    """A span carrying the backend/platform/device-kind attribute set
    — for dispatch sites that already synchronize (np.asarray fetches
    etc.), where no extra fence is needed for the time to be honest."""
    return TRACER.span(name, category="device",
                       **device_span_attrs(), **attrs)


def _under_jit_trace() -> bool:
    """True when called during jax tracing (vmap/jit of a wrapped
    dispatch): the call is then part of the enclosing program's trace,
    not a dispatch, and the observers stay out of it."""
    try:
        import jax

        return not jax.core.trace_state_clean()
    except Exception:  # noqa: BLE001 — jax version drift: stay safe
        return False


class InstrumentedDispatch:
    """Transparent proxy over a jitted callable: ``__call__`` runs it
    under the compile and memory observers (serve's ``/debug/compiles``
    and ``/debug/memory`` read them); every other attribute
    (``_cache_size``, ``lower``, …) forwards to the wrapped function, so
    compile-cache cross-checks and AOT tooling keep working."""

    def __init__(self, fn, name: str):
        self.__wrapped__ = fn
        self._obs_name = name
        self.__name__ = name
        self.__doc__ = getattr(fn, "__doc__", None)

    def __call__(self, *args, **kwargs):
        if _under_jit_trace():
            return self.__wrapped__(*args, **kwargs)
        from .compiles import TRACKER, family_of_dispatch
        from .memplane import TRACKER as MEM_TRACKER

        family = family_of_dispatch(self._obs_name)
        cache_size = self.__wrapped__._cache_size
        # the memory plane shares this seam: buffers born during the
        # dispatch are attributed to its family (a bare yield until a
        # sampler arms the tracker)
        with TRACKER.observe(family,
                             cache_size_fn=cache_size,
                             trigger="dispatch"), \
                MEM_TRACKER.observe(family):
            return self.__wrapped__(*args, **kwargs)

    def __getattr__(self, item):
        return getattr(self.__wrapped__, item)

    def __repr__(self):
        return f"InstrumentedDispatch({self.__wrapped__!r})"


# ---- the dispatch hop: host <-> device transfers, unfenced ----
# A dispatch site wraps its own host staging in span("pack"|"unpack",
# category="transfer"); these two cover the hops in between. Category
# "transfer" keeps all five out of the stage totals they are children of.

def h2d(arrays, sharding=None) -> tuple:
    """Place host ``arrays`` on the device (``sharding``, or the default
    device) inside an ``h2d`` span that waits for the copies, and add
    their bytes to ``xla.h2d_bytes_total``."""
    import jax

    with span("h2d", category="transfer"):
        out = tuple(jax.device_put(a, sharding) for a in arrays)
        jax.block_until_ready(out)
    REGISTRY.counter("xla.h2d_bytes_total").inc(
        sum(a.nbytes for a in arrays))
    return out


def fetch(*results) -> tuple:
    """Device ``results`` as NumPy arrays: a ``device-wait`` span until
    they are ready, at the place that is about to fetch them (the fetch
    would wait there anyway, so no overlap is lost), then a ``d2h`` span
    for the copies, whose bytes go to ``xla.d2h_bytes_total``."""
    import jax
    import numpy as np

    with span("device-wait", category="transfer"):
        jax.block_until_ready(results)
    with span("d2h", category="transfer"):
        out = tuple(np.asarray(r) for r in results)
    REGISTRY.counter("xla.d2h_bytes_total").inc(
        sum(a.nbytes for a in out))
    return out
