"""Backend/platform provenance: the ONE place that answers "what ran
this" — shared by the run manifest and the device-event spans, so
their platform/device fields can never drift apart.
"""

from __future__ import annotations

import threading

_lock = threading.Lock()
_cached: dict | None = None


def backend_provenance(refresh: bool = False) -> dict:
    """{platform, device, device_kind, device_count, jax,
    compile_cache_dir} for the live backend — or an ``{"error": ...}``
    record when no backend comes up (provenance must never crash the
    run it describes).

    Cached after the first successful look: the answer cannot change
    within a process, and the hot device-span path reads it per
    dispatch. NOTE: calling this initializes the jax backend — CLI
    device commands only reach it after ``take_backend``.
    """
    global _cached
    with _lock:
        if _cached is not None and not refresh:
            return dict(_cached)
    try:
        import jax

        devs = jax.devices()
        rec = {
            "platform": devs[0].platform,
            "device": str(devs[0]),
            "device_kind": devs[0].device_kind,
            "device_count": len(devs),
            "jax": jax.__version__,
            # where this process's persistent compile cache is (None:
            # it has none) — a run's compile seconds mean nothing
            # without it
            "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        }
    except Exception as e:  # noqa: BLE001 — degrade, don't crash
        return {"error": repr(e)}
    with _lock:
        _cached = rec
    return dict(rec)


def device_span_attrs() -> dict:
    """The attribute set every device-event span carries: backend,
    platform and device kind (the honest-evidence contract — a span
    that says 'compute' without saying on WHAT is how stale chip
    numbers survive three rounds)."""
    prov = backend_provenance()
    if "error" in prov:
        return {"platform": "unavailable"}
    return {"platform": prov["platform"],
            "device_kind": prov["device_kind"],
            "device_count": prov["device_count"]}


def env_provenance() -> dict:
    """Host/environment block for the run manifest."""
    import os
    import platform as _platform
    import sys

    try:
        cores = len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        cores = os.cpu_count()
    rec = {
        "python": sys.version.split()[0],
        "machine": _platform.machine(),
        "node": _platform.node(),
        "effective_cores": cores,
        "pid": os.getpid(),
    }
    knobs = {k: v for k, v in os.environ.items()
             if k.startswith(("GOLEFT_TPU_", "JAX_PLATFORM"))}
    if knobs:
        rec["env_knobs"] = knobs
    return rec
