"""Device milliseconds of the traced job's XLA modules whose names match
``module_regex``: a kernel that runs once a job, whatever the job's
units."""

from __future__ import annotations

from reducers import device_trace


def reduce(args: dict, run: dict) -> float | None:
    trace = run.get("trace")
    if not trace:
        return None
    seconds = device_trace.module_seconds(trace, args["module_regex"])
    return 1e3 * seconds if seconds else None
