"""Per-layer metric readers. A metric's file under ``benchmark/metrics``
names one of these modules and its arguments; each has
``reduce(args, run) -> float | None`` and returns None, never 0, when it
finds nothing to read. ``run`` is what ``run.py`` gathered: ``spans``,
``counters`` (before/after the window), ``gbases``, ``job_gbases``,
``meta`` (the fixture's), ``trace`` (``device_trace.summarize``'s result,
or None in an untraced run) and ``device``."""
