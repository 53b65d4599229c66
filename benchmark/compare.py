"""What decides ``correct``: every job's output files against the plain
reference's, each through the comparator its entry of the configuration's
``outputs`` names (``"compare"``): a module under ``benchmark/comparators``
with ``differ(got_path, want_path) -> int``, the lines that differ. The
numbers compared are those counts with the limit 0 (an exact comparison),
one per kind of output, plus the jobs whose exit code was not 0."""

from __future__ import annotations

import importlib


def comparator(name: str):
    return importlib.import_module(f"comparators.{name}")


def compare_jobs(jobs: list[dict], outputs: list[dict],
                 fixture_dir: str) -> dict:
    """``jobs``: [{"rc", "outputs": {kind: path}}]; ``outputs``: the
    configuration's, [{"name": kind, "compare", "expected"}]. Returns the
    numbers compared, {name: {"value", "limit"}}, summed over the jobs,
    and marks each job's ``ok``."""
    kinds = [(o["name"], comparator(o["compare"]).differ,
              f"{fixture_dir}/{o['expected']}") for o in outputs]
    totals = {f"{kind}_lines_differ": 0 for kind, _, _ in kinds}
    nonzero = 0
    for job in jobs:
        bad = job["rc"] != 0
        nonzero += bad
        for kind, differ, want in kinds:
            n = differ(job["outputs"][kind], want)
            totals[f"{kind}_lines_differ"] += n
            bad = bad or n > 0
        job["ok"] = not bad
    numbers = {"jobs_exit_nonzero": {"value": nonzero, "limit": 0}}
    numbers.update({k: {"value": v, "limit": 0} for k, v in totals.items()})
    return numbers
