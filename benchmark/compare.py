"""What decides ``correct``: every job's output files against the plain
reference's texts, byte for byte. The numbers compared are counts with
the limit 0 (an exact comparison), one per kind of output, plus the jobs
whose exit code was not 0."""

from __future__ import annotations


def lines_differ(got: str, want: str) -> int:
    """How many lines of ``got`` differ from ``want``'s, a missing or
    surplus line counting as one."""
    if got == want:
        return 0
    g, w = got.splitlines(), want.splitlines()
    return sum(a != b for a, b in zip(g, w)) + abs(len(g) - len(w))


def read(path: str) -> str:
    try:
        with open(path) as fh:
            return fh.read()
    except OSError:
        return ""


def compare_jobs(jobs: list[dict], expected: dict[str, str]) -> dict:
    """``jobs``: [{"rc", "outputs": {kind: path}}]; ``expected``:
    {kind: text}. Returns the numbers compared, {name: {"value",
    "limit"}}, summed over the jobs, and marks each job's ``ok``."""
    totals = {f"{kind}_lines_differ": 0 for kind in expected}
    nonzero = 0
    for job in jobs:
        bad = job["rc"] != 0
        nonzero += bad
        for kind, want in expected.items():
            n = lines_differ(read(job["outputs"][kind]), want)
            totals[f"{kind}_lines_differ"] += n
            bad = bad or n > 0
        job["ok"] = not bad
    numbers = {"jobs_exit_nonzero": {"value": nonzero, "limit": 0}}
    numbers.update({k: {"value": v, "limit": 0} for k, v in totals.items()})
    return numbers
