"""A counter of the program's registry per Gbase of the runs it
counted: its growth between the two readings, less that of the counter
named in ``minus`` where one is, over the growth of ``cli.runs_total``
(one a ``goleft_tpu.cli.main``) times one job's Gbases. The same base in
every cell, whether the readings hold the window's jobs alone or a
traced job run after the window too. A stage's off-CPU seconds are its
spans' wall seconds less their CPU seconds, two counters that only
grow."""

from __future__ import annotations

RUNS = "cli.runs_total"


def _growth(run: dict, name: str) -> float:
    before, after = run["counters"]["before"], run["counters"]["after"]
    return after[name] - before.get(name, 0)


def reduce(args: dict, run: dict) -> float | None:
    after = run["counters"]["after"]
    names = [args["counter"], RUNS] + (
        [args["minus"]] if "minus" in args else [])
    if any(n not in after for n in names):
        # a program without the counters (the parent of the PR that
        # brought them) has nothing to read
        return None
    runs = _growth(run, RUNS)
    if not runs or not run["job_gbases"]:
        return None
    delta = _growth(run, args["counter"])
    if "minus" in args:
        delta -= _growth(run, args["minus"])
    return delta * args.get("scale", 1.0) / (runs * run["job_gbases"])
