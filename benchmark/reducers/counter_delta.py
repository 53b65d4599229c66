"""A counter of the program's registry: after the window less before."""

from __future__ import annotations


def reduce(args: dict, run: dict) -> float | None:
    name = args["counter"]
    before, after = run["counters"]["before"], run["counters"]["after"]
    if name not in after:
        # the registry makes a counter on first use, and the warm-up job
        # loads programs: one that is still absent has no hook behind it
        return None
    return float(after[name] - before.get(name, 0))
