"""A counter of the program's registry, after the window less before,
per Gbase of work completed there: a raw delta grows with the jobs a
window holds, so a faster program would read worse."""

from __future__ import annotations


def reduce(args: dict, run: dict) -> float | None:
    name = args["counter"]
    before, after = run["counters"]["before"], run["counters"]["after"]
    if name not in after or not run["gbases"]:
        # a program without the counter (the parent of the PR that
        # brought it) has nothing to read
        return None
    delta = after[name] - before.get(name, 0)
    return delta * args.get("scale", 1.0) / run["gbases"]
