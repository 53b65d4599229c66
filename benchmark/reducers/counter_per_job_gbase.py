"""A counter of the program's registry per Gbase of the jobs it counted,
where the jobs between the two readings are not the window's alone: a
window that ends before job ``traced_job`` has a traced job run after
it, and the counters are read after that one. How many jobs lie between
the readings is taken from a counter that grows by a known number a job:
``jobs_counter`` grows by ``meta["work"][per_job]``."""

from __future__ import annotations


def reduce(args: dict, run: dict) -> float | None:
    before, after = run["counters"]["before"], run["counters"]["after"]
    name, jobs_name = args["counter"], args["jobs_counter"]
    if name not in after or jobs_name not in after:
        # a program without the counters has nothing to read
        return None
    jobs = ((after[jobs_name] - before.get(jobs_name, 0))
            / run["meta"]["work"][args["per_job"]])
    if not jobs or not run["job_gbases"]:
        return None
    delta = after[name] - before.get(name, 0)
    return delta * args.get("scale", 1.0) / (jobs * run["job_gbases"])
