"""Seconds of the program's own spans in the window, per Gbase of work
completed there. Thread-seconds: spans of parallel decode threads add."""

from __future__ import annotations


def reduce(args: dict, run: dict) -> float | None:
    picked = [s for s in run["spans"]
              if s["name"] in args["spans"]
              and s["category"] == args.get("category", s["category"])]
    if not picked or not run["gbases"]:
        return None
    per_gbase = sum(s["t1"] - s["t0"] for s in picked) / run["gbases"]
    if args.get("minus_traced_device_busy"):
        # the spans cover pack + H2D + kernel + D2H + unpack; what is left
        # after the device's own busy time is the host's share
        if not run.get("trace"):
            return None
        per_gbase -= run["trace"]["busy_s"] / run["job_gbases"]
    return per_gbase
