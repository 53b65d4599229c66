"""Memory readings taken when the window closed."""

from __future__ import annotations


def reduce(args: dict, run: dict) -> float | None:
    value = run["device"].get(args["source"])
    if not value:
        return None
    return value * args.get("scale", 1.0)
