"""Self seconds of the program's own spans in the window, per Gbase of
work completed there: the seconds of the spans named in ``spans`` less
those of the spans named in ``minus`` that lie inside them. A layer's
self time is its spans' duration less the part its child spans cover,
so it needs no trace: the dispatch hop's host share is its
``device-compute`` and ``pca`` spans less the ``device-wait`` spans
under them.

Thread-seconds, as in ``stage_spans``: spans of one name on parallel
threads add. A listed span that lies inside a listed span of another
name (a child in the same thread: one name never nests in itself) is
the outer span's time already and is counted once; a ``minus`` span is
taken off once, and only where it lies inside a counted span."""

from __future__ import annotations


def _named(spans: list[dict], names: list[str],
           category: str | None) -> list[dict]:
    return [s for s in spans if s["name"] in names
            and category in (None, s["category"])]


def _inside(s: dict, o: dict) -> bool:
    return o["t0"] <= s["t0"] and s["t1"] <= o["t1"]


def reduce(args: dict, run: dict) -> float | None:
    picked = _named(run["spans"], args["spans"], args.get("category"))
    if not picked or not run["gbases"]:
        return None
    outer = [s for s in picked
             if not any(o["name"] != s["name"] and _inside(s, o)
                        for o in picked)]
    minus = [s for s in _named(run["spans"], args.get("minus", []),
                               args.get("minus_category"))
             if any(_inside(s, o) for o in outer)]
    seconds = (sum(s["t1"] - s["t0"] for s in outer)
               - sum(s["t1"] - s["t0"] for s in minus))
    return seconds / run["gbases"]
