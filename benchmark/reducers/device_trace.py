"""From a ``jax.profiler`` trace (.xplane.pb) to device numbers.

``summarize`` reads the device planes (``/device:TPU:<n>``): the union of
the intervals in which an operation ran (line ``XLA Ops``) is the busy
time, the events of line ``XLA Modules`` are the executables. Transfers
are not modules. Nothing here is a host-clock time except the traced
window's length, which the caller passes in; the trace's own clock starts
at the profiler's start, and ``anchor`` (a ``TraceAnnotation`` the driver
wraps the traced job in) ties it to the program's spans.
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
DEVICE_LINES = {"XLA Ops": "ops", "XLA Modules": "modules"}


def merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def read_planes(path: str) -> dict:
    """{"devices": {plane: {"ops": [(name, s, e)], "modules": [...]}},
    "anchors": {name: (s, e)}, "structure": {plane: {line: n_events}}};
    seconds on the trace's clock. A host plane has one line a thread,
    several of them with one name."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, anchors, structure = {}, {}, {}
    for plane in data.planes:
        counts = structure.setdefault(plane.name, {})
        is_device = bool(DEVICE_PLANE.match(plane.name))
        if is_device:
            devices[plane.name] = {"ops": [], "modules": []}
        for line in plane.lines:
            events = [(e.name, e.start_ns * 1e-9,
                       (e.start_ns + e.duration_ns) * 1e-9)
                      for e in line.events]
            counts[line.name] = counts.get(line.name, 0) + len(events)
            if is_device and line.name in DEVICE_LINES:
                devices[plane.name][DEVICE_LINES[line.name]] += events
            elif plane.name.startswith("/host:"):
                anchors.update({n: (s, e) for n, s, e in events
                                if n.startswith("bench.")})
    return {"devices": devices, "anchors": anchors, "structure": structure}


def complement(spans, lo: float, hi: float) -> list[tuple[float, float]]:
    out, t = [], lo
    for s, e in spans:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def summarize(path: str, window_s: float, chips: int,
              anchor: str = "bench.job") -> dict | None:
    """Busy seconds (averaged over the chips), module and op seconds by
    name, and the idle gaps, all inside the anchor's interval where the
    trace has it. None when no operation ran on a device."""
    planes = read_planes(path)
    anchored = anchor in planes["anchors"]
    lo, hi = planes["anchors"].get(anchor, (float("-inf"), float("inf")))
    busy, modules, ops, gaps = [], {}, {}, []
    for dev in planes["devices"].values():
        spans = [(max(s, lo), min(e, hi)) for s, e in merge(
            [(s, e) for _, s, e in dev["ops"] or dev["modules"]])
            if e > lo and s < hi]
        if not spans:
            continue
        busy.append(sum(e - s for s, e in spans))
        for key, total in (("modules", modules), ("ops", ops)):
            for name, s, e in dev[key]:
                if e > lo and s < hi:
                    total[name] = total.get(name, 0.0) + (e - s)
        # without the anchor, only the gaps between operations are known
        gaps += complement(spans, lo if anchored else spans[0][0],
                           hi if anchored else spans[-1][1])
    if not busy:
        return None
    return {
        "busy_s": sum(busy) / max(chips, len(busy)),
        "window_s": window_s,
        "anchor": planes["anchors"].get(anchor),
        "modules": sorted(modules.items(), key=lambda kv: -kv[1]),
        "ops": sorted(ops.items(), key=lambda kv: -kv[1]),
        "gaps": sorted(gaps, key=lambda g: g[0] - g[1]),
        "structure": planes["structure"],
    }


def module_seconds(trace: dict, regex: str) -> float:
    pat = re.compile(regex)
    return sum(sec for name, sec in trace["modules"] if pat.search(name))


def reduce(args: dict, run: dict) -> float | None:
    from work import job_bytes, job_units, peak

    trace = run.get("trace")
    if not trace:
        return None
    what = args["quantity"]
    if what == "idle_percent":
        return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    kernel_s = module_seconds(trace, args.get("module_regex", ".*"))
    if not kernel_s:
        return None
    if what == "module_ms_per_sample_shard":
        return 1e3 * kernel_s / job_units(run["meta"])
    if what == "hbm_roofline_percent":
        least_s = job_bytes(run["meta"]) / peak(run["device"]["kind"],
                                                "hbm_bytes_per_s")
        return 100.0 * least_s / kernel_s
    raise ValueError(f"unknown quantity {what!r}")
