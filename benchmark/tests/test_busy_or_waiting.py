"""The per-layer metrics that read what each layer did itself: the
dispatch hop's self time from its own spans (``span_self``) and the
stages' off-CPU seconds from their counters (``counter_per_run_gbase``),
each reader on hand-made input. Run by hand, like ``test_benchmark.py``:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import json
import os

import pytest

from reducers import counter_per_run_gbase, span_self, stage_spans

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spec(name: str) -> dict:
    with open(f"{BENCH}/metrics/{name}.json") as fh:
        return json.load(fh)


def span(name, category, t0, t1):
    return {"name": name, "category": category, "t0": t0, "t1": t1}


# two threads' dispatches side by side, one a fetch inside a pca stage
SPANS = [
    span("device-compute", "stage", 0.0, 1.0),
    span("pack", "transfer", 0.0, 0.25),
    span("device-wait", "transfer", 0.5, 0.875),
    span("device-compute", "stage", 0.25, 0.75),   # the other thread's
    span("device-wait", "transfer", 0.375, 0.5),
    span("pca", "stage", 2.0, 3.0),
    span("device-compute", "stage", 2.25, 2.5),    # nested: counted once
    span("device-wait", "transfer", 2.25, 2.375),
    span("device-wait", "transfer", 5.0, 6.0),     # under no listed span
    span("pca", "wait", 4.0, 9.0),                 # another category
]


@pytest.mark.parametrize("spans,seconds", [
    (SPANS, (1.0 + 0.5 + 1.0) - (0.375 + 0.125 + 0.125)),
    # one listed span inside another of the list: its interval once
    ([span("pca", "stage", 0.0, 2.0),
      span("device-compute", "stage", 0.5, 1.5)], 2.0),
    # nothing to take off
    ([span("device-compute", "stage", 0.0, 0.5)], 0.5),
], ids=["two-threads-and-a-nested-fetch", "nested-counted-once",
        "no-wait"])
def test_dispatch_self_seconds(spans, seconds):
    s = spec("dispatch_self_s_per_gbase")
    assert s["reducer"] == "span_self"
    got = span_self.reduce(s["args"], {"spans": spans, "gbases": 0.5})
    assert got == pytest.approx(seconds / 0.5)
    assert got >= 0


def test_dispatch_self_without_its_spans_is_nothing():
    args = spec("dispatch_self_s_per_gbase")["args"]
    waits = [s for s in SPANS if s["name"] == "device-wait"]
    assert span_self.reduce(args, {"spans": waits, "gbases": 0.5}) is None
    assert span_self.reduce(args, {"spans": SPANS, "gbases": 0.0}) is None


def test_open_inputs_reads_its_stage_spans():
    s = spec("open_inputs_s_per_gbase")
    assert s["reducer"] == "stage_spans"
    spans = [span("open-inputs", "stage", 0.0, 0.5),
             span("open-inputs", "stage", 0.125, 0.5),  # another thread
             span("host-decode", "stage", 0.5, 1.0)]
    assert stage_spans.reduce(s["args"], {"spans": spans, "gbases": 2.0}) \
        == pytest.approx(0.875 / 2.0)
    # the parent records no such span: nothing, not 0
    assert stage_spans.reduce(
        s["args"], {"spans": spans[2:], "gbases": 2.0}) is None


@pytest.mark.parametrize("metric,stage", [
    ("host_decode_offcpu_s_per_gbase", "host-decode"),
    ("write_offcpu_s_per_gbase", "write-output"),
    ("pca_offcpu_s_per_gbase", "pca"),
])
def test_offcpu_seconds_per_run_gbase(metric, stage):
    s = spec(metric)
    assert s["reducer"] == "counter_per_run_gbase"
    wall = f"span.wall_seconds_total.{stage}"
    cpu = f"span.cpu_seconds_total.{stage}"
    assert s["args"] == {"counter": wall, "minus": cpu}
    # two runs between the readings (the window's and a traced one after
    # it), whatever the window's Gbases: 3.0 s of wall, 1.5 on the CPU
    counters = {"before": {wall: 1.5, cpu: 1.0, "cli.runs_total": 3},
                "after": {wall: 4.5, cpu: 2.5, "cli.runs_total": 5}}
    run = {"counters": counters, "job_gbases": 0.75, "gbases": 99.0}
    assert counter_per_run_gbase.reduce(s["args"], run) == \
        pytest.approx(1.5 / (2 * 0.75))
    # made during the window: before has no reading
    counters["before"] = {"cli.runs_total": 3}
    assert counter_per_run_gbase.reduce(s["args"], run) == \
        pytest.approx(2.0 / (2 * 0.75))
    # a program without any of the counters has nothing to read, never 0
    for missing in (wall, cpu, "cli.runs_total"):
        after = {k: v for k, v in counters["after"].items() if k != missing}
        assert counter_per_run_gbase.reduce(s["args"], {
            "counters": {"before": {}, "after": after},
            "job_gbases": 0.75}) is None
    # no run between the readings
    assert counter_per_run_gbase.reduce(s["args"], {
        "counters": {"before": counters["after"],
                     "after": counters["after"]},
        "job_gbases": 0.75}) is None


def test_offcpu_keeps_a_coarse_clocks_excess():
    """A thread clock that ticks in 10 ms steps reads a short busy span
    at a whole tick, past its wall: the excess stays in the sum, where
    the steps average out, so off-CPU can read below 0 for a stage that
    never waits."""
    args = {"counter": "span.wall_seconds_total.format",
            "minus": "span.cpu_seconds_total.format"}
    run = {"counters": {
        "before": {"cli.runs_total": 0},
        "after": {"span.wall_seconds_total.format": 0.05,
                  "span.cpu_seconds_total.format": 0.06,
                  "cli.runs_total": 1}},
        "job_gbases": 1.0}
    assert counter_per_run_gbase.reduce(args, run) == pytest.approx(-0.01)
