"""The per-layer metrics of the dispatch hop and the decode wait: each
reader on hand-made input, and every metric of ``BENCHMARK.json`` against
its data file and reader. Run by hand, like ``test_benchmark.py``:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import importlib
import json
import os

import pytest

from reducers import counter_per_gbase, stage_spans

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def spec(name: str) -> dict:
    with open(f"{BENCH}/metrics/{name}.json") as fh:
        return json.load(fh)


def span(name, category, t0, t1):
    return {"name": name, "category": category, "t0": t0, "t1": t1}


SPANS = [
    span("host-decode", "stage", 0.0, 4.0),
    span("decode-wait", "wait", 0.0, 1.5),
    span("device-compute", "stage", 1.5, 3.0),
    span("pack", "transfer", 1.5, 1.75),
    span("pack", "stage", 0.0, 100.0),     # another layer's: not counted
    span("h2d", "transfer", 1.75, 1.875),
    span("device-wait", "transfer", 2.0, 2.5),
    span("d2h", "transfer", 2.5, 2.5625),
    span("unpack", "transfer", 2.5625, 3.0),
    span("decode-wait", "wait", 3.0, 3.5),
]


@pytest.mark.parametrize("metric,per_gbase", [
    ("decode_wait_s_per_gbase", (1.5 + 0.5) / 0.5),
    ("host_stage_s_per_gbase", (0.25 + 0.4375) / 0.5),
    ("h2d_s_per_gbase", 0.125 / 0.5),
    ("d2h_s_per_gbase", 0.0625 / 0.5),
])
def test_span_metrics_on_a_hand_made_span_list(metric, per_gbase):
    args = spec(metric)["args"]
    assert spec(metric)["reducer"] == "stage_spans"
    run = {"spans": SPANS, "gbases": 0.5}
    assert stage_spans.reduce(args, run) == pytest.approx(per_gbase)
    # a program without these spans (the parent) gives nothing, not 0
    parent = [s for s in SPANS if s["category"] == "stage"]
    assert stage_spans.reduce(args, {"spans": parent, "gbases": 0.5}) is None


@pytest.mark.parametrize("metric,counter", [
    ("h2d_mb_per_gbase", "xla.h2d_bytes_total"),
    ("d2h_mb_per_gbase", "xla.d2h_bytes_total"),
])
def test_counter_per_gbase(metric, counter):
    args = spec(metric)["args"]
    assert spec(metric)["reducer"] == "counter_per_gbase"
    assert args == {"counter": counter, "scale": 1e-6}
    counters = {"before": {counter: 3_000_000, "xla.compiles_total": 7},
                "after": {counter: 5_000_000, "xla.compiles_total": 7}}
    run = {"counters": counters, "gbases": 0.5}
    assert counter_per_gbase.reduce(args, run) == pytest.approx(4.0)
    # the counter was made during the window: before has no reading
    counters["before"].pop(counter)
    assert counter_per_gbase.reduce(args, run) == pytest.approx(10.0)
    # a counter absent, or no work done: nothing to read, never 0
    absent = {"before": {}, "after": {"xla.compiles_total": 7}}
    assert counter_per_gbase.reduce(
        args, {"counters": absent, "gbases": 0.5}) is None
    assert counter_per_gbase.reduce(
        args, {"counters": counters, "gbases": 0.0}) is None


def test_every_per_layer_metric_has_its_file_and_its_reader():
    with open(f"{ROOT}/BENCHMARK.json") as fh:
        bench = json.load(fh)
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        s = spec(m["name"])
        assert s["name"] == m["name"]
        reader = importlib.import_module(f"reducers.{s['reducer']}")
        assert callable(reader.reduce), m["name"]
        assert isinstance(s["args"], dict)
        assert set(m["workloads"]) <= cells
    assert sorted(f[:-5] for f in os.listdir(f"{BENCH}/metrics")) == sorted(
        m["name"] for m in bench["per_layer"])


def test_every_configuration_names_a_maker_comparators_and_work_that_import():
    """What a configuration names is there under ``makers/`` and
    ``comparators/`` with the calls the harness makes; every module under
    ``works/`` gives both counts (which of them a fixture uses, its maker
    says in ``meta.json``, and ``fixtures.build`` imports it there)."""
    with open(f"{ROOT}/BENCHMARK.json") as fh:
        bench = json.load(fh)
    for c in bench["configs"]:
        with open(f"{ROOT}/{c['file']}") as fh:
            cfg = json.load(fh)
        assert cfg["work_unit"], c["name"]
        maker = importlib.import_module(f"makers.{cfg['fixture']['maker']}")
        assert callable(maker.build) and callable(maker.expected), c["name"]
        assert isinstance(maker.CONTROLS, tuple), c["name"]
        for out in cfg["outputs"]:
            comparator = importlib.import_module(
                f"comparators.{out['compare']}")
            assert callable(comparator.differ), (c["name"], out["name"])
    kinds = sorted(f[:-3] for f in os.listdir(f"{BENCH}/works")
                   if f.endswith(".py"))
    assert "depth_shards" in kinds
    for kind in kinds:
        counted = importlib.import_module(f"works.{kind}")
        assert callable(counted.job_bytes) and callable(counted.job_units)
