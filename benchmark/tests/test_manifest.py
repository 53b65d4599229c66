"""``BENCHMARK.json`` against the files it names, one case an entry.
Imports no JAX and nothing of ``goleft_tpu``, and takes seconds.
(ISSUE 28 asked for it as ``tests/test_benchmark_manifest.py``, in the
tier-1 suite; a benchmark PR may add no file outside ``benchmark/``, so
it waits here for a later PR to move it: PERF.md section 7.)
"""

import importlib
import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
with open(f"{ROOT}/BENCHMARK.json") as _fh:
    MANIFEST = json.load(_fh)
CONFIG_KEYS = ("source", "deployment", "argv", "outputs", "guarantees",
               "reduced", "assumed", "work_unit")


def ids(entries):
    return [e["name"] for e in entries]


def load(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


@pytest.mark.parametrize("entry", MANIFEST["configs"],
                         ids=ids(MANIFEST["configs"]))
def test_a_configuration_has_its_file_and_states_its_keys(entry):
    assert entry["file"].startswith(tuple(
        f"{p}/" for p in MANIFEST["paths"]))
    cfg = load(f"{ROOT}/{entry['file']}")
    assert cfg["name"] == entry["name"]
    assert [k for k in CONFIG_KEYS if not cfg.get(k)] == []
    # every cut of scale the manifest lists is explained in the file
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert cfg["fixture"]["maker"]
    for out in cfg["outputs"]:
        assert [k for k in ("name", "file", "expected", "compare")
                if not out.get(k)] == [], out


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=ids(MANIFEST["workloads"]))
def test_a_cell_has_its_configuration_and_its_traffic_file(cell):
    assert cell["config"] in ids(MANIFEST["configs"])
    mix = load(f"{BENCH}/traffic/{cell['traffic']}.json")
    assert mix["name"] == cell["traffic"]
    assert os.path.exists(f"{BENCH}/drivers/{mix['driver']}.py")


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=ids(MANIFEST["per_layer"]))
def test_a_per_layer_metric_has_its_file_reducer_and_cells(metric):
    spec = load(f"{BENCH}/metrics/{metric['name']}.json")
    assert spec["name"] == metric["name"]
    reducer = importlib.import_module(f"reducers.{spec['reducer']}")
    assert callable(reducer.reduce)
    assert isinstance(spec["args"], dict)
    assert metric["workloads"]
    assert set(metric["workloads"]) <= set(ids(MANIFEST["workloads"]))
    assert metric["moves"] in ids(MANIFEST["end_to_end"])


def test_what_this_file_imports_brings_no_jax_and_no_program():
    readers = sorted({load(f"{BENCH}/metrics/{m['name']}.json")["reducer"]
                      for m in MANIFEST["per_layer"]})
    code = (f"import sys, importlib; sys.path.insert(0, {BENCH!r}); "
            f"[importlib.import_module('reducers.' + r) for r in {readers}]; "
            "sys.exit(any(m in sys.modules for m in ('jax', 'goleft_tpu')))")
    assert subprocess.run([sys.executable, "-c", code]).returncode == 0
