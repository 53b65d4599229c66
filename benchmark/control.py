#!/usr/bin/env python3
"""The controls of ``correct``: the plain reference put in the program's
place with one stated guarantee broken, at the cell's own size, counted
by the same comparison a run uses. Every control has to read above the
limit (0 differing lines). No JAX, no input file: the configuration's
maker draws from the seed what its fixture would hold, and gives the
guarantees that can be broken (``CONTROLS``).

    python benchmark/control.py --workload depth30x.jobs --seeds 1,2,3
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import compare  # noqa: E402
import fixtures  # noqa: E402
from run import ROOT, by_name, load  # noqa: E402


def control_readings(config: dict, seed: int, tmp: str) -> dict:
    """{control: {"<kind>_lines_differ": n}} for one seed. ``tmp`` is a
    directory for the two texts of each comparison: the comparators read
    files, as they do in a run."""
    maker = fixtures.maker_of(config)
    want, _ = maker.expected(config, seed)
    for o in config["outputs"]:
        with open(f"{tmp}/want.{o['name']}", "w") as fh:
            fh.write(want[o["expected"]])
    out = {}
    for control in maker.CONTROLS:
        got, _ = maker.expected(config, seed, control)
        out[control] = {}
        for o in config["outputs"]:
            with open(f"{tmp}/got", "w") as fh:
                fh.write(got[o["expected"]])
            out[control][f"{o['name']}_lines_differ"] = compare.comparator(
                o["compare"]).differ(f"{tmp}/got", f"{tmp}/want.{o['name']}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    a = ap.parse_args(argv)
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    cell = by_name(bench["workloads"], a.workload, "workload")
    config = load(os.path.join(
        ROOT, by_name(bench["configs"], cell["config"], "config")["file"]))
    with tempfile.TemporaryDirectory() as tmp:
        for seed in map(int, a.seeds.split(",")):
            print(json.dumps({
                "workload": a.workload, "seed": seed,
                "controls": control_readings(config, seed, tmp)}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
