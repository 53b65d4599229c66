#!/usr/bin/env python3
"""The controls of ``correct``: the plain reference put in the program's
place with one stated guarantee broken, at the cell's own size, counted
by the same comparison a run uses. Every control has to read above the
limit (0 differing lines). No JAX, no BAM: the read lists come from the
seed as the fixture's do.

    python benchmark/control.py --workload depth30x.jobs --seeds 1,2,3
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import compare  # noqa: E402
import fixtures  # noqa: E402
import reference  # noqa: E402
from run import ROOT, by_name, load  # noqa: E402


def control_readings(config: dict, seed: int) -> dict:
    """{control: {"<kind>_lines_differ": n}} for one seed."""
    fx = config["fixture"]
    reads = [fixtures.read_list(fx, seed, k)
             for k in range(fx["distinct_samples"])]
    want, _ = fixtures.expected_texts(config, seed, reads=reads)
    names = {o["expected"]: o["name"] for o in config["outputs"]}
    out = {}
    for control in reference.CONTROLS:
        got, _ = fixtures.expected_texts(config, seed, control, reads=reads)
        out[control] = {
            f"{names[f]}_lines_differ": compare.lines_differ(got[f], want[f])
            for f in want}
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
    for seed in map(int, a.seeds.split(",")):
        print(json.dumps({"workload": a.workload, "seed": seed,
                          "controls": control_readings(config, seed)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
