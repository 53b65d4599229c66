"""The work a job's device pass needs, counted from the algorithm and the
fixture, not from the implementation: it reads the same whatever kernel,
wire format, bucket or batching the program uses.

What the algorithm is, the fixture says: ``meta["work"]["kind"]`` names a
module under ``benchmark/works`` that gives ``job_bytes(work)``, the least
HBM bytes of one job, and ``job_units(work)``, the pieces a kernel time is
quoted per (sample-shards, for the depth pipeline).
"""

from __future__ import annotations

import importlib
import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def _of(meta: dict):
    return importlib.import_module(f"works.{meta['work']['kind']}")


def job_units(meta: dict) -> int:
    return _of(meta).job_units(meta["work"])


def job_bytes(meta: dict) -> float:
    return _of(meta).job_bytes(meta["work"])


def peak(device_kind: str, key: str) -> float:
    """A published peak of this chip; a chip not in the table is an error,
    never a default."""
    with open(PEAKS) as fh:
        table = json.load(fh)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}: add it with its source")
    return table[device_kind][key]
