"""The work a job's device pass needs, counted from the algorithm and the
fixture, not from the implementation: it reads the same whatever kernel,
wire format, bucket or batching the program uses.

Per sample-shard, in bytes of HBM traffic:

    8 * kept_segments   two int32 endpoints per kept segment, un-padded
  + 2 * 4 * span        one int32 per-base accumulator, written once and
                        read once: the least a difference array and its
                        scan can do
  + 4 * span / window   window sums out
  + span / 4            2-bit classes out (``depth`` only)
"""

from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def shard_bytes(kept_segments: int, span: int, window: int,
                classes_out: bool) -> float:
    return (8 * kept_segments + 2 * 4 * span + 4 * span / window
            + (span / 4 if classes_out else 0))


def job_sample_shards(meta: dict) -> int:
    return sum(len(s["kept_segments"]) for s in meta["shards"])


def job_bytes(meta: dict) -> float:
    """Least HBM bytes of one job: every sample-shard of the fixture."""
    return sum(
        shard_bytes(k, s["end"] - s["start"], meta["window"],
                    meta["classes_out"])
        for s in meta["shards"] for k in s["kept_segments"])


def peak(device_kind: str, key: str) -> float:
    """A published peak of this chip; a chip not in the table is an error,
    never a default."""
    with open(PEAKS) as fh:
        table = json.load(fh)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}: add it with its source")
    return table[device_kind][key]
