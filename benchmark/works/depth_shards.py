"""The device work of ``depth`` and ``cohortdepth``: one pass of the
depth pipeline a sample-shard. ``meta["work"]`` holds ``window``,
``classes_out`` and ``shards`` ([{"start", "end", "kept_segments": one
count a sample}]).

Per sample-shard, in bytes of HBM traffic:

    8 * kept_segments   two int32 endpoints per kept segment, un-padded
  + 2 * 4 * span        one int32 per-base accumulator, written once and
                        read once: the least a difference array and its
                        scan can do
  + 4 * span / window   window sums out
  + span / 4            2-bit classes out (``depth`` only)
"""

from __future__ import annotations


def shard_bytes(kept_segments: int, span: int, window: int,
                classes_out: bool) -> float:
    return (8 * kept_segments + 2 * 4 * span + 4 * span / window
            + (span / 4 if classes_out else 0))


def job_units(work: dict) -> int:
    """Sample-shards of one job."""
    return sum(len(s["kept_segments"]) for s in work["shards"])


def job_bytes(work: dict) -> float:
    """Least HBM bytes of one job: every sample-shard of the fixture."""
    return sum(
        shard_bytes(k, s["end"] - s["start"], work["window"],
                    work["classes_out"])
        for s in work["shards"] for k in s["kept_segments"])
