"""The device work of ``emdepth``: the EM over every window of the matrix,
a device chunk of up to ``em_chunk`` windows at a time, and the CN of
every window-sample. ``meta["work"]`` holds ``windows``, ``samples`` and
``em_chunk``.

Least HBM bytes of one job, whatever implements it:

    4 * windows * samples   one float32 normalised depth a window-sample,
                            read once
  + 4 * 9 * windows         the nine float32 lambdas of every window
  + 4 * windows * samples   out: one int32 CN a window-sample
"""

from __future__ import annotations


def job_units(work: dict) -> int:
    """Device chunks of one job."""
    return -(-work["windows"] // work["em_chunk"])


def job_bytes(work: dict) -> float:
    cells = work["windows"] * work["samples"]
    return 4 * cells + 4 * 9 * work["windows"] + 4 * cells
