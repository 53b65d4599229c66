"""The device work of ``indexcov``: a QC pass a contig over the cohort's
normalised depths, and one PCA over the quantised non-sex tiles.
``meta["work"]`` holds ``samples``, ``contigs`` (dispatched), ``slots``,
``components``, ``tile_samples`` (tiles the samples have, over those
contigs) and ``pca_tile_samples`` (the PCA matrix's cells).

Least HBM bytes of one job, whatever implements it:

    4 * tile_samples       one float32 depth in a tile-sample the QC reads
                           (the mask follows from the rows' lengths)
  + 2 * pca_tile_samples   the quantised matrix, uint16, read once
  + 4 * samples * (slots + 5) * contigs
                           out, a contig: the ROC, four counters and the
                           copy number of every sample
  + 4 * samples * components
                           out: the projection
"""

from __future__ import annotations


def job_units(work: dict) -> int:
    """Contigs dispatched by one job."""
    return work["contigs"]


def job_bytes(work: dict) -> float:
    return (4 * work["tile_samples"] + 2 * work["pca_tile_samples"]
            + 4 * work["samples"] * (work["slots"] + 5) * work["contigs"]
            + 4 * work["samples"] * work["components"])
