"""One float32 in and out a tile and sample."""


def job_units(work: dict) -> int:
    return work["tiles"]


def job_bytes(work: dict) -> float:
    return 2 * 4 * work["tiles"] * work["samples"]
