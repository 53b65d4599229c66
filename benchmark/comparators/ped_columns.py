"""``indexcov``'s ``.ped`` against the plain reference's: every field
identical as text but the principal components, which are compared as
numbers, a column at a time: up to one sign a column (a singular vector's
sign is the solver's choice), each value within ``TOL`` times the
largest magnitude of the reference's column.

``TOL`` is stated in ``configs/indexcov500.json`` (``pc_tol``) with the
readings it was set from; ``tests/test_indexcov_reference.py`` holds the
two equal."""

from __future__ import annotations

import math
import sys

TOL = 8e-5


def read(path: str) -> list[list[str]]:
    with open(path) as fh:
        return [line.rstrip("\n").split("\t") for line in fh]


def number(cell: str) -> float:
    """A cell as a number; one that is none, or not finite, is infinitely
    far from any."""
    try:
        value = float(cell)
    except ValueError:
        return math.inf
    return value if math.isfinite(value) else math.inf


def pc_errors(got: list[list[str]], want: list[list[str]]) -> dict:
    """{column name: (sign, [error of each row, as a share of the
    reference column's largest magnitude])} for rows both files have."""
    out = {}
    n = min(len(got), len(want))
    for j, name in enumerate(want[0]):
        if not name.startswith("PC"):
            continue
        w = [float(r[j]) for r in want[1:n]]
        g = [number(r[j]) if j < len(r) else math.inf for r in got[1:n]]
        scale = max(map(abs, w), default=0.0) or 1.0
        by_sign = {sign: [abs(sign * a - b) / scale for a, b in zip(g, w)]
                   for sign in (1, -1)}
        sign = min(by_sign, key=lambda k: max(by_sign[k], default=0.0))
        out[name] = (sign, by_sign[sign])
    return out


def differ(got_path: str, want_path: str) -> int:
    """Lines that differ; a job that wrote no file wrote no line."""
    want = read(want_path)
    try:
        got = read(got_path)
    except OSError:
        got = []
    if not got:
        return len(want)
    errors = pc_errors(got, want)
    worst = max((e for _, es in errors.values() for e in es), default=0.0)
    print(f"ped_columns: worst principal-component error {worst:.3g} "
          f"(limit {TOL:g})", file=sys.stderr)
    pcs = {j for j, name in enumerate(want[0]) if name in errors}
    bad = abs(len(got) - len(want)) + (got[0] != want[0])
    for i, (g, w) in enumerate(zip(got[1:], want[1:])):
        others_differ = ([v for j, v in enumerate(g) if j not in pcs]
                         != [v for j, v in enumerate(w) if j not in pcs])
        bad += others_differ or any(es[i] > TOL for _, es in errors.values())
    return bad
