"""Text outputs, compared by whole lines: byte for byte."""

from __future__ import annotations


def lines_differ(got: str, want: str) -> int:
    """How many lines of ``got`` differ from ``want``'s, a missing or
    surplus line counting as one."""
    if got == want:
        return 0
    g, w = got.splitlines(), want.splitlines()
    return sum(a != b for a, b in zip(g, w)) + abs(len(g) - len(w))


def differ(got_path: str, want_path: str) -> int:
    """A job that wrote no file wrote no line; an expected file that is
    not there is the fixture's fault and raises."""
    with open(want_path) as fh:
        want = fh.read()
    try:
        with open(got_path) as fh:
            got = fh.read()
    except OSError:
        got = ""
    return lines_differ(got, want)
