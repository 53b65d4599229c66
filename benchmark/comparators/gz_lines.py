"""A BGZF (multi-member gzip) output against a plain expected text, by
whole lines, byte for byte, a line at a time: neither side is held whole
(``indexcov500``'s is 0.5 GB of text a job)."""

from __future__ import annotations

import gzip
import itertools


def lines_differ(got, want) -> int:
    """How many lines of ``got`` differ from ``want``'s, a missing or
    surplus line counting as one; both are iterables of lines (bytes)."""
    return sum(g is None or w is None or g.rstrip(b"\n") != w.rstrip(b"\n")
               for g, w in itertools.zip_longest(got, want))


def differ(got_path: str, want_path: str) -> int:
    """A job that wrote no file, or a gzip that breaks off, wrote no line;
    an expected file that is not there is the fixture's fault and raises.
    A file that is no gzip at all is compared as the text it is and
    counts one line more for not being one: ``control.py`` hands the
    reference's plain text in the program's place, and a job's plain
    text must never compare equal."""
    with open(want_path, "rb") as want:
        try:
            with open(got_path, "rb") as raw:
                if raw.read(2) != b"\x1f\x8b":
                    raw.seek(0)
                    return 1 + lines_differ(raw, want)
            with gzip.open(got_path, "rb") as got:
                return lines_differ(got, want)
        except (OSError, EOFError):
            want.seek(0)
            return lines_differ((), want)
