"""A BGZF (multi-member gzip) output against a plain expected text."""

import gzip

from comparators import lines


def differ(got_path: str, want_path: str) -> int:
    with open(want_path) as fh:
        want = fh.read()
    try:
        with gzip.open(got_path, "rt") as fh:
            got = fh.read()
    except OSError:
        got = ""
    return lines.lines_differ(got, want)
