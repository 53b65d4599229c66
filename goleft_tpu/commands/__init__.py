"""Shared CLI plumbing for subcommands."""

from __future__ import annotations

import os


def add_no_crc_flag(parser) -> None:
    """Register ``--no-crc`` on a decode-heavy subcommand. BGZF payload
    CRC verification is a large share of per-sample decode cost (how
    large is not measured on the chip host: no cell runs ``--no-crc``);
    skipping it is for trusted local files. What remains
    caught without it — truncation (EOF check), broken deflate streams
    (inflate failure), length mismatches (isize check) — and what does
    not — a bit flip that leaves a valid stream, i.e. silent data
    change — is pinned class-by-class in tests/test_no_crc.py, which is
    why CRC stays the default. The reference has no such escape: its
    htslib path always verifies."""
    parser.add_argument(
        "--no-crc", action="store_true",
        help="skip BGZF payload CRC verification (faster decode). "
             "Truncation, broken streams and length "
             "mismatches are still caught; a bit flip that leaves a "
             "valid stream is NOT — only use on trusted local files")


def apply_no_crc(enabled: bool) -> None:
    """Propagate the flag through the existing env knob: the native
    streaming decoders and any worker subprocess read
    GOLEFT_TPU_SKIP_CRC at call time (io/native.py bam_*_stream)."""
    if enabled:
        os.environ["GOLEFT_TPU_SKIP_CRC"] = "1"
