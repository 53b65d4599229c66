"""Fixtures and expected outputs of one configuration and seed.

Runs as a child of ``run.py`` (``python benchmark/fixtures.py --config F
--seed N --out DIR``) and imports neither JAX nor ``goleft_tpu``: what it
allocates never shows in the measured process's ``VmHWM``, and the inputs
do not move when the program's readers and writers do.

What the inputs are is the configuration's own: ``fixture.maker`` names a
module under ``benchmark/makers`` that gives

    build(config, seed, dir) -> meta      the job's input files, into dir
    expected(config, seed, break_guarantee=None) -> (texts, meta)
                                          {expected file name: text} by
                                          the maker's plain reference
    CONTROLS                              the guarantees ``expected`` can
                                          break (``control.py``)

This file is what every maker shares. DIR is written under a temporary
name and renamed when whole; a DIR that exists is reused. Beside the
maker's files it holds ``expected.*`` and ``meta.json``, which is all
that ``run.py``, the drivers and the reducers read of a fixture. The two
metas together have to give:

    inputs        file names in DIR, placed into argv by ``{inputs}``
    job_bases     the work of one job, in the units of ``gbases_per_s``
    native_probe  a BAM of DIR that the native library's probe decodes,
                  or null where the fixture has none
    work          {"kind": a module under ``benchmark/works``, ...}: what
                  that module counts a job's device bytes and units from

``work_unit``, the configuration's sentence on what ``job_bases`` counts,
is copied beside it. Anything else in ``meta.json`` is the maker's own.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

META_KEYS = ("inputs", "job_bases", "native_probe", "work")


def maker_of(config: dict):
    return importlib.import_module(f"makers.{config['fixture']['maker']}")


def build(config: dict, seed: int, out: str) -> dict:
    t0 = time.monotonic()
    maker = maker_of(config)
    tmp = f"{out}.tmp{os.getpid()}"
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    meta = maker.build(config, seed, tmp)
    texts, counted = maker.expected(config, seed)
    for name, text in texts.items():
        with open(f"{tmp}/{name}", "w") as fh:
            fh.write(text)
    meta.update(counted)
    missing = [k for k in META_KEYS if k not in meta]
    if missing:
        raise KeyError(f"maker {maker.__name__} gave no {missing} for "
                       "meta.json")
    # a kind with no module fails here, not after a traced window
    importlib.import_module(f"works.{meta['work']['kind']}")
    meta.update(
        work_unit=config["work_unit"], seed=seed, config=config["name"],
        fixture_seconds=round(time.monotonic() - t0, 3))
    with open(f"{tmp}/meta.json", "w") as fh:
        json.dump(meta, fh)
    shutil.rmtree(out, ignore_errors=True)
    os.rename(tmp, out)
    os.sync()  # the write-back of ~0.5 GB must not fall into the window
    return meta


def read_through(d: str) -> None:
    """Read every file once, so that a run that reuses a fixture finds it
    in the page cache as the run that built it does."""
    for name in sorted(os.listdir(d)):
        with open(os.path.join(d, name), "rb") as fh:
            while fh.read(1 << 24):
                pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)
    with open(a.config) as fh:
        config = json.load(fh)
    said = {"fixture": "reused", "dir": a.out}
    if not os.path.exists(f"{a.out}/meta.json"):
        meta = build(config, a.seed, a.out)
        said.update(fixture="built", seconds=meta["fixture_seconds"],
                    job_bases=meta["job_bases"])
    read_through(a.out)
    print(json.dumps(said))
    return 0


if __name__ == "__main__":
    sys.exit(main())
