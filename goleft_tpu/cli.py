"""goleft-tpu: subcommand dispatcher.

Mirrors the reference's command-plugin table (cmd/goleft/goleft.go:24-31):
a name → (help, main) registry; unknown or missing subcommands print the
sorted table. New tools register by adding one entry.

Global observability flags — valid before OR after the subcommand name
(they are stripped here, so individual commands never re-declare them):

  --trace-out FILE    write the run's span timeline as Chrome
                      trace-event JSON (loads in Perfetto); the run
                      is the one an untraced user gets, nothing fenced
  --metrics-out FILE  write the run manifest (env + backend provenance
                      + span summary + metrics-registry snapshot)
  --log-level LEVEL   debug/info/warning/error on the goleft-tpu.*
                      logger tree
  -v / -vv            shorthand for --log-level info / debug
                      (``goleft-tpu -v`` as the sole argument still
                      prints the version, as it always has)
  --inject-faults S   install a deterministic fault schedule
                      (resilience/faults.py grammar; also settable
                      via GOLEFT_TPU_FAULTS) — chaos testing for any
                      command

Every invocation runs under a run-scoped trace: the ``run.<cmd>`` root
span parents the pipeline stages, whichever threads record them.
"""

from __future__ import annotations

import sys

from . import __version__


def _lazy(module: str):
    def runner(argv):
        import importlib

        mod = importlib.import_module(module, package=__package__)
        return mod.main(argv)

    return runner


# name -> (help, runner, uses_device). Device-using commands take their
# backend at dispatch (utils/device_guard.take_backend): the shared
# path, so a new tool declares one flag instead of wiring its own call
# site, and a command that uses no device never imports jax here.
PROGS = {
    "depth": ("parallelize calls to the TPU depth engine",
              _lazy(".commands.depth"), True),
    "depthwed": ("matricize depth bed files to n-sites * n-samples",
                 _lazy(".commands.depthwed"), False),
    "covstats": ("coverage and insert-size statistics by sampling",
                 _lazy(".commands.covstats"), False),
    "indexcov": ("quick coverage estimate using only the bam/cram index",
                 _lazy(".commands.indexcov"), True),
    "indexsplit": ("create regions of even data size across bams/crams",
                   _lazy(".commands.indexsplit"), False),
    "samplename": ("report samples in a bam file",
                   _lazy(".commands.samplename"), False),
    "emdepth": ("EM copy-number calls from a depth matrix",
                _lazy(".commands.emdepth_cmd"), True),
    "multidepth": ("joint depth over many bams with min-coverage blocks",
                   _lazy(".commands.multidepth"), True),
    "dcnv": ("GC-debias + normalize a depth matrix",
             _lazy(".commands.dcnv_cmd"), True),
    "cnveval": ("evaluate CNV calls against a truth set",
                _lazy(".commands.cnveval_cmd"), False),
    "pairhmm": ("pair-HMM genotype likelihoods for candidate windows",
                _lazy(".commands.pairhmm_cmd"), True),
    "map": ("map FASTQ reads: minimizer seeding + banded "
            "Smith-Waterman on device",
            _lazy(".commands.map_cmd"), True),
    "anonymize": ("make shareable header-only bam+bai fixtures",
                  _lazy(".commands.anonymize"), False),
    "lint": ("AST invariant analyzer: determinism, tracer hygiene, "
             "lock discipline", _lazy(".analysis.cli"), False),
    "cohortdepth": ("depth matrix for many bams in one device pass",
                    _lazy(".commands.cohortdepth"), True),
    "cohortscan": ("streaming, incremental indexcov for biobank-scale "
                   "cohorts", _lazy(".commands.cohortscan"), True),
    "cnv": ("CNV calls straight from bams (cohort depth + EM)",
            _lazy(".commands.cnv"), True),
    "serve": ("warm-mesh coverage daemon with request micro-batching",
              _lazy(".commands.serve"), True),
    # the router never touches a device: it spawns/fronts serve
    # workers (which bring up their OWN backends) and must not pay —
    # or hang on — backend bring-up itself
    "fleet": ("multi-worker serve fleet behind a file-affinity router",
              _lazy(".commands.fleet"), False),
    # pure HTTP client over the router's /fleet/trace — no device
    "trace": ("fetch + pretty-print a stitched cross-process fleet "
              "trace", _lazy(".commands.trace_cmd"), False),
    # the tier above fleet: fronts N fleet routers (which spawn and
    # supervise their own workers) — jax-free like the fleet router
    "federation": ("multi-fleet failover tier with tenant-scoped "
                   "overload isolation",
                   _lazy(".commands.federation"), False),
    # pure HTTP clients over the observability surfaces — no device
    "warmup": ("export the compile observatory's warmup manifest "
               "from a live worker or router",
               _lazy(".commands.warmup"), False),
    "profile": ("collect + render a fleet-wide sampling CPU profile",
                _lazy(".commands.profile_cmd"), False),
    "memory": ("render the host/device memory observatory of a "
               "worker or fleet",
               _lazy(".commands.memory_cmd"), False),
}

_VALUE_FLAGS = {"--trace-out": "trace_out",
                "--metrics-out": "metrics_out",
                "--log-level": "log_level",
                "--inject-faults": "inject_faults"}


def _extract_global_flags(argv: list[str]):
    """Strip the global observability flags from anywhere in argv.

    Returns (opts dict, remaining argv) or raises ValueError on a flag
    missing its value / an unknown level. ``-v``/``-vv`` count as
    verbosity here; the caller handles the historical ``goleft-tpu -v``
    == version case before calling this.
    """
    opts = {"trace_out": None, "metrics_out": None, "log_level": None,
            "inject_faults": None, "verbose": 0}
    rest: list[str] = []
    i = 0
    while i < len(argv):
        a = argv[i]
        key = _VALUE_FLAGS.get(a)
        if key is not None:
            if i + 1 >= len(argv):
                raise ValueError(f"{a} needs a value")
            opts[key] = argv[i + 1]
            i += 2
            continue
        flag, _, val = a.partition("=")
        key = _VALUE_FLAGS.get(flag)
        if key is not None and _ == "=":
            opts[key] = val
            i += 1
            continue
        if a == "-v":
            opts["verbose"] += 1
            i += 1
            continue
        if a == "-vv":
            opts["verbose"] += 2
            i += 1
            continue
        rest.append(a)
        i += 1
    if opts["log_level"] is not None:
        from .obs.logging import parse_level

        parse_level(opts["log_level"])  # fail fast on a bad level
    if opts["inject_faults"] is not None:
        from .resilience.faults import parse_faults

        parse_faults(opts["inject_faults"])  # fail fast on a bad spec
    return opts, rest


def usage() -> str:
    lines = [
        f"goleft-tpu Version: {__version__}",
        "",
    ]
    for name in sorted(PROGS):
        lines.append(f"{name:<11}: {PROGS[name][0]}")
    lines += [
        "",
        "global flags (before or after the subcommand):",
        "  --trace-out FILE    Perfetto/Chrome trace of the run's spans",
        "  --metrics-out FILE  run manifest (provenance + span summary "
        "+ metrics)",
        "  --log-level LEVEL   debug|info|warning|error",
        "  -v / -vv            info / debug logging",
        "  --inject-faults S   deterministic fault schedule "
        "(docs/resilience.md; e.g. shard:after=3:kill)",
    ]
    return "\n".join(lines)


def _run_command(prog: str, argv: list[str]) -> int:
    """Dispatch to the subcommand with the historical error contract
    (exit 0/1/141, see tests/test_cli_dispatch.py)."""
    sys.argv = [f"goleft-tpu {prog}"] + argv
    try:
        ret = PROGS[prog][1](argv)
        # flush INSIDE the guard: when the downstream exits before
        # reading anything (| head -c0), the EPIPE only surfaces at
        # the exit-time flush — which would otherwise print
        # "Exception ignored in <stdout>" and exit 120
        sys.stdout.flush()
    except BrokenPipeError:
        # downstream closed our stdout (`... | head`): the reference's
        # Go tools die to SIGPIPE silently; match that (exit 141 =
        # 128+SIGPIPE) instead of spraying a traceback. stdout's fd is
        # pointed at devnull so the interpreter's exit flush cannot
        # raise a second BrokenPipeError.
        import os

        try:
            devnull = os.open(os.devnull, os.O_WRONLY)
            os.dup2(devnull, sys.stdout.fileno())
        except (OSError, ValueError, AttributeError):
            # (io.UnsupportedOperation subclasses OSError/ValueError)
            pass
        return 141
    except ValueError as e:
        # the io parsers raise typed ValueError on corrupt input (bai/
        # crai/fai/bed contract; bam/cram convert to SystemExit in
        # open_bam_file) — surface it as one clean line. The cost: a
        # ValueError from a genuine bug is masked as bad input, so
        # GOLEFT_TPU_DEBUG=1 re-raises with the full traceback.
        import os

        if os.environ.get("GOLEFT_TPU_DEBUG"):
            raise
        print(f"goleft-tpu {prog}: {e}", file=sys.stderr)
        return 1
    return int(ret or 0)


def main(argv: list[str] | None = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    # historical contract first: `goleft-tpu -v` is the version, not
    # verbosity (scripts pin it); -v elsewhere means verbose logging
    if argv and argv[0] in ("-v", "--version", "version"):
        print(__version__)
        return 0
    try:
        gopts, argv = _extract_global_flags(argv)
    except ValueError as e:
        print(f"goleft-tpu: {e}", file=sys.stderr)
        return 1
    if not argv or argv[0] in ("-h", "--help", "help"):
        print(usage(), file=sys.stderr)
        return 0
    prog = argv[0]
    if prog not in PROGS:
        # a close match is almost always a typo: suggest it instead of
        # dumping the whole table (which still prints when the guess
        # would be noise)
        import difflib

        close = difflib.get_close_matches(prog, PROGS, n=1, cutoff=0.6)
        if close:
            print(f"unknown subcommand: {prog} — did you mean "
                  f"{close[0]}?", file=sys.stderr)
        else:
            print(f"unknown subcommand: {prog}\n", file=sys.stderr)
            print(usage(), file=sys.stderr)
        return 1

    from . import obs

    level = gopts["log_level"] or (
        "debug" if gopts["verbose"] >= 2
        else "info" if gopts["verbose"] else "warning")
    obs.configure_logging(level)
    if gopts["inject_faults"]:
        from .resilience import faults

        faults.install(gopts["inject_faults"])
    if PROGS[prog][2]:
        # multi-host world (no-op without GOLEFT_TPU_COORDINATOR): must
        # come before take_backend's jax.devices() brings the backend up
        from .obs.compiles import ensure_compile_hook
        from .parallel.mesh import init_distributed
        from .utils.device_guard import take_backend

        init_distributed()
        take_backend()
        # count every compile of the run from its first jit, seam or
        # no seam around it (xla.compiles_total, xla.compile_seconds_
        # total, xla.cache_hits_total in the --metrics-out manifest)
        ensure_compile_hook()

    trace_id = None
    rc = 1
    try:
        with obs.trace(f"run.{prog}", kind="cli",
                       argv=" ".join(argv[1:])) as root:
            trace_id = root.trace_id
            # a reader divides a counter's growth by the runs between
            # its two readings
            obs.get_registry().counter("cli.runs_total").inc()
            rc = _run_command(prog, argv[1:])
            root.attrs["exit_code"] = rc
        return rc
    finally:
        # artifacts are written even when the command failed: a failed
        # run's evidence is the evidence most worth keeping. The CLI
        # process IS the run, so spans are exported unfiltered (pool
        # threads included) with the run's trace id recorded alongside.
        if gopts["trace_out"]:
            try:
                obs.get_tracer().write_chrome_trace(gopts["trace_out"])
            except OSError as e:
                print(f"goleft-tpu: could not write --trace-out: {e}",
                      file=sys.stderr)
        if gopts["metrics_out"]:
            from .obs.manifest import write_manifest

            try:
                write_manifest(
                    gopts["metrics_out"], trace_id=trace_id,
                    argv=[f"goleft-tpu {prog}"] + argv[1:],
                    extra={"command": prog, "exit_code": rc})
            except OSError as e:
                print(f"goleft-tpu: could not write --metrics-out: "
                      f"{e}", file=sys.stderr)


if __name__ == "__main__":
    sys.exit(main())
