"""Closed loop, one client: whole jobs back to back, each a call of
``goleft_tpu.cli.main`` in this process: the function ``python -m
goleft_tpu`` calls. A job has ended when ``main`` returns: its device
results were fetched and its files are closed.

Set-up runs ``warmup_jobs`` whole jobs (their outputs are compared like
any other's); the window then starts jobs until ``--seconds`` have
passed and ends with the job that is running then. With ``--trace 1`` a
``jax.profiler`` trace wraps job ``traced_job`` of the window.

The rate, ``gbases_per_s``, is all completed jobs' bases over all the
window's seconds. Each job carries host notes for ``run.py``'s job lines:
what ``getrusage`` says the process used over it, the CPU seconds of the
thread that ran it and the machine's load at its end.
"""

from __future__ import annotations

import contextlib
import os
import resource
import time

RUSAGE = ("ru_utime", "ru_stime", "ru_minflt", "ru_majflt", "ru_nvcsw",
          "ru_nivcsw")


def rusage() -> list:
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return [getattr(ru, f) for f in RUSAGE]


def loadavg1() -> float | None:
    try:
        with open("/proc/loadavg") as fh:
            return float(fh.read().split()[0])
    except (OSError, ValueError, IndexError):
        return None


def run_job(ctx, index: int) -> dict:
    from goleft_tpu import cli

    prefix = os.path.join(ctx.out_dir, f"job{index}")
    argv = ctx.job_argv(prefix)
    outputs = ctx.job_outputs(prefix)
    stdout = next((p for kind, p in outputs.items()
                   if ctx.output_files[kind] == "stdout"), None)
    ru0, cpu0 = rusage(), time.thread_time()
    t0 = time.perf_counter()
    try:
        if stdout:
            with open(stdout, "w") as fh, contextlib.redirect_stdout(fh):
                rc = cli.main(argv)
        else:
            rc = cli.main(argv)
    except SystemExit as e:  # the commands exit this way on a failed shard
        rc = e.code if isinstance(e.code, int) else 1
    t1 = time.perf_counter()
    # host notes, read at the job's two ends and outside its seconds: what
    # the process used over the job, and the machine's load at its end
    host = {f: b - a for f, a, b in zip(RUSAGE, ru0, rusage())}
    # this thread's own CPU seconds: the one reading that told the cell's
    # two speeds apart where the kernel counts no faults (PERF.md, PR 36)
    host["main_thread_cpu_s"] = time.thread_time() - cpu0
    host["loadavg1"] = loadavg1()
    return {"index": index, "t0": t0, "t1": t1, "rc": int(rc or 0),
            "outputs": outputs, "host": host}


def run(ctx) -> dict:
    mix = ctx.mix
    warmup = [run_job(ctx, -1 - i) for i in range(mix["warmup_jobs"])]
    ctx.setup_done()
    jobs, traced = [], None
    t_open = time.perf_counter()
    while time.perf_counter() - t_open < ctx.seconds:
        i = len(jobs)
        if ctx.trace and i == mix["traced_job"]:
            with ctx.profiler("bench.job"):
                jobs.append(run_job(ctx, i))
            traced = i
        else:
            jobs.append(run_job(ctx, i))
    t_close = time.perf_counter()
    if ctx.trace and traced is None:
        # the window held fewer jobs than traced_job + 1: trace one more,
        # outside the window, so that the per-layer metrics have a source
        with ctx.profiler("bench.job"):
            extra = run_job(ctx, len(jobs))
        warmup.append(extra)
        traced_job = extra
    else:
        traced_job = jobs[traced] if traced is not None else None
    work_done = len(jobs) * ctx.meta["job_bases"]
    return {"warmup": warmup, "jobs": jobs, "t_open": t_open,
            "t_close": t_close, "traced_job": traced_job,
            "work_done": work_done,
            # all completed jobs' bases over all elapsed seconds, to the end
            # of the last job
            "end_to_end": {
                "gbases_per_s": work_done * 1e-9 / (t_close - t_open)}}
