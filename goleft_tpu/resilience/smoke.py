"""Chaos smoke: the ``make chaos-smoke`` body.

A REAL ``goleft-tpu cohortdepth`` subprocess is killed mid-flight and
must come back byte-identical:

  1. cold run → reference bytes
  2. same run with ``--checkpoint-dir`` + an injected deterministic
     SIGKILL between journal commits (``shard:after=3:kill``) → the
     process dies like a preempted worker (rc -9/137), the journal
     holds the committed prefix
  3. ``--resume`` → exit 0, stdout byte-identical to (1), and the run
     manifest proves the journal replay skipped committed shards
     (``checkpoint.shards_resumed_total``)
  4. a permanently-corrupt sample → the run quarantines it and exits 3
     with the partial cohort, byte-identical to a cold run over the
     healthy samples, plus ``quarantine.json`` naming the culprit

then the serve legs — the same failure domains against a REAL
``goleft-tpu serve`` daemon (PR 7):

  5. poison isolation: a coalesced batch of 8 depth requests with one
     corrupt BAM → seven 200s byte-identical to solo runs, one 400
     flagged ``poison``, ``serve.poison_total`` incremented
  6. circuit breaker: injected permanent device faults trip the
     endpoint (500,500,500 → 503 shed with retry_after) and a
     half-open probe recovers it to 200/closed
  7. watchdog: an injected hung device pass is abandoned after the
     budget and its request re-queued to a 200
     (``serve.watchdog_requeues_total``)
  8. checkpointed serve requests: a ``checkpoint: true`` cohortdepth
     request dies with a SIGKILLed daemon mid-run; re-issued against a
     restarted daemon it resumes from the journal byte-identically
     (``checkpoint.shards_resumed_total`` > 0 in the /metrics
     Prometheus body)

and the fleet legs (PR 9, bodies shared with ``make fleet-smoke``):

  9. a fleet worker is SIGKILLed mid-flight; the router retries the
      request on its sibling to a byte-identical 200
  10. one worker's ``pairhmm`` breaker is tripped; the router imports
      the breaker state and re-routes ONLY pairhmm traffic — the
      worker's depth traffic keeps landing on it (plus the per-tenant
      quota 429/retry_after_s leg riding the same router)

Run directly::

    python -m goleft_tpu.resilience.smoke
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile


def _make_cohort(d: str, n_samples: int = 3, ref_len: int = 6000,
                 n_reads: int = 500, n_regions: int = 6):
    """Tiny multi-region cohort fixture (hermetic, like the obs/serve
    smokes): n BAMs + .fai + a bed tiling the contig into n_regions
    shard-sized intervals."""
    import numpy as np

    from ..io.bai import build_bai, write_bai
    from ..io.bam import BamWriter

    rng = np.random.default_rng(5)
    bams = []
    for i in range(n_samples):
        starts = np.sort(rng.integers(0, ref_len - 100, size=n_reads))
        p = os.path.join(d, f"s{i}.bam")
        with open(p, "wb") as fh:
            with BamWriter(
                fh, "@HD\tVN:1.6\tSO:coordinate\n@SQ\tSN:chr1\tLN:"
                f"{ref_len}\n@RG\tID:r\tSM:s{i}\n", ["chr1"],
                [ref_len], level=1,
            ) as w:
                for j, s in enumerate(starts):
                    w.write_record(0, int(s), [(100, 0)], mapq=60,
                                   name=f"r{j}")
        write_bai(build_bai(p), p + ".bai")
        bams.append(p)
    fai = os.path.join(d, "ref.fa.fai")
    with open(fai, "w") as fh:
        fh.write(f"chr1\t{ref_len}\t6\t60\t61\n")
    bed = os.path.join(d, "regions.bed")
    step = ref_len // n_regions
    with open(bed, "w") as fh:
        for lo in range(0, ref_len, step):
            fh.write(f"chr1\t{lo}\t{min(ref_len, lo + step)}\n")
    return bams, fai, bed


def _run(args, env, timeout_s):
    return subprocess.run(args, env=env, capture_output=True,
                          timeout=timeout_s)


def _spawn_daemon(env, *extra_args):
    """A real ``goleft-tpu serve`` child on an ephemeral port; returns
    (child, base_url) once the listen line is scraped."""
    child = subprocess.Popen(
        [sys.executable, "-m", "goleft_tpu", "serve", "--port", "0",
         "--no-warmup", *extra_args],
        stdout=subprocess.PIPE, text=True, env=env)
    line = child.stdout.readline()
    if "listening on " not in line:
        child.kill()
        raise RuntimeError(
            f"serve did not announce its port: {line!r}")
    return child, line.rsplit("listening on ", 1)[1].strip()


def _stop_daemon(child):
    import signal as _signal

    if child.poll() is None:
        child.send_signal(_signal.SIGTERM)
        try:
            child.wait(timeout=30)
        except subprocess.TimeoutExpired:
            child.kill()
    child.stdout.close()


def _serve_poison_leg(d, fai, template_bam, env, verbose):
    """Leg 6: one corrupt BAM in a coalesced batch of 8 fails alone
    (400, flagged poison) while its seven neighbors' responses are
    byte-identical to solo runs on the same daemon."""
    import shutil
    import threading

    from ..serve.client import ServeClient, ServeError

    pool = []
    for i in range(8):
        p = os.path.join(d, f"pool{i}.bam")
        shutil.copy(template_bam, p)
        shutil.copy(template_bam + ".bai", p + ".bai")
        pool.append(p)
    with open(pool[3], "r+b") as fh:
        fh.write(b"\x00" * 64)  # the poison: exists, but corrupt
    child, url = _spawn_daemon(env, "--batch-window-ms", "400")
    try:
        client = ServeClient(url, timeout_s=60.0)
        solo = {p: client.depth(p, fai=fai, window=200)
                for p in pool if p != pool[3]}
        codes = [0] * 8
        bodies: list = [None] * 8

        def one(i):
            try:
                bodies[i] = client.depth(pool[i], fai=fai,
                                         window=200)
                codes[i] = 200
            except ServeError as e:
                codes[i] = e.status
                bodies[i] = e.message
        ts = [threading.Thread(target=one, args=(i,))
              for i in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        if sorted(codes) != [200] * 7 + [400]:
            raise RuntimeError(
                f"poison batch: expected seven 200s + one 400, got "
                f"{codes}")
        if codes[3] != 400 or "poison" not in str(bodies[3]):
            raise RuntimeError(
                f"the corrupt request was not the poisoned one: "
                f"{codes[3]} {bodies[3]!r}")
        for i, p in enumerate(pool):
            if i != 3 and bodies[i] != solo[p]:
                raise RuntimeError(
                    f"neighbor {i} response differs from its solo "
                    "run")
        m = client.metrics()
        if m["counters"].get("poison_total", 0) < 1:
            raise RuntimeError("serve.poison_total not incremented")
        if verbose:
            print("chaos-smoke: serve poison isolated (one 400, "
                  "seven byte-identical 200s, poison_total="
                  f"{m['counters']['poison_total']})")
    finally:
        _stop_daemon(child)


def _serve_breaker_leg(d, fai, bam, env, verbose):
    """Leg 7: three injected permanent device faults trip the depth
    breaker (503 shed before any queue/device work), and the half-open
    probe after the cooldown recovers it to 200/closed."""
    import time as _time

    from ..serve.client import ServeClient, ServeError

    env = dict(env, GOLEFT_TPU_FAULTS="device:every=1:permanent:"
                                      "times=3")
    child, url = _spawn_daemon(env, "--breaker-threshold", "3",
                               "--breaker-cooldown-s", "0.5")
    try:
        client = ServeClient(url, timeout_s=60.0)
        codes = []
        for _ in range(4):
            try:
                client.depth(bam, fai=fai, window=200)
                codes.append(200)
            except ServeError as e:
                codes.append(e.status)
        if codes != [500, 500, 500, 503]:
            raise RuntimeError(
                f"breaker trip: expected [500, 500, 500, 503], got "
                f"{codes}")
        if client.metrics()["breakers"]["depth"] != "open":
            raise RuntimeError("breaker not open after the trip")
        _time.sleep(0.7)  # past the cooldown: half-open probe allowed
        r = client.depth(bam, fai=fai, window=200)
        if "depth_bed" not in r:
            raise RuntimeError(f"probe response malformed: {r!r}")
        m = client.metrics()
        if m["breakers"]["depth"] != "closed":
            raise RuntimeError("breaker did not close after the "
                               "successful probe")
        if m["counters"].get("breaker_rejected_total.depth", 0) < 1:
            raise RuntimeError("no shed counted while open")
        if verbose:
            print("chaos-smoke: serve breaker tripped (3x500 -> 503 "
                  "shed) and recovered (probe 200 -> closed)")
    finally:
        _stop_daemon(child)


def _serve_watchdog_leg(d, fai, bam, env, verbose):
    """Leg 8: the first device pass hangs (injected); the watchdog
    abandons it after the 1s budget, re-queues the request at the
    front, and the retry pass answers 200."""
    from ..serve.client import ServeClient

    env = dict(env, GOLEFT_TPU_FAULTS="device:after=1:hang")
    child, url = _spawn_daemon(env, "--watchdog-s", "1",
                               "--watchdog-requeues", "1")
    try:
        client = ServeClient(url, timeout_s=120.0)
        r = client.depth(bam, fai=fai, window=200)
        if "depth_bed" not in r or not r["depth_bed"]:
            raise RuntimeError(f"post-requeue response empty: {r!r}")
        m = client.metrics()
        if m["counters"].get("watchdog_requeues_total", 0) != 1:
            raise RuntimeError(
                "watchdog_requeues_total != 1: "
                f"{m['counters'].get('watchdog_requeues_total')}")
        if verbose:
            print("chaos-smoke: serve watchdog abandoned the hung "
                  "pass and the re-queued request answered 200")
    finally:
        _stop_daemon(child)


def _serve_checkpoint_leg(d, bams, fai, bed, env, verbose):
    """Leg 9: a ``checkpoint: true`` cohortdepth request rides a
    daemon that is SIGKILLed mid-run by an injected fault; re-issued
    against a FRESH daemon on the same --checkpoint-root it resumes
    from the journal, byte-identical to a non-checkpointed run."""
    import re

    from ..serve.client import ServeClient

    ckroot = os.path.join(d, "serve-ck")
    req = dict(fai=fai, window=200, bed=bed)
    # after=5: the serve path batches journal commits (DeferredCommits,
    # one fsync per JOURNAL_FLUSH_EVERY=4 regions) — the kill must land
    # past the first flush so a committed prefix exists to resume from
    kill_env = dict(env, GOLEFT_TPU_FAULTS="shard:after=5:kill")
    child, url = _spawn_daemon(kill_env, "--checkpoint-root", ckroot)
    try:
        client = ServeClient(url, timeout_s=60.0)
        try:
            client.cohortdepth(bams, checkpoint=True, **req)
            raise RuntimeError(
                "request survived a daemon that should have died")
        except OSError:
            pass  # connection died with the daemon — expected
        rc = child.wait(timeout=30)
        if rc not in (-9, 137):
            raise RuntimeError(f"daemon did not die by SIGKILL: {rc}")
    finally:
        _stop_daemon(child)
    journal = os.path.join(ckroot, "cohortdepth", "journal.jsonl")
    with open(journal) as fh:
        committed = sum(1 for _ in fh)
    if committed <= 0:
        raise RuntimeError("no shards committed before the kill")

    child, url = _spawn_daemon(env, "--checkpoint-root", ckroot)
    try:
        client = ServeClient(url, timeout_s=60.0)
        resumed = client.cohortdepth(bams, checkpoint=True, **req)
        reference = client.cohortdepth(bams, **req)
        if resumed["matrix_tsv"] != reference["matrix_tsv"]:
            raise RuntimeError(
                "resumed serve matrix is NOT byte-identical to the "
                "non-checkpointed run")
        prom = client.metrics_prometheus()
        m = re.search(r"^checkpoint_shards_resumed_total (\d+)",
                      prom, re.M)
        if m is None or int(m.group(1)) < committed:
            raise RuntimeError(
                f"journal replay not proven: committed={committed}, "
                f"prom={'absent' if m is None else m.group(1)}")
        if verbose:
            print("chaos-smoke: serve checkpoint resumed across a "
                  f"daemon SIGKILL+restart ({m.group(1)} shard(s) "
                  "replayed, byte-identical)")
    finally:
        _stop_daemon(child)


def run_smoke(timeout_s: float = 180.0, verbose: bool = True) -> int:
    """Returns 0 on success; raises on any failed step."""
    env = dict(os.environ,
               JAX_PLATFORMS="cpu")     # CI has no accelerator
    env.pop("GOLEFT_TPU_FAULTS", None)  # hermetic: no inherited plan
    with tempfile.TemporaryDirectory(prefix="goleft_chaos_") as d:
        bams, fai, bed = _make_cohort(d)
        base = [sys.executable, "-m", "goleft_tpu", "cohortdepth",
                "--fai", fai, "-w", "200", "-b", bed, "-p", "2"]
        ck = os.path.join(d, "ck")

        # 1. the reference bytes
        cold = _run(base + bams, env, timeout_s)
        if cold.returncode != 0:
            raise RuntimeError(
                f"cold run failed ({cold.returncode}):\n"
                f"{cold.stderr.decode()}")
        if not cold.stdout:
            raise RuntimeError("cold run produced no matrix")

        # 2. deterministic mid-flight SIGKILL between journal commits
        kill = _run(base + ["--checkpoint-dir", ck, "--inject-faults",
                            "shard:after=3:kill"] + bams, env,
                    timeout_s)
        if kill.returncode not in (-9, 137):
            raise RuntimeError(
                "injected kill did not kill: rc="
                f"{kill.returncode}\n{kill.stderr.decode()}")
        journal = os.path.join(ck, "journal.jsonl")
        with open(journal) as fh:
            committed = sum(1 for _ in fh)
        if not 0 < committed < 6 * len(bams):
            raise RuntimeError(
                f"expected a committed prefix, journal has "
                f"{committed} line(s)")
        if verbose:
            print(f"chaos-smoke: killed mid-flight (rc "
                  f"{kill.returncode}, {committed} shard(s) "
                  "committed)")

        # 3. resume: byte-identical + journal replay proven by metrics
        manifest_p = os.path.join(d, "resume.json")
        res = _run(base + ["--checkpoint-dir", ck, "--resume",
                           "--metrics-out", manifest_p] + bams, env,
                   timeout_s)
        if res.returncode != 0:
            raise RuntimeError(
                f"resume failed ({res.returncode}):\n"
                f"{res.stderr.decode()}")
        if res.stdout != cold.stdout:
            raise RuntimeError(
                "resumed output is NOT byte-identical to the cold run")
        with open(manifest_p) as fh:
            man = json.load(fh)
        counters = man["metrics"]["counters"]
        resumed = counters.get("checkpoint.shards_resumed_total", 0)
        if resumed != committed:
            raise RuntimeError(
                f"journal replay skipped {resumed} shard(s), "
                f"expected {committed}")
        if man.get("resilience", {}).get("quarantined"):
            raise RuntimeError("healthy resume reported quarantine")
        if verbose:
            print(f"chaos-smoke: resume byte-identical "
                  f"({resumed} shard(s) replayed, "
                  f"{counters.get('checkpoint.shards_written_total')}"
                  " written fresh)")

        # 4. quarantine: a permanently-corrupt sample degrades, never
        # kills — and the partial cohort equals a cold run without it
        with open(bams[1], "r+b") as fh:
            fh.write(b"\x00" * 64)  # trash the BGZF header
        ck2 = os.path.join(d, "ck2")
        quar = _run(base + ["--checkpoint-dir", ck2] + bams, env,
                    timeout_s)
        if quar.returncode != 3:
            raise RuntimeError(
                "quarantined run should exit 3, got "
                f"{quar.returncode}\n{quar.stderr.decode()}")
        healthy = _run(base + [bams[0], bams[2]], env, timeout_s)
        if quar.stdout != healthy.stdout:
            raise RuntimeError(
                "partial cohort is not byte-identical to a cold run "
                "over the healthy samples")
        qman_p = os.path.join(ck2, "quarantine.json")
        with open(qman_p) as fh:
            qman = json.load(fh)
        q_sources = [e["source"] for e in qman["quarantined"]]
        if q_sources != [bams[1]]:
            raise RuntimeError(
                f"quarantine manifest names {q_sources}, expected "
                f"[{bams[1]}]")
        if b"quarantined" not in quar.stderr:
            raise RuntimeError("exit summary missing from stderr")
        if verbose:
            print("chaos-smoke: corrupt sample quarantined (exit 3, "
                  "partial cohort byte-identical, manifest ok)")

        # 5-8. the serve legs: the same failure domains against a
        # real daemon (poison isolation, breaker trip/recover,
        # watchdog re-queue, checkpointed requests across a SIGKILL)
        healthy_bam = bams[0]  # bams[1] was corrupted by step 4
        _serve_poison_leg(d, fai, healthy_bam, env, verbose)
        _serve_breaker_leg(d, fai, healthy_bam, env, verbose)
        _serve_watchdog_leg(d, fai, healthy_bam, env, verbose)
        _serve_checkpoint_leg(d, [bams[0], bams[2]], fai, bed, env,
                              verbose)

        # 9-10. the fleet failure domains (bodies shared with
        # `make fleet-smoke`): SIGKILLed worker → router retry, and
        # a tripped per-site breaker shedding only its own traffic.
        # bams[1] is corrupt by now — hand the legs healthy inputs.
        from ..fleet.smoke import (
            _leg_breaker_shed_and_quota, _leg_router_sigkill_retry,
            _write_windows,
        )

        fleet_bams = [bams[0], bams[2], bams[0]]
        windows = _write_windows(d)
        _leg_router_sigkill_retry(d, fleet_bams, fai, env, verbose)
        _leg_breaker_shed_and_quota(d, fleet_bams, fai, windows,
                                    env, verbose)
        if verbose:
            print("chaos-smoke: PASS")
    return 0


if __name__ == "__main__":
    sys.exit(run_smoke())
