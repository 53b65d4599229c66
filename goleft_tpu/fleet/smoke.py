"""End-to-end fleet smoke: the ``make fleet-smoke`` body.

Real subprocess daemons all the way down (the acceptance contract):

  1. **byte identity**: a continuous-batching daemon and a
     window-batching daemon answer depth / indexcov / cohortdepth /
     pairhmm identically, and the payloads that ARE one-shot-CLI bytes
     (depth beds, the cohortdepth matrix, the pairhmm table) equal the
     CLI bodies run in-process on the same fixtures. (The indexcov
     serve response has been a JSON summary — not CLI file bytes —
     since PR 2; it is pinned continuous == window.)
  2. **cross-request step dedup**: two concurrent identical depth
     requests against a daemon whose first device pass is held open by
     an injected ``hang`` fault produce ONE device pass
     (``serve_device_passes_total == 1``,
     ``plan_steps_deduped_total >= 1``) and two byte-identical 200s.
  3. **router retry across worker death**: a depth request is routed
     to its affinity home, the home worker is SIGKILLed mid-flight,
     and the router retries on the sibling — the client sees one
     byte-identical 200 (``fleet.retries_total`` incremented).
  4. **per-site breaker shed**: a worker whose ``pairhmm`` breaker is
     tripped (injected permanent faults) loses only its pairhmm
     traffic after the router imports its breaker state; depth
     traffic with affinity to that worker keeps landing on it.
  5. **per-tenant quotas**: a tenant exhausting its token bucket gets
     429 + ``retry_after_s`` while another tenant's requests sail
     through; a retry-aware client (serve/client.py ``retries=1``)
     honors the hint and lands the follow-up 200.

``run_chaos`` (the ``make fleet-chaos`` body) adds the SUPERVISOR
legs, still against real subprocess daemons:

  6. **SIGKILL storm**: every worker killed -9; the supervisor
     restores full capacity without operator action and the next
     routed response is byte-identical to the one-shot CLI.
  7. **SIGSTOP hang**: a stopped worker answers no ``/healthz``; the
     supervisor SIGKILLs and recycles it (``fleet.hangs_total``).
  8. **crash-loop quarantine**: a slot dying ``crash_limit`` times
     inside the window is PARKED; the remaining fleet keeps serving
     byte-identical responses (cohortdepth's quarantine contract).
  9. **elastic scale-up**: a deterministic backlog (injected device
     hangs + ``max_inflight=1``) ages the router queue past target;
     the autoscaler spawns a second worker.
 10. **scale-down drain**: the least-affine worker is drained while a
     request is in flight ON it — the response lands byte-identical
     (zero in-flight loss), THEN the worker exits.
 11. **shared cache tier**: with ``--shared-cache``, a request
     replayed after its worker was SIGKILLed and restarted is served
     from the shared ResultCache — the restarted worker performs ZERO
     device passes — byte-identical to the original response.

Run directly::

    python -m goleft_tpu.fleet.smoke           # legs 1-5
    python -m goleft_tpu.fleet.smoke --chaos   # legs 6-11
"""

from __future__ import annotations

import io
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

from ..resilience.smoke import _make_cohort, _stop_daemon


def _spawn(args, env):
    """A goleft-tpu child announcing ``listening on URL``; returns
    (child, url)."""
    child = subprocess.Popen(
        [sys.executable, "-m", "goleft_tpu", *args],
        stdout=subprocess.PIPE, text=True, env=env)
    line = child.stdout.readline()
    if "listening on " not in line:
        child.kill()
        raise RuntimeError(f"child did not announce its port: "
                           f"{line!r} (args {args})")
    return child, line.rsplit("listening on ", 1)[1].strip()


def _spawn_worker(env, *extra):
    return _spawn(["serve", "--port", "0", "--no-warmup", *extra],
                  env)


def _spawn_router(env, worker_urls, *extra):
    args = ["fleet", "--port", "0", "--poll-interval-s", "0.3",
            "--down-after", "1"]
    for u in worker_urls:
        args += ["--worker", u]
    return _spawn(args + list(extra), env)


def _write_windows(d: str) -> str:
    """The pairhmm fixture (the pairhmm smoke's shape: one informative
    window, one far-away window)."""
    import numpy as np

    rng = np.random.default_rng(6)
    bases = list("ACGT")
    ref = "".join(rng.choice(bases, 60))
    alt = ref[:29] + ("A" if ref[29] != "A" else "C") + ref[30:]
    reads = [{"seq": (ref if i % 2 else alt)[s:s + 40], "quals": 35}
             for i, s in ((i, int(rng.integers(0, 10)))
                          for i in range(8))]
    doc = {"schema": "goleft-tpu.pairhmm-windows/1",
           "windows": [
               {"chrom": "chr1", "start": 100, "end": 400,
                "haplotypes": [ref, alt], "reads": reads},
               {"chrom": "chr1", "start": 4000, "end": 4100,
                "haplotypes": [ref], "reads": reads[:2]},
           ]}
    path = os.path.join(d, "windows.json")
    with open(path, "w") as fh:
        json.dump(doc, fh)
    return path


def _prom_counter(prom: str, name: str) -> int:
    import re

    m = re.search(rf"^{re.escape(name)} (\d+)", prom, re.M)
    return int(m.group(1)) if m else 0


def _leg_byte_identity(d, bams, fai, windows, env, verbose):
    """Leg 1: continuous == window == one-shot CLI bytes."""
    from ..commands.cohortdepth import run_cohortdepth
    from ..commands.depth import run_depth
    from ..commands.pairhmm_cmd import run_pairhmm
    from ..serve.client import ServeClient

    # in-process one-shot CLI references (run_* ARE the CLI bodies)
    dp, cp = run_depth(bams[0], os.path.join(d, "ref-depth"),
                       fai=fai, window=200)
    with open(dp) as fh:
        ref_depth = fh.read()
    with open(cp) as fh:
        ref_callable = fh.read()
    buf = io.StringIO()
    assert run_cohortdepth(bams, fai=fai, window=200, out=buf,
                           processes=2) == 0
    ref_matrix = buf.getvalue()
    buf = io.StringIO()
    assert run_pairhmm(windows, out=buf) == 0
    ref_table = buf.getvalue()

    responses = {}
    for mode in ("continuous", "window"):
        child, url = _spawn_worker(env, "--batch-mode", mode)
        try:
            client = ServeClient(url, timeout_s=120.0)
            responses[mode] = {
                "depth": client.depth(bams[0], fai=fai, window=200),
                "indexcov": client.indexcov(bams, fai),
                "cohortdepth": client.cohortdepth(bams, fai=fai,
                                                  window=200),
                "pairhmm": client.pairhmm(windows),
            }
        finally:
            _stop_daemon(child)
    cont, win = responses["continuous"], responses["window"]
    for kind in ("depth", "indexcov", "cohortdepth", "pairhmm"):
        if cont[kind] != win[kind]:
            raise RuntimeError(
                f"continuous vs window responses differ for {kind}")
    if cont["depth"]["depth_bed"] != ref_depth \
            or cont["depth"]["callable_bed"] != ref_callable:
        raise RuntimeError("serve depth != one-shot CLI bytes")
    if cont["cohortdepth"]["matrix_tsv"] != ref_matrix:
        raise RuntimeError("serve cohortdepth != one-shot CLI bytes")
    if cont["pairhmm"]["likelihoods_tsv"] != ref_table:
        raise RuntimeError("serve pairhmm != one-shot CLI bytes")
    if verbose:
        print("fleet-smoke: continuous == window == one-shot CLI "
              "bytes (depth/indexcov/cohortdepth/pairhmm)")


def _leg_dedup(d, bams, fai, env, verbose):
    """Leg 2: two concurrent identical requests → one device pass."""
    from ..serve.client import ServeClient

    # hold the FIRST device pass open 1.5s so the second (identical)
    # request provably arrives while the leader is in flight
    env = dict(env, GOLEFT_TPU_FAULTS="device:after=1:hang=1.5")
    child, url = _spawn_worker(env)
    try:
        client = ServeClient(url, timeout_s=120.0)
        out = [None, None]
        errs = []

        def fire(i):
            try:
                out[i] = client.depth(bams[0], fai=fai, window=180)
            except Exception as e:  # noqa: BLE001 — asserted below
                errs.append(e)

        t0 = threading.Thread(target=fire, args=(0,))
        t0.start()
        time.sleep(0.6)  # leader is inside the 1.5s hang
        t1 = threading.Thread(target=fire, args=(1,))
        t1.start()
        for t in (t0, t1):
            t.join(timeout=120)
        if errs:
            raise RuntimeError(f"dedup leg request failed: {errs}")
        if out[0] != out[1] or not out[0]["depth_bed"]:
            raise RuntimeError("deduped responses are not "
                               "byte-identical")
        prom = client.metrics_prometheus()
        passes = _prom_counter(prom, "serve_device_passes_total")
        deduped = _prom_counter(prom, "plan_steps_deduped_total")
        req_dedup = _prom_counter(prom,
                                  "serve_request_deduped_total_depth")
        if passes != 1:
            raise RuntimeError(
                f"two identical concurrent requests cost {passes} "
                "device pass(es), want exactly 1")
        if deduped < 1 or req_dedup != 1:
            raise RuntimeError(
                f"dedup counters wrong: plan={deduped}, "
                f"request={req_dedup}")
        if verbose:
            print("fleet-smoke: concurrent identical requests "
                  f"deduped (1 device pass, {deduped} plan-level "
                  "join(s), byte-identical 200s)")
    finally:
        _stop_daemon(child)


def _leg_router_sigkill_retry(d, bams, fai, env, verbose):
    """Leg 3: SIGKILL the affinity home mid-flight → router retries
    on the sibling → byte-identical 200."""
    from ..commands.depth import run_depth
    from ..serve.client import ServeClient

    dp, _ = run_depth(bams[1], os.path.join(d, "ref-kill"),
                      fai=fai, window=175)
    with open(dp) as fh:
        ref_bed = fh.read()
    # every device pass hangs 2s (twice): the mid-flight window we
    # kill into, on whichever worker gets the request
    wenv = dict(env, GOLEFT_TPU_FAULTS="device:every=1:hang=2:times=2")
    w0, u0 = _spawn_worker(wenv)
    w1, u1 = _spawn_worker(wenv)
    router = None
    try:
        router, rurl = _spawn_router(env, [u0, u1])
        client = ServeClient(rurl, timeout_s=120.0)
        home = client.route_plan("depth", bam=bams[1])[0]
        victim = w0 if home == u0 else w1
        out = {}
        errs = []

        def fire():
            try:
                out["r"] = client.depth(bams[1], fai=fai, window=175)
            except Exception as e:  # noqa: BLE001 — asserted below
                errs.append(e)

        t = threading.Thread(target=fire)
        t.start()
        time.sleep(0.9)  # forwarded; home is inside its 2s hang
        victim.kill()    # SIGKILL, not SIGTERM: no drain, no goodbye
        victim.wait(timeout=10)
        t.join(timeout=120)
        if errs:
            raise RuntimeError(
                f"request did not survive the worker kill: {errs}")
        if out["r"]["depth_bed"] != ref_bed:
            raise RuntimeError(
                "post-retry response is not byte-identical to the "
                "one-shot CLI")
        m = client.metrics()
        if m["counters"].get("fleet.retries_total", 0) < 1:
            raise RuntimeError("router did not count the retry")
        if m["workers"][home]["healthy"]:
            raise RuntimeError("dead worker still marked healthy")
        if verbose:
            print("fleet-smoke: SIGKILLed the affinity home "
                  "mid-flight; router retried on the sibling "
                  "(byte-identical 200, retries_total="
                  f"{m['counters']['fleet.retries_total']})")
    finally:
        if router is not None:
            _stop_daemon(router)
        for w in (w0, w1):
            if w.poll() is None:
                w.kill()
                w.wait(timeout=10)
            w.stdout.close()


def _leg_breaker_shed_and_quota(d, bams, fai, windows, env, verbose):
    """Legs 4+5: per-site breaker shed via the router, then tenant
    quotas (one router hosts both: quotas configured at spawn)."""
    import shutil

    from ..serve.client import ServeClient, ServeError

    # w_fault: every pairhmm dispatch fails permanently; threshold 2
    # trips its breaker. w_clean: healthy sibling.
    fenv = dict(env, GOLEFT_TPU_FAULTS="pairhmm:every=1:permanent")
    w_fault, uf = _spawn_worker(fenv, "--breaker-threshold", "2",
                                "--breaker-cooldown-s", "600")
    w_clean, uc = _spawn_worker(env)
    router = None
    try:
        router, rurl = _spawn_router(
            env, [uf, uc], "--quota", "alice=0.5:2")
        client = ServeClient(rurl, timeout_s=120.0)

        # trip w_fault's pairhmm breaker DIRECTLY (not via the
        # router: the trip itself is the worker's own 500 story)
        direct = ServeClient(uf, timeout_s=60.0)
        for _ in range(2):
            try:
                direct.pairhmm(windows)
                raise RuntimeError("faulted pairhmm unexpectedly ok")
            except ServeError as e:
                if e.status != 500:
                    raise RuntimeError(
                        f"want 500 from faulted worker, got "
                        f"{e.status}")
        if direct.metrics()["breakers"]["pairhmm"] != "open":
            raise RuntimeError("pairhmm breaker did not trip")
        time.sleep(0.8)  # two poll intervals: router imports state

        # pairhmm now avoids w_fault entirely…
        plan = client.route_plan("pairhmm", input=windows)
        if plan[0] == uf:
            raise RuntimeError(
                "router still plans pairhmm onto the tripped worker")
        r = client.pairhmm(windows)
        if not r.get("likelihoods_tsv"):
            raise RuntimeError("re-routed pairhmm response empty")
        # …while depth traffic whose affinity home IS w_fault keeps
        # landing there (shed is per-site, not per-worker). Find —
        # or mint — a bam homed on w_fault (content identity includes
        # the path, so copies re-roll the ring position).
        probe = None
        for i in range(24):
            cand = bams[2] if i == 0 \
                else os.path.join(d, f"homed{i}.bam")
            if i > 0:
                shutil.copy(bams[2], cand)
                shutil.copy(bams[2] + ".bai", cand + ".bai")
            if client.route_plan("depth", bam=cand)[0] == uf:
                probe = cand
                break
        if probe is None:
            raise RuntimeError(
                "could not mint a bam homed on the tripped worker")
        if not client.depth(probe, fai=fai,
                            window=200)["depth_bed"]:
            raise RuntimeError("depth via tripped-pairhmm worker "
                               "failed")
        port_f = uf.rsplit(":", 1)[-1]
        m = client.metrics()
        if m["counters"].get(
                f"fleet.routed_total.{port_f}.depth", 0) < 1:
            raise RuntimeError(
                "depth request did not land on the tripped worker")
        if m["counters"].get(
                f"fleet.routed_total.{port_f}.pairhmm", 0) != 0:
            raise RuntimeError(
                "pairhmm traffic still reached the tripped worker")
        if verbose:
            print("fleet-smoke: tripped pairhmm breaker sheds ONLY "
                  "pairhmm traffic (depth still lands on the "
                  "worker)")

        # leg 5: tenant quotas. alice has burst 2 at 0.5/s; bob is
        # unmetered. Distinct cache_busters keep requests distinct.
        client.depth(probe, fai=fai, window=200, tenant="alice",
                     cache_buster=1)
        client.depth(probe, fai=fai, window=200, tenant="alice",
                     cache_buster=2)
        try:
            client.depth(probe, fai=fai, window=200, tenant="alice",
                         cache_buster=3)
            raise RuntimeError("alice's third burst request was not "
                               "shed")
        except ServeError as e:
            if e.status != 429 or not e.retry_after_s:
                raise RuntimeError(
                    f"want 429 + retry_after_s, got {e.status} "
                    f"{e.retry_after_s!r}")
            hint = e.retry_after_s
        # bob is untouched by alice's exhaustion
        if not client.depth(probe, fai=fai, window=200,
                            tenant="bob")["depth_bed"]:
            raise RuntimeError("bob's request failed during alice's "
                               "quota exhaustion")
        # the retry-aware client honors the hint and lands the 200
        patient = ServeClient(rurl, timeout_s=120.0, retries=1)
        t0 = time.monotonic()
        r = patient.depth(probe, fai=fai, window=200,
                          tenant="alice", cache_buster=4)
        waited = time.monotonic() - t0
        if not r["depth_bed"] or waited < min(hint, 1.0) * 0.5:
            raise RuntimeError(
                f"retry-aware client did not honor retry_after_s "
                f"(waited {waited:.2f}s, hint {hint:.2f}s)")
        if verbose:
            print("fleet-smoke: tenant quota shed alice with 429 + "
                  f"retry_after_s={hint:.2f} (bob unaffected; "
                  "retry-aware client honored the hint)")
    finally:
        if router is not None:
            _stop_daemon(router)
        for w in (w_fault, w_clean):
            _stop_daemon(w)


# ---------------- supervisor chaos legs (make fleet-chaos) ----------


def _wait_until(pred, timeout_s: float, what: str) -> None:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.05)
    raise RuntimeError(f"fleet-chaos: timed out after {timeout_s:g}s "
                       f"waiting for {what}")


class _SupervisedFleet:
    """Supervisor + router in-process, workers as REAL ``goleft-tpu
    serve`` subprocess daemons (the acceptance contract)."""

    def __init__(self, n: int, env: dict, worker_args=("--no-warmup",),
                 shared_cache: str | None = None,
                 sup_kwargs: dict | None = None,
                 router_kwargs: dict | None = None):
        from ..fleet.router import RouterApp, RouterThread
        from ..fleet.supervisor import Supervisor
        from ..obs.metrics import MetricsRegistry

        self.registry = MetricsRegistry()
        self.sup = Supervisor(
            worker_args=list(worker_args), env=env,
            registry=self.registry, shared_cache=shared_cache,
            interval_s=0.25, hang_timeout_s=1.0, hang_after=2,
            spawn_timeout_s=120.0, drain_timeout_s=60.0,
            **(sup_kwargs or {}))
        urls = self.sup.spawn_initial(n)
        self.app = RouterApp(urls, poll_interval_s=0.3, down_after=1,
                             registry=self.registry,
                             **(router_kwargs or {}))
        self.sup.bind(self.app)
        self._rt = RouterThread(self.app)

    def __enter__(self) -> str:
        url = self._rt.__enter__()
        self.sup.start()
        return url

    def __exit__(self, *exc):
        # supervisor first: it must stop restarting workers before
        # close() SIGTERMs them; the router shuts down after
        self.sup.close()
        return self._rt.__exit__(*exc)

    def counter(self, name: str) -> float:
        snap = self.registry.snapshot()
        return snap["counters"].get(name, 0)


def _chaos_lifecycle_legs(d, bams, fai, env, verbose):
    """Legs 6-8 on ONE supervised 2-worker fleet: SIGKILL storm,
    SIGSTOP hang recycle, crash-loop quarantine. Death budget per
    slot across the legs: storm costs each slot 1, the hang costs
    slot B 1 more, then two kills push slot A to crash_limit=3."""
    from ..commands.depth import run_depth
    from ..serve.client import ServeClient

    dp, _ = run_depth(bams[0], os.path.join(d, "ref-chaos"),
                      fai=fai, window=190)
    with open(dp) as fh:
        ref_bed = fh.read()

    fleet = _SupervisedFleet(
        2, env,
        sup_kwargs={"min_workers": 2, "max_workers": 2,
                    "crash_limit": 3, "crash_window_s": 600.0})
    with fleet as url:
        client = ServeClient(url, timeout_s=120.0, retries=3,
                             retry_cap_s=2.0)
        r = client.depth(bams[0], fai=fai, window=190)
        if r["depth_bed"] != ref_bed:
            raise RuntimeError("pre-chaos response != CLI bytes")

        # ---- leg 6: SIGKILL storm — every worker dies at once ----
        slots = fleet.sup.slots()
        pids = {s.index: s.proc.pid for s in slots}
        for s in slots:
            s.proc.kill()
            s.proc.wait(timeout=10)
        # wait on the restart COUNTER, not capacity: capacity only
        # dips once the supervisor notices the deaths, so a
        # capacity==2 wait could pass before anything happened
        _wait_until(
            lambda: fleet.counter("fleet.restarts_total") >= 2
            and fleet.sup.capacity == 2, 180.0,
            "capacity restored after SIGKILL storm")
        for s in fleet.sup.slots():
            if s.proc.pid == pids.get(s.index):
                raise RuntimeError("worker not actually respawned")
        _wait_until(
            lambda: len(fleet.app.pool.eligible("depth")) == 2,
            30.0, "router to readmit restarted workers")
        r = client.depth(bams[0], fai=fai, window=190,
                         cache_buster="post-storm")
        if r["depth_bed"] != ref_bed:
            raise RuntimeError("post-storm response != CLI bytes")
        if verbose:
            print("fleet-chaos: SIGKILL storm — supervisor restored "
                  "full capacity unaided "
                  f"(restarts_total="
                  f"{fleet.counter('fleet.restarts_total'):g}), "
                  "byte-identical 200")

        # ---- leg 7: SIGSTOP hang detected and recycled ----
        slot_b = fleet.sup.slots()[1]
        restarts_before = slot_b.restarts
        hung_pid = slot_b.proc.pid
        os.kill(hung_pid, signal.SIGSTOP)
        _wait_until(
            lambda: slot_b.restarts > restarts_before
            and slot_b.state == "healthy", 120.0,
            "hung worker to be recycled")
        if fleet.counter("fleet.hangs_total") < 1:
            raise RuntimeError("hang not counted")
        if slot_b.proc.pid == hung_pid:
            raise RuntimeError("hung worker was not replaced")
        r = client.depth(bams[0], fai=fai, window=190,
                         cache_buster="post-hang")
        if r["depth_bed"] != ref_bed:
            raise RuntimeError("post-hang response != CLI bytes")
        if verbose:
            print("fleet-chaos: SIGSTOP hang detected via healthz "
                  "timeout, worker SIGKILLed + recycled "
                  f"(hangs_total="
                  f"{fleet.counter('fleet.hangs_total'):g})")

        # ---- leg 8: crash-looper quarantined after K deaths ----
        slot_a = fleet.sup.slots()[0]
        deadline = time.monotonic() + 240.0
        while slot_a.state != "quarantined":
            if time.monotonic() > deadline:
                raise RuntimeError("slot never quarantined")
            if slot_a.state == "healthy" \
                    and slot_a.proc.poll() is None:
                slot_a.proc.kill()
                slot_a.proc.wait(timeout=10)
            time.sleep(0.1)
        if fleet.counter("fleet.slot_quarantines") != 1 \
                or len(fleet.sup.quarantine) != 1:
            raise RuntimeError("quarantine not recorded")
        if fleet.sup.capacity != 1:
            raise RuntimeError(
                f"want degraded capacity 1, got {fleet.sup.capacity}")
        # the remaining fleet keeps serving, byte-identically
        r = client.depth(bams[0], fai=fai, window=190,
                         cache_buster="post-quarantine")
        if r["depth_bed"] != ref_bed:
            raise RuntimeError(
                "degraded-fleet response != CLI bytes")
        man = os.path.join(d, "slot_quarantine.json")
        fleet.sup.quarantine.write(man)
        with open(man) as fh:
            entries = json.load(fh)["quarantined"]
        if len(entries) != 1 \
                or entries[0]["classification"] != "crash-loop":
            raise RuntimeError(f"bad quarantine manifest: {entries}")
        if verbose:
            print("fleet-chaos: crash-looper quarantined after "
                  "3 deaths — fleet serves degraded at capacity 1, "
                  "byte-identical 200s, manifest written")


def _chaos_scaling_legs(d, bams, fai, env, verbose):
    """Legs 9-10: autoscale up under deterministic backlog, then a
    manual scale-down whose drain completes an in-flight request
    byte-identically before the worker exits."""
    import shutil

    from ..commands.depth import run_depth
    from ..serve.client import ServeClient

    dp, _ = run_depth(bams[1], os.path.join(d, "ref-scale"),
                      fai=fai, window=185)
    with open(dp) as fh:
        ref_bed = fh.read()

    # every worker device pass hangs 1.0s (deterministic service
    # time); max_inflight=1 serializes forwards so concurrent
    # requests age in the router queue — the backlog signal
    wenv = dict(env,
                GOLEFT_TPU_FAULTS="device:every=1:hang=1.0:times=50")
    fleet = _SupervisedFleet(
        1, wenv,
        sup_kwargs={"min_workers": 1, "max_workers": 2,
                    "target_queue_age_s": 0.4,
                    "scale_cooldown_s": 0.5,
                    # auto scale-down disabled (huge hysteresis): leg
                    # 10 drives the drain deterministically instead
                    "scale_down_idle_ticks": 10_000},
        router_kwargs={"max_inflight": 1})
    with fleet as url:
        client = ServeClient(url, timeout_s=300.0)

        # ---- leg 9: synthetic backlog -> autoscaler spawns #2 ----
        outs: list = []
        errs: list = []

        def fire(i):
            try:
                outs.append(client.depth(
                    bams[1], fai=fai, window=185,
                    cache_buster=f"backlog{i}"))
            except Exception as e:  # noqa: BLE001 — asserted below
                errs.append(e)

        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        _wait_until(lambda: fleet.sup.capacity == 2, 180.0,
                    "autoscaler to add a worker under backlog")
        if fleet.counter("fleet.scale_up_total") < 1 \
                or fleet.counter("fleet.scale_events") < 1:
            raise RuntimeError("scale-up not counted")
        for t in threads:
            t.join(timeout=300)
        if errs:
            raise RuntimeError(
                f"requests failed during scale-up: {errs}")
        if any(o["depth_bed"] != ref_bed for o in outs):
            raise RuntimeError("scale-up responses != CLI bytes")
        if verbose:
            print("fleet-chaos: backlog aged past target; autoscaler "
                  "scaled 1 -> 2 workers, all responses "
                  "byte-identical")

        # ---- leg 10: scale-down drains in-flight work first ----
        victim = fleet.sup.pick_scale_down_victim()
        # mint a bam homed on the victim (path is part of content
        # identity: copies re-roll the ring position)
        probe = None
        for i in range(32):
            cand = bams[2] if i == 0 \
                else os.path.join(d, f"drain{i}.bam")
            if i > 0:
                shutil.copy(bams[2], cand)
                shutil.copy(bams[2] + ".bai", cand + ".bai")
            if client.route_plan(
                    "depth", bam=cand)[0] == victim.url:
                probe = cand
                break
        if probe is None:
            raise RuntimeError("could not mint a bam homed on the "
                               "scale-down victim")
        pd, _ = run_depth(probe, os.path.join(d, "ref-drain"),
                          fai=fai, window=185)
        with open(pd) as fh:
            probe_ref = fh.read()
        box: dict = {}

        def fire_probe():
            try:
                box["r"] = client.depth(probe, fai=fai, window=185)
            except Exception as e:  # noqa: BLE001 — asserted below
                box["e"] = e

        t = threading.Thread(target=fire_probe)
        t.start()
        _wait_until(
            lambda: fleet.app.pool.inflight(victim.url) > 0, 30.0,
            "probe request to be in flight on the victim")
        gone = fleet.sup.scale_down(reason="chaos leg")
        t.join(timeout=300)
        if gone != victim.url:
            raise RuntimeError(
                f"scale-down retired {gone}, wanted {victim.url} "
                "(least-affine)")
        if "e" in box:
            raise RuntimeError(
                f"in-flight request lost during drain: {box['e']}")
        if box["r"]["depth_bed"] != probe_ref:
            raise RuntimeError(
                "drained response != CLI bytes")
        if victim.proc.poll() is None:
            raise RuntimeError("victim worker still running")
        if fleet.sup.capacity != 1 \
                or fleet.counter("fleet.scale_down_total") != 1:
            raise RuntimeError("scale-down not recorded")
        if verbose:
            print("fleet-chaos: scale-down drained the least-affine "
                  "worker — in-flight request completed "
                  "byte-identically, THEN the worker exited")


def _chaos_shared_cache_leg(d, bams, fai, env, verbose):
    """Leg 11: shared cache tier — after SIGKILL + restart the replay
    is a cache hit: ZERO device passes on the restarted worker,
    byte-identical body."""
    from ..serve.client import ServeClient

    cache_dir = os.path.join(d, "shared-cache")
    fleet = _SupervisedFleet(
        1, env, shared_cache=cache_dir,
        sup_kwargs={"min_workers": 1, "max_workers": 1,
                    "crash_limit": 5})
    with fleet as url:
        client = ServeClient(url, timeout_s=120.0, retries=3,
                             retry_cap_s=2.0)
        slot = fleet.sup.slots()[0]
        wdirect = ServeClient(slot.url, timeout_s=60.0)
        if wdirect.healthz().get("cache") != "shared":
            raise RuntimeError("worker does not report the shared "
                               "cache tier")
        first = client.depth(bams[0], fai=fai, window=170)
        if first.get("cached"):
            raise RuntimeError("first request unexpectedly cached")
        restarts_before = slot.restarts
        slot.proc.kill()
        slot.proc.wait(timeout=10)
        _wait_until(lambda: slot.restarts > restarts_before
                    and slot.state == "healthy", 180.0,
                    "worker restart after SIGKILL")
        _wait_until(
            lambda: len(fleet.app.pool.eligible("depth")) == 1,
            30.0, "router to readmit the restarted worker")
        second = client.depth(bams[0], fai=fai, window=170)
        if not second.get("cached"):
            raise RuntimeError(
                "replay after restart was not a shared-cache hit")
        if second["depth_bed"] != first["depth_bed"] \
                or second["callable_bed"] != first["callable_bed"]:
            raise RuntimeError("cache replay not byte-identical")
        prom = ServeClient(fleet.sup.slots()[0].url,
                           timeout_s=60.0).metrics_prometheus()
        if _prom_counter(prom, "serve_device_passes_total") != 0:
            raise RuntimeError(
                "restarted worker recomputed on the device despite "
                "the shared cache")
        if verbose:
            print("fleet-chaos: SIGKILL + restart replayed from the "
                  "shared cache tier (0 device passes on the new "
                  "worker, byte-identical body)")


def run_chaos(timeout_s: float = 900.0, verbose: bool = True) -> int:
    """The ``make fleet-chaos`` body. Returns 0 on success; raises on
    any failed leg."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    env = dict(os.environ,
               JAX_PLATFORMS="cpu")     # CI has no accelerator
    env.pop("GOLEFT_TPU_FAULTS", None)  # hermetic
    t0 = time.monotonic()
    with tempfile.TemporaryDirectory(prefix="goleft_chaos_") as d:
        bams, fai, _bed = _make_cohort(d, ref_len=20_000)
        _chaos_lifecycle_legs(d, bams, fai, env, verbose)
        _chaos_scaling_legs(d, bams, fai, env, verbose)
        _chaos_shared_cache_leg(d, bams, fai, env, verbose)
        if time.monotonic() - t0 > timeout_s:
            raise RuntimeError(
                f"fleet-chaos exceeded its {timeout_s:g}s budget")
        if verbose:
            print(f"fleet-chaos: PASS "
                  f"({time.monotonic() - t0:.1f}s)")
    return 0


def run_smoke(timeout_s: float = 600.0, verbose: bool = True) -> int:
    """Returns 0 on success; raises on any failed step."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    env = dict(os.environ,
               JAX_PLATFORMS="cpu")     # CI has no accelerator
    env.pop("GOLEFT_TPU_FAULTS", None)  # hermetic
    t0 = time.monotonic()
    with tempfile.TemporaryDirectory(prefix="goleft_fleet_") as d:
        # ref_len 20k: indexcov needs at least one full 16kb index
        # tile per chromosome to have usable bins
        bams, fai, _bed = _make_cohort(d, ref_len=20_000)
        windows = _write_windows(d)
        _leg_byte_identity(d, bams, fai, windows, env, verbose)
        _leg_dedup(d, bams, fai, env, verbose)
        _leg_router_sigkill_retry(d, bams, fai, env, verbose)
        _leg_breaker_shed_and_quota(d, bams, fai, windows, env,
                                    verbose)
        if time.monotonic() - t0 > timeout_s:
            raise RuntimeError(
                f"fleet-smoke exceeded its {timeout_s:g}s budget")
        if verbose:
            print(f"fleet-smoke: PASS "
                  f"({time.monotonic() - t0:.1f}s)")
    return 0


if __name__ == "__main__":
    if "--chaos" in sys.argv[1:]:
        sys.exit(run_chaos())
    sys.exit(run_smoke())
