"""The one Step executor: retry × quarantine × checkpoint × faults × spans.

Every dispatch path — CLI shard schedulers, the prefetched cohort
pipeline, the per-chromosome indexcov loop, the pair-HMM bucket
dispatch, the serve executors — runs its Steps through
:meth:`Executor.run_step`, so the composition order is defined once:

    1. quarantine short-circuit (an already-quarantined key degrades
       to its fallback with zero work)
    2. checkpoint resume (every key committed → restore, no fault
       site, no retry, counted in ``checkpoint.shards_resumed_total``)
    3. result-cache lookup (I/O failures never fail the step —
       ``result_cache.io_errors_total``)
    4. the attempt loop under the RetryPolicy: each attempt fires the
       step's fault-injection site, then runs ``fn`` inside the step's
       span (a device-event span for device steps)
    5. on exhaustion: quarantine + fallback when the step carries a
       quarantine identity, else the failure lands in the outcome
    6. cache put, then checkpoint commit (one journal commit per step)

``execute_task`` is the shard-scheduler facade (moved here from
resilience/policy.py): same (key, thunk, cache, policy) →
``ShardResult`` contract both scheduler paths have used since PR 5.
``run_device_step`` is the serve executors' facade: one coalesced
device dispatch as a retried Step, so a transient device fault
costs one backoff instead of failing the whole batch.
"""

from __future__ import annotations

import threading
from typing import Any

from ..obs import get_registry
from ..resilience import faults
from ..resilience.policy import (
    DEFAULT_POLICY, RetriesExhausted, RetryPolicy,
)
from .core import Plan, Step, StepOutcome


class _InflightEntry:
    __slots__ = ("event", "outcome")

    def __init__(self):
        self.event = threading.Event()
        self.outcome: StepOutcome | None = None


class InflightSteps:
    """Cross-request in-flight step table: the dedup machinery.

    Two concurrent Steps carrying the same content key (and
    ``dedup=True``) share ONE execution: the first arrival is the
    *leader* and computes; every later arrival is a *follower* that
    waits on the leader's outcome and reuses its value — one device
    pass serves all of them. Content keys make this safe: the key
    pins every input's identity (``file_key`` = path+size+mtime_ns)
    plus the canonical parameters, so "same key" means "same bytes
    out".

    Failures are NOT shared: a follower whose leader errored (or
    vanished past ``wait_s``) computes independently — dedup is an
    optimization, never a correlated-failure amplifier.

    The process-wide instance is :data:`INFLIGHT`; executors use it by
    default so dedup spans every Executor in the process (the serve
    executors construct one per dispatch).
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._inflight: dict = {}

    def join(self, key) -> tuple[_InflightEntry, bool]:
        """(entry, is_leader). The leader MUST eventually
        :meth:`settle` its entry (use try/finally)."""
        with self._lock:
            entry = self._inflight.get(key)
            if entry is None:
                entry = self._inflight[key] = _InflightEntry()
                return entry, True
            return entry, False

    def settle(self, key, entry: _InflightEntry,
               outcome: StepOutcome | None) -> None:
        with self._lock:
            # pop only our own entry: a follower that timed out and
            # re-led must not have its fresh entry evicted by the
            # stale leader settling late
            if self._inflight.get(key) is entry:
                del self._inflight[key]
        entry.outcome = outcome
        entry.event.set()

    def depth(self) -> int:
        with self._lock:
            return len(self._inflight)


#: the process-wide in-flight table (one dedup domain per process)
INFLIGHT = InflightSteps()

#: how long a follower waits on its leader before giving up and
#: computing independently — generous (a wedged leader is the
#: watchdog's business, not the follower's), but bounded so a leaked
#: leader cannot wedge every future identical request
DEDUP_WAIT_S = 600.0


class Executor:
    """Runs Steps under one (policy, quarantine, checkpoint, cache)
    composition. All collaborators optional: a bare ``Executor()``
    just calls the thunk — entry points construct one unconditionally
    and the resilience features engage exactly when their objects are
    wired, which is what makes the lowering transparent."""

    def __init__(self, policy: RetryPolicy | None = None,
                 quarantine=None, checkpoint=None, cache=None,
                 inflight: InflightSteps | None = None):
        self.policy = policy
        self.quarantine = quarantine
        self.checkpoint = checkpoint
        self.cache = cache
        # dedup domain: the process-wide table unless a test injects
        # its own — steps only participate when they set dedup=True
        self.inflight = inflight if inflight is not None else INFLIGHT

    # ---- the composition ----

    def run_step(self, step: Step) -> StepOutcome:
        q = self.quarantine
        if q is not None and step.quarantine_key is not None \
                and step.quarantine_key in q:
            return StepOutcome(
                step.key, quarantined=True,
                value=step.fallback() if step.fallback else None)

        ck = self.checkpoint
        ck_keys = step.ck_keys() if ck is not None else []
        if ck_keys and step.resumable \
                and all(ck.has(k) for k in ck_keys):
            vals = [ck.get(k) for k in ck_keys]
            value = step.restore(vals) if step.restore is not None \
                else vals[0] if step.checkpoint_key is not None \
                else vals
            return StepOutcome(step.key, value=value, resumed=True)

        reg = get_registry()
        if self.cache is not None and step.cacheable:
            try:
                hit = self.cache.get(step.key)
            except Exception:  # noqa: BLE001 — cache must not fail steps
                reg.counter("result_cache.io_errors_total").inc()
                hit = None
            if hit is not None:
                return StepOutcome(step.key, value=hit, from_cache=True)

        def attempt():
            if step.site:
                faults.maybe_fail(step.site, step.key)
            with self._span(step):
                return step.fn()

        def compute() -> StepOutcome:
            policy = step.policy if step.policy is not None \
                else self.policy
            if policy is None or not step.retry:
                # resilience layer off (or a no-retry boundary step):
                # run raw — errors propagate to the caller, exactly
                # the pre-plan behavior of the unguarded paths
                return StepOutcome(step.key, value=attempt())
            try:
                value, attempts = policy.call(step.key, attempt)
            except RetriesExhausted as rx:
                if q is not None and step.quarantine_key is not None:
                    q.add(step.quarantine_key, step.quarantine_name,
                          step.quarantine_source, rx.cause,
                          rx.attempts, rx.classification)
                    return StepOutcome(
                        step.key, quarantined=True,
                        attempts=rx.attempts,
                        classification=rx.classification,
                        value=step.fallback() if step.fallback
                        else None)
                return StepOutcome(step.key, error=rx.cause,
                                   retries_exhausted=rx,
                                   attempts=rx.attempts,
                                   classification=rx.classification)
            return StepOutcome(step.key, value=value,
                               attempts=attempts)

        if step.dedup:
            outcome = self._run_deduped(step, compute, reg)
        else:
            outcome = compute()

        if outcome.error is None and not outcome.quarantined \
                and not outcome.deduped:
            # persistence is the leader's job: a follower's value is
            # already covered by the execution it joined
            if self.cache is not None and step.cacheable:
                try:
                    self.cache.put(step.key, outcome.value)
                except Exception:  # noqa: BLE001 — cache must not fail steps
                    reg.counter("result_cache.io_errors_total").inc()
            if ck_keys:
                items = step.commit(outcome.value) \
                    if step.commit is not None \
                    else [(ck_keys[0], outcome.value)]
                ck.put_many(items)
        return outcome

    def _run_deduped(self, step: Step, compute, reg) -> StepOutcome:
        """Leader-or-follower execution through the in-flight table.

        Exceptions escaping ``compute()`` (the no-policy raw path)
        still settle the entry — a follower never waits on a leader
        that already died."""
        entry, leader = self.inflight.join(step.key)
        if leader:
            outcome = None
            try:
                outcome = compute()
            finally:
                self.inflight.settle(step.key, entry, outcome)
            return outcome
        reg.counter("plan.steps_deduped_total").inc()
        shared = entry.outcome if entry.event.wait(DEDUP_WAIT_S) \
            else None
        if shared is not None and shared.error is None \
                and not shared.quarantined:
            return StepOutcome(step.key, value=shared.value,
                               deduped=True)
        # leader failed / was quarantined / timed out: compute
        # independently — failures are never shared
        reg.counter("plan.dedup_fallbacks_total").inc()
        return compute()

    def run(self, step: Step):
        """run_step, raising the failure (the exhausted attempt's
        original cause) instead of returning it — the call shape for
        entry points that want plain values."""
        return self.run_step(step).value_or_raise()

    def execute(self, plan: Plan):
        """Run a whole Plan, yielding one StepOutcome per Step in
        order (lazy: a generator, so streaming consumers overlap)."""
        for step in plan:
            yield self.run_step(step)

    # ---- span plumbing ----

    @staticmethod
    def _span(step: Step):
        import contextlib

        if step.span is None:
            return contextlib.nullcontext()
        from .. import obs

        if step.device:
            return obs.device_span(step.span, **step.attrs)
        return obs.span(step.span, **step.attrs)


def execute_task(key, thunk, cache=None,
                 policy: RetryPolicy | None = None):
    """Cache-lookup + retry for one shard task: the ONE helper behind
    ``run_sharded`` and ``iter_prefetched``.

    Returns a ``parallel.scheduler.ShardResult``; failures come back
    with ``.error`` set (shard isolation — the caller decides whether
    to raise). Cache I/O failures never fail the task: a computed
    value beats a broken cache (counted in
    ``result_cache.io_errors_total``).
    """
    from ..parallel.scheduler import ShardResult

    ex = Executor(policy=policy if policy is not None
                  else DEFAULT_POLICY, cache=cache)
    out = ex.run_step(Step(key=key, fn=thunk, site="shard",
                           cacheable=cache is not None))
    return ShardResult(key, out.value, error=out.error,
                       attempts=out.attempts,
                       from_cache=out.from_cache)


def run_device_step(name: str, fn, *, key=None, metrics=None,
                    policy: RetryPolicy | None = None,
                    retry: bool = True, dedup: bool = False,
                    count_passes: bool = False, signature=None,
                    **attrs):
    """One coalesced serve device dispatch as a Step.

    The serve executors' dispatch boundary: the shared ``compute``
    stage wall-clock PLUS a device-event span carrying backend/
    platform attributes, with the ``device`` fault site fired per
    attempt — so an injected (or real) transient device fault is
    retried under the policy instead of failing every request that
    shared the batch. The wrapped ``fn`` fetches its results to host
    numpy before returning, so the span already fences on the device
    work. Raises the original failure on exhaustion (the batcher's
    bisect-and-retry isolation takes it from there).

    ``dedup=True`` (with a content-identity ``key``) routes the step
    through the process-wide in-flight table: a concurrent dispatch of
    the same key joins the running pass instead of re-executing —
    cross-request step dedup (``plan.steps_deduped_total``).
    ``count_passes=True`` moves the executors'
    ``device_passes_total`` accounting here, where a deduped dispatch
    is visibly NOT a pass: only a genuinely executed step increments
    it — the honesty the fleet smoke's one-pass assertion rests on.
    """
    import contextlib

    from ..obs.compiles import TRACKER, family_of_dispatch

    def staged():
        if metrics is None:
            cm = contextlib.nullcontext()
        else:
            cm = metrics.timer.stage("compute")
        # the compile observation runs INSIDE the device span the
        # Executor opens around this fn, so a jit miss surfaced here
        # lands as a nested xla.compile.<family> span in flight trees.
        # ``signature`` (program geometry) makes the observation
        # warmstart-actionable: the warmup manifest records it and
        # serve --warmup can recreate the compile before admission.
        with cm, TRACKER.observe(family_of_dispatch(name),
                                 signature=signature, trigger=name):
            return fn()

    ex = Executor(policy=policy if policy is not None
                  else DEFAULT_POLICY)
    out = ex.run_step(Step(key=key if key is not None else (name,),
                           fn=staged, site="device", retry=retry,
                           dedup=dedup, span=name, device=True,
                           attrs=attrs))
    if count_passes and metrics is not None and not out.deduped \
            and out.error is None:
        metrics.inc("device_passes_total")
    return out.value_or_raise()
