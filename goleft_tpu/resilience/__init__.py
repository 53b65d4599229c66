"""goleft_tpu.resilience — preemption-tolerant cohort runs.

The robustness layer the ROADMAP's production north-star needs: multi-
hour, thousands-of-input cohort jobs die to preemption, one corrupt
BAM, or a flaky filesystem, and before this subsystem the only
primitives were a blind retry-once loop (duplicated in two scheduler
paths) and the depth-only ResultCache.

  - :mod:`~goleft_tpu.resilience.checkpoint` — atomic sharded
    checkpoint store + fsync'd append-only journal
    (``--checkpoint-dir`` / ``--resume`` on cohortdepth and indexcov;
    resumed output is byte-identical to a cold run)
  - :mod:`~goleft_tpu.resilience.policy` — the unified
    :class:`RetryPolicy` (exponential backoff, deterministic jitter,
    transient-vs-permanent classification, per-task deadline) plus
    :class:`Quarantine` (graceful degradation: the cohort completes
    without a permanently-failing sample)
  - :mod:`~goleft_tpu.resilience.faults` — deterministic seeded fault
    injection (``GOLEFT_TPU_FAULTS`` / global ``--inject-faults``)
    hooked into BGZF decode, shard execution, cache I/O and the serve
    executors' device dispatch
  - :mod:`~goleft_tpu.resilience.smoke` — the ``make chaos-smoke``
    body: SIGKILL a cohort run mid-flight, resume it, assert
    byte-identity (+ a quarantine check)

Import is jax-free and cheap; the run-manifest "resilience" section is
registered here so any command that engages the subsystem reports its
quarantine/checkpoint evidence in ``--metrics-out``.
"""

from __future__ import annotations

import threading

from .breaker import CircuitBreaker  # noqa: F401
from .checkpoint import CheckpointCorrupt, CheckpointStore  # noqa: F401
from .faults import (  # noqa: F401
    InjectedFault, InjectedPermanentFault, maybe_fail, parse_faults,
)
from .policy import (  # noqa: F401
    DEFAULT_POLICY, Quarantine, RetriesExhausted, RetryPolicy,
)

__all__ = [
    "CheckpointCorrupt", "CheckpointStore", "CircuitBreaker",
    "DEFAULT_POLICY",
    "InjectedFault", "InjectedPermanentFault", "Quarantine",
    "RetriesExhausted", "RetryPolicy", "execute_task", "maybe_fail",
    "parse_faults", "set_run_state",
]

def __getattr__(name):
    # execute_task moved to the plan layer (PR 7); lazy alias so the
    # historical `from goleft_tpu.resilience import execute_task`
    # keeps working without an eager resilience → plan import
    if name == "execute_task":
        from ..plan.executor import execute_task as impl

        return impl
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")


_STATE_LOCK = threading.Lock()
_RUN_STATE: dict = {}


def set_run_state(quarantine: Quarantine | None = None,
                  checkpoint: CheckpointStore | None = None) -> None:
    """Record the live quarantine/checkpoint objects so the run
    manifest's ``resilience`` section reflects this run (the CLI
    writes the manifest after the command returns)."""
    with _STATE_LOCK:
        _RUN_STATE["quarantine"] = quarantine
        _RUN_STATE["checkpoint"] = checkpoint


def _manifest_section() -> dict | None:
    """The ``resilience`` block for ``--metrics-out`` manifests; None
    (section omitted) when the subsystem was not engaged."""
    with _STATE_LOCK:
        q = _RUN_STATE.get("quarantine")
        ck = _RUN_STATE.get("checkpoint")
    if q is None and ck is None:
        return None
    out: dict = {}
    if q is not None:
        out.update(q.summary())
    if ck is not None:
        out["checkpoint"] = {
            "dir": ck.dir,
            "resume": ck.resume,
            "completed_shards": ck.completed_count,
        }
    return out


def _register_manifest_section() -> None:
    from ..obs import manifest

    manifest.register_section("resilience", _manifest_section)


_register_manifest_section()
