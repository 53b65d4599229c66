"""Warm-start consumer: pre-compile a warmup manifest's signatures.

The other half of the compile observatory's elastic warm-start story
(obs/compiles.py produces the ranked manifest, ``goleft-tpu warmup``
exports/merges it): ``serve --warmup PATH`` replays the manifest's
top-K signatures through the real program families BEFORE the daemon
binds its port — so a freshly restarted worker rejoins the fleet
already holding the compiled programs its predecessor spent seconds
building, and the first production request after a preemption pays
a cache hit, not a compile storm.

Each family registers a *precompiler* that reconstructs the compile
geometry from the recorded signature (the same dicts the executors
attach at their dispatch boundaries) and drives the family's actual
jit entry on zero-filled arrays of that geometry — the compile cache
keys on shapes/dtypes/statics only, so zeros produce exactly the
program the recorded traffic would. Every precompile runs under
``TRACKER.observe`` with the parsed signature, so ``/debug/compiles``
on the fresh worker shows the signature compiled at startup (what the
profile-smoke prewarm leg asserts) and re-exports keep ranking it.

Entries that cannot be replayed are skipped, never fatal: unknown
families, geometry-less signatures (old manifests recorded ``""``),
or seed-stage swalign entries (their tables are reference-bound and
only exist once a request names the reference).
"""

from __future__ import annotations

import json
import time

import numpy as np

from ..obs import get_logger, get_registry
from ..obs.compiles import TRACKER, load_warmup_manifest

log = get_logger("serve.warmstart")

#: default number of top-ranked manifest entries to pre-compile
DEFAULT_TOP_K = 8


def _warm_depth(sig: dict) -> None:
    from ..commands.depth import _batched_cls_packed

    b = int(sig["b"])
    bucket = int(sig["bucket"])
    length = int(sig["length"])
    window = int(sig["window"])
    z = np.zeros((b, bucket), np.int32)
    i32 = np.int32
    import jax

    jax.block_until_ready(_batched_cls_packed()(
        z, z, z.astype(bool), i32(0), i32(0), i32(min(256, length)),
        i32(2500), i32(4), i32(0), length=length, window=window))


def _warm_pairhmm(sig: dict) -> None:
    from ..ops import pairhmm

    b = int(sig["b"])
    r_pad = int(sig["r_pad"])
    h_pad = int(sig["h_pad"])
    rescale = bool(sig["rescale"])
    dtype = np.dtype(sig.get("dtype", "float32"))
    reads = [np.zeros(r_pad, np.uint8)] * b
    errs = [np.full(r_pad, 0.001, np.float64)] * b
    haps = [np.zeros(h_pad, np.uint8)] * b
    packed = pairhmm._pack_bucket(list(range(b)), reads, errs, haps,
                                  r_pad, h_pad, dtype)
    trans = pairhmm.transition_probs().astype(dtype)
    import jax

    jax.block_until_ready(pairhmm._forward_bucket(
        *packed, trans, rescale=rescale))


def _warm_swalign(sig: dict) -> None:
    if sig.get("stage") != "extend":
        # seed-stage programs close over the reference's device
        # tables — nothing to compile until a request names one
        raise _Skip("seed-stage signature is reference-bound")
    from ..ops import swalign

    b = int(sig["b"])
    r_pad = int(sig["r_pad"])
    w_pad = int(sig["w_pad"])
    reads_p = np.full((b, r_pad + 1), swalign.N_CODE, np.uint8)
    rlens = np.ones(b, np.int32)
    wins_p = np.full((b, w_pad), swalign.N_CODE, np.uint8)
    wlens = np.ones(b, np.int32)
    sc = np.asarray(swalign.DEFAULT_SCORES.astuple(), np.int32)
    import jax

    jax.block_until_ready(swalign.sw_bucket(reads_p, rlens, wins_p,
                                            wlens, sc))


class _Skip(Exception):
    """Entry is legitimately not replayable (not a failure)."""


_PRECOMPILERS = {
    "depth": _warm_depth,
    "pairhmm": _warm_pairhmm,
    "swalign": _warm_swalign,
}


def _cache_size_fn(family: str):
    if family == "pairhmm":
        from ..ops import pairhmm

        return lambda: (pairhmm._FORWARD_JIT._cache_size()
                        if pairhmm._FORWARD_JIT is not None else 0)
    if family == "swalign":
        from ..ops.swalign import _sw_jit_cache_size

        return _sw_jit_cache_size
    return lambda: 0


def warm_start(path: str, top_k: int = DEFAULT_TOP_K) -> dict:
    """Pre-compile the manifest's top-K signatures. Returns counts
    ``{"warmed", "skipped", "failed", "seconds"}``; raises only on an
    unreadable/invalid manifest (a bad ``--warmup`` argument is an
    operator error, a stale entry is not)."""
    t0 = time.monotonic()
    manifest = load_warmup_manifest(path)
    reg = get_registry()
    warmed = skipped = failed = 0
    for entry in manifest["signatures"][:top_k]:
        family = entry["family"]
        pre = _PRECOMPILERS.get(family)
        sig_str = entry.get("signature") or ""
        if pre is None or not sig_str:
            skipped += 1
            reg.counter("serve.warmstart_skipped_total").inc()
            continue
        try:
            sig = json.loads(sig_str)
            with TRACKER.observe(family, signature=sig,
                                 cache_size_fn=_cache_size_fn(family),
                                 trigger="warmstart"):
                pre(sig)
            warmed += 1
            reg.counter("serve.warmstart_compiles_total").inc()
        except _Skip as e:
            skipped += 1
            reg.counter("serve.warmstart_skipped_total").inc()
            log.info("warmstart: skipped %s entry: %s", family, e)
        except Exception as e:  # noqa: BLE001 — stale entries must
            # never block admission; the worker just cold-misses them
            failed += 1
            reg.counter("serve.warmstart_failed_total").inc()
            log.warning("warmstart: failed to pre-compile %s %s: %r",
                        family, sig_str, e)
    seconds = time.monotonic() - t0
    log.info("warmstart: %d pre-compiled, %d skipped, %d failed in "
             "%.2fs (%s)", warmed, skipped, failed, seconds, path)
    return {"warmed": warmed, "skipped": skipped, "failed": failed,
            "seconds": seconds}
