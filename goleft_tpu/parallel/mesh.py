"""Device-mesh construction for the cohort pipeline.

The reference's parallelism is process pools over genome shards and
goroutines over samples (SURVEY.md §2.5); the TPU-native mapping is a 2D
``jax.sharding.Mesh``:

  - ``data`` axis: samples (cohort data parallelism — the analog of the
    8-goroutine index readers, indexcov/indexcov.go:417-434)
  - ``seq`` axis: genome position (sequence parallelism — the analog of
    the 10Mb shard loop, depth/depth.go:150-153, but with on-device
    carry exchange instead of tmp-file merges)

Multi-host: call ``init_distributed()`` first (jax.distributed over DCN),
then the same mesh code spans all hosts' devices — collectives ride ICI
within a slice and DCN across slices.
"""

from __future__ import annotations

import os

import jax
import numpy as np
from jax.sharding import Mesh


def best_grid(n: int, prefer_seq: int | None = None) -> tuple[int, int]:
    """(data, seq) grid for n devices; seq gets the larger factor since
    genome length dwarfs cohort size."""
    if prefer_seq:
        if n % prefer_seq:
            raise ValueError(f"{prefer_seq} does not divide {n}")
        return n // prefer_seq, prefer_seq
    best = (1, n)
    for d in range(1, int(np.sqrt(n)) + 1):
        if n % d == 0:
            best = (d, n // d)
    return best


def make_mesh(n_devices: int | None = None,
              axis_names: tuple[str, str] = ("data", "seq"),
              prefer_seq: int | None = None) -> Mesh:
    """Topology-aware 2D mesh.

    ``mesh_utils.create_device_mesh`` orders devices so the trailing
    (``seq``) axis — which carries the cumsum-carry ppermute traffic of
    the sharded coverage kernel — maps to physically adjacent ICI
    neighbors on real TPU topologies, instead of the raw ``jax.devices()``
    enumeration order. A plain reshape is used on the CPU, and when the
    requested count is a strict subset of the process's devices (subset
    meshes have no topology guarantee anyway).
    """
    devs = jax.devices()
    n = n_devices or len(devs)
    if n > len(devs):
        raise ValueError(f"requested {n} devices, have {len(devs)}")
    d, s = best_grid(n, prefer_seq)
    if n == len(devs) and devs[0].platform != "cpu":
        # virtual CPU devices have no topology to order by; on an
        # accelerator a failure here is raised, never turned into
        # enumeration order (which would lose ICI adjacency in silence)
        from jax.experimental import mesh_utils

        return Mesh(mesh_utils.create_device_mesh((d, s), devices=devs),
                    axis_names)
    grid = np.asarray(devs[:n]).reshape(d, s)
    return Mesh(grid, axis_names)


_distributed_up = False


def init_distributed() -> None:
    """Multi-host bring-up over DCN (no-op single-host, idempotent).

    Honors the standard JAX coordinator env vars; the reference has no
    distributed backend at all (SURVEY.md §2.5) — this is the rebuild's
    equivalent of an NCCL/MPI world init. Must run before anything
    initializes the XLA backend — the CLI dispatcher calls it ahead of
    ``take_backend``.
    """
    global _distributed_up

    addr = os.environ.get("GOLEFT_TPU_COORDINATOR")
    if not addr or _distributed_up:
        return
    jax.distributed.initialize(
        coordinator_address=addr,
        num_processes=int(os.environ.get("GOLEFT_TPU_NUM_PROCESSES", "1")),
        process_id=int(os.environ.get("GOLEFT_TPU_PROCESS_ID", "0")),
    )
    _distributed_up = True
