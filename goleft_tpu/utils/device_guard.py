"""Backend policy: which platform this process computes on, decided
once, in this process, before any backend use.

- The CPU is used only when it was asked for: ``GOLEFT_TPU_CPU=1`` or
  ``JAX_PLATFORMS=cpu`` in the environment.
- Otherwise the process takes the accelerator with a plain
  ``jax.devices()`` and exits non-zero when JAX answers with the CPU.
  A chip belongs to one process at a time, so nothing is probed in a
  child and nothing is remembered between runs: a run that cannot have
  the chip fails, it never computes somewhere else in silence.
- The persistent compile cache lives where ``JAX_COMPILATION_CACHE_DIR``
  says; without it, at the fixed ``<checkout>/.jax_cache``, so that
  every run of one checkout finds what the last one compiled.

``cli.main`` (device commands) and ``__graft_entry__.py`` both come
through :func:`take_backend`.
"""

from __future__ import annotations

import os

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

NO_ACCELERATOR = (
    "goleft-tpu: JAX found no accelerator (platform is cpu); set "
    "GOLEFT_TPU_CPU=1 or JAX_PLATFORMS=cpu to run on the CPU")


def _cpu_knob() -> bool:
    return os.environ.get("GOLEFT_TPU_CPU", "").strip() == "1"


def cpu_requested() -> bool:
    return _cpu_knob() or os.environ.get(
        "JAX_PLATFORMS", "").strip().lower() == "cpu"


def take_backend() -> list:
    """Place the compile cache, then take the backend: the CPU when it
    was asked for, else the accelerator — or exit. Returns
    ``jax.devices()``. Must run before anything else initializes a jax
    backend."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    if _cpu_knob():
        jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    if devs[0].platform == "cpu" and not cpu_requested():
        raise SystemExit(NO_ACCELERATOR)
    return devs
