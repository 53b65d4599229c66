"""Test environment: force JAX onto a virtual 8-device CPU platform.

Real-TPU execution is exercised by benchmark/run.py and chip_smoke.py; tests
must be hermetic and validate sharding semantics on virtual devices
(one real chip is all we have, and CI may have none).

This must run before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# float64 on the CPU test platform so EM kernels can be validated exactly
# against float64 oracles (device kernels that want f32 request it
# explicitly, so this only upgrades default-precision math).
import jax

jax.config.update("jax_enable_x64", True)
# In-process tests call cli.main, whose take_backend() points jax at the
# checkout's persistent compile cache; keep the test process itself off
# it so a run never depends on what an earlier run left there.
jax.config.update("jax_enable_compilation_cache", False)
