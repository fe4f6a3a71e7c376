"""Process bootstrap for entry points (stdlib only at import — safe to
import anywhere).

jax locks the device count at first initialization, so multi-device CPU
runs (the distributed-pricing tests and benchmarks) must append
``--xla_force_host_platform_device_count`` to XLA_FLAGS BEFORE anything
imports jax.  Shared by tests/conftest.py and benchmarks/run.py so the
two always agree on the virtual mesh size.

``use_compile_cache`` points jax's persistent compilation cache at one
fixed directory; entry points (``chip_smoke.py``, ``examples/``,
``benchmarks/run.py``) call it, importing the library never does.
"""
from __future__ import annotations

import os

DEFAULT_HOST_DEVICES = 4

# <checkout>/.jax_cache: this file lives at <checkout>/src/repro/
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def use_compile_cache() -> None:
    """Persist compiled executables across processes.  When
    ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it and nothing is
    set here; otherwise the cache lives in ``.jax_cache/`` at the root
    of the checkout."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)


def ensure_host_devices(count: int = DEFAULT_HOST_DEVICES) -> None:
    """Idempotent: no-op when XLA_FLAGS already pins a device count
    (e.g. on a real TPU host or an explicit override)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={count}"
        ).strip()
