"""Shared force-CPU setup for test processes (conftest + subprocess workers).

Tests run on XLA:CPU with a virtual multi-device mesh. Both are chosen by
the environment before the first backend touch: ``JAX_PLATFORMS=cpu`` and
``--xla_force_host_platform_device_count``. Subprocess workers (e.g.
tests/multihost_worker.py) can't rely on conftest running, so the logic
lives here once.
"""

import os
import re


def force_cpu(n_devices: int = 8) -> None:
    """Point THIS process at an n-device virtual CPU backend.

    Must run before the first jax backend touch. Idempotent.
    """
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+\s*",
        "",
        os.environ.get("XLA_FLAGS", ""),
    ).strip()
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    # jax reads JAX_PLATFORMS at import; cover a process that imported it
    # before we ran.
    jax.config.update("jax_platforms", "cpu")
    # The crypto kernels are compile-heavy; the persistent cache cuts
    # repeat runs from minutes to seconds.
    from pbft_tpu.utils.cache import configure_compile_cache

    configure_compile_cache()
