"""Put JAX on a virtual multi-device CPU mesh.

Test and dry-run lanes need N virtual CPU devices
(``--xla_force_host_platform_device_count``) whatever accelerator the
host has.  ``JAX_PLATFORMS`` decides the backend, so :func:`force_cpu`
sets it, requests the device count in ``XLA_FLAGS`` (honored as long
as the CPU client has not been instantiated yet) and updates
``jax.config`` for a jax that was imported earlier in this process.

Call it before the first ``jax.devices()`` / trace.  Idempotent.
"""

from __future__ import annotations

import os

__all__ = ["force_cpu"]


def force_cpu(n_devices: int = 8) -> None:
    flags = [
        f
        for f in os.environ.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")
