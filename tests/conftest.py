"""Test configuration: run JAX on a virtual multi-device CPU mesh.

The suite runs on the CPU backend (the driver sets
``JAX_PLATFORMS=cpu``); all sharding tests use
``--xla_force_host_platform_device_count=N`` (default 8, override with
``BFTKV_TEST_DEVICES``) so multi-chip layouts compile and execute
without chips.  The chip itself is checked by ``chip_smoke.py``, not
by this suite.
"""

import os

import pytest

from bftkv_tpu.hostcpu import force_cpu

force_cpu(int(os.environ.get("BFTKV_TEST_DEVICES", "8")))


@pytest.fixture(scope="session", autouse=True)
def _lockwatch_gate():
    """The lockwatch pytest gate (DESIGN.md §16): with
    ``BFTKV_LOCKWATCH=1`` the whole tier runs under the runtime lock
    sanitizer, and any lock-order cycle or blocking-call-under-lock
    recorded across the session fails it here.  Disarmed (the default)
    this fixture is inert — ``named_lock`` returned plain stdlib locks
    and nothing was recorded."""
    yield
    from bftkv_tpu.devtools import lockwatch

    if lockwatch.enabled():
        msg = lockwatch.fail_message()
        assert msg is None, msg
