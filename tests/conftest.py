"""Test configuration.

Tests run on the CPU backend with 8 virtual devices so multi-chip sharding
(mesh/shard_map paths) is exercised without TPU hardware. These env vars
must be set before jax is first imported anywhere in the test process.
"""

import contextlib
import os

import pytest

# Force CPU even when the ambient environment selects a TPU platform:
# unit tests use tiny shapes where CPU is faster, and the virtual
# 8-device mesh needs the host platform. Set HSTREAM_TEST_PLATFORM to
# override (e.g. to run the suite on real TPU).
os.environ["JAX_PLATFORMS"] = os.environ.get("HSTREAM_TEST_PLATFORM", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()



@pytest.fixture
def retrace_guard():
    """Context factory asserting ZERO XLA compiles inside the block —
    the runtime complement of the static retrace pass."""
    from hstream_tpu.common.tracing import RetraceGuard

    @contextlib.contextmanager
    def guard_zero():
        with RetraceGuard() as g:
            yield g
        assert g.count == 0, \
            f"steady state compiled {g.count} new XLA executable(s)"

    return guard_zero
