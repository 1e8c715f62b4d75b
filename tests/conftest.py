"""Test configuration: force an 8-device virtual CPU platform for mesh tests.

The reference's test strategy (SURVEY.md section 4) never spins up a cluster: it
exercises the InputFormat/RecordReader *interfaces* in-process.  We adopt the
same philosophy — all distributed logic is tested on a virtual 8-device CPU
mesh, and correctness of split planning is tested with every-byte-offset
property tests.
"""
import os

# Must be set before jax initializes its backends; child processes the
# tests spawn inherit the same platform.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# The entry points turn on JAX's persistent compile cache
# (utils/backend.py).  Tests leave it off: XLA:CPU executables are not
# what the cache is for, and its loader logs an error pair per hit.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu_devices():
    import jax

    return jax.devices("cpu")


@pytest.fixture(autouse=True)
def _pristine_resilience():
    """Every test starts with closed breakers, empty fault domains and
    no armed chaos points — adaptive state (an OPEN native-plane breaker
    from a corruption test, say) must never leak into the next test's
    plane selection."""
    from hadoop_bam_tpu import resilience

    resilience.reset()
    resilience.chaos.clear_fault_points()
    yield
    resilience.reset()
    resilience.chaos.clear_fault_points()
