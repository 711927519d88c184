"""Test harness: 8 virtual CPU devices simulate a multi-chip TPU mesh.

Mirrors the reference's DistributedTest pattern (tests/unit/common.py) of
simulating multi-node on localhost — here via XLA's host-platform device-count
flag instead of forked NCCL processes. Set DSTPU_TEST_PLATFORM=tpu to run the
suite against real chips.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax

if os.environ.get("DSTPU_TEST_PLATFORM", "cpu") == "cpu":
    # the suite runs on the virtual CPU mesh whatever the outer environment
    # pins; config.update still works after jax has been imported
    jax.config.update("jax_platforms", "cpu")

import pytest

# the chaos env knob must never leak into the suite from the outer
# environment — a stray DSTPU_CHAOS would fail arbitrary checkpoint tests
os.environ.pop("DSTPU_CHAOS", None)


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture(autouse=True)
def _chaos_isolation():
    """Deterministic fault injection: every test starts and ends with no
    armed failpoints, and DSTPU_CHAOS set by a test (for its subprocesses)
    is scrubbed afterwards."""
    from deepspeed_tpu.testing import chaos
    chaos.reset_for_tests()
    yield
    chaos.reset_for_tests()
    os.environ.pop("DSTPU_CHAOS", None)
