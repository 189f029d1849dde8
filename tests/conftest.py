"""Test configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding logic is
exercised without TPU hardware (chip_smoke.py is what runs on the real
chip).  Environment must be set before jax is imported anywhere.
"""

import os
import sys

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Force an 8-device CPU platform for tests (before any backend
# initialisation); the XLA_FLAGS form above is what child processes
# spawned by tests inherit.
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import pytest

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

REFERENCE_ROOT = "/root/reference"


def reference_fixture(relpath):
    """Absolute path of a binary fixture in the read-only reference tree,
    or None when the reference is not mounted (tests should skip)."""
    p = os.path.join(REFERENCE_ROOT, relpath)
    return p if os.path.exists(p) else None


def pytest_configure(config):
    """One build of native/*.so before xdist starts its workers.  On a
    fresh checkout each of six workers ran `make` into the same two
    files, and a worker whose own make had ended could load a library
    that another's compiler was still writing."""
    if not hasattr(config, "workerinput"):
        from seaweedfs_tpu.ops import native

        native.build()


@pytest.fixture
def eager_switching():
    """Threads switch every 10 us: a step taken twice or lost between
    the workers of a read stage, were the hand-out not atomic, shows as a
    wrong shard."""
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        yield
    finally:
        sys.setswitchinterval(interval)


# Custom markers are registered in pytest.ini (the shared config) —
# tests/test_markers_registered.py fails tier-1 if a test file uses a
# marker that is not listed there.


def pytest_collection_modifyitems(config, items):
    """@pytest.mark.multidevice needs the >=4-device mesh this conftest
    forces above (8 virtual CPU devices).  If the backend came up
    smaller anyway — an outer XLA_FLAGS pinning the count, or a jax
    build that ignores the flag — skip rather than shard a 1-device
    mesh and silently not exercise the sharded path."""
    if jax.device_count() < 4:
        skip = pytest.mark.skip(
            reason=f"multidevice needs >=4 devices, backend has "
                   f"{jax.device_count()}")
        for item in items:
            if "multidevice" in item.keywords:
                item.add_marker(skip)

    # @pytest.mark.multiproc forks real prefork gateway workers; on a
    # 1-core box the workers time-slice one CPU and the sharding/chaos
    # assertions measure the scheduler.  WEED_TEST_FORCE_MULTIPROC=1
    # overrides for boxes where affinity under-reports.
    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:
        cores = os.cpu_count() or 1
    if cores < 2 and os.environ.get("WEED_TEST_FORCE_MULTIPROC") != "1":
        skip_mp = pytest.mark.skip(
            reason=f"multiproc needs >=2 cores, have {cores} "
                   "(set WEED_TEST_FORCE_MULTIPROC=1 to force)")
        for item in items:
            if "multiproc" in item.keywords:
                item.add_marker(skip_mp)
