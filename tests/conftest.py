"""Test harness: force a virtual 8-device CPU mesh before JAX initializes.

Mirrors the reference's in-JVM multi-node test model (InternalTestCluster,
/root/reference/src/test/java/org/elasticsearch/test/InternalTestCluster.java:135):
many "nodes"/devices inside one process, no real cluster needed.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

# Arm the chaos leak detectors for the WHOLE suite: every Engine.close()
# under pytest asserts searcher refcounts drained, the per-site breaker
# ledger balanced, and no fielddata entries outliving the engine — a leak
# anywhere fails the leaking test by name instead of silently inflating
# the parent breaker for the tests behind it.
from elasticsearch_tpu.testing.chaos import detectors as _chaos_detectors  # noqa: E402

_chaos_detectors.arm()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "chaos: seeded randomized disruption rounds "
        "(CHAOS_SEED / CHAOS_ROUNDS env knobs)")
    config.addinivalue_line("markers", "slow: excluded from the tier-1 run")


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture(scope="session", autouse=True)
def _roomy_disk():
    """Tier-1 must not depend on how full the host's disk is: the cluster
    tests allocate replicas through the disk-watermark decider, which
    refuses every node once the real filesystem reports >= 85% used
    (total - free, the reference's formula) — as a quota'd sandbox volume
    does with 20 GB free. Tests of the watermarks inject their own usages."""
    import collections
    import shutil
    usage = collections.namedtuple("usage", "total used free")
    mp = pytest.MonkeyPatch()
    mp.setattr(shutil, "disk_usage",
               lambda path: usage(100 << 30, 40 << 30, 60 << 30))
    yield
    mp.undo()
