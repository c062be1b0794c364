"""Test harness: force an 8-device virtual CPU mesh.

Mirrors the reference's PseudoCluster strategy (fe test
pseudocluster/PseudoCluster.java:1 — multi-"node" cluster in one JVM): we fake
a multi-chip TPU slice with 8 host CPU devices so sharding/exchange logic is
exercised without hardware. Tests run on CPU devices only — the chip is
driven by chip_smoke.py and the benchmark, never by pytest — so the platform
is pinned here before any backend initializes, whatever JAX_PLATFORMS says.
What they run is the chip's program: no module of the package asks which
backend it is on (tests/test_one_program.py), so a statement traces here to
the formulations it traces to on a TPU.
"""

import os

# Lock-witness (starrocks_tpu/lockdep.py): run every factory-created lock
# through DebugLock for the whole tier-1 + chaos run, recording the global
# lock-ORDER graph; the session-teardown fixture below fails the run on a
# cycle. Must be set before the FIRST starrocks_tpu import — module-level
# singletons (metrics registry, failpoint registry, query registry) create
# their locks at import time. SR_TPU_LOCK_WITNESS=0 opts out.
os.environ.setdefault("SR_TPU_LOCK_WITNESS", "1")

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# The suite is compile-dominated (>9 min cold); warm runs reuse compiled
# programs through the persistent XLA cache that `import starrocks_tpu`
# places (JAX_COMPILATION_CACHE_DIR, else <checkout>/.xla_cache).
from starrocks_tpu.runtime.config import config as _sr_config  # noqa: E402

# Static verification in warn mode for the whole tier-1 suite: every
# optimized plan and every fresh compile runs the analysis/ passes; findings
# log + count in the profile but never fail a test (strict enforcement lives
# in tools/plan_lint.py and the golden fixtures of test_plan_verifier.py).
# SR_TPU_PLAN_VERIFY_LEVEL overrides (e.g. "off" to time the suite bare).
if "SR_TPU_PLAN_VERIFY_LEVEL" not in os.environ:
    _sr_config.set("plan_verify_level", "warn")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "chaos: failpoint/kill/timeout/mem-limit fault-injection scenarios "
        "(tests/test_chaos.py; also run as a dedicated stage in "
        "tools/run_tier1.sh)")


@pytest.fixture(scope="session", autouse=True)
def lock_witness_gate():
    """Teardown gate of the runtime lock-witness: after the whole session
    (647 tests' worth of real interleavings) the global lock-order graph
    must be acyclic — a cycle means two threads CAN deadlock, and the
    report carries both acquisition stacks. Tests that deliberately seed
    inversions use private lockdep.Witness instances, so this graph stays
    clean by construction."""
    from starrocks_tpu import lockdep

    yield
    cycles = lockdep.WITNESS.order_cycles()
    assert not cycles, (
        "runtime lock-witness found lock-order cycle(s):\n"
        + lockdep.WITNESS.render(cycles))


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs
