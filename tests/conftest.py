"""Test configuration: force an 8-device virtual CPU mesh so sharding
tests run without TPU hardware.  The platform is pinned in the config
before any backend initialises, so the tests run on the CPU whatever
JAX_PLATFORMS says (tests/test_chip_compile.py compiles FOR a described
TPU, still on the CPU backend)."""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    """Collection-time guard: no orphan .pyc may shadow a deleted
    module.  Committed-era __pycache__ artifacts of removed modules
    (e.g. a stale gateway.cpython-*.pyc) confuse greps, tooling and
    coverage; fail fast with the offending paths."""
    config.addinivalue_line(
        "markers",
        "faultinject: test arms loro_tpu.resilience.faultinject faults "
        "(the conftest guard asserts they are cleared afterwards)",
    )
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 run (-m 'not slow'); the full "
        "suite and explicit invocations still execute these",
    )
    import pathlib

    root = pathlib.Path(__file__).resolve().parent.parent
    orphans = []
    for pkg in (root / "loro_tpu", root / "tests"):
        for pyc in pkg.rglob("__pycache__/*.pyc"):
            mod = pyc.name.split(".", 1)[0]
            src_dir = pyc.parent.parent
            if not (src_dir / f"{mod}.py").exists():
                orphans.append(str(pyc.relative_to(root)))
    if orphans:
        import pytest

        raise pytest.UsageError(
            "orphan .pyc artifacts shadow deleted modules (delete them): "
            + ", ".join(sorted(orphans))
        )


import pytest


@pytest.fixture(autouse=True)
def _faultinject_leak_guard():
    """Tier-1 hygiene: a test that arms a fault and leaks it would make
    some unrelated test three files later fail mysteriously.  Assert
    the fault table is empty after EVERY test; clear it regardless so
    one leak produces exactly one failure (the leaking test's)."""
    from loro_tpu.resilience import faultinject

    yield
    leaked = faultinject.active()
    faultinject.clear()
    faultinject.set_sleep(None)
    assert not leaked, (
        f"faultinject faults leaked by this test: {leaked} — wrap arms in "
        "try/finally faultinject.clear() (see the 'faultinject' marker)"
    )
