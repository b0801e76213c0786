"""CI-style guard for the driver entry points (__graft_entry__.py).

The driver compile-checks entry() single-chip and runs
dryrun_multichip(N) under xla_force_host_platform_device_count=N.
dryrun_multichip must force the CPU platform before any backend
initialises (touching the ambient backend first would take the chip,
or fail where there is none); this test reproduces the driver
invocation in a fresh subprocess under a hard timeout so a regression
fails fast instead of hanging.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, n: int, timeout: float = 300.0, stage_flags: bool = True):
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    # strip any prior forcing so we exercise the driver's own setting
    flags = " ".join(
        f for f in flags.split() if "xla_force_host_platform_device_count" not in f
    )
    if stage_flags:
        flags = (flags + f" --xla_force_host_platform_device_count={n}").strip()
    env["XLA_FLAGS"] = flags
    # No subprocess.run(timeout=...): that SIGKILLs on expiry.  The
    # child is pinned to the CPU, but a regression that let it reach a
    # chip would make a hard kill mid-launch costly: SIGTERM with a
    # grace period.
    proc = subprocess.Popen(
        [sys.executable, "-c", code],
        cwd=REPO,
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
    )
    try:
        out, err = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.terminate()
        try:
            out, err = proc.communicate(timeout=30)
        except subprocess.TimeoutExpired:
            out, err = "", "hung: SIGTERM ignored; leaving process to exit on its own"
        pytest.fail(f"timed out after {timeout}s: {err[-2000:]}")
    return subprocess.CompletedProcess(proc.args, proc.returncode, out, err)


@pytest.mark.parametrize("n", [8])
def test_dryrun_multichip_subprocess(n):
    r = _run(
        f"import __graft_entry__ as g; g.dryrun_multichip({n}); print('MULTICHIP_OK')",
        n,
    )
    assert r.returncode == 0, r.stderr[-4000:]
    assert "MULTICHIP_OK" in r.stdout


def test_dryrun_multichip_self_stages_device_count():
    """dryrun_multichip must work even when the caller did NOT set
    xla_force_host_platform_device_count — it stages the flag itself
    before backend init."""
    r = _run(
        "import __graft_entry__ as g; g.dryrun_multichip(4); print('MULTICHIP_OK')",
        4,
        stage_flags=False,
    )
    assert r.returncode == 0, r.stderr[-4000:]
    assert "MULTICHIP_OK" in r.stdout


def test_entry_compiles_subprocess():
    code = (
        "import jax; jax.config.update('jax_platforms','cpu');\n"
        "import __graft_entry__ as g\n"
        "fn, args = g.entry()\n"
        "out = jax.jit(fn)(*args)\n"
        "jax.block_until_ready(out)\n"
        "print('ENTRY_OK')\n"
    )
    r = _run(code, 1)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "ENTRY_OK" in r.stdout
