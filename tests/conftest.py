"""Shared fixtures. NOTE: no XLA_FLAGS here — tests see the real single CPU
device; multi-device tests (collectives, sharding) spawn subprocesses that
set --xla_force_host_platform_device_count themselves."""

import jax
import numpy as np
import pytest


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)


@pytest.fixture(autouse=True)
def _seed_numpy():
    np.random.seed(0)


@pytest.fixture
def own_runtime():
    """A process-default TransferRuntime for this test alone. Admission
    sheds a backlogged tenant when the class's recent deadline-miss rate
    is high; on a busy host, earlier tests' token traffic in the shared
    runtime's 5 s window would otherwise shed this test's requests."""
    from repro.core.runtime import TransferRuntime, set_runtime
    rt = TransferRuntime()
    prev = set_runtime(rt)
    yield rt
    set_runtime(prev)
    rt.close()


def run_in_subprocess(code: str, n_devices: int = 8) -> str:
    """Run python code with N fake host devices; returns stdout."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))),
                         timeout=600)
    assert out.returncode == 0, f"stdout:{out.stdout}\nstderr:{out.stderr}"
    return out.stdout
