"""Tests run on a virtual 8-device CPU mesh.

Real multi-chip hardware is not available to the test suite; sharding
correctness is validated the JAX-idiomatic way — 8 virtual CPU devices —
and the chip is driven through chip_smoke.py.

The platform and the virtual device count are read when jax starts its
backend, so they are set here, before anything imports jax.  If jax was
already loaded with another environment (a plugin imported it first),
pytest re-execs itself once with the right one.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from k8s1m_tpu.envboot import cleaned_cpu_env  # noqa: E402

_WANT = cleaned_cpu_env(os.environ, 8)
_DIFFERS = any(os.environ.get(k) != _WANT[k] for k in ("JAX_PLATFORMS", "XLA_FLAGS"))
if _DIFFERS and "jax" not in sys.modules:
    os.environ.update(_WANT)
    _DIFFERS = False


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-minute drills (the 1M megarow run) — excluded "
        "from tier-1 via -m 'not slow'",
    )
    if not _DIFFERS or os.environ.get("K8S1M_TEST_REEXEC") == "1":
        return
    # Restore the real stdout/stderr before exec'ing, or the child's
    # output lands in this process's capture tempfiles and vanishes.
    capman = config.pluginmanager.getplugin("capturemanager")
    if capman is not None:
        capman.stop_global_capturing()
    env = dict(_WANT)
    env["K8S1M_TEST_REEXEC"] = "1"
    os.execve(sys.executable, [sys.executable, "-m", "pytest", *sys.argv[1:]], env)


import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)
