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

# PR 26: tests/benchmark_cells/test_span_metrics.py pins, among what it
# checks of a tiny run, `intake_slow_lane_pct.fill == 100.0`: every pod of
# make_pods through json.loads, which is the behaviour that PR removed
# (the reading is 0 now).  Only a `benchmark` PR may edit a file under
# tests/benchmark_cells, so until one corrects that line the test is
# expected to fail, strictly (a pass fails it, and this entry goes), and
# tests/test_intake_shapes.py::test_span_report_reads_no_slow_lane_in_a_tiny_run
# checks the same run with the reading the program now gives.
_EXPECTED_TO_FAIL = {
    "test_span_metrics.py::test_span_report_reads_the_counters_of_a_tiny_run":
        "pins intake_slow_lane_pct.fill at 100, the pre-PR-26 intake lane; "
        "a benchmark PR corrects the assertion (PERF.md section 7)",
}


def pytest_collection_modifyitems(items):
    for item in items:
        for test, why in _EXPECTED_TO_FAIL.items():
            if item.nodeid.split("[")[0].endswith(test):
                item.add_marker(pytest.mark.xfail(reason=why, strict=True))


@pytest.fixture
def rng():
    return np.random.default_rng(0)
